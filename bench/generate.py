"""The one traffic generator: reads a mix's parameters
(``bench/traffic/<mix>.json``) and draws requests from ``--seed``.

Every seed gets the same multiset of sizes and arrival gaps, in another
order, so seeds change the order of the work and not its amount:

* lengths are stratified quantiles of a clipped lognormal, one set per
  block of ``block`` requests, shuffled within the block;
* open-loop arrival gaps are stratified quantiles of an exponential at
  ``rate_per_s``, as many as fall in the window, shuffled; each request is
  stamped with its due time, from which its time to first token counts;
* tenants (shared system prompts) follow Zipf counts, shuffled.

Token ids are drawn per request from ``(seed, index)``, so a request's
content does not depend on how many came before it."""
from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist

import numpy as np

_NORMAL = NormalDist()


def seed_key(seed: int):
    """A JAX key from any whole ``--seed`` (wider than 32 bits too)."""
    import jax
    import jax.numpy as jnp

    words = np.random.SeedSequence(seed).generate_state(2, np.uint32)
    return jax.random.wrap_key_data(jnp.asarray(words))


def _strata(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lognormal_strata(spec: dict, n: int, *, length_biased: bool = False):
    """``n`` stratified draws of a lognormal with ``median`` and ``sigma``,
    rounded and clipped to ``[min, max]``. ``length_biased`` draws from the
    density weighted by length (the request a busy slot holds)."""
    mu = math.log(spec["median"]) + (spec["sigma"] ** 2 if length_biased else 0)
    z = np.array([_NORMAL.inv_cdf(q) for q in _strata(n)])
    x = np.rint(np.exp(mu + spec["sigma"] * z))
    return np.clip(x, spec["min"], spec["max"]).astype(np.int64)


def zipf_counts(n_items: int, n: int, s: float) -> np.ndarray:
    """Counts of ``n`` draws over ``n_items`` ranks with Zipf(s) weights,
    by largest remainder (sums to ``n``)."""
    w = 1.0 / np.arange(1, n_items + 1) ** s
    exact = n * w / w.sum()
    counts = np.floor(exact).astype(np.int64)
    rest = n - counts.sum()
    counts[np.argsort(-(exact - counts), kind="stable")[:rest]] += 1
    return counts


@dataclasses.dataclass
class Trace:
    """Request sizes in order of use; ``due`` (open loop) in seconds from
    the window's start; ``tenant`` -1 where no prefix is shared."""
    prompt: np.ndarray
    output: np.ndarray
    tenant: np.ndarray
    due: np.ndarray | None
    seed: int
    vocab: int

    def __len__(self) -> int:
        return len(self.prompt)

    def tokens(self, i: int, n: int, stream: int = 0) -> np.ndarray:
        rng = np.random.default_rng([self.seed, stream, i])
        return rng.integers(0, self.vocab, n, dtype=np.int64).astype(np.int32)

    def system_prompt(self, tenant: int, n: int) -> np.ndarray:
        return self.tokens(tenant, n, stream=1)


def _blocked(rng, draws: np.ndarray, n: int) -> np.ndarray:
    """Repeat one block of draws to length ``n``, shuffling each block."""
    out = []
    while len(out) * len(draws) < n:
        out.append(rng.permutation(draws))
    return np.concatenate(out)[:n]


def make_trace(spec: dict, seed: int, vocab: int, seconds: float) -> Trace:
    rng = np.random.default_rng(seed)
    if spec["loop"] == "open":
        n = max(1, round(spec["rate_per_s"] * seconds))
        block = n
        q = _strata(n)
        gaps = -np.log1p(-q) / spec["rate_per_s"]
        gaps *= seconds / gaps.sum()       # exactly n arrivals in the window
        due = np.cumsum(rng.permutation(gaps)) - gaps.min()
    else:
        n, block, due = spec["requests"], spec["block"], None
    prompt = _blocked(rng, lognormal_strata(spec["prompt"], block), n)
    output = _blocked(rng, lognormal_strata(spec["output"], block), n)
    cap = spec.get("max_context")
    pre = spec.get("shared_prefix")
    if pre:
        tenant = rng.permutation(np.repeat(
            np.arange(pre["tenants"]),
            zipf_counts(pre["tenants"], n, pre["zipf"]),
        ))
    else:
        tenant = np.full(n, -1)
    if cap:
        base = pre["length"] if pre else 0
        output = np.minimum(output, cap - base - prompt)
    return Trace(prompt, output, tenant, due, seed, vocab)


def steady_start(spec: dict, seed: int, n_slots: int):
    """Requests already part-way through when the window opens, one per
    client: (prompt length, history length, remaining output). Outputs are
    length-biased, progress is stratified over the slots."""
    rng = np.random.default_rng([seed, 2])
    prompt = rng.permutation(lognormal_strata(spec["prompt"], n_slots))
    output = rng.permutation(
        lognormal_strata(spec["output"], n_slots, length_biased=True)
    )
    if spec.get("max_context"):
        output = np.minimum(output, spec["max_context"] - prompt)
    done = np.floor(rng.permutation(_strata(n_slots)) * output).astype(np.int64)
    return [(int(p), int(d), int(o - d)) for p, o, d in zip(prompt, output, done)]
