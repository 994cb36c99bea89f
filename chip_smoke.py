#!/usr/bin/env python3
"""Smoke run of the serving engine and the federated trainer on a TPU.

    python3 chip_smoke.py              # one chip: serving, then training
    python3 chip_smoke.py --chips 4    # four chips: tensor-parallel serving
                                       # and the pod-axis federated step,
                                       # each against its one-chip twin

Phases (one chip):

* serve — stablelm-1.6b at its published widths (random weights from
  ``--seed``) through ``serve_continuous``, the function
  ``python -m repro.launch.serve --full --continuous`` runs, with the CLI
  defaults (paged pool, prefix cache) and the Pallas kernels. Sixteen
  requests over four slots: eight distinct prompts of 256-1024 tokens,
  then eight that share a 768-token system prompt, so later ones hit the
  prefix cache and take the suffix-prefill kernel. The same requests are
  served again on the jnp path, and the two are compared (see
  ``LOGIT_TOL``). The compiled decode and suffix-prefill programs must
  hold the kernel (``tpu_custom_call``), and both kernels must match their
  f32 oracles (see ``KERNEL_TOL``).
* train — xlstm-125m through ``run_training``: 3 clouds, 2 local steps
  between syncs, sequence 2048, per-cloud batch 2 (one chip's memory caps
  the batch); every loss must be finite.

Phases (``--chips 4``): tensor-parallel serving over a 4-way ``model``
mesh against the one-device engine, and the federated step with one
xlstm-125m cloud per chip (``pod`` mesh axis) against the vmapped
4-cloud trainer on one chip, step by step from the same state (see
``LOSS_RTOL`` and ``NORM_RTOL``).

Everything runs in this one process (a child could not reach the chip the
parent holds). Exits non-zero if any phase fails or no TPU is visible;
the last stdout line, printed only on success, is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import gc
import importlib.metadata
import json
import math
import sys
import time
import traceback
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

SERVE_ARCH = "stablelm-1.6b"
TRAIN_ARCH = "xlstm-125m"

# Kernel path vs jnp path, and 4-way tensor-parallel vs one device, at the
# logits: the same attention in bf16 with f32 accumulation, in a different
# order, and the jnp path rounds attention probabilities to bf16 before the
# PV matmul. The two read 3-4% of the row's largest |logit| apart at 24
# layers with random weights, so a logit may differ by 2^-4 of it. That
# catches a gross fault (a page table shifted by one page reads 7-72% at 2
# layers) but not a mask off by one key, which moves the logits no more
# than the rounding does: KERNEL_TOL covers those. A greedy token may
# differ only where the two candidates are tied within LOGIT_TOL under the
# jnp reference forward; every later token then follows a different prefix
# and is not compared.
LOGIT_TOL = 2.0**-4

# Each serving kernel against its f32 oracle on the same random inputs:
# both keep scores and probabilities in f32 and round only the output to
# bf16, so they differ by about one output rounding (2^-9). Per row and kv
# head the relative L2 gap may be 2^-6; one key dropped from the mask (the
# newest token, the last prefix token, or a query's own key) reads
# 0.05-0.14.
KERNEL_TOL = 2.0**-6

# Pod-axis federated step vs the vmapped trainer. The pod step is run from
# every state the vmapped trainer passes through, on the same batch, so
# each step starts both sides from the same parameters and no difference
# compounds. The loss is then the same f32 mean over the same bf16
# forward, summed in another order: relative 2^-14 (the chip read
# 3.5e-7 to 1.5e-5 at four such steps).
LOSS_RTOL = 2.0**-14
# What a step changes, per cloud and in the global model at a sync, as
# the norm over all parameters of Δ = after − before: |Δpod| / |Δref| may
# be 1 ± 2^-3. Element by element the two moves differ by about as much as
# they are: xlstm-125m's bf16 gradient at initialisation is mostly rounding
# (it differs from the same gradient in f32 by 0.86 of its norm), so
# Adam's sign-like steps go opposite ways on a share of the weights, and
# that share flips with any change of summation order. Their sizes agree
# all the same. A pod step that skips its update or its sync moves by 0;
# one that syncs one cloud's delta instead of the all-reduced mean moves
# the global model by 0.26-0.37 of the vmapped trainer's sync.
NORM_RTOL = 2.0**-3


SLOTS = 4
GEN = 32                     # tokens generated per request
PROMPT_MIN, PROMPT_MAX = 256, 1024
SYSTEM_LEN = 768             # shared system prompt of the second burst
USER_MIN, USER_MAX = 32, 192
SEQ_LEN = 2048               # training sequence length
TRAIN_BATCH = 2              # per-cloud batch, one chip
POD_BATCH = 1                # per-cloud batch, 4-cloud comparison
STEPS = 4
LOCAL_STEPS = 2


def log(msg: str) -> None:
    print(msg, flush=True)


# ------------------------------------------------------------------ timing
class CompileClock:
    """Sums JAX's trace + lower + compile (or compile-cache load) time."""

    def __init__(self):
        import jax

        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_) -> None:
        if event.startswith("/jax/core/compile/"):
            self.seconds += duration


def device_memory(devices) -> list[dict]:
    out = []
    for d in devices:
        stats = d.memory_stats() or {}
        out.append({
            "device": d.id,
            "bytes_in_use": stats.get("bytes_in_use"),
            "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
        })
    return out


# ----------------------------------------------------------------- serving
def serve_requests(vocab: int, seed: int):
    """Eight distinct prompts, then eight sharing one system prompt."""
    import numpy as np

    from repro.launch.engine import Request

    rng = np.random.default_rng(seed)
    reqs = []
    for uid in range(8):
        n = int(rng.integers(PROMPT_MIN, PROMPT_MAX + 1))
        reqs.append(Request(uid, rng.integers(0, vocab, n), GEN))
    system = rng.integers(0, vocab, SYSTEM_LEN)
    for uid in range(8, 16):
        user = rng.integers(0, vocab, int(rng.integers(USER_MIN, USER_MAX + 1)))
        reqs.append(Request(uid, np.concatenate([system, user]), GEN))
    return reqs


def serve(seed: int, **engine_kw) -> dict:
    from repro.configs import get_config
    from repro.launch.engine import serve_continuous

    reqs = serve_requests(get_config(SERVE_ARCH).vocab_size, seed)
    res = serve_continuous(
        SERVE_ARCH, smoke=False, num_slots=SLOTS, requests=reqs,
        seed=seed, log_fn=log, **engine_kw,
    )
    gc.collect()   # the engine holds reference cycles: free its HBM now
    short = [i for i, g in enumerate(res["generated"]) if len(g) != GEN]
    if short:
        raise AssertionError(f"requests {short} did not get {GEN} tokens")
    pool = res["pool"]
    log(f"  {engine_kw}: suffix_dispatches={pool['suffix_dispatches']} "
        f"prefix_hit_rate={pool['prefix_hit_rate']} "
        f"compiles={res['compiles']} steady_s={res['wall_seconds']}")
    if pool["suffix_dispatches"] <= 0:
        raise AssertionError("no request took the suffix-prefill path")
    res["prompts"] = [r.prompt for r in reqs]
    return res


def _model_and_params(seed: int):
    """The model and weights ``serve_continuous`` builds for ``seed``."""
    import jax

    from repro.configs import get_config
    from repro.models import build_model

    model = build_model(get_config(SERVE_ARCH))
    return model, model.init(jax.random.PRNGKey(seed))


def compare_tokens(run, ref, model, params) -> None:
    """Greedy tokens of ``run`` and ``ref`` agree up to the first near-tie.

    At a request's first divergence both tokens must lie within
    ``LOGIT_TOL`` of the best logit of the jnp reference forward over the
    common prefix."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    same = total = 0
    diverged = []                      # (request, position)
    for i, (a, b) in enumerate(zip(run["generated"], ref["generated"])):
        total += len(b)
        d = next((t for t, (x, y) in enumerate(zip(a, b)) if x != y), None)
        same += len(b) if d is None else d
        if d is not None:
            diverged.append((i, d))
    log(f"  tokens identical: {same}/{total}; "
        f"requests diverging: {len(diverged)}/{len(ref['generated'])}")
    if not diverged:
        return
    seqs = [
        np.concatenate([run["prompts"][i], ref["generated"][i][:d]])
        for i, d in diverged
    ]
    # right-padded rows (causal: padding is invisible), 4 per forward so
    # the (rows, width, vocab) logits stay a few GB
    rows = -(-len(seqs) // 4) * 4
    width = -(-max(len(s) for s in seqs) // 256) * 256
    toks = np.zeros((rows, width), np.int32)
    last = np.zeros((rows,), np.int32)
    for r, s in enumerate(seqs):
        toks[r, : len(s)] = s
        last[r] = len(s) - 1

    @jax.jit
    def last_logits(p, t, idx):
        logits = model.forward(p, {"tokens": t})
        return jnp.take_along_axis(logits, idx[:, None, None], axis=1)[:, 0]

    logits = np.concatenate([
        np.asarray(last_logits(params, toks[c : c + 4], last[c : c + 4]),
                   np.float32)
        for c in range(0, rows, 4)
    ])[:, : model.cfg.vocab_size]
    for r, (i, d) in enumerate(diverged):
        row = logits[r]
        a, b = run["generated"][i][d], ref["generated"][i][d]
        tol = LOGIT_TOL * float(np.max(np.abs(row)))
        gap = float(row.max() - min(row[a], row[b]))
        log(f"  request {i} diverges at token {d}: {a} vs {b}, "
            f"gap to best {gap} (tolerance {tol})")
        if gap > tol:
            raise AssertionError(
                f"request {i}: token {d} differs beyond a near-tie"
            )


def check_kernel_in(prog, kernel: bool, name: str) -> None:
    """A compiled program holds a Pallas kernel iff it was built with one."""
    has = "tpu_custom_call" in prog.as_text()
    log(f"  {name} program (kernel={kernel}): tpu_custom_call={has}")
    if has != kernel:
        raise AssertionError(
            f"{name} program kernel={kernel}: tpu_custom_call={has}"
        )


def compare_programs(model, params) -> None:
    """One decode step and one suffix prefill over the same paged cache,
    compiled with and without the kernels: the kernel programs must hold
    ``tpu_custom_call`` and their logits agree within ``LOGIT_TOL``."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.models import attention

    slots, page = SLOTS, 16
    pages_per_slot = -(-(PROMPT_MAX + 2 * GEN) // page)
    table = 1 + np.arange(slots * pages_per_slot, dtype=np.int32).reshape(
        slots, pages_per_slot
    )
    cache = model.init_paged_cache(
        params, slots, 1 + slots * pages_per_slot, page, pages_per_slot
    )
    cache["table"] = jnp.asarray(table)
    rng = np.random.default_rng(0)
    lengths = np.linspace(PROMPT_MIN, PROMPT_MAX, slots).astype(np.int32)
    toks = rng.integers(0, model.cfg.vocab_size, (slots, PROMPT_MAX))
    cache, logits = jax.jit(model.prefill_slots)(
        params, cache, jnp.asarray(toks, jnp.int32), jnp.asarray(lengths),
        jnp.arange(slots, dtype=jnp.int32),
    )
    nxt = jnp.argmax(logits[:, : model.cfg.vocab_size], axis=-1)
    nxt = nxt.astype(jnp.int32)[:, None]
    suffix = jnp.asarray(
        rng.integers(0, model.cfg.vocab_size, (slots, GEN)), jnp.int32
    )
    suffix_args = (
        params, cache, suffix, jnp.full((slots,), GEN, jnp.int32),
        jnp.arange(slots, dtype=jnp.int32), jnp.asarray(lengths),
    )

    out = {}
    for kernel in (True, False):
        # fresh functions per variant: JAX caches a trace by function, and
        # the kernel switch is a module flag read at trace time
        def decode_fn(p, c, t):
            return model.decode(p, c, t)

        def suffix_fn(p, c, t, l, s, st):
            return model.prefill_slots(
                p, c, t, l, s, starts=st, prefix_pages=pages_per_slot
            )

        attention.set_decode_kernel(kernel, paged=True)
        attention.set_suffix_kernel(kernel)
        try:
            dec = jax.jit(decode_fn).lower(params, cache, nxt).compile()
            suf = jax.jit(suffix_fn).lower(*suffix_args).compile()
        finally:
            attention.set_decode_kernel(False)
            attention.set_suffix_kernel(False)
        check_kernel_in(dec, kernel, "decode")
        check_kernel_in(suf, kernel, "suffix prefill")
        out[kernel] = (dec(params, cache, nxt)[1], suf(*suffix_args)[1])
    for idx, name in enumerate(("decode", "suffix prefill")):
        k = np.asarray(out[True][idx], np.float32)[:, : model.cfg.vocab_size]
        j = np.asarray(out[False][idx], np.float32)[:, : model.cfg.vocab_size]
        if not (np.isfinite(k).all() and np.isfinite(j).all()):
            raise AssertionError(f"{name} logits are not finite")
        err = np.max(np.abs(k - j), axis=-1)
        scale = np.max(np.abs(j), axis=-1)
        log(f"  {name} logits kernel vs jnp: max |diff| per row "
            f"{err.tolist()}, as a share of the row's max |logit| "
            f"{(err / scale).tolist()} (tolerance {LOGIT_TOL})")
        if np.any(err > LOGIT_TOL * scale):
            raise AssertionError(f"{name} logits differ beyond tolerance")


def compare_kernels(seed: int) -> None:
    """The decode and suffix-prefill kernels, as the engine calls them at
    stablelm-1.6b widths, against their f32 oracles on random q/k/v: per
    row and kv head, |kernel − oracle| / |oracle| within ``KERNEL_TOL``."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import get_config
    from repro.kernels import ops

    cfg = get_config(SERVE_ARCH)
    hkv, hd = cfg.n_kv_heads, cfg.resolved_head_dim
    g = cfg.n_heads // hkv
    page = 16
    pages_per_slot = -(-(PROMPT_MAX + 2 * GEN) // page)
    table = jnp.asarray(1 + np.arange(SLOTS * pages_per_slot, dtype=np.int32)
                        .reshape(SLOTS, pages_per_slot))
    lengths = jnp.asarray(np.linspace(PROMPT_MIN, PROMPT_MAX, SLOTS), jnp.int32)
    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 6))

    def rand(*shape):
        return jax.random.normal(next(keys), shape, jnp.bfloat16)

    pool = (1 + SLOTS * pages_per_slot, page, hkv, hd)
    k_pool, v_pool = rand(*pool), rand(*pool)
    q_dec = rand(SLOTS, hkv, g, hd)
    q_suf = rand(SLOTS, GEN, hkv, g, hd)
    k_suf, v_suf = rand(SLOTS, GEN, hkv, hd), rand(SLOTS, GEN, hkv, hd)

    def decode(use_kernel):
        return ops.swa_decode_attention(
            q_dec, k_pool, v_pool, lengths, 0, use_kernel=use_kernel,
            table=table,
        )

    def suffix(use_kernel):
        out = ops.suffix_prefill_attention(
            q_suf, k_suf, v_suf, k_pool, v_pool, table, lengths,
            prefix_width=pages_per_slot, use_kernel=use_kernel,
        )
        return jnp.swapaxes(out, 1, 2)            # (rows, Hkv, S, G, hd)

    for name, fn in (("decode", decode), ("suffix prefill", suffix)):
        got = np.asarray(jax.jit(fn, static_argnums=0)(True), np.float32)
        with jax.default_matmul_precision("highest"):
            want = np.asarray(jax.jit(fn, static_argnums=0)(False), np.float32)
        got, want = (x.reshape(SLOTS, hkv, -1) for x in (got, want))
        rel = (np.linalg.norm(got - want, axis=-1)
               / np.linalg.norm(want, axis=-1)).max(axis=-1)
        log(f"  {name} kernel vs f32 oracle: worst kv head per row "
            f"{rel.tolist()} (tolerance {KERNEL_TOL})")
        if not np.all(rel <= KERNEL_TOL):
            raise AssertionError(f"{name} kernel differs from its oracle")


def phase_serve(seed: int) -> None:
    kernel = serve(seed, use_kernel=True)
    plain = serve(seed, use_kernel=False)
    compare_kernels(seed)
    model, params = _model_and_params(seed)
    compare_programs(model, params)
    compare_tokens(kernel, plain, model, params)


def phase_tp_serve(seed: int) -> None:
    import jax

    sharded = serve(seed, use_kernel=True, num_shards=4)
    log(f"  per-device memory after the 4-shard run: "
        f"{device_memory(jax.devices())}")
    single = serve(seed, use_kernel=True)
    model, params = _model_and_params(seed)
    compare_tokens(sharded, single, model, params)


# ---------------------------------------------------------------- training
def phase_train(seed: int) -> None:
    from repro.launch.train import run_training

    res = run_training(
        TRAIN_ARCH, smoke=False, steps=STEPS, seq_len=SEQ_LEN,
        per_cloud_batch=TRAIN_BATCH, n_clouds=3, local_steps=LOCAL_STEPS,
        seed=seed, log_every=1, log_fn=lambda m: log("  " + m),
    )
    losses = [h["loss"] for h in res["history"]]
    if len(losses) != STEPS or not all(math.isfinite(x) for x in losses):
        raise AssertionError(f"losses not finite: {losses}")
    log(f"  losses {losses}; {STEPS // LOCAL_STEPS} sync rounds; "
        f"wall_seconds {res['wall_seconds']} (first step compiles)")


def _params(state) -> dict:
    return {"clouds": state["clouds"]["params"],
            "global": state["global"]["params"]}


def step_norms(before, ref, pod, clouds: int):
    """Per cloud, and for the global model: |Δref|, |Δpod| and
    |Δpod − Δref| over all parameters, Δ = after − before (host arrays)."""
    import jax
    import numpy as np

    def norm(a, b, rows):
        sq = np.zeros(rows)
        for x, y in zip(jax.tree_util.tree_leaves(a),
                        jax.tree_util.tree_leaves(b)):
            d = (np.asarray(x, np.float32).reshape(rows, -1)
                 - np.asarray(y, np.float32).reshape(rows, -1))
            sq += np.sum(np.square(d), axis=1, dtype=np.float64)
        return np.sqrt(sq)

    return [
        norm(x[k], y[k], rows)
        for k, rows in (("clouds", clouds), ("global", 1))
        for x, y in ((ref, before), (pod, before), (pod, ref))
    ]


def phase_pod_train(seed: int) -> None:
    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.configs import get_config
    from repro.configs.base import FederatedConfig, TrainConfig
    from repro.core.federated import FederatedTrainer
    from repro.data import SyntheticCorpus, dirichlet_mixtures, federated_batch
    from repro.launch.mesh import federated_state_pspecs, make_sim_mesh
    from repro.launch.steps import make_federated_step
    from repro.models import build_model
    from repro.utils.hlo import analyze

    clouds = 4
    cfg = get_config(TRAIN_ARCH)
    model = build_model(cfg)
    fed = FederatedConfig(n_clouds=clouds, local_steps=LOCAL_STEPS)
    tcfg = TrainConfig(
        seq_len=SEQ_LEN, global_batch=POD_BATCH * clouds, steps=STEPS,
        warmup_steps=1, seed=seed,
    )
    corpus = SyntheticCorpus(vocab_size=cfg.vocab_size, noise=0.1)
    mix = dirichlet_mixtures(jax.random.PRNGKey(seed + 1), clouds, 8, 0.3)
    batches = [
        federated_batch(
            corpus, jax.random.fold_in(jax.random.PRNGKey(seed + 2), i), mix,
            POD_BATCH, SEQ_LEN,
        )
        for i in range(STEPS)
    ]
    key = jax.random.PRNGKey(seed)

    # the vmapped trainer on one device; every state it passes through is
    # kept on the host
    one = FederatedTrainer(model, fed, tcfg)
    state = jax.jit(one.init_state)(key)
    ref_step = jax.jit(one.train_step, donate_argnums=(0,))
    states, ref_losses = [jax.device_get(state)], []
    for batch in batches:
        state, metrics = ref_step(state, batch)
        ref_losses.append(float(metrics["loss"]))
        states.append(jax.device_get(state))
    del state
    gc.collect()

    # the pod-axis step, one cloud per device, run from each of those states
    mesh = make_sim_mesh(clouds)
    trainer, fed_step = make_federated_step(model, fed, tcfg, mesh=mesh)
    state_sh = jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s),
        federated_state_pspecs(jax.eval_shape(trainer.init_state, key), cfg,
                               mesh),
        is_leaf=lambda x: isinstance(x, P),
    )
    batch_sh = NamedSharding(mesh, P("pod"))
    with jax.set_mesh(mesh):   # spmd_axis_name="pod" resolves against it
        step = jax.jit(
            fed_step, in_shardings=(state_sh, batch_sh),
            out_shardings=(state_sh, NamedSharding(mesh, P())),
            donate_argnums=(0,),
        ).lower(states[0], batches[0]).compile()
    colls = {}
    for c in analyze(step.as_text()).collectives:
        colls[c.kind] = colls.get(c.kind, 0) + c.count
    log(f"  collectives in the compiled pod step: {colls}")

    failed = []
    for t, batch in enumerate(batches):
        before, ref = states[t], states[t + 1]
        out, metrics = step(jax.device_put(before, state_sh),
                            jax.device_put(batch, batch_sh))
        pod = jax.device_get(_params(out))
        del out
        ref_c, pod_c, gap_c, ref_g, pod_g, gap_g = step_norms(
            _params(before), _params(ref), pod, clouds
        )
        loss, ref_loss = float(metrics["loss"]), ref_losses[t]
        ratio_c = pod_c / ref_c
        ratio_g = pod_g / ref_g if ref_g[0] > 0 else pod_g   # 0: no sync
        log(f"  step {t + 1}: loss pod {loss} vmapped {ref_loss} (gap "
            f"{abs(loss - ref_loss) / abs(ref_loss)}, rtol {LOSS_RTOL}); "
            f"|Δpod|/|Δref| per cloud {ratio_c.tolist()}, global "
            f"{ratio_g.tolist()} (rtol {NORM_RTOL}); |Δpod − Δref|/|Δref| "
            f"per cloud {(gap_c / ref_c).tolist()}, global "
            f"{(gap_g / np.maximum(ref_g, 1e-30)).tolist()}")
        moved_ok = np.all(np.abs(ratio_c - 1) <= NORM_RTOL) and (
            np.all(np.abs(ratio_g - 1) <= NORM_RTOL) if ref_g[0] > 0
            else pod_g[0] == 0
        )
        if not (math.isfinite(loss) and moved_ok
                and abs(loss - ref_loss) <= LOSS_RTOL * abs(ref_loss)):
            failed.append(t + 1)
    log(f"  per-device memory after the pod steps: "
        f"{device_memory(jax.devices())}")
    if failed:
        raise AssertionError(f"pod-axis steps {failed} differ from the "
                             f"vmapped trainer's")


# -------------------------------------------------------------------- main
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    try:
        from repro.utils.compile_cache import enable_compile_cache
    except ImportError as e:
        print(f"chip_smoke: repository sources not found next to this "
              f"script ({e})", file=sys.stderr)
        return 2
    cache_dir = enable_compile_cache()

    import jax
    import jaxlib

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        print(f"chip_smoke: no TPU found; JAX sees {len(devices)} "
              f"{platform} device(s)", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} TPU "
              f"devices, found {len(devices)}", file=sys.stderr)
        return 1
    try:
        libtpu = importlib.metadata.version("libtpu")
    except importlib.metadata.PackageNotFoundError:
        libtpu = "not installed as a package"
    log(f"jax {jax.__version__}, jaxlib {jaxlib.__version__}, libtpu {libtpu}")
    log(f"devices: {len(devices)} x {devices[0].device_kind}")
    log(f"compile cache: {cache_dir}")

    phases = (
        [("serve", phase_serve), ("train", phase_train)]
        if args.chips == 1
        else [("tp_serve", phase_tp_serve), ("pod_train", phase_pod_train)]
    )
    clock = CompileClock()
    ok = True
    for name, fn in phases:
        log(f"phase {name}")
        t0, c0 = time.perf_counter(), clock.seconds
        try:
            fn(args.seed)
        except Exception:  # noqa: BLE001 — report the phase, run the rest
            traceback.print_exc()
            ok = False
            status = "FAILED"
        else:
            status = "ok"
        gc.collect()
        wall = time.perf_counter() - t0
        compile_s = clock.seconds - c0
        peak = [m["peak_bytes_in_use"] for m in device_memory(devices)]
        log(f"phase {name}: {status}; wall {wall} s, compile {compile_s} s, "
            f"rest {wall - compile_s} s; peak_bytes_in_use {peak}")
    if not ok:
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
