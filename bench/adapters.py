"""The one place the benchmark builds and reads the program's objects.

Everything the harness needs from the serving engine goes through here:
its construction (``build_engine``), the view of its live slots that the
harness stamps tokens from (``live``), its counters (``counters``), the
host spans wrapped around its device dispatches and the logits its decode
program returns (``annotate``). The same holds for the federated trainer
(``build_trainer``, ``federated_batches``). An API change touches this
file only."""
from __future__ import annotations

import dataclasses
import time

import jax

from bench.lookup import BenchError


def program_config(cfg: dict):
    """The program's model config for a dense-decoder configuration file."""
    from repro.configs import get_config

    return dataclasses.replace(
        get_config(cfg["program_arch"]),
        n_layers=cfg["num_hidden_layers"],
        d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"],
        d_ff=cfg["intermediate_size"],
        vocab_size=cfg["vocab_size"],
        head_dim=cfg.get("head_dim") or cfg["hidden_size"] // cfg["num_attention_heads"],
        rope_theta=float(cfg["rope_theta"]),
        norm_eps=cfg["rms_norm_eps"],
        dtype=cfg["torch_dtype"],
        tie_embeddings=cfg["tie_word_embeddings"],
        act=cfg["hidden_act"],
    )


def build_engine(cfg: dict, params, *, slots: int, num_pages: int,
                 table_width: int, page_size: int, seed: int):
    """``ServeEngine`` as an operator on a TPU runs it: paged pool, prefix
    cache, Pallas kernels; the table sized to the longest request."""
    from repro.launch.engine import ServeEngine
    from repro.models import build_model

    model = build_model(program_config(cfg))
    want = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    got = jax.eval_shape(lambda: params)
    if jax.tree_util.tree_structure(want) != jax.tree_util.tree_structure(got) or any(
        (a.shape, a.dtype) != (b.shape, b.dtype)
        for a, b in zip(jax.tree_util.tree_leaves(want), jax.tree_util.tree_leaves(got))
    ):
        raise BenchError("the program's parameter tree no longer matches "
                         "the benchmark's weights")
    return ServeEngine(
        model, params, num_slots=slots, max_seq=table_width * page_size,
        use_kernel=True, paged_cache=True, page_size=page_size,
        num_pages=num_pages, table_width=table_width, prefix_cache=True,
        seed=seed, time_fn=time.perf_counter,
    )


def bucket_length(n: int) -> int:
    from repro.launch.engine import bucket_length as f

    return f(n)


def make_request(uid: int, prompt, max_new: int, due: float):
    from repro.launch.engine import Request

    return Request(uid=uid, prompt=prompt, max_new_tokens=max_new,
                   arrival_time=due)


@dataclasses.dataclass
class Slot:
    uid: int
    generated: int      # tokens emitted so far
    feed: int           # prompt tokens (incl. any resume history) prefilled
    prefix: int         # leading feed tokens served from the prefix cache
    admit: float        # engine's admission stamp, on the harness's clock


def live(engine) -> list[Slot]:
    """Live slots; ``build_engine`` gives the engine ``time.perf_counter``,
    so its stamps plus its clock origin are harness times."""
    t0 = engine._t0
    return [
        Slot(s.req.uid, len(s.generated),
             len(s.feed) if s.feed is not None else len(s.req.prompt),
             s.prefix_len, s.admit_time + t0)
        for s in engine.slots if s is not None
    ]


def counters(engine) -> dict:
    """Running totals; the harness reports their change over a window."""
    pool = engine.pool_stats
    return {
        "steps": engine.steps,
        "prefix_hit_tokens": engine.prefix_hit_tokens,
        "prefix_lookup_tokens": engine.prefix_lookup_tokens,
        "prefill_tokens": engine.prefill_tokens,
        "cold_dispatches": engine.cold_dispatches,
        "suffix_dispatches": engine.suffix_dispatches,
        "preemptions": engine.preemptions,
        "cow_copies": engine.cow_copies,
        "prefix_evicted_pages": pool["prefix_evicted_pages"],
        "compiles": sum(engine.compiles.values()),
    }


def clear_prefix_index(engine) -> None:
    if engine.prefix is not None:
        engine.prefix.clear()


class DecodeLogits:
    """The program's logit of each token its decode program emits: after
    every decode dispatch, the row maxima of the returned logits (the
    greedy token's logit) are kept on the device with the ``(uid, token
    index)`` each row is producing; ``read`` fetches them once a window
    has closed. Rows that re-feed a stored or pending token are skipped."""

    def __init__(self, vocab: int):
        import jax.numpy as jnp

        self.vocab = vocab
        self.on = False
        self.calls: list = []
        self._max = jax.jit(lambda lg: jnp.max(lg[:, :vocab], axis=-1))

    def after(self, engine, logits) -> None:
        mx = self._max(logits)
        if self.on:
            rows = [(i, s.req.uid, len(s.generated))
                    for i, s in enumerate(engine.slots)
                    if s is not None and not s.pending and not s.resumed]
            self.calls.append((rows, mx))

    def read(self) -> dict:
        """``{(uid, token index): logit}`` for every captured row."""
        import numpy as np

        out = {}
        for rows, mx in self.calls:
            mx = np.asarray(mx)
            for i, uid, idx in rows:
                out[(uid, idx)] = float(mx[i])
        return out


def annotate(engine, capture: DecodeLogits | None = None) -> None:
    """Wrap the engine's device dispatches in named host spans, so a trace
    attributes device gaps to what the host was doing; ``capture`` also
    sees every decode dispatch's logits."""
    for attr, name in (("_decode", "engine.decode"),
                       ("_prefill_slots", "engine.prefill_cold"),
                       ("_prefill_suffix", "engine.prefill_suffix"),
                       ("_copy_page", "engine.copy_page")):
        fn = getattr(engine, attr, None)
        if fn is None:
            continue

        def wrapped(*a, _fn=fn, _name=name, **k):
            with jax.profiler.TraceAnnotation(_name):
                out = _fn(*a, **k)
            if capture is not None and _name == "engine.decode":
                capture.after(engine, out[1])
            return out

        setattr(engine, attr, wrapped)


# ------------------------------------------------------------- training
@dataclasses.dataclass
class Federated:
    """The paper's pod-axis federated step, one cloud per chip: the
    compiled step, its first state, the batch maker, and the trainer."""
    step: object          # (state, batch) -> (state, metrics), state donated
    state: dict
    batch: object         # key -> batch stack (n_clouds, B, ...), on the pod axis
    trainer: object
    mesh: object


def program_xlstm(cfg: dict):
    """The program's model config for an xLSTM configuration file."""
    from repro.configs import get_config

    return dataclasses.replace(
        get_config(cfg["program_arch"]),
        d_model=cfg["hidden_size"], n_layers=cfg["num_hidden_layers"],
        n_heads=cfg["num_heads"], n_kv_heads=cfg["num_heads"],
        vocab_size=cfg["vocab_size"], slstm_every=cfg["slstm_every"],
        dtype=cfg["torch_dtype"], norm_eps=cfg["norm_eps"])


def _same_tree(a, b) -> bool:
    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    return (jax.tree_util.tree_structure(a) == jax.tree_util.tree_structure(b)
            and all((x.shape, x.dtype) == (y.shape, y.dtype) for x, y in zip(la, lb)))


def build_federated(cfg: dict, job: dict, weights_fn, key, *, breaker=None) -> Federated:
    """``FederatedTrainer`` on the pod axis as ``make_federated_step``
    builds it: ``job["clouds"]`` clouds, one per chip, fedavg every
    ``job["local_steps"]`` steps, no compression; its state made on the
    chips in one call from ``key`` with the benchmark's weights
    (``weights_fn``) in place of the program's initialiser; batches from
    the program's federated data pipeline (Dirichlet mixtures over
    domains), made on the chips each step. ``breaker(trainer, fn)`` may
    replace the step function before it is compiled (tests)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.configs.base import FederatedConfig, TrainConfig
    from repro.data import SyntheticCorpus, dirichlet_mixtures, federated_batch
    from repro.launch.mesh import federated_state_pspecs, make_sim_mesh
    from repro.launch.steps import make_federated_step
    from repro.models import build_model

    pcfg = program_xlstm(cfg)
    model = build_model(pcfg)
    if not _same_tree(jax.eval_shape(model.init, key), jax.eval_shape(weights_fn, key)):
        raise BenchError("the program's parameter tree no longer matches "
                         "the benchmark's weights")
    model = dataclasses.replace(model, init=weights_fn)
    c, b, seq = job["clouds"], job["per_cloud_batch"], job["seq"]
    fed = FederatedConfig(n_clouds=c, local_steps=job["local_steps"],
                          aggregation="fedavg", compression="none")
    tcfg = TrainConfig(seq_len=seq, global_batch=c * b, steps=job["steps"],
                       lr=job["lr"], warmup_steps=job["warmup_steps"],
                       weight_decay=job["weight_decay"], beta1=job["beta1"],
                       beta2=job["beta2"], eps=job["eps"], grad_clip=job["grad_clip"])
    mesh = make_sim_mesh(c)
    trainer, fn = make_federated_step(model, fed, tcfg, mesh=mesh)
    if breaker is not None:
        fn = breaker(trainer, fn)
    state_sh = jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s),
        federated_state_pspecs(jax.eval_shape(trainer.init_state, key), pcfg, mesh),
        is_leaf=lambda x: isinstance(x, P))
    pod, rep = NamedSharding(mesh, P("pod")), NamedSharding(mesh, P())
    corpus = SyntheticCorpus(vocab_size=cfg["vocab_size"], n_domains=job["domains"],
                             noise=job["noise"])
    with jax.set_mesh(mesh):
        mixtures = dirichlet_mixtures(jax.random.fold_in(key, 1), c, job["domains"],
                                      job["beta"])
        batch = jax.jit(lambda k: federated_batch(corpus, k, mixtures, b, seq),
                        out_shardings=pod)
        state = jax.jit(trainer.init_state, out_shardings=state_sh)(key)
        shapes = jax.eval_shape(batch, key)
        step = jax.jit(fn, in_shardings=(state_sh, pod), out_shardings=(state_sh, rep),
                       donate_argnums=(0,)).lower(state, shapes).compile()
    return Federated(step, state, batch, trainer, mesh)
