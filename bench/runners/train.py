"""Training runner: the paper's federated step across the chips, one cloud
per chip on the ``pod`` mesh axis, under a training job's parameters.

Set-up (counted in ``setup_s``): the compiled step and its state, made on
the chips from the seed with the benchmark's weights; then the first
``local_steps`` steps (the first sync round) through the window's own call
and feed, recording what the optimizer took: each cloud's loss per step,
each cloud's first clipped gradient per leaf (from AdamW's first moment
after step 1) and the global model's change per leaf after the round.

Window (``--seconds``): steps back to back, each with a fresh batch made on
the chips by the program's data pipeline, at most two in flight; it closes
when the last step dispatched has finished.

After the window: peak memory is read, the state freed, and the
configuration's plain reference follows the same first round from the
same weights and batches; the program's readings are compared with its.
"""
from __future__ import annotations

import functools
import gc
import json
import shutil
import time

import numpy as np

from bench import adapters, flops, generate, lookup
from bench import trace as tracing

IN_FLIGHT = 2
SMALL = 1e-3      # leaves whose reference gradient is under this share of
                  # the median leaf's move by round-off alone: left out
# faults that can be planted in the reference put in the program's place
FAULTS = ("half_batch", "no_exchange")


def span(name: str):
    import jax

    return jax.profiler.TraceAnnotation(name)


def _names(tree) -> list[str]:
    import jax

    return [jax.tree_util.keystr(p) for p, _ in
            jax.tree_util.tree_flatten_with_path(tree)[0]]


def _cloud_norms(tree, scale: float = 1.0):
    """Per leaf, per cloud (leading axis): the float32 norm, as (leaves, C)."""
    import jax
    import jax.numpy as jnp

    def one(x):
        x = x.astype(jnp.float32) * scale
        return jnp.sqrt(jnp.sum(x * x, axis=tuple(range(1, x.ndim))))

    return jnp.stack([one(x) for x in jax.tree_util.tree_leaves(tree)])


def _change_norms(new, old):
    """Per leaf: the float32 norm of ``new - old``."""
    import jax
    import jax.numpy as jnp

    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32) - b.astype(jnp.float32))))
                      for a, b in zip(jax.tree_util.tree_leaves(new),
                                      jax.tree_util.tree_leaves(old))])


# ------------------------------------------------------------------ run
def run(ctx) -> dict:
    import jax

    job, cfg = ctx.traffic, ctx.cfg
    log = ctx.log
    if len(ctx.devices) < job["clouds"]:
        raise lookup.BenchError(f"{job['clouds']} clouds need as many devices, "
                                f"JAX sees {len(ctx.devices)}")
    compiles = tracing.CompileCount()
    ref = lookup.reference(ctx.cell["config"])
    key = generate.seed_key(ctx.seed)
    wfn = functools.partial(ref.make_weights, cfg)
    fed = adapters.build_federated(cfg, job, wfn, key, breaker=ctx.breaker)
    log(f"step compiled, state made; device memory {_memory(ctx)}")
    h, b1 = job["local_steps"], job["beta1"]
    kb = jax.random.fold_in(key, 2)
    with jax.set_mesh(fed.mesh):
        grad_norms = jax.jit(lambda st: _cloud_norms(st["clouds"]["opt"]["m"], 1 / (1 - b1)))
        change = jax.jit(lambda st, k: _change_norms(st["global"]["params"], wfn(k)))
        state, first, losses = fed.state, [], []
        for t in range(1, h + 1):
            batch = fed.batch(jax.random.fold_in(kb, t))
            first.append(batch)
            state, met = fed.step(state, batch)
            losses.append(np.asarray(met["per_cloud_loss"]))
            if t == 1:
                g1 = np.asarray(grad_norms(state))
        prog = {"loss": np.stack(losses), "grad": g1,
                "change": np.asarray(change(state, key)),
                "synced": float(met["synced"])}
        jax.block_until_ready(state)
        gc.collect()
        t_start = time.perf_counter()
        setup_s = t_start - ctx.t0
        log(f"set-up {setup_s:.3f} s (first round: losses {prog['loss'].tolist()}); "
            "window opens")
        xla0 = compiles.n
        state, steps, syncs, tr = _window(ctx, fed, state, kb, h, t_start)
        t_close = time.perf_counter()
        xla_in_window = compiles.n - xla0
    window_s = t_close - t_start
    chips = ctx.cell["chips"]
    memory_peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                      for d in ctx.devices[:chips])
    tokens = steps * job["clouds"] * job["per_cloud_batch"] * job["seq"]
    log(f"window {window_s:.3f} s: {steps} steps, {syncs} syncs, {tokens} tokens, "
        f"compiles in window: XLA {xla_in_window}")
    del state, fed
    gc.collect()

    t_ref = time.perf_counter()
    sides = ("program",) + tuple(ctx.readings)
    if ctx.control and ctx.control not in sides:
        sides += (ctx.control,)
    base = _follow(ref, cfg, job, wfn, key, first)
    readings = {}
    for side in sides:
        if side == "program":
            got = prog
        elif side in FAULTS:
            got = _follow(ref, cfg, job, wfn, key, first, fault=side)
        else:
            got = _follow(ref, cfg, job, wfn, key, first, control=side)
        readings[side] = compare(got, base, _names(jax.eval_shape(wfn, key)))
    log(f"reference check, first round of {h} steps, "
        f"{time.perf_counter() - t_ref:.3f} s: {json.dumps(readings)}")
    judged = readings[ctx.control or "program"]
    lim = job["check"]
    checks = {k: {"value": judged[k], "limit": lim[k]} for k in lim}
    rec = {
        "e2e": {"train_tok_s": tokens / window_s, "setup_s": setup_s},
        "attempted": steps,
        "failed": 0,
        "checks": checks,
        "memory_peak_bytes": memory_peak,
        "window_s": window_s,
        "readings": readings,
        "train_flops": tokens * flops.xlstm_train_flops_per_token(cfg, job["seq"]),
        "chips": chips,
        "peaks": ctx.peaks,
    }
    if tr is not None:
        rec["trace"] = tracing.load(ctx.trace_dir)
        shutil.rmtree(ctx.trace_dir, ignore_errors=True)
        rec["traced"] = tr
        rec["traced"]["flops"] = (tr["steps"] * job["clouds"] * job["per_cloud_batch"]
                                  * job["seq"] * flops.xlstm_train_flops_per_token(cfg, job["seq"]))
    return rec


def _window(ctx, fed, state, kb, h, t_start):
    """Steps until ``ctx.seconds`` have passed, at most ``IN_FLIGHT``
    unfinished; returns the state, the steps and syncs run, and what the
    traced part of the window saw."""
    import jax

    tw = ctx.traffic.get("trace", {})
    t_end = t_start + ctx.seconds
    t_tr0 = t_start + tw.get("offset_s", 0.0)
    t_tr1 = t_tr0 + tw.get("seconds", ctx.seconds)
    pending, steps, t = [], 0, h
    ann, traced = None, None
    while True:
        now = time.perf_counter()
        if ctx.trace and traced is None and now >= t_tr0:
            tracing.start(ctx.trace_dir)
            ann = span(tracing.WINDOW_SPAN)
            ann.__enter__()
            traced = {"first": t + 1, "steps": 0}
        if ann is not None and now >= min(t_tr1, t_end):
            with span("bench.wait"):
                jax.block_until_ready(pending)
            ann.__exit__(None, None, None)
            tracing.stop()
            traced["steps"] = t - traced["first"] + 1
            ann = None
        if now >= t_end:
            break
        t += 1
        with span("bench.batch"):
            batch = fed.batch(jax.random.fold_in(kb, t))
        with span("bench.step"):
            state, met = fed.step(state, batch)
        steps += 1
        pending.append(met["loss"])
        if len(pending) > IN_FLIGHT:
            with span("bench.wait"):
                pending.pop(0).block_until_ready()
    jax.block_until_ready(pending)
    syncs = sum(1 for i in range(h + 1, t + 1) if i % h == 0)
    if traced is not None:
        traced["syncs"] = sum(1 for i in range(traced["first"], traced["first"] + traced["steps"])
                              if i % h == 0)
    return state, steps, syncs, traced


def _memory(ctx) -> str:
    st = ctx.devices[0].memory_stats() or {}
    return f"in use {st.get('bytes_in_use')}, peak {st.get('peak_bytes_in_use')}"


# ------------------------------------------------------------ reference
def _follow(ref, cfg, job, wfn, key, batches, control=None, fault=None):
    """The reference through the same first round: each cloud's losses,
    first clipped gradient norms per leaf, and the global model's change
    per leaf after the sync. In the program's place: ``control`` computes
    it in a lower precision; ``fault`` plants one of ``FAULTS`` (half of
    each batch left out, the mean taken over the rest; the exchange
    between chips left out, so the global model is one cloud's)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    c = job["clouds"]
    mesh = jax.make_mesh((c,), ("pod",), axis_types=(jax.sharding.AxisType.Auto,))
    pod, rep = NamedSharding(mesh, P("pod")), NamedSharding(mesh, P())
    w0 = jax.jit(wfn, out_shardings=rep)(key)
    stack = jax.jit(lambda w: jax.tree_util.tree_map(
        lambda x: jnp.broadcast_to(x[None], (c,) + x.shape), w), out_shardings=pod)
    zeros = jax.jit(lambda w: jax.tree_util.tree_map(
        lambda x: jnp.zeros((c,) + x.shape, jnp.float32), w), out_shardings=pod)
    params, m, v = stack(w0), zeros(w0), zeros(w0)
    one = jax.jit(jax.vmap(functools.partial(ref.local_step, cfg, job, control=control),
                           in_axes=(0, 0, 0, None, 0, 0)),
                  in_shardings=(pod, pod, pod, rep, pod, pod),
                  out_shardings=(pod, pod, pod, pod, pod))
    losses = []
    for t, batch in enumerate(batches, start=1):
        tok, lab = batch["tokens"], batch["labels"]
        if fault == "half_batch":
            half = tok.shape[1] // 2
            tok, lab = tok[:, :half], lab[:, :half]
        params, m, v, loss, g = one(params, m, v, jnp.float32(t), tok, lab)
        losses.append(np.asarray(loss))
        if t == 1:
            g1 = np.asarray(jax.jit(_cloud_norms)(g))
        del g
    weights = jnp.full((c,), 1.0 / c, jnp.float32)
    if fault == "no_exchange":
        new = jax.jit(lambda p: jax.tree_util.tree_map(lambda x: x[0], p),
                      out_shardings=rep)(params)
    else:
        new = jax.jit(ref.fedavg, out_shardings=rep)(params, w0, weights)
    return {"loss": np.stack(losses), "grad": g1,
            "change": np.asarray(jax.jit(_change_norms)(new, w0)), "synced": 1.0}


def compare(got: dict, want: dict, names: list[str]) -> dict:
    """The numbers ``correct`` is decided on, ``got`` against the
    reference's ``want``:

    * ``loss_gap``: the largest relative gap of a cloud's loss at a step;
    * ``grad_gap``: over clouds and leaves, the largest gap between the
      first clipped gradient's norms, over the larger of the reference
      leaf's norm and the median leaf's; ``grad_gap_median`` the median
      leaf's gap (largest over clouds);
    * ``change_gap`` and ``change_gap_median``: the same for the global
      model's change over the round.

    Leaves whose reference gradient is under ``SMALL`` of the median
    leaf's are left out of both (they move by round-off alone)."""
    out = {"loss_gap": float(np.max(np.abs(got["loss"] - want["loss"]) / want["loss"]))}
    gw, gg = want["grad"], got["grad"]                   # (leaves, C)
    med = np.median(gw, axis=0)
    keep = np.mean(gw, axis=1) >= SMALL * np.median(np.mean(gw, axis=1))
    gap = np.abs(gg - gw) / np.maximum(gw, med[None, :])
    gap = gap[keep]
    kept = [n for n, k in zip(names, keep) if k]
    worst = np.unravel_index(np.argmax(gap), gap.shape)
    out["grad_gap"] = float(gap[worst])
    out["grad_gap_median"] = float(np.max(np.median(gap, axis=0)))
    out["grad_worst_leaf"] = kept[worst[0]]
    cw, cg = want["change"][keep], got["change"][keep]
    cgap = np.abs(cg - cw) / np.maximum(cw, np.median(cw))
    out["change_gap"] = float(np.max(cgap))
    out["change_gap_median"] = float(np.median(cgap))
    out["change_worst_leaf"] = kept[int(np.argmax(cgap))]
    out["synced"] = got["synced"]
    out["left_out"] = int(np.sum(~keep))
    return out
