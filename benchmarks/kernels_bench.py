"""Kernel micro-benchmarks: wall time of the pure-jnp reference path (the
CPU production path) and derived TPU-side arithmetic-intensity estimates for
each Pallas kernel. Interpret-mode timings are not meaningful hardware
numbers, so the derived column reports the kernel's bytes/elem roofline
character instead — EXCEPT the paged-decode occupancy sweep, where the
paged/unpaged ratio at fixed occupancy is the point: page skipping removes
whole grid steps, which interpret mode reproduces faithfully.

``--smoke`` shrinks sizes/iters to the CI budget (runs in CI next to
``serve_bench --smoke``).
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from benchmarks.common import emit, save_results
from repro.kernels import ops


def bench(fn, *args, iters=20):
    fn(*args)  # compile
    t0 = time.time()
    for _ in range(iters):
        r = fn(*args)
    jax.block_until_ready(r)
    return (time.time() - t0) / iters * 1e6


def bench_min(fn, *args, iters=5):
    """Min-of-N wall time (µs): the robust estimator for the noisy
    interpret-mode kernel timings the occupancy sweep compares."""
    jax.block_until_ready(fn(*args))  # compile
    best = float("inf")
    for _ in range(iters):
        t0 = time.time()
        jax.block_until_ready(fn(*args))
        best = min(best, time.time() - t0)
    return best * 1e6


def decode_occupancy_sweep(
    occupancies: dict, *, slots: int = 4, cap: int = 4096, hkv: int = 2,
    g: int = 2, hd: int = 64, iters: int = 5,
) -> dict:
    """SHARED probe (also driven by serve_bench): time the paged and the
    unpaged decode kernel, plus the PAGE-TABLE kernel over an equivalent
    shared pool, for each ``occupancies[label]`` position vector, returning
    ``{f"{paged|unpaged|table}_{label}_us": µs}``.

    The paged kernel's win scales with how much of the ring the live spans
    leave dead; the unpaged kernel streams cap slots per row regardless,
    so the low-occupancy rows are the load-bearing comparison. At full
    occupancy both kernels visit every page — any residual gap there is
    interpret-mode dispatch overhead, not page skipping, and should be
    read as noise. The cap must split into several auto-sized (512-slot)
    pages for skipping to exist at all.

    The ``table_*`` rows run the page-table mode (kernels/paged_decode.py
    pool layout) with each slot's pages deliberately SCATTERED across the
    pool — the indirection cost on top of ring-paged skipping is exactly
    the table_* − paged_* gap."""
    key = jax.random.PRNGKey(0)
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (slots, hkv, g, hd), jnp.bfloat16)
    kc = jax.random.normal(ks[1], (slots, cap, hkv, hd), jnp.bfloat16)
    vc = jax.random.normal(ks[2], (slots, cap, hkv, hd), jnp.bfloat16)
    # page-table layout of the SAME values: slot s's logical page j lands
    # at pool page 1 + j·slots + s (strided interleave — every logical
    # step jumps ``slots`` pages, the worst case for a contiguous reader;
    # pool page 0 is the reserved scratch page)
    # same page size the contiguous paged kernel auto-picks (_chunk), so
    # the table_* − paged_* gap isolates indirection, not partitioning
    from repro.kernels.swa_decode import _chunk

    page = _chunk(cap, hkv)
    t_w = cap // page
    flat_k = kc.reshape(slots * t_w, page, hkv, hd)   # row s·t_w + j
    flat_v = vc.reshape(slots * t_w, page, hkv, hd)
    idx = jnp.arange(slots * t_w)
    dest = 1 + (idx % t_w) * slots + idx // t_w       # (s, j) → 1 + j·slots + s
    pool_shape = (1 + slots * t_w, page, hkv, hd)
    pool_k = jnp.zeros(pool_shape, jnp.bfloat16).at[dest].set(flat_k)
    pool_v = jnp.zeros(pool_shape, jnp.bfloat16).at[dest].set(flat_v)
    table = dest.reshape(slots, t_w).astype(jnp.int32)
    # one jitted fn per variant, shared across labels — pos shape/dtype is
    # identical for every label, so each compiles exactly once
    fns = {
        "paged": jax.jit(
            lambda p: ops.swa_decode_attention(
                q, kc, vc, p, 0, use_kernel=True, paged=True
            )
        ),
        "unpaged": jax.jit(
            lambda p: ops.swa_decode_attention(
                q, kc, vc, p, 0, use_kernel=True, paged=False
            )
        ),
        "table": jax.jit(
            lambda p: ops.swa_decode_attention(
                q, pool_k, pool_v, p, 0, use_kernel=True, table=table,
            )
        ),
    }
    out = {}
    pos_arrs = {
        label: jnp.asarray(pos, jnp.int32)
        for label, pos in occupancies.items()
    }
    # warm EVERY (variant, label) dispatch before any timing. bench_min
    # already excludes each call's own compile, but the first variant timed
    # would still absorb one-time process costs (allocator growth, dispatch
    # caches) that later variants inherit for free — min-of-N cannot remove
    # a bias that never recurs, so pay all of it up front.
    for fn in fns.values():
        for pos in pos_arrs.values():
            jax.block_until_ready(fn(pos))
    for label, pos in pos_arrs.items():
        for variant, fn in fns.items():
            out[f"{variant}_{label}_us"] = bench_min(fn, pos, iters=iters)
    return out


def suffix_occupancy_sweep(
    start_levels: dict, *, rows: int = 2, suffix: int = 16, page: int = 16,
    t_w: int = 8, hkv: int = 2, g: int = 2, hd: int = 64, iters: int = 5,
) -> dict:
    """Suffix-prefill kernel time vs. cached-prefix depth over a SCATTERED
    paged pool (same strided layout as ``decode_occupancy_sweep``: row r's
    logical page j sits at pool page 1 + j·rows + r, so every logical step
    jumps ``rows`` pool pages).

    Two effects, both reproduced faithfully by interpret mode because each
    removes whole grid steps:

    * ``full_*`` rows fix the static prefix width at the table width — the
      ``pl.when`` dead-page skip is the only lever, so the shallow-vs-deep
      gap is pure page skipping;
    * ``bucket_*`` rows ALSO shrink the static width to the pow2 bucket
      covering ``max(starts)`` (``launch/engine.py::bucket_pages`` — the
      engine's start-bucket ladder); the saving over ``full_*`` at the
      same depth is the grid truncation the ladder buys on top.

    ``ref_us`` times the displaced jnp gather-concat path once — its cost
    is depth-independent (it always gathers the full table width), which
    is exactly why the kernel exists."""
    from repro.kernels.flash_suffix_prefill import suffix_prefill
    from repro.launch.engine import bucket_pages

    key = jax.random.PRNGKey(0)
    ks = jax.random.split(key, 5)
    q = jax.random.normal(ks[0], (rows, suffix, hkv, g, hd), jnp.bfloat16)
    k_suf = jax.random.normal(ks[1], (rows, suffix, hkv, hd), jnp.bfloat16)
    v_suf = jax.random.normal(ks[2], (rows, suffix, hkv, hd), jnp.bfloat16)
    pool_shape = (1 + rows * t_w, page, hkv, hd)
    pool_k = jax.random.normal(ks[3], pool_shape, jnp.bfloat16)
    pool_v = jax.random.normal(ks[4], pool_shape, jnp.bfloat16)
    idx = jnp.arange(rows * t_w)
    dest = 1 + (idx % t_w) * rows + idx // t_w    # (r, j) → 1 + j·rows + r
    table = dest.reshape(rows, t_w).astype(jnp.int32)

    def kernel_fn(width):
        return lambda s: suffix_prefill(
            q, k_suf, v_suf, pool_k, pool_v, table, s,
            prefix_width=width,
        )

    out = {}
    for label, start_tokens in start_levels.items():
        starts = jnp.full((rows,), int(start_tokens), jnp.int32)
        wb = bucket_pages(-(-int(start_tokens) // page), t_w)
        for variant, width in (("full", t_w), ("bucket", wb)):
            if variant == "bucket" and width == t_w:
                continue  # same trace as full_* — nothing new to time
            us = bench_min(kernel_fn(width), starts, iters=iters)
            out[f"{variant}_{label}_us"] = us
    ref_fn = jax.jit(
        lambda s: ops.suffix_prefill_attention(
            q, k_suf, v_suf, pool_k, pool_v, table, s, prefix_width=t_w
        )
    )
    out["ref_us"] = bench_min(
        ref_fn, jnp.full((rows,), t_w * page, jnp.int32), iters=iters
    )
    return out


def bench_suffix_occupancy(rows: dict, *, smoke: bool) -> None:
    """Suffix-prefill kernel across cached-prefix depths: shallow (one live
    page of the table) vs. deep (every page live), full static width vs.
    the engine's start bucket."""
    page, t_w = 16, 8
    iters = 3 if smoke else 6
    start_levels = {
        "shallow": page,          # 1 of t_w pages live → 7 skipped
        "deep": t_w * page,       # every page live → nothing to skip
    }
    sweep = suffix_occupancy_sweep(
        start_levels, page=page, t_w=t_w, iters=iters
    )
    for key, us in sweep.items():
        name = f"suffix_{key[: -len('_us')]}"
        rows[name] = us
        detail = (
            "jnp gather-concat path (depth-independent)" if key == "ref_us"
            else f"table_width={t_w};page={page}"
        )
        emit(f"kernels/{name}", us, detail)


def bench_decode_occupancy(rows: dict, *, smoke: bool) -> None:
    """Paged vs. unpaged decode kernel across ring occupancy levels.

    Two axes: every-slot depth (all shallow vs. all past wrap) and MIXED
    occupancy (one deep slot among freshly reset ones — the continuous-
    batching engine's steady state right after a backfill)."""
    slots, cap = 4, (2048 if smoke else 4096)
    iters = 3 if smoke else 8
    shallow = 16
    occupancies = {
        "1live": [cap + 5] + [shallow] * (slots - 1),
        "alllive": [cap + 5] * slots,
        "allshallow": [shallow] * slots,
    }
    sweep = decode_occupancy_sweep(
        occupancies, slots=slots, cap=cap, iters=iters
    )
    if smoke:
        # CI guard: page skipping must make an all-shallow paged decode
        # cheaper than a full-ring unpaged one — the probe's load-bearing
        # contrast. A silently broken skip path (kernel visiting dead
        # pages) would otherwise hide inside timing noise.
        assert sweep["paged_allshallow_us"] < sweep["unpaged_alllive_us"], (
            "occupancy probe inverted: shallow paged decode "
            f"({sweep['paged_allshallow_us']:.0f}us) should beat full "
            f"unpaged ({sweep['unpaged_alllive_us']:.0f}us) — page "
            "skipping is not skipping"
        )
    for key, us in sweep.items():
        variant, label, _ = key.split("_", 2)
        name = f"decode_{variant}_{label}"
        rows[name] = us
        emit(
            f"kernels/{name}", us,
            f"cap={cap};pages_live={'mixed' if label == '1live' else label}",
        )


def run(argv: list[str] | None = None) -> dict:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized run: ONLY the paged-decode occupancy "
                    "sweep, at smaller shapes/iters")
    args = ap.parse_args(argv if argv is not None else [])

    rows = {}
    if args.smoke:
        # CI cares about the paged/unpaged occupancy contrast; the legacy
        # full-size rows (1M-element refs, 8k-ring decode, flash prefill)
        # would dominate the step's wall time for no signal
        bench_decode_occupancy(rows, smoke=True)
        bench_suffix_occupancy(rows, smoke=True)
        save_results("kernels_smoke", rows)
        return rows

    key = jax.random.PRNGKey(0)

    x = jax.random.normal(key, (1 << 20,))  # 1M-element gradient leaf
    us = bench(jax.jit(lambda v: ops.topk_sparsify_leaf(v, 0.01)), x)
    rows["topk_ref_1M"] = us
    emit("kernels/topk_1M", us, "hbm=8B/elem;compute=k·max/256elem")

    us = bench(jax.jit(lambda v: ops.int8_roundtrip_leaf(v)), x)
    rows["int8_ref_1M"] = us
    emit("kernels/int8_1M", us, "hbm=8B/elem;compute=3flop/elem")

    tree = {"a": x, "b": jax.random.normal(key, (1 << 18,))}
    us = bench(
        jax.jit(lambda t: ops.dp_transmit(t, key, 1.0, 0.1)), tree
    )
    rows["dp_transmit_1.25M"] = us
    emit("kernels/dp_transmit", us, "two-pass;hbm=12B/elem")

    q = jax.random.normal(key, (4, 8, 4, 128), jnp.bfloat16)
    kc = jax.random.normal(key, (4, 8192, 8, 128), jnp.bfloat16)
    vc = jax.random.normal(key, (4, 8192, 8, 128), jnp.bfloat16)
    us = bench(
        jax.jit(lambda a, b, c: ops.swa_decode_attention(a, b, c, jnp.asarray(9000), 8192)),
        q, kc, vc,
    )
    rows["swa_decode_ref_8k_window"] = us
    emit("kernels/swa_decode_8k", us, "hbm-bound:2·C·Hkv·hd·2B/token")

    bench_decode_occupancy(rows, smoke=False)
    bench_suffix_occupancy(rows, smoke=False)

    # flash prefill attention (causal GQA): ref oracle at CPU-feasible size.
    # HBM model: flash = O(Q+K+V+O) vs naive = O(S²·H) probs materialized.
    qf = jax.random.normal(key, (2, 512, 4, 4, 64), jnp.bfloat16)
    kf = jax.random.normal(key, (2, 512, 4, 64), jnp.bfloat16)
    vf = jax.random.normal(key, (2, 512, 4, 64), jnp.bfloat16)
    us = bench(
        jax.jit(lambda a, b, c: ops.flash_prefill_attention(a, b, c, causal=True)),
        qf, kf, vf,
    )
    rows["flash_prefill_ref_512"] = us
    emit("kernels/flash_prefill_512", us, "vmem-resident softmax;hbm=Q+K+V+O")

    save_results("kernels", rows)
    return rows


if __name__ == "__main__":
    import sys

    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    run(sys.argv[1:])
