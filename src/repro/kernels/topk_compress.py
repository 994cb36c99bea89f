"""Pallas TPU kernel: block-local top-k gradient sparsification (§3.2).

TPU adaptation (DESIGN.md §2.4): no sort. The per-row k-th-largest magnitude
is found by k rounds of masked vector max — every operation is a VPU
reduce/select over a (ROWS, 256) VMEM tile, fully lane-parallel. Ties at the
threshold are kept (threshold semantics, matching ref.topk_sparsify_ref).

Tile shape (8, 256): 8 sublanes × 2 lane-groups of 128 — one fp32 VREG tile
pair per row-block, k ≤ 64 keeps the loop cheap next to the HBM round trip
(the op is memory-bound: 8 KiB in / 8 KiB out per tile)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.backend import resolve_interpret

ROWS = 8
BLOCK = 256


def _topk_kernel(x_ref, o_ref, *, k: int):
    x = x_ref[...].astype(jnp.float32)            # (ROWS, BLOCK)
    mag = jnp.abs(x)

    def body(_, carry):
        # per row: lower thr to the next distinct magnitude until the number
        # of elements ≥ thr reaches k (ties counted as a group, matching the
        # oracle's "k-th largest" threshold semantics)
        active, thr, cnt = carry
        cur = jnp.max(jnp.where(active, mag, -1.0), axis=1, keepdims=True)
        ties = jnp.sum((mag == cur).astype(jnp.int32), axis=1, keepdims=True)
        need = cnt < k
        thr = jnp.where(need, cur, thr)
        cnt = cnt + jnp.where(need, ties, 0)
        active = active & (mag < cur)
        return active, thr, cnt

    init = (
        jnp.ones(mag.shape, jnp.bool_),
        jnp.zeros((ROWS, 1), jnp.float32),
        jnp.zeros((ROWS, 1), jnp.int32),
    )
    _, thr, _ = jax.lax.fori_loop(0, k, body, init)
    o_ref[...] = jnp.where(mag >= thr, x, 0.0).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("k", "interpret"))
def topk_sparsify(
    x: jax.Array, k: int, *, interpret: bool | None = None
) -> jax.Array:
    """x: (nb, 256) fp32 → same shape with sub-threshold entries zeroed.

    nb must be a multiple of 8 (pad upstream)."""
    nb, block = x.shape
    assert block == BLOCK, f"expected block {BLOCK}, got {block}"
    assert nb % ROWS == 0, f"rows {nb} not a multiple of {ROWS}"
    return pl.pallas_call(
        functools.partial(_topk_kernel, k=k),
        out_shape=jax.ShapeDtypeStruct((nb, block), x.dtype),
        grid=(nb // ROWS,),
        in_specs=[pl.BlockSpec((ROWS, BLOCK), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((ROWS, BLOCK), lambda i: (i, 0)),
        interpret=resolve_interpret(interpret),
        name="topk_compress",
    )(x)
