"""Pallas TPU kernel: length-aware paged flash-decode over ring-buffer KV,
in two flavors — per-row contiguous rings and a SHARED page-table pool.

``swa_decode`` streams EVERY cache chunk for every batch row, so a slot
holding 8 tokens in a 512-slot ring pays the same HBM traffic and MXU time
as a full slot. This kernel is its paged sibling for the continuous-batching
engine, where rows (slots) sit at wildly different depths: the ring is cut
into pages of ``page`` slots, the per-row number of LIVE pages

    live_pages[b] = ceil(min(pos[b] + 1, C) / page)

is scalar-prefetched (``pltpu.PrefetchScalarGridSpec``), and the grid is
(B, C/page) — each step takes one page of ALL kv heads, as in
``swa_decode`` — where dead pages are skipped two ways:

* the k/v index map clamps the page index to ``live_pages[b] - 1``, so a
  dead page issues NO new DMA (it re-reads the already-resident last live
  page — the standard paged-attention trick);
* the kernel body runs under ``pl.when(j < live_pages[b])``, so the MXU
  work is skipped outright.

A page is dead exactly when every one of its slots fails the ring validity
mask, which happens iff the ring has not wrapped past it (slot index >
pos): skipping it is therefore BITWISE identical to the unpaged kernel —
a fully-masked chunk contributes exp(NEG − m) == 0.0 to the online-softmax
state (and a leading garbage chunk is annihilated exactly by
``alpha = exp(NEG − m_new) == 0.0`` at the first live chunk). Tests pin
paged == unpaged bitwise and both against the jnp oracle.

Note ``live_pages`` depends on ``pos`` only through ``min(pos + 1, C)``:
once a row's ring wraps, every page is live and the kernel degrades to
exactly ``swa_decode``. The win is the engine's common case — short or
freshly admitted slots far from wrap.

Page-table mode (``table`` passed): the KV cache is ONE shared pool of
physical pages, shape (P, page, Hkv, hd) with no batch dimension, and
``table`` is a (B, T) int32 map — row b's logical page j lives at pool
page ``table[b, j]``, so a slot's pages may sit ANYWHERE in the pool
(vLLM-PagedAttention layout). The table rows are scalar-prefetched along
with ``pos``/``live_pages`` and drive the k/v DMA index map directly:

    kv_block(b, j) = pool[table[b, min(j, live_pages[b]-1)]]

Everything else — the ring-position validity mask over LOGICAL slot
indices ``j·page + i`` with capacity C = T·page, the live-page gating, the
online-softmax state — is identical to ring mode, so the output is bitwise
equal to the contiguous paged kernel at the SAME page size run over the
gathered cache ``pool[table].reshape(B, C, Hkv, hd)`` (tests pin exactly
that; comparing against ``swa_decode`` instead is only allclose when the
page size differs from its auto chunk — online softmax reassociates).

int8 pool mode (``k_scale``/``v_scale`` passed with ``table``): the pool
pages are int8 with one f32 scale per page slot per kv-head, shape
(P, page, Hkv), riding the SAME scalar-prefetched table indirection as the
pages themselves (one (page, Hkv) scale block per step). The body
dequantizes each block to the fp pool dtype
(``kv_quant``'s row scheme inverted: ``q·s`` in f32, cast) before the
unchanged online-softmax math, so the int8 kernel is bitwise equal to the
fp kernel run over the jnp-dequantized pool — the pin the tests use; the
tolerance story vs. the fp ENGINE lives at engine level.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.backend import resolve_interpret
from repro.kernels.swa_decode import (
    _attend_block,
    _chunk,
    _init_state,
    _ring_valid,
    _state_scratch,
    _write_out,
)


def _paged_kernel(
    *refs, page: int, cap: int, window: int, scale: float, deq=None,
):
    # refs = (pos_ref, pages_ref, [table_ref,] q_ref, k_ref, v_ref,
    #         [ks_ref, vs_ref,] o_ref, m_ref, l_ref, acc_ref) — the optional
    #         table_ref (page-table mode) is consumed by the kv index maps,
    #         not the body: the body masks LOGICAL slot indices, identical
    #         in both modes. With ``deq`` set (int8 pool mode) the k/v pool
    #         blocks are int8 and ks/vs carry one f32 scale per page slot
    #         per kv-head; dequant happens here, in-body, reproducing
    #         ``quantize.kv_dequant(..., dtype=deq)`` bitwise so the output
    #         equals the fp kernel run over the jnp-dequantized pool.
    pos_ref, pages_ref = refs[0], refs[1]
    if deq is not None:
        (q_ref, k_ref, v_ref, ks_ref, vs_ref,
         o_ref, m_ref, l_ref, acc_ref) = refs[-9:]
    else:
        q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref = refs[-7:]
    b = pl.program_id(0)
    j = pl.program_id(1)
    n_pages = cap // page

    @pl.when(j == 0)
    def _init():
        _init_state(m_ref, l_ref, acc_ref)

    @pl.when(j < pages_ref[b])
    def _live_page():
        k = k_ref[0]                                   # (page, Hkv, hd)
        v = v_ref[0]
        if deq is not None:
            k = (k.astype(jnp.float32) * ks_ref[0][:, :, None]).astype(deq)
            v = (v.astype(jnp.float32) * vs_ref[0][:, :, None]).astype(deq)
        _attend_block(
            q_ref[0].astype(jnp.float32),
            k.astype(jnp.float32),
            v.astype(jnp.float32),
            _ring_valid(pos_ref[b], j * page, page, cap, window),
            m_ref, l_ref, acc_ref, scale,
        )

    @pl.when(j == n_pages - 1)
    def _finish():
        _write_out(o_ref, l_ref, acc_ref)


@functools.partial(jax.jit, static_argnames=("window", "page", "interpret"))
def paged_decode(
    q: jax.Array,          # (B, Hkv, G, hd)
    k_cache: jax.Array,    # (B, C, Hkv, hd) — or (P, page, Hkv, hd) pool
    v_cache: jax.Array,    # same layout as k_cache
    pos: jax.Array,        # () or (B,) i32 — tokens already cached per row
    window: int = 0,
    *,
    page: int = 0,         # 0 = auto (swa_decode's chunk for this cache)
    table: jax.Array | None = None,  # (B, T) i32 page table → pool mode
    k_scale: jax.Array | None = None,  # (P, page, Hkv) f32 — int8 pool mode
    v_scale: jax.Array | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    b, hkv, g, hd = q.shape
    interpret = resolve_interpret(interpret)
    if table is not None:
        return _table_decode(
            q, k_cache, v_cache, pos, table, window=window,
            k_scale=k_scale, v_scale=v_scale, interpret=interpret,
        )
    assert k_scale is None and v_scale is None, (
        "int8 pool scales require page-table mode"
    )
    cap = k_cache.shape[1]
    pg = page or _chunk(cap, hkv)
    assert cap % pg == 0, f"cap {cap} not divisible by page {pg}"
    scale = hd**-0.5
    n_pages = cap // pg

    pos_b = jnp.broadcast_to(jnp.asarray(pos, jnp.int32).reshape(-1), (b,))
    # pages holding at least one slot the ring head has reached
    live = jnp.minimum(pos_b + 1, cap)
    pages = jnp.clip((live + pg - 1) // pg, 1, n_pages)

    kernel = functools.partial(
        _paged_kernel, page=pg, cap=cap, window=window, scale=scale
    )

    def kv_map(b_, j, pos_ref, pages_ref):
        # dead pages re-read the last live page: no fresh DMA
        return (b_, jnp.minimum(j, pages_ref[b_] - 1), 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, n_pages),
        in_specs=[
            pl.BlockSpec((1, hkv, g, hd), lambda b_, j, *_: (b_, 0, 0, 0)),
            pl.BlockSpec((1, pg, hkv, hd), kv_map),
            pl.BlockSpec((1, pg, hkv, hd), kv_map),
        ],
        out_specs=pl.BlockSpec((1, hkv, g, hd), lambda b_, j, *_: (b_, 0, 0, 0)),
        scratch_shapes=_state_scratch(hkv, g, hd),
    )
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((b, hkv, g, hd), q.dtype),
        grid_spec=grid_spec,
        interpret=interpret,
        name="paged_decode",
    )(pos_b, pages, q, k_cache, v_cache)


def _table_decode(
    q: jax.Array,          # (B, Hkv, G, hd)
    k_pool: jax.Array,     # (P, page, Hkv, hd) shared physical page pool
    v_pool: jax.Array,     # (P, page, Hkv, hd)
    pos: jax.Array,        # () or (B,) i32
    table: jax.Array,      # (B, T) i32 — logical page j of row b lives at
    #                        pool page table[b, j]; entries past the row's
    #                        live span are never dereferenced (index map
    #                        clamps to the last live page first)
    *,
    window: int,
    k_scale: jax.Array | None,  # (P, page, Hkv) f32 per-slot-per-head
    v_scale: jax.Array | None,  # scales → int8 pool mode (dequant
    #                        in-body to q.dtype, the fp pool dtype)
    interpret: bool,
) -> jax.Array:
    b, hkv, g, hd = q.shape
    pg = k_pool.shape[1]
    t_w = table.shape[1]
    cap = t_w * pg         # logical ring capacity per row
    scale = hd**-0.5
    quant = k_scale is not None
    assert quant == (v_scale is not None), "need both or neither scale pool"

    pos_b = jnp.broadcast_to(jnp.asarray(pos, jnp.int32).reshape(-1), (b,))
    live = jnp.minimum(pos_b + 1, cap)
    pages = jnp.clip((live + pg - 1) // pg, 1, t_w)
    table = jnp.asarray(table, jnp.int32)

    kernel = functools.partial(
        _paged_kernel, page=pg, cap=cap, window=window, scale=scale,
        deq=q.dtype if quant else None,
    )

    def kv_map(b_, j, pos_ref, pages_ref, table_ref):
        # page-table indirection: logical page j of row b_ lives wherever
        # the slot's table row says; dead logical pages re-read the last
        # live one (clamp BEFORE the table lookup, so an unallocated table
        # entry — by convention 0, the reserved scratch page — is never
        # the target of a fresh DMA for a live computation)
        return (table_ref[b_, jnp.minimum(j, pages_ref[b_] - 1)], 0, 0, 0)

    def scale_map(b_, j, pos_ref, pages_ref, table_ref):
        # scales ride the same table indirection as their pages
        return (table_ref[b_, jnp.minimum(j, pages_ref[b_] - 1)], 0, 0)

    in_specs = [
        pl.BlockSpec((1, hkv, g, hd), lambda b_, j, *_: (b_, 0, 0, 0)),
        pl.BlockSpec((1, pg, hkv, hd), kv_map),
        pl.BlockSpec((1, pg, hkv, hd), kv_map),
    ]
    inputs = [q, k_pool, v_pool]
    if quant:
        in_specs += [
            pl.BlockSpec((1, pg, hkv), scale_map),
            pl.BlockSpec((1, pg, hkv), scale_map),
        ]
        inputs += [k_scale, v_scale]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b, t_w),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, hkv, g, hd), lambda b_, j, *_: (b_, 0, 0, 0)),
        scratch_shapes=_state_scratch(hkv, g, hd),
    )
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((b, hkv, g, hd), q.dtype),
        grid_spec=grid_spec,
        interpret=interpret,
        name="paged_decode",
    )(pos_b, pages, table, *inputs)
