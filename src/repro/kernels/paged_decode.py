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
(vLLM-PagedAttention layout). ``pos``, ``live_pages`` and the table are
scalar-prefetched, and the grid is (B,): each row walks ONLY its live
pages,

    ceil(live_pages[b] / ppb) blocks of ppb pages, C = T·page,

in a ``lax.fori_loop`` with a dynamic trip count. The pools stay in HBM
(``memory_space=pl.ANY``); a block is ``ppb`` page DMAs,
``pool[table[b, j]] → buf[slot, p]``, into a double-buffered VMEM
scratch, and the copies of the next block (or of the next row's first
block) start before the current one is computed. No dead page costs a
grid step or a DMA, except for the slots of the row's last block past its
live span: those re-read the last live page (the index is clamped BEFORE
the table lookup), so a table entry past the live span — the engine
leaves them at scratch page 0 — is never dereferenced, every value that
reaches ``p·v`` is a live page's, and the ring mask kills the slots.
``ppb`` comes from the shapes (``pages_per_block``: the most pages whose
keys fit ``BLOCK_BYTES`` in the query dtype; 8 at Mistral-NeMo's 8 kv
heads of 128 in bf16). The math is ring mode's — the same ``_ring_valid``
mask over LOGICAL slot indices ``j·page + i`` and the same
``_attend_block`` state in f32 — over blocks of ``ppb·page`` tokens, so
the output is bitwise equal to the contiguous paged kernel at page size
``ppb·page`` run over the gathered cache ``pool[table].reshape(B, C, Hkv,
hd)`` (tests pin exactly that; comparing against ``swa_decode`` instead is
only allclose when the block differs from its auto chunk — online softmax
reassociates).

A page DMA needs lane-aligned minor dims (``walks_pages``): a head_dim
that is not a multiple of 128 (stablelm-1.6b's 64) keeps the (B, T) grid
of one page a step, where a dead page skips its DMA (the index map clamps
to the last live page) and its body (``pl.when``) but still costs a grid
step; its output is bitwise the contiguous kernel at the SAME page size.

int8 pool mode (``k_scale``/``v_scale`` passed with ``table``): the pool
pages are int8 with one f32 scale per page slot per kv-head, shape
(P, page, Hkv), riding the SAME table indirection as the pages: in the
walk the scale planes are flattened to (P, page·Hkv) (lane-aligned when
page·Hkv is a multiple of 128) and each page's scales come with its page
DMAs; in the grid one (page, Hkv) scale block comes per step. The body
dequantizes each block to the query dtype (``kv_quant``'s row scheme
inverted: ``q·s`` in f32, cast) before the unchanged online-softmax math,
and blocks alike for fp and int8, so the int8 kernel is bitwise equal to
the fp kernel run over the jnp-dequantized pool — the pin the tests use;
the tolerance story vs. the fp ENGINE lives at engine level.
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


# Bytes of keys (and as many of values) one compute block of the table
# walk holds, counted in the query dtype: 8 pages of 16 tokens x 8 kv heads
# x 128 in bf16 (Mistral-NeMo).
BLOCK_BYTES = 256 * 1024
# A page DMA copies whole (8, 128) tiles: Mosaic refuses a slice of the
# pool whose minor dim is not a multiple of the lane width.
LANES = 128


def pages_per_block(t_w: int, page: int, hkv: int, hd: int, dtype) -> int:
    """Pages per compute block of the table walk: the most pages, of a
    power-of-two ladder dividing the table width ``t_w``, whose keys fit
    ``BLOCK_BYTES`` in ``dtype`` (the query dtype, which the int8 pool
    dequantizes to, so fp and int8 pools block alike); 1 if none does."""
    item = jnp.dtype(dtype).itemsize
    for ppb in (64, 32, 16, 8, 4, 2):
        if t_w % ppb == 0 and ppb * page * hkv * hd * item <= BLOCK_BYTES:
            return ppb
    return 1


def walks_pages(page: int, hkv: int, hd: int, quant: bool) -> bool:
    """Whether the table mode takes the page walk (``_table_walk``): its
    page DMAs need lane-aligned minor dims, ``hd`` for the pool and
    ``page·Hkv`` for an int8 pool's flattened scale planes. Other shapes
    keep the (B, T) grid (``_table_grid``)."""
    return hd % LANES == 0 and (not quant or page * hkv % LANES == 0)


def _table_decode(
    q: jax.Array,          # (B, Hkv, G, hd)
    k_pool: jax.Array,     # (P, page, Hkv, hd) shared physical page pool
    v_pool: jax.Array,     # (P, page, Hkv, hd)
    pos: jax.Array,        # () or (B,) i32
    table: jax.Array,      # (B, T) i32 — logical page j of row b lives at
    #                        pool page table[b, j]; entries past the row's
    #                        live span are never dereferenced
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
    quant = k_scale is not None
    assert quant == (v_scale is not None), "need both or neither scale pool"

    pos_b = jnp.broadcast_to(jnp.asarray(pos, jnp.int32).reshape(-1), (b,))
    live = jnp.minimum(pos_b + 1, t_w * pg)
    pages = jnp.clip((live + pg - 1) // pg, 1, t_w)
    table = jnp.asarray(table, jnp.int32)
    run = _table_walk if walks_pages(pg, hkv, hd, quant) else _table_grid
    return run(
        q, k_pool, v_pool, pos_b, pages, table, window=window,
        k_scale=k_scale, v_scale=v_scale, interpret=interpret,
    )


def _table_kernel(
    pos_ref, pages_ref, table_ref, q_ref, *refs,
    page: int, ppb: int, cap: int, window: int, scale: float, deq=None,
):
    # refs = (k_hbm, v_hbm, [ks_hbm, vs_hbm,] o_ref,
    #         k_buf, v_buf, [ks_buf, vs_buf,] sem, slot_ref, m, l, acc).
    # The pools (and int8 scale planes, flattened to (P, page·Hkv)) stay in
    # HBM; each buffer holds two blocks of ``ppb`` pages, and ``slot_ref``
    # says which of the two holds the current row's first block (the
    # previous row started its copies).
    n = 4 if deq is not None else 2
    hbm, o_ref, bufs = refs[:n], refs[n], refs[n + 1 : 2 * n + 1]
    sem, slot_ref, m_ref, l_ref, acc_ref = refs[2 * n + 1 :]
    b = pl.program_id(0)
    n_rows = pl.num_programs(0)
    ck = ppb * page

    def copies(row, blk, slot):
        # one DMA per page and pool; slots past the row's live span re-read
        # its last live page, so no table entry past the span is ever
        # dereferenced and every value the body sees is a live page's
        last = pages_ref[row] - 1
        out = []
        for p in range(ppb):
            phys = table_ref[row, jnp.minimum(blk * ppb + p, last)]
            out += [
                pltpu.make_async_copy(
                    src.at[phys], buf.at[slot, p], sem.at[i, slot]
                )
                for i, (src, buf) in enumerate(zip(hbm, bufs))
            ]
        return out

    def start(row, blk, slot):
        for c in copies(row, blk, slot):
            c.start()

    @pl.when(b == 0)
    def _first_row():
        slot_ref[0] = 0
        start(0, 0, 0)

    _init_state(m_ref, l_ref, acc_ref)
    q = q_ref[0].astype(jnp.float32)
    pos = pos_ref[b]
    n_blocks = (pages_ref[b] + ppb - 1) // ppb
    first = slot_ref[0]

    def block(i, carry):
        slot = (first + i) % 2

        # fetch the row's next block, or the next row's first block, while
        # this one is computed
        @pl.when(i + 1 < n_blocks)
        def _next_block():
            start(b, i + 1, 1 - slot)

        @pl.when((i + 1 == n_blocks) & (b + 1 < n_rows))
        def _next_row():
            start(b + 1, 0, 1 - slot)

        for c in copies(b, i, slot):
            c.wait()
        k = bufs[0][slot].astype(jnp.float32)      # (ppb, page, Hkv, hd)
        v = bufs[1][slot].astype(jnp.float32)
        if deq is not None:
            # ``quantize.kv_dequant`` bitwise: q·s in f32, cast
            rows = (ppb, page * k.shape[2], k.shape[3])
            k = (k.reshape(rows) * bufs[2][slot][..., None]).astype(deq)
            v = (v.reshape(rows) * bufs[3][slot][..., None]).astype(deq)
        shape = (ck,) + bufs[0].shape[-2:]
        _attend_block(
            q,
            k.astype(jnp.float32).reshape(shape),
            v.astype(jnp.float32).reshape(shape),
            _ring_valid(pos, i * ck, ck, cap, window),
            m_ref, l_ref, acc_ref, scale,
        )
        return carry

    jax.lax.fori_loop(0, n_blocks, block, 0)
    slot_ref[0] = (first + n_blocks) % 2
    _write_out(o_ref, l_ref, acc_ref)


def _table_walk(
    q, k_pool, v_pool, pos_b, pages, table, *, window, k_scale, v_scale,
    interpret,
) -> jax.Array:
    """Grid (B,): each row walks its ``ceil(pages[b] / ppb)`` live blocks
    with page DMAs from the HBM pool, double-buffered."""
    b, hkv, g, hd = q.shape
    n_pool, pg = k_pool.shape[:2]
    t_w = table.shape[1]
    ppb = pages_per_block(t_w, pg, hkv, hd, q.dtype)
    quant = k_scale is not None
    kernel = functools.partial(
        _table_kernel, page=pg, ppb=ppb, cap=t_w * pg, window=window,
        scale=hd**-0.5, deq=q.dtype if quant else None,
    )
    pools = [k_pool, v_pool]
    if quant:
        pools += [s.reshape(n_pool, pg * hkv) for s in (k_scale, v_scale)]
    bufs = [pltpu.VMEM((2, ppb) + x.shape[1:], x.dtype) for x in pools]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b,),
        in_specs=[pl.BlockSpec((1, hkv, g, hd), lambda b_, *_: (b_, 0, 0, 0))]
        + [pl.BlockSpec(memory_space=pl.ANY)] * len(pools),
        out_specs=pl.BlockSpec((1, hkv, g, hd), lambda b_, *_: (b_, 0, 0, 0)),
        scratch_shapes=bufs + [
            pltpu.SemaphoreType.DMA((len(pools), 2)),
            pltpu.SMEM((1,), jnp.int32),
        ] + _state_scratch(hkv, g, hd),
    )
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((b, hkv, g, hd), q.dtype),
        grid_spec=grid_spec,
        # rows run in order: each starts the next row's first block
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)
        ),
        interpret=interpret,
        name="paged_decode",
    )(pos_b, pages, table, q, *pools)


def _table_grid(
    q, k_pool, v_pool, pos_b, pages, table, *, window, k_scale, v_scale,
    interpret,
) -> jax.Array:
    """Grid (B, T), one page a step: a dead page skips its DMA (the index
    map clamps to the last live page before the table lookup) and its body
    (``pl.when``), but still costs a grid step."""
    b, hkv, g, hd = q.shape
    pg = k_pool.shape[1]
    t_w = table.shape[1]
    quant = k_scale is not None
    kernel = functools.partial(
        _paged_kernel, page=pg, cap=t_w * pg, window=window, scale=hd**-0.5,
        deq=q.dtype if quant else None,
    )

    def kv_map(b_, j, pos_ref, pages_ref, table_ref):
        # clamp BEFORE the table lookup: an unallocated table entry (by
        # convention 0, the reserved scratch page) is never the target of
        # a fresh DMA for a live computation
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
