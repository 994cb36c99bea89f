"""Pallas TPU kernel: suffix prefill over a shared paged KV pool — flash
attention for the PREFIX-SHARING admission path.

A prefix-cache hit admits a request whose first ``starts[r]`` tokens are
already resident in shared pool pages mapped by the row's page table; only
the uncached suffix runs through prefill. The jnp production path gathers
EVERY table page into a contiguous (n, T·page, Hkv, hd) ring row in HBM and
concatenates the suffix k/v before one full-softmax attend — the gather
alone moves ``table_width × page_size`` lanes per row per layer regardless
of how short the cached prefix is, and the (n, Hkv, G, S, T·page+S) score
tensor is materialized on top.

This kernel removes both terms with the scalar-prefetched table-row idiom
proven in ``kernels/paged_decode.py``: the last grid axis streams

    j in [0, W)        — the row's cached PREFIX pages, read directly from
                         the pool at ``table[b, j]`` (no gather); a page at
                         or beyond the row's live prefix (``j >= ceil(
                         starts[b]/page)``) is skipped with ``pl.when`` and
                         its DMA clamps to the last live page (no fresh
                         traffic — the paged-decode trick);
    j in [W, W + S/BK) — the suffix's own k/v blocks, standard causal
                         flash tiling (``kernels/flash_prefill.py``),

carrying the online-softmax state (m, l, acc) of every kv head in VMEM
scratch. Each grid step takes ALL kv heads: a pool page is (page, Hkv, hd)
and the TPU lowering only accepts blocks ending in the whole (Hkv, hd), so
the body swaps each page to head-major in VMEM and runs one batched matmul
over heads; q and the suffix k/v arrive head-major ((n, Hkv, S·G, hd),
transposed by the wrapper), with block sizes capped so a block holds at
most ``ROWS_PER_BLOCK`` (row, head) pairs. ``W`` is a
STATIC prefix width in pages — the engine buckets ``max(starts)`` up a
pow2 ladder (``launch/engine.py::bucket_pages``) so compile counts stay
gated exactly like the (width, length) shape buckets.

Masking: a prefix lane at ring slot ``c`` is live iff ``c < starts[b]``
(windowless, non-wrapping ring: slot c holds global position c). Causality
is implied — every query sits at an absolute position ``>= starts[b]`` —
so no per-query prefix mask is needed. Suffix blocks mask causally in
LOCAL coordinates, identical to ``flash_prefill``. The streaming order
[prefix pages | suffix blocks] matches the jnp path's concat order, so the
kernel is the flash reassociation of the same reduction; tests pin it
allclose against ``ref.suffix_prefill_ref`` and the engine pins greedy
tokens bitwise through ``use_kernel=True``.

int8 pool mode (``pool_k_scale``/``pool_v_scale`` passed): prefix pages are
int8 with (P, page, Hkv) f32 scales riding the same table indirection;
the prefix phase dequantizes in-body to the q dtype (bitwise
``quantize.kv_dequant``) before the unchanged flash math, while the fresh
suffix k/v stay fp — bitwise equal to the fp kernel over the
jnp-dequantized pool.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.backend import resolve_interpret
from repro.kernels.flash_prefill import _block_size, causal_mask, to_head_major
from repro.kernels.swa_decode import ROWS_PER_BLOCK

NEG = -2.0**30


def _suffix_kernel(
    starts_ref, pp_ref, table_ref,
    q_ref, ks_ref, vs_ref, pk_ref, pv_ref,
    *rest,
    bq: int, bk: int, w: int, page: int, n_total: int, g: int,
    scale: float, deq=None,
):
    # rest = ([pks_ref, pvs_ref,] o_ref, m_ref, l_ref, acc_ref) — with
    # ``deq`` set (int8 pool mode) the POOL pages are int8 and pks/pvs hold
    # one f32 scale per page slot per kv-head; the fresh suffix k/v stay fp.
    if deq is not None:
        pks_ref, pvs_ref, o_ref, m_ref, l_ref, acc_ref = rest
    else:
        o_ref, m_ref, l_ref, acc_ref = rest
    b = pl.program_id(0)
    i = pl.program_id(1)          # query block
    j = pl.program_id(2)          # streaming axis: W prefix pages, then
    #                               S/BK suffix blocks

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def _update(k, v, mask):
        # k/v: (Hkv, K, hd) f32 head-major; mask broadcasts to (Hkv, R, K)
        q = q_ref[0].astype(jnp.float32)                 # (Hkv, BQ·G, hd)
        s = jnp.einsum(
            "hrd,hkd->hrk", q, k, preferred_element_type=jnp.float32
        ) * scale
        s = jnp.where(mask, s, NEG)
        m_prev, l_prev, acc_prev = m_ref[...], l_ref[...], acc_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=2, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_new = l_prev * alpha + jnp.sum(p, axis=2, keepdims=True)
        pv = jnp.einsum(
            "hrk,hkd->hrd", p, v, preferred_element_type=jnp.float32
        )
        acc_new = acc_prev * alpha + pv
        m_ref[...], l_ref[...], acc_ref[...] = m_new, l_new, acc_new

    # ---- prefix phase: stream the row's live cached pages via the table.
    # A dead page (j >= live prefix pages) does no MXU work; its DMA
    # re-read the last live page (index-map clamp), never fresh traffic.
    @pl.when((j < w) & (j < pp_ref[b]))
    def _prefix_block():
        k = pk_ref[0]                                    # (page, Hkv, hd)
        v = pv_ref[0]
        if deq is not None:
            # in-body dequant, bitwise ``kv_dequant(..., dtype=deq)``
            k = (k.astype(jnp.float32) * pks_ref[0][:, :, None]).astype(deq)
            v = (v.astype(jnp.float32) * pvs_ref[0][:, :, None]).astype(deq)
        # ring slot c holds global position c (windowless, no wrap); lanes
        # at/after the row's start hold no prefix. Causality is implied:
        # every query position is >= starts[b] > any live prefix lane.
        kpos = j * page + jax.lax.broadcasted_iota(jnp.int32, (1, 1, page), 2)
        _update(
            jnp.swapaxes(k.astype(jnp.float32), 0, 1),
            jnp.swapaxes(v.astype(jnp.float32), 0, 1),
            kpos < starts_ref[b],
        )

    # ---- suffix phase: standard causal flash tiling in LOCAL suffix
    # coordinates (absolute = starts[b] + local on both sides, so the
    # offset cancels out of the causal comparison).
    jj = j - w
    q_lo = i * bq
    q_hi = q_lo + bq - 1
    k_lo = jj * bk

    @pl.when((j >= w) & (k_lo <= q_hi))
    def _suffix_block():
        _update(
            ks_ref[0].astype(jnp.float32),               # (Hkv, BK, hd)
            vs_ref[0].astype(jnp.float32),
            causal_mask(q_lo, k_lo, (1, bq * g, bk), g),
        )

    @pl.when(j == n_total - 1)
    def _finish():
        out = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = out.astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("prefix_width", "bq", "bk", "interpret")
)
def suffix_prefill(
    q: jax.Array,        # (n, S, Hkv, G, hd) — roped at starts[r] + i
    k_suf: jax.Array,    # (n, S, Hkv, hd) suffix keys (rotated)
    v_suf: jax.Array,    # (n, S, Hkv, hd)
    pool_k: jax.Array,   # (P, page, Hkv, hd) shared physical page pool
    pool_v: jax.Array,   # (P, page, Hkv, hd)
    table: jax.Array,    # (n, T) i32 — row r's logical page j lives at
    #                      pool page table[r, j]
    starts: jax.Array,   # (n,) i32 — cached prefix tokens per row
    *,
    prefix_width: int,   # STATIC pages streamed per row (bucketed
    #                      ceil(max(starts)/page); must cover every row)
    pool_k_scale: jax.Array | None = None,  # (P, page, Hkv) f32 — int8 pool
    pool_v_scale: jax.Array | None = None,
    bq: int = 256,
    bk: int = 256,
    interpret: bool | None = None,
) -> jax.Array:
    """Returns (n, S, Hkv, G, hd) attention output, fp32-accumulated."""
    n, s, hkv, g, hd = q.shape
    page = pool_k.shape[1]
    t_w = table.shape[1]
    w = min(prefix_width, t_w)
    assert w >= 1, f"prefix_width must be >= 1, got {prefix_width}"
    quant = pool_k_scale is not None
    assert quant == (pool_v_scale is not None), "need both or neither scale"
    # every step holds all kv heads: cap the (row, head) pairs per block
    bq = _block_size(s, min(bq, max(8, ROWS_PER_BLOCK // (hkv * g))))
    bk = _block_size(s, min(bk, max(8, ROWS_PER_BLOCK // hkv)))
    scale = hd**-0.5
    n_total = w + s // bk

    starts = jnp.asarray(starts, jnp.int32).reshape(-1)
    # live prefix pages per row; rows beyond the static width were bucketed
    # wrong by the caller — clip keeps the kernel memory-safe regardless
    pp = jnp.clip(-(-starts // page), 0, w)
    table = jnp.asarray(table, jnp.int32)

    kernel = functools.partial(
        _suffix_kernel, bq=bq, bk=bk, w=w, page=page, n_total=n_total,
        g=g, scale=scale, deq=q.dtype if quant else None,
    )

    def q_map(b, i, j, *_):
        return (b, 0, i, 0)

    def suf_map(b, i, j, *_):
        # prefix-phase steps clamp to suffix block 0: already resident,
        # no fresh DMA (the body never touches it before j reaches w)
        return (b, 0, jnp.maximum(j - w, 0), 0)

    def live_page(b, j, pp_ref, table_ref):
        # page-table indirection with the paged-decode clamp: suffix-phase
        # steps and dead prefix pages re-read the last live page (clamp
        # BEFORE the table lookup so an unallocated entry — scratch page 0
        # by convention — is never the target of a live-block DMA)
        jp = jnp.minimum(jnp.minimum(j, w - 1), pp_ref[b] - 1)
        return table_ref[b, jnp.maximum(jp, 0)]

    def pool_map(b, i, j, starts_ref, pp_ref, table_ref):
        return (live_page(b, j, pp_ref, table_ref), 0, 0, 0)

    def pool_scale_map(b, i, j, starts_ref, pp_ref, table_ref):
        return (live_page(b, j, pp_ref, table_ref), 0, 0)

    in_specs = [
        pl.BlockSpec((1, hkv, bq * g, hd), q_map),
        pl.BlockSpec((1, hkv, bk, hd), suf_map),
        pl.BlockSpec((1, hkv, bk, hd), suf_map),
        pl.BlockSpec((1, page, hkv, hd), pool_map),
        pl.BlockSpec((1, page, hkv, hd), pool_map),
    ]
    inputs = [
        to_head_major(q), to_head_major(k_suf), to_head_major(v_suf),
        pool_k, pool_v,
    ]
    if quant:
        in_specs += [
            pl.BlockSpec((1, page, hkv), pool_scale_map),
            pl.BlockSpec((1, page, hkv), pool_scale_map),
        ]
        inputs += [pool_k_scale, pool_v_scale]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(n, s // bq, n_total),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, hkv, bq * g, hd), q_map),
        scratch_shapes=[
            pltpu.VMEM((hkv, bq * g, 1), jnp.float32),
            pltpu.VMEM((hkv, bq * g, 1), jnp.float32),
            pltpu.VMEM((hkv, bq * g, hd), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((n, hkv, s * g, hd), q.dtype),
        grid_spec=grid_spec,
        interpret=resolve_interpret(interpret),
        name="suffix_prefill",
    )(starts, pp, table, *inputs)
    return jnp.moveaxis(out.reshape(n, hkv, s, g, hd), 1, 2)
