"""Pallas TPU kernel: flash-decode attention over a ring-buffer KV cache.

The long_500k serving shape decodes ONE token against a sliding-window ring
cache; this kernel is that hot path. Online-softmax accumulation over cache
chunks keeps VMEM at O(chunk · Hkv · head_dim):

    grid = (B, C/CK); the last grid axis is the streaming reduction —
    running (m, l, acc) for every kv head live in VMEM scratch across grid
    steps (TPU grid iteration is sequential per core), the output block is
    written on the final chunk.

Every grid step takes ALL kv heads of its chunk: the cache layout is
(B, C, Hkv, hd), and the TPU lowering only accepts blocks whose last two
dims are (8, 128)-aligned or whole, so a block ends in the full
(Hkv, hd). The body swaps the chunk to head-major (Hkv, CK, hd) in VMEM
and runs one batched matmul over heads; GQA blocks all G = H/Hkv query
heads of a kv head into its (G, hd) q tile. The chunk is capped so a
block holds at most ``ROWS_PER_BLOCK`` (token, head) rows, which keeps
wide models (32 kv heads) inside scoped VMEM.

Ring-buffer masking is position arithmetic, not data movement: slot s holds
global position  pos − ((pos mod C) − s) mod C ; valid ⇔ within
[pos−window+1, pos]."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.backend import resolve_interpret

NEG = -2.0**30

# (token, kv-head) rows per k/v block: CK · Hkv <= this
ROWS_PER_BLOCK = 2048


def _init_state(m_ref, l_ref, acc_ref):
    m_ref[...] = jnp.full_like(m_ref, NEG)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)


def _attend_block(q, k, v, valid, m_ref, l_ref, acc_ref, scale):
    """One online-softmax step over a chunk, for every kv head at once.

    q: (Hkv, G, hd) f32; k/v: (CK, Hkv, hd) f32 in cache layout; valid:
    (1, 1, CK) ring mask. Shared by every decode kernel (ring, paged, page
    table, int8), which is what keeps them bitwise equal to each other."""
    k = jnp.swapaxes(k, 0, 1)                       # (Hkv, CK, hd)
    v = jnp.swapaxes(v, 0, 1)
    s = jnp.einsum(
        "hgd,hcd->hgc", q, k, preferred_element_type=jnp.float32
    ) * scale                                       # (Hkv, G, CK)
    s = jnp.where(valid, s, NEG)

    m_prev, l_prev, acc_prev = m_ref[...], l_ref[...], acc_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=2, keepdims=True))
    p = jnp.exp(s - m_new)                          # (Hkv, G, CK)
    alpha = jnp.exp(m_prev - m_new)                 # (Hkv, G, 1)
    l_new = l_prev * alpha + jnp.sum(p, axis=2, keepdims=True)
    pv = jnp.einsum(
        "hgc,hcd->hgd", p, v, preferred_element_type=jnp.float32
    )                                               # (Hkv, G, hd)
    acc_ref[...] = acc_prev * alpha + pv
    m_ref[...], l_ref[...] = m_new, l_new


def _ring_valid(pos, first_slot, ck: int, cap: int, window: int):
    """(1, 1, CK) mask: which ring slots first_slot.. hold live tokens."""
    slots = first_slot + jax.lax.broadcasted_iota(jnp.int32, (1, 1, ck), 2)
    slot_w = pos % cap
    gpos = pos - (slot_w - slots) % cap
    lo = jnp.maximum(pos - (window - 1), 0) if window > 0 else 0
    return (gpos >= lo) & (gpos <= pos)


def _write_out(o_ref, l_ref, acc_ref):
    o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)).astype(
        o_ref.dtype
    )


def _state_scratch(hkv: int, g: int, hd: int) -> list:
    return [
        pltpu.VMEM((hkv, g, 1), jnp.float32),
        pltpu.VMEM((hkv, g, 1), jnp.float32),
        pltpu.VMEM((hkv, g, hd), jnp.float32),
    ]


def _swa_kernel(
    pos_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
    *, ck: int, cap: int, window: int, scale: float,
):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        _init_state(m_ref, l_ref, acc_ref)

    # per-row position: pos_ref is (B, 1) in SMEM; grid axis 0 is the batch
    # row, so each program masks against its own slot's depth (continuous
    # batching runs every row at a different position).
    pos = pos_ref[pl.program_id(0), 0]
    _attend_block(
        q_ref[0].astype(jnp.float32),
        k_ref[0].astype(jnp.float32),
        v_ref[0].astype(jnp.float32),
        _ring_valid(pos, j * ck, ck, cap, window),
        m_ref, l_ref, acc_ref, scale,
    )

    @pl.when(j == (cap // ck) - 1)
    def _finish():
        _write_out(o_ref, l_ref, acc_ref)


def _chunk(cap: int, hkv: int = 1) -> int:
    """Largest chunk of the ladder dividing ``cap`` whose block stays
    within ``ROWS_PER_BLOCK`` (token, head) rows; ``cap`` itself if none."""
    for ck in (512, 256, 128, 64, 32, 16, 8):
        if cap % ck == 0 and cap >= ck and ck * hkv <= ROWS_PER_BLOCK:
            return ck
    return cap


@functools.partial(jax.jit, static_argnames=("window", "interpret"))
def swa_decode(
    q: jax.Array,          # (B, Hkv, G, hd)
    k_cache: jax.Array,    # (B, C, Hkv, hd)
    v_cache: jax.Array,    # (B, C, Hkv, hd)
    pos: jax.Array,        # () or (B,) i32 — tokens already cached per row
    window: int = 0,
    *,
    interpret: bool | None = None,
) -> jax.Array:
    b, hkv, g, hd = q.shape
    cap = k_cache.shape[1]
    ck = _chunk(cap, hkv)
    scale = hd**-0.5
    kernel = functools.partial(
        _swa_kernel, ck=ck, cap=cap, window=window, scale=scale
    )
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((b, hkv, g, hd), q.dtype),
        grid=(b, cap // ck),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, hkv, g, hd), lambda b_, j: (b_, 0, 0, 0)),
            pl.BlockSpec((1, ck, hkv, hd), lambda b_, j: (b_, j, 0, 0)),
            pl.BlockSpec((1, ck, hkv, hd), lambda b_, j: (b_, j, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, hkv, g, hd), lambda b_, j: (b_, 0, 0, 0)),
        scratch_shapes=_state_scratch(hkv, g, hd),
        interpret=resolve_interpret(interpret),
        name="swa_decode",
    )(
        jnp.broadcast_to(jnp.asarray(pos, jnp.int32).reshape(-1, 1), (b, 1)),
        q, k_cache, v_cache,
    )
