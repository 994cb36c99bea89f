"""Pallas TPU kernel: flash attention for training/prefill (causal GQA,
optional sliding window).

Why this is the §Roofline hot spot: the jnp chunked-attention path
materializes the (B, Hkv, G, q_chunk, T) probability tensor in HBM between
the two matmuls — at prefill_32k that is the dominant memory term for every
attention architecture (≈100 TB/step/device on the 12B configs). Flash
tiling keeps the running softmax state in VMEM so HBM traffic drops to
O(Q + K + V + O).

Layout:
    grid = (B, Hkv, S/BQ, T/BK); the LAST grid axis streams over KV blocks
    (TPU grid iteration is sequential per core), carrying (m, l, acc) in
    VMEM scratch. The kernel works head-major: the wrapper transposes q to
    (B, Hkv, S·G, hd) and k/v to (B, Hkv, T, hd), so every block ends in a
    (rows, hd) tile the TPU lowering accepts (a block of one head out of
    the token-major (…, Hkv, hd) layout would end in (1, hd), which it
    refuses). One q tile blocks all G = H/Hkv query heads of one KV head:
    the MXU sees (BQ·G, hd) × (hd, BK) — both dims ≥128 for
    hardware-aligned shapes at hd=128, BK=128.

Causality is position arithmetic on block indices; fully-masked (future)
KV blocks are skipped with ``pl.when`` so the streaming pass does no MXU
work above the diagonal (the HBM prefetch of those blocks is hidden by the
sequential grid)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.backend import resolve_interpret

NEG = -2.0**30


def _flash_kernel(
    q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
    *, bq: int, bk: int, n_k: int, g: int,
    causal: bool, window: int, scale: float,
):
    i = pl.program_id(2)          # query block
    j = pl.program_id(3)          # kv block (streaming reduction axis)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # block-level skip: the whole KV block is in the future of the whole
    # q block (or beyond the window's past edge)
    q_lo = i * bq
    q_hi = q_lo + bq - 1
    k_lo = j * bk
    k_hi = k_lo + bk - 1
    if causal:
        live = k_lo <= q_hi
        if window > 0:
            live = live & (k_hi >= q_lo - (window - 1))
    else:
        live = jnp.asarray(True)

    @pl.when(live)
    def _block():
        q = q_ref[0, 0].astype(jnp.float32)            # (BQ·G, hd)
        k = k_ref[0, 0].astype(jnp.float32)            # (BK, hd)
        v = v_ref[0, 0].astype(jnp.float32)

        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale                                       # (BQ·G, BK)

        if causal:
            s = jnp.where(
                causal_mask(q_lo, k_lo, (bq * g, bk), g, window), s, NEG
            )

        m_prev, l_prev, acc_prev = m_ref[...], l_ref[...], acc_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_new = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
        pv = jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        acc_new = acc_prev * alpha + pv
        m_ref[...], l_ref[...], acc_ref[...] = m_new, l_new, acc_new

    @pl.when(j == n_k - 1)
    def _finish():
        out = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = out.astype(o_ref.dtype)


def causal_mask(q_lo, k_lo, shape, g: int, window: int = 0):
    """Causal (optionally windowed) mask over a score tile whose rows are
    (query, group) pairs flattened query-major — row r is query
    ``q_lo + r // g`` — and whose last axis is keys ``k_lo + c``."""
    rows = jax.lax.broadcasted_iota(jnp.int32, shape, len(shape) - 2)
    qpos = q_lo + (rows if g == 1 else rows // g)
    kpos = k_lo + jax.lax.broadcasted_iota(jnp.int32, shape, len(shape) - 1)
    mask = qpos >= kpos
    if window > 0:
        mask &= (qpos - kpos) < window
    return mask


def _block_size(n: int, target: int) -> int:
    for b in (target, target // 2, target // 4, 64, 32, 16, 8):
        if b and n % b == 0 and n >= b:
            return b
    return n


def to_head_major(x: jax.Array) -> jax.Array:
    """(B, S, Hkv, [G,] hd) → (B, Hkv, S·G, hd): the kernels' q/k/v layout."""
    x = jnp.moveaxis(x, 2, 1)
    return x.reshape(*x.shape[:2], -1, x.shape[-1])


@functools.partial(
    jax.jit, static_argnames=("causal", "window", "interpret", "bq", "bk")
)
def flash_prefill(
    q: jax.Array,          # (B, S, Hkv, G, hd)
    k: jax.Array,          # (B, T, Hkv, hd)
    v: jax.Array,          # (B, T, Hkv, hd)
    *,
    causal: bool = True,
    window: int = 0,
    bq: int = 256,
    bk: int = 256,
    interpret: bool | None = None,
) -> jax.Array:
    """Returns (B, S, Hkv, G, hd) attention output, fp32-accumulated."""
    b, s, hkv, g, hd = q.shape
    t = k.shape[1]
    bq = _block_size(s, bq)
    bk = _block_size(t, bk)
    scale = hd**-0.5
    kernel = functools.partial(
        _flash_kernel, bq=bq, bk=bk, n_k=t // bk, g=g,
        causal=causal, window=window, scale=scale,
    )
    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((b, hkv, s * g, hd), q.dtype),
        grid=(b, hkv, s // bq, t // bk),
        in_specs=[
            pl.BlockSpec((1, 1, bq * g, hd), lambda b_, h, i, j: (b_, h, i, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda b_, h, i, j: (b_, h, j, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda b_, h, i, j: (b_, h, j, 0)),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, bq * g, hd), lambda b_, h, i, j: (b_, h, i, 0)
        ),
        scratch_shapes=[
            pltpu.VMEM((bq * g, 1), jnp.float32),
            pltpu.VMEM((bq * g, 1), jnp.float32),
            pltpu.VMEM((bq * g, hd), jnp.float32),
        ],
        interpret=resolve_interpret(interpret),
        name="flash_prefill",
    )(to_head_major(q), to_head_major(k), to_head_major(v))
    return jnp.moveaxis(out.reshape(b, hkv, s, g, hd), 1, 2)
