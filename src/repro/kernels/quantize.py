"""Pallas TPU kernels: per-block symmetric int8 quantization (§3.2 uplink).

Two entry points over (nb, 256) fp32 rows:
* ``int8_encode`` — (q int8, scale fp32/row): what actually crosses the
  cross-cloud link (1 byte/elem + 4 bytes/row ≈ 3.98× compression).
* ``int8_roundtrip`` — fused quantize→dequantize: the lossy-channel form the
  jitted sync step consumes (no int8 materialization in HBM).

Both are single-pass VPU tiles: row max-abs reduce → scale → round/clip.
Tile (8, 256) as in topk_compress; the op is memory-bound.

Row counts need not be multiples of the tile: inputs are zero-padded to the
next ROWS multiple internally and the outputs sliced back, so page-shaped
callers (e.g. int8 KV pools) quantize without reshaping. ``kv_quant`` /
``kv_dequant`` expose the same per-row scheme as plain jnp over an arbitrary
trailing axis — the form the paged engine's int8 KV cache writes use inside
its jitted steps (per token-slot, per kv-head scales)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.backend import resolve_interpret

ROWS = 8
BLOCK = 256
EPS = 1e-12


def kv_quant(x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Symmetric int8 over the LAST axis: (..., d) → (q int8, scale f32 (...)).

    Exactly the ``_encode_kernel`` row math (max-abs/127 scale, round, clip)
    applied per trailing vector — the int8 KV pool stores one scale per
    token-slot per kv-head this way."""
    xf = x.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(xf), axis=-1) / 127.0, EPS)
    q = jnp.clip(jnp.round(xf / scale[..., None]), -127.0, 127.0)
    return q.astype(jnp.int8), scale


def kv_dequant(q: jax.Array, scale: jax.Array, dtype=jnp.float32) -> jax.Array:
    """Inverse of ``kv_quant``: f32 multiply then cast — the kernels'
    in-body dequant reproduces this bitwise."""
    return (q.astype(jnp.float32) * scale[..., None]).astype(dtype)


def _pad_rows(x: jax.Array) -> jax.Array:
    """Zero-pad the row axis to the next ROWS multiple (padding rows
    quantize to q=0 / scale=EPS and are sliced off by the callers)."""
    pad = (-x.shape[0]) % ROWS
    if pad:
        x = jnp.concatenate([x, jnp.zeros((pad, x.shape[1]), x.dtype)])
    return x


def _encode_kernel(x_ref, q_ref, s_ref):
    x = x_ref[...].astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(x), axis=1, keepdims=True) / 127.0, EPS)
    q = jnp.clip(jnp.round(x / scale), -127.0, 127.0)
    q_ref[...] = q.astype(jnp.int8)
    s_ref[...] = scale


def _roundtrip_kernel(x_ref, o_ref):
    x = x_ref[...].astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(x), axis=1, keepdims=True) / 127.0, EPS)
    q = jnp.clip(jnp.round(x / scale), -127.0, 127.0)
    o_ref[...] = (q * scale).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def int8_encode(x: jax.Array, *, interpret: bool | None = None):
    nb, block = x.shape
    assert block == BLOCK
    xp = _pad_rows(x)
    q, s = pl.pallas_call(
        _encode_kernel,
        out_shape=(
            jax.ShapeDtypeStruct((xp.shape[0], block), jnp.int8),
            jax.ShapeDtypeStruct((xp.shape[0], 1), jnp.float32),
        ),
        grid=(xp.shape[0] // ROWS,),
        in_specs=[pl.BlockSpec((ROWS, BLOCK), lambda i: (i, 0))],
        out_specs=(
            pl.BlockSpec((ROWS, BLOCK), lambda i: (i, 0)),
            pl.BlockSpec((ROWS, 1), lambda i: (i, 0)),
        ),
        interpret=resolve_interpret(interpret),
        name="int8_encode",
    )(xp)
    return q[:nb], s[:nb]


@functools.partial(jax.jit, static_argnames=("interpret",))
def int8_roundtrip(
    x: jax.Array, *, interpret: bool | None = None
) -> jax.Array:
    nb, block = x.shape
    assert block == BLOCK
    xp = _pad_rows(x)
    out = pl.pallas_call(
        _roundtrip_kernel,
        out_shape=jax.ShapeDtypeStruct((xp.shape[0], block), x.dtype),
        grid=(xp.shape[0] // ROWS,),
        in_specs=[pl.BlockSpec((ROWS, BLOCK), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((ROWS, BLOCK), lambda i: (i, 0)),
        interpret=resolve_interpret(interpret),
        name="int8_roundtrip",
    )(xp)
    return out[:nb]
