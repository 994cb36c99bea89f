"""Jit'd public wrappers around the Pallas kernels.

The wrappers own the layout plumbing the kernels don't: flattening pytrees
into (rows, 256) tiles (with padding), restoring shapes, and dispatching
kernel vs. pure-jnp reference. ``interpret=None`` (the default) lets
``kernels/backend.py`` choose: the Pallas interpreter on the CPU backend, a
native compile on the TPU."""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp

from repro.kernels import dp_clip as _dp
from repro.kernels import paged_decode as _paged
from repro.kernels import quantize as _quant
from repro.kernels import ref as _ref
from repro.kernels import swa_decode as _swa
from repro.kernels import topk_compress as _topk

Pytree = Any

BLOCK = 256
ROWS = 8
TILE = BLOCK * ROWS


def _to_tiles(x: jax.Array) -> tuple[jax.Array, int]:
    flat = x.astype(jnp.float32).ravel()
    n = flat.shape[0]
    pad = (-n) % TILE
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), jnp.float32)])
    return flat.reshape(-1, BLOCK), n


def _from_tiles(t: jax.Array, n: int, shape, dtype) -> jax.Array:
    return t.ravel()[:n].reshape(shape).astype(dtype)


# ----------------------------------------------------------- top-k sparsify
def topk_sparsify_leaf(
    x: jax.Array, ratio: float, *, use_kernel: bool = False,
    interpret: bool | None = None,
) -> jax.Array:
    tiles, n = _to_tiles(x)
    k = max(1, int(round(ratio * BLOCK)))
    if use_kernel:
        out = _topk.topk_sparsify(tiles, k, interpret=interpret)
    else:
        out = _ref.topk_sparsify_ref(tiles, k)
    return _from_tiles(out, n, x.shape, x.dtype)


# ------------------------------------------------------------ int8 channel
def int8_roundtrip_leaf(
    x: jax.Array, *, use_kernel: bool = False, interpret: bool | None = None
) -> jax.Array:
    tiles, n = _to_tiles(x)
    if use_kernel:
        out = _quant.int8_roundtrip(tiles, interpret=interpret)
    else:
        out = _ref.int8_roundtrip_ref(tiles)
    return _from_tiles(out, n, x.shape, x.dtype)


def int8_encode_leaf(
    x: jax.Array, *, use_kernel: bool = False, interpret: bool | None = None
):
    tiles, n = _to_tiles(x)
    if use_kernel:
        return _quant.int8_encode(tiles, interpret=interpret) + (n,)
    return _ref.int8_encode_ref(tiles) + (n,)


# ------------------------------------------------------------------ DP clip
def tree_sq_norm(
    tree: Pytree, *, use_kernel: bool = False, interpret: bool | None = None
) -> jax.Array:
    total = jnp.zeros((), jnp.float32)
    for leaf in jax.tree_util.tree_leaves(tree):
        tiles, _ = _to_tiles(leaf)  # zero-padding does not change Σx²
        if use_kernel:
            total = total + _dp.sq_norm(tiles, interpret=interpret)
        else:
            total = total + _ref.sq_norm_ref(tiles)
    return total


def dp_transmit(
    tree: Pytree,
    key: jax.Array,
    clip_norm: float,
    stddev: float,
    *,
    use_kernel: bool = False,
    interpret: bool | None = None,
) -> Pytree:
    """Fused DP channel: clip the pytree to clip_norm, add N(0, stddev²)."""
    norm = jnp.sqrt(tree_sq_norm(tree, use_kernel=use_kernel, interpret=interpret))
    scale = jnp.minimum(1.0, clip_norm / jnp.maximum(norm, 1e-9))
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    keys = jax.random.split(key, len(leaves))
    out = []
    for leaf, k in zip(leaves, keys):
        tiles, n = _to_tiles(leaf)
        noise = jax.random.normal(k, tiles.shape, jnp.float32)
        if use_kernel:
            y = _dp.clip_noise(tiles, scale, noise, stddev, interpret=interpret)
        else:
            y = _ref.clip_noise_ref(tiles, scale, noise, stddev)
        out.append(_from_tiles(y, n, leaf.shape, leaf.dtype))
    return jax.tree_util.tree_unflatten(treedef, out)


# ------------------------------------------------------- swa decode attention
def swa_decode_attention(
    q: jax.Array,
    k_cache: jax.Array,
    v_cache: jax.Array,
    pos: jax.Array,
    window: int = 0,
    *,
    use_kernel: bool = False,
    paged: bool = False,
    table: jax.Array | None = None,
    k_scale: jax.Array | None = None,
    v_scale: jax.Array | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """(B, Hkv, G, hd) x ring cache (B, C, Hkv, hd) → (B, Hkv, G, hd).

    ``pos`` is () for a lockstep batch or (B,) for per-slot positions
    (continuous-batching engine). ``paged=True`` selects the length-aware
    paged variant (kernels/paged_decode.py): rows far from ring wrap skip
    dead KV pages entirely — bitwise-identical output, less work.

    ``table`` switches to page-table mode: k/v are a SHARED physical pool
    (P, page, Hkv, hd) and ``table`` (B, T) maps each row's logical pages
    into it (capacity = T·page). The kernel reads the pool through
    scalar-prefetched table rows; the reference path gathers the pages
    into contiguous rings first — both bitwise-match the ring semantics.

    ``k_scale``/``v_scale`` (with ``table``) select the int8-pool variant:
    pages are int8 with (P, page, Hkv) f32 scales; the kernel dequantizes
    in-body and the reference dequantizes the pool before gathering —
    bitwise the same value set either way."""
    if table is not None:
        if use_kernel:
            return _paged.paged_decode(
                q, k_cache, v_cache, pos, window, table=table,
                k_scale=k_scale, v_scale=v_scale, interpret=interpret,
            )
        if k_scale is not None:
            return _ref.paged_table_decode_int8_ref(
                q, k_cache, v_cache, k_scale, v_scale, pos, table, window
            )
        return _ref.paged_table_decode_ref(q, k_cache, v_cache, pos, table, window)
    assert k_scale is None and v_scale is None, (
        "int8 pool scales require page-table mode"
    )
    if use_kernel:
        if paged:
            return _paged.paged_decode(
                q, k_cache, v_cache, pos, window, interpret=interpret
            )
        return _swa.swa_decode(q, k_cache, v_cache, pos, window, interpret=interpret)
    if paged:
        return _ref.paged_decode_ref(q, k_cache, v_cache, pos, window)
    return _ref.swa_decode_ref(q, k_cache, v_cache, pos, window)


# -------------------------------------------------------- flash prefill attn
def flash_prefill_attention(
    q: jax.Array, k: jax.Array, v: jax.Array, *, causal: bool = True,
    window: int = 0, use_kernel: bool = False, interpret: bool | None = None,
) -> jax.Array:
    """Causal GQA flash attention for training/prefill (see
    kernels/flash_prefill.py). q: (B,S,Hkv,G,hd); k/v: (B,T,Hkv,hd)."""
    from repro.kernels import flash_prefill as _fp

    if use_kernel:
        return _fp.flash_prefill(
            q, k, v, causal=causal, window=window, interpret=interpret
        )
    return _ref.flash_prefill_ref(q, k, v, causal=causal, window=window)


# ------------------------------------------------------- suffix prefill attn
def suffix_prefill_attention(
    q: jax.Array,
    k_suf: jax.Array,
    v_suf: jax.Array,
    pool_k: jax.Array,
    pool_v: jax.Array,
    table: jax.Array,
    starts: jax.Array,
    *,
    prefix_width: int,
    pool_k_scale: jax.Array | None = None,
    pool_v_scale: jax.Array | None = None,
    use_kernel: bool = False,
    interpret: bool | None = None,
) -> jax.Array:
    """Suffix prefill over a cached prefix held in a shared page pool (see
    kernels/flash_suffix_prefill.py). q: (n,S,Hkv,G,hd) roped at absolute
    positions starts[r]+i; k_suf/v_suf: (n,S,Hkv,hd); pool: (P,page,Hkv,hd);
    table: (n,T); starts: (n,). ``prefix_width`` statically bounds the pages
    streamed per row (engine buckets max(starts) up a pow2 ladder). The
    reference path is the displaced gather-concat attend — the house-rules
    oracle for the kernel. ``pool_k_scale``/``pool_v_scale`` select the
    int8-pool variant (in-body dequant in the kernel, dequantized-pool
    gather in the reference)."""
    if use_kernel:
        from repro.kernels import flash_suffix_prefill as _fsp

        return _fsp.suffix_prefill(
            q, k_suf, v_suf, pool_k, pool_v, table, starts,
            prefix_width=prefix_width, pool_k_scale=pool_k_scale,
            pool_v_scale=pool_v_scale, interpret=interpret,
        )
    if pool_k_scale is not None:
        return _ref.suffix_prefill_int8_ref(
            q, k_suf, v_suf, pool_k, pool_v, pool_k_scale, pool_v_scale,
            table, starts, prefix_width=prefix_width,
        )
    return _ref.suffix_prefill_ref(
        q, k_suf, v_suf, pool_k, pool_v, table, starts,
        prefix_width=prefix_width,
    )
