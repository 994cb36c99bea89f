"""Pallas TPU kernels: fused DP-SGD transmit transform (§3.1 security).

Two passes over each update tensor (viewed as (nb, 256) fp32 rows):

1. ``sq_norm`` — tiled Σx² reduction. All grid steps map to the same (1,1)
   output block; TPU grid iteration is sequential per core, so the kernel
   accumulates into the output block across steps (initializing at step 0).
   The host combines per-leaf partials into the global pytree norm.
2. ``clip_noise`` — out = x·scale + σ·noise, fusing the clip rescale and the
   Gaussian perturbation in one HBM round trip (noise is generated upstream
   with jax.random — counter-based RNG on TPU; keeping it outside makes the
   kernel deterministic and testable).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.backend import resolve_interpret

ROWS = 8
BLOCK = 256


def _sq_norm_kernel(x_ref, o_ref):
    @pl.when(pl.program_id(0) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    x = x_ref[...].astype(jnp.float32)
    o_ref[0, 0] += jnp.sum(x * x)


@functools.partial(jax.jit, static_argnames=("interpret",))
def sq_norm(x: jax.Array, *, interpret: bool | None = None) -> jax.Array:
    """(nb, 256) fp32 → () squared L2 norm."""
    nb, block = x.shape
    assert block == BLOCK and nb % ROWS == 0
    out = pl.pallas_call(
        _sq_norm_kernel,
        out_shape=jax.ShapeDtypeStruct((1, 1), jnp.float32),
        grid=(nb // ROWS,),
        in_specs=[pl.BlockSpec((ROWS, BLOCK), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((1, 1), lambda i: (0, 0)),
        interpret=resolve_interpret(interpret),
        name="dp_sq_norm",
    )(x)
    return out[0, 0]


def _clip_noise_kernel(x_ref, scale_ref, noise_ref, o_ref, *, stddev: float):
    x = x_ref[...].astype(jnp.float32)
    s = scale_ref[0, 0]
    o_ref[...] = (x * s + stddev * noise_ref[...]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("stddev", "interpret"))
def clip_noise(
    x: jax.Array, scale: jax.Array, noise: jax.Array, stddev: float,
    *, interpret: bool | None = None,
) -> jax.Array:
    """out = x·scale + stddev·noise. x/noise: (nb, 256); scale: () fp32."""
    nb, block = x.shape
    assert block == BLOCK and nb % ROWS == 0
    return pl.pallas_call(
        functools.partial(_clip_noise_kernel, stddev=stddev),
        out_shape=jax.ShapeDtypeStruct((nb, block), x.dtype),
        grid=(nb // ROWS,),
        in_specs=[
            pl.BlockSpec((ROWS, BLOCK), lambda i: (i, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec((ROWS, BLOCK), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((ROWS, BLOCK), lambda i: (i, 0)),
        interpret=resolve_interpret(interpret),
        name="dp_clip_noise",
    )(x, scale.reshape(1, 1), noise)
