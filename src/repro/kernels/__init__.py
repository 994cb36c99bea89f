"""Pallas TPU kernels for the framework's compute hot spots.

Layout: one ``<name>.py`` per kernel (pl.pallas_call + BlockSpec),
``ops.py`` with the jit'd public wrappers (pytree plumbing + kernel/ref
dispatch), ``ref.py`` with the pure-jnp oracles every kernel is tested
against, and ``backend.py`` which picks the mode: kernels compile natively
on the TPU and run in the Pallas interpreter on the CPU backend."""
from repro.kernels.ops import (
    dp_transmit,
    int8_encode_leaf,
    int8_roundtrip_leaf,
    swa_decode_attention,
    topk_sparsify_leaf,
    tree_sq_norm,
)

__all__ = [
    "dp_transmit",
    "int8_encode_leaf",
    "int8_roundtrip_leaf",
    "swa_decode_attention",
    "topk_sparsify_leaf",
    "tree_sq_norm",
]
