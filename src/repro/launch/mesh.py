"""Production meshes + parameter/state/input sharding rules.

Mesh axes:
    pod   — the cross-cloud boundary (federated replicas; slow DCN links)
    data  — intra-cloud data parallelism (+ FSDP/ZeRO param sharding)
    model — intra-cloud tensor/expert parallelism

Parameter sharding is rule-based on leaf path names (MaxText-style): every
architecture uses the same names for analogous weights (wq/wk/wv/wo,
w_gate/w_up/w_down, tok/unembed, router, ...), so one rule table covers all
10 archs. Rules only assign an axis when it divides the dimension; otherwise
the dim stays replicated (e.g. kv heads < model-axis size under GQA)."""
from __future__ import annotations

import functools
import re
from typing import Any

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig

Pytree = Any


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def make_sim_mesh(n_clouds: int = 1) -> Mesh:
    """One cloud per device on a ``pod``-only mesh (on CPU this needs the
    host device override). The axis is Auto: the federated step shards it
    through ``vmap(spmd_axis_name="pod")`` and lets XLA propagate."""
    n = len(jax.devices())
    if n < n_clouds:
        raise ValueError(f"need {n_clouds} devices, have {n}")
    return jax.make_mesh(
        (n_clouds,), ("pod",), axis_types=(jax.sharding.AxisType.Auto,)
    )


def axis_size(mesh, name: str) -> int:
    """Works for both concrete Mesh and AbstractMesh."""
    shape = dict(mesh.shape)
    return int(shape.get(name, 1))


def _fits(dim: int, mesh: Mesh, axis) -> bool:
    if axis is None:
        return True
    if isinstance(axis, tuple):
        size = int(np.prod([axis_size(mesh, a) for a in axis]))
    else:
        size = axis_size(mesh, axis)
    return size > 1 and dim % size == 0


# --------------------------------------------------------------- param rules
# (regex on the leaf path, rule) — first match wins. The rule maps
# dimension-role → axis; `_spec_for` instantiates it against the leaf shape.
#   "last"/-1 etc. index dims from the END so stacked layer/period/cloud
#   leading dims never shift the rule.
_PARAM_RULES: list[tuple[str, dict[int, str]]] = [
    # embeddings: vocab over model (megatron vocab-parallel)
    (r"embed/tok$", {-2: "model", -1: "fsdp"}),
    (r"embed/unembed$", {-1: "model", -2: "fsdp"}),
    (r"router$", {-1: None}),
    # attention: output-feature dim over model (column parallel), input dim
    # of the out-projection over model (row parallel)
    (r"(attn|xattn)/(wq|wk|wv)$", {-1: "model", -2: "fsdp"}),
    (r"(attn|xattn)/wo$", {-2: "model", -1: "fsdp"}),
    # gated MLPs (dense, griffin, whisper-plain): column/row parallel
    (r"(ffn|mlp)/(w_gate|w_up)$", {-1: "model", -2: "fsdp"}),
    (r"(ffn|mlp)/w_down$", {-2: "model", -1: "fsdp"}),
    # griffin local-attention blocks keep attention weights under mix/
    (r"mix/(wq|wk|wv)$", {-1: "model", -2: "fsdp"}),
    (r"mix/wo$", {-2: "model", -1: "fsdp"}),
    # griffin recurrent block
    (r"mix/(w_x|w_y)$", {-1: "model", -2: "fsdp"}),
    (r"mix/w_out$", {-2: "model", -1: "fsdp"}),
    (r"mix/conv_w$", {-1: "model"}),
    (r"mix/(gate_r|gate_i)$", {}),          # block-diag per head: replicate
    # xLSTM blocks
    (r"blk/w_up$", {-1: "model", -2: "fsdp"}),
    (r"blk/(wq|wk|wv)$", {-1: "model", -2: "fsdp"}),
    (r"blk/(w_i|w_f)$", {-2: "fsdp"}),
    (r"blk/w_down$", {-2: "model", -1: "fsdp"}),
    (r"blk/ff_up$", {-1: "model", -2: "fsdp"}),
    (r"blk/ff_down$", {-2: "model", -1: "fsdp"}),
    (r"blk/conv_w$", {-1: "model"}),
    # vlm projector
    (r"projector/w$", {-1: "model"}),
]


def _leaf_path(path) -> str:
    return "/".join(str(getattr(p, "key", getattr(p, "idx", p))) for p in path)


def _apply_rule(rule: dict[int, str], shape: tuple, fsdp_axis, mesh: Mesh) -> P:
    axes: list = [None] * len(shape)
    for rel_dim, axis_name in rule.items():
        dim = len(shape) + rel_dim if rel_dim < 0 else rel_dim
        if dim < 0 or dim >= len(shape):
            continue
        axis = fsdp_axis if axis_name == "fsdp" else axis_name
        if axis is not None and _fits(shape[dim], mesh, axis):
            axes[dim] = axis
    return P(*axes)


def param_spec(path: str, shape: tuple, cfg: ModelConfig, mesh: Mesh) -> P:
    """PartitionSpec for one parameter leaf (no pod dim — caller prepends)."""
    if cfg.pure_dp:
        return P()  # replicate everything; batch covers both axes
    fsdp_axis = "data" if cfg.fsdp else None
    if cfg.arch_type == "moe":
        # expert-parallel MoE weights: (L, E, D, F)/(L, E, F, D)
        if re.search(r"ffn/(w_gate|w_up)$", path):
            return _apply_rule({-3: "model", -1: "fsdp"}, shape, fsdp_axis, mesh)
        if re.search(r"ffn/w_down$", path):
            return _apply_rule({-3: "model", -2: "fsdp"}, shape, fsdp_axis, mesh)
    for pat, rule in _PARAM_RULES:
        if re.search(pat, path):
            return _apply_rule(rule, shape, fsdp_axis, mesh)
    return P()  # norms, biases, scalars: replicated


def params_pspec_tree(params_shapes: Pytree, cfg: ModelConfig, mesh: Mesh, prefix: tuple = ()) -> Pytree:
    """Pytree of PartitionSpecs matching ``params_shapes``."""

    def spec_fn(path, leaf):
        return P(*prefix, *param_spec(_leaf_path(path), leaf.shape, cfg, mesh))

    return jax.tree_util.tree_map_with_path(spec_fn, params_shapes)


def shardings_from_pspecs(pspecs: Pytree, mesh: Mesh) -> Pytree:
    return jax.tree_util.tree_map(lambda s: NamedSharding(mesh, s), pspecs)


# ------------------------------------------------------------ non-param state
def federated_state_pspecs(state_shapes: Pytree, cfg: ModelConfig, mesh: Mesh) -> Pytree:
    """Specs for ``FederatedTrainer`` state on a mesh with a ``pod`` axis:
    every per-cloud leaf (params, AdamW moments, error feedback, counts)
    splits its leading cloud axis over ``pod``; the global model follows
    the intra-pod parameter rules; step and rng replicate."""
    params = state_shapes["global"]["params"]
    p_pspec = params_pspec_tree(params, cfg, mesh)
    pod_p = params_pspec_tree(params, cfg, mesh, prefix=("pod",))
    specs: dict[str, Any] = {
        "clouds": {
            "params": pod_p,
            "opt": {"m": pod_p, "v": pod_p, "count": P("pod")},
        },
        "global": {
            "params": p_pspec,
            "outer": jax.tree_util.tree_map(
                lambda _: P(), state_shapes["global"]["outer"]
            ),
        },
        "sample_counts": P("pod"),
        "loss_accum": P("pod"),
        "step": P(),
        "rng": P(),
    }
    if "ef" in state_shapes:
        specs["ef"] = pod_p
    return specs


def opt_pspec_tree(opt_shapes: Pytree, param_pspecs: Pytree, mesh: Mesh) -> Pytree:
    """AdamW m/v inherit the parameter sharding (ZeRO: fsdp covers them)."""
    return {
        "m": param_pspecs,
        "v": param_pspecs,
        "count": P(),
    }


def batch_pspec(
    batch_shapes: Pytree, mesh: Mesh, *, pod_stacked: bool = False,
    pure_dp: bool = False,
) -> Pytree:
    """tokens/labels (B, S) → P(batch_axes, None); embeds get the same B rule.

    pure_dp: the model axis carries no tensor parallelism, so batch shards
    over (data, model) (or (pod, data, model) when serving multi-pod)."""
    dp = ("data", "model") if pure_dp else ("data",)
    b_axes: Any = (
        ("pod",) + dp if ("pod" in mesh.axis_names and not pod_stacked) else dp
    )
    b_axes = b_axes if len(b_axes) > 1 else b_axes[0]

    def spec_fn(path, leaf):
        dims: list = [None] * len(leaf.shape)
        if pod_stacked:
            dims[0] = "pod"
            if len(leaf.shape) > 1:
                if _fits(leaf.shape[1], mesh, dp):
                    dims[1] = dp if len(dp) > 1 else dp[0]
                elif _fits(leaf.shape[1], mesh, "data"):
                    dims[1] = "data"
        else:
            if _fits(leaf.shape[0], mesh, b_axes):
                dims[0] = b_axes
            elif _fits(leaf.shape[0], mesh, dp):
                dims[0] = dp if len(dp) > 1 else dp[0]
            elif _fits(leaf.shape[0], mesh, "data"):
                dims[0] = "data"
        return P(*dims)

    return jax.tree_util.tree_map_with_path(spec_fn, batch_shapes)


def cache_pspec(cache_shapes: Pytree, cfg: ModelConfig, mesh: Mesh, batch: int) -> Pytree:
    """Decode-cache sharding.

    Large-batch decode: shard batch over (pod,data). Batch-1 long-context:
    shard the cache-length dim over (pod,data) instead (context parallelism)
    — this is what makes a 500k-token cache fit."""
    pod = "pod" in mesh.axis_names
    dp: tuple = ("data", "model") if cfg.pure_dp else ("data",)
    b_axes = (("pod",) + dp) if pod else dp
    b_axes = b_axes if len(b_axes) > 1 else b_axes[0]
    seq_axes = b_axes  # used only when batch cannot shard

    batch_shardable = _fits(batch, mesh, b_axes) or _fits(batch, mesh, "data")
    b_axis = b_axes if _fits(batch, mesh, b_axes) else ("data" if _fits(batch, mesh, "data") else None)

    def spec_fn(path, leaf):
        p = _leaf_path(path)
        shape = leaf.shape
        dims: list = [None] * len(shape)
        if re.search(r"(^|/)(k|v|xk|xv)$", p) and len(shape) >= 4:
            # (L, B, C, Hkv, hd) or stacked periods (P, B, C, Hkv, hd)
            bdim, cdim, hdim = len(shape) - 4, len(shape) - 3, len(shape) - 2
            if batch_shardable:
                dims[bdim] = b_axis
                if _fits(shape[hdim], mesh, "model"):
                    dims[hdim] = "model"
            else:
                if _fits(shape[cdim], mesh, seq_axes):
                    dims[cdim] = seq_axes
                if _fits(shape[hdim], mesh, "model"):
                    dims[hdim] = "model"
            return P(*dims)
        # recurrent states: (..., B, W) / (B, H, dh, dh) / conv tails
        if len(shape) >= 2 and not re.search(r"(pos|window)$", p):
            bdim = None
            for d in range(len(shape)):
                if shape[d] == batch:
                    bdim = d
                    break
            if bdim is not None and batch_shardable:
                dims[bdim] = b_axis
            # shard the widest trailing dim over model if divisible
            last = len(shape) - 1
            if _fits(shape[last], mesh, "model") and shape[last] >= 128:
                dims[last] = "model"
            return P(*dims)
        return P()

    return jax.tree_util.tree_map_with_path(spec_fn, cache_shapes)


# ------------------------------------------------------------- serving (TP)
# The continuous-batching engine runs tensor-parallel over a 1-D ``model``
# mesh: attention heads split across shards, the paged KV pool holds each
# shard's kv-head slice of every page (pages are addressed (shard, page) —
# same page id on every shard, different head slice), and page tables stay
# host-side and shard-invariant. Everything outside attention (embeddings,
# norms, FFN, logits) is replicated: each shard redoes that math on identical
# inputs, which keeps the shard-local trace equal to the single-device trace
# on its head slice — the property the engine's token-identity tests pin.

_SERVE_COL = re.compile(r"(attn|xattn)/(wq|wk|wv)$")   # column-parallel


def make_serve_mesh(num_shards: int) -> Mesh:
    """1-D tensor-parallel serving mesh over the ``model`` axis."""
    devs = jax.devices()
    if num_shards < 1 or num_shards > len(devs):
        raise ValueError(
            f"serve mesh wants {num_shards} device(s), have {len(devs)}; on "
            "CPU set XLA_FLAGS=--xla_force_host_platform_device_count=N "
            "before the first jax call"
        )
    return Mesh(np.asarray(devs[:num_shards]), ("model",))


def serve_param_specs(params: Pytree) -> Pytree:
    """Attention-TP specs for serving: wq/wk/wv split their output-feature
    (head) dim over ``model``; every other leaf — including wo — replicates.
    wo stays replicated on purpose: the per-shard head slices all-gather
    back to the full pre-wo activation (``sharding.gather_heads``) and every
    shard runs the identical full out-projection, which keeps sharded
    serving bitwise token-identical to the single-device engine. The
    row-parallel wo + psum alternative rounds partial sums differently and
    flips near-tied argmaxes in bf16."""

    def spec(path, leaf):
        p = _leaf_path(path)
        nd = getattr(leaf, "ndim", 0)
        if _SERVE_COL.search(p) and nd >= 1:
            return P(*([None] * (nd - 1)), "model")
        return P()

    return jax.tree_util.tree_map_with_path(spec, params)


def serve_cache_specs(cache: Pytree) -> Pytree:
    """KV caches split the kv-head axis — dim -2 in both the paged pool
    (L, P, page, Hkv, hd) and ring (L, B, C, Hkv, hd) layouts — over
    ``model``; positions and page tables are shard-invariant (replicated).
    An int8 pool's scale planes (``ks``/``vs``: (L, P, page, Hkv)) carry
    the kv-head axis LAST, so they split dim -1 — each shard holds exactly
    the scales of its page slice."""

    def spec(path, leaf):
        name = _leaf_path(path)
        nd = getattr(leaf, "ndim", 0)
        if re.search(r"(^|/)(k|v)$", name) and nd >= 4:
            axes: list = [None] * nd
            axes[-2] = "model"
            return P(*axes)
        if re.search(r"(^|/)(ks|vs)$", name) and nd >= 4:
            axes = [None] * nd
            axes[-1] = "model"
            return P(*axes)
        return P()

    return jax.tree_util.tree_map_with_path(spec, cache)


def serve_shardings(pspecs: Pytree, mesh: Mesh) -> Pytree:
    """NamedShardings for a pytree of PartitionSpecs (P is a tuple subclass,
    so plain tree_map would flatten it — pin it as a leaf)."""
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), pspecs,
        is_leaf=lambda x: isinstance(x, P),
    )


# ---------------------------------------------------------------- constants
# TPU v5e per chip
PEAK_FLOPS = 197e12          # bf16
HBM_BW = 819e9               # bytes/s
ICI_BW = 50e9                # bytes/s per link (intra-pod)
DCN_BW = 6.25e9              # bytes/s cross-pod (cross-cloud, 50 Gbit/s)
