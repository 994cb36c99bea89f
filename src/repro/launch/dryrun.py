import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

# NOTE: the two lines above MUST run before any jax import — jax locks the
# device count at first init. This also means this module must not be
# imported by code that wants real single-device CPU semantics.

DOC = """Multi-pod dry-run: AOT lower + compile every (architecture × input-shape ×
mesh) combination and extract the roofline terms.

No arrays are ever allocated: inputs are ShapeDtypeStructs, outputs are the
compiled executable's memory/cost analyses plus the collective traffic
parsed from its HLO. This is the proof that the distribution config is
coherent — a sharding mismatch, a compile-time OOM, or an unsupported
collective fails here.

Usage:
    python -m repro.launch.dryrun --arch stablelm-1.6b --shape train_4k
    python -m repro.launch.dryrun --arch all --shape all --multi-pod both \
        --out experiments/dryrun.jsonl
"""

import argparse
import dataclasses
import json
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ARCH_IDS, INPUT_SHAPES, get_config, get_shape
from repro.configs.base import FederatedConfig, ModelConfig, ShapeConfig, TrainConfig
from repro.launch import mesh as meshlib
from repro.launch import specs as speclib
from repro.launch.steps import (
    decode_window_for,
    make_decode_step,
    make_federated_step,
    make_prefill_step,
    make_train_step,
)
from repro.models import build_model
from repro.models.sharding import DEFAULT_RULES, ShardingRules, use_rules
from repro.utils import hlo as hlolib

SDS = jax.ShapeDtypeStruct


def _ns(mesh, pspec_tree):
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s),
        pspec_tree,
        is_leaf=lambda x: isinstance(x, P),
    )


def _rules_for(mesh, kind: str = "training", cfg=None) -> ShardingRules:
    rules = dict(DEFAULT_RULES)
    if cfg is not None and cfg.pure_dp:
        # no tensor parallelism: every model-axis mapping goes away and the
        # batch dimension claims both intra-pod axes.
        rules = {k: None for k in rules}
        dp = ("data", "model")
        if "pod" in mesh.axis_names and kind in ("prefill", "decode"):
            dp = ("pod", "data", "model")
        rules["batch"] = dp
        return ShardingRules(mesh, rules)
    if "pod" in mesh.axis_names:
        rules["cache_seq"] = ("pod", "data")
        if kind in ("prefill", "decode"):
            # Serving has no federated (divergent-replica) pod semantics: the
            # pod axis is just more data parallelism. Shard batch over
            # (pod, data) to MATCH cache_pspec — a bare "data" here makes
            # every in-step constraint contradict the cache in_shardings and
            # XLA "involuntarily rematerializes" (cross-pod all-gathers the
            # full KV cache, ~1.7 TB/dev on stablelm-12b decode_32k).
            # constrain()'s dedup then drops overlapping axes from cache_seq
            # when batch claims them (and vice versa for batch=1 long_500k).
            rules["batch"] = ("pod", "data")
    return ShardingRules(mesh, rules)


def model_flops(cfg: ModelConfig, shape: ShapeConfig) -> float:
    """6·N·D (training) / 2·N·D (forward-only), N = active params."""
    n = cfg.active_param_count()
    tokens = shape.global_batch * speclib.text_len(cfg, shape)
    if shape.kind == "training":
        return 6.0 * n * tokens
    if shape.kind == "prefill":
        return 2.0 * n * tokens
    return 2.0 * n * shape.global_batch  # decode: one token per sequence


# --------------------------------------------------------------- lower paths
def _effective_cfg(cfg, shape, mesh, *, federated: bool = False):
    """pure_dp needs the (per-pod) batch to cover BOTH intra-pod axes; when it
    cannot (e.g. 128-per-cloud over 16x16), fall back to the TP rule set
    rather than letting the model axis idle."""
    if not cfg.pure_dp:
        return cfg
    n_pods = meshlib.axis_size(mesh, "pod") if federated else 1
    dp = meshlib.axis_size(mesh, "data") * meshlib.axis_size(mesh, "model")
    per_pod = shape.global_batch // (n_pods or 1)
    if shape.kind != "training" and "pod" in mesh.axis_names and not federated:
        dp *= meshlib.axis_size(mesh, "pod")  # serving: pod is extra DP
    if per_pod % dp == 0 or per_pod == 1:     # batch=1 long-ctx: rules no-op
        return cfg
    return dataclasses.replace(cfg, pure_dp=False)


def lower_train(cfg, shape, mesh, microbatches):
    cfg = _effective_cfg(cfg, shape, mesh)
    model = build_model(cfg)
    params_s, opt_s = speclib.state_specs(model)
    batch_s = speclib.train_batch_specs(cfg, shape)

    p_pspec = meshlib.params_pspec_tree(params_s, cfg, mesh)
    o_pspec = meshlib.opt_pspec_tree(opt_s, p_pspec, mesh)
    b_pspec = meshlib.batch_pspec(batch_s, mesh, pure_dp=cfg.pure_dp)

    train_cfg = TrainConfig(seq_len=shape.seq_len, global_batch=shape.global_batch)
    step = make_train_step(
        model, train_cfg, microbatches, grad_shardings=_ns(mesh, p_pspec)
    )
    jitted = jax.jit(
        step,
        in_shardings=(_ns(mesh, p_pspec), _ns(mesh, o_pspec), _ns(mesh, b_pspec)),
        out_shardings=(
            _ns(mesh, p_pspec),
            _ns(mesh, o_pspec),
            NamedSharding(mesh, P()),
        ),
        donate_argnums=(0, 1),   # params/opt update in place (real deployment)
    )
    with use_rules(_rules_for(mesh, cfg=cfg)):
        return jitted.lower(params_s, opt_s, batch_s)


def lower_federated_train(cfg, shape, mesh, microbatches, fed_cfg=None):
    cfg = _effective_cfg(cfg, shape, mesh, federated=True)
    n_pods = meshlib.axis_size(mesh, "pod")
    model = build_model(cfg)
    fed_cfg = fed_cfg or FederatedConfig(
        n_clouds=n_pods, local_steps=4, aggregation="fedavg", compression="none"
    )
    train_cfg = TrainConfig(seq_len=shape.seq_len, global_batch=shape.global_batch)
    params_only = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    p_pspec = meshlib.params_pspec_tree(params_only, cfg, mesh)
    trainer, fed_step = make_federated_step(
        model, fed_cfg, train_cfg, microbatches,
        grad_shardings=_ns(mesh, p_pspec), mesh=mesh,
    )

    state_s = jax.eval_shape(trainer.init_state, jax.random.PRNGKey(0))
    state_pspec = meshlib.federated_state_pspecs(state_s, cfg, mesh)
    batch_s = speclib.train_batch_specs(cfg, shape, n_pods=n_pods)
    b_pspec = meshlib.batch_pspec(batch_s, mesh, pod_stacked=True, pure_dp=cfg.pure_dp)

    jitted = jax.jit(
        fed_step,
        in_shardings=(_ns(mesh, state_pspec), _ns(mesh, b_pspec)),
        out_shardings=(_ns(mesh, state_pspec), NamedSharding(mesh, P())),
        donate_argnums=(0,),     # federated state updates in place
    )
    with use_rules(_rules_for(mesh, cfg=cfg)):
        return jitted.lower(state_s, batch_s)


def lower_prefill(cfg, shape, mesh):
    cfg = _effective_cfg(cfg, shape, mesh)
    model = build_model(cfg)
    params_s, _ = speclib.state_specs(model)
    batch_s = speclib.train_batch_specs(cfg, shape)
    batch_s.pop("labels")
    p_pspec = meshlib.params_pspec_tree(params_s, cfg, mesh)
    b_pspec = meshlib.batch_pspec(batch_s, mesh, pure_dp=cfg.pure_dp)

    step = make_prefill_step(model, shape)
    cache_s = jax.eval_shape(step, params_s, batch_s)[0]
    c_pspec = meshlib.cache_pspec(cache_s, cfg, mesh, shape.global_batch)
    logits_pspec = P(None, "model")

    jitted = jax.jit(
        step,
        in_shardings=(_ns(mesh, p_pspec), _ns(mesh, b_pspec)),
        out_shardings=(_ns(mesh, c_pspec), NamedSharding(mesh, logits_pspec)),
    )
    with use_rules(_rules_for(mesh, "prefill", cfg=cfg)):
        return jitted.lower(params_s, batch_s)


def lower_decode(cfg, shape, mesh):
    cfg = _effective_cfg(cfg, shape, mesh)
    model = build_model(cfg)
    params_s, _ = speclib.state_specs(model)
    window = decode_window_for(cfg, shape)
    cache_s = speclib.cache_specs(model, cfg, shape, window)
    tokens_s = speclib.decode_token_specs(shape)

    p_pspec = meshlib.params_pspec_tree(params_s, cfg, mesh)
    c_pspec = meshlib.cache_pspec(cache_s, cfg, mesh, shape.global_batch)
    t_pspec = meshlib.batch_pspec({"tokens": tokens_s}, mesh, pure_dp=cfg.pure_dp)["tokens"]

    step = make_decode_step(model, window)
    jitted = jax.jit(
        step,
        in_shardings=(_ns(mesh, p_pspec), _ns(mesh, c_pspec), NamedSharding(mesh, t_pspec)),
        out_shardings=(_ns(mesh, c_pspec), NamedSharding(mesh, P(None, "model"))),
        donate_argnums=(1,),     # KV cache updates in place
    )
    with use_rules(_rules_for(mesh, "decode", cfg=cfg)):
        return jitted.lower(params_s, cache_s, tokens_s)


# ------------------------------------------------------------------ analysis
def analyse(lowered, compiled, mesh, cfg, shape, *, seconds: float) -> dict:
    n_dev = mesh.devices.size
    # devices per pod — cross-pod classification must follow the actual mesh
    # (the production pod is 256 chips, but tests run tiny meshes)
    pod_size = (
        n_dev // meshlib.axis_size(mesh, "pod")
        if "pod" in mesh.axis_names else 0
    )

    cost = compiled.cost_analysis()
    if isinstance(cost, (list, tuple)):
        cost = cost[0]
    xla_flops = float(cost.get("flops", 0.0))
    xla_bytes = float(cost.get("bytes accessed", 0.0))

    try:
        hlo_text = compiled.as_text()
    except Exception:
        hlo_text = lowered.as_text()
    # trip-count-aware accounting (XLA's cost_analysis counts while bodies
    # once — see utils/hlo.py)
    hcost = hlolib.analyze(hlo_text, pod_size=pod_size)
    flops = max(hcost.flops, xla_flops)
    bytes_accessed = max(hcost.hbm_bytes, xla_bytes)
    coll = hcost

    mem = compiled.memory_analysis()
    mem_rec = {
        "argument_bytes": getattr(mem, "argument_size_in_bytes", 0),
        "output_bytes": getattr(mem, "output_size_in_bytes", 0),
        "temp_bytes": getattr(mem, "temp_size_in_bytes", 0),
        "code_bytes": getattr(mem, "generated_code_size_in_bytes", 0),
    }

    mf = model_flops(cfg, shape)
    compute_term = flops / meshlib.PEAK_FLOPS
    memory_term = bytes_accessed / meshlib.HBM_BW
    ici_bytes = coll.link_bytes(cross_pod=False)
    dcn_bytes = coll.link_bytes(cross_pod=True)
    collective_term = ici_bytes / meshlib.ICI_BW + dcn_bytes / meshlib.DCN_BW

    terms = {
        "compute_s": compute_term,
        "memory_s": memory_term,
        "collective_s": collective_term,
        "ici_link_bytes": ici_bytes,
        "dcn_link_bytes": dcn_bytes,
        "n_collectives": coll.n_collectives(),
        "collectives_by_kind": coll.by_kind(),
        "xla_reported_flops": xla_flops,
        "xla_reported_bytes": xla_bytes,
    }
    dominant = max(
        ("compute", compute_term), ("memory", memory_term), ("collective", collective_term),
        key=lambda kv: kv[1],
    )[0]
    return {
        "hlo_flops_per_device": flops,
        "hlo_bytes_per_device": bytes_accessed,
        "model_flops_total": mf,
        "model_flops_per_device": mf / n_dev,
        "useful_flops_ratio": (mf / n_dev) / flops if flops else 0.0,
        "memory": mem_rec,
        "roofline": terms,
        "dominant": dominant,
        "compile_seconds": seconds,
        "devices": n_dev,
    }


def dryrun_pair(arch: str, shape_name: str, *, multi_pod: bool, verbose: bool = True) -> dict:
    cfg = get_config(arch)
    shape = get_shape(shape_name)
    mesh = meshlib.make_production_mesh(multi_pod=multi_pod)
    data_ax = meshlib.axis_size(mesh, "data")
    n_pods = meshlib.axis_size(mesh, "pod")
    mb = speclib.microbatch_policy(cfg, shape, n_pods=n_pods, data_axis=data_ax)

    t0 = time.time()
    with mesh:
        if shape.kind == "training":
            if multi_pod:
                lowered = lower_federated_train(cfg, shape, mesh, mb)
            else:
                lowered = lower_train(cfg, shape, mesh, mb)
        elif shape.kind == "prefill":
            lowered = lower_prefill(cfg, shape, mesh)
        else:
            lowered = lower_decode(cfg, shape, mesh)
        compiled = lowered.compile()
    dt = time.time() - t0

    rec = {
        "arch": arch,
        "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "kind": shape.kind,
        "microbatches": mb,
        "params": cfg.param_count(),
        "active_params": cfg.active_param_count(),
    }
    rec.update(analyse(lowered, compiled, mesh, cfg, shape, seconds=dt))
    if verbose:
        print(compiled.memory_analysis())
        r = rec["roofline"]
        print(
            f"[{arch} × {shape_name} × {rec['mesh']}] mb={mb} "
            f"compute={r['compute_s']*1e3:.2f}ms memory={r['memory_s']*1e3:.2f}ms "
            f"collective={r['collective_s']*1e3:.2f}ms dominant={rec['dominant']} "
            f"useful={rec['useful_flops_ratio']:.2f} compile={dt:.0f}s"
        )
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--multi-pod", choices=["on", "off", "both"], default="off")
    ap.add_argument("--out", default="")
    ap.add_argument("--skip-existing", action="store_true")
    args = ap.parse_args()

    archs = list(ARCH_IDS) if args.arch == "all" else args.arch.split(",")
    shapes = list(INPUT_SHAPES) if args.shape == "all" else args.shape.split(",")
    pods = {"on": [True], "off": [False], "both": [False, True]}[args.multi_pod]

    done = set()
    if args.out and args.skip_existing and os.path.exists(args.out):
        with open(args.out) as f:
            for line in f:
                try:
                    r = json.loads(line)
                    done.add((r["arch"], r["shape"], r["mesh"]))
                except Exception:
                    pass

    failures = []
    for arch in archs:
        for shape_name in shapes:
            for mp in pods:
                mesh_name = "2x16x16" if mp else "16x16"
                if (arch, shape_name, mesh_name) in done:
                    continue
                try:
                    rec = dryrun_pair(arch, shape_name, multi_pod=mp)
                except Exception as e:
                    traceback.print_exc()
                    rec = {
                        "arch": arch, "shape": shape_name, "mesh": mesh_name,
                        "error": f"{type(e).__name__}: {e}",
                    }
                    failures.append(rec)
                if args.out:
                    with open(args.out, "a") as f:
                        f.write(json.dumps(rec) + "\n")
    if failures:
        print(f"\n{len(failures)} FAILURES:")
        for f_ in failures:
            print(f"  {f_['arch']} × {f_['shape']} × {f_['mesh']}: {f_['error'][:120]}")
        raise SystemExit(1)
    print("\nall dry-runs passed")


if __name__ == "__main__":
    main()
