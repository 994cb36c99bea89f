"""Training driver — runs real federated training (CPU-sized configs here;
the same code path lowers to the production mesh via dryrun.py).

Examples:
    PYTHONPATH=src python -m repro.launch.train --arch stablelm-1.6b \
        --smoke --steps 200 --aggregation dynamic --clouds 3 --beta 0.2
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import Checkpointer
from repro.configs import get_config, get_smoke_config
from repro.configs.base import FederatedConfig, TrainConfig
from repro.core.federated import FederatedTrainer
from repro.core.scheduler import CloudSpec, events_to_round_masks, simulate_async_schedule
from repro.data import SyntheticCorpus, dirichlet_mixtures, federated_batch
from repro.models import build_model
from repro.utils.compile_cache import enable_compile_cache
from repro.utils.tree import tree_count_params


def run_training(
    arch: str,
    *,
    smoke: bool = True,
    steps: int = 100,
    seq_len: int = 64,
    per_cloud_batch: int = 8,
    n_clouds: int = 3,
    local_steps: int = 4,
    aggregation: str = "fedavg",
    compression: str = "none",
    topk_ratio: float = 0.01,
    dp_clip: float = 0.0,
    dp_noise: float = 0.0,
    beta: float = 0.3,
    lr: float = 1e-3,
    seed: int = 0,
    outer_optimizer: str = "none",
    log_every: int = 10,
    checkpoint_dir: str = "",
    n_domains: int = 8,
    log_fn=print,
) -> dict:
    cfg = get_smoke_config(arch) if smoke else get_config(arch)
    model = build_model(cfg)
    fed = FederatedConfig(
        n_clouds=n_clouds,
        local_steps=local_steps,
        aggregation=aggregation,
        compression=compression,
        topk_ratio=topk_ratio,
        dp_clip=dp_clip,
        dp_noise_mult=dp_noise,
        outer_optimizer=outer_optimizer,
    )
    tcfg = TrainConfig(
        seq_len=seq_len, global_batch=per_cloud_batch * n_clouds,
        steps=steps, lr=lr, warmup_steps=max(steps // 10, 1), seed=seed,
    )
    trainer = FederatedTrainer(model, fed, tcfg)
    state = trainer.init_state(jax.random.PRNGKey(seed))
    n_params = tree_count_params(state["global"]["params"])
    log_fn(f"arch={cfg.name} params={n_params:,} agg={aggregation} "
           f"H={local_steps} compression={compression}")

    corpus = SyntheticCorpus(vocab_size=cfg.vocab_size, n_domains=n_domains, noise=0.1)
    mixtures = dirichlet_mixtures(jax.random.PRNGKey(seed + 1), n_clouds, n_domains, beta)

    # async arrival schedule from heterogeneous cloud speeds
    clouds = [CloudSpec(f"cloud{i}", speed=1.0 + 0.5 * i) for i in range(n_clouds)]
    n_rounds = max(steps // max(local_steps, 1), 1)
    events = simulate_async_schedule(clouds, local_steps, n_rounds + 1,
                                     base_alpha=fed.async_alpha)
    arrived_rounds, alpha_rounds = events_to_round_masks(events, n_clouds, n_rounds + 1)

    step_fn = jax.jit(trainer.train_step)
    ckpt = Checkpointer(checkpoint_dir) if checkpoint_dir else None
    history = []
    t0 = time.time()
    for i in range(steps):
        key = jax.random.fold_in(jax.random.PRNGKey(seed + 2), i)
        batch = federated_batch(corpus, key, mixtures, per_cloud_batch, seq_len)
        rnd = i // max(local_steps, 1)
        state, metrics = step_fn(
            state, batch,
            jnp.asarray(arrived_rounds[min(rnd, n_rounds)]),
            jnp.asarray(alpha_rounds[min(rnd, n_rounds)]),
        )
        if (i + 1) % log_every == 0 or i == steps - 1:
            loss = float(metrics["loss"])
            acc = float(metrics["accuracy"])
            history.append({"step": i + 1, "loss": loss, "accuracy": acc})
            log_fn(f"step {i+1:5d}  loss {loss:.4f}  acc {acc:.4f}  "
                   f"({(time.time()-t0)/(i+1):.2f}s/step)")
        if ckpt and (i + 1) % 100 == 0:
            ckpt.save(i + 1, state["global"]["params"])

    bytes_per_sync = trainer.sync_bytes_per_cloud(state["global"]["params"])
    total_syncs = steps * trainer.syncs_per_step()
    result = {
        "arch": cfg.name,
        "params": n_params,
        "aggregation": aggregation,
        "compression": compression,
        "final_loss": history[-1]["loss"] if history else None,
        "final_accuracy": history[-1]["accuracy"] if history else None,
        "history": history,
        "oracle_accuracy": corpus.oracle_accuracy(),
        "bytes_per_cloud_per_sync": bytes_per_sync,
        "total_comm_gb": bytes_per_sync * total_syncs * n_clouds / 1e9,
        "wall_seconds": time.time() - t0,
    }
    return result


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="stablelm-1.6b")
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--full", dest="smoke", action="store_false")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--seq-len", type=int, default=64)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--clouds", type=int, default=3)
    ap.add_argument("--local-steps", type=int, default=4)
    ap.add_argument("--aggregation", default="fedavg",
                    choices=["fedavg", "dynamic", "gradient", "async"])
    ap.add_argument("--compression", default="none",
                    choices=["none", "topk", "int8", "topk+int8"])
    ap.add_argument("--topk-ratio", type=float, default=0.01)
    ap.add_argument("--dp-clip", type=float, default=0.0)
    ap.add_argument("--dp-noise", type=float, default=0.0)
    ap.add_argument("--beta", type=float, default=0.3)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--outer", default="none", choices=["none", "sgd", "nesterov"])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--checkpoint-dir", default="")
    ap.add_argument("--json-out", default="")
    args = ap.parse_args()

    result = run_training(
        args.arch, smoke=args.smoke, steps=args.steps, seq_len=args.seq_len,
        per_cloud_batch=args.batch, n_clouds=args.clouds,
        local_steps=args.local_steps, aggregation=args.aggregation,
        compression=args.compression, topk_ratio=args.topk_ratio,
        dp_clip=args.dp_clip, dp_noise=args.dp_noise, beta=args.beta,
        lr=args.lr, seed=args.seed, outer_optimizer=args.outer,
        checkpoint_dir=args.checkpoint_dir,
    )
    print(f"final: loss={result['final_loss']:.4f} acc={result['final_accuracy']:.4f} "
          f"(oracle acc {result['oracle_accuracy']:.3f}); "
          f"comm {result['total_comm_gb']:.3f} GB")
    if args.json_out:
        with open(args.json_out, "w") as f:
            json.dump(result, f, indent=1)


if __name__ == "__main__":
    main()
