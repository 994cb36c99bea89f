"""Paper Table 3: convergence accuracy (%) and final loss for FedAvg /
Dynamic Weighted / Gradient Aggregation under non-IID cross-cloud data.

Real training runs (smoke-scale model, synthetic non-IID corpus with
Dirichlet β=0.05 — strongly skewed, the regime the paper targets). Metrics:
final next-token accuracy (% of the corpus oracle) and final loss, mirroring
the paper's two columns. The paper's qualitative claims to validate:
dynamic > fedavg, gradient ≥ dynamic on heterogeneous data."""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit, save_results
from repro.configs import get_smoke_config
from repro.configs.base import FederatedConfig, TrainConfig
from repro.core.federated import FederatedTrainer
from repro.data import SyntheticCorpus, dirichlet_mixtures, federated_batch
from repro.models import build_model

STEPS = 150
SEQ = 48
PCB = 8          # per-cloud batch
BETA = 0.05      # strong non-IID skew
N_CLOUDS = 3
H = 4


def train_one(aggregation: str, seed: int = 0) -> dict:
    cfg = get_smoke_config("stablelm-1.6b")
    model = build_model(cfg)
    corpus = SyntheticCorpus(vocab_size=cfg.vocab_size, n_domains=6, noise=0.1)
    mix = dirichlet_mixtures(jax.random.PRNGKey(99), N_CLOUDS, 6, beta=BETA)
    fed = FederatedConfig(
        n_clouds=N_CLOUDS, local_steps=H, aggregation=aggregation,
        # give clouds uneven sample counts (formula 1 weighting is active)
        cloud_sample_counts=(2000, 4000, 6000),
    )
    tcfg = TrainConfig(steps=STEPS, lr=3e-3, warmup_steps=10)
    trainer = FederatedTrainer(model, fed, tcfg)
    state = trainer.init_state(jax.random.PRNGKey(seed))
    step = jax.jit(trainer.train_step)
    t0 = time.time()
    losses, accs = [], []
    for i in range(STEPS):
        batch = federated_batch(
            corpus, jax.random.fold_in(jax.random.PRNGKey(seed + 5), i), mix, PCB, SEQ
        )
        rnd = i // H
        arrived = jnp.asarray([rnd % N_CLOUDS == j for j in range(N_CLOUDS)])
        state, m = step(state, batch, arrived, jnp.full((N_CLOUDS,), 0.5))
        losses.append(float(m["loss"]))
        accs.append(float(m["accuracy"]))
    wall = time.time() - t0

    # held-out IID evaluation of the GLOBAL model (the paper's accuracy col)
    eval_mix = jnp.ones(6) / 6
    eval_batch = corpus.sample(jax.random.PRNGKey(1234), eval_mix, 32, SEQ)
    loss, metrics = model.loss(
        state["global"]["params"],
        {"tokens": eval_batch["tokens"], "labels": eval_batch["labels"]},
    )
    return {
        "final_train_loss": float(np.mean(losses[-10:])),
        "eval_loss": float(loss),
        "eval_accuracy_pct": float(metrics["accuracy"]) * 100,
        "oracle_accuracy_pct": corpus.oracle_accuracy() * 100,
        "wall_seconds": wall,
        "us_per_step": wall / STEPS * 1e6,
        "loss_curve": losses[::10],
    }


def run() -> dict:
    rows = {}
    for aggregation in ("fedavg", "dynamic", "gradient"):
        r = train_one(aggregation)
        rows[aggregation] = r
        emit(
            f"table3/{aggregation}",
            r["us_per_step"],
            f"acc={r['eval_accuracy_pct']:.1f}%;loss={r['eval_loss']:.3f}",
        )
    save_results("table3_convergence", rows)
    return rows


if __name__ == "__main__":
    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    run()
