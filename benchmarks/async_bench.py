"""§3.3 claim: asynchronous aggregation reduces wall-clock latency on
heterogeneous clouds while maintaining accuracy.

Two measurements:
  (a) scheduler simulation — wall time for 100 aggregation rounds, sync vs
      async, as the speed spread between clouds widens;
  (b) real smoke training — async vs sync final loss at matched wall-clock
      budget (modeled), confirming the "small accuracy cost" caveat."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit, save_results
from repro.configs import get_smoke_config
from repro.configs.base import FederatedConfig, TrainConfig
from repro.core.federated import FederatedTrainer
from repro.core.scheduler import (
    CloudSpec,
    events_to_round_masks,
    simulate_async_schedule,
    sync_round_time,
)
from repro.data import SyntheticCorpus, dirichlet_mixtures, federated_batch
from repro.models import build_model

ROUNDS = 100
H = 4


def schedule_comparison() -> dict:
    rows = {}
    for spread in (1.0, 2.0, 4.0):
        clouds = [
            CloudSpec("slow", 1.0), CloudSpec("mid", (1 + spread) / 2),
            CloudSpec("fast", spread),
        ]
        sync_total = ROUNDS * sync_round_time(clouds, H, 1.0, sync_bytes=3.2e9)
        events = simulate_async_schedule(clouds, H, ROUNDS, sync_bytes=3.2e9)
        async_total = events[-1].time
        rows[f"spread_{spread}x"] = {
            "sync_seconds": sync_total,
            "async_seconds": async_total,
            "speedup": sync_total / async_total,
            "mean_staleness": float(np.mean([e.staleness for e in events])),
        }
        emit(
            f"async/spread_{spread}x",
            async_total / ROUNDS * 1e6,
            f"speedup={sync_total/async_total:.2f}x",
        )
    return rows


def accuracy_comparison() -> dict:
    cfg = get_smoke_config("stablelm-1.6b")
    model = build_model(cfg)
    corpus = SyntheticCorpus(vocab_size=cfg.vocab_size, n_domains=4, noise=0.1)
    mix = dirichlet_mixtures(jax.random.PRNGKey(11), 3, 4, beta=0.3)
    clouds = [CloudSpec("a", 1.0), CloudSpec("b", 2.0), CloudSpec("c", 4.0)]
    steps = 80
    events = simulate_async_schedule(clouds, H, steps // H + 1)
    arrived, alphas = events_to_round_masks(events, 3, steps // H + 1)
    out = {}
    for aggregation in ("fedavg", "async"):
        fed = FederatedConfig(n_clouds=3, local_steps=H, aggregation=aggregation)
        trainer = FederatedTrainer(model, fed, TrainConfig(steps=steps, lr=3e-3, warmup_steps=8))
        state = trainer.init_state(jax.random.PRNGKey(0))
        step = jax.jit(trainer.train_step)
        losses = []
        for i in range(steps):
            batch = federated_batch(
                corpus, jax.random.fold_in(jax.random.PRNGKey(13), i), mix, 4, 32
            )
            rnd = i // H
            state, m = step(
                state, batch, jnp.asarray(arrived[rnd]), jnp.asarray(alphas[rnd])
            )
            losses.append(float(m["loss"]))
        out[aggregation] = float(np.mean(losses[-8:]))
        emit(f"async/final_loss_{aggregation}", 0.0, f"loss={out[aggregation]:.3f}")
    return out


def run() -> dict:
    rows = {"schedule": schedule_comparison(), "accuracy": accuracy_comparison()}
    save_results("async", rows)
    return rows


if __name__ == "__main__":
    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    run()
