"""§3.2 local-update schedule ablation: H local steps between syncs.

The paper claims local updates "effectively reduce the number of cross-cloud
communications and improve overall efficiency" but gives no schedule. This
sweep quantifies the tradeoff the claim hides: sync traffic falls 1/H while
the per-cloud replicas drift between syncs, costing convergence on non-IID
data. Reported per H: total sync bytes per cloud, modeled wall-clock
(compute + QUIC cross-cloud transfer), and final loss at a fixed step
budget."""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit, save_results
from repro.configs import get_smoke_config
from repro.configs.base import FederatedConfig, TrainConfig
from repro.core.federated import FederatedTrainer
from repro.core.protocols import QUIC, Link, sync_wall_time
from repro.data import SyntheticCorpus, dirichlet_mixtures, federated_batch
from repro.models import build_model

STEPS = 96
SEQ = 48
PCB = 8
BETA = 0.05
N_CLOUDS = 3
H_SWEEP = (1, 2, 4, 8, 16)


def run():
    cfg = get_smoke_config("stablelm-1.6b")
    model = build_model(cfg)
    corpus = SyntheticCorpus(vocab_size=cfg.vocab_size, n_domains=4, noise=0.1)
    mix = dirichlet_mixtures(jax.random.PRNGKey(0), N_CLOUDS, 4, beta=BETA)
    link = Link()

    rows = {}
    for h in H_SWEEP:
        fed = FederatedConfig(n_clouds=N_CLOUDS, local_steps=h, aggregation="fedavg")
        tcfg = TrainConfig(steps=STEPS, lr=3e-3, warmup_steps=6)
        trainer = FederatedTrainer(model, fed, tcfg)
        state = trainer.init_state(jax.random.PRNGKey(1))
        step = jax.jit(trainer.train_step)
        losses = []
        t0 = time.time()
        for i in range(STEPS):
            key = jax.random.fold_in(jax.random.PRNGKey(7), i)
            batch = federated_batch(corpus, key, mix, PCB, SEQ)
            state, m = step(state, batch)
            losses.append(float(m["loss"]))
        wall = time.time() - t0
        sync_bytes = trainer.sync_bytes_per_cloud(state["global"]["params"])
        n_syncs = STEPS // h
        comm_s = n_syncs * sync_wall_time(sync_bytes, N_CLOUDS, QUIC, link)
        final = float(np.mean(losses[-8:]))
        rows[f"H={h}"] = {
            "final_loss": final,
            "syncs": n_syncs,
            "sync_bytes_per_cloud": int(sync_bytes),
            "total_comm_gb": sync_bytes * n_syncs / 1e9,
            "modeled_comm_seconds": comm_s,
            "wall_seconds": wall,
        }
        emit(
            f"local_steps/H={h}", wall / STEPS * 1e6,
            f"loss={final:.3f};comm={sync_bytes*n_syncs/1e9:.1f}GB;"
            f"quic_s={comm_s:.1f}",
        )
    save_results("local_steps", rows)
    return rows


if __name__ == "__main__":
    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    run()
