"""§3.2 ablation: compression method × ratio → wire bytes, reconstruction
error, and convergence impact (short training runs with error feedback)."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit, save_results
from repro.configs import get_smoke_config
from repro.configs.base import FederatedConfig, TrainConfig
from repro.core.compression import Compressor
from repro.core.federated import FederatedTrainer
from repro.data import SyntheticCorpus, dirichlet_mixtures, federated_batch
from repro.models import build_model

STEPS = 60


def convergence_with(compression: str, ratio: float, seed=0) -> float:
    cfg = get_smoke_config("stablelm-1.6b")
    model = build_model(cfg)
    corpus = SyntheticCorpus(vocab_size=cfg.vocab_size, n_domains=4, noise=0.1)
    mix = dirichlet_mixtures(jax.random.PRNGKey(3), 3, 4, beta=0.3)
    fed = FederatedConfig(
        n_clouds=3, local_steps=2, aggregation="fedavg",
        compression=compression, topk_ratio=ratio,
    )
    trainer = FederatedTrainer(model, fed, TrainConfig(steps=STEPS, lr=3e-3, warmup_steps=5))
    state = trainer.init_state(jax.random.PRNGKey(seed))
    step = jax.jit(trainer.train_step)
    losses = []
    for i in range(STEPS):
        batch = federated_batch(
            corpus, jax.random.fold_in(jax.random.PRNGKey(seed + 7), i), mix, 4, 32
        )
        state, m = step(state, batch)
        losses.append(float(m["loss"]))
    return float(np.mean(losses[-8:]))


def run() -> dict:
    rows = {}
    key = jax.random.PRNGKey(0)
    grad_like = {"w": jax.random.normal(key, (1 << 18,)) * 0.01}

    settings = [
        ("none", 1.0), ("int8", 1.0),
        ("topk", 0.10), ("topk", 0.01), ("topk+int8", 0.01),
    ]
    for method, ratio in settings:
        comp = Compressor(method, topk_ratio=ratio)
        recon = comp.roundtrip(grad_like)["w"]
        err = float(
            jnp.linalg.norm(recon - grad_like["w"]) / jnp.linalg.norm(grad_like["w"])
        )
        cr = comp.compression_ratio(grad_like)
        final_loss = convergence_with(method, ratio)
        name = f"{method}@{ratio}" if "topk" in method else method
        rows[name] = {
            "compression_ratio": cr,
            "recon_rel_error": err,
            "final_loss": final_loss,
        }
        emit(f"compression/{name}", 0.0,
             f"ratio={cr:.1f}x;err={err:.3f};loss={final_loss:.3f}")
    save_results("compression", rows)
    return rows


if __name__ == "__main__":
    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    run()
