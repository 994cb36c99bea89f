"""Paper Table 1 row "Data Partitioning Strategy: Fixed vs Dynamic".

Simulates heterogeneous clouds (speeds 1×/2×/4×, plus a mid-run slowdown on
cloud 2 — the paper's "real-time monitoring and adjustment" scenario) and
compares synchronous-round latency and utilization under fixed, weighted,
and dynamic partitioning, sweeping the granularity knob."""
from __future__ import annotations

import numpy as np

from benchmarks.common import emit, save_results
from repro.core.partition import Partitioner

GLOBAL_BATCH = 128
ROUNDS = 60


def simulate(strategy: str, granule: int = 1) -> dict:
    speeds = np.asarray([1.0, 2.0, 4.0])
    p = Partitioner(strategy=strategy, n_clouds=3, granule=granule)
    state = p.init(nominal_throughput=[1.0, 1.0, 1.0])  # mis-provisioned
    total_time = 0.0
    utils = []
    for r in range(ROUNDS):
        if r == ROUNDS // 2:
            speeds = np.asarray([1.0, 0.5, 4.0])  # cloud 1 degrades mid-run
        sizes = p.quantize(state, GLOBAL_BATCH)
        t = Partitioner.round_time(sizes, speeds)
        total_time += t
        utils.append(Partitioner.utilization(sizes, speeds))
        state = p.observe(state, sizes, sizes / speeds)
    return {
        "total_time": total_time,
        "mean_utilization": float(np.mean(utils)),
        "final_shares": state.shares.tolist(),
        "granule": granule,
    }


def run() -> dict:
    rows = {}
    for strategy in ("fixed", "weighted", "dynamic"):
        r = simulate(strategy)
        rows[strategy] = r
        emit(
            f"partitioning/{strategy}",
            r["total_time"] / ROUNDS * 1e6,
            f"util={r['mean_utilization']:.2f};time={r['total_time']:.1f}",
        )
    # granularity sweep (paper §3.1: "finding the right partition size")
    for granule in (1, 4, 16, 64):
        r = simulate("dynamic", granule)
        rows[f"dynamic_g{granule}"] = r
        emit(
            f"partitioning/granule_{granule}",
            r["total_time"] / ROUNDS * 1e6,
            f"util={r['mean_utilization']:.2f}",
        )
    save_results("partitioning", rows)
    return rows


if __name__ == "__main__":
    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    run()
