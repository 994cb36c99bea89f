"""Benchmark harness: one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--only table2,table3,...]

Prints ``name,us_per_call,derived`` CSV rows; full payloads are saved to
benchmarks/results.json. Mapping to the paper:

    table2_comm         — Table 2 (communication overhead + training time)
    table3_convergence  — Table 3 (convergence accuracy + final loss)
    partitioning        — Table 1 row: fixed vs dynamic partitioning
    protocols_bench     — Table 1 row: gRPC vs QUIC (+ TCP, multiplexing)
    compression_bench   — §3.2 gradient compression ablation
    async_bench         — §3.3 async aggregation latency/accuracy claim
    local_steps_bench   — §3.2 local-update schedule (H) comm/convergence sweep
    kernels_bench       — Pallas kernel micro-benchmarks
"""
from __future__ import annotations

import argparse
import sys
import time

from repro.utils.compile_cache import enable_compile_cache

MODULES = [
    "table2_comm",
    "table3_convergence",
    "partitioning",
    "protocols_bench",
    "compression_bench",
    "async_bench",
    "local_steps_bench",
    "kernels_bench",
    "serve_bench",
]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="")
    args = ap.parse_args()
    selected = args.only.split(",") if args.only else MODULES
    enable_compile_cache()

    print("name,us_per_call,derived")
    failures = []
    for name in selected:
        mod = __import__(f"benchmarks.{name}", fromlist=["run"])
        t0 = time.time()
        try:
            mod.run()
        except Exception as e:  # keep the harness going; report at the end
            import traceback
            traceback.print_exc()
            failures.append((name, repr(e)))
        print(f"# {name} done in {time.time()-t0:.1f}s", file=sys.stderr)
    if failures:
        for name, err in failures:
            print(f"# FAILED {name}: {err}", file=sys.stderr)
        raise SystemExit(1)


if __name__ == "__main__":
    main()
