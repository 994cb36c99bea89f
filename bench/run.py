#!/usr/bin/env python3
"""Run one benchmark cell on the chip and print its result.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Reads ``BENCHMARK.json`` at the checkout's root, finds the cell's
configuration, traffic mix and metrics by name (``bench/lookup.py``), runs
the mix's runner, and prints one JSON line last on standard output:
``correct``, ``attempted``, ``failed``, ``metrics`` (end-to-end with
``--trace 0``, per-layer with ``--trace 1``), ``device``, with ``--trace 1``
a ``breakdown``, and last ``checks``: each number that decides ``correct``
with its limit, also printed as the last lines of standard error.

Refuses (exit code 2, no result line) when JAX finds no TPU or fewer chips
than the cell asks for, when the device kind has no entry in
``bench/peaks.json``, and when a name does not resolve."""
from __future__ import annotations

import time

T0 = time.perf_counter()      # set-up time counts from the process's start

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Any, Callable  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
# the TPU runtime would otherwise log to a fixed /tmp path
os.environ.setdefault("TPU_LOG_DIR", "disabled")
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import lookup  # noqa: E402
from bench.lookup import BenchError  # noqa: E402


@dataclasses.dataclass
class Ctx:
    cell: dict
    cfg: dict
    traffic: dict
    seed: int
    seconds: float
    trace: bool
    devices: list
    peaks: dict
    t0: float
    trace_dir: str
    log: Callable[[str], None]
    control: str | None = None    # a control judged in the program's place
    readings: tuple = ()          # controls read beside the program
    breaker: Any = None       # tests: breaks the timed path underneath


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def setup_jax(require_chips: int | None):
    """Compile cache in the checkout; the devices, refusing a host without
    the chips a cell needs (``None``: tests, any backend, no cache)."""
    try:
        from repro.utils.compile_cache import enable_compile_cache
    except ImportError as e:
        raise BenchError(f"the program's sources are not in this checkout ({e})")
    import jax

    if require_chips is not None:
        enable_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    devices = jax.devices()
    if require_chips is not None:
        if devices[0].platform != "tpu":
            raise BenchError(f"no TPU: JAX sees {len(devices)} "
                             f"{devices[0].platform} device(s)")
        if len(devices) < require_chips:
            raise BenchError(f"the cell needs {require_chips} chips, "
                             f"JAX sees {len(devices)}")
    return devices


def execute(args, *, overrides: dict | None = None, chip_check: bool = True,
            control: str | None = None, readings: tuple = (),
            breaker=None) -> dict:
    """One run of a cell; returns the result object (not printed)."""
    overrides = overrides or {}
    man = overrides.get("manifest") or lookup.manifest()
    cell = lookup.workload(man, args.workload)
    cfg = {**lookup.config(cell["config"]), **overrides.get("config", {})}
    traffic = {**lookup.traffic(cell["traffic"]), **overrides.get("traffic", {})}
    metrics = lookup.cell_metrics(man, cell["name"], bool(args.trace))
    readers = {m["name"]: lookup.metric_reader(m["name"])
               for m in metrics} if args.trace else {}
    devices = setup_jax(cell["chips"] if chip_check else None)
    kind = devices[0].device_kind
    peaks = overrides.get("peaks") or lookup.peaks(kind)
    runner = lookup.runner(traffic["runner"])
    ctx = Ctx(cell=cell, cfg=cfg, traffic=traffic, seed=args.seed,
              seconds=args.seconds, trace=bool(args.trace), devices=devices,
              peaks=peaks, t0=T0,
              trace_dir=str(ROOT / ".bench_traces" / cell["name"]),
              log=log, control=control, readings=readings, breaker=breaker)
    rec = runner.run(ctx)
    rec["peaks"] = peaks
    out_metrics = {}
    for m in metrics:
        if args.trace:
            v = readers[m["name"]](rec)
        else:
            v = rec["e2e"].get(m["name"])
            if v is None:
                raise BenchError(f"the runner measured no {m['name']}")
        if v is not None:
            out_metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices),
              "memory_peak_bytes": rec["memory_peak_bytes"]}
    checks = rec["checks"]
    result = {
        "correct": all(c["value"] <= c["limit"] for c in checks.values()),
        "attempted": rec["attempted"],
        "failed": rec["failed"],
        "metrics": out_metrics,
        "device": device,
    }
    if args.trace:
        from bench import trace as tracing

        tr = rec.get("trace")
        if tr and tr["window"] and tr["ops"]:
            device["busy_s"] = tracing.busy_mean(tr)
            device["window_s"] = tr["window"][1] - tr["window"][0]
            result["breakdown"] = tracing.breakdown(tr)
        device["chips_traced"] = len(tr["ops"]) if tr else 0
    result["checks"] = checks
    result["_rec"] = rec
    return result


def main(argv=None) -> int:
    args = parse(argv)
    try:
        result = execute(args)
    except BenchError as e:
        log(f"bench: {e}")
        return 2
    rec = result.pop("_rec")
    log(f"end-to-end (all): {json.dumps({k: v for k, v in rec['e2e'].items()})}")
    if "counters" in rec:
        log(f"counters in window: {json.dumps(rec['counters'])}")
    for name, c in result["checks"].items():
        log(f"check {name}: {c['value']} (limit {c['limit']})")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    rc = main()
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(rc)
