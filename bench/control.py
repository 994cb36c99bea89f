#!/usr/bin/env python3
"""Readings that a cell's correctness limit is set from (not part of a
benchmark run).

    python3 bench/control.py --workload <cell> --seconds <s> --seeds 1,2,3 \
        [--readings int8,fp8]

For each seed, one run of the cell (a short window at the cell's own load
and sizes), then, on the same served requests or the same first training
round, the readings of the program (the lower reading comes from these)
and of each name in ``--readings`` put in the program's place: a control
(the reference computed with int8 or fp8 products; the upper reading) or,
for a training cell, a fault planted in the reference (``half_batch``,
``no_exchange``). Each number is as the cell's check computes it. All
seeds run in this one process; one JSON line per seed on standard output."""
from __future__ import annotations

import argparse
import json
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

import bench.run as run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--readings", default="int8,fp8")
    a = ap.parse_args(argv)
    readings = tuple(x for x in a.readings.split(",") if x)
    for seed in a.seeds.split(","):
        args = run.parse(["--workload", a.workload, "--seed", seed,
                          "--seconds", str(a.seconds), "--trace", "0"])
        res = run.execute(args, readings=readings)
        rec = res.pop("_rec")
        print(json.dumps({
            "seed": int(seed),
            "readings": rec["readings"],
            "checks": res["checks"],
            "correct": res["correct"],
            "e2e": rec["e2e"],
        }), flush=True)
    return 0


if __name__ == "__main__":
    rc = main()
    sys.stdout.flush()
    os._exit(rc)
