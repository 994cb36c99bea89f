"""Child process of ``test_bench_train_faults.py``: runs the training cell
(``fed.xlstm-125m.pod4``, under the manifest entries below) on four virtual
CPU devices at a tiny size, once sound, once with each planted fault and
once with the control in the program's place, and prints each run's
``correct`` and checks as one JSON line."""
import json
import os
import sys
from pathlib import Path

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
os.environ["JAX_PLATFORMS"] = "cpu"
ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

import bench.run as run  # noqa: E402
from bench import lookup  # noqa: E402

CELL = "fed.xlstm-125m.pod4"
# the manifest entries the training cell runs under; the cell is not in
# BENCHMARK.json while its chip readings fail (PERF.md, Open questions)
MANIFEST_ENTRIES = {
    "configs": [{"name": "xlstm-125m", "file": "bench/configs/xlstm-125m.json"}],
    "workloads": [{"name": CELL, "config": "xlstm-125m", "traffic": "pod4", "chips": 4}],
    "end_to_end": [{"name": "train_tok_s", "unit": "tokens/s", "workloads": [CELL]}],
    "per_layer": [{"name": n, "unit": u, "moves": "train_tok_s", "workloads": [CELL]}
                  for n, u in (("train.mfu", "%"), ("fed.sync_exposed_ms", "ms"),
                               ("device.idle_share.train", "%"))],
}
# a vocabulary the program stores padded, as it stores the real one
TINY_CONFIG = {"hidden_size": 128, "num_hidden_layers": 4, "num_heads": 4,
               "vocab_size": 500}
TINY_JOB = {"per_cloud_batch": 2, "seq": 64, "trace": {"offset_s": 0.2, "seconds": 0.5}}


def stale_state(trainer, fn):
    """The step returns its state unchanged."""
    return lambda s, b: (s, fn(s, b)[1])


def half_batch(trainer, fn):
    """Half of each cloud's batch is left out, the mean taken over the
    rest."""
    import jax

    return lambda s, b: fn(s, jax.tree_util.tree_map(lambda x: x[:, : x.shape[1] // 2], b))


def no_exchange(trainer, fn):
    """The sync round's exchange between chips is left out: no cloud sends
    its change, the global model stays as it was."""
    trainer._sync = lambda state, arrived, alphas: state
    return fn


def main() -> None:
    man = lookup.manifest()
    man = {k: (man[k] + MANIFEST_ENTRIES[k] if k in MANIFEST_ENTRIES else man[k]) for k in man}
    out = {}
    variants = {"sound": {}, "stale_state": {"breaker": stale_state},
                "half_batch": {"breaker": half_batch},
                "no_exchange": {"breaker": no_exchange}, "fp8": {"control": "fp8"}}
    for name, kw in variants.items():
        args = run.parse(["--workload", CELL, "--seed", str(2**33 + 5),
                          "--seconds", "1", "--trace", "1" if name == "sound" else "0"])
        res = run.execute(args, chip_check=False, overrides={
            "manifest": man, "config": TINY_CONFIG, "traffic": TINY_JOB,
            "peaks": {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}}, **kw)
        out[name] = {"correct": res["correct"], "checks": res["checks"],
                     "metrics": sorted(res["metrics"]), "attempted": res["attempted"]}
    print(json.dumps(out))


if __name__ == "__main__":
    main()
