"""The benchmark's CPU tests import ``bench`` from the checkout's root."""
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

import pytest  # noqa: E402

# the benchmark's configurations cut to a size the CPU runs in seconds;
# grouped-query attention kept (2 key/value heads for 4 query heads)
TINY_CONFIG = {"hidden_size": 128, "intermediate_size": 352,
               "num_hidden_layers": 2, "num_attention_heads": 4,
               "num_key_value_heads": 2, "head_dim": 32, "vocab_size": 512}
# short prompts and long outputs, so that most of a request's context is
# what it generated: a fault in the decode step shows in the logits
TINY_TRAFFIC = {
    "decode-heavy": {
        "clients": 4, "slots": 4, "block": 4,
        "prompt": {"median": 4, "sigma": 0.5, "min": 2, "max": 8},
        "output": {"median": 48, "sigma": 0.2, "min": 32, "max": 64},
        "max_context": 128, "pool": {"pages": 64},
        "warm": {"cold_widths": [1, 2], "cold_extra": [[1, 128]]},
        "check": {"tokens": 60, "rows": 4, "length": 512},
        "trace": {"offset_s": 0.3, "seconds": 0.6}},
}
CPU_PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}


@pytest.fixture
def tiny_run():
    """Run a cell through the whole harness at a tiny size on the CPU, the
    chip check skipped; the cell's own correctness limits apply."""
    import bench.run as run
    from bench import lookup

    def go(cell, *, seed=2**33 + 3, seconds=3.0, trace=0, **kw):
        mix = lookup.workload(lookup.manifest(), cell)["traffic"]
        traffic = dict(TINY_TRAFFIC[mix])
        limits = {k: v for k, v in lookup.traffic(mix)["check"].items()
                  if k.startswith("limit")}
        traffic["check"] = {**traffic["check"], **limits}
        args = run.parse(["--workload", cell, "--seed", str(seed),
                          "--seconds", str(seconds), "--trace", str(trace)])
        return run.execute(args, chip_check=False, overrides={
            "config": TINY_CONFIG, "traffic": traffic, "peaks": CPU_PEAKS}, **kw)

    return go
