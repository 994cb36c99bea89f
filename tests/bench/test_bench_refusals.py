"""The harness refuses, printing no result, where it cannot measure."""
import json
import os
import shutil
import subprocess
import sys

import pytest

from bench import lookup
from bench.lookup import ROOT, BenchError

CELL = json.loads((ROOT / "BENCHMARK.json").read_text())["workloads"][0]["name"]


def run(cwd, *args):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", *args], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=300,
    )


def test_no_chip_exits_nonzero_without_a_result():
    p = run(ROOT, "--workload", CELL, "--seed", "1", "--seconds", "1", "--trace", "0")
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_unknown_workload_is_refused():
    p = run(ROOT, "--workload", "no.such.cell", "--seed", "1", "--seconds", "1")
    assert p.returncode != 0 and p.stdout.strip() == ""
    assert "unknown workload" in p.stderr


def test_unknown_device_kind_is_an_error():
    with pytest.raises(BenchError, match="not in bench/peaks.json"):
        lookup.peaks("TPU v99 imaginary")
    assert lookup.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12


def test_benchmark_files_alone_are_refused(tmp_path):
    """A checkout holding only BENCHMARK.json and the benchmark's paths has
    no program to run."""
    man = lookup.manifest()
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    for p in man["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    p = run(tmp_path, "--workload", CELL, "--seed", "1", "--seconds", "1")
    assert p.returncode != 0 and p.stdout.strip() == ""


def test_every_name_in_the_manifest_resolves():
    man = lookup.manifest()
    for cell in man["workloads"]:
        lookup.config(cell["config"])
        lookup.reference(cell["config"])
        lookup.runner(lookup.traffic(cell["traffic"])["runner"])
        assert lookup.cell_metrics(man, cell["name"], trace=False)
        assert lookup.cell_metrics(man, cell["name"], trace=True)
    for m in man["per_layer"]:
        assert callable(lookup.metric_reader(m["name"]))
