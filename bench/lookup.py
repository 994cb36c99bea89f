"""Find each piece of a cell by its name in ``BENCHMARK.json``.

* configuration ``<c>``: ``bench/configs/<c>.json`` (sizes as run) and
  ``bench/configs/<c>.py`` (its plain reference and weight maker);
* traffic mix ``<t>``: ``bench/traffic/<t>.json`` (parameters only; its
  ``runner`` key names ``bench/runners/<runner>.py``);
* per-layer metric ``<m>``: ``bench/metrics/<m>.py`` (a ``read(rec)``);
* peaks: ``bench/peaks.json``, keyed by ``device_kind``.

Adding a cell or a metric adds files and a manifest entry; nothing here
changes."""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent


class BenchError(Exception):
    """A refusal: the run prints no result and exits non-zero."""


def manifest(root: Path = ROOT) -> dict:
    path = root / "BENCHMARK.json"
    if not path.is_file():
        raise BenchError(f"no BENCHMARK.json at {root}")
    return json.loads(path.read_text())


def workload(man: dict, name: str) -> dict:
    for w in man["workloads"]:
        if w["name"] == name:
            return w
    known = ", ".join(w["name"] for w in man["workloads"])
    raise BenchError(f"unknown workload {name!r}; known: {known}")


def _json(kind: str, name: str) -> dict:
    path = BENCH_DIR / kind / f"{name}.json"
    if not path.is_file():
        raise BenchError(f"no {kind} file {path.relative_to(ROOT)}")
    return json.loads(path.read_text())


def config(name: str) -> dict:
    return _json("configs", name)


def traffic(name: str) -> dict:
    return _json("traffic", name)


def load_module(path: Path, name: str):
    if not path.is_file():
        raise BenchError(f"no module {path.relative_to(ROOT)}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reference(config_name: str):
    """The configuration's plain reference (``bench/configs/<c>.py``)."""
    return load_module(BENCH_DIR / "configs" / f"{config_name}.py",
                       f"bench_ref_{config_name}")


def runner(name: str):
    return load_module(BENCH_DIR / "runners" / f"{name}.py",
                       f"bench_runner_{name}")


def metric_reader(name: str):
    return load_module(BENCH_DIR / "metrics" / f"{name}.py",
                       f"bench_metric_{name}").read


def peaks(device_kind: str) -> dict:
    table = json.loads((BENCH_DIR / "peaks.json").read_text())
    if device_kind not in table["devices"]:
        raise BenchError(
            f"device kind {device_kind!r} is not in bench/peaks.json "
            f"(known: {sorted(table['devices'])})"
        )
    return table["devices"][device_kind]


def cell_metrics(man: dict, cell: str, trace: bool) -> list[dict]:
    """The metrics a run of ``cell`` reports: its end-to-end metrics, or
    with ``trace`` its per-layer ones."""
    group = man["per_layer"] if trace else man["end_to_end"]
    return [m for m in group if cell in m.get("workloads", [cell])]
