"""The engine's host spans and the two metrics that read them
(``engine.exposed_host_ms``, ``engine.retraces``): the readers on a small
hand-made trace whose answers can be worked out by hand, and the spans as a
whole serving run on the CPU records them."""
import re
from pathlib import Path

import pytest

from bench import lookup
from bench import trace as tracing

SRC = Path(__file__).resolve().parents[2] / "src"
CELL = "serve.mistral-nemo-12b.decode-heavy"
# the spans the benchmark itself wraps around the engine's dispatches
WRAPPERS = {"engine.decode", "engine.prefill_cold", "engine.prefill_suffix",
            "engine.copy_page"}


def _metric(name):
    return lookup.load_module(lookup.BENCH_DIR / "metrics" / f"{name}.py",
                              f"bench_metric_{name}")


EXPOSED = _metric("engine.exposed_host_ms")
RETRACES = _metric("engine.retraces")

# window [0, 10] s; two decode steps, each inside the benchmark's bench.step,
# with the benchmark's stamping between them and its engine.decode wrapper
# inside each engine.dispatch_decode
HOST = [
    (tracing.WINDOW_SPAN, 0.0, 10.0),
    ("bench.step", 0.1, 3.0),
    ("engine.step", 0.2, 2.9),
    ("engine.admit", 0.21, 0.24),
    ("engine.dispatch_decode", 0.25, 0.5),
    ("engine.decode", 0.3, 0.45),
    ("engine.read_tokens", 0.5, 2.5),
    ("engine.emit", 2.5, 2.9),
    ("bench.stamp", 3.0, 4.0),
    ("bench.step", 4.0, 9.0),
    ("engine.step", 4.05, 8.9),
    ("engine.dispatch_decode", 4.1, 6.0),
    ("engine.decode", 4.7, 5.4),
    ("engine.read_tokens", 6.0, 8.5),
    ("engine.emit", 8.5, 8.9),
    ("engine.retire", 8.6, 8.7),
]


def _trace(busy, host=HOST):
    """One chip whose operations cover ``busy``; a layer loop (while) over
    the first one spans it and is not work of its own."""
    ops = [("while.1 (s32[]", busy[0][0], busy[0][1])]
    ops += [(f"fusion.{i} bf16[8]", a, b) for i, (a, b) in enumerate(busy)]
    return {"ops": [ops], "modules": [[]], "host": list(host),
            "window": (0.0, 10.0)}


def _approx(d):
    return {k: pytest.approx(v) for k, v in d.items()}


def test_an_idle_interval_is_split_at_every_span_edge():
    # the one idle interval, [2.0, 4.2], opens under engine.read_tokens,
    # runs on under bench.stamp and closes under engine.dispatch_decode
    tr = _trace([(0.0, 2.0), (4.2, 10.0)])
    assert EXPOSED.idle_by_span(tr) == _approx({
        "engine.read_tokens": 0.5, "engine.emit": 0.4, "bench.step": 0.15,
        "bench.stamp": 1.0, "engine.step": 0.05,
        "engine.dispatch_decode": 0.1})
    # engine spans 1.05 s over the window's two decode steps
    assert EXPOSED.read({"trace": tr}) == pytest.approx(525.0)


def test_a_gap_under_a_nested_wrapper_counts_once():
    tr = _trace([(0.0, 4.8), (5.2, 10.0)])
    assert EXPOSED.idle_by_span(tr) == _approx({"engine.decode": 0.4})
    assert EXPOSED.read({"trace": tr}) == pytest.approx(200.0)


def test_every_idle_second_goes_to_exactly_one_span():
    tr = _trace([(0.3, 2.0), (4.2, 4.8), (5.2, 6.5), (9.5, 10.5)])
    got = EXPOSED.idle_by_span(tr)
    assert got == _approx({
        "none": 0.6, "bench.step": 0.35, "bench.stamp": 1.0,
        "engine.step": 0.07, "engine.admit": 0.03,
        "engine.dispatch_decode": 0.15, "engine.decode": 0.4,
        "engine.read_tokens": 2.5, "engine.emit": 0.7, "engine.retire": 0.1})
    assert sum(got.values()) == pytest.approx(10.0 * tracing.idle_share(tr))
    engine = 0.07 + 0.03 + 0.15 + 0.4 + 2.5 + 0.7 + 0.1
    assert EXPOSED.read({"trace": tr}) == pytest.approx(1e3 * engine / 2)


def test_retraces_count_only_spans_that_start_in_the_window():
    host = HOST + [
        ("engine.retrace.decode", -0.5, -0.2),         # before the window
        ("engine.retrace.prefill_slots", -0.1, 0.05),  # opens before it
        ("engine.retrace.decode", 4.3, 4.4),
        ("engine.retrace.prefill_suffix", 9.9, 10.2),
        ("engine.retrace.decode", 10.5, 10.6),         # after it
    ]
    tr = _trace([(0.0, 10.0)], host)
    assert RETRACES.read({"trace": tr}) == 2
    assert RETRACES.read({"trace": _trace([(0.0, 10.0)])}) == 0


def test_readers_read_nothing_without_the_engines_spans():
    for reader in (EXPOSED.read, RETRACES.read):
        assert reader({}) is None
        assert reader({"trace": None}) is None
        assert reader({"trace": {**_trace([(0.0, 1.0)]), "window": None}}) is None
    # a program whose engine has no spans of its own: only the benchmark's
    bare = [h for h in HOST
            if h[0].startswith("bench.") or h[0] in WRAPPERS]
    tr = _trace([(0.3, 2.0), (4.2, 4.8)], bare)
    assert EXPOSED.read({"trace": tr}) is None
    assert RETRACES.read({"trace": tr}) is None
    # no device plane (a CPU trace): no idle time to share out, and no
    # device clock for the window the re-traces are counted in
    for reader in (EXPOSED.read, RETRACES.read):
        assert reader({"trace": {**_trace([(0.0, 1.0)]), "ops": []}}) is None


def _src_span_names() -> set[str]:
    names = set()
    for path in SRC.rglob("*.py"):
        text = path.read_text()
        names |= set(re.findall(r'(?:_span|TraceAnnotation)\(\s*"([^"]+)"', text))
    return names


def test_traced_serving_run_records_the_engines_spans(tiny_run):
    res = tiny_run(CELL, trace=1)
    assert res["correct"], res["checks"]
    tr = res["_rec"]["trace"]
    lo, hi = tr["window"]
    host = [h for h in tr["host"] if lo <= h[1] < hi]
    steps = [h for h in host if h[0] == "engine.step"]
    assert steps

    def inside(step, name):
        return [h for h in host if h[0] == name
                and step[1] <= h[1] <= h[2] <= step[2]]

    decoding = [s for s in steps if inside(s, "engine.dispatch_decode")]
    assert decoding
    for s in decoding:
        (d,), (r,), (e,) = (inside(s, n) for n in (
            "engine.dispatch_decode", "engine.read_tokens", "engine.emit"))
        assert d[2] <= r[1] and r[2] <= e[1]
        # the benchmark's wrapper stays innermost around the decode call
        (w,) = inside(s, "engine.decode")
        assert d[1] <= w[1] <= w[2] <= d[2]
    # every shape was compiled in set-up: the window holds no re-trace span.
    # A CPU trace has no device plane, so the run reports neither metric;
    # given one, the re-trace count reads 0
    assert not [h for h in host if h[0].startswith("engine.retrace.")]
    assert not {"engine.retraces", "engine.exposed_host_ms"} & set(res["metrics"])
    assert RETRACES.read({"trace": {**tr, "ops": [[("fusion.0", lo, hi)]]}}) == 0
    # no span of the program's takes a name the benchmark's wrappers use
    src = _src_span_names()
    assert {"engine.step", "engine.dispatch_decode", "engine.retrace.decode"} <= src
    assert not src & WRAPPERS
    recorded = {h[0] for h in host} - WRAPPERS
    assert {n for n in recorded if n.startswith("engine.")} <= src
