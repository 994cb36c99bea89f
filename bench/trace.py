"""Profiler capture and the reduction from a trace to numbers.

A trace is reduced to plain intervals first (``load``), so the arithmetic
below works on lists and is tested on a small hand-made trace:

* ``ops``: per chip, device operations ``(name, start_s, end_s)``;
* ``modules``: per chip, whole XLA programs ``(name, start_s, end_s)``;
* ``host``: the benchmark's host spans ``(name, start_s, end_s)``;
* ``window``: the traced window, from the host span ``WINDOW_SPAN``.

All times are seconds on the profiler's clock."""
from __future__ import annotations

import glob
import os
import shutil

WINDOW_SPAN = "bench.traced_window"
COLLECTIVE_WORDS = ("all-reduce", "all-gather", "reduce-scatter",
                    "all-to-all", "collective-permute")


# ---------------------------------------------------------------- capture
def start(path: str) -> None:
    import jax

    shutil.rmtree(path, ignore_errors=True)
    os.makedirs(path, exist_ok=True)
    jax.profiler.start_trace(path)


def stop() -> None:
    import jax

    jax.profiler.stop_trace()


class CompileCount:
    """XLA compiles (cache loads included) seen by ``jax.monitoring``."""

    def __init__(self):
        import jax

        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1


def load(path: str) -> dict:
    """Read the newest ``.xplane.pb`` under ``path`` into intervals."""
    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(path, "plugins", "profile", "*",
                                          "*.xplane.pb")))
    if not files:
        return {"ops": [], "modules": [], "host": [], "window": None}
    pd = ProfileData.from_file(files[-1])
    ops, modules, host = [], [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:") and "NON_CORE" not in plane.name:
            lines = {line.name: line for line in plane.lines}
            ops.append(_events(lines.get("XLA Ops"), rename=True))
            modules.append(_events(lines.get("XLA Modules")))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host += [e for e in _events(line)
                         if e[0].startswith(("bench.", "engine."))]
    win = [e for e in host if e[0] == WINDOW_SPAN]
    window = (win[0][1], win[0][2]) if win else None
    return {"ops": ops, "modules": modules, "host": host, "window": window}


def op_name(text: str) -> str:
    """An XLA op event's name is its whole HLO instruction; keep the
    instruction's name and result type (``paged_decode.10 bf16[16,32,1,64]``),
    so that matching a kernel never hits an op that merely reads its
    output."""
    if " = " not in text:
        return text
    name, rest = text.split(" = ", 1)
    return f"{name.lstrip('%')} {rest.split(' ', 1)[0].split('{', 1)[0]}"


def _events(line, rename=False) -> list:
    if line is None:
        return []
    return [(op_name(e.name) if rename else e.name, e.start_ns * 1e-9,
             (e.start_ns + e.duration_ns) * 1e-9) for e in line.events]


# -------------------------------------------------------------- reduction
def clip(events, window):
    lo, hi = window
    return [(n, max(a, lo), min(b, hi)) for n, a, b in events if b > lo and a < hi]


def union(intervals) -> list[tuple[float, float]]:
    """Merged (start, end) pairs of ``(name, start, end)`` or pairs."""
    spans = sorted((iv[-2], iv[-1]) for iv in intervals)
    out: list[list[float]] = []
    for a, b in spans:
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def busy_seconds(ops, window) -> float:
    """Seconds in which some operation ran; control-flow wrappers do not
    count, only the operations they run."""
    return sum(b - a for a, b in union(
        e for e in clip(ops, window) if not _wrapper(e[0])))


def busy_mean(tr: dict) -> float:
    """Device busy seconds in the window, averaged over the chips traced."""
    per_chip = [busy_seconds(o, tr["window"]) for o in tr["ops"]]
    return sum(per_chip) / len(per_chip) if per_chip else 0.0


def idle_share(tr: dict) -> float | None:
    if not tr["ops"] or tr["window"] is None:
        return None
    span = tr["window"][1] - tr["window"][0]
    return 1.0 - busy_mean(tr) / span


def matching(events, words) -> list:
    """Events whose name (for ops: the instruction's name) holds a word."""
    words = (words,) if isinstance(words, str) else tuple(words)
    return [e for e in events if any(w in e[0].split(" ", 1)[0] for w in words)]


def op_seconds(tr: dict, words) -> tuple[float, int]:
    """Total device time and count of the operations whose name holds any
    of ``words``, over all chips, inside the window."""
    evs = [e for o in tr["ops"] for e in matching(clip(o, tr["window"]), words)]
    return sum(b - a for _, a, b in evs), len(evs)


def module_seconds(tr: dict, words) -> tuple[float, int]:
    evs = [e for m in tr["modules"] for e in matching(clip(m, tr["window"]), words)]
    return sum(b - a for _, a, b in evs), len(evs)


def exposed_collective(ops, window) -> float:
    """Seconds in which a collective runs on this chip and no other
    operation does."""
    ops = clip(ops, window)
    coll_ops = matching(ops, COLLECTIVE_WORDS)
    coll = union(coll_ops)
    other = union([e for e in ops if e not in coll_ops and not _wrapper(e[0])])
    exposed = 0.0
    for a, b in coll:
        covered = sum(max(0.0, min(b, d) - max(a, c)) for c, d in other)
        exposed += (b - a) - covered
    return exposed


def idle_gaps(tr: dict, chip: int = 0) -> list[tuple[str, float]]:
    """Idle gaps of one chip inside the window, each labelled by the
    innermost host span open at its midpoint ("none" if none is)."""
    lo, hi = tr["window"]
    busy = union(e for e in clip(tr["ops"][chip], tr["window"])
                 if not _wrapper(e[0])) if tr["ops"] else []
    edges = [lo] + [t for iv in busy for t in iv] + [hi]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    spans = [h for h in tr["host"] if h[0] != WINDOW_SPAN]
    out = []
    for a, b in gaps:
        mid = (a + b) / 2
        open_ = [h for h in spans if h[1] <= mid <= h[2]]
        label = max(open_, key=lambda h: h[1])[0] if open_ else "none"
        out.append((label, b - a))
    return out


def _wrapper(name: str) -> bool:
    """Control-flow ops (a layer loop, a branch) whose events span the ops
    they run: they count toward busy time, not as work of their own."""
    return name.startswith(("while", "conditional", "call"))


def breakdown(tr: dict, n: int = 10) -> dict:
    """The device operations that took most time (control-flow wrappers
    left out), and the longest idle gaps by what the host was doing
    (chip 0)."""
    totals: dict[str, float] = {}
    for o in tr["ops"]:
        for name, a, b in clip(o, tr["window"]):
            if _wrapper(name):
                continue
            totals[name] = totals.get(name, 0.0) + (b - a)
    ops = sorted(totals.items(), key=lambda kv: -kv[1])[:n]
    gaps = sorted(idle_gaps(tr), key=lambda g: -g[1])[:n]
    return {"device_ops": [[k, v] for k, v in ops],
            "idle_gaps": [[k, v] for k, v in gaps]}
