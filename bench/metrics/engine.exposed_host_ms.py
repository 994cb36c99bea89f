"""Engine host work the chip waits on, per decode step. Chip 0's idle time
in the traced window (the window less the union of its operations) is cut
at every host-span edge, and each piece goes to the innermost host span
open over it, the one that started last. The pieces under an ``engine.*``
span are summed: the engine's own phases, and the benchmark's ``engine.*``
spans around the engine's dispatches, which time the engine's work too.
The sum is divided by the window's ``engine.dispatch_decode`` spans, one a
decode step. A program without that span reads nothing."""
from bench import trace as tracing

DISPATCH = "engine.dispatch_decode"


def idle_by_span(tr: dict, chip: int = 0) -> dict[str, float]:
    """Idle seconds of one chip in the traced window, by the innermost host
    span open over them ("none" where no span is open)."""
    lo, hi = tr["window"]
    busy = tracing.union(e for e in tracing.clip(tr["ops"][chip], tr["window"])
                         if not tracing._wrapper(e[0]))
    edges = [lo] + [t for iv in busy for t in iv] + [hi]
    idle = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    spans = [h for h in tracing.clip(tr["host"], tr["window"])
             if h[0] != tracing.WINDOW_SPAN]
    # each span's start and end, in time order; a span opens at its first
    # edge and closes at its second
    marks = sorted([(a, k) for k, (_, a, _) in enumerate(spans)]
                   + [(b, k) for k, (_, _, b) in enumerate(spans)])
    open_: dict[int, float] = {}
    out: dict[str, float] = {}

    def toggle(k):
        if open_.pop(k, None) is None:
            open_[k] = spans[k][1]

    def charge(seconds):
        if seconds <= 0:
            return
        name = spans[max(open_, key=lambda k: (open_[k], k))][0] if open_ else "none"
        out[name] = out.get(name, 0.0) + seconds

    j = 0
    for a, b in idle:
        while j < len(marks) and marks[j][0] <= a:
            toggle(marks[j][1])
            j += 1
        t = a
        while j < len(marks) and marks[j][0] < b:
            charge(marks[j][0] - t)
            t = marks[j][0]
            toggle(marks[j][1])
            j += 1
        charge(b - t)
    return out


def read(rec):
    tr = rec.get("trace")
    if not tr or tr["window"] is None or not tr["ops"]:
        return None
    lo, hi = tr["window"]
    steps = sum(1 for name, a, _ in tr["host"] if name == DISPATCH and lo <= a < hi)
    if not steps:
        return None
    idle = idle_by_span(tr)
    return 1e3 * sum(v for k, v in idle.items() if k.startswith("engine.")) / steps
