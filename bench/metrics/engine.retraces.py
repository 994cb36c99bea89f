"""Re-traces of the engine's jitted entry points in the traced window: the
``engine.retrace.*`` spans that start inside it, each one a new shape
traced and compiled while serving. A steady window reads 0. Like every
metric read from the device trace, it reads nothing from a trace with no
device plane (a CPU trace); nor from a program without the engine's spans
(no ``engine.step`` span in the window)."""


def read(rec):
    tr = rec.get("trace")
    if not tr or tr["window"] is None or not tr["ops"]:
        return None
    lo, hi = tr["window"]
    inside = [name for name, a, _ in tr["host"] if lo <= a < hi]
    if "engine.step" not in inside:
        return None
    return sum(name.startswith("engine.retrace.") for name in inside)
