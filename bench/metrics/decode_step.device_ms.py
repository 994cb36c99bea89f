"""Device time of the engine's decode program per call, from the trace:
the XLA program the jitted decode step compiles to."""
from bench import trace as tracing

PROGRAM = "_decode_fn"


def read(rec):
    tr = rec.get("trace")
    if not tr or tr["window"] is None:
        return None
    secs, n = tracing.module_seconds(tr, PROGRAM)
    return 1e3 * secs / n if n else None
