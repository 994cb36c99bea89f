"""Share of the traced window in which no operation ran on a chip:
1 - (union of device operation intervals) / window, averaged over the
chips."""
from bench import trace as tracing


def read(rec):
    tr = rec.get("trace")
    if not tr or tr["window"] is None or not tr["ops"]:
        return None
    return 100.0 * tracing.idle_share(tr)
