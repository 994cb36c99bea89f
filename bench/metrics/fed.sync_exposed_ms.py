"""Cross-chip time per sync round that no computation hides: in the traced
window, the seconds in which a collective ran on a chip and nothing else
did (averaged over the chips), over the sync rounds the window's steps
held. The local steps' own small collectives (the mean loss) count too."""
from bench import trace as tracing


def read(rec):
    tr, traced = rec.get("trace"), rec.get("traced")
    if not tr or tr["window"] is None or not tr["ops"] or not traced or not traced["syncs"]:
        return None
    exposed = [tracing.exposed_collective(o, tr["window"]) for o in tr["ops"]]
    return 1e3 * sum(exposed) / len(exposed) / traced["syncs"]
