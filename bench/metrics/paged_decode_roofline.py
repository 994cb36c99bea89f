"""Paged-decode kernel's share of its roofline: for the decode steps in the
traced window, the least time the chip needs for the kernel's operations
and bytes (each row's live keys, read page by page; ``bench/flops.py``)
over the kernel's device time in the trace. Memory-bound at these sizes."""
from bench import flops
from bench import trace as tracing

KERNEL = ("_paged_kernel", "paged_decode")


def read(rec):
    tr, traced = rec.get("trace"), rec.get("traced")
    if not tr or tr["window"] is None or not traced:
        return None
    secs, n = tracing.op_seconds(tr, KERNEL)
    steps = [k for k in traced["decode_keys"] if k]
    if not n or not steps:
        return None
    fl = by = 0.0
    for keys in steps:
        f, b = flops.paged_decode(rec["cfg"], keys, rec["page"])
        fl, by = fl + f, by + b
    t, _ = flops.roofline_seconds(fl, by, rec["peaks"])
    return 100.0 * t / secs
