"""Whole serving step's share of the chip's bf16 peak: the operations the
window's served tokens need (prefill of every admitted prompt, one decode
step per later token; ``bench/flops.py``) over window seconds times peak."""


def read(rec):
    if not rec["serve_flops"]:
        return None
    return 100.0 * rec["serve_flops"] / (rec["window_s"] * rec["peaks"]["bf16_flops_per_s"])
