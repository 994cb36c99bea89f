"""Whole federated step's share of the chips' bf16 peak: the operations the
window's trained tokens need (``bench/flops.py``, forward and backward, no
recompute) over window seconds times the chips' peak."""


def read(rec):
    if not rec.get("train_flops"):
        return None
    peak = rec["chips"] * rec["peaks"]["bf16_flops_per_s"]
    return 100.0 * rec["train_flops"] / (rec["window_s"] * peak)
