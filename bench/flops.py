"""Operations and bytes the work needs, from shapes alone.

These are the benchmark's own counts: what a dense decoder (the sizes of a
configuration file) must compute for each token it serves, and what the
paged-decode and suffix-prefill attention kernels must compute and move
for one call. Roofline and MFU shares divide them by measured time."""
from __future__ import annotations

import math


def dims(cfg: dict) -> dict:
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    return {"L": cfg["num_hidden_layers"], "d": d, "H": h,
            "Hkv": cfg["num_key_value_heads"], "hd": cfg.get("head_dim") or d // h,
            "f": cfg["intermediate_size"], "V": cfg["vocab_size"],
            "bytes": 2 if cfg["torch_dtype"] in ("bfloat16", "float16") else 4}


def body_flops_per_token(cfg: dict) -> float:
    """Projections of every layer (q, k, v, o and the gated MLP)."""
    n = dims(cfg)
    per_layer = 2 * n["d"] * (2 * n["H"] * n["hd"] + 2 * n["Hkv"] * n["hd"]) \
        + 2 * 3 * n["d"] * n["f"]
    return n["L"] * per_layer


def head_flops(cfg: dict) -> float:
    """Output projection for one position."""
    n = dims(cfg)
    return 2 * n["d"] * n["V"]


def attn_flops(cfg: dict, keys: int) -> float:
    """Scores and weighted values of one query over ``keys`` keys, all
    layers."""
    n = dims(cfg)
    return n["L"] * 4 * n["H"] * n["hd"] * keys


def decode_token_flops(cfg: dict, keys: int) -> float:
    """One served token from the decode step, attending ``keys`` keys."""
    return body_flops_per_token(cfg) + head_flops(cfg) + attn_flops(cfg, keys)


def prefill_flops(cfg: dict, start: int, length: int) -> float:
    """Prefill of ``length`` tokens at positions ``start..start+length-1``
    (``start`` of them already cached), with the logits of the last."""
    keys = length * start + length * (length + 1) // 2
    return (length * body_flops_per_token(cfg) + head_flops(cfg)
            + attn_flops(cfg, 1) * keys)


# ---------------------------------------------------------------- kernels
def paged_decode(cfg: dict, contexts, page: int) -> tuple[float, float]:
    """(flops, bytes) of the paged-decode kernel over all layers for one
    step: each row's query attends its ``contexts[r]`` keys, read page by
    page from the pool, and writes one output."""
    n = dims(cfg)
    flops = sum(attn_flops(cfg, c) for c in contexts)
    kv_row = 2 * n["Hkv"] * n["hd"] * n["bytes"]            # k and v, one token
    qo_row = 2 * n["H"] * n["hd"] * n["bytes"]               # q in, out
    pages = sum(math.ceil(c / page) for c in contexts)
    return flops, n["L"] * (pages * page * kv_row + len(contexts) * qo_row)


def suffix_prefill(cfg: dict, rows, page: int) -> tuple[float, float]:
    """(flops, bytes) of the suffix-prefill kernel over all layers for one
    call: ``rows`` are (start, length) with ``start`` tokens cached in whole
    pages; each suffix query attends the prefix and the causal suffix."""
    n = dims(cfg)
    kv_tok = 2 * n["Hkv"] * n["hd"] * n["bytes"]
    q_tok = 2 * n["H"] * n["hd"] * n["bytes"]                # q in, out
    flops = byt = 0.0
    for start, length in rows:
        flops += attn_flops(cfg, 1) * (length * start + length * (length + 1) // 2)
        byt += n["L"] * (math.ceil(start / page) * page * kv_tok
                         + length * (kv_tok + q_tok))
    return flops, byt


def roofline_seconds(flops: float, byt: float, peaks: dict) -> tuple[float, str]:
    """Least time the chip could take, and which bound sets it."""
    tc = flops / peaks["bf16_flops_per_s"]
    tm = byt / peaks["hbm_bytes_per_s"]
    return (tc, "compute") if tc >= tm else (tm, "memory")


# ------------------------------------------------------------- training
def xlstm_train_flops_per_token(cfg: dict, seq: int) -> float:
    """Matrix-product operations an xLSTM (the sizes of an xLSTM
    configuration file) needs to train on one token of a ``seq``-long row:
    three times the forward's (the backward takes two), no recompute. The
    forward: every projection, the output head, the recurrent block-diagonal
    products of the sLSTM blocks, and the parallel mLSTM form's scores and
    weighted values over the ``(seq + 1) / 2`` keys a token sees on
    average."""
    d, heads, V = cfg["hidden_size"], cfg["num_heads"], cfg["vocab_size"]
    inner = cfg["mlstm_proj_factor"] * d
    ff = max(64, int(d * 4 / 3) // 64 * 64)
    period, layers = cfg["slstm_every"], cfg["num_hidden_layers"]
    n_s = layers // period
    n_m = layers - n_s
    mlstm = d * 2 * inner + 3 * inner * inner + 2 * inner * heads + inner * d
    slstm = 4 * d * d + 4 * d * d // heads + d * 2 * ff + ff * d
    fwd = 2 * (n_m * mlstm + n_s * slstm + d * V) + n_m * 4 * inner * (seq + 1) / 2
    return 3.0 * fwd
