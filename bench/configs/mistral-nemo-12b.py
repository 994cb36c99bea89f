"""Plain reference of the dense decoder that ``mistral-nemo-12b.json`` states.

Written from Mistral's published architecture (``MistralForCausalLM``),
not from the program: embedding lookup; per layer RMSNorm with weight g,
grouped-query attention (32 query heads over 8 key/value heads, head 128,
query head h reads key/value head h // 4) with rotary position embedding
over the whole head (rotate-half form, theta from the config), causal
softmax scaled by 1/sqrt(head_dim), output projection, residual; RMSNorm,
SwiGLU feed-forward, residual; final RMSNorm and an untied output
projection. No biases, no sliding window.

* ``make_weights`` draws the weights from a key, in bf16 as they are
  served, in one call (the harness jits it on the device). A norm weight
  is stored as its offset from one, ``w = g - 1``, which is the form the
  program keeps; the reference forms ``g = 1 + w`` exactly in float32.
* ``program_params`` only re-nests those arrays into the parameter tree
  the program takes; it copies nothing and computes nothing.
* ``logits_at`` runs the reference in float32 at ``Precision.HIGHEST``
  over one sequence (prompt and served tokens) and returns, at each
  position that predicts a served token, the reference's best logit, its
  logit of the served token, and, with ``control="int8"`` (or ``"fp8"``,
  e4m3), the token that a second forward with every projection in that
  precision (weights per output channel, activations per row) ranks
  first, that control's logit of it and the reference's logit of it: the
  precision below the config's bf16, put in the program's place.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
F32 = jnp.float32
Q_CHUNK = 512


def dims(cfg: dict) -> dict:
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    return {
        "L": cfg["num_hidden_layers"], "d": d, "H": h,
        "Hkv": cfg["num_key_value_heads"], "hd": cfg.get("head_dim") or d // h,
        "f": cfg["intermediate_size"], "V": cfg["vocab_size"],
    }


def make_weights(cfg: dict, key) -> dict:
    n = dims(cfg)
    L, d, H, Hkv, hd, f, V = (n[k] for k in ("L", "d", "H", "Hkv", "hd", "f", "V"))
    ks = iter(jax.random.split(key, 12))
    out_scale = (2.0 * L) ** -0.5

    def w(shape, std):
        return (jax.random.normal(next(ks), shape, F32) * std).astype(jnp.bfloat16)

    return {
        "tok": w((V, d), 1.0),
        "ln1": w((L, d), 0.1),
        "wq": w((L, d, H * hd), d ** -0.5),
        "wk": w((L, d, Hkv * hd), d ** -0.5),
        "wv": w((L, d, Hkv * hd), d ** -0.5),
        "wo": w((L, H * hd, d), (H * hd) ** -0.5 * out_scale),
        "ln2": w((L, d), 0.1),
        "w_gate": w((L, d, f), d ** -0.5),
        "w_up": w((L, d, f), d ** -0.5),
        "w_down": w((L, f, d), f ** -0.5 * out_scale),
        "ln_f": w((d,), 0.1),
        "unembed": w((d, V), d ** -0.5),
    }


def program_params(w: dict) -> dict:
    return {
        "embed": {"tok": w["tok"], "unembed": w["unembed"]},
        "layers": {
            "ln1": {"scale": w["ln1"]},
            "attn": {k: w[k] for k in ("wq", "wk", "wv", "wo")},
            "ln2": {"scale": w["ln2"]},
            "ffn": {k: w[k] for k in ("w_gate", "w_up", "w_down")},
        },
        "ln_f": {"scale": w["ln_f"]},
    }


# ------------------------------------------------------------------ maths
def _rms(x, w, eps):
    """RMSNorm with weight g = 1 + w (``w`` the stored offset)."""
    g = 1.0 + w.astype(F32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def _rope(x, theta):
    """x (S, heads, hd): rotate the two halves of every head."""
    s, _, hd = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd)
    ang = jnp.arange(s, dtype=F32)[:, None] * inv          # (S, hd/2)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _quant(x, axis, kind):
    """Symmetric int8, or float8 e4m3, with one scale per slice along
    ``axis``; returns the low-precision values and their scales."""
    top = 127.0 if kind == "int8" else 448.0
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / top
    s = jnp.where(s == 0, 1.0, s)
    if kind == "int8":
        return jnp.clip(jnp.round(x / s), -127, 127).astype(jnp.int8), s
    return (x / s).astype(jnp.float8_e4m3fn), s


def _proj(x, w, control):
    """x (rows, k) f32 @ w (k, n) bf16; ``control`` names the low
    precision ("int8" or "fp8") the projection runs in instead."""
    if not control:
        return jnp.dot(x, w.astype(F32), precision=HI)
    xq, xs = _quant(x, 1, control)
    wq, ws = _quant(w.astype(F32), 0, control)
    acc_t = jnp.int32 if control == "int8" else F32
    acc = jax.lax.dot(xq, wq, preferred_element_type=acc_t)
    return acc.astype(F32) * xs * ws


def _attention(q, k, v):
    """Causal attention, q (S, H, hd) with H == kv heads or a multiple."""
    s, h, hd = q.shape
    g = h // k.shape[1]
    k = jnp.repeat(k, g, axis=1)
    v = jnp.repeat(v, g, axis=1)
    keys = jnp.arange(s)

    def chunk(i):
        qc = jax.lax.dynamic_slice_in_dim(q, i * Q_CHUNK, Q_CHUNK, 0)
        rows = i * Q_CHUNK + jnp.arange(Q_CHUNK)
        sc = jnp.einsum("qhd,khd->hqk", qc, k, precision=HI) * hd ** -0.5
        sc = jnp.where(keys[None, None, :] <= rows[None, :, None], sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        return jnp.einsum("hqk,khd->qhd", p, v, precision=HI)

    out = jax.lax.map(chunk, jnp.arange(s // Q_CHUNK))
    return out.reshape(s, h * hd)


def hidden(cfg: dict, w: dict, tokens, control=None):
    """Final normalised hidden states (S, d) of one token sequence."""
    n = dims(cfg)
    eps, theta = cfg["rms_norm_eps"], float(cfg["rope_theta"])
    s = tokens.shape[0]
    x = w["tok"][tokens].astype(F32)
    layers = {k: w[k] for k in ("ln1", "wq", "wk", "wv", "wo", "ln2",
                                "w_gate", "w_up", "w_down")}

    def layer(x, lw):
        a = _rms(x, lw["ln1"], eps)
        q = _rope(_proj(a, lw["wq"], control).reshape(s, n["H"], n["hd"]), theta)
        k = _rope(_proj(a, lw["wk"], control).reshape(s, n["Hkv"], n["hd"]), theta)
        v = _proj(a, lw["wv"], control).reshape(s, n["Hkv"], n["hd"])
        x = x + _proj(_attention(q, k, v), lw["wo"], control)
        m = _rms(x, lw["ln2"], eps)
        ff = jax.nn.silu(_proj(m, lw["w_gate"], control)) * _proj(m, lw["w_up"], control)
        return x + _proj(ff, lw["w_down"], control), None

    x, _ = jax.lax.scan(layer, x, layers)
    return _rms(x, w["ln_f"], eps)


@functools.partial(jax.jit, static_argnums=(0, 5))
def _logits_at(cfg_items, w, tokens, at, served, control):
    cfg = dict(cfg_items)
    h = hidden(cfg, w, tokens)
    h_ctl = hidden(cfg, w, tokens, control) if control else None

    def chunk(i):
        rows = jax.lax.dynamic_slice_in_dim(at, i * Q_CHUNK, Q_CHUNK)
        pick = jax.lax.dynamic_slice_in_dim(served, i * Q_CHUNK, Q_CHUNK)
        ref = _proj(h[rows], w["unembed"], False)
        own = jnp.zeros(pick.shape, F32)
        if control:
            ctl = _proj(h_ctl[rows], w["unembed"], control)
            pick = jnp.argmax(ctl, axis=-1)
            own = jnp.take_along_axis(ctl, pick[:, None], axis=-1)[:, 0]
        at_pick = jnp.take_along_axis(ref, pick[:, None], axis=-1)[:, 0]
        return jnp.stack([jnp.max(ref, axis=-1), at_pick, own,
                          pick.astype(F32)])

    out = jax.lax.map(chunk, jnp.arange(at.shape[0] // Q_CHUNK))
    return out.transpose(1, 0, 2).reshape(4, -1)


def logits_at(cfg: dict, w: dict, tokens, n_prompt: int, served, length: int,
              control: str | None = None) -> dict:
    """Reference logits at the positions that predict each served token.

    ``tokens`` is the prompt followed by the served tokens, padded on the
    right to ``length`` (a multiple of 512; causal, so padding is
    invisible); served token ``i`` is predicted from position
    ``n_prompt + i - 1``. Returns arrays over the served tokens: ``best``
    (the reference's best logit), ``ref`` (its logit of the token judged:
    the served one, or with ``control`` the control's first choice),
    ``own`` (the control's logit of its choice) and ``pick`` (that
    choice). One compiled program per ``length``."""
    import numpy as np

    seq = np.zeros(length, np.int32)
    seq[: len(tokens)] = tokens
    n = len(served)
    at = np.zeros(length, np.int32)
    at[:n] = n_prompt - 1 + np.arange(n)
    pick = np.zeros(length, np.int32)
    pick[:n] = served
    key = tuple(sorted((k, v) for k, v in cfg.items()
                       if isinstance(v, (int, float, str, bool))))
    out = np.asarray(_logits_at(key, w, jnp.asarray(seq), jnp.asarray(at),
                                jnp.asarray(pick), control))[:, :n]
    return {"best": out[0], "ref": out[1], "own": out[2],
            "pick": out[3].astype(np.int64)}
