"""Plain reference of the xLSTM that ``xlstm-125m.json`` states, and of the
first round of its federated training job.

Written from the equations (arXiv:2405.04517, with the departures the
configuration file lists), not from the program. In float32 at
``Precision.HIGHEST``:

* embedding lookup; per block RMSNorm with weight ``1 + w``, the block,
  residual; final RMSNorm; output head; token-mean cross entropy.
* mLSTM block: up-projection to ``2 * inner`` split into a main and a gate
  branch; causal depthwise convolution (width 4) and SiLU on the main
  branch; q, k, v and the input and forget gate pre-activations from it;
  the stabilised parallel form ``h_t = sum_s (q_t.k_s / sqrt(dh)) D_ts v_s
  / max(|sum_s (q_t.k_s / sqrt(dh)) D_ts|, exp(-m_t))`` with
  ``log D_ts = F_t - F_s + i_s`` (``F`` the running sum of log sigmoid of
  the forget gate, ``s <= t``) and ``m_t = max_s log D_ts``; per-head RMS
  norm scaled by ``gn``; plus ``skip`` times the convolved branch; times
  SiLU of the gate branch; down-projection.
* sLSTM block: causal convolution and SiLU; z, i, f from the convolved
  input and o from the block input, each plus a block-diagonal recurrent
  term of ``h_{t-1}`` and a bias; ``m_t = max(log sigmoid f + m_{t-1}, i)``,
  ``c_t = exp(log sigmoid f + m_{t-1} - m_t) c_{t-1} + exp(i - m_t) tanh z``,
  ``n_t`` alike with 1 for ``tanh z``, ``h_t = sigmoid(o) c_t / max(n_t,
  1e-6)`` from zero state; per-head RMS norm; GeLU-gated feed-forward.
* the job: AdamW with gradients clipped to global norm ``grad_clip``,
  linear warm-up then cosine decay, decoupled weight decay on every stored
  leaf of two or more dimensions, float32 moments, parameters stored in
  their own dtype after each update; every ``local_steps`` steps federated
  averaging of the clouds' parameter changes, weighted by sample counts.

``make_weights`` draws the weights from a key in the tree the program
takes (layers stacked by position in the block pattern), in one call the
harness jits on the device. ``control`` ("int8" or "fp8", e4m3) runs every
matrix product of the forward and backward passes in that precision
(operands scaled per row and per column), the precision below the
configuration's bf16 put in the program's place.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
F32 = jnp.float32
CHUNK = 512          # query rows of the parallel mLSTM form at a time
ROWS = 256           # the embedding and head are stored with the vocabulary
                     # rounded up to a multiple of this; the extra rows are
                     # never read and the extra head columns never scored


def dims(cfg: dict) -> dict:
    d, h = cfg["hidden_size"], cfg["num_heads"]
    inner = cfg["mlstm_proj_factor"] * d
    return {"L": cfg["num_hidden_layers"], "d": d, "H": h, "inner": inner,
            "V": cfg["vocab_size"], "Vp": -(-cfg["vocab_size"] // ROWS) * ROWS,
            "cw": cfg["conv_width"],
            "ff": max(64, int(d * 4 / 3) // 64 * 64),
            "period": cfg["slstm_every"]}


def pattern(cfg: dict) -> list[str]:
    return ["mlstm"] * (cfg["slstm_every"] - 1) + ["slstm"]


# ---------------------------------------------------------------- weights
def make_weights(cfg: dict, key) -> dict:
    n = dims(cfg)
    d, H, inner, V, cw, ff = (n[k] for k in ("d", "H", "inner", "V", "cw", "ff"))
    reps = n["L"] // n["period"]
    bf = jnp.bfloat16
    ks = iter(jax.random.split(key, 64))

    def he(shape, fan):
        return (jax.random.normal(next(ks), (reps,) + shape, F32)
                * math.sqrt(2.0 / fan)).astype(bf)

    def full(shape, value, dtype=bf):
        return jnp.full((reps,) + shape, value, dtype)

    def mlstm():
        return {"w_up": he((d, 2 * inner), d), "conv_w": he((cw, inner), cw),
                "conv_b": full((inner,), 0), "wq": he((inner, inner), inner),
                "wk": he((inner, inner), inner), "wv": he((inner, inner), inner),
                "w_i": he((inner, H), inner), "b_i": full((H,), 0.0, F32),
                "w_f": he((inner, H), inner), "b_f": full((H,), 3.0, F32),
                "skip": full((inner,), 1), "gn": full((inner,), 1),
                "w_down": he((inner, d), inner)}

    def slstm():
        dh = d // H
        out = {"conv_w": he((cw, d), cw), "conv_b": full((d,), 0)}
        out.update({k: he((d, d), d) for k in ("w_z", "w_i", "w_f", "w_o")})
        out.update({k: he((H, dh, dh), dh) for k in ("r_z", "r_i", "r_f", "r_o")})
        out.update({k: full((d,), 3.0 if k == "b_f" else 0.0, F32)
                    for k in ("b_z", "b_i", "b_f", "b_o")})
        out.update({"gn": full((d,), 1), "ff_up": he((d, 2 * ff), d),
                    "ff_down": he((ff, d), ff)})
        return out

    periods = {f"pos{i}": {"ln": {"scale": full((d,), 0)},
                           "blk": mlstm() if kind == "mlstm" else slstm()}
               for i, kind in enumerate(pattern(cfg))}
    vp = n["Vp"]
    emb = {"tok": (jax.random.normal(next(ks), (vp, d), F32) * 0.02).astype(bf),
           "unembed": (jax.random.normal(next(ks), (d, vp), F32) * 0.02).astype(bf)}
    return {"embed": emb, "periods": periods, "rest": [],
            "ln_f": {"scale": jnp.zeros((d,), bf)}}


# ------------------------------------------------------ products, control
def _quant(x, axis, kind):
    top = 127.0 if kind == "int8" else 448.0
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / top
    s = jnp.where(s == 0, 1.0, s)
    if kind == "int8":
        return jnp.clip(jnp.round(x / s), -127, 127).astype(jnp.int8), s
    return (x / s).astype(jnp.float8_e4m3fn), s


def _qdot(a, b, kind):
    """a (m, k) @ b (k, n) with each operand in ``kind``: a per row, b per
    column."""
    aq, sa = _quant(a, 1, kind)
    bq, sb = _quant(b, 0, kind)
    acc = jnp.int32 if kind == "int8" else F32
    return jax.lax.dot(aq, bq, preferred_element_type=acc).astype(F32) * sa * sb


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _low(a, b, kind):
    return _qdot(a, b, kind)


def _low_fwd(a, b, kind):
    return _qdot(a, b, kind), (a, b)


def _low_bwd(kind, res, g):
    a, b = res
    return _qdot(g, b.T, kind), _qdot(a.T, g, kind)


_low.defvjp(_low_fwd, _low_bwd)


def _mm(x, w, control):
    """x (..., k) @ w (k, n), float32 or the control's precision."""
    shp = x.shape
    x2 = x.reshape(-1, shp[-1])
    w = w.astype(F32)
    y = _low(x2, w, control) if control else jnp.dot(x2, w, precision=HI)
    return y.reshape(shp[:-1] + (w.shape[-1],))


# ------------------------------------------------------------------ model
def _rms(x, w, eps):
    g = 1.0 + w.astype(F32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def _head_rms(x, g, heads, eps):
    xs = x.reshape(x.shape[:-1] + (heads, -1))
    xs = xs * jax.lax.rsqrt(jnp.mean(xs * xs, axis=-1, keepdims=True) + eps)
    return xs.reshape(x.shape) * g.astype(F32)


def _conv(x, w, b):
    """Causal depthwise convolution over time: x (B, S, W), w (cw, W)."""
    cw = w.shape[0]
    xp = jnp.pad(x, ((0, 0), (cw - 1, 0), (0, 0)))
    s = x.shape[1]
    return sum(xp[:, i:i + s] * w[i].astype(F32) for i in range(cw)) + b.astype(F32)


def _rows(qc, fc, t, k, v, ig, big_f):
    """Output rows ``t`` of the parallel form: qc (B, C, H, dh), fc the
    running forget sums at those rows (B, C, H)."""
    keys = jnp.arange(k.shape[1])
    logd = fc[:, :, None, :] - big_f[:, None, :, :] + ig[:, None, :, :]
    logd = jnp.where((keys[None, :] <= t[:, None])[None, :, :, None], logd, -jnp.inf)
    m = jnp.max(logd, axis=2)                                       # (B, C, H)
    dmat = jnp.exp(logd - m[:, :, None, :])
    sc = jnp.einsum("bchd,bshd->bcsh", qc, k, precision=HI) / math.sqrt(k.shape[-1])
    sc = sc * dmat
    nrm = jnp.maximum(jnp.abs(jnp.sum(sc, axis=2)), jnp.exp(-m))
    return jnp.einsum("bcsh,bshd->bchd", sc, v, precision=HI) / nrm[..., None]


def _mlstm_cell(q, k, v, ig, fg):
    """q, k, v (B, S, H, dh); ig, fg (B, S, H); ``CHUNK`` rows at a time."""
    b, s, h, dh = q.shape
    big_f = jnp.cumsum(jax.nn.log_sigmoid(fg), axis=1)
    if s <= CHUNK:
        return _rows(q, big_f, jnp.arange(s), k, v, ig, big_f)
    if s % CHUNK:
        raise ValueError(f"sequence length {s} is not a multiple of {CHUNK}")

    def chunk(i):
        sl = functools.partial(jax.lax.dynamic_slice_in_dim, start_index=i * CHUNK,
                               slice_size=CHUNK, axis=1)
        return _rows(sl(q), sl(big_f), i * CHUNK + jnp.arange(CHUNK), k, v, ig, big_f)

    out = jax.lax.map(chunk, jnp.arange(s // CHUNK))               # (n, B, C, H, dh)
    return out.transpose(1, 0, 2, 3, 4).reshape(b, s, h, dh)


def _mlstm(p, x, n, eps, control):
    bsz, s, _ = x.shape
    H, inner = n["H"], n["inner"]
    up = _mm(x, p["w_up"], control)
    main, gate = up[..., :inner], up[..., inner:]
    c = jax.nn.silu(_conv(main, p["conv_w"], p["conv_b"]))
    heads = (bsz, s, H, inner // H)
    q = _mm(c, p["wq"], control).reshape(heads)
    k = _mm(c, p["wk"], control).reshape(heads)
    v = _mm(c, p["wv"], control).reshape(heads)
    ig = _mm(c, p["w_i"], control) + p["b_i"]
    fg = _mm(c, p["w_f"], control) + p["b_f"]
    h = _mlstm_cell(q, k, v, ig, fg).reshape(bsz, s, inner)
    h = _head_rms(h, p["gn"], H, eps) + p["skip"].astype(F32) * c
    return _mm(h * jax.nn.silu(gate), p["w_down"], control)


def _slstm(p, x, n, eps, control):
    bsz, s, d = x.shape
    H = n["H"]
    c = jax.nn.silu(_conv(x, p["conv_w"], p["conv_b"]))
    pre = {"z": _mm(c, p["w_z"], control), "i": _mm(c, p["w_i"], control),
           "f": _mm(c, p["w_f"], control), "o": _mm(x, p["w_o"], control)}

    def rec(w, h):
        hs = h.reshape(bsz, H, d // H)
        if control:
            return jax.vmap(lambda a, b: _mm(a, b, control), in_axes=(1, 0),
                            out_axes=1)(hs, w).reshape(bsz, d)
        return jnp.einsum("bhi,hij->bhj", hs, w.astype(F32), precision=HI).reshape(bsz, d)

    def step(carry, xs):
        cc, nn, hh, mm = carry
        zt, it, ft, ot = xs
        z = jnp.tanh(zt + rec(p["r_z"], hh) + p["b_z"])
        i = it + rec(p["r_i"], hh) + p["b_i"]
        f = ft + rec(p["r_f"], hh) + p["b_f"]
        o = jax.nn.sigmoid(ot + rec(p["r_o"], hh) + p["b_o"])
        logf = jax.nn.log_sigmoid(f)
        m = jnp.maximum(logf + mm, i)
        cc = jnp.exp(logf + mm - m) * cc + jnp.exp(i - m) * z
        nn = jnp.exp(logf + mm - m) * nn + jnp.exp(i - m)
        hh = o * cc / jnp.maximum(nn, 1e-6)
        return (cc, nn, hh, m), hh

    zero = jnp.zeros((bsz, d), F32)
    xs = tuple(pre[g].swapaxes(0, 1) for g in "zifo")
    _, hs = jax.lax.scan(step, (zero, zero, zero, jnp.full((bsz, d), -1e30, F32)), xs)
    h = _head_rms(hs.swapaxes(0, 1), p["gn"], H, eps)
    gate, up = jnp.split(_mm(h, p["ff_up"], control), 2, axis=-1)
    return _mm(jax.nn.gelu(gate, approximate=True) * up, p["ff_down"], control)


def loss(cfg: dict, params: dict, tokens, labels, control=None):
    """Token-mean cross entropy of one cloud's batch, float32."""
    n = dims(cfg)
    eps = cfg["norm_eps"]
    pat = pattern(cfg)
    x = params["embed"]["tok"].astype(F32)[tokens]
    for layer in range(n["L"]):
        pos = layer % n["period"]
        lp = jax.tree_util.tree_map(lambda a: a[layer // n["period"]],
                                    params["periods"][f"pos{pos}"])
        block = _mlstm if pat[pos] == "mlstm" else _slstm

        def body(x, lp, block=block):
            return x + block(lp["blk"], _rms(x, lp["ln"]["scale"], eps), n, eps, control)

        x = jax.checkpoint(body)(x, lp)
    x = _rms(x, params["ln_f"]["scale"], eps)
    logits = _mm(x, params["embed"]["unembed"][:, :n["V"]], control)
    logz = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(logz - gold)


# -------------------------------------------------------------------- job
def lr_at(job: dict, t):
    t = jnp.asarray(t, F32)
    warm = jnp.minimum(t / max(job["warmup_steps"], 1), 1.0)
    frac = jnp.clip((t - job["warmup_steps"]) / max(job["steps"] - job["warmup_steps"], 1),
                    0.0, 1.0)
    return job["lr"] * warm * (0.1 + 0.45 * (1.0 + jnp.cos(jnp.pi * frac)))


def local_step(cfg, job, params, m, v, t, tokens, labels, control=None):
    """One AdamW step of one cloud; returns the new (params, m, v), the
    loss and the clipped gradient the optimizer took."""
    p32 = jax.tree_util.tree_map(lambda a: a.astype(F32), params)
    val, g = jax.value_and_grad(
        lambda p: loss(cfg, p, tokens, labels, control))(p32)
    leaves = jax.tree_util.tree_leaves(g)
    norm = jnp.sqrt(sum(jnp.sum(x * x) for x in leaves))
    scale = jnp.minimum(1.0, job["grad_clip"] / jnp.maximum(norm, 1e-9))
    g = jax.tree_util.tree_map(lambda x: x * scale, g)
    b1, b2 = job["beta1"], job["beta2"]
    lr = lr_at(job, t)

    def upd(p, gi, mi, vi):
        mi = b1 * mi + (1 - b1) * gi
        vi = b2 * vi + (1 - b2) * gi * gi
        step = (mi / (1 - b1 ** t)) / (jnp.sqrt(vi / (1 - b2 ** t)) + job["eps"])
        if p.ndim >= 2:
            step = step + job["weight_decay"] * p.astype(F32)
        return (p.astype(F32) - lr * step).astype(p.dtype), mi, vi

    out = jax.tree_util.tree_map(upd, params, g, m, v)
    tup = lambda o: isinstance(o, tuple)  # noqa: E731
    pick = [jax.tree_util.tree_map(lambda o, i=i: o[i], out, is_leaf=tup) for i in range(3)]
    return pick[0], pick[1], pick[2], val, g


def fedavg(stacked: dict, global_params: dict, weights) -> dict:
    """Global parameters plus the weighted mean of the clouds' changes."""
    def avg(s, gp):
        delta = s.astype(F32) - gp.astype(F32)[None]
        mean = jnp.sum(delta * weights.reshape((-1,) + (1,) * gp.ndim), axis=0)
        return (gp.astype(F32) + mean).astype(gp.dtype)

    return jax.tree_util.tree_map(avg, stacked, global_params)
