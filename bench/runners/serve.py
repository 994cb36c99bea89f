"""Serving runner: one ``ServeEngine`` on one chip under a traffic mix.

Set-up (counted in ``setup_s``): weights from the seed on the device, the
engine with its pool sized to the memory left, every prefill shape the mix
can reach compiled and run once, then the state a running deployment would
be in (a closed loop starts with every slot part-way through a request; a
mix with shared system prompts starts with each tenant's prompt cached).

Window (``--seconds``): the harness drives ``engine.step`` and stamps each
token when the step that produced it returns, which is when a streaming
server would send it. A closed loop gives each client its next request as
soon as the last one finishes; an open loop submits each request when it is
due and times its first token from that due time.

After the window: requests due in it are stepped to their first token, the
device's peak memory is read, the engine is freed, and a sample of the
finished requests (drawn from the seed, the longest among them) is checked
against the configuration's plain reference.
"""
from __future__ import annotations

import collections
import functools
import gc
import json
import math
import shutil
import time

import numpy as np

from bench import adapters, flops, generate, lookup
from bench import trace as tracing

DRAIN_S = 60.0


HEADROOM = 0.05    # share of the chip's memory left to the allocator


def pool_pages(ctx, cfg: dict, spec: dict, params, page: int,
               table_width: int) -> int:
    """Pages that fill the chip's memory: the engine's decode program and
    its widest cold-prefill program are compiled at two small pool sizes,
    and each one's peak (arguments + outputs - aliased + temporaries, from
    ``memory_analysis``) is fitted as a line in the pool's pages; the pool
    is the most pages at which the larger peak, with whatever else is
    held, stays under the memory limit less ``HEADROOM``. The line's slope
    holds every copy of the pool a program makes. In steps of 64 pages, so
    that run-to-run jitter in memory in use never changes a shape."""
    import jax
    import jax.numpy as jnp

    if "pages" in spec.get("pool", {}):
        return spec["pool"]["pages"]
    w = spec["warm"]
    shapes = [(wd, adapters.bucket_length(spec["prompt"]["max"]))
              for wd in w["cold_widths"]] + [tuple(x) for x in w.get("cold_extra", [])]
    width, length = max(shapes, key=lambda x: x[0] * x[1])
    length = adapters.bucket_length(min(length, spec["max_context"] - 2))
    sds = jax.ShapeDtypeStruct
    probes, peaks = (129, 257), []
    for n in probes:
        eng = adapters.build_engine(cfg, params, slots=spec["slots"], num_pages=n,
                                    table_width=table_width, page_size=page, seed=0)
        progs = [
            eng._decode.lower(eng.params, eng.cache,
                              sds((spec["slots"], 1), jnp.int32)),
            eng._prefill_slots.lower(eng.params, eng.cache,
                                     sds((width, length), jnp.int32),
                                     sds((width,), jnp.int32), sds((width,), jnp.int32)),
        ]
        peak = 0
        for lowered in progs:
            m = lowered.compile().memory_analysis()
            peak = max(peak, m.argument_size_in_bytes + m.output_size_in_bytes
                       - m.alias_size_in_bytes + m.temp_size_in_bytes)
        peaks.append(peak)
        del eng, progs
    gc.collect()
    per_page = (peaks[1] - peaks[0]) / (probes[1] - probes[0])
    base = peaks[0] - per_page * probes[0]
    params_bytes = sum(x.nbytes for x in jax.tree_util.tree_leaves(params))
    stats = ctx.devices[0].memory_stats()
    held = stats["bytes_in_use"] - params_bytes    # outside the programs
    room = stats["bytes_limit"] * (1 - HEADROOM) - held - base
    ctx.log(f"pool sizing: peak {base:.0f} + {per_page:.0f} bytes/page "
            f"(decode and {width}x{length} prefill), {held} bytes held besides")
    return int(room // per_page) // 64 * 64 + 1


class Stamps:
    """Tokens as the harness sees them at step boundaries."""

    def __init__(self, cfg: dict):
        self.cfg = cfg
        self.seen: dict[int, int] = {}
        self.times: dict[int, list[float]] = collections.defaultdict(list)
        self.feed: dict[int, tuple[int, int]] = {}    # uid -> (feed, prefix)
        self.admit: dict[int, float] = {}              # uid -> admission time
        self.flops = 0.0                               # work inside the window
        self.decode_keys: list[list[int]] = []         # traced steps only
        self.suffix_rows: list[tuple[int, int]] = []   # traced calls only
        self.counting = False
        self.tracing = False

    def step(self, t: float, live, finished) -> None:
        keys, suffix = [], []
        rows = [(s.uid, s.generated) for s in live]
        rows += [(o.uid, len(o.tokens)) for o in finished]
        by_uid = {s.uid: s for s in live}
        for uid, n in rows:
            old = self.seen.get(uid, 0)
            if n <= old:
                continue
            self.times[uid] += [t] * (n - old)
            self.seen[uid] = n
            if uid not in self.feed and uid in by_uid:
                s = by_uid[uid]
                self.feed[uid] = (s.feed, s.prefix)
                self.admit[uid] = s.admit
            feed, prefix = self.feed.get(uid, (0, 0))
            for j in range(old + 1, n + 1):
                if j == 1:
                    if self.counting:
                        self.flops += flops.prefill_flops(
                            self.cfg, prefix, feed - prefix)
                    if prefix:
                        suffix.append((prefix, feed - prefix))
                else:
                    if self.counting:
                        self.flops += flops.decode_token_flops(self.cfg, feed + j - 1)
                    keys.append(feed + j - 1)
        if self.tracing:
            self.decode_keys.append(keys)
            self.suffix_rows += suffix


class Window:
    """Drives the engine; the closed and open loops differ only in when
    requests are submitted."""

    def __init__(self, engine, trace, spec, stamps):
        self.engine, self.trace, self.spec = engine, trace, spec
        self.stamps = stamps
        self.next = 0                 # next request of the trace to submit
        self.due: dict[int, float] = {}    # uid -> due time (perf_counter)
        self.max_new: dict[int, int] = {}
        self.fin: list = []

    def request(self, uid: int, prompt, max_new: int, due: float):
        self.due[uid] = due
        self.max_new[uid] = max_new
        with tracing_span("bench.submit"):
            self.engine.submit(adapters.make_request(uid, prompt, max_new, 0.0))

    def submit_trace(self, i: int, due: float) -> None:
        tr, pre = self.trace, self.spec.get("shared_prefix")
        user = tr.tokens(i, int(tr.prompt[i]))
        if pre:
            user = np.concatenate([tr.system_prompt(int(tr.tenant[i]), pre["length"]), user])
        self.request(1000 + i, user, int(tr.output[i]), due)

    def step(self) -> list:
        with tracing_span("bench.step"):
            fin = self.engine.step()
        t = time.perf_counter()
        with tracing_span("bench.stamp"):
            self.stamps.step(t, adapters.live(self.engine), fin)
        self.fin += fin
        return fin


def memory(ctx) -> str:
    st = ctx.devices[0].memory_stats() or {}
    return (f"in use {st.get('bytes_in_use')}, peak {st.get('peak_bytes_in_use')}, "
            f"limit {st.get('bytes_limit')}")


def tracing_span(name: str):
    import jax

    return jax.profiler.TraceAnnotation(name)


# ----------------------------------------------------------------- set-up
def warm(engine, cfg: dict, spec: dict, seed: int, log) -> int:
    """Compile and run once every prefill shape the mix can reach: each
    (round width, length bucket) of cold prompts, and with shared prefixes
    each (width, suffix bucket) over a cached prefix. Prompts are distinct
    random tokens, so no warm round hits the prefix cache by accident."""
    w = spec["warm"]
    rng = np.random.default_rng([seed, 9])
    vocab = cfg["vocab_size"]
    uid = [-1]

    def run(prompts):
        reqs = []
        for p in prompts:
            uid[0] -= 1
            reqs.append(adapters.make_request(uid[0], p, 2, 0.0))
        engine.run(reqs)

    def buckets(lo, hi):
        return sorted({adapters.bucket_length(n) for n in range(lo, hi + 1)})

    rounds = 0
    p = spec["prompt"]
    cold = [(wd, b) for wd in w["cold_widths"]
            for b in buckets(p["min"], p["max"])] + [tuple(x) for x in w.get("cold_extra", [])]
    for wd, b in cold:
        b = min(b, spec["max_context"] - 2)
        run([rng.integers(0, vocab, b) for _ in range(wd)])
        adapters.clear_prefix_index(engine)
        rounds += 1
    pre = spec.get("shared_prefix")
    if pre:
        suffix = [(wd, b) for wd in w["suffix_widths"]
                  for b in buckets(p["min"], p["max"])] + [tuple(x) for x in w.get("suffix_extra", [])]
        head = rng.integers(0, vocab, pre["length"])
        run([head])                                   # publish one prefix
        for wd, b in suffix:
            b = min(b, spec["max_context"] - pre["length"] - 2)
            run([np.concatenate([head, rng.integers(0, vocab, b)]) for _ in range(wd)])
            rounds += 1
        adapters.clear_prefix_index(engine)
    log(f"warm-up: {rounds} rounds, engine compiles {adapters.counters(engine)['compiles']}")
    return rounds


# ------------------------------------------------------------------ run
def run(ctx) -> dict:
    import jax

    spec, cfg = ctx.traffic, ctx.cfg
    log = ctx.log
    compiles = tracing.CompileCount()
    ref = lookup.reference(ctx.cell["config"])
    weights = jax.jit(functools.partial(ref.make_weights, cfg))(generate.seed_key(ctx.seed))
    jax.block_until_ready(weights)
    page = spec["page_size"]
    table_width = math.ceil(spec["max_context"] / page)
    params = ref.program_params(weights)
    log(f"weights made; device memory {memory(ctx)}")
    num_pages = pool_pages(ctx, cfg, spec, params, page, table_width)
    log(f"pool: {num_pages} pages of {page} tokens")
    engine = adapters.build_engine(
        cfg, params, slots=spec["slots"], num_pages=num_pages,
        table_width=table_width, page_size=page, seed=ctx.seed)
    if ctx.breaker is not None:
        ctx.breaker(engine)
    capture = adapters.DecodeLogits(cfg["vocab_size"])
    adapters.annotate(engine, capture)
    log(f"engine built; device memory {memory(ctx)}")
    warm(engine, cfg, spec, ctx.seed, log)
    log(f"warmed; device memory {memory(ctx)}")

    trace = generate.make_trace(spec, ctx.seed, cfg["vocab_size"], ctx.seconds)
    stamps = Stamps(cfg)
    win = Window(engine, trace, spec, stamps)
    closed = spec["loop"] == "closed"
    if closed:
        _fill_closed(win, spec, ctx)
    else:
        _fill_tenants(win, spec, ctx)
    gc.collect()
    c0 = adapters.counters(engine)
    xla0 = compiles.n
    t_start = time.perf_counter()
    setup_s = t_start - ctx.t0
    log(f"set-up {setup_s:.3f} s; window opens")
    t_end = t_start + ctx.seconds
    stamps.counting = True
    capture.on = True
    tw = spec.get("trace", {})
    t_tr0 = t_start + tw.get("offset_s", 0.0)
    t_tr1 = t_tr0 + tw.get("seconds", ctx.seconds)
    traced = None
    ann = None
    c_tr0 = c_tr1 = None
    if not closed:
        due_abs = t_start + trace.due
    while True:
        now = time.perf_counter()
        if ctx.trace and traced is None and now >= t_tr0:
            tracing.start(ctx.trace_dir)
            ann = tracing_span(tracing.WINDOW_SPAN)
            ann.__enter__()
            traced, stamps.tracing, c_tr0 = now, True, adapters.counters(engine)
        if ann is not None and now >= min(t_tr1, t_end):
            ann.__exit__(None, None, None)
            tracing.stop()
            ann, stamps.tracing, c_tr1 = None, False, adapters.counters(engine)
        if now >= t_end:
            break
        if closed:
            fin = win.step()
            for _ in fin:
                win.submit_trace(win.next, time.perf_counter())
                win.next += 1
            continue
        while win.next < len(trace) and due_abs[win.next] <= now:
            win.submit_trace(win.next, due_abs[win.next])
            win.next += 1
        if not engine.has_work:
            nxt = due_abs[win.next] if win.next < len(trace) else t_end
            with tracing_span("bench.idle"):
                time.sleep(max(0.0, min(nxt, t_end) - now))
            continue
        win.step()
    t_close = time.perf_counter()
    stamps.counting = False
    capture.on = False
    c1 = adapters.counters(engine)
    xla_in_window = compiles.n - xla0
    if not closed:
        # every request due in the window arrives, even if the last step
        # ran past the close; each is timed from its own due time
        while win.next < len(trace) and due_abs[win.next] < t_end:
            win.submit_trace(win.next, due_abs[win.next])
            win.next += 1
        t_drain = t_close + DRAIN_S
    in_window = [u for u, d in win.due.items() if t_start <= d < t_end]
    if not closed:
        while (any(stamps.seen.get(u, 0) == 0 for u in in_window)
               and time.perf_counter() < t_drain and engine.has_work):
            win.step()
    unanswered = sum(stamps.seen.get(u, 0) == 0 for u in in_window)
    window_s = t_close - t_start
    e2e = _end_to_end(stamps, win, in_window, t_start, t_close, window_s)
    log(f"window {window_s:.3f} s: {c1['steps'] - c0['steps']} steps, "
        f"compiles in window: engine {c1['compiles'] - c0['compiles']}, "
        f"XLA {xla_in_window}, "
        f"preemptions {c1['preemptions'] - c0['preemptions']}, "
        f"evicted prefix pages {c1['prefix_evicted_pages'] - c0['prefix_evicted_pages']}")
    memory_peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                      for d in ctx.devices[:ctx.cell["chips"]])

    outs = [o for o in win.fin if o.uid >= 0 and stamps.times[o.uid]
            and stamps.times[o.uid][-1] >= t_start]
    sample = _sample(outs, spec["check"], ctx.seed)
    served = capture.read()
    del engine, win.engine, capture
    gc.collect()
    t_ref = time.perf_counter()
    checks, readings = _check(ref, cfg, weights, sample, spec["check"], win,
                              served, ctx)
    log(f"reference check of {len(sample)} requests, "
        f"{sum(len(o.tokens) for o in sample)} served tokens, "
        f"{time.perf_counter() - t_ref:.3f} s: {json.dumps(readings)}")
    checks["unanswered"] = {"value": unanswered, "limit": 0}

    rec = {
        "e2e": {**e2e, "setup_s": setup_s},
        "attempted": len(in_window) if not closed else sum(
            1 for u, ts in stamps.times.items()
            if any(t_start <= t <= t_close for t in ts)),
        "failed": unanswered,
        "checks": checks,
        "memory_peak_bytes": memory_peak,
        "window_s": window_s,
        "counters": {k: c1[k] - c0[k] for k in c0},
        "serve_flops": stamps.flops,
        "queue_wait_s": [stamps.admit[u] - win.due[u] for u in in_window
                         if u in stamps.admit],
        "requests": [(win.due[u] - t_start, stamps.admit.get(u, math.inf) - t_start,
                      (stamps.times[u] or [math.inf])[0] - t_start) for u in in_window],
        "cfg": cfg,
        "page": page,
        "readings": readings,
    }
    if traced is not None:
        rec["trace"] = tracing.load(ctx.trace_dir)
        shutil.rmtree(ctx.trace_dir, ignore_errors=True)
        rec["traced"] = {
            "decode_steps": len([k for k in stamps.decode_keys if k]),
            "decode_keys": stamps.decode_keys,
            "suffix_rows": stamps.suffix_rows,
            "counters": {k: c_tr1[k] - c_tr0[k] for k in c_tr0} if c_tr1 else None,
        }
    return rec


def _fill_closed(win: Window, spec: dict, ctx) -> None:
    """Every client starts part-way through a request: its history is
    prefilled as part of its prompt and its remaining output drawn. One
    client is admitted per step, as a running server would have them."""
    start = generate.steady_start(spec, ctx.seed, spec["clients"])
    for c, (p, done, left) in enumerate(start):
        prompt = win.trace.tokens(c, p + done, stream=2)
        win.request(c, prompt, left, time.perf_counter())
        fin = win.step()
        for _ in fin:
            win.submit_trace(win.next, time.perf_counter())
            win.next += 1


def _fill_tenants(win: Window, spec: dict, ctx) -> None:
    """Serve one request per tenant (a short user turn, one token out), so
    each system prompt is cached."""
    pre = spec["shared_prefix"]
    rng = np.random.default_rng([ctx.seed, 4])
    for t in range(pre["tenants"]):
        user = rng.integers(0, ctx.cfg["vocab_size"], spec["prompt"]["min"])
        prompt = np.concatenate([win.trace.system_prompt(t, pre["length"]), user])
        win.request(-100 - t, prompt, 1, time.perf_counter())
        while win.engine.has_work:
            win.step()


def _pct(x, q):
    return float(np.percentile(np.asarray(x, np.float64), q)) if len(x) else None


def _end_to_end(stamps, win, in_window, t0, t1, window_s) -> dict:
    toks = sum(1 for ts in stamps.times.values() for t in ts if t0 <= t <= t1)
    gaps = []
    for ts in stamps.times.values():
        w = [t for t in ts if t0 <= t <= t1]
        gaps += list(np.diff(w))
    ttft = [stamps.times[u][0] - win.due[u] for u in in_window if stamps.times.get(u)]
    return {
        "decode_tok_s": toks / window_s,
        "itl_p95_ms": 1e3 * _pct(gaps, 95) if gaps else None,
        "ttft_p50_ms": 1e3 * _pct(ttft, 50) if ttft else None,
        "_ttft_p95_ms": 1e3 * _pct(ttft, 95) if ttft else None,
        "_tokens": toks,
        "_gaps": len(gaps),
        "_ttft_n": len(ttft),
        "_itl_p50_ms": 1e3 * _pct(gaps, 50) if gaps else None,
    }


def _sample(outs, check: dict, seed: int) -> list:
    """The longest finished request, then others drawn from the seed, until
    ``check.tokens`` served tokens or ``check.rows`` requests."""
    if not outs:
        return []
    rng = np.random.default_rng([seed, 5])
    order = sorted(outs, key=lambda o: (-len(o.tokens), o.uid))
    chosen, rest = [order[0]], list(rng.permutation(len(order) - 1) + 1)
    while rest and len(chosen) < check["rows"] and \
            sum(len(o.tokens) for o in chosen) < check["tokens"]:
        chosen.append(order[rest.pop(0)])
    return chosen


def _stats(gap: list, err: list) -> dict:
    """Numbers of one side (the program, or a control in its place) over
    the sample: how far each judged token's logit lies below the
    reference's best (mean, widest, share off the best), and how far that
    side's own logit of the token lies from the reference's (mean, widest;
    tokens from the decode program)."""
    g = np.concatenate(gap) if gap else np.zeros(0)
    e = np.concatenate(err) if err else np.zeros(0)
    inf = math.inf
    return {"mean_logit_gap": float(g.mean()) if g.size else inf,
            "max_logit_gap": float(g.max()) if g.size else inf,
            "off_best": float(np.mean(g > 0)) if g.size else inf,
            "logit_err": float(e.mean()) if e.size else inf,
            "max_logit_err": float(e.max()) if e.size else inf,
            "tokens": int(g.size), "err_tokens": int(e.size)}


def _check(ref, cfg, weights, sample, check: dict, win, served: dict, ctx):
    """The program's served tokens against the configuration's plain
    reference, over the sample: the mean gap of each served token's logit
    below the reference's best, and the mean distance between the logit
    the program's decode program gave that token and the reference's.
    ``ctx.readings`` names controls whose numbers are read beside the
    program's on the same prompts and tokens; ``ctx.control`` names one
    whose numbers are judged in the program's place."""
    short = 0
    sides = ("program",) + tuple(ctx.readings)
    if ctx.control and ctx.control not in sides:
        sides += (ctx.control,)
    gap: dict = {k: [] for k in sides}
    err: dict = {k: [] for k in sides}
    for o in sample:
        if len(o.tokens) != win.max_new[o.uid]:
            short += 1
        seq = list(o.prompt) + list(o.tokens)
        n = len(o.tokens)
        for side in sides:
            ctl = None if side == "program" else side
            r = ref.logits_at(cfg, weights, seq, len(o.prompt), o.tokens,
                              check["length"], control=ctl)
            gap[side].append(r["best"] - r["ref"])
            if ctl is None:
                idx = [j for j in range(1, n) if (o.uid, j) in served]
                own = np.array([served[(o.uid, j)] for j in idx])
                err[side].append(np.abs(own - r["ref"][idx]))
            else:
                err[side].append(np.abs(r["own"] - r["ref"])[1:])
    readings = {k: _stats(gap[k], err[k]) for k in sides}
    judged = readings[ctx.control or "program"]
    checks = {"mean_logit_gap": {"value": judged["mean_logit_gap"],
                                 "limit": check["limit_gap"]},
              "logit_err": {"value": judged["logit_err"],
                            "limit": check["limit_err"]},
              "short_answers": {"value": short, "limit": 0}}
    return checks, readings
