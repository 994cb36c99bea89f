"""Continuous-batching serve benchmark: Poisson arrivals → tokens/sec and
p50/p95 request latency; burst arrivals → prefill-dispatch count and TTFT.

Drives ``launch/engine.py`` with a Poisson request trace (exponential
inter-arrival times, mixed prompt lengths) in realtime mode, and contrasts
it with the sequential oracle (``serve_batch``) running the same workload
as back-to-back fixed batches. The headline numbers:

* ``tokens_per_second`` — generated tokens / wall time over the trace
* ``latency_p50`` / ``latency_p95`` — per-request arrival→finish seconds
  (includes queueing: the p95 is where continuous batching pays off, a
  late-arriving request backfills a freed slot instead of waiting for the
  whole previous batch)
* ``ttft_p50`` — arrival→first-token seconds

``--burst N`` switches to a burst-arrival trace (N simultaneous arrivals
per burst) and runs the engine five ways — PAGED KV cache (shared page
pool + per-slot page tables, the serve-CLI default), paged with a TIGHT
(oversubscribed) pool that forces watermark admission + youngest-slot
preemption, ring-cache shape-bucketed batched prefill, unbucketed batched,
and one-dispatch-per-request — asserting all five emit identical greedy
tokens and reporting ``prefill_dispatches``, ``prefill_compiles``,
latency/TTFT percentiles, and (paged variants) pool occupancy +
preemption counts. Burst mode also probes the paged decode kernel in
isolation: mean decode-step time at low vs. full ring occupancy, paged
vs. unpaged vs. page-table mode (page skipping only helps rows far from
wrap, so the low-occupancy row is where the win shows), and runs the
SHARED-PREFIX probe: N requests over one common system prompt through the
paged engine with and without the prefix cache, asserting identical
greedy tokens, ≥ 50% fewer prefilled tokens, a nonzero prefix hit rate,
and an exercised copy-on-write split (``bench_shared_prefix``).

Burst mode also runs the SHARDED probe: the same burst trace through a
tensor-parallel engine on a ``model``-axis CPU mesh
(``ServeEngine(mesh=...)``) vs. the single-device paged engine, asserting
BITWISE-identical greedy tokens and reporting sharded tokens/sec,
per-shard occupancy, and compile counts. A one-device process (the plain
local run) re-execs itself with
``XLA_FLAGS=--xla_force_host_platform_device_count=2`` so the trajectory
still carries real multi-shard numbers; under the CI sharded job (4
forced host devices) the probe runs in-process on 4 shards.
``--sharded-probe`` runs just this probe and prints its JSON — the CI
sharded job's entry point.

Burst mode also runs the ROUTER probe (``bench_router``): the burst trace
through a 2-replica fault-tolerant ``ServeRouter`` with replica 0 KILLED
mid-decode, asserting zero dropped requests and greedy+sampled token
identity against a fault-free single engine, and reporting the failover
round-trip (migrations, migrated requests, per-replica occupancy, sheds,
retries). ``--router-probe`` runs just this probe — the CI chaos smoke
job's entry point.

Burst mode also runs the KV-QUANT and TIERED-KV probes. ``bench_kv_int8``
sizes an int8 page pool (int8 payload + per-page-slot per-kv-head fp32
scales) to a float32 pool's exact device-byte budget
(``dataclasses.replace(cfg, dtype="float32")``) and asserts ≥ 2×
concurrent resident sequences, actually serving that many simultaneous
requests without a single preemption, and records the int8 engine's
greedy-token agreement against the float32 one. ``bench_tiered`` replays
an oversubscribed long-prompt trace with the host KV tier ON (preempted
pages swap to host, resume = device scatter) vs. OFF (resume = full
re-prefill), asserting bitwise-identical greedy tokens, real host-tier
swap-ins, and strictly fewer prefilled tokens with swap, and records both
walls — the swap-vs-recompute resume contrast in the trajectory.
``--tiered-probe`` runs just these two probes — the CI tiered smoke job's
entry point.

Burst mode also runs the SPEC-DECODE probe (``bench_spec``): the burst
trace target-only vs. draft-model speculative decoding (k-token lookahead
verified in one batched suffix-prefill dispatch per round). The
same-params draft row is the deterministic upper bound CI pins — greedy
tokens bitwise identical and ≥ 1.5× fewer target dispatches are both
asserted — and a foreign-seed draft row records realistic acceptance.
``--spec-probe`` runs just this probe — the CI spec smoke job's entry
point.

``--smoke`` is the CI-sized burst run. Besides the usual
``benchmarks/results.json`` entry it APPENDS a timestamped entry to
``BENCH_serve.json`` at the repo root — the perf trajectory future PRs
diff against (schema 2: ``{"schema": 2, "entries": [...]}``; a schema-1
file is migrated by wrapping its single snapshot as the first entry).

    PYTHONPATH=src python -m benchmarks.serve_bench --requests 12 --rate 2.0
    PYTHONPATH=src python -m benchmarks.serve_bench --burst 4 --requests 12
"""
from __future__ import annotations

import argparse
import dataclasses
import datetime
import json
import os
import subprocess
import sys
import time

import jax
import numpy as np

from benchmarks.common import emit, save_results
from repro.configs import get_smoke_config
from repro.data import SyntheticCorpus
from repro.launch.engine import Request, ServeEngine
from repro.launch.serve import serve_batch
from repro.models import build_model

BENCH_SEED_PATH = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "BENCH_serve.json",
)


def poisson_trace(
    cfg, *, n_requests: int, rate: float, prompt_lens: tuple[int, ...],
    gen_tokens: int, seed: int,
) -> list[Request]:
    """Poisson arrivals (rate req/s), prompt length sampled per request."""
    rng = np.random.default_rng(seed)
    arrivals = np.cumsum(rng.exponential(1.0 / rate, size=n_requests))
    corpus = SyntheticCorpus(vocab_size=cfg.vocab_size, n_domains=4, noise=0.0)
    reqs = []
    for r in range(n_requests):
        plen = int(rng.choice(prompt_lens))
        prompt = np.asarray(
            corpus.sample(
                jax.random.PRNGKey(seed + 100 + r), np.ones(4) / 4, 1, plen
            )["tokens"][0],
            np.int32,
        )
        reqs.append(
            Request(
                uid=r, prompt=prompt, max_new_tokens=gen_tokens,
                arrival_time=float(arrivals[r]),
            )
        )
    return reqs


def bench_engine(args) -> dict:
    cfg = get_smoke_config(args.arch)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(args.seed))
    max_seq = max(args.prompt_lens) + args.gen
    engine = ServeEngine(
        model, params, num_slots=args.slots, max_seq=max_seq,
        window=args.window, use_kernel=args.use_kernel, prefill=args.prefill,
        paged_cache=args.paged_cache, page_size=args.page_size,
        num_pages=args.num_pages,
    )
    reqs = poisson_trace(
        cfg, n_requests=args.requests, rate=args.rate,
        prompt_lens=tuple(args.prompt_lens), gen_tokens=args.gen,
        seed=args.seed,
    )
    # warm the jit caches outside the timed region so the trace measures
    # steady state, not compilation
    engine.warm(args.prompt_lens)

    t0 = time.time()
    outs = engine.run(reqs, realtime=True)
    wall = time.time() - t0
    total = sum(len(o.tokens) for o in outs)
    lat = np.asarray([o.latency for o in outs])
    ttft = np.asarray([o.ttft for o in outs])
    return {
        "mode": "continuous",
        "slots": args.slots,
        "requests": args.requests,
        "rate_req_per_s": args.rate,
        "prompt_lens": list(args.prompt_lens),
        "gen_tokens": args.gen,
        "window": args.window,
        "prefill": args.prefill,
        "use_kernel": args.use_kernel,
        "engine_steps": engine.steps,
        "wall_seconds": wall,
        "tokens_per_second": total / max(wall, 1e-9),
        "latency_p50": float(np.percentile(lat, 50)),
        "latency_p95": float(np.percentile(lat, 95)),
        "ttft_p50": float(np.percentile(ttft, 50)),
        "pool": engine.pool_stats,
    }


def burst_trace(
    cfg, *, n_requests: int, burst_size: int, gap: float,
    prompt_lens: tuple[int, ...], gen_tokens: int, seed: int,
) -> list[Request]:
    """Bursts of ``burst_size`` simultaneous arrivals, ``gap`` seconds apart
    — the arrival pattern iteration-level batched admission exists for."""
    rng = np.random.default_rng(seed)
    corpus = SyntheticCorpus(vocab_size=cfg.vocab_size, n_domains=4, noise=0.0)
    reqs = []
    for r in range(n_requests):
        plen = int(rng.choice(prompt_lens))
        prompt = np.asarray(
            corpus.sample(
                jax.random.PRNGKey(seed + 100 + r), np.ones(4) / 4, 1, plen
            )["tokens"][0],
            np.int32,
        )
        reqs.append(
            Request(
                uid=r, prompt=prompt, max_new_tokens=gen_tokens,
                arrival_time=(r // burst_size) * gap,
            )
        )
    return reqs


def bench_decode_occupancy(
    *, slots: int = 4, cap: int = 4096, iters: int = 5, shallow_pos: int = 16,
) -> dict:
    """Isolated decode-attention step time vs. ring occupancy, paged vs.
    unpaged kernel (interpret mode — relative, not absolute, numbers;
    shared probe in ``benchmarks.kernels_bench.decode_occupancy_sweep``).

    ``cap`` must be large enough to split into several pages (auto page is
    512), or there is nothing to skip: 4096 → 8 pages. ``low`` occupancy
    parks every slot at ``shallow_pos`` (one live page of the ring);
    ``full`` parks every slot past wrap (every page live). The paged
    kernel must win at LOW occupancy — that pair is the acceptance
    comparison. At full occupancy both kernels visit every page; any gap
    in the full rows is interpret-mode dispatch overhead, kept in the seed
    only as a noise floor for diffing the low rows against."""
    from benchmarks.kernels_bench import decode_occupancy_sweep

    sweep = decode_occupancy_sweep(
        {
            "low": [shallow_pos] * slots,
            "full": [cap + shallow_pos] * slots,
        },
        slots=slots, cap=cap, iters=iters,
    )
    return {"cap": cap, "slots": slots, "shallow_pos": shallow_pos, **sweep}


BURST_VARIANTS = (
    # label, batch_prefill, bucket_prefill, paged_cache, tight_pool
    ("paged", True, True, True, False),            # serve-CLI default
    ("paged_tight", True, True, True, True),       # oversubscribed pool:
    #                                                watermark + preemption
    ("batched", True, True, False, False),         # ring-cache contrast
    ("batched_unbucketed", True, False, False, False),
    ("per_request", False, False, False, False),   # one dispatch per request
)

TIGHT_POOL_FRACTION = 0.5  # tight pool ≈ half of ring-equivalent capacity


def shared_prefix_trace(
    cfg, *, n_requests: int, prefix_len: int, page_size: int,
    gen_tokens: int, seed: int,
) -> list[Request]:
    """N requests over one common system prompt: ``prefix_len`` shared
    tokens + a short unique user suffix each. Requests 0 and n-1 carry the
    IDENTICAL page-aligned prompt (different admission rounds), so a warm
    index serves the last one entirely from cache — the copy-on-write
    split path."""
    rng = np.random.default_rng(seed)
    vocab = cfg.vocab_size
    system = rng.integers(1, vocab, prefix_len).astype(np.int32)
    # page-aligned full duplicate: forces a 100% hit + CoW on its re-run
    dup_suffix = rng.integers(
        1, vocab, page_size - (prefix_len % page_size) or page_size
    ).astype(np.int32)
    reqs = []
    for r in range(n_requests):
        if r == 0 or r == n_requests - 1:
            suffix = dup_suffix
        else:
            suffix = rng.integers(1, vocab, 3 + (r % 5)).astype(np.int32)
        reqs.append(
            Request(
                uid=r,
                prompt=np.concatenate([system, suffix]),
                max_new_tokens=gen_tokens,
            )
        )
    return reqs


def bench_shared_prefix(args) -> dict:
    """The prefix-sharing probe: the same common-system-prompt burst
    through the paged engine WITH and WITHOUT the prefix cache.

    Asserted here (CI runs this under --smoke): identical greedy tokens,
    ≥ 50% fewer prefilled tokens with sharing, a nonzero prefix hit rate,
    at least one copy-on-write page split exercised (the fully cached
    duplicate prompt), and — since hit/cold round splitting — that warm
    rounds actually take the SUFFIX dispatch path (``suffix_dispatches``
    > 0 with sharing, 0 without) while the cold publish round stays on
    the cold trace. ``prefill_tokens_saved_frac`` is the headline —
    prefill FLOPs scale linearly in prefilled tokens at fixed width.
    ``steady_round_seconds`` times a SECOND identical warm burst (same
    prompts, fresh uids) after the first burst has paid the jit compiles:
    the on/off contrast is the suffix-round latency saving (suffix rounds
    attend over starts-bounded prefix pages + short suffixes instead of
    re-prefilling the full prompt)."""
    cfg = get_smoke_config(args.arch)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(args.seed))
    prefix_len = 3 * args.page_size
    max_seq = prefix_len + args.page_size + 8 + args.gen
    out = {}
    for label, prefix in (("prefix_on", True), ("prefix_off", False)):
        engine = ServeEngine(
            model, params, num_slots=args.slots, max_seq=max_seq,
            prefill="chunked", paged_cache=True, page_size=args.page_size,
            prefix_cache=prefix,
        )
        reqs = shared_prefix_trace(
            cfg, n_requests=args.requests, prefix_len=prefix_len,
            page_size=args.page_size, gen_tokens=args.gen, seed=args.seed,
        )
        # second identical warm burst (same prompts, fresh uids): by the
        # time it runs, burst #1 has paid every jit compile, so its wall
        # time is the steady-state warm-round latency
        burst2 = [
            Request(
                uid=1000 + r.uid, prompt=r.prompt,
                max_new_tokens=r.max_new_tokens,
            )
            for r in reqs[1:]
        ]
        t0 = time.time()
        # the first request runs alone (publishing the system prompt on
        # retirement), then the burst — otherwise the whole first
        # admission round is cold and the probe undercounts what a warm
        # system-prompt cache saves
        outs = engine.run(reqs[:1])
        outs += engine.run(reqs[1:])
        t_steady = time.time()
        outs += engine.run(burst2)
        t_end = time.time()
        out[label] = {
            "wall_seconds": t_end - t0,
            "steady_round_seconds": t_end - t_steady,
            "prefill_tokens": engine.prefill_tokens,
            "prefill_dispatches": engine.prefill_dispatches,
            "engine_steps": engine.steps,
            "pool": engine.pool_stats,
            "generated": [o.tokens for o in outs],
        }
    on, off = out["prefix_on"], out["prefix_off"]
    assert on["generated"] == off["generated"], (
        "prefix sharing changed greedy output"
    )
    saved = 1.0 - on["prefill_tokens"] / max(off["prefill_tokens"], 1)
    assert saved >= 0.5, (
        f"shared-prefix trace saved only {saved:.0%} prefilled tokens "
        f"({on['prefill_tokens']} vs {off['prefill_tokens']})"
    )
    assert on["pool"]["prefix_hit_rate"] > 0, "no prefix hits on a shared trace"
    assert on["pool"]["cow_copies"] > 0, (
        "fully cached duplicate prompt never exercised copy-on-write"
    )
    # hit/cold round splitting: warm rounds must dispatch the suffix
    # trace, the cold publish round the cold trace — and without the
    # prefix cache every round is cold
    assert on["pool"]["suffix_dispatches"] > 0, (
        "warm shared-prefix rounds never took the suffix dispatch path"
    )
    assert on["pool"]["cold_dispatches"] > 0, (
        "the cold publish round did not take the cold dispatch path"
    )
    assert off["pool"]["suffix_dispatches"] == 0, (
        "suffix dispatch fired with the prefix cache disabled"
    )
    for m in out.values():
        del m["generated"]
    return {
        "prefix_len": prefix_len,
        "prefill_tokens_saved_frac": saved,
        **out,
    }


def _pool_kv_bytes(cache) -> int:
    """Device bytes held by the shared KV pool — payload planes plus, when
    quantized, the fp32 scale planes. This is the HBM budget the residency
    probe equates across dtypes."""
    return sum(
        int(cache[n].size) * cache[n].dtype.itemsize
        for n in ("k", "v", "ks", "vs")
        if n in cache
    )


def bench_kv_int8(args) -> dict:
    """int8 KV residency probe: at an EQUAL pool byte budget, how many
    sequences stay resident with int8 pages vs. a float32 pool
    (``dataclasses.replace(cfg, dtype="float32")`` — same float32 weights
    drive both engines, only the pool dtype differs)?

    An int8 page costs ``head_dim + 4`` bytes per kv-head per token slot
    (1-byte payload + one fp32 scale each) against the float32 pool's
    ``4·head_dim`` — ×3.6 at head_dim 32 — so the probe sizes the int8
    pool to the float32 pool's measured byte budget, asserts ≥ 2× resident
    sequences, then actually serves that many SIMULTANEOUS requests
    through the int8 engine and asserts zero preemptions (the claim is
    residency, not arithmetic). Quantization quality is pinned in
    tests/test_kv_int8.py; here the greedy-token agreement against the
    float32 engine is just recorded (and sanity-bounded)."""
    cfg = dataclasses.replace(get_smoke_config(args.arch), dtype="float32")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(args.seed))
    plen = max(args.prompt_lens)
    max_seq = plen + args.gen
    pages_per_seq = -(-max_seq // args.page_size)
    base_slots = 2
    fp_pages = base_slots * pages_per_seq + 1  # + the reserved scratch page
    eng_fp = ServeEngine(
        model, params, num_slots=base_slots, max_seq=max_seq,
        prefill="chunked", paged_cache=True, page_size=args.page_size,
        num_pages=fp_pages,
    )
    fp_bytes = _pool_kv_bytes(eng_fp.cache)
    # int8 page bytes from the float32 pool's geometry: payload 4 → 1
    # byte/element plus one fp32 scale per (token slot, kv head) per page
    layers, _, page, hkv, hd = eng_fp.cache["k"].shape
    per_page_int8 = 2 * layers * page * hkv * (hd + 4)
    int8_pages = int(fp_bytes // per_page_int8)
    resident_fp = (fp_pages - 1) // pages_per_seq
    resident_int8 = (int8_pages - 1) // pages_per_seq
    assert resident_int8 >= 2 * resident_fp, (
        f"int8 pool at the float32 byte budget holds only {resident_int8} "
        f"resident sequences vs {resident_fp} float32 (< 2x)"
    )
    eng8 = ServeEngine(
        model, params, num_slots=resident_int8, max_seq=max_seq,
        prefill="chunked", paged_cache=True, page_size=args.page_size,
        num_pages=int8_pages, kv_dtype="int8",
    )
    int8_bytes = _pool_kv_bytes(eng8.cache)
    assert int8_bytes <= fp_bytes, (
        f"int8 pool ({int8_bytes}B) exceeds the float32 budget ({fp_bytes}B)"
    )
    rng = np.random.default_rng(args.seed)
    prompts = [
        rng.integers(1, cfg.vocab_size, plen).astype(np.int32)
        for _ in range(resident_int8)
    ]

    def trace():
        return [
            Request(uid=r, prompt=p, max_new_tokens=args.gen)
            for r, p in enumerate(prompts)
        ]

    t0 = time.time()
    outs8 = eng8.run(trace())
    wall8 = time.time() - t0
    pool8 = eng8.pool_stats
    assert pool8["preemptions"] == 0, (
        f"{resident_int8} sequences did not fit resident in the int8 pool "
        f"({pool8['preemptions']} preemptions)"
    )
    outs_fp = eng_fp.run(trace())  # 2 slots: same trace, serialized
    tok8 = [o.tokens for o in outs8]
    tokfp = [o.tokens for o in outs_fp]
    agreement = sum(a == b for a, b in zip(tok8, tokfp)) / len(tok8)
    assert agreement >= 0.5, (
        f"int8 engine agreed with float32 on only {agreement:.0%} of "
        "requests — quantization is off the rails, see tests/test_kv_int8.py"
    )
    return {
        "pool_bytes_fp32": fp_bytes,
        "pool_bytes_int8": int8_bytes,
        "pages_fp32": fp_pages,
        "pages_int8": int8_pages,
        "pages_per_seq": pages_per_seq,
        "resident_seqs_fp32": resident_fp,
        "resident_seqs_int8": resident_int8,
        "residency_ratio": resident_int8 / max(resident_fp, 1),
        "token_agreement": agreement,
        "wall_seconds_int8": wall8,
        "occupancy_max_int8": pool8["occupancy_max"],
    }


def bench_tiered(args) -> dict:
    """Tiered-KV resume probe: the SAME oversubscribed trace with the host
    tier ON (preempted pages swap out to host, resume = one device
    scatter + table rewrite) vs. OFF (resume = re-prefill the victim's
    whole token stream). Long prompts + short gens make the run
    prefill-dominated, so the recompute engine's extra resume prefills
    land directly in its wall time.

    Asserted: bitwise-identical greedy tokens across both engines, both
    engines actually preempt, the swap engine resumes from the host tier
    (``swapped_in_pages > 0`` — the CI smoke gate for the tiered path),
    and it prefills STRICTLY fewer tokens than the recompute engine (the
    deterministic form of "swap resume does no prefill work"). Walls for
    both engines go to the trajectory as the resume-cost contrast."""
    cfg = get_smoke_config(args.arch)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(args.seed))
    plen = 24 * args.page_size  # long prompts: resume cost ≈ prefill cost
    gen = 4
    max_seq = plen + gen
    pages_per_seq = -(-max_seq // args.page_size)
    # both prompts fit, both COMPLETIONS don't: the collision lands
    # mid-decode, which is where a swap resume is a pure page scatter
    num_pages = 2 * pages_per_seq
    n_reqs = 4
    rng = np.random.default_rng(args.seed + 1)
    prompts = [
        rng.integers(1, cfg.vocab_size, plen).astype(np.int32)
        for _ in range(n_reqs)
    ]
    out = {}
    for label, host_pages in (("recompute", 0), ("swap", n_reqs * pages_per_seq)):
        engine = ServeEngine(
            model, params, num_slots=2, max_seq=max_seq, prefill="chunked",
            paged_cache=True, page_size=args.page_size, num_pages=num_pages,
            prefix_cache=False, host_pages=host_pages,
        )
        reqs = [
            Request(uid=r, prompt=prompts[r], max_new_tokens=gen)
            for r in range(n_reqs)
        ]
        engine.warm([plen])
        t0 = time.time()
        outs = engine.run(reqs)
        wall = time.time() - t0
        out[label] = {
            "wall_seconds": wall,
            "prefill_tokens": engine.prefill_tokens,
            "prefill_dispatches": engine.prefill_dispatches,
            "engine_steps": engine.steps,
            "pool": engine.pool_stats,
            "generated": [o.tokens for o in outs],
        }
    sw, rc = out["swap"], out["recompute"]
    assert sw["generated"] == rc["generated"], (
        "host-tier swap changed greedy output"
    )
    assert rc["pool"]["preemptions"] > 0 and sw["pool"]["preemptions"] > 0, (
        f"tight pool never preempted (recompute "
        f"{rc['pool']['preemptions']}, swap {sw['pool']['preemptions']}) — "
        "the probe is not exercising resume at all"
    )
    assert sw["pool"]["swapped_in_pages"] > 0, (
        "swap engine preempted but never resumed from the host tier"
    )
    assert rc["pool"]["swapped_in_pages"] == 0, (
        "recompute engine (host tier off) reported host swap-ins"
    )
    assert sw["prefill_tokens"] < rc["prefill_tokens"], (
        f"swap resume should prefill fewer tokens than recompute "
        f"({sw['prefill_tokens']} vs {rc['prefill_tokens']})"
    )
    # prefill-dominated by construction, so the extra resume prefills are
    # the wall-time story (locally ~2x; the margin absorbs CI jitter)
    assert sw["wall_seconds"] < rc["wall_seconds"], (
        f"swap resume was not faster than recompute "
        f"({sw['wall_seconds']:.3f}s vs {rc['wall_seconds']:.3f}s)"
    )
    for m in out.values():
        del m["generated"]
    return {
        "prompt_len": plen,
        "gen_tokens": gen,
        "num_pages": num_pages,
        "requests": n_reqs,
        **out,
    }


def _sharded_probe(args, shards: int) -> dict:
    """The same burst trace through the paged engine unsharded and
    tensor-parallel over ``shards`` devices (``model``-axis mesh,
    per-shard kv-head page pool). Sharding must be invisible in the
    output — the probe ASSERTS bitwise-identical greedy tokens — so the
    contrast rows measure pure engine overhead/speedup, never quality."""
    from repro.launch.mesh import make_serve_mesh

    cfg = get_smoke_config(args.arch)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(args.seed))
    max_seq = max(args.prompt_lens) + args.gen

    def trace():
        return burst_trace(
            cfg, n_requests=args.requests, burst_size=max(args.burst, 1),
            gap=0.0, prompt_lens=tuple(args.prompt_lens),
            gen_tokens=args.gen, seed=args.seed,
        )

    out = {}
    for label, mesh in (
        ("unsharded", None), ("sharded", make_serve_mesh(shards)),
    ):
        engine = ServeEngine(
            model, params, num_slots=args.slots, max_seq=max_seq,
            prefill="chunked", paged_cache=True, page_size=args.page_size,
            mesh=mesh,
        )
        engine.warm(args.prompt_lens)
        t0 = time.time()
        outs = engine.run(trace())
        wall = time.time() - t0
        total = sum(len(o.tokens) for o in outs)
        ps = engine.pool_stats
        out[label] = {
            "wall_seconds": wall,
            "tokens_per_second": total / max(wall, 1e-9),
            "engine_steps": engine.steps,
            "prefill_compiles": engine.prefill_compiles,
            "compiles": engine.compiles,
            "shards": ps["shards"],
            "mesh_axes": ps["mesh_axes"],
            "occupancy": ps["occupancy"],
            "occupancy_max": ps["occupancy_max"],
            "preemptions": ps["preemptions"],
            "generated": [o.tokens for o in outs],
        }
    assert out["sharded"]["generated"] == out["unsharded"]["generated"], (
        "tensor-parallel serving changed greedy output"
    )
    for m in out.values():
        del m["generated"]
    return {"shards": shards, **out}


def bench_router(args) -> dict:
    """The fault-tolerance probe: the burst trace through a 2-replica
    ``ServeRouter`` with replica 0 KILLED mid-decode, vs. a fault-free
    single engine — greedy AND sampled.

    Asserted here (CI runs this under --router-probe): zero dropped
    requests (every submitted uid completes), token streams BITWISE
    identical to the fault-free run in both decode modes, and the kill
    actually landed mid-flight (``migrated_requests`` > 0 — a kill that
    migrates nothing proves nothing). The reported numbers are the
    failover round-trip the trajectory tracks: migrations, migrated
    requests, per-replica occupancy, sheds, retries, and merged
    throughput under the fault."""
    from repro.launch.router import FaultPlan, ServeRouter
    from repro.launch.sampling import SamplingParams
    import dataclasses

    cfg = get_smoke_config(args.arch)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(args.seed))
    max_seq = max(args.prompt_lens) + args.gen
    engine_kw = dict(
        num_slots=args.slots, max_seq=max_seq, prefill="chunked",
        paged_cache=True, page_size=args.page_size, prefix_cache=True,
        seed=args.seed,
    )

    def trace(sampling):
        reqs = burst_trace(
            cfg, n_requests=args.requests, burst_size=max(args.burst, 1),
            gap=0.0, prompt_lens=tuple(args.prompt_lens),
            gen_tokens=args.gen, seed=args.seed,
        )
        if sampling is not None:
            for r in reqs:
                r.sampling = dataclasses.replace(
                    sampling, seed=sampling.seed + r.uid
                )
        return reqs

    out = {}
    for label, sampling in (
        ("greedy", None),
        ("sampled", SamplingParams(
            temperature=0.8, top_p=0.95, seed=args.seed + 17,
        )),
    ):
        baseline = ServeEngine(model, params, **engine_kw)
        baseline.warm(args.prompt_lens, sampling=sampling)
        base = {o.uid: o.tokens for o in baseline.run(trace(sampling))}

        router = ServeRouter(
            model, params, replicas=2,
            fault_plan=FaultPlan(kill={0: args.kill_step}), **engine_kw,
        )
        router.warm(args.prompt_lens, sampling=sampling)
        t0 = time.time()
        outs = router.run(trace(sampling))
        wall = time.time() - t0
        got = {o.uid: o.tokens for o in outs}
        rs = router.router_stats

        assert len(outs) == args.requests and not router.shed_errors, (
            f"[{label}] dropped requests under failover: "
            f"{len(outs)}/{args.requests} completed, "
            f"shed {[(e.uid, e.reason) for e in router.shed_errors]}"
        )
        assert got == base, (
            f"[{label}] failover changed output tokens"
        )
        assert rs["migrated_requests"] > 0, (
            f"[{label}] kill at step {args.kill_step} migrated nothing — "
            "the fault missed the in-flight window"
        )
        total = sum(len(t) for t in got.values())
        out[label] = {
            "wall_seconds": wall,
            "tokens_per_second": total / max(wall, 1e-9),
            "completed": len(outs),
            "migrations": rs["migrations"],
            "migrated_requests": rs["migrated_requests"],
            "shed_requests": rs["shed_requests"],
            "retries": rs["retries"],
            "occupancy": rs["occupancy"],
            "replica_requests": rs["replica_requests"],
            "replica_steps": rs["replica_steps"],
            "healthy": rs["healthy"],
            "affinity_routed": rs["affinity_routed"],
            "balance_routed": rs["balance_routed"],
        }
    return {
        "replicas": 2,
        "kill_step": args.kill_step,
        "token_identical": True,  # asserted above, recorded for the seed
        **out,
    }


def bench_spec(args) -> dict:
    """Speculative-decoding probe: the burst trace through the paged
    engine target-only vs. with a draft proposing ``--spec-tokens``
    lookahead tokens per slot per round, verified in one batched
    suffix-prefill dispatch.

    The CI-pinned upper bound uses a SAME-ARCH draft initialized from the
    SAME seed — identical parameters, so the target agrees with every
    proposal and acceptance sits at ~100%. That makes the probe
    deterministic: greedy tokens must be BITWISE identical to the
    target-only engine (asserted), and the engine must take ≥ 1.5× fewer
    target dispatches overall (asserted; at full acceptance a k-token
    round replaces k+1 decode steps, so the per-token dispatch rate
    approaches 1/(k+1) against the non-spec engine's 1.0). A second
    FOREIGN-seed draft row records the realistic-acceptance contrast —
    reported, not asserted, since a randomly initialized smoke draft's
    agreement is an accident of the seed."""
    cfg = get_smoke_config(args.arch)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(args.seed))
    max_seq = max(args.prompt_lens) + args.gen

    def trace():
        return burst_trace(
            cfg, n_requests=args.requests, burst_size=max(args.burst, 1),
            gap=0.0, prompt_lens=tuple(args.prompt_lens),
            gen_tokens=args.gen, seed=args.seed,
        )

    out = {}
    for label, draft_seed in (
        ("target_only", None), ("spec", args.seed), ("spec_foreign", None),
    ):
        kw = {}
        if label != "target_only":
            dseed = args.seed if draft_seed is not None else args.seed + 7
            dmodel = build_model(cfg)
            kw = dict(
                draft_model=dmodel,
                draft_params=dmodel.init(jax.random.PRNGKey(dseed)),
                spec_tokens=args.spec_tokens,
            )
        engine = ServeEngine(
            model, params, num_slots=args.slots, max_seq=max_seq,
            prefill="chunked", paged_cache=True, page_size=args.page_size,
            **kw,
        )
        t0 = time.time()
        outs = engine.run(trace())
        wall = time.time() - t0
        total = sum(len(o.tokens) for o in outs)
        ps = engine.pool_stats
        out[label] = {
            "wall_seconds": wall,
            "tokens_per_second": total / max(wall, 1e-9),
            "engine_steps": engine.steps,
            "spec_rounds": ps["spec_rounds"],
            "spec_accept_rate": ps["spec_accept_rate"],
            "spec_dispatches_per_token": ps["spec_dispatches_per_token"],
            "pool_occupancy_max": ps["occupancy_max"],
            "generated": [o.tokens for o in outs],
        }
    base, spec = out["target_only"], out["spec"]
    assert spec["generated"] == base["generated"], (
        "speculative decoding changed greedy output (same-params draft)"
    )
    assert out["spec_foreign"]["generated"] == base["generated"], (
        "speculative decoding changed greedy output (foreign draft)"
    )
    reduction = base["engine_steps"] / max(spec["engine_steps"], 1)
    assert reduction >= 1.5, (
        f"same-params draft cut target dispatches only {reduction:.2f}x "
        f"({base['engine_steps']} -> {spec['engine_steps']} steps) — "
        "lookahead is not landing"
    )
    for m in out.values():
        del m["generated"]
    return {
        "spec_tokens": args.spec_tokens,
        "dispatch_reduction": reduction,
        "token_identical": True,  # asserted above, recorded for the seed
        **out,
    }


_SHARDED_PROBE_MARK = "SHARDED_PROBE_JSON "


def bench_sharded(args) -> dict:
    """Run the sharded probe, in-process when this process already holds
    enough devices (the CI sharded job forces 4 host devices; a multi-chip
    host has them). A one-device CPU host re-execs this module with a
    forced 2-device host platform — XLA reads
    ``--xla_force_host_platform_device_count`` once at jaxlib import, so an
    already-initialized one-device process can never shard itself. On any
    other platform a single device is an error: the probe never swaps an
    accelerator for CPU devices."""
    ndev = len(jax.devices())
    shards = args.shards or min(4, ndev)
    if shards >= 2:
        if ndev < shards:
            raise RuntimeError(
                f"sharded probe wants {shards} shards but only {ndev} "
                "device(s) are visible; set XLA_FLAGS="
                f"--xla_force_host_platform_device_count={shards}"
            )
        return _sharded_probe(args, shards)
    platform = jax.devices()[0].platform
    if platform != "cpu":
        raise RuntimeError(
            f"sharded probe needs >= 2 {platform} devices, found {ndev}"
        )
    if args.sharded_probe:
        # we ARE the re-exec (or the CI probe entry) — if the forced
        # device count did not take, recursing would loop forever
        raise RuntimeError(
            "--sharded-probe needs >= 2 devices; XLA_FLAGS="
            "--xla_force_host_platform_device_count was not applied"
        )
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    cmd = [
        sys.executable, "-m", "benchmarks.serve_bench", "--sharded-probe",
        "--shards", "2", "--arch", args.arch, "--slots", str(args.slots),
        "--requests", str(args.requests), "--burst", str(max(args.burst, 1)),
        "--gen", str(args.gen), "--page-size", str(args.page_size),
        "--seed", str(args.seed), "--prompt-lens",
        *map(str, args.prompt_lens),
    ]
    r = subprocess.run(
        cmd, capture_output=True, text=True, timeout=900, cwd=root,
        env={**os.environ,
             "XLA_FLAGS": "--xla_force_host_platform_device_count=2",
             "JAX_PLATFORMS": "cpu", "PYTHONPATH": "src:."},
    )
    for line in r.stdout.splitlines():
        if line.startswith(_SHARDED_PROBE_MARK):
            return json.loads(line[len(_SHARDED_PROBE_MARK):])
    raise RuntimeError(
        f"sharded probe subprocess failed (exit {r.returncode}):\n"
        f"{r.stderr[-2000:]}"
    )


def bench_burst(args) -> dict:
    """Burst arrivals through the engine: bucketed-batched vs. unbucketed-
    batched vs. per-request prefill.

    The load-bearing numbers: ``prefill_dispatches`` (one per admission
    round when batched — a burst of N costs 1 forward, not N),
    ``prefill_compiles`` (shape bucketing bounds jit specializations by the
    bucket ladder instead of the trace's shape diversity) and TTFT p50/p95
    (the per-request path serializes N prefills before the burst's last
    request sees its first token). With the default ``--burst-gap 0``
    everything arrives at t=0 and runs in virtual time — deterministic and
    CI-safe; a positive gap switches to realtime so arrival-relative TTFT
    stays meaningful."""
    cfg = get_smoke_config(args.arch)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(args.seed))
    max_seq = max(args.prompt_lens) + args.gen
    pages_per_ring = -(-max_seq // args.page_size)
    tight_pages = max(
        pages_per_ring + 1,
        int(args.slots * pages_per_ring * TIGHT_POOL_FRACTION),
    ) + 1
    out = {}
    for label, batched, bucketed, paged, tight in BURST_VARIANTS:
        engine = ServeEngine(
            model, params, num_slots=args.slots, max_seq=max_seq,
            window=args.window, use_kernel=args.use_kernel, prefill="chunked",
            batch_prefill=batched, bucket_prefill=bucketed,
            paged_cache=paged, page_size=args.page_size,
            num_pages=tight_pages if tight else 0,
        )
        reqs = burst_trace(
            cfg, n_requests=args.requests, burst_size=args.burst,
            gap=args.burst_gap, prompt_lens=tuple(args.prompt_lens),
            gen_tokens=args.gen, seed=args.seed,
        )
        # warm every shape a round can dispatch outside the measured window
        # (jit compilation is not a scheduling effect). Compile counters
        # intentionally KEEP the warm traces — total specializations is the
        # number bucketing bounds.
        engine.warm(args.prompt_lens)
        t0 = time.time()
        # gap 0 (default): virtual time, deterministic. gap > 0: honor
        # arrivals against the wall clock so TTFT-from-arrival stays
        # meaningful (virtual time would race ahead of future arrivals and
        # report negative TTFT).
        outs = engine.run(reqs, realtime=args.burst_gap > 0)
        wall = time.time() - t0
        total = sum(len(o.tokens) for o in outs)
        ttft = np.asarray([o.ttft for o in outs])
        lat = np.asarray([o.latency for o in outs])
        out[label] = {
            "prefill_dispatches": engine.prefill_dispatches,
            "prefill_compiles": engine.prefill_compiles,
            "compiles": engine.compiles,
            "engine_steps": engine.steps,
            "wall_seconds": wall,
            "tokens_per_second": total / max(wall, 1e-9),
            "latency_p50": float(np.percentile(lat, 50)),
            "latency_p95": float(np.percentile(lat, 95)),
            "ttft_p50": float(np.percentile(ttft, 50)),
            "ttft_p95": float(np.percentile(ttft, 95)),
            "pool": engine.pool_stats,
            "generated": [o.tokens for o in outs],
        }
    ref = out["batched"]["generated"]
    for label, m in out.items():
        # the paged-vs-ring probe: EVERY variant — paged, tight-pool paged
        # (preempting), and all three ring admissions — must emit the same
        # greedy tokens; memory layout and scheduling are invisible
        assert m["generated"] == ref, (
            f"{label} admission changed greedy output"
        )
        del m["generated"]
    assert (
        out["paged_tight"]["pool"]["preemptions"] > 0
        or out["paged_tight"]["pool"]["occupancy_max"] >= 0.5
    ), "tight pool exercised neither preemption nor high occupancy"
    assert (
        out["batched"]["prefill_compiles"]
        <= out["batched_unbucketed"]["prefill_compiles"]
    ), "bucketed engine must not compile more than the unbucketed one"
    return {
        "mode": "burst",
        "slots": args.slots,
        "requests": args.requests,
        "burst_size": args.burst,
        "burst_gap": args.burst_gap,
        "prompt_lens": list(args.prompt_lens),
        "gen_tokens": args.gen,
        "window": args.window,
        "decode_occupancy": bench_decode_occupancy(slots=args.slots),
        "shared_prefix": bench_shared_prefix(args),
        "kv_int8": bench_kv_int8(args),
        "tiered": bench_tiered(args),
        "sharded": bench_sharded(args),
        "router": bench_router(args),
        "spec": bench_spec(args),
        **out,
    }


def write_bench_seed(res: dict) -> None:
    """APPEND a timestamped entry to the perf trajectory at the repo root.

    The file is ``{"schema": 2, "entries": [...]}`` — one entry per
    ``--smoke`` run, oldest first, so the repo root carries the actual
    perf history PR over PR instead of a single overwritten snapshot. A
    legacy schema-1 file (one flat snapshot) is migrated in place: its
    snapshot becomes the first entry (timestamp null). Entries are flat so
    future PRs diff field-by-field."""
    b = res["batched"]
    pg = res["paged"]
    tight = res["paged_tight"]
    occ = res["decode_occupancy"]
    sp = res["shared_prefix"]
    sh = res["sharded"]
    rt = res["router"]
    k8 = res["kv_int8"]
    td = res["tiered"]
    sd = res["spec"]
    entry = {
        "timestamp": datetime.datetime.now(datetime.timezone.utc).isoformat(
            timespec="seconds"
        ),
        "mode": res["mode"],
        "slots": res["slots"],
        "requests": res["requests"],
        "prompt_lens": res["prompt_lens"],
        "gen_tokens": res["gen_tokens"],
        "tokens_per_second": b["tokens_per_second"],
        "tokens_per_second_paged": pg["tokens_per_second"],
        "latency_p50": b["latency_p50"],
        "latency_p95": b["latency_p95"],
        "ttft_p95": b["ttft_p95"],
        "prefill_dispatches": b["prefill_dispatches"],
        "prefill_dispatches_per_request": res["per_request"][
            "prefill_dispatches"
        ],
        "prefill_compiles": b["prefill_compiles"],
        "prefill_compiles_unbucketed": res["batched_unbucketed"][
            "prefill_compiles"
        ],
        "compiles": b["compiles"],
        "pool_occupancy_mean": pg["pool"]["occupancy_mean"],
        "pool_occupancy_max": pg["pool"]["occupancy_max"],
        "pool_preemptions": pg["pool"]["preemptions"],
        "pool_tight_occupancy_max": tight["pool"]["occupancy_max"],
        "pool_tight_preemptions": tight["pool"]["preemptions"],
        "decode_step_paged_low_us": occ["paged_low_us"],
        "decode_step_unpaged_low_us": occ["unpaged_low_us"],
        "decode_step_paged_full_us": occ["paged_full_us"],
        "decode_step_unpaged_full_us": occ["unpaged_full_us"],
        "decode_step_table_low_us": occ.get("table_low_us"),
        "decode_step_table_full_us": occ.get("table_full_us"),
        "prefix_hit_rate": sp["prefix_on"]["pool"]["prefix_hit_rate"],
        "prefix_prefill_saved_frac": sp["prefill_tokens_saved_frac"],
        "prefix_cow_copies": sp["prefix_on"]["pool"]["cow_copies"],
        "prefix_suffix_dispatches": sp["prefix_on"]["pool"][
            "suffix_dispatches"
        ],
        "prefix_cold_dispatches": sp["prefix_on"]["pool"]["cold_dispatches"],
        "suffix_round_s": sp["prefix_on"]["steady_round_seconds"],
        "cold_round_s": sp["prefix_off"]["steady_round_seconds"],
        "sharded_shards": sh["shards"],
        "tokens_per_second_sharded": sh["sharded"]["tokens_per_second"],
        "tokens_per_second_sharded_base": sh["unsharded"][
            "tokens_per_second"
        ],
        "sharded_occupancy_max": sh["sharded"]["occupancy_max"],
        "sharded_prefill_compiles": sh["sharded"]["prefill_compiles"],
        "router_replicas": rt["replicas"],
        "router_kill_step": rt["kill_step"],
        "router_token_identical": rt["token_identical"],
        "router_migrations": rt["greedy"]["migrations"],
        "router_migrated_requests": rt["greedy"]["migrated_requests"],
        "router_shed_requests": rt["greedy"]["shed_requests"],
        "router_retries": rt["greedy"]["retries"],
        "router_replica_occupancy": rt["greedy"]["occupancy"],
        "router_tokens_per_second": rt["greedy"]["tokens_per_second"],
        "router_tokens_per_second_sampled": rt["sampled"][
            "tokens_per_second"
        ],
        "kv_int8_resident_seqs": k8["resident_seqs_int8"],
        "kv_int8_resident_seqs_fp32": k8["resident_seqs_fp32"],
        "kv_int8_residency_ratio": k8["residency_ratio"],
        "kv_int8_pool_bytes": k8["pool_bytes_int8"],
        "kv_fp32_pool_bytes": k8["pool_bytes_fp32"],
        "kv_int8_token_agreement": k8["token_agreement"],
        "tiered_preemptions": td["swap"]["pool"]["preemptions"],
        "tiered_swapped_out_pages": td["swap"]["pool"]["swapped_out_pages"],
        "tiered_swapped_in_pages": td["swap"]["pool"]["swapped_in_pages"],
        "tiered_wall_swap_s": td["swap"]["wall_seconds"],
        "tiered_wall_recompute_s": td["recompute"]["wall_seconds"],
        "tiered_prefill_tokens_swap": td["swap"]["prefill_tokens"],
        "tiered_prefill_tokens_recompute": td["recompute"]["prefill_tokens"],
        "spec_tokens_k": sd["spec_tokens"],
        "spec_accept_rate": sd["spec"]["spec_accept_rate"],
        "spec_tok_s": sd["spec"]["tokens_per_second"],
        "spec_tok_s_base": sd["target_only"]["tokens_per_second"],
        "spec_dispatches_per_token": sd["spec"]["spec_dispatches_per_token"],
        "spec_dispatch_reduction": sd["dispatch_reduction"],
        "spec_accept_rate_foreign": sd["spec_foreign"]["spec_accept_rate"],
    }
    trajectory = {"schema": 2, "entries": []}
    if os.path.exists(BENCH_SEED_PATH):
        try:
            with open(BENCH_SEED_PATH) as f:
                prior = json.load(f)
        except (OSError, json.JSONDecodeError):
            prior = None
        if isinstance(prior, dict) and isinstance(prior.get("entries"), list):
            trajectory = prior
        elif isinstance(prior, dict):  # schema-1 single snapshot
            prior.setdefault("timestamp", None)
            trajectory["entries"].append(prior)
    trajectory["schema"] = 2
    trajectory["entries"].append(entry)
    with open(BENCH_SEED_PATH, "w") as f:
        json.dump(trajectory, f, indent=1)
        f.write("\n")


def bench_oracle(args) -> dict:
    """Same token budget as sequential fixed batches (batch = slots): the
    baseline a continuous engine replaces."""
    n_batches = (args.requests + args.slots - 1) // args.slots
    plen = max(args.prompt_lens)
    t0 = time.time()
    for b in range(n_batches):
        serve_batch(
            args.arch, batch=args.slots, prompt_len=plen, gen_tokens=args.gen,
            window=args.window, use_kernel=args.use_kernel,
            seed=args.seed + b, log_fn=lambda *_: None,
        )
    wall = time.time() - t0
    total = n_batches * args.slots * args.gen
    return {
        "mode": "oracle-batches",
        "wall_seconds": wall,
        "tokens_per_second": total / max(wall, 1e-9),
    }


def _parser():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--arch", default="stablelm-1.6b")
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--rate", type=float, default=2.0,
                    help="Poisson arrival rate, requests/sec")
    ap.add_argument("--prompt-lens", type=int, nargs="+", default=[16, 32])
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--window", type=int, default=0)
    ap.add_argument("--prefill", choices=("chunked", "interleaved"),
                    default="chunked")
    ap.add_argument("--use-kernel", action="store_true")
    ap.add_argument("--no-paged-cache", dest="paged_cache",
                    action="store_false",
                    help="[poisson] ring KV caches instead of the paged pool")
    ap.add_argument("--page-size", type=int, default=8,
                    help="tokens per physical KV page (paged variants)")
    ap.add_argument("--num-pages", type=int, default=0,
                    help="[poisson] pool pages (0 = ring-equivalent)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--skip-oracle", action="store_true")
    ap.add_argument("--burst", type=int, default=0,
                    help="burst-arrival mode: simultaneous arrivals per "
                    "burst (0 = Poisson trace)")
    ap.add_argument("--burst-gap", type=float, default=0.0,
                    help="seconds between bursts (0 = all at t=0 in "
                    "virtual time; > 0 runs realtime, honoring arrivals)")
    ap.add_argument("--shards", type=int, default=0,
                    help="model-axis shards for the sharded probe (0 = "
                    "auto: min(4, visible devices); a one-device CPU host "
                    "re-execs with 2 forced host devices, a one-device "
                    "accelerator host fails)")
    ap.add_argument("--sharded-probe", action="store_true",
                    help="run ONLY the sharded-vs-unsharded probe and "
                    "print its JSON (the CI sharded job entry point; also "
                    "used internally by the one-device re-exec fallback)")
    ap.add_argument("--router-probe", action="store_true",
                    help="run ONLY the fault-tolerant router probe (2 "
                    "replicas, one injected kill mid-decode; asserts zero "
                    "dropped requests and greedy+sampled token identity "
                    "vs. a fault-free engine) and print its JSON — the CI "
                    "chaos smoke job entry point")
    ap.add_argument("--tiered-probe", action="store_true",
                    help="run ONLY the tiered-KV probes (int8 page pool "
                    "residency at the fp32 byte budget; swap-vs-recompute "
                    "preemption resume — asserts swapped_in_pages > 0, "
                    "fewer prefill tokens, and token identity) and print "
                    "their JSON — the CI tiered smoke job entry point")
    ap.add_argument("--spec-probe", action="store_true",
                    help="run ONLY the speculative-decoding probe (same-"
                    "params draft for the deterministic ~100%% acceptance "
                    "upper bound; asserts greedy token identity and >= "
                    "1.5x fewer target dispatches) and print its JSON — "
                    "the CI spec smoke job entry point")
    ap.add_argument("--spec-tokens", type=int, default=4,
                    help="[spec probe] draft lookahead tokens per slot "
                    "per round")
    ap.add_argument("--kill-step", type=int, default=3,
                    help="[router probe] kill replica 0 at its own step "
                    "number (default lands mid-decode for smoke sizes)")
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized burst run: 8 requests in bursts of 4 "
                    "through 4 slots, mixed prompt lengths; writes the "
                    "BENCH_serve.json perf-trajectory seed at the repo root")
    return ap


def run(argv: list[str] | None = None):
    """Entry point for benchmarks/run.py (and the CLI)."""
    args = _parser().parse_args(argv if argv is not None else [])
    if args.smoke:
        args.burst = args.burst or 4
        args.requests = min(args.requests, 8)
        # mixed lengths so admission rounds span several shapes — the
        # prefill_compiles contrast (bucketed vs. not) needs diversity
        args.prompt_lens = [5, 9, 16]
        args.gen = 8

    if args.sharded_probe:
        res = bench_sharded(args)
        print(_SHARDED_PROBE_MARK + json.dumps(res))
        return res

    if args.router_probe:
        res = bench_router(args)
        g = res["greedy"]
        emit(
            "serve_router_failover",
            g["migrated_requests"],
            f"2 replicas, kill@{res['kill_step']}: {g['completed']} reqs "
            f"completed, {g['migrations']} migration "
            f"({g['migrated_requests']} reqs moved), "
            f"{g['shed_requests']} shed — greedy+sampled tokens identical "
            "to fault-free engine",
        )
        print("ROUTER_PROBE_JSON " + json.dumps(res))
        return res

    if args.spec_probe:
        res = bench_spec(args)
        sp_ = res["spec"]
        emit(
            "serve_spec_decode",
            res["dispatch_reduction"],
            f"k={res['spec_tokens']} same-params draft: accept "
            f"{sp_['spec_accept_rate']:.0%}, "
            f"{sp_['spec_dispatches_per_token']:.2f} dispatch/tok, "
            f"{res['dispatch_reduction']:.1f}x fewer target dispatches "
            f"({res['target_only']['engine_steps']} -> "
            f"{sp_['engine_steps']} steps); foreign-draft accept "
            f"{res['spec_foreign']['spec_accept_rate']:.0%} — greedy "
            "tokens identical",
        )
        print("SPEC_PROBE_JSON " + json.dumps(res))
        return res

    if args.tiered_probe:
        res = {"kv_int8": bench_kv_int8(args), "tiered": bench_tiered(args)}
        k8, td = res["kv_int8"], res["tiered"]
        emit(
            "serve_kv_int8",
            k8["residency_ratio"],
            f"int8 pool at the fp32 byte budget: {k8['resident_seqs_int8']} "
            f"resident seqs vs {k8['resident_seqs_fp32']} fp32 "
            f"({k8['pool_bytes_int8']}B vs {k8['pool_bytes_fp32']}B), 0 "
            f"preempt, token agreement {k8['token_agreement']:.0%}",
        )
        emit(
            "serve_tiered_kv",
            td["swap"]["pool"]["swapped_in_pages"],
            f"tight pool {td['num_pages']} pages: swap resume "
            f"{td['swap']['wall_seconds']:.2f}s "
            f"({td['swap']['pool']['swapped_out_pages']}↓/"
            f"{td['swap']['pool']['swapped_in_pages']}↑ pages, "
            f"{td['swap']['prefill_tokens']} prefill tok) vs recompute "
            f"{td['recompute']['wall_seconds']:.2f}s "
            f"({td['recompute']['prefill_tokens']} prefill tok) — tokens "
            "identical",
        )
        print("TIERED_PROBE_JSON " + json.dumps(res))
        return res

    if args.burst > 0:
        res = bench_burst(args)
        b, u, p = res["batched"], res["batched_unbucketed"], res["per_request"]
        pg, tight = res["paged"], res["paged_tight"]
        occ = res["decode_occupancy"]
        emit(
            "serve_burst_prefill",
            1e6 * b["wall_seconds"] / max(b["engine_steps"], 1),
            f"dispatches {b['prefill_dispatches']} (batched) vs "
            f"{p['prefill_dispatches']} (per-request); compiles "
            f"{b['prefill_compiles']} (bucketed) vs {u['prefill_compiles']} "
            f"(unbucketed); ttft95 {b['ttft_p95']:.3f}s vs "
            f"{p['ttft_p95']:.3f}s",
        )
        emit(
            "serve_paged_pool",
            1e6 * pg["wall_seconds"] / max(pg["engine_steps"], 1),
            f"paged {pg['tokens_per_second']:.1f} tok/s occ "
            f"{pg['pool']['occupancy_max']:.0%} "
            f"{pg['pool']['preemptions']} preempt; tight pool "
            f"({tight['pool']['allocatable_pages']} pages) occ "
            f"{tight['pool']['occupancy_max']:.0%} "
            f"{tight['pool']['preemptions']} preempt — tokens identical "
            "to ring",
        )
        emit(
            "serve_decode_occupancy",
            occ["paged_low_us"],
            f"paged low-occ {occ['paged_low_us']:.0f}us vs unpaged "
            f"{occ['unpaged_low_us']:.0f}us; full-occ "
            f"{occ['paged_full_us']:.0f}us vs {occ['unpaged_full_us']:.0f}us",
        )
        sp = res["shared_prefix"]
        emit(
            "serve_shared_prefix",
            sp["prefix_on"]["prefill_tokens"],
            f"prefilled {sp['prefix_on']['prefill_tokens']} tok shared vs "
            f"{sp['prefix_off']['prefill_tokens']} unshared "
            f"({sp['prefill_tokens_saved_frac']:.0%} saved, hit rate "
            f"{sp['prefix_on']['pool']['prefix_hit_rate']:.0%}, "
            f"{sp['prefix_on']['pool']['cow_copies']} CoW, "
            f"{sp['prefix_on']['pool']['suffix_dispatches']} suffix / "
            f"{sp['prefix_on']['pool']['cold_dispatches']} cold rounds; "
            f"steady warm round {sp['prefix_on']['steady_round_seconds']:.2f}s"
            f" vs {sp['prefix_off']['steady_round_seconds']:.2f}s cold) — "
            "tokens identical",
        )
        k8 = res["kv_int8"]
        emit(
            "serve_kv_int8",
            k8["residency_ratio"],
            f"int8 pool at the fp32 byte budget: {k8['resident_seqs_int8']} "
            f"resident seqs vs {k8['resident_seqs_fp32']} fp32 "
            f"({k8['pool_bytes_int8']}B vs {k8['pool_bytes_fp32']}B), 0 "
            f"preempt, token agreement {k8['token_agreement']:.0%}",
        )
        td = res["tiered"]
        emit(
            "serve_tiered_kv",
            td["swap"]["pool"]["swapped_in_pages"],
            f"tight pool {td['num_pages']} pages: swap resume "
            f"{td['swap']['wall_seconds']:.2f}s "
            f"({td['swap']['pool']['swapped_out_pages']}↓/"
            f"{td['swap']['pool']['swapped_in_pages']}↑ pages, "
            f"{td['swap']['prefill_tokens']} prefill tok) vs recompute "
            f"{td['recompute']['wall_seconds']:.2f}s "
            f"({td['recompute']['prefill_tokens']} prefill tok) — tokens "
            "identical",
        )
        sh = res["sharded"]
        emit(
            "serve_sharded",
            1e6 * sh["sharded"]["wall_seconds"]
            / max(sh["sharded"]["engine_steps"], 1),
            f"{sh['shards']}-shard mesh {sh['sharded']['tokens_per_second']:.1f}"
            f" tok/s vs {sh['unsharded']['tokens_per_second']:.1f} unsharded; "
            f"per-shard occ {sh['sharded']['occupancy_max']:.0%}, "
            f"{sh['sharded']['prefill_compiles']} prefill compiles — "
            "tokens bitwise identical",
        )
        sd = res["spec"]
        emit(
            "serve_spec_decode",
            sd["dispatch_reduction"],
            f"k={sd['spec_tokens']} same-params draft: accept "
            f"{sd['spec']['spec_accept_rate']:.0%}, "
            f"{sd['spec']['spec_dispatches_per_token']:.2f} dispatch/tok, "
            f"{sd['dispatch_reduction']:.1f}x fewer target dispatches; "
            f"foreign-draft accept "
            f"{sd['spec_foreign']['spec_accept_rate']:.0%} — greedy "
            "tokens identical",
        )
        rt = res["router"]
        emit(
            "serve_router_failover",
            rt["greedy"]["migrated_requests"],
            f"2 replicas, kill@{rt['kill_step']}: "
            f"{rt['greedy']['completed']} reqs completed, "
            f"{rt['greedy']['migrations']} migration "
            f"({rt['greedy']['migrated_requests']} reqs moved), "
            f"{rt['greedy']['shed_requests']} shed, occ "
            f"{['%.0f%%' % (100 * o) for o in rt['greedy']['occupancy']]} — "
            "greedy+sampled tokens identical to fault-free engine",
        )
        save_results("serve_bench_burst", res)
        if args.smoke:
            write_bench_seed(res)
        return res

    res = bench_engine(args)
    emit(
        "serve_continuous",
        1e6 * res["wall_seconds"] / max(res["engine_steps"], 1),
        f"{res['tokens_per_second']:.1f} tok/s "
        f"p50 {res['latency_p50']:.3f}s p95 {res['latency_p95']:.3f}s "
        f"ttft50 {res['ttft_p50']:.3f}s",
    )
    payload = {"continuous": res}
    if not args.skip_oracle:
        ob = bench_oracle(args)
        emit(
            "serve_oracle_batches",
            1e6 * ob["wall_seconds"] / max(args.requests * args.gen, 1),
            f"{ob['tokens_per_second']:.1f} tok/s (sequential fixed batches)",
        )
        payload["oracle"] = ob
    save_results("serve_bench", payload)
    return payload


if __name__ == "__main__":
    import sys

    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    run(sys.argv[1:])
