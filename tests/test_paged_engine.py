"""Paged KV cache engine tests (launch/engine.py, paged_cache=True).

The contract, mirroring the rest of the engine suite: memory layout must be
INVISIBLE in the output. The contiguous-ring engine is the oracle — the
paged engine (shared page pool + per-slot page tables) must emit bitwise
token-identical output on every trace both can serve, through admission,
slot reuse, watermark throttling, OOM preemption + resume, sliding
windows, interleaved prefill, sampling, and the page-table decode kernel.
On top of identity, paged mode must do what rings cannot: serve a request
with ``prompt + gen > max_seq``."""
import jax
import numpy as np
import pytest
from tests._hypothesis_compat import given, settings, st

from repro.configs import get_smoke_config
from repro.launch.engine import (
    AdmissionError,
    Request,
    ServeEngine,
    make_requests,
)
from repro.launch.sampling import SamplingParams

ARCH = "stablelm-1.6b"
P, G = 8, 6  # default prompt / generated tokens (ring cap 14)


@pytest.fixture(scope="module")
def model_and_params():
    from repro.models import build_model

    cfg = get_smoke_config(ARCH)
    model = build_model(cfg)
    return cfg, model, model.init(jax.random.PRNGKey(0))


def _build(model_and_params, **kw):
    _, model, params = model_and_params
    kw.setdefault("num_slots", 2)
    kw.setdefault("max_seq", P + G)
    return ServeEngine(model, params, **kw)


def _reqs(cfg, lens, *, gen=G, uid0=0, seed=0):
    base = make_requests(
        cfg, n_requests=len(lens), prompt_len=max(lens), gen_tokens=gen,
        seed=seed,
    )
    return [
        Request(uid=uid0 + j, prompt=r.prompt[: lens[j]], max_new_tokens=gen)
        for j, r in enumerate(base)
    ]


def _assert_same_tokens(a, b):
    ref = {o.uid: o.tokens for o in b}
    assert len(a) == len(b)
    for o in a:
        assert o.tokens == ref[o.uid], (
            f"uid {o.uid}: {o.tokens} != {ref[o.uid]}"
        )


# ------------------------------------------------------- bitwise ring oracle
@pytest.mark.parametrize("page_size", [2, 4, 16])
def test_paged_matches_ring_bitwise(model_and_params, page_size):
    """Mixed prompt lengths + slot backfill: paged == ring token-for-token.
    The jnp paged read gathers pages then runs the ring math verbatim, so
    this holds BITWISE at any page size, including one larger than most
    prompts."""
    cfg, _, _ = model_and_params
    lens = [4, 8, 3, 7, 6]
    ring = _build(model_and_params).run(_reqs(cfg, lens))
    paged = _build(model_and_params, paged_cache=True, page_size=page_size)
    _assert_same_tokens(paged.run(_reqs(cfg, lens)), ring)


def test_identity_pool_size_is_ring_equivalent(model_and_params):
    """Auto pool (num_pages=0) sizes to ring-equivalent capacity: same KV
    budget as the rings it replaces, identical tokens — the degenerate
    page-table configuration reproducing today's engine."""
    cfg, _, _ = model_and_params
    engine = _build(model_and_params, paged_cache=True, page_size=4)
    assert engine.pool.capacity == 2 * -(-(P + G) // 4)
    ring = _build(model_and_params).run(_reqs(cfg, [P] * 4))
    _assert_same_tokens(engine.run(_reqs(cfg, [P] * 4)), ring)
    assert engine.pool.in_use == 0  # every page returned on retirement


def test_placement_invariance(model_and_params):
    """The same trace with every physical page SHIFTED (a bystander holds
    the low pages, and the pool/table are wider): tokens must not move, on
    both the jnp gather path and the table kernel. This is the degenerate-
    vs-scattered page-table equivalence at engine level."""
    cfg, _, _ = model_and_params
    lens = [5, 8, 6]
    for kernel in (False, True):
        a = _build(
            model_and_params, paged_cache=True, page_size=4, use_kernel=kernel
        ).run(_reqs(cfg, lens))
        shifted = _build(
            model_and_params, paged_cache=True, page_size=4, num_pages=31,
            use_kernel=kernel,
        )
        held = shifted.pool.alloc(7)  # push all real allocations up 7 pages
        b = shifted.run(_reqs(cfg, lens))
        shifted.pool.free(held)
        _assert_same_tokens(a, b)


def test_windowed_paged_matches_windowed_ring(model_and_params):
    """Sliding window smaller than the prompt: prefill wraps each slot's
    logical ring across page boundaries."""
    cfg, _, _ = model_and_params
    w = 6
    lens = [P, 5, P, 7]
    ring = _build(model_and_params, window=w).run(_reqs(cfg, lens))
    paged = _build(model_and_params, window=w, paged_cache=True, page_size=2)
    assert paged.cap == w  # logical ring == window, split into pages
    _assert_same_tokens(paged.run(_reqs(cfg, lens)), ring)


def test_interleaved_paged_matches_ring(model_and_params):
    cfg, _, _ = model_and_params
    lens = [P, 4, 6, 5]
    ring = _build(model_and_params, prefill="interleaved").run(_reqs(cfg, lens))
    paged = _build(
        model_and_params, prefill="interleaved", paged_cache=True, page_size=4
    )
    _assert_same_tokens(paged.run(_reqs(cfg, lens)), ring)


def test_per_request_prefill_paged_matches_ring(model_and_params):
    """batch_prefill=False in paged mode routes through width-1
    prefill_slots (prefill_into_slot is ring-only) — same tokens, one
    dispatch per request."""
    cfg, _, _ = model_and_params
    lens = [5, 8, 3]
    ring = _build(model_and_params, batch_prefill=False).run(_reqs(cfg, lens))
    paged = _build(
        model_and_params, batch_prefill=False, paged_cache=True, page_size=4
    )
    outs = paged.run(_reqs(cfg, lens))
    assert paged.prefill_dispatches == len(lens)
    _assert_same_tokens(outs, ring)


@given(
    lens=st.lists(st.integers(2, P), min_size=1, max_size=6),
    page_size=st.sampled_from([2, 4, 8]),
)
@settings(max_examples=10, deadline=None)
def test_property_paged_bitwise_identical(model_and_params, lens, page_size):
    """Any trace that fits both engines: paged output is bitwise identical
    to the ring engine (shared-feasible traces, arbitrary page size)."""
    cfg, _, _ = model_and_params
    ring = _build(model_and_params).run(_reqs(cfg, lens, gen=3))
    paged = _build(model_and_params, paged_cache=True, page_size=page_size)
    _assert_same_tokens(paged.run(_reqs(cfg, lens, gen=3)), ring)


# --------------------------------------------------- beyond ring capacity
def test_oversubscribed_length_served_ring_rejects(model_and_params):
    """The acceptance case: prompt + gen > max_seq is a structured
    rejection in ring mode but serves fine from the paged pool, where a
    sequence is bounded by pool pages, not slot capacity. Tokens pinned
    against a ring engine with a large-enough max_seq."""
    cfg, _, _ = model_and_params
    big_gen = G + 10  # P + G + 10 == 24 > max_seq == 14
    big = lambda: _reqs(cfg, [P], gen=big_gen)

    ring = _build(model_and_params)
    with pytest.raises(AdmissionError, match="exceeds max_seq") as ei:
        ring.submit(big()[0])
    assert ei.value.reason == "exceeds_max_seq" and ei.value.uid == 0

    paged = _build(model_and_params, paged_cache=True, page_size=4)
    assert paged.cap > P + big_gen  # whole-pool logical capacity
    outs = paged.run(big())
    assert len(outs[0].tokens) == big_gen
    oracle = _build(model_and_params, max_seq=P + big_gen).run(big())
    _assert_same_tokens(outs, oracle)


def test_mixed_oversized_and_regular_share_pool(model_and_params):
    """An oversized request decodes alongside regular ones in the shared
    pool; each request matches its own feasible-ring oracle."""
    cfg, _, _ = model_and_params
    gens = [G + 10, G, G]
    base = _reqs(cfg, [P, P, P], gen=max(gens))
    reqs = lambda: [
        Request(uid=r.uid, prompt=r.prompt, max_new_tokens=gens[r.uid])
        for r in base
    ]
    paged = _build(model_and_params, paged_cache=True, page_size=4)
    outs = paged.run(reqs())
    oracle = _build(model_and_params, max_seq=P + max(gens)).run(reqs())
    _assert_same_tokens(outs, oracle)


# ------------------------------------------------- OOM preemption + resume
def test_oom_preempts_youngest_and_resumes_token_identical(model_and_params):
    """A pool too small for two full sequences: decode OOM preempts the
    youngest slot back to the waiting queue; its re-admission re-prefills
    prompt + generated and continues bit-exactly."""
    cfg, _, _ = model_and_params
    lens = [P, P, 7]
    ample = _build(model_and_params, paged_cache=True, page_size=4)
    ref = ample.run(_reqs(cfg, lens))
    assert ample.preemptions == 0
    tight = _build(
        model_and_params, paged_cache=True, page_size=4, num_pages=6
    )  # 5 allocatable pages = 20 tokens for sequences needing 14 each
    outs = tight.run(_reqs(cfg, lens))
    assert tight.preemptions > 0, "tight pool must preempt"
    _assert_same_tokens(outs, ref)
    assert tight.pool.in_use == 0
    # the preempted request visited more than one slot epoch
    assert any(len(h) > 1 for h in tight.slot_history.values())


def test_preemption_preserves_sampling_streams(model_and_params):
    """Preemption must not replay or skip PRNG draws: sampled output under
    a preempting pool equals the ample-pool run stream-for-stream."""
    cfg, _, _ = model_and_params
    lens = [P, P, 6]

    def reqs():
        rs = _reqs(cfg, lens)
        for r in rs:
            r.sampling = SamplingParams(
                temperature=0.9, top_k=7, seed=100 + r.uid
            )
        return rs

    ref = _build(model_and_params, paged_cache=True, page_size=4).run(reqs())
    tight = _build(
        model_and_params, paged_cache=True, page_size=4, num_pages=6
    )
    outs = tight.run(reqs())
    assert tight.preemptions > 0
    _assert_same_tokens(outs, ref)


def test_interleaved_preemption_token_identical(model_and_params):
    """Interleaved prefill allocates pages lazily per teacher-forced step;
    preemption can strike mid-prompt and must still resume exactly."""
    cfg, _, _ = model_and_params
    lens = [P, P, 5]
    ref = _build(
        model_and_params, prefill="interleaved", paged_cache=True, page_size=4
    ).run(_reqs(cfg, lens))
    tight = _build(
        model_and_params, prefill="interleaved", paged_cache=True,
        page_size=4, num_pages=6,
    )
    outs = tight.run(_reqs(cfg, lens))
    _assert_same_tokens(outs, ref)


def test_watermark_throttles_admission(model_and_params):
    """watermark_pages holds back admissions while other slots are live,
    trading concurrency for fewer preemptions — output unchanged."""
    cfg, _, _ = model_and_params
    lens = [P, P, 7]
    ref = _build(model_and_params, paged_cache=True, page_size=4).run(
        _reqs(cfg, lens)
    )
    throttled = _build(
        model_and_params, paged_cache=True, page_size=4, num_pages=8,
        watermark_pages=2,
    )
    outs = throttled.run(_reqs(cfg, lens))
    _assert_same_tokens(outs, ref)
    assert throttled.pool.in_use == 0


# ------------------------------------------------------ page-table kernel
def test_kernel_paged_engine_matches_ring_kernel_engine(model_and_params):
    """With page_size == the ring kernel's chunk (== ring cap here), the
    table kernel streams identical chunks in identical order — engine
    output is bitwise equal to the ring engine under the same kernel."""
    cfg, _, _ = model_and_params
    lens = [P, 5, 7, 6]
    ring = _build(model_and_params, use_kernel=True).run(_reqs(cfg, lens))
    paged = _build(
        model_and_params, use_kernel=True, paged_cache=True, page_size=P + G
    )
    _assert_same_tokens(paged.run(_reqs(cfg, lens)), ring)


def test_kernel_preemption_token_identical(model_and_params):
    cfg, _, _ = model_and_params
    lens = [P, P, 6]
    ref = _build(
        model_and_params, use_kernel=True, paged_cache=True, page_size=4
    ).run(_reqs(cfg, lens))
    tight = _build(
        model_and_params, use_kernel=True, paged_cache=True, page_size=4,
        num_pages=6,
    )
    outs = tight.run(_reqs(cfg, lens))
    assert tight.preemptions > 0
    _assert_same_tokens(outs, ref)


def test_kernel_windowed_paged_matches_ring_kernel(model_and_params):
    """Windowed, wrapping paged cache through the table kernel: with
    page_size == window the table kernel streams the one logical page the
    ring kernel streams as its one chunk — engine output is bitwise equal
    to the windowed ring engine under the same kernel. (Comparing kernel
    against the jnp path instead is only ~allclose in bf16 — online
    softmax reassociates — so the deterministic pin is kernel-vs-kernel.)"""
    cfg, _, _ = model_and_params
    w = 6
    lens = [P, 5, P, 7]
    ring = _build(model_and_params, window=w, use_kernel=True).run(
        _reqs(cfg, lens)
    )
    paged = _build(
        model_and_params, window=w, use_kernel=True, paged_cache=True,
        page_size=w,
    )
    _assert_same_tokens(paged.run(_reqs(cfg, lens)), ring)


def test_kernel_windowed_placement_invariance(model_and_params):
    """Windowed table kernel at sub-window page size: physical placement
    (different pool sizes) must be bitwise invisible even while the
    logical ring wraps across page boundaries every ``window`` tokens."""
    cfg, _, _ = model_and_params
    lens = [P, 5, P, 7]
    a = _build(
        model_and_params, window=6, use_kernel=True, paged_cache=True,
        page_size=2,
    ).run(_reqs(cfg, lens))
    shifted = _build(
        model_and_params, window=6, use_kernel=True, paged_cache=True,
        page_size=2, num_pages=17,
    )
    held = shifted.pool.alloc(5)  # different physical homes for every page
    b = shifted.run(_reqs(cfg, lens))
    shifted.pool.free(held)
    _assert_same_tokens(a, b)


# ------------------------------------------------- structured admission
def test_submit_rejection_is_structured_and_does_not_wedge(model_and_params):
    """An oversized submit raises AdmissionError (uid + reason attached)
    WITHOUT entering the queue; the engine then serves later requests
    normally — the scheduling round can never wedge on a doomed request."""
    cfg, _, _ = model_and_params
    engine = _build(model_and_params)
    with pytest.raises(AdmissionError) as ei:
        engine.submit(
            Request(uid=99, prompt=np.zeros(P, np.int32), max_new_tokens=G + 1)
        )
    assert ei.value.uid == 99
    assert ei.value.reason == "exceeds_max_seq"
    assert isinstance(ei.value, ValueError)  # legacy handler compatibility
    assert len(engine.waiting) == 0
    ring = _build(model_and_params).run(_reqs(cfg, [P, 5]))
    outs = engine.run(_reqs(cfg, [P, 5]))
    _assert_same_tokens(outs, ring)


def test_paged_submit_rejects_beyond_pool(model_and_params):
    """Paged mode still rejects what the POOL can never hold — with its
    own structured reason."""
    cfg, _, _ = model_and_params
    engine = _build(
        model_and_params, paged_cache=True, page_size=4, num_pages=4
    )  # cap = 3 pages × 4 = 12 tokens
    with pytest.raises(AdmissionError, match="pool capacity") as ei:
        engine.submit(
            Request(uid=7, prompt=np.zeros(P, np.int32), max_new_tokens=5)
        )
    assert ei.value.reason == "exceeds_pool" and ei.value.uid == 7
    # a fitting request still serves
    outs = engine.run(_reqs(cfg, [4], gen=4))
    assert len(outs[0].tokens) == 4


def test_submit_rejects_prompt_pool_can_never_hold(model_and_params):
    """Regression: a request within LOGICAL table capacity but whose pages
    can never all be physically resident (tight pool) used to wait at the
    queue head forever — alloc kept returning None while admission clamped
    to table_width. It must be a structured submit-time rejection, and the
    engine must keep serving."""
    cfg, _, _ = model_and_params
    engine = _build(
        model_and_params, paged_cache=True, page_size=4, num_pages=5
    )  # 4 allocatable pages = 16 resident tokens; logical cap is wider
    assert engine.cap > engine.pool.capacity * engine.page_size
    doomed = Request(
        uid=13, prompt=np.zeros(15, np.int32), max_new_tokens=3
    )  # 18 tokens <= cap, but ceil(18/4) = 5 pages > 4 allocatable
    with pytest.raises(AdmissionError, match="pool capacity") as ei:
        engine.submit(doomed)
    assert ei.value.reason == "exceeds_pool" and ei.value.uid == 13
    assert len(engine.waiting) == 0
    ring = _build(model_and_params).run(_reqs(cfg, [5, 4], gen=3))
    outs = engine.run(_reqs(cfg, [5, 4], gen=3))
    _assert_same_tokens(outs, ring)


def test_default_table_width_is_ring_equivalent(model_and_params):
    """Windowless table width defaults to num_slots × pages_per_ring (the
    jnp gather/attend work the ring engine paid), NOT the whole pool — an
    oversized pool must not widen every slot's logical ring. Whole-pool
    width is the ``long_requests`` / ``table_width=`` opt-in."""
    cfg, _, _ = model_and_params
    ppr = -(-(P + G) // 4)
    bounded = _build(
        model_and_params, paged_cache=True, page_size=4, num_pages=31
    )
    assert bounded.table_width == 2 * ppr  # not 30
    wide = _build(
        model_and_params, paged_cache=True, page_size=4, num_pages=31,
        long_requests=True,
    )
    assert wide.table_width == 30
    explicit = _build(
        model_and_params, paged_cache=True, page_size=4, num_pages=31,
        table_width=12,
    )
    assert explicit.table_width == 12 and explicit.cap == 48
    # same tokens at every width on a shared-feasible trace
    lens = [5, 8, 6]
    ring = _build(model_and_params).run(_reqs(cfg, lens))
    for eng in (bounded, wide, explicit):
        _assert_same_tokens(eng.run(_reqs(cfg, lens)), ring)


# ------------------------------------------------------------- bookkeeping
def test_pool_stats_and_occupancy_trace(model_and_params):
    cfg, _, _ = model_and_params
    ring = _build(model_and_params)
    assert ring.pool_stats is None
    engine = _build(model_and_params, paged_cache=True, page_size=4)
    # the pool fill each decode dispatch sees, recorded beside the engine's
    # own running count, sum and max
    seen = []
    decode = engine._decode

    def observed(*a):
        seen.append(engine.pool.in_use / engine.pool.capacity)
        return decode(*a)

    engine._decode = observed
    engine.run(_reqs(cfg, [P, P, 5]))
    stats = engine.pool_stats
    assert stats["pages_in_use"] == 0
    assert stats["peak_pages_in_use"] > 0
    assert 0.0 < stats["occupancy_max"] <= 1.0
    assert engine.occupancy_steps == engine.steps == len(seen)
    assert stats["occupancy_mean"] == pytest.approx(np.mean(seen))
    assert stats["occupancy_max"] == max(seen)
    engine.reset_metrics()
    assert engine.pool_stats["occupancy_max"] == 0.0
    assert engine.pool_stats["occupancy_mean"] == 0.0
    assert engine.occupancy_steps == 0


def test_paged_cache_specs_shapes(model_and_params):
    """The dry-run spec helper mirrors the paged pool layout without
    allocating: KV bytes scale with num_pages, not num_slots × max_seq."""
    from repro.launch.specs import paged_cache_specs
    from repro.models import build_model

    cfg, model, _ = model_and_params
    specs = paged_cache_specs(
        model, num_slots=3, num_pages=9, page_size=4, table_width=8
    )
    assert specs["pos"].shape == (3,)
    assert specs["table"].shape == (3, 8)
    assert specs["k"].shape == (
        cfg.n_layers, 9, 4, cfg.n_kv_heads, cfg.resolved_head_dim
    )
    ssm = build_model(get_smoke_config("xlstm-125m"))
    with pytest.raises(ValueError, match="no paged-cache API"):
        paged_cache_specs(ssm, num_slots=2, num_pages=5, page_size=4,
                          table_width=4)


def test_retired_slot_drift_is_harmless(model_and_params):
    """After a slot retires, its device ``pos`` keeps advancing inside the
    jitted step while its table row points at the scratch page — live
    slots' pages must never be touched (pinned by serving a long request
    next to repeatedly retiring short ones)."""
    cfg, _, _ = model_and_params
    lens = [P, 3, 3, 3, 3]
    gens = [G + 8, 1, 1, 1, 1]  # slot 0 long-lived, slot 1 churns
    base = _reqs(cfg, lens, gen=max(gens))
    reqs = lambda: [
        Request(uid=r.uid, prompt=r.prompt[: lens[r.uid]],
                max_new_tokens=gens[r.uid])
        for r in base
    ]
    paged = _build(model_and_params, paged_cache=True, page_size=4)
    outs = paged.run(reqs())
    oracle = _build(model_and_params, max_seq=P + max(gens)).run(reqs())
    _assert_same_tokens(outs, oracle)


# ------------------------------------------------------ SLO-aware preemption
def test_priority_overrides_youngest_preemption(model_and_params):
    """Decode-OOM victim selection is lowest-priority-then-youngest: a
    LOW-priority OLD slot is preempted before a default-priority younger
    one (pre-SLO behavior picked the youngest unconditionally) — and the
    preempted request still resumes token-identically."""
    cfg, _, _ = model_and_params
    lens = [P, P, 7]
    ample = _build(model_and_params, paged_cache=True, page_size=4)
    ref = ample.run(_reqs(cfg, lens))

    def reqs_with_prio():
        rs = _reqs(cfg, lens)
        rs[0].priority = -1  # oldest slot, but lowest priority
        return rs

    tight = _build(
        model_and_params, paged_cache=True, page_size=4, num_pages=6
    )
    outs = tight.run(reqs_with_prio())
    assert tight.preemptions > 0, "tight pool must preempt"
    _assert_same_tokens(outs, ref)
    # uid0 (not the youngest) paid the preemptions: it re-admitted at
    # least once, while the default-priority slots never did
    assert len(tight.slot_history[0]) > 1
    assert all(len(tight.slot_history[u]) == 1 for u in (1, 2))


def test_equal_priorities_preempt_youngest_as_before(model_and_params):
    """All-default-priority traffic must reproduce the pre-SLO victim
    order exactly: the YOUNGEST slot is preempted, never an older one."""
    cfg, _, _ = model_and_params
    lens = [P, P]
    tight = _build(
        model_and_params, paged_cache=True, page_size=4, num_pages=6
    )
    outs = tight.run(_reqs(cfg, lens))
    assert tight.preemptions > 0
    assert len(tight.slot_history[1]) > 1, "youngest must be the victim"
    assert len(tight.slot_history[0]) == 1
    ample = _build(model_and_params, paged_cache=True, page_size=4)
    _assert_same_tokens(outs, ample.run(_reqs(cfg, lens)))


# ----------------------------------------------------- migration export/import
def test_export_import_mid_decode_token_identical(model_and_params):
    """The failover primitive: strip a half-served engine's in-flight
    population (live slots + queue) and adopt it on a fresh engine; the
    merged outputs equal an uninterrupted single-engine run."""
    cfg, _, _ = model_and_params
    lens = [P, P, 7, 6]
    ref = _build(model_and_params, paged_cache=True, page_size=4).run(
        _reqs(cfg, lens)
    )
    a = _build(model_and_params, paged_cache=True, page_size=4)
    for r in _reqs(cfg, lens):
        a.submit(r)
    early = []
    for _ in range(3):  # mid-decode: slots live, queue non-empty
        early += a.step()
    items = a.export_inflight()
    assert items and not a.has_work, "export must strip everything"
    assert a.pool.in_use - (
        0 if a.prefix is None else a.prefix.size
    ) == 0, "exported slots must release their pages"
    b = _build(model_and_params, paged_cache=True, page_size=4)
    b.import_inflight(items)
    outs = early + b.run()
    _assert_same_tokens(outs, ref)


def test_export_import_sampled_streams_continue(model_and_params):
    """Migration re-enters via the resume path: per-request PRNG streams
    continue where they stopped — no draw replayed or skipped."""
    cfg, _, _ = model_and_params
    lens = [P, 7]

    def reqs():
        rs = _reqs(cfg, lens)
        for r in rs:
            r.sampling = SamplingParams(
                temperature=0.9, top_k=7, seed=100 + r.uid
            )
        return rs

    ref = _build(model_and_params, paged_cache=True, page_size=4).run(reqs())
    a = _build(model_and_params, paged_cache=True, page_size=4)
    for r in reqs():
        a.submit(r)
    early = []
    for _ in range(3):
        early += a.step()
    b = _build(model_and_params, paged_cache=True, page_size=4)
    b.import_inflight(a.export_inflight())
    _assert_same_tokens(early + b.run(), ref)


def test_import_rejects_over_capacity(model_and_params):
    """A migrated request no replica-sized pool can hold is refused with a
    structured error, not silently truncated."""
    _, model, params = model_and_params
    cfg, _, _ = model_and_params
    big = _reqs(cfg, [P], gen=20)[0]
    small = ServeEngine(
        model, params, num_slots=1, max_seq=P + G,
        paged_cache=True, page_size=4,
    )
    with pytest.raises(AdmissionError) as ei:
        small.import_inflight([(big, None)])
    assert ei.value.reason == "exceeds_pool"
