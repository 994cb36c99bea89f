"""Continuous-batching serve engine: slot-based scheduler over a shared
per-slot ring-buffer KV cache.

The engine turns the single-batch serve path (launch/serve.py, kept as the
reference oracle) into iteration-level scheduling in the Orca/vLLM style,
sized for this repo's CPU-verifiable models:

* A fixed pool of ``num_slots`` KV-cache slots — the rows of ONE stacked
  (L, B, C, Hkv, hd) ring cache with per-slot positions
  (``models/attention.py``; ``models/transformer.py::init_decode_cache``
  with ``per_slot=True``). Admitting a request claims a free slot and
  resets its position; retiring a request frees the slot for immediate
  backfill. Stale k/v are never cleared — the decode validity mask derives
  entirely from ``pos``.
* Requests arrive at arbitrary times with arbitrary prompt/output lengths
  (mirroring how ``core/scheduler.py`` handles clouds completing at
  different wall times). A FIFO admission queue feeds free slots in
  arrival order.
* Prefill is either **chunked** (the whole prompt in one q-chunked
  ``attend_full`` forward written into the slot's ring rows —
  ``prefill_into_slot``) or **interleaved** (prompt tokens teacher-forced
  one per engine step through the SAME jitted decode step that serves the
  decoding slots, so a step can simultaneously prefill some slots and
  decode others). Both are token-identical to the sequential oracle.
* One jitted decode step per engine iteration advances every live slot by
  one token; sequences retire on EOS or max-new-tokens. The sliding-window
  ring cache (``window > 0``) and the Pallas flash-decode kernel
  (``use_kernel=True``, interpret mode on CPU) thread straight through.
* Hot-path perf, all default-on and output-invisible: admission rounds are
  padded to SHAPE BUCKETS (pow2 width × geometric length ladder) so
  ``prefill_slots`` compiles O(buckets) not O(distinct round shapes) — the
  ``compiles`` counters prove the bound; the KV cache is DONATED through
  every jitted step (no per-step full-cache copy); and with the kernel on,
  decode runs the PAGED variant (``kernels/paged_decode.py``) so each slot
  skips ring pages beyond its live span.
* PAGED KV CACHE (``paged_cache=True``): instead of per-slot contiguous
  rings sized ``num_slots × max_seq``, ONE shared pool of fixed-size
  physical pages plus per-slot page tables (vLLM-PagedAttention layout).
  A host-side ``PagePool`` free-list allocator hands pages out at
  admission (enough for the prompt) and LAZILY one page per slot as
  decode crosses page boundaries; retirement frees them for immediate
  reuse. When the pool runs dry mid-decode the YOUNGEST slot is preempted
  back to the head of the waiting queue (its pages freed, its generated
  tokens carried in a resume record) and re-admitted later by re-prefilling
  prompt+generated — token-identical to an uninterrupted run. The
  ``prompt + gen ≤ max_seq`` admission guard disappears: a sequence is
  bounded by POOL pages (logical capacity = table_width × page_size), so
  one request may stretch across memory that ring mode would have
  statically split across all slots. The ring path stays as the oracle —
  paged output is pinned bitwise token-identical to it.
* PREFIX SHARING (``prefix_cache=True``, paged mode): pages carry
  REFCOUNTS, and a radix trie (``launch/prefix_cache.py``) indexes retired
  requests' full prompt pages by page-sized token chunks. A new request
  whose prompt starts with an indexed prefix maps those logical pages onto
  the SAME physical pages (``PagePool.share``) and prefills only the
  uncached suffix through ``prefill_slots(starts=...)`` — one prefill,
  many readers. A fully cached prompt re-prefills just its final token
  into a COPY-ON-WRITE split of the last shared page (the index copy
  stays immutable). Index entries are LRU-evicted under pool pressure —
  before watermark throttling and before OOM preemption — so a cache-hot
  pool degrades gracefully to the no-sharing engine. Output stays
  token-identical to the non-shared paged engine, which stays the oracle.

    PYTHONPATH=src python -m repro.launch.serve --continuous \
        --arch stablelm-1.6b --slots 4 --requests 8 --page-size 16
"""
from __future__ import annotations

import collections
import dataclasses
import functools
import logging
import time
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec

from repro.configs import get_config, get_smoke_config
from repro.data import SyntheticCorpus
from repro.launch.mesh import (
    axis_size,
    make_serve_mesh,
    serve_cache_specs,
    serve_param_specs,
    serve_shardings,
)
from repro.launch.prefix_cache import PrefixCache
from repro.launch.sampling import (
    SamplingParams,
    sample_token,
    speculative_acceptance,
)
from repro.launch.spec_decode import make_draft_backend
from repro.models import attention, build_model
from repro.models.model import ModelAPI, localize_config
from repro.models.sharding import use_tensor_axis
from repro.models.transformer import reset_slot

PREFILL_MODES = ("chunked", "interleaved")

# Smallest padded prompt length the bucket ladder produces. Rounds pad up to
# the next power of two from here, so ``prefill_slots`` compiles at most
# O(log(max_prompt / LEN_BUCKET_MIN)) distinct lengths instead of one per
# distinct round maximum.
LEN_BUCKET_MIN = 8

# Host spans of the engine's phases, named ``engine.<phase>``. They land on
# the profiler's clock beside the device's events, and cost about a
# microsecond each when no profiler session is running.
_span = jax.profiler.TraceAnnotation


def bucket_width(n: int, num_slots: int) -> int:
    """Round an admission-round width up to a power of two, capped at the
    slot-pool size — the extra rows are no-op padding rows (length 0)."""
    w = 1
    while w < n:
        w *= 2
    return min(w, num_slots)


def bucket_length(s: int, floor: int = LEN_BUCKET_MIN) -> int:
    """Round a padded prompt length up the geometric ladder
    floor, 2·floor, 4·floor, … — right-padding is invisible to the
    causally-masked prefill, and ring writes stop at each row's true
    length."""
    length = floor
    while length < s:
        length *= 2
    return length


def bucket_pages(pages: int, table_width: int) -> int:
    """Round a suffix round's max cached-prefix width (pages) up the pow2
    ladder 1, 2, 4, …, capped at the table width — the static
    ``prefix_pages`` bound handed to the suffix-prefill trace, so compile
    counts stay O(log table_width) across arbitrary start diversity. Rows
    whose prefix is shorter than the bucket attend dead lanes that the
    FAR-position mask (jnp path) / ``pl.when`` page skip (kernel) kill."""
    w = 1
    while w < pages:
        w *= 2
    return min(w, max(table_width, 1))


class AdmissionError(ValueError):
    """Structured submit-time rejection.

    Raised by ``ServeEngine.submit`` for requests the engine could NEVER
    serve (they exceed static capacity) — rejecting at submit keeps a
    doomed request out of the queue entirely, so a scheduling round can
    never wedge on it. ``uid`` and ``reason`` let callers map the failure
    back to the request without parsing the message; ``reason`` is one of
    ``"exceeds_max_seq"`` (ring mode) or ``"exceeds_pool"`` (paged mode).
    Subclasses ValueError so pre-existing callers' handlers keep working.
    """

    def __init__(self, uid: int, reason: str, message: str):
        super().__init__(message)
        self.uid = uid
        self.reason = reason


class PagePool:
    """Host-side refcounted free-list allocator over the shared physical KV
    page pool.

    Page 0 is the reserved SCRATCH page: it is never handed out, and every
    unallocated page-table entry points at it, so stray writes (retired
    slots whose ``pos`` keeps drifting inside the jitted decode step, tail
    entries of a prefill scatter) land somewhere harmless.

    Pages carry REFCOUNTS so one physical page can back several logical
    views (shared prompt prefixes, the prefix-cache index): ``alloc`` hands
    a page out at rc=1, ``share`` adds a reference to an already-live page,
    and ``free`` drops one reference — only a page whose count reaches 0
    returns to the free list. Sharing a free page and over-freeing a live
    one are both hard errors (rc-underflow / double-free guards), because
    either would let two owners scribble on one page.

    The free list is a LIFO stack: ``free`` pushes, ``alloc`` pops, so the
    MOST RECENTLY freed pages are reused first (they are the likeliest to
    still be resident in any cache hierarchy) — ``tests/test_page_pool.py``
    pins this order. A fresh pool allocates pages in ascending order
    1, 2, …, P-1.
    """

    def __init__(self, num_pages: int, page_size: int):
        if num_pages < 2:
            raise ValueError(f"num_pages must be >= 2 (page 0 is reserved), got {num_pages}")
        if page_size < 1:
            raise ValueError(f"page_size must be >= 1, got {page_size}")
        self.num_pages = num_pages
        self.page_size = page_size
        # stack: pop() yields 1, 2, 3, … on a fresh pool
        self._free = list(range(num_pages - 1, 0, -1))
        self._rc: dict[int, int] = {}
        self.peak_in_use = 0

    @property
    def capacity(self) -> int:
        """Allocatable pages (the scratch page is not)."""
        return self.num_pages - 1

    @property
    def available(self) -> int:
        return len(self._free)

    @property
    def in_use(self) -> int:
        return self.capacity - self.available

    @property
    def live_refs(self) -> int:
        """Total outstanding references across all live pages (≥ in_use;
        the excess is the number of shared views)."""
        return sum(self._rc.values())

    def refcount(self, page: int) -> int:
        return self._rc.get(page, 0)

    def alloc(self, n: int) -> list[int] | None:
        """Pop ``n`` pages at rc=1 each, or None (and no partial
        allocation) if the pool cannot cover the request."""
        if n > len(self._free):
            return None
        pages = [self._free.pop() for _ in range(n)]
        for p in pages:
            self._rc[p] = 1
        self.peak_in_use = max(self.peak_in_use, self.in_use)
        return pages

    def share(self, page: int) -> int:
        """Add a reference to a LIVE page (prefix sharing / index pin).
        Returns the new count. Sharing a free or foreign page is an error —
        a free page may be re-allocated and overwritten at any moment."""
        if self._rc.get(page, 0) < 1:
            raise ValueError(f"share of free/foreign page {page}")
        self._rc[page] += 1
        return self._rc[page]

    def free(self, pages) -> None:
        """Drop one reference per listed page; pages reaching rc=0 return
        to the free list (LIFO). Freeing a page with no live references is
        the double-free / rc-underflow guard firing."""
        for p in pages:
            rc = self._rc.get(p, 0)
            if rc < 1:
                raise ValueError(f"double/foreign free of page {p}")
            if rc == 1:
                del self._rc[p]
                self._free.append(p)
            else:
                self._rc[p] = rc - 1


class HostTier:
    """Host-RAM page store backing the device pool: the second tier of the
    KV cache hierarchy.

    Two kinds of entry share one LRU budget of ``capacity_pages``:

    * SWAP entries (key ``("swap", uid)``): every page of a preempted slot,
      gathered device→host before the pool reference drops. Re-admission
      restores them with one batched host→device scatter instead of
      recomputing the KV through a resume re-prefill.
    * PREFIX entries (key ``("prefix", token_tuple)``): a prefix-index page
      demoted at LRU eviction; a later radix match promotes it back into a
      freshly allocated pool page.

    Content is immutable once stored (pages are copied, never aliased), so
    a dropped entry is never a correctness event — the engine falls back to
    recompute (swap) or a cold prefill (prefix). Pure host-side numpy; all
    device traffic lives in the engine's gather/scatter jits."""

    def __init__(self, capacity_pages: int):
        if capacity_pages < 1:
            raise ValueError(
                f"host tier capacity must be >= 1 page, got {capacity_pages}"
            )
        self.capacity_pages = capacity_pages
        self._entries: collections.OrderedDict[tuple, dict] = (
            collections.OrderedDict()
        )
        self._pages = 0
        self.evictions = 0  # entries dropped by LRU pressure

    @property
    def pages(self) -> int:
        """Pages currently resident in the tier."""
        return self._pages

    def put(self, key: tuple, arrays: dict, n_pages: int) -> bool:
        """Store ``arrays`` (name → (L, n_pages, …) numpy) under ``key``,
        LRU-evicting older entries to fit. False (and no store) when the
        entry alone exceeds the tier."""
        if n_pages > self.capacity_pages:
            return False
        self.pop(key)
        while self._pages + n_pages > self.capacity_pages:
            _, old = self._entries.popitem(last=False)
            self._pages -= old["n"]
            self.evictions += 1
        self._entries[key] = {"arrays": arrays, "n": n_pages}
        self._pages += n_pages
        return True

    def get(self, key: tuple) -> dict | None:
        """Entry arrays for ``key`` (LRU touch), or None."""
        entry = self._entries.get(key)
        if entry is None:
            return None
        self._entries.move_to_end(key)
        return entry["arrays"]

    def n_pages(self, key: tuple) -> int:
        entry = self._entries.get(key)
        return 0 if entry is None else entry["n"]

    def pop(self, key: tuple) -> dict | None:
        entry = self._entries.pop(key, None)
        if entry is None:
            return None
        self._pages -= entry["n"]
        return entry["arrays"]

    def clear(self) -> None:
        self._entries.clear()
        self._pages = 0


@dataclasses.dataclass
class _ResumeState:
    """Generation state of a preempted request, carried across its trip
    back through the waiting queue. Re-admission prefills prompt +
    generated[:-1] in one chunked forward, restores these fields, and
    continues decoding exactly where the preempted slot stopped.

    ``host_key`` marks a SWAPPED preemption: the slot's KV pages were
    copied to the ``HostTier`` before its pool refs dropped, and
    re-admission restores them with a device scatter (no prefill at all) —
    bitwise the pages the slot held, so token-identity is trivial. A
    dropped tier entry (LRU) falls back to the recompute path above.
    ``pos`` is the slot's write position at preemption (tokens written =
    prompt + generated[:-1] for a decoding slot).

    ``host_arrays`` carries the page CONTENT itself (name → (L, n, …)
    numpy) when the record migrates BETWEEN engines (``export_inflight``):
    a host-tier key is meaningless outside the engine that owns the tier,
    but the copied pages are engine-independent — ``import_inflight``
    adopts layout-compatible arrays into the local tier so a migrated
    request swaps in instead of re-prefilling its whole history."""
    generated: list[int]
    key: jax.Array | None
    first_token_time: float
    admit_time: float
    host_key: tuple | None = None
    pos: int = 0
    host_arrays: dict | None = None


@dataclasses.dataclass
class Request:
    """One generation request. ``arrival_time`` is seconds relative to the
    engine clock; the engine never admits a request before it arrives.
    ``sampling=None`` (or temperature 0) decodes greedily.

    ``priority`` orders preemption, not admission: when the paged pool
    runs dry the LOWEST-priority live slot is preempted first (ties break
    youngest-first, the pre-SLO behavior — priority 0 everywhere
    reproduces it exactly). Higher numbers are more important.
    ``deadline_s`` is an SLO relative to ``arrival_time``: a request still
    QUEUED past its deadline is shed with a structured
    ``AdmissionError("deadline_exceeded")`` record instead of being served
    uselessly late (and instead of wedging admission behind it). A request
    already decoding is never deadline-shed — its tokens are real work."""
    uid: int
    prompt: np.ndarray            # (prompt_len,) int32 token ids
    max_new_tokens: int
    arrival_time: float = 0.0
    sampling: SamplingParams | None = None
    priority: int = 0
    deadline_s: float | None = None

    def __post_init__(self):
        self.prompt = np.asarray(self.prompt, np.int32).reshape(-1)
        assert self.prompt.size > 0, "empty prompt"
        assert self.max_new_tokens > 0, "max_new_tokens must be positive"


@dataclasses.dataclass
class RequestOutput:
    uid: int
    prompt: list[int]
    tokens: list[int]             # generated ids (greedy or sampled), <= max_new
    slot: int                     # slot the request was served from
    finish_reason: str            # "eos" | "length" | "timeout"
    arrival_time: float
    admit_time: float
    first_token_time: float
    finish_time: float

    @property
    def latency(self) -> float:
        return self.finish_time - self.arrival_time

    @property
    def ttft(self) -> float:
        """Time to first token, from arrival (includes queueing)."""
        return self.first_token_time - self.arrival_time


@dataclasses.dataclass
class _Slot:
    """Host-side state of one live slot."""
    req: Request
    pending: collections.deque    # prompt tokens not yet fed (interleaved)
    generated: list[int]
    next_feed: int                # token the next decode step consumes
    admit_time: float
    first_token_time: float = -1.0
    key: jax.Array | None = None  # per-REQUEST sampling stream (None = greedy)
    feed: np.ndarray | None = None  # tokens to prefill / teacher-force —
    #                                 the prompt, or prompt + generated[:-1]
    #                                 when resuming a preempted request
    prefix_len: int = 0           # leading feed tokens already resident in
    #                               shared prefix pages (chunked prefill
    #                               covers only feed[prefix_len:])
    resumed: bool = False         # suppress the next emission: the token is
    #                               already known (generated[-1])
    pos_host: int = 0             # host mirror of the slot's write position
    #                               (tokens written so far) — drives lazy
    #                               page allocation in paged mode
    seq: int = 0                  # admission sequence number (preemption
    #                               picks the YOUNGEST = max seq)


class ServeEngine:
    """Slot-based continuous-batching scheduler around one jitted decode step.

    Parameters
    ----------
    model, params : a ``ModelAPI`` with the slot-cache members (dense / MoE
        transformer family) and its initialized parameters.
    num_slots : size of the fixed KV-slot pool == decode batch width.
    max_seq : ring capacity per slot when ``window == 0``; every request
        must satisfy prompt_len + max_new_tokens <= max_seq.
    window : sliding-window span; > 0 shrinks the ring to the window.
    use_kernel : route decode attention through the Pallas flash-decode
        kernel (interpret mode on CPU).
    prefill : "chunked" (whole prompt in one forward at admission) or
        "interleaved" (teacher-force the prompt through the decode step,
        one token per engine iteration).
    batch_prefill : chunked mode only — prefill ALL slots admitted in one
        scheduling round through ONE ``prefill_slots`` forward (prompts
        right-padded to the round's max length) instead of one dispatch per
        request. Greedy output is token-identical either way; a burst of N
        arrivals costs 1 prefill dispatch instead of N.
    bucket_prefill : pad each batched admission round to a SHAPE BUCKET —
        width to the next power of two (capped at ``num_slots``, extra rows
        are length-0 no-op padding), padded prompt length to the geometric
        ladder ``LEN_BUCKET_MIN · 2^k`` — so ``prefill_slots`` compiles
        O(log num_slots · log max_prompt) times instead of once per distinct
        (round width, round max length). Token-identical to the unbucketed
        path; the ``compiles`` counters prove the bound.
    paged_decode : with ``use_kernel``, route decode attention through the
        length-aware paged kernel (``kernels/paged_decode.py``): each slot
        skips KV pages beyond its live span, so freshly admitted /
        short-prompt slots stop paying full-ring attention cost. Output is
        bitwise-identical to the unpaged kernel.
    donate_cache : donate the KV-cache pytree through the jitted decode and
        prefill steps (``jax.jit(..., donate_argnums=...)``) so XLA updates
        the ring buffers in place instead of copying the full cache through
        every step. The engine never re-reads a donated buffer: ``.cache``
        is rebound to the step's output before any other access.
    mesh : optional 1-D ``jax.sharding.Mesh`` with a ``model`` axis
        (``launch.mesh.make_serve_mesh``) — serve tensor-parallel. Attention
        heads split over the axis; the KV pool (paged or ring) splits its
        kv-head dim, so each shard holds its head slice of every physical
        page while page tables stay host-side and shard-invariant; every
        hot-path jit runs through ``shard_map``, each shard tracing the
        single-device math on its head slice (``localize_config``) with an
        all-gather of attention outputs before the replicated wo matmul.
        That combine keeps sharded serving BITWISE token-identical to
        ``mesh=None`` — which itself still traces the old single-device
        code unchanged, so the unsharded engine remains the oracle.
        Requires ``n_heads`` and ``n_kv_heads`` divisible by the axis size.
    paged_cache : replace the per-slot contiguous rings with ONE shared
        pool of physical pages + per-slot page tables. Decoupling logical
        sequence state from physical placement removes the
        ``prompt + gen <= max_seq`` admission guard (sequences are bounded
        by pool pages) and lets heterogeneous traffic share memory that
        ring mode statically splits ``num_slots`` ways. Token-identical to
        ring mode on any trace both can serve.
    page_size : tokens per physical page (paged mode). Small pages waste
        less memory on partial tails but make tables longer and decode DMA
        more scattered; large pages amortize indirection but strand up to
        ``page_size - 1`` dead token slots per sequence.
    num_pages : total physical pages INCLUDING the reserved scratch page 0.
        0 (default) sizes the pool to ring-equivalent capacity:
        ``num_slots * ceil(capacity / page_size) + 1``. Undersizing it
        oversubscribes memory — admission throttles on a watermark and
        decode OOM preempts the youngest slot.
    table_width : logical pages per slot (windowless paged mode). 0
        (default) bounds it to RING-EQUIVALENT width (``num_slots ×
        pages_per_ring``) so the jnp decode/prefill gather+attend work per
        step stays at ring scale even over an oversized pool; an explicit
        value (or ``long_requests``) widens it.
    long_requests : give every slot whole-pool logical width
        (``num_pages - 1`` table entries) — one request may stretch across
        every allocatable page, at ``num_slots×`` the per-step jnp gather
        cost the ring engine paid.
    watermark_pages : free pages admission must leave in reserve while any
        OTHER slot is live (paged mode) — headroom that lets running slots
        keep decoding without immediate preemption. Waived when nothing
        else is live, so progress is always possible.
    prefix_cache : index retired requests' full prompt pages in a radix
        trie (``launch/prefix_cache.py``) keyed by page-sized token
        chunks, and map common prompt prefixes of later requests onto the
        SAME physical pages — only the uncached suffix is prefilled. Pages
        are refcounted; the last shared page splits copy-on-write when a
        suffix or re-prefilled token would overwrite it; index entries are
        LRU-evicted under pool pressure (before watermark throttling and
        OOM preemption), so a cache-hot pool degrades to the no-sharing
        engine instead of thrashing. Output is token-identical to
        ``prefix_cache=False``. Requires chunked prefill and ``window ==
        0`` (silently off otherwise).
    prefix_cache_pages : cap on pages the prefix index may pin (0 = the
        pool's allocatable capacity).
    draft_model, draft_params, spec_tokens : speculative decoding. A
        second, cheap model (``spec_decode.make_draft_backend`` picks its
        state layout: small KV ring for transformer-family drafts,
        recurrent snapshots for ssm drafts like ``xlstm_125m``) proposes
        ``spec_tokens`` lookahead tokens per live slot per scheduling
        round; the TARGET model then scores ALL of them in ONE batched
        suffix-prefill dispatch (``prefill_slots(starts=..., return_all_
        logits=True)``) over the shared page pool instead of k sequential
        decode dispatches. Accepted tokens keep the KV pages the verify
        pass just wrote; the first rejection rolls back by pos truncation
        plus freeing the round's unreached fresh pages — a table edit, no
        recompute. Greedy requests emit BITWISE the tokens of the
        non-speculative engine (the per-token decode path stays as the
        oracle); sampled requests run Leviathan rejection sampling on
        their request-uid PRNG streams, preserving the target
        distribution (not bitwise-pinned). Requires paged_cache, chunked
        prefill, window == 0, no mesh, and matching draft/target vocab;
        all three arguments travel together.
    eos_id : optional token id that retires a sequence early.
    seed : engine-level sampling seed; requests without an explicit
        ``SamplingParams.seed`` draw from PRNGKey(seed) folded with their
        uid, so slot reuse never reuses a stream.
    max_wall_s : per-request wall-clock watchdog (0 = off). A live slot
        older than this (measured from its ORIGINAL admission — preemption
        round trips don't reset it) is retired with
        ``finish_reason="timeout"`` and whatever tokens it generated, so a
        request whose slot stops advancing (a stalled dispatch under fault
        injection, a runaway generation) can never wedge ``run()`` forever.
        Timed-out prompt pages are freed WITHOUT being published to the
        prefix index (a mid-prefill timeout may hold partially written
        pages).
    time_fn : monotonic clock; injectable for deterministic tests.
    """

    def __init__(
        self,
        model: ModelAPI,
        params,
        *,
        num_slots: int = 4,
        max_seq: int = 128,
        window: int = 0,
        use_kernel: bool = False,
        prefill: str = "chunked",
        batch_prefill: bool = True,
        bucket_prefill: bool = True,
        paged_decode: bool = True,
        donate_cache: bool = True,
        mesh: Mesh | None = None,
        paged_cache: bool = False,
        page_size: int = 16,
        num_pages: int = 0,
        table_width: int = 0,
        long_requests: bool = False,
        watermark_pages: int = 0,
        prefix_cache: bool = False,
        prefix_cache_pages: int = 0,
        kv_dtype: str = "fp",
        host_pages: int = 0,
        swap: bool = True,
        draft_model: ModelAPI | None = None,
        draft_params=None,
        spec_tokens: int = 0,
        eos_id: int | None = None,
        seed: int = 0,
        max_wall_s: float = 0.0,
        time_fn: Callable[[], float] | None = None,
    ):
        if model.init_slot_cache is None or model.prefill_slot is None:
            raise ValueError(
                f"arch {model.cfg.name!r} ({model.cfg.arch_type}) has no "
                "slot-cache API; the engine serves the transformer family"
            )
        if prefill not in PREFILL_MODES:
            raise ValueError(f"prefill {prefill!r} not in {PREFILL_MODES}")
        if num_slots < 1:
            raise ValueError(f"num_slots must be >= 1, got {num_slots}")
        if max_seq < 1:
            raise ValueError(f"max_seq must be >= 1, got {max_seq}")
        self.cfg = model.cfg
        self.model = model
        self.params = params
        # Tensor-parallel serving: resolve the shard count and the PER-SHARD
        # model. Inside shard_map each shard sees 1/S of the heads, so the
        # shard-local trace is built from a localized config; a 1-shard mesh
        # still exercises the full shard_map plumbing (useful as the
        # any-machine identity probe) but keeps the global model.
        self.mesh = mesh
        self.num_shards = 1
        self._tp_axis: str | None = None
        serve_model = model
        if mesh is not None:
            if "model" not in mesh.axis_names:
                raise ValueError(
                    f"serving mesh needs a 'model' axis, got {mesh.axis_names}"
                )
            self.num_shards = axis_size(mesh, "model")
            self._tp_axis = "model"
            if self.num_shards > 1:
                serve_model = build_model(
                    localize_config(model.cfg, self.num_shards)
                )
        self._serve_model = serve_model
        self.num_slots = num_slots
        self.max_seq = max_seq
        self.window = window
        self.use_kernel = use_kernel
        self.prefill_mode = prefill
        self.batch_prefill = (
            batch_prefill and prefill == "chunked"
            and model.prefill_slots is not None
        )
        self.bucket_prefill = bucket_prefill and self.batch_prefill
        self.paged_decode = paged_decode
        self.donate_cache = donate_cache
        self.eos_id = eos_id
        self.seed = seed
        self.max_wall_s = max_wall_s
        self._time_fn = time_fn or time.monotonic
        self._t0 = self._time_fn()

        self.paged_cache = paged_cache
        self.preemptions = 0
        # pool fill fraction over decode steps: count, sum and max
        self.occupancy_steps = 0
        self._occupancy_sum = 0.0
        self._occupancy_max = 0.0
        # SLO bookkeeping (both cache modes): deadline-expired queued
        # requests are recorded here as structured AdmissionErrors instead
        # of being raised (shedding happens inside the scheduler, where
        # there is no caller to catch); watchdog retirements count below.
        self.shed: list[AdmissionError] = []
        self.shed_requests = 0
        self.timeouts = 0
        # preemption-resume records + admission sequence live in BOTH cache
        # modes: a router may migrate another engine's in-flight requests
        # into this one (``import_inflight``), and the re-prefill resume
        # path is layout-independent. Only paged mode CREATES records
        # itself (ring mode never preempts).
        self._resume: dict[int, _ResumeState] = {}
        self._admit_seq = 0
        if kv_dtype not in ("fp", "int8"):
            raise ValueError(f"kv_dtype must be 'fp' or 'int8', got {kv_dtype!r}")
        if kv_dtype == "int8" and not paged_cache:
            raise ValueError(
                "kv_dtype='int8' quantizes POOL pages; it requires "
                "paged_cache=True (the contiguous ring cache stays fp)"
            )
        if host_pages > 0 and not paged_cache:
            raise ValueError(
                "host_pages tiers the page pool; it requires paged_cache=True"
            )
        self.kv_dtype = kv_dtype
        if paged_cache:
            if model.init_paged_cache is None or model.prefill_slots is None:
                raise ValueError(
                    f"arch {model.cfg.name!r} has no paged-cache API; "
                    "use the contiguous ring engine"
                )
            cap_ring = window if (0 < window < max_seq) else max_seq
            pages_per_ring = -(-cap_ring // page_size)
            if num_pages <= 0:
                # ring-equivalent capacity: same total KV memory as the
                # contiguous engine, now shareable across slots
                num_pages = num_slots * pages_per_ring + 1
            self.page_size = page_size
            self.num_pages = num_pages
            # Logical ring capacity per slot: the window when sliding-window
            # attention bounds context anyway; else RING-EQUIVALENT width
            # (num_slots × pages_per_ring — the work the jnp decode/prefill
            # gathers scale with) by default, or the WHOLE allocatable pool
            # with ``long_requests`` / an explicit ``table_width`` so one
            # request may stretch across every page. Logical width may
            # exceed the PHYSICAL pool (a tight pool oversubscribes);
            # ``submit`` rejects what the physical pool can never hold.
            if 0 < window < max_seq:
                self.table_width = pages_per_ring
                if num_pages - 1 < self.table_width:
                    raise ValueError(
                        f"num_pages {num_pages} cannot back a table of "
                        f"{self.table_width} pages (window {window})"
                    )
            elif table_width > 0:
                self.table_width = table_width
            elif long_requests:
                self.table_width = num_pages - 1
            else:
                self.table_width = num_slots * pages_per_ring
            self.cap = self.table_width * page_size
            self.pool = PagePool(num_pages, page_size)
            self.watermark_pages = watermark_pages
            self._table_np = np.zeros((num_slots, self.table_width), np.int32)
            self._table_dirty = False
            self._slot_pages: list[list[int]] = [[] for _ in range(num_slots)]
            self.cache = model.init_paged_cache(
                params, num_slots, num_pages, page_size, self.table_width,
                window=window, kv_dtype=kv_dtype,
            )
            # Host tier: second level of the KV hierarchy. Gated off under a
            # mesh — the pool is sharded across devices there and the
            # host-side gather/scatter below assumes a single-device layout.
            self.swap_disabled_reason = None
            if host_pages > 0 and mesh is not None:
                self.swap_disabled_reason = (
                    "mesh serving (KV pool is sharded; host tier assumes a "
                    "single-device pool)"
                )
            self.host = (
                HostTier(host_pages)
                if host_pages > 0 and self.swap_disabled_reason is None
                else None
            )
            self.swap = swap and self.host is not None
            # Prefix sharing rides the page table: it needs chunked prefill
            # (suffix rounds) and a non-wrapping logical ring (windowless).
            # A requested-but-unsatisfiable config stays off, WITH a named
            # reason — logged once here and exposed via
            # pool_stats["prefix_cache_enabled"] — so "default-on" callers
            # (serve.py) can tell sharing from a silently degraded engine.
            self.prefix_disabled_reason = None
            if prefix_cache:
                if window > 0:
                    self.prefix_disabled_reason = (
                        f"window={window} (sliding-window ring wraps; "
                        "prefix pages would be overwritten)"
                    )
                elif prefill != "chunked":
                    self.prefix_disabled_reason = (
                        f"prefill={prefill!r} (suffix rounds need chunked "
                        "batched admission)"
                    )
            self.prefix = (
                PrefixCache(
                    self.pool, prefix_cache_pages,
                    demote_fn=self._demote_prefix_page if self.host else None,
                    promote_fn=self._promote_prefix_page if self.host else None,
                )
                if prefix_cache and self.prefix_disabled_reason is None
                else None
            )
        else:
            self.pool = None
            self.prefix = None
            self.host = None
            self.swap = False
            self.swap_disabled_reason = None
            self.prefix_disabled_reason = (
                "paged_cache=False (prefix sharing rides the page table)"
                if prefix_cache
                else None
            )
            self.cache = model.init_slot_cache(
                params, num_slots, max_seq, window=window
            )
        # Mesh serving: commit params + cache as sharded arrays. wq/wk/wv
        # split their head (output-feature) dim, KV pools split the kv-head
        # axis — each shard's slice of every page — and everything else
        # (incl. page tables / positions: host-mirrored, shard-invariant)
        # replicates. ``serve_param_specs`` documents why wo replicates.
        if mesh is not None:
            self._pspecs = serve_param_specs(params)
            self._cspecs = serve_cache_specs(self.cache)
            self.params = jax.device_put(
                params, serve_shardings(self._pspecs, mesh)
            )
            self.cache = jax.device_put(
                self.cache, serve_shardings(self._cspecs, mesh)
            )
        if self.prefix_disabled_reason is not None:
            logging.getLogger(__name__).warning(
                "prefix_cache requested but disabled: %s",
                self.prefix_disabled_reason,
            )
        self.prefix_cache = self.prefix is not None
        # prefix-sharing counters (reset by reset_metrics): hit/lookup
        # tokens drive the hit rate, prefill_tokens counts tokens actually
        # run through chunked prefill (the FLOPs the cache saves), and
        # cow_copies counts copy-on-write page splits.
        # prefix_resume_hit_tokens tracks preemption-resume re-admissions
        # separately: a resume replays a feed the engine itself published
        # (prompt + generated-so-far), so its near-total prefix hit says
        # nothing about cross-request sharing and must not inflate the
        # externally-reported prefix_hit_rate.
        self.prefix_hit_pages = 0
        self.prefix_hit_tokens = 0
        self.prefix_lookup_tokens = 0
        self.prefix_resume_hit_tokens = 0
        self.prefill_tokens = 0
        self.cow_copies = 0
        self.swapped_out_pages = 0
        self.swapped_in_pages = 0
        self.host_demoted_pages = 0
        self.host_promote_hits = 0
        # Every hot-path jit donates the cache pytree (argument 1): the ring
        # buffers are updated in place instead of being functionally copied
        # through each step. Each wrapper body runs exactly once per input
        # shape signature — at trace time — so the trace counters below ARE
        # compile counters (``self.compiles``).
        self._compiles = {
            "decode": 0, "prefill": 0, "prefill_slots": 0,
            "prefill_suffix": 0,
        }
        donate = (1,) if donate_cache else ()
        tp_axis = self._tp_axis

        def _shard(fn, n_extra):
            """Wrap a hot-path fn ``(params, cache, *operands)`` in
            shard_map on the serving mesh: params/cache by their serve
            specs, every other operand replicated, (cache, logits) out.
            Replication of the logits is real, not asserted-away — each
            shard all-gathers the attention heads and runs the identical
            replicated tail (``check_vma=False`` only because the
            varying-axes checker has no rule for the Pallas calls).
            ``mesh=None`` returns fn untouched, so the single-device trace
            stays bitwise the pre-mesh one."""
            if mesh is None:
                return fn
            rep = PartitionSpec()
            return jax.shard_map(
                fn, mesh=mesh,
                in_specs=(self._pspecs, self._cspecs) + (rep,) * n_extra,
                out_specs=(self._cspecs, rep),
                check_vma=False,
            )

        def _decode_fn(p, c, t):
            self._compiles["decode"] += 1
            with _span("engine.retrace.decode"), use_tensor_axis(tp_axis):
                return serve_model.decode(p, c, t, window=window)

        def _prefill_fn(p, c, t, s):
            self._compiles["prefill"] += 1
            with _span("engine.retrace.prefill"), use_tensor_axis(tp_axis):
                return serve_model.prefill_slot(p, c, t, s, window=window)

        self._decode = jax.jit(_shard(_decode_fn, 1), donate_argnums=donate)
        self._prefill = jax.jit(_shard(_prefill_fn, 2), donate_argnums=donate)
        if model.prefill_slots is not None:
            def _prefill_slots_fn(p, c, t, l, s):
                self._compiles["prefill_slots"] += 1
                with _span("engine.retrace.prefill_slots"), \
                        use_tensor_axis(tp_axis):
                    return serve_model.prefill_slots(
                        p, c, t, l, s, window=window
                    )

            self._prefill_slots = jax.jit(
                _shard(_prefill_slots_fn, 3), donate_argnums=donate
            )

            # suffix-prefill entry (prefix sharing): its own compile
            # counter (cold rounds must never touch it — tests pin that)
            # and its own shape axis, the static pow2-bucketed prefix-page
            # width, so the recompile gate bounds (width, length,
            # prefix_pages) triples
            def _prefill_suffix_fn(p, c, t, l, s, st, pw):
                self._compiles["prefill_suffix"] += 1
                with _span("engine.retrace.prefill_suffix"), \
                        use_tensor_axis(tp_axis):
                    return serve_model.prefill_slots(
                        p, c, t, l, s, starts=st, prefix_pages=pw,
                        window=window,
                    )

            if mesh is None:
                self._prefill_suffix = jax.jit(
                    _prefill_suffix_fn, donate_argnums=donate,
                    static_argnums=(6,),
                )
            else:
                # bind the static prefix width BEFORE the shard_map wrap —
                # shard_map maps array operands only
                def _suffix_entry(p, c, t, l, s, st, pw):
                    return _shard(
                        functools.partial(_prefill_suffix_fn, pw=pw), 4
                    )(p, c, t, l, s, st)

                self._prefill_suffix = jax.jit(
                    _suffix_entry, donate_argnums=donate, static_argnums=(6,),
                )
        else:
            self._prefill_slots = None
            self._prefill_suffix = None

        # Pool arrays that carry page content (int8 mode adds the scale
        # planes) — the unit every page-granular copy/swap moves together.
        kv_names = tuple(
            n for n in ("k", "v", "ks", "vs")
            if paged_cache and n in self.cache
        )
        self._kv_names = kv_names

        # COW page split: copy one physical page (all layers, every pool
        # plane) inside the donated cache — in place under donation, one
        # compile total
        def _copy_page_fn(c, src, dst):
            out = dict(c)
            for n in kv_names:
                out[n] = c[n].at[:, dst].set(c[n][:, src])
            return out

        self._copy_page = jax.jit(
            _copy_page_fn, donate_argnums=(0,) if donate_cache else ()
        )

        # Host-tier traffic: batched page gather (device→host reads the
        # cache, NOT donated) and scatter (host→device rewrites pages in
        # the donated cache). Page-batch sizes are pow2-bucketed by the
        # callers (padding with scratch page 0) so compile counts stay
        # bounded like every other hot-path shape axis.
        def _gather_pages_fn(c, idx):
            return tuple(c[n][:, idx] for n in kv_names)

        def _scatter_pages_fn(c, idx, arrs):
            out = dict(c)
            for n, a in zip(kv_names, arrs):
                out[n] = c[n].at[:, idx].set(a)
            return out

        self._gather_pages_jit = jax.jit(_gather_pages_fn)
        self._scatter_pages_jit = jax.jit(
            _scatter_pages_fn, donate_argnums=(0,) if donate_cache else ()
        )
        self._sample = jax.jit(
            lambda key, row, t, k, p: sample_token(
                key, row, t, k, p, model.cfg.vocab_size
            )
        )

        # batched per-step sampler: split each slot's stream and draw, one
        # dispatch + one host transfer for ALL sampled slots (mirrors the
        # batched-argmax discipline of the greedy path). Always called at
        # the full (num_slots, Vp) width — greedy/pending rows get dummy
        # keys and their draws are discarded — so it compiles exactly once
        # instead of once per live sampled-slot count.
        def _rows(keys, rows, t, k, p):
            def one(key, row, t1, k1, p1):
                nk, sub = jax.random.split(key)
                return nk, sample_token(sub, row, t1, k1, p1, model.cfg.vocab_size)

            return jax.vmap(one)(keys, rows, t, k, p)

        self._sample_rows = jax.jit(_rows)
        self._dummy_key = jax.random.PRNGKey(0)

        # ---------------------------------------------- speculative decoding
        # counters exist in every mode (pool_stats/bench schema stability);
        # the machinery only when a draft is wired up
        self.spec_rounds = 0
        self.spec_drafted = 0
        self.spec_accepted = 0
        self.spec_emitted = 0
        self.draft = None
        self.spec_tokens = 0
        if draft_model is not None or draft_params is not None or spec_tokens:
            blockers = []
            if draft_model is None or draft_params is None:
                blockers.append("draft_model and draft_params are required")
            if spec_tokens < 1:
                blockers.append("spec_tokens must be >= 1")
            if not paged_cache:
                blockers.append(
                    "paged_cache=False (rollback is a page-table edit)"
                )
            if prefill != "chunked":
                blockers.append(
                    f"prefill={prefill!r} (verification is a batched "
                    "suffix-prefill round)"
                )
            if window != 0:
                blockers.append(
                    f"window={window} (suffix prefill is windowless)"
                )
            if mesh is not None:
                blockers.append("mesh serving (single-device verify only)")
            if model.prefill_slots is None:
                blockers.append("target arch has no prefill_slots API")
            if (
                draft_model is not None
                and draft_model.cfg.vocab_size != model.cfg.vocab_size
            ):
                blockers.append(
                    f"draft vocab {draft_model.cfg.vocab_size} != target "
                    f"vocab {model.cfg.vocab_size}"
                )
            if blockers:
                raise ValueError(
                    "speculative decoding unavailable: " + "; ".join(blockers)
                )
            self.spec_tokens = spec_tokens
            self._compiles.update(
                {"spec_verify": 0, "draft_propose": 0, "draft_prefill": 0}
            )
            limit = min(self.cap, self.pool.capacity * self.page_size)
            self.draft = make_draft_backend(
                draft_model, draft_params, num_slots=num_slots, cap=limit,
                spec_tokens=spec_tokens, compiles=self._compiles,
                donate=donate_cache,
            )
            # host mirror of each draft row's consumed-token count; -1 =
            # diverged/dead, forcing a re-sync prefill before the next
            # propose (slot reuse can never alias the old occupant's state)
            self._draft_pos = np.full(num_slots, -1, np.int64)
            self._spec_dummy_keys = jnp.stack([self._dummy_key] * num_slots)

            # k-token verify: the suffix-prefill trace with logits at EVERY
            # position (the cache write is bit-for-bit the plain suffix
            # trace — tests pin greedy identity through this entry)
            def _spec_verify_fn(p, c, t, l, s, st, pw):
                self._compiles["spec_verify"] += 1
                with _span("engine.retrace.spec_verify"):
                    return serve_model.prefill_slots(
                        p, c, t, l, s, starts=st, prefix_pages=pw,
                        window=window, return_all_logits=True,
                    )

            self._spec_verify = jax.jit(
                _spec_verify_fn, donate_argnums=donate, static_argnums=(6,),
            )

            # masked pos correction: verify advances every dispatched row
            # to starts+lengths (= p + k + 1); acceptance truncates each to
            # its accepted span. One compile, reused every round.
            def _fix_pos_fn(c, pos_vec, mask):
                return {
                    **c, "pos": jnp.where(mask, pos_vec, c["pos"]),
                }

            self._fix_pos = jax.jit(
                _fix_pos_fn, donate_argnums=(0,) if donate_cache else ()
            )

            # batched per-row round-key split (mirrors _sample_rows'
            # fixed-width discipline: dummy rows for greedy slots)
            def _split_fn(keys):
                def one(k):
                    nk, sub = jax.random.split(k)
                    return nk, sub

                return jax.vmap(one)(keys)

            self._spec_split = jax.jit(_split_fn)

            # batched acceptance: one vmapped rejection-sampling dispatch
            # over the verify round's rows (greedy/padding rows ride along
            # with dummy keys; their outputs are discarded host-side).
            # fold_in(sub, 2) keeps the acceptance uniforms on a stream
            # disjoint from the draft's (sub, 1, t) proposal draws.
            kk = spec_tokens
            vocab = model.cfg.vocab_size

            def _accept_fn(keys, vlog, dtoks, dlogq, klive, temps, tks, tps):
                def one(key, tl, dt, dq, kl, t, k, p):
                    # verify rows are padded to the round's length bucket;
                    # clip-take exactly k+1 positions (rows past each row's
                    # own k_live are never read by the acceptance math)
                    tl = jnp.take(
                        tl,
                        jnp.minimum(jnp.arange(kk + 1), tl.shape[0] - 1),
                        axis=0,
                    )
                    return speculative_acceptance(
                        jax.random.fold_in(key, 2), tl, dt, dq, kl,
                        t, k, p, vocab,
                    )

                return jax.vmap(one)(
                    keys, vlog, dtoks, dlogq, klive, temps, tks, tps
                )

            self._spec_accept = jax.jit(_accept_fn)

        self.waiting: collections.deque[Request] = collections.deque()
        self.slots: list[_Slot | None] = [None] * num_slots
        self.finished: list[RequestOutput] = []
        self.steps = 0            # decode steps executed
        self.prefill_dispatches = 0   # chunked-prefill forwards launched
        # split-admission dispatch counters: every batched round is
        # partitioned into a COLD dispatch (starts == 0, the pre-existing
        # prefill_slots trace) and a HIT dispatch (suffix trace) so cold
        # rows never pay the prefix tax — these count each kind launched
        self.suffix_dispatches = 0
        self.cold_dispatches = 0
        self.slot_history: dict[int, list[int]] = {}  # uid -> slots used
        # bucket shapes already warmed, keyed by the full dispatch
        # configuration (see ``warm``) — persists across warm() calls
        self._warmed: set[tuple] = set()

    # ------------------------------------------------------------- plumbing
    def _now(self) -> float:
        return self._time_fn() - self._t0

    def reset_clock(self) -> None:
        """Restart the engine clock at 0 — call after warmup so request
        arrival times (relative to the clock) and latency metrics exclude
        jit compilation."""
        self._t0 = self._time_fn()

    def reset_metrics(self) -> None:
        """Drop warmup outputs and counters and restart the clock, so a
        subsequent trace measures steady state, not jit compilation."""
        self.finished.clear()
        self.slot_history.clear()
        self.steps = 0
        self.prefill_dispatches = 0
        self.preemptions = 0
        self.shed.clear()
        self.shed_requests = 0
        self.timeouts = 0
        self.occupancy_steps = 0
        self._occupancy_sum = 0.0
        self._occupancy_max = 0.0
        self.prefix_hit_pages = 0
        self.prefix_hit_tokens = 0
        self.prefix_lookup_tokens = 0
        self.prefix_resume_hit_tokens = 0
        self.prefill_tokens = 0
        self.cow_copies = 0
        self.swapped_out_pages = 0
        self.swapped_in_pages = 0
        self.host_demoted_pages = 0
        self.host_promote_hits = 0
        self.suffix_dispatches = 0
        self.cold_dispatches = 0
        self.spec_rounds = 0
        self.spec_drafted = 0
        self.spec_accepted = 0
        self.spec_emitted = 0
        if self.paged_cache:
            self.pool.peak_in_use = self.pool.in_use
        if self.prefix is not None:
            self.prefix.reset_stats()
        self.reset_clock()

    def warm(self, prompt_lens, *, gen_tokens: int = 2,
             sampling: SamplingParams | None = None) -> None:
        """Compile every shape a trace can dispatch, then reset metrics.

        Batched admission specializes ``prefill_slots`` per (round width,
        padded prompt length) — and a mixed round pads to its max length,
        always one of ``prompt_lens`` — so warm each (width, length) pair;
        per-request / interleaved admission only ever sees width 1. With
        shape bucketing, many (width, length) pairs collapse onto one bucket
        shape, so only one representative per bucket is traced. Pass
        ``sampling`` when the trace will sample, so the (fixed-width)
        batched sampler compiles here too.

        Dedup is keyed by the full dispatch configuration — bucket shape
        plus the mesh shard count and whether prefix sharing is on (which
        decides if a warm run traces the cold path alone or cold + suffix
        rounds) — and PERSISTS across calls: re-warming an engine, or
        warming a sharded engine after construction-time probing, skips
        every shape already traced instead of re-running it (a sharded
        engine dispatches only its own shard-count configuration, never
        the single-device shapes)."""
        widths = range(1, self.num_slots + 1) if self.batch_prefill else [1]
        for p in sorted(set(prompt_lens)):
            for w in widths:
                shape = (
                    (bucket_width(w, self.num_slots), bucket_length(p))
                    if self.bucket_prefill
                    else (w, p)
                )
                key = (self.num_shards, self.prefix_cache, *shape)
                if key in self._warmed:
                    continue
                self._warmed.add(key)
                self.run([
                    Request(uid=-1 - j, prompt=np.zeros(p, np.int32),
                            max_new_tokens=max(gen_tokens, 1),
                            sampling=sampling)
                    for j in range(w)
                ])
        if self.prefix is not None:
            # warm traffic published its zero-token pages (deliberately —
            # repeated warm rounds hit them, tracing the suffix-prefill and
            # COW paths too); real traffic must start from an empty index
            self.prefix.clear()
        if self.host is not None:
            # warm preemptions/demotions may have parked synthetic pages on
            # the host tier; real traffic starts from an empty tier
            self.host.clear()
        self.reset_metrics()

    @property
    def compiles(self) -> dict[str, int]:
        """Jit specializations per hot-path entry point since construction.
        NOT reset by ``reset_metrics`` — compiled code outlives a metrics
        window, and the whole point of shape bucketing is keeping these
        bounded as traffic diversity grows."""
        return dict(self._compiles)

    @property
    def prefill_compiles(self) -> int:
        """``prefill_slots`` + suffix + per-request prefill specializations
        — the number the recompile-guard test bounds by the bucket-ladder
        size."""
        return (
            self._compiles["prefill_slots"]
            + self._compiles["prefill_suffix"]
            + self._compiles["prefill"]
        )

    @property
    def pool_stats(self) -> dict | None:
        """Paged-pool occupancy and preemption counters (None in ring
        mode). ``occupancy_*`` summarize the per-decode-step pool fill
        fraction since the last ``reset_metrics``."""
        if not self.paged_cache:
            return None
        n = self.occupancy_steps
        return {
            "shards": self.num_shards,
            "mesh_axes": (
                dict(self.mesh.shape) if self.mesh is not None else None
            ),
            # per-shard pool fill: page tables are shard-invariant — every
            # shard holds its kv-head slice of the same live pages — so
            # each shard's occupancy equals the pool's. Reported per shard
            # anyway: the equal entries ARE the invariant, and a future
            # per-shard allocator would show skew here.
            "occupancy": [
                self.pool.in_use / max(self.pool.capacity, 1)
            ] * self.num_shards,
            "page_size": self.page_size,
            "num_pages": self.num_pages,
            "allocatable_pages": self.pool.capacity,
            "pages_in_use": self.pool.in_use,
            "peak_pages_in_use": self.pool.peak_in_use,
            "preemptions": self.preemptions,
            "shed_requests": self.shed_requests,
            "timeouts": self.timeouts,
            "occupancy_mean": self._occupancy_sum / n if n else 0.0,
            "occupancy_max": self._occupancy_max if n else 0.0,
            "prefix_cache": self.prefix_cache,
            "prefix_cache_enabled": self.prefix_cache,
            "prefix_disabled_reason": self.prefix_disabled_reason,
            "prefix_hit_pages": self.prefix_hit_pages,
            # hit rate over FRESH lookups only — resume re-admissions
            # (prefix_resume_hit_tokens) replay engine-published tokens
            # and are excluded from both numerator and denominator
            "prefix_hit_rate": (
                self.prefix_hit_tokens / self.prefix_lookup_tokens
                if self.prefix_lookup_tokens
                else 0.0
            ),
            "prefix_resume_hit_tokens": self.prefix_resume_hit_tokens,
            "prefix_lookup_tokens": self.prefix_lookup_tokens,
            "prefill_tokens": self.prefill_tokens,
            "cow_copies": self.cow_copies,
            "suffix_dispatches": self.suffix_dispatches,
            "cold_dispatches": self.cold_dispatches,
            "prefix_pages_cached": (
                self.prefix.size if self.prefix is not None else 0
            ),
            "prefix_evicted_pages": (
                self.prefix.evicted_pages if self.prefix is not None else 0
            ),
            "kv_dtype": self.kv_dtype,
            "swap_enabled": self.swap,
            "swap_disabled_reason": self.swap_disabled_reason,
            "host_capacity_pages": (
                self.host.capacity_pages if self.host is not None else 0
            ),
            "host_tier_pages": (
                self.host.pages if self.host is not None else 0
            ),
            "swapped_out_pages": self.swapped_out_pages,
            "swapped_in_pages": self.swapped_in_pages,
            "host_demoted_pages": self.host_demoted_pages,
            "host_promote_hits": self.host_promote_hits,
            # speculative decoding: accept_rate is accepted DRAFTS over
            # proposed drafts (the bonus/rejection token is free either
            # way); dispatches_per_token is target decode dispatches per
            # emitted token — 1.0 for the non-spec engine, 1/(k+1) at full
            # acceptance
            "spec_enabled": self.draft is not None,
            "spec_tokens": self.spec_tokens,
            "spec_rounds": self.spec_rounds,
            "spec_drafted": self.spec_drafted,
            "spec_accepted": self.spec_accepted,
            "spec_emitted": self.spec_emitted,
            "spec_accept_rate": (
                self.spec_accepted / self.spec_drafted
                if self.spec_drafted
                else 0.0
            ),
            "spec_dispatches_per_token": (
                self.spec_rounds / self.spec_emitted
                if self.spec_emitted
                else 0.0
            ),
        }

    @property
    def has_work(self) -> bool:
        return bool(self.waiting) or any(s is not None for s in self.slots)

    @property
    def active_slots(self) -> int:
        return sum(s is not None for s in self.slots)

    def next_arrival(self) -> float | None:
        """Earliest arrival among waiting requests, or None."""
        return min((r.arrival_time for r in self.waiting), default=None)

    def prefix_probe(self, tokens) -> int:
        """Predicted cached-prefix TOKENS for a prompt: a READ-ONLY walk
        of the radix index — no LRU touch, no hit/lookup counting, no
        page refs taken. The trie's page-chunk keys make hit prediction
        O(prompt/page_size) dict lookups, so a router can score every
        replica's affinity for a prompt without prefilling anything (and
        without the probe itself perturbing eviction order or the honest
        ``prefix_hit_rate``). 0 when prefix sharing is off."""
        if self.prefix is None:
            return 0
        return self.prefix.probe(tokens) * self.page_size

    def capacity_shortfall(self, req: Request) -> int:
        """Tokens by which ``req`` exceeds this engine's STATIC capacity
        (0 = servable). Non-mutating — a router probes every replica with
        this before rejecting a request anywhere, so the best-fit shortfall
        it reports is the true system-wide one, not one pool's."""
        need = len(req.prompt) + req.max_new_tokens
        if self.window != 0:
            return 0  # the sliding-window ring wraps; any length fits
        if self.paged_cache:
            # Windowless sequences are bounded by BOTH limits: the logical
            # table (cap tokens) and the physical pool (allocatable pages —
            # a tight pool may be smaller than the table, and a request
            # whose pages can never all be resident would otherwise sit at
            # the queue head forever while alloc keeps returning None).
            limit = min(self.cap, self.pool.capacity * self.page_size)
            return max(0, need - limit)
        return max(0, need - self.max_seq)

    def submit(self, req: Request) -> None:
        """Enqueue a request, or reject it with a structured
        ``AdmissionError`` if the engine could NEVER serve it. Rejection
        happens HERE, not mid-``_admit``: a doomed request must not enter
        the queue, where it would wedge a scheduling round at the head of
        FIFO admission. A rejected submit leaves the engine fully usable."""
        short = self.capacity_shortfall(req)
        if short > 0:
            if self.paged_cache:
                raise AdmissionError(
                    req.uid, "exceeds_pool",
                    f"request {req.uid}: prompt {len(req.prompt)} + gen "
                    f"{req.max_new_tokens} exceeds pool capacity by {short} "
                    f"tokens "
                    f"({min(self.cap, self.pool.capacity * self.page_size)} "
                    f"tokens: table {self.table_width} pages × "
                    f"{self.page_size}, pool {self.pool.capacity} "
                    "allocatable pages)",
                )
            raise AdmissionError(
                req.uid, "exceeds_max_seq",
                f"request {req.uid}: prompt {len(req.prompt)} + gen "
                f"{req.max_new_tokens} exceeds max_seq {self.max_seq} by "
                f"{short} tokens "
                "(full-attention ring would overwrite live context)",
            )
        self.waiting.append(req)

    # ------------------------------------------------------------ scheduling
    def _shed_expired(self, now: float) -> None:
        """Shed QUEUED requests whose deadline has passed, recording a
        structured ``AdmissionError("deadline_exceeded")`` per shed instead
        of raising (shedding happens inside the scheduler — there is no
        submit caller to catch). Serving an already-expired request wastes
        slots and, worse, an unservable-but-expired head would sit in front
        of FIFO admission forever. Mid-stream requests (a preemption-resume
        record with generated tokens — the client has already received
        output) are exempt: their remaining tokens are real work."""
        if not any(r.deadline_s is not None for r in self.waiting):
            return
        kept: collections.deque[Request] = collections.deque()
        while self.waiting:
            req = self.waiting.popleft()
            resume = self._resume.get(req.uid)
            mid_stream = resume is not None and bool(resume.generated)
            if (
                req.deadline_s is not None
                and not mid_stream
                and now - req.arrival_time > req.deadline_s
            ):
                dropped = self._resume.pop(req.uid, None)
                if (
                    dropped is not None and dropped.host_key is not None
                    and self.host is not None
                ):
                    self.host.pop(dropped.host_key)
                self.shed.append(AdmissionError(
                    req.uid, "deadline_exceeded",
                    f"request {req.uid}: queued {now - req.arrival_time:.3f}s"
                    f" past arrival, deadline was {req.deadline_s:.3f}s; "
                    "shed unserved",
                ))
                self.shed_requests += 1
            else:
                kept.append(req)
        self.waiting = kept

    def _greedy(self, logits_row) -> int:
        return int(jnp.argmax(logits_row[: self.cfg.vocab_size]))

    def _request_key(self, req: Request) -> jax.Array | None:
        """Per-REQUEST sampling stream. Keyed by the request (explicit seed,
        or engine seed + uid), never by the slot: backfilling a retired
        request's slot can't resume the previous occupant's stream."""
        sp = req.sampling
        if sp is None or sp.is_greedy:
            return None
        if sp.seed is not None:
            return jax.random.PRNGKey(sp.seed)
        return jax.random.fold_in(jax.random.PRNGKey(self.seed), req.uid)

    def _next_token(self, slot: _Slot, logits_row) -> int:
        """First/next token for a slot from its row of logits (greedy or
        temperature/top-k/top-p sampling on the request's own stream)."""
        if slot.key is None:
            return self._greedy(logits_row)
        sp = slot.req.sampling
        slot.key, sub = jax.random.split(slot.key)
        return int(self._sample(sub, logits_row, sp.temperature, sp.top_k, sp.top_p))

    def _admit(self, now: float, respect_arrivals: bool) -> None:
        """Fill free slots from the queue in arrival order.

        Chunked mode prefills every request claimed in a round through ONE
        batched ``prefill_slots`` forward (or one dispatch each with
        ``batch_prefill=False``). A request that finishes on its very first
        token frees its slot immediately, so the round loop re-admits into
        it before the next decode step — same backfill behavior as the old
        one-at-a-time path.

        Paged mode allocates each claim's prompt pages up front (resumed
        requests: prompt + already-generated) and stops claiming — without
        dequeuing — when the pool can't cover the next request plus the
        watermark; the request waits for retirements to free pages. The
        watermark is waived when no other slot is live, so the queue can
        always make progress."""
        self._shed_expired(now)
        while True:
            free = [i for i, s in enumerate(self.slots) if s is None]
            claimed: list[int] = []
            while free and self.waiting:
                req = self.waiting[0]
                if respect_arrivals and req.arrival_time > now:
                    break
                resume = self._resume.get(req.uid)
                if resume is not None and resume.host_key is not None and (
                    self.host is None
                    or self.host.n_pages(resume.host_key) == 0
                ):
                    # tier dropped the entry (LRU) or the record migrated in
                    # from another engine — fall back to recompute-resume
                    resume.host_key = None
                if resume is not None and resume.host_key is not None:
                    # SWAP-IN: the preempted slot's pages are resident on
                    # the host tier. Restore them with one batched scatter,
                    # rebuild the table row, and continue decoding — no
                    # prefill at all. The restored pages are bitwise the
                    # ones the slot held, so token identity vs. the
                    # recompute oracle is structural.
                    n_need = self.host.n_pages(resume.host_key)
                    others_live = any(s is not None for s in self.slots)
                    hold = self.watermark_pages if others_live else 0
                    if self.pool.available < n_need + hold:
                        if self.prefix is not None:
                            self.prefix.evict(
                                n_need + hold - self.pool.available
                            )
                        if self.pool.available < n_need + hold:
                            break  # stays queued; recompute needs no fewer
                    with _span("engine.claim", uid=req.uid):
                        pages = self.pool.alloc(n_need)
                        self.waiting.popleft()
                        i = free.pop(0)
                        self._resume.pop(req.uid)
                        self._restore_pages(
                            pages, self.host.pop(resume.host_key)
                        )
                        self.swapped_in_pages += n_need
                        self._slot_pages[i] = pages
                        self._table_np[i, :] = 0
                        self._table_np[i, : n_need] = pages
                        self._table_dirty = True
                        self.cache = {
                            **self.cache,
                            "pos": self.cache["pos"].at[i].set(resume.pos),
                        }
                        # written tokens = stream[:pos]; the slot re-feeds
                        # stream[pos] next step and (for a mid-prefill victim)
                        # teacher-forces the remaining prompt through pending
                        stream = [int(t) for t in req.prompt] + list(
                            resume.generated
                        )
                        slot = _Slot(
                            req=req,
                            pending=collections.deque(stream[resume.pos + 1:]),
                            generated=list(resume.generated),
                            next_feed=stream[resume.pos],
                            admit_time=resume.admit_time,
                            key=resume.key,
                            feed=None,
                            prefix_len=0,
                        )
                        slot.first_token_time = resume.first_token_time
                        slot.pos_host = resume.pos
                        self._admit_seq += 1
                        slot.seq = self._admit_seq
                        self.slot_history.setdefault(req.uid, []).append(i)
                        self.slots[i] = slot
                    continue
                feed = req.prompt
                if resume is not None and resume.generated:
                    feed = np.concatenate([
                        req.prompt,
                        np.asarray(resume.generated[:-1], np.int32),
                    ])
                hits: list[int] = []
                suffix_start = 0
                cow = False
                if self.paged_cache:
                    if self.prefill_mode == "chunked":
                        total_pages = min(
                            -(-len(feed) // self.page_size), self.table_width
                        )
                        if self.prefix is not None:
                            # map cached prefix pages straight into the
                            # table; SHARE them first so eviction below can
                            # never recycle a page we are about to alias
                            hits = self.prefix.match(feed)
                            for p in hits:
                                self.pool.share(p)
                            # at least one suffix token must run through
                            # prefill (the emission needs its logits); a
                            # fully cached prompt re-prefills its last
                            # token into a COW copy of the final hit page
                            suffix_start = min(
                                len(hits) * self.page_size, len(feed) - 1
                            )
                            cow = len(hits) * self.page_size > suffix_start
                        n_fresh = total_pages - len(hits) + (1 if cow else 0)
                    else:
                        n_fresh = 1  # interleaved: pages arrive lazily
                    # slots claimed earlier this round are already assigned
                    # into self.slots, so this also covers them
                    others_live = any(s is not None for s in self.slots)
                    hold = self.watermark_pages if others_live else 0
                    if self.pool.available < n_fresh + hold:
                        # pool pressure: shed cold index entries before
                        # throttling (graceful degradation to no-sharing)
                        if self.prefix is not None:
                            self.prefix.evict(
                                n_fresh + hold - self.pool.available
                            )
                        if self.pool.available < n_fresh + hold:
                            self.pool.free(hits)  # undo the shares
                            break  # request stays queued
                with _span("engine.claim", uid=req.uid):
                    self.waiting.popleft()
                    i = free.pop(0)
                    self.cache = reset_slot(self.cache, i)
                    slot = _Slot(
                        req=req,
                        pending=collections.deque(feed.tolist()),
                        generated=[],
                        next_feed=-1,
                        admit_time=now,
                        key=self._request_key(req),
                        feed=feed,
                        prefix_len=suffix_start,
                    )
                    self._admit_seq += 1
                    slot.seq = self._admit_seq
                    if self.paged_cache:
                        self._table_np[i, :] = 0
                        if self.prefill_mode == "chunked":
                            pages = list(hits)
                            if cow:
                                # the suffix overwrites the tail of the last
                                # shared page: split it (copy-on-write) so the
                                # index copy stays immutable
                                src = pages[-1]
                                dst = self.pool.alloc(1)[0]
                                self.cache = self._copy_page(
                                    self.cache,
                                    jnp.asarray(src, jnp.int32),
                                    jnp.asarray(dst, jnp.int32),
                                )
                                self.pool.free([src])  # drop our share
                                pages[-1] = dst
                                self.cow_copies += 1
                            fresh = self.pool.alloc(total_pages - len(pages))
                            pages.extend(fresh)
                            self._slot_pages[i] = pages
                            self._table_np[i, : len(pages)] = pages
                            if resume is None:
                                self.prefix_hit_pages += len(hits)
                                self.prefix_hit_tokens += suffix_start
                                self.prefix_lookup_tokens += len(feed)
                            else:
                                # a resume replays tokens the engine
                                # itself published — its (near-total) hit
                                # is real work saved but says nothing
                                # about cross-request sharing, so it must
                                # not inflate the external prefix_hit_rate
                                self.prefix_resume_hit_tokens += suffix_start
                        else:
                            self._slot_pages[i] = []
                        self._table_dirty = True
                    if resume is not None:
                        # resume restoration is cache-layout independent: the
                        # re-prefill of prompt + generated[:-1] (the feed built
                        # above) works over rings and page tables alike, so a
                        # router may migrate paged-engine state into any engine
                        self._resume.pop(req.uid)
                        slot.generated = list(resume.generated)
                        slot.key = resume.key
                        slot.first_token_time = resume.first_token_time
                        slot.admit_time = resume.admit_time
                        slot.resumed = bool(resume.generated)
                    self.slot_history.setdefault(req.uid, []).append(i)
                    self.slots[i] = slot
                    if self.prefill_mode == "chunked":
                        slot.pos_host = len(feed)
                        claimed.append(i)
                    else:  # interleaved: decode step consumes prompt tokens
                        slot.pos_host = 0
                        slot.next_feed = slot.pending.popleft()
            if not claimed:
                return
            with _span("engine.prefill", rows=len(claimed)):
                retired = self._prefill_claimed(claimed)
            if not retired:
                return  # no slot freed, nothing more to admit this round

    def _prefill_claimed(self, claimed: list[int]) -> bool:
        """Chunked-prefill the claimed slots; returns True if any retired.

        ``first_token_time`` is stamped per slot AFTER its token is
        extracted (``_next_token``'s host transfer forces the async jax
        dispatch), so TTFT includes the prefill compute it waited on.

        Each slot prefills its ``feed`` — the prompt, or prompt +
        generated[:-1] for a preemption resume, whose next token is already
        known: its logits row is discarded and the stored token re-fed, so
        neither the greedy argmax nor the sampling stream replays a draw."""
        retired = False

        def emit(i, row):
            nonlocal retired
            slot = self.slots[i]
            slot.pending.clear()
            if slot.resumed:
                # resume: every generated token survived preemption; decode
                # continues by re-feeding the last one. The slot was live
                # when preempted, so it cannot be done here.
                slot.resumed = False
                slot.next_feed = slot.generated[-1]
                return
            g = self._next_token(slot, row)
            slot.first_token_time = self._now()
            slot.generated.append(g)
            slot.next_feed = g
            if self._done(slot, g):
                self._retire(i, slot)
                retired = True

        self._sync_table()
        if self.batch_prefill:
            # SPLIT ADMISSION: partition the round into a cold group
            # (prefix_len == 0 — the pre-existing prefill_slots trace,
            # bitwise unchanged) and a hit group (suffix trace), so one
            # cache hit never routes the whole round — each cold row's
            # padded length and trace — through the suffix path, and cold
            # rounds compile/dispatch ZERO suffix traces.
            cold = [i for i in claimed if self.slots[i].prefix_len == 0]
            hits = [i for i in claimed if self.slots[i].prefix_len > 0]
            logits_by_slot: dict[int, jax.Array] = {}
            for group, suffix in ((cold, False), (hits, True)):
                if not group:
                    continue
                # each row prefills only the UNCACHED SUFFIX of its feed
                sufs = [
                    self.slots[i].feed[self.slots[i].prefix_len:]
                    for i in group
                ]
                round_len = max(p.size for p in sufs)
                if self.bucket_prefill:
                    width = bucket_width(len(group), self.num_slots)
                    padded_len = bucket_length(round_len)
                else:
                    width = len(group)
                    padded_len = round_len
                tokens = np.zeros((width, padded_len), np.int32)
                lengths = np.zeros(width, np.int32)
                starts = np.zeros(width, np.int32)
                slot_ids = np.zeros(width, np.int32)
                for j, (i, p) in enumerate(zip(group, sufs)):
                    tokens[j, : p.size] = p
                    lengths[j] = p.size
                    starts[j] = self.slots[i].prefix_len
                    slot_ids[j] = i
                if width > len(group):
                    # width-bucket padding rows: length 0 (prefill_slots
                    # writes nothing for them), aimed at DISTINCT slots
                    # outside THIS call — slots outside the whole claimed
                    # set first, the other group's slots as overflow (a
                    # zero-length row reads and rewrites their pages
                    # unchanged, so ordering between the two dispatches
                    # doesn't matter). width <= num_slots guarantees
                    # enough spares.
                    in_group = set(group)
                    spare = [
                        i for i in range(self.num_slots)
                        if i not in in_group and i not in set(claimed)
                    ] + [i for i in set(claimed) - in_group]
                    slot_ids[len(group):] = spare[: width - len(group)]
                if suffix:
                    # static pow2-bucketed prefix width: the suffix attend
                    # streams only this many leading table pages per row
                    pw = bucket_pages(
                        -(-max(int(s) for s in starts) // self.page_size),
                        self.table_width,
                    )
                    self.cache, logits = self._prefill_suffix(
                        self.params, self.cache, jnp.asarray(tokens),
                        jnp.asarray(lengths), jnp.asarray(slot_ids),
                        jnp.asarray(starts), pw,
                    )
                    self.suffix_dispatches += 1
                else:
                    self.cache, logits = self._prefill_slots(
                        self.params, self.cache, jnp.asarray(tokens),
                        jnp.asarray(lengths), jnp.asarray(slot_ids),
                    )
                    self.cold_dispatches += 1
                self.prefill_dispatches += 1
                self.prefill_tokens += int(sum(p.size for p in sufs))
                for j, i in enumerate(group):
                    logits_by_slot[i] = logits[j]
            for i in claimed:  # emit in admission order
                emit(i, logits_by_slot[i])
        elif self.paged_cache:
            # per-request dispatches, but through prefill_slots (the paged
            # writer) at width 1 — prefill_into_slot is ring-only
            for i in claimed:
                slot = self.slots[i]
                suf = slot.feed[slot.prefix_len:]
                if slot.prefix_len:
                    pw = bucket_pages(
                        -(-slot.prefix_len // self.page_size),
                        self.table_width,
                    )
                    self.cache, lg = self._prefill_suffix(
                        self.params, self.cache, jnp.asarray(suf[None, :]),
                        jnp.asarray([suf.size], np.int32),
                        jnp.asarray([i], np.int32),
                        jnp.asarray([slot.prefix_len], np.int32), pw,
                    )
                    self.suffix_dispatches += 1
                else:
                    self.cache, lg = self._prefill_slots(
                        self.params, self.cache, jnp.asarray(suf[None, :]),
                        jnp.asarray([suf.size], np.int32),
                        jnp.asarray([i], np.int32),
                    )
                    self.cold_dispatches += 1
                self.prefill_dispatches += 1
                self.prefill_tokens += int(suf.size)
                emit(i, lg[0])
        else:
            for i in claimed:
                self.cache, lg = self._prefill(
                    self.params, self.cache,
                    jnp.asarray(self.slots[i].feed[None, :]), i,
                )
                self.prefill_dispatches += 1
                self.prefill_tokens += int(self.slots[i].feed.size)
                emit(i, lg[0])
        return retired

    def _done(self, slot: _Slot, last: int) -> bool:
        if self.eos_id is not None and last == self.eos_id:
            return True
        return len(slot.generated) >= slot.req.max_new_tokens

    def _retire(self, i: int, slot: _Slot) -> None:
        with _span("engine.retire", uid=slot.req.uid):
            eos = self.eos_id is not None and slot.generated[-1] == self.eos_id
            reason = "eos" if eos else "length"
            self.finished.append(
                RequestOutput(
                    uid=slot.req.uid,
                    prompt=slot.req.prompt.tolist(),
                    tokens=list(slot.generated),
                    slot=i,
                    finish_reason=reason,
                    arrival_time=slot.req.arrival_time,
                    admit_time=slot.admit_time,
                    first_token_time=slot.first_token_time,
                    finish_time=self._now(),
                )
            )
            self.slots[i] = None
            if self.draft is not None:
                # the next occupant must re-sync the draft
                self._draft_pos[i] = -1
            if self.paged_cache:
                if self.prefix is not None:
                    # publish the slot's FULL prompt pages into the
                    # prefix index (the index takes its own refs) BEFORE
                    # dropping the slot's — already-indexed chunks dedupe
                    # to their existing physical page. Generated tokens
                    # and partial tail pages are never indexed.
                    n_pub = len(slot.req.prompt) // self.page_size
                    n_pub = min(n_pub, len(self._slot_pages[i]))
                    if n_pub > 0:
                        self.prefix.insert(
                            slot.req.prompt, self._slot_pages[i][:n_pub]
                        )
                # the slot's refs return to the pool for IMMEDIATE reuse
                # (pages the index pinned stay live); the table row
                # reverts to the scratch page so the retired slot's
                # drifting ``pos`` writes nothing anyone reads
                self.pool.free(self._slot_pages[i])
                self._slot_pages[i] = []
                self._table_np[i, :] = 0
                self._table_dirty = True

    def _retire_timeout(self, i: int, slot: _Slot) -> None:
        """Watchdog retirement: the slot exceeded ``max_wall_s`` of wall
        clock since its ORIGINAL admission. It leaves with a structured
        ``finish_reason="timeout"`` result carrying whatever it generated,
        so callers distinguish a timed-out stream from a complete one.
        Pages are freed WITHOUT publishing to the prefix index — a
        mid-prefill (interleaved) timeout may hold a partially written
        final page, which must never be aliased by another request."""
        with _span("engine.retire", uid=slot.req.uid):
            self.timeouts += 1
            self.finished.append(
                RequestOutput(
                    uid=slot.req.uid,
                    prompt=slot.req.prompt.tolist(),
                    tokens=list(slot.generated),
                    slot=i,
                    finish_reason="timeout",
                    arrival_time=slot.req.arrival_time,
                    admit_time=slot.admit_time,
                    first_token_time=slot.first_token_time,
                    finish_time=self._now(),
                )
            )
            self.slots[i] = None
            if self.draft is not None:
                self._draft_pos[i] = -1
            if self.paged_cache:
                self.pool.free(self._slot_pages[i])
                self._slot_pages[i] = []
                self._table_np[i, :] = 0
                self._table_dirty = True

    def _watchdog(self) -> None:
        """Per-request wall-clock guard (``max_wall_s``): retire any live
        slot older than the budget. Runs at the top of every engine step,
        so even a step loop whose slots never advance (a stalled dispatch
        under fault injection) keeps shedding rather than hanging."""
        if self.max_wall_s <= 0:
            return
        now = self._now()
        for i, slot in enumerate(self.slots):
            if slot is not None and now - slot.admit_time > self.max_wall_s:
                self._retire_timeout(i, slot)

    # ----------------------------------------------------------- paged pool
    def _sync_table(self) -> None:
        """Push the host page-table mirror to the device before a dispatch.
        The mirror is authoritative — allocation, retirement and preemption
        all mutate it — and the device copy is refreshed lazily, once per
        batch of changes."""
        if self.paged_cache and self._table_dirty:
            self.cache = {**self.cache, "table": jnp.asarray(self._table_np)}
            self._table_dirty = False

    # -------------------------------------------------------- host tier I/O
    @staticmethod
    def _page_bucket(n: int) -> int:
        """Pow2 page-batch bucket: keeps the gather/scatter jits to
        O(log pool) compiled shapes, like every other hot-path axis."""
        m = 1
        while m < n:
            m *= 2
        return m

    def _gather_host(self, pages: list[int]) -> dict:
        """Copy page CONTENT device→host: name → (L, n, page, …) numpy.
        ``np.asarray`` blocks until the copy lands, so callers may free
        (and let the pool rewrite) the source pages immediately after."""
        with _span("engine.swap_out", pages=len(pages)):
            n = len(pages)
            m = self._page_bucket(n)
            idx = np.asarray(list(pages) + [0] * (m - n), np.int32)
            idx = jnp.asarray(idx)
            arrs = self._gather_pages_jit(self.cache, idx)
            return {
                name: np.asarray(a[:, :n])
                for name, a in zip(self._kv_names, arrs)
            }

    def _restore_pages(self, pages: list[int], arrays: dict) -> None:
        """Scatter host content back into freshly allocated pool pages.
        Bucket padding targets scratch page 0 (reserved: writes harmless,
        never validly read)."""
        with _span("engine.swap_in", pages=len(pages)):
            n = len(pages)
            m = self._page_bucket(n)
            idx = np.asarray(list(pages) + [0] * (m - n), np.int32)
            arrs = []
            for name in self._kv_names:
                a = arrays[name]
                if m > n:
                    pad = np.zeros(
                        (a.shape[0], m - n) + a.shape[2:], a.dtype
                    )
                    a = np.concatenate([a, pad], axis=1)
                arrs.append(jnp.asarray(a))
            self.cache = self._scatter_pages_jit(
                self.cache, jnp.asarray(idx), tuple(arrs)
            )

    def _demote_prefix_page(self, key: tuple, page: int) -> None:
        """PrefixCache eviction hook: copy the page's content to the host
        tier (keyed by the full token prefix it caches) before the index
        drops its pool ref. Content is copied, never aliased — co-readers
        still holding the page are unaffected."""
        if self.host is None:
            return
        if self.host.put(("prefix", key), self._gather_host([page]), 1):
            self.host_demoted_pages += 1

    def _promote_prefix_page(self, key: tuple) -> int | None:
        """PrefixCache miss hook: restore a demoted prefix page into a
        fresh pool page; the returned rc=1 ref becomes the index's. None
        when the tier holds no copy or the pool is too tight to spend a
        page on caching (promotion must never starve live admission)."""
        if self.host is None or self.host.n_pages(("prefix", key)) != 1:
            return None
        if self.pool.available <= self.watermark_pages + 1:
            return None
        pages = self.pool.alloc(1)
        if pages is None:
            return None
        self._restore_pages(pages, self.host.pop(("prefix", key)))
        self.host_promote_hits += 1
        return pages[0]

    def _preempt_victim(self) -> int:
        """SLO-aware preemption order: the LOWEST-priority live slot goes
        first; within a priority tier, the YOUNGEST (max admission seq) —
        stalling the most recently admitted work keeps the oldest requests
        flowing, the recency order vLLM uses. All-default-priority traffic
        reproduces the pre-SLO youngest-first behavior exactly."""
        return min(
            (i for i, s in enumerate(self.slots) if s is not None),
            key=lambda i: (self.slots[i].req.priority, -self.slots[i].seq),
        )

    def _preempt(self, i: int) -> None:
        """Evict slot ``i`` back to the HEAD of the waiting queue (it must
        re-admit before anything that arrived after it), freeing its pages.
        Generated tokens, the sampling stream and timing stamps ride along
        in a resume record — re-admission recomputes the KV state by
        prefilling prompt + generated and continues token-identically.

        With the host tier on, the pages are first copied device→host
        (BEFORE the pool refs drop — a freed page may be rewritten by the
        very next decode): re-admission then swaps them back in with one
        scatter instead of re-prefilling. The recompute path stays the
        fallback (and the oracle) whenever the tier dropped the entry."""
        slot = self.slots[i]
        pages = self._slot_pages[i]
        host_key = None
        if self.swap and pages:
            key = ("swap", slot.req.uid)
            if self.host.put(key, self._gather_host(pages), len(pages)):
                host_key = key
                self.swapped_out_pages += len(pages)
        self.pool.free(pages)
        self._slot_pages[i] = []
        self._table_np[i, :] = 0
        self._table_dirty = True
        self._resume[slot.req.uid] = _ResumeState(
            generated=list(slot.generated),
            key=slot.key,
            first_token_time=slot.first_token_time,
            admit_time=slot.admit_time,
            host_key=host_key,
            pos=slot.pos_host,
        )
        self.waiting.appendleft(slot.req)
        self.slots[i] = None
        if self.draft is not None:
            self._draft_pos[i] = -1
        self.preemptions += 1

    # ------------------------------------------------------------ migration
    def export_inflight(self) -> list[tuple[Request, _ResumeState | None]]:
        """Strip EVERY in-flight request off this engine for migration to
        another one: live slots first (admission order), then the waiting
        queue (front first, with any preemption-resume records attached).
        Slots are cleared and their pages freed — after this the engine
        holds no work.

        The returned records are pure host-side state: generated tokens,
        the sampling key, and timing stamps. That is exactly what a
        router fronting real replica processes would hold anyway — it has
        streamed every generated token to the client, and the request-keyed
        PRNG stream is derivable from (seed, uid, tokens emitted), since
        each emission advances the key by one ``jax.random.split``.

        KV pages ride along as HOST-SIDE COPIES (``host_arrays``) when the
        engine can take them: a live slot's pages are gathered
        device→host before being freed, and a waiting request's
        already-swapped tier entry is popped into its record (the tier KEY
        is meaningless to another engine; the content is not). A
        layout-compatible importer adopts them into its own tier and the
        migrated request swaps back in with one scatter — no re-prefill.
        Incompatible or absent arrays fall back to the recompute-resume
        path, which remains the oracle."""
        items: list[tuple[Request, _ResumeState | None]] = []
        live = sorted(
            (i for i, s in enumerate(self.slots) if s is not None),
            key=lambda i: self.slots[i].seq,
        )
        can_carry = self.paged_cache and self.mesh is None
        for i in live:
            slot = self.slots[i]
            resume = None
            if slot.generated:
                resume = _ResumeState(
                    generated=list(slot.generated),
                    key=slot.key,
                    first_token_time=slot.first_token_time,
                    admit_time=slot.admit_time,
                    pos=slot.pos_host,
                )
                if can_carry and self._slot_pages[i]:
                    # copy BEFORE the free below — a freed page may be
                    # rewritten by the importer's very first dispatch
                    resume.host_arrays = self._gather_host(
                        self._slot_pages[i]
                    )
            items.append((slot.req, resume))
            self.slots[i] = None
            if self.draft is not None:
                self._draft_pos[i] = -1
            if self.paged_cache:
                self.pool.free(self._slot_pages[i])
                self._slot_pages[i] = []
                self._table_np[i, :] = 0
                self._table_dirty = True
        while self.waiting:
            req = self.waiting.popleft()
            resume = self._resume.pop(req.uid, None)
            if resume is not None and resume.host_key is not None:
                # pop the swapped pages out of THIS engine's tier and carry
                # their content in the record itself
                if self.host is not None:
                    resume.host_arrays = self.host.pop(resume.host_key)
                resume.host_key = None
            items.append((req, resume))
        return items

    def _adopt_host_arrays(
        self, uid: int, resume: _ResumeState, arrays: dict
    ) -> bool:
        """Take a migrated record's page content into the LOCAL host tier
        (under this engine's own ("swap", uid) key) so admission swaps the
        request in instead of recomputing. Adoption requires an exactly
        matching pool layout — same plane set (fp vs int8+scales), same
        layer count, page shape and dtypes — anything else recomputes."""
        if self.host is None or resume.pos <= 0:
            return False
        if set(arrays) != set(self._kv_names):
            return False
        for name in self._kv_names:
            ref = self.cache[name]
            a = arrays[name]
            if (
                a.shape[0] != ref.shape[0]
                or a.shape[2:] != tuple(ref.shape[2:])
                or a.dtype != np.dtype(ref.dtype)
            ):
                return False
        n = int(arrays[self._kv_names[0]].shape[1])
        key = ("swap", uid)
        if not self.host.put(key, arrays, n):
            return False
        resume.host_key = key
        return True

    def import_inflight(
        self, items: list[tuple[Request, _ResumeState | None]]
    ) -> None:
        """Adopt migrated requests at the FRONT of the queue, preserving
        their order — in-flight work from a failed replica is older than
        anything queued locally, and FIFO admission owes it first service.
        Requests with generated tokens re-enter through the preemption-
        resume path (re-prefill prompt + generated[:-1], re-feed the last
        token, continue the sampling stream where it stopped), so the
        merged output stream is token-identical to an uninterrupted run."""
        for req, resume in reversed(items):
            if self.capacity_shortfall(req) > 0:
                raise AdmissionError(
                    req.uid, "exceeds_pool",
                    f"migrated request {req.uid} exceeds this engine's "
                    "static capacity",
                )
            if resume is not None and resume.generated:
                if resume.host_arrays is not None:
                    self._adopt_host_arrays(req.uid, resume, resume.host_arrays)
                    resume.host_arrays = None
                self._resume[req.uid] = resume
            self.waiting.appendleft(req)

    def _ensure_decode_pages(self, live: list[int]) -> None:
        """Lazy per-step allocation: before a decode dispatch, every live
        slot whose next write position crosses into an unallocated logical
        page gets one. When the pool is dry, the LOWEST-priority-then-
        youngest slot is preempted (repeatedly, until a page frees up) —
        see ``_preempt_victim``. If the starving slot preempts ITSELF the
        loop stops: its request is back in the queue, its pages freed."""
        for i in live:
            slot = self.slots[i]
            if slot is None:
                continue  # preempted while serving an earlier slot's need
            pi = (slot.pos_host % self.cap) // self.page_size
            if self._table_np[i, pi] != 0:
                continue
            while True:
                pages = self.pool.alloc(1)
                if pages is not None:
                    self._slot_pages[i].append(pages[0])
                    self._table_np[i, pi] = pages[0]
                    self._table_dirty = True
                    break
                # shed cold prefix-index pages before preempting live work
                if self.prefix is not None and self.prefix.evict(1) > 0:
                    continue
                victim = self._preempt_victim()
                with _span("engine.preempt", uid=self.slots[victim].req.uid):
                    self._preempt(victim)
                if victim == i:
                    break  # the needy slot itself went back to the queue

    # ------------------------------------------------------- spec decoding
    def _ensure_spec_pages(
        self, live: list[int], k_r: dict[int, int]
    ) -> dict[int, list[tuple[int, int]]]:
        """BEST-EFFORT lookahead pages for a speculative round: slot ``i``
        verifying ``k_r[i]`` drafts writes positions ``pos .. pos+k_r[i]``,
        which may cross into logical pages beyond the one
        ``_ensure_decode_pages`` guarantees. Lookahead pages never preempt
        live work and never dip below the admission watermark — when the
        pool is tight the round simply runs SHALLOWER (``k_r`` shrinks to
        what the covered pages can hold; 0 degenerates to a 1-token verify,
        i.e. plain decode with an extra logit row). Returns the freshly
        allocated (page_index, page) pairs per slot so rejection rollback
        can free exactly the pages that ended up holding no kept tokens."""
        fresh: dict[int, list[tuple[int, int]]] = {}
        for i in live:
            p = self.slots[i].pos_host
            first = p // self.page_size + 1
            last = (p + k_r[i]) // self.page_size
            got = []
            for pi in range(first, last + 1):
                if self._table_np[i, pi] != 0:
                    continue
                pages = None
                if self.pool.available > self.watermark_pages:
                    pages = self.pool.alloc(1)
                if (
                    pages is None
                    and self.prefix is not None
                    and self.prefix.evict(1) > 0
                    and self.pool.available > self.watermark_pages
                ):
                    pages = self.pool.alloc(1)
                if pages is None:
                    k_r[i] = pi * self.page_size - 1 - p
                    break
                self._slot_pages[i].append(pages[0])
                self._table_np[i, pi] = pages[0]
                self._table_dirty = True
                got.append((pi, pages[0]))
            if got:
                fresh[i] = got
        return fresh

    def _rollback_spec_pages(
        self, i: int, fresh_i: list[tuple[int, int]], keep_pos: int
    ) -> None:
        """Free the round's fresh lookahead pages past the accepted span:
        after acceptance the slot keeps ``keep_pos`` written tokens, so a
        fresh page whose index is beyond the last kept token's page holds
        only rejected KV. Pre-existing pages are never touched (they hold
        committed history), so rejection storms cannot leak or double-free
        — the accounting invariant the spec tests pin."""
        last = (keep_pos - 1) // self.page_size
        for pi, page in fresh_i:
            if pi > last:
                self.pool.free([page])
                self._slot_pages[i].remove(page)
                self._table_np[i, pi] = 0
                self._table_dirty = True

    def _spec_round(self, live: list[int]) -> None:
        """One speculative iteration over the live slots: draft-propose k
        tokens per row, verify ALL rows' proposals in ONE batched
        suffix-prefill dispatch of the target, then accept a prefix of each
        row's drafts (greedy: longest argmax-matching run; sampled:
        Leviathan rejection sampling) and roll rejected KV back by pos
        truncation + lookahead-page free.

        Greedy rows emit EXACTLY the target-only decode stream: the verify
        logits at position p+j are the same forward the per-token path
        would compute after consuming the same j accepted tokens, and the
        walk stops at the first draft/argmax mismatch — so every emitted
        token is an argmax the sequential engine would have produced
        (bitwise, pinned by tests). Sampled rows draw from the target
        distribution exactly (speculative-sampling guarantee), on a
        per-request stream advanced ONE split per round."""
        kk = self.spec_tokens
        for i in live:
            slot = self.slots[i]
            # chunked admission prefills prompts whole, so decode-phase
            # slots can never be mid-prefill or resume-suppressed here
            assert not slot.pending and not slot.resumed, (
                "spec round over a mid-prefill/resumed slot"
            )
        # ---- draft re-sync: rows whose draft state does not sit exactly at
        # pos_host (fresh admissions, preemption returns, slot reuse) get a
        # full re-prefill of their written stream; rows in sync ride along
        # as length-0 no-ops
        stale = [i for i in live if self._draft_pos[i] != self.slots[i].pos_host]
        if stale:
            lb = bucket_length(max(self.slots[i].pos_host for i in stale))
            toks = np.zeros((self.num_slots, lb), np.int32)
            lens = np.zeros(self.num_slots, np.int32)
            for i in stale:
                slot = self.slots[i]
                p = slot.pos_host
                stream = list(slot.req.prompt) + slot.generated
                toks[i, :p] = stream[:p]
                lens[i] = p
            self.draft.prefill_rows(jnp.asarray(toks), jnp.asarray(lens))
            for i in stale:
                self._draft_pos[i] = self.slots[i].pos_host
        # ---- per-row depth: never draft past max_new (the +1 correction /
        # bonus token must still fit) or the slot's token capacity; the page
        # pass below may shrink depths further
        lim = min(self.cap, self.pool.capacity * self.page_size)
        k_r = {}
        for i in live:
            slot = self.slots[i]
            rem = slot.req.max_new_tokens - len(slot.generated)
            k_r[i] = max(0, min(kk, rem - 1, lim - 1 - slot.pos_host))
        fresh = self._ensure_spec_pages(live, k_r)
        # ---- round inputs (full slot width, like every engine dispatch)
        feed = np.zeros(self.num_slots, np.int32)
        greedy = np.ones(self.num_slots, bool)
        temps = np.ones(self.num_slots, np.float32)
        topks = np.zeros(self.num_slots, np.int32)
        topps = np.ones(self.num_slots, np.float32)
        samp = [i for i in live if self.slots[i].key is not None]
        for i in live:
            slot = self.slots[i]
            feed[i] = slot.next_feed
            if slot.key is not None:
                sp = slot.req.sampling
                greedy[i] = False
                temps[i] = sp.temperature
                topks[i] = sp.top_k
                topps[i] = sp.top_p
        subs = None
        if samp:
            in_samp = set(samp)
            keys = [
                self.slots[i].key if i in in_samp else self._dummy_key
                for i in range(self.num_slots)
            ]
            new_keys, subs = self._spec_split(jnp.stack(keys))
            for i in samp:
                self.slots[i].key = new_keys[i]
        keys_arr = subs if subs is not None else self._spec_dummy_keys
        # ---- draft proposals: k sequential CHEAP steps, all rows at once
        drafts_dev, logq_dev = self.draft.propose(
            jnp.asarray(feed), keys_arr, jnp.asarray(greedy),
            jnp.asarray(temps), jnp.asarray(topks), jnp.asarray(topps),
        )
        drafts = np.asarray(drafts_dev)  # (num_slots, k)
        # ---- single-dispatch verify: row j feeds [next_feed, d_1..d_kr]
        # as a SUFFIX at starts=pos over the shared page table — one target
        # forward replaces kr+1 sequential decode dispatches
        self._sync_table()
        n = len(live)
        width = bucket_width(n, self.num_slots)
        s_len = bucket_length(max(k_r[i] for i in live) + 1)
        tokens = np.zeros((width, s_len), np.int32)
        lengths = np.zeros(width, np.int32)
        starts = np.zeros(width, np.int32)
        slot_ids = np.zeros(width, np.int32)
        for j, i in enumerate(live):
            slot = self.slots[i]
            kr = k_r[i]
            tokens[j, 0] = slot.next_feed
            tokens[j, 1:kr + 1] = drafts[i, :kr]
            lengths[j] = kr + 1
            starts[j] = slot.pos_host
            slot_ids[j] = i
        in_round = set(live)
        spare = [s for s in range(self.num_slots) if s not in in_round]
        slot_ids[n:] = spare[: width - n]
        pw = bucket_pages(
            -(-max(int(s) for s in starts) // self.page_size),
            self.table_width,
        )
        self.cache, vlog = self._spec_verify(
            self.params, self.cache, jnp.asarray(tokens),
            jnp.asarray(lengths), jnp.asarray(slot_ids),
            jnp.asarray(starts), pw,
        )
        self.spec_rounds += 1
        self.steps += 1
        # ---- acceptance: one batched argmax transfer for greedy rows, one
        # batched rejection-sampling dispatch for sampled rows
        g_host = None
        if any(self.slots[i].key is None for i in live):
            g_host = np.asarray(
                jnp.argmax(vlog[..., : self.cfg.vocab_size], axis=-1)
            )  # (width, s_len)
        n_emit_host = emitted_host = None
        if samp:
            klive = np.zeros(width, np.int32)
            for j, i in enumerate(live):
                klive[j] = k_r[i]
            sl = jnp.asarray(slot_ids)
            n_emit_dev, emitted_dev = self._spec_accept(
                keys_arr[sl], vlog, jnp.asarray(drafts)[sl], logq_dev[sl],
                jnp.asarray(klive), jnp.asarray(temps[slot_ids]),
                jnp.asarray(topks[slot_ids]), jnp.asarray(topps[slot_ids]),
            )
            n_emit_host = np.asarray(n_emit_dev)
            emitted_host = np.asarray(emitted_dev)
        # ---- commit: append each row's accepted run, truncate target pos
        # to the kept span, free lookahead pages past it, restore the draft
        now = self._now()
        new_pos = np.zeros(self.num_slots, np.int32)
        mask = np.zeros(self.num_slots, bool)
        snap_idx = np.full(self.num_slots, kk, np.int32)
        for j, i in enumerate(live):
            slot = self.slots[i]
            kr = k_r[i]
            p = slot.pos_host
            if slot.key is None:
                g = g_host[j]
                emitted = []
                t = 0
                while True:
                    emitted.append(int(g[t]))
                    if t >= kr or int(drafts[i, t]) != int(g[t]):
                        break
                    t += 1
            else:
                ne = min(int(n_emit_host[j]), kr + 1)
                emitted = [int(x) for x in emitted_host[j, :ne]]
            if slot.first_token_time < 0:
                slot.first_token_time = now
            appended = 0
            done = False
            for tok in emitted:
                slot.generated.append(tok)
                appended += 1
                if self._done(slot, tok):
                    done = True
                    break
            self.spec_drafted += kr
            self.spec_emitted += appended
            self.spec_accepted += max(0, appended - 1)
            new_pos[i] = p + appended
            mask[i] = True
            snap_idx[i] = appended - 1
            if done:
                # _retire frees EVERY slot page (lookahead included), so
                # rollback must not run first — that would double-free
                self._retire(i, slot)
            else:
                self._rollback_spec_pages(i, fresh.get(i, []), p + appended)
                slot.pos_host = p + appended
                slot.next_feed = emitted[appended - 1]
                self._draft_pos[i] = p + appended
        self.cache = self._fix_pos(
            self.cache, jnp.asarray(new_pos), jnp.asarray(mask)
        )
        self.draft.commit(
            jnp.asarray(mask), jnp.asarray(new_pos), jnp.asarray(snap_idx)
        )
        self._record_occupancy()

    def _record_occupancy(self) -> None:
        occ = self.pool.in_use / max(self.pool.capacity, 1)
        self.occupancy_steps += 1
        self._occupancy_sum += occ
        self._occupancy_max = max(self._occupancy_max, occ)

    def step(self, *, respect_arrivals: bool = False) -> list[RequestOutput]:
        """One engine iteration: admit → one batched decode step → retire.

        Returns the requests that finished during this iteration. With
        ``respect_arrivals`` the admission gate compares each request's
        ``arrival_time`` against the engine clock; otherwise the queue
        drains in arrival order as slots free up (virtual time).

        Each phase runs under its own host span (``engine.admit``,
        ``engine.pages``, ``engine.dispatch_decode``, ``engine.read_tokens``,
        ``engine.emit`` or ``engine.spec_round``) inside ``engine.step``.
        """
        n_done = len(self.finished)
        with _span("engine.step"):
            attention.set_decode_kernel(
                self.use_kernel, paged=self.paged_decode
            )
            # prefix-hit admission rounds (dispatched from _admit below) run
            # the Pallas suffix-prefill kernel under the same engine-wide flag
            attention.set_suffix_kernel(self.use_kernel)
            try:
                with _span("engine.admit") as span:
                    seq = self._admit_seq
                    self._watchdog()
                    self._admit(self._now(), respect_arrivals)
                    span.set_metadata(claimed=self._admit_seq - seq)
                live = [i for i, s in enumerate(self.slots) if s is not None]
                if live and self.paged_cache:
                    # lazy page allocation (may preempt the youngest slot
                    # when the pool runs dry — re-collect the survivors)
                    with _span("engine.pages"):
                        self._ensure_decode_pages(live)
                    live = [
                        i for i, s in enumerate(self.slots) if s is not None
                    ]
                if live and self.draft is not None:
                    # speculative round: draft k tokens per slot, verify all
                    # of them in one batched target dispatch (_spec_round)
                    with _span("engine.spec_round", rows=len(live)):
                        self._spec_round(live)
                elif live:
                    with _span("engine.dispatch_decode", rows=len(live)):
                        logits = self._dispatch_decode(live)
                    with _span("engine.read_tokens"):
                        greedy, sampled = self._read_tokens(live, logits)
                    with _span("engine.emit"):
                        self._emit(live, greedy, sampled)
            finally:
                attention.set_decode_kernel(False)
                attention.set_suffix_kernel(False)
        return self.finished[n_done:]

    def _dispatch_decode(self, live: list[int]) -> jax.Array:
        """Launch one decode step over every slot (live rows fed their next
        token); returns the step's logits without waiting for them."""
        self._sync_table()
        feed = np.zeros((self.num_slots, 1), np.int32)
        for i in live:
            feed[i, 0] = self.slots[i].next_feed
        self.cache, logits = self._decode(
            self.params, self.cache, jnp.asarray(feed)
        )
        self.steps += 1
        for i in live:
            self.slots[i].pos_host += 1  # one token written per row
        if self.paged_cache:
            self._record_occupancy()
        return logits

    def _read_tokens(
        self, live: list[int], logits: jax.Array
    ) -> tuple[np.ndarray | None, dict[int, int]]:
        """The step's tokens on the host: the greedy rows' argmax and the
        sampled rows' draws, each batched into one host transfer."""
        # one batched argmax + host transfer per step, not per slot
        # (skipped entirely when every emitting slot samples).
        # Resumed slots re-feed a stored token this step: no argmax,
        # no sampling draw — their streams must not advance.
        need_greedy = any(
            self.slots[i].key is None and not self.slots[i].pending
            and not self.slots[i].resumed
            for i in live
        )
        greedy = (
            np.asarray(jnp.argmax(logits[:, : self.cfg.vocab_size], axis=-1))
            if need_greedy
            else None
        )
        # sampled slots batch the same way: split every stream and
        # draw in ONE fixed-width dispatch (dummy rows for greedy /
        # mid-prefill slots), then one host transfer
        samp = [
            i for i in live
            if self.slots[i].key is not None
            and not self.slots[i].pending
            and not self.slots[i].resumed
        ]
        sampled: dict[int, int] = {}
        if samp:
            keys, temps, ks, ps = [], [], [], []
            for i in range(self.num_slots):
                if i in samp:
                    sp = self.slots[i].req.sampling
                    keys.append(self.slots[i].key)
                    temps.append(sp.temperature)
                    ks.append(sp.top_k)
                    ps.append(sp.top_p)
                else:
                    keys.append(self._dummy_key)
                    temps.append(1.0)
                    ks.append(1)
                    ps.append(1.0)
            new_keys, toks = self._sample_rows(
                jnp.stack(keys), logits,
                jnp.asarray(temps, jnp.float32),
                jnp.asarray(ks, jnp.int32),
                jnp.asarray(ps, jnp.float32),
            )
            toks = np.asarray(toks)
            for i in samp:
                self.slots[i].key = new_keys[i]
                sampled[i] = int(toks[i])
        return greedy, sampled

    def _emit(
        self, live: list[int], greedy: np.ndarray | None,
        sampled: dict[int, int],
    ) -> None:
        """Per-slot bookkeeping after the read: advance mid-prefill and
        resumed rows, append each emitted token, retire finished rows."""
        now = self._now()
        for i in live:
            slot = self.slots[i]
            if slot.pending:  # mid-prefill: logits are teacher-forced
                slot.next_feed = slot.pending.popleft()
                continue
            if slot.resumed:
                # interleaved resume just finished re-feeding its
                # history: the next token is already known
                slot.resumed = False
                slot.next_feed = slot.generated[-1]
                continue
            g = sampled[i] if slot.key is not None else int(greedy[i])
            if slot.first_token_time < 0:
                slot.first_token_time = now
            slot.generated.append(g)
            slot.next_feed = g
            if self._done(slot, g):
                self._retire(i, slot)  # freed; backfilled next admit

    def run(
        self, requests=(), *, realtime: bool = False
    ) -> list[RequestOutput]:
        """Drain ``requests`` (plus anything already queued) to completion.

        ``realtime=True`` honors arrival times against the wall clock,
        sleeping while all slots are idle and the next arrival is in the
        future — the benchmark's Poisson-trace mode. ``realtime=False``
        replays the queue in arrival order at full speed (deterministic)."""
        for req in sorted(requests, key=lambda r: r.arrival_time):
            self.submit(req)
        outs: list[RequestOutput] = []
        while self.has_work:
            if realtime and self.active_slots == 0:
                nxt = self.next_arrival()
                if nxt is not None:
                    delay = nxt - self._now()
                    if delay > 0:
                        time.sleep(delay)
            outs.extend(self.step(respect_arrivals=realtime))
        return sorted(outs, key=lambda o: o.uid)


# ----------------------------------------------------------------- helpers
def make_requests(
    cfg,
    *,
    n_requests: int,
    prompt_len: int,
    gen_tokens: int,
    seed: int = 0,
    stagger: float = 0.0,
) -> list[Request]:
    """Synthetic request trace with the serve oracle's prompt distribution:
    row r of the (n_requests, prompt_len) corpus sample is request r, so the
    uid-r output is directly comparable against ``serve_batch`` row r."""
    corpus = SyntheticCorpus(vocab_size=cfg.vocab_size, n_domains=4, noise=0.0)
    prompts = corpus.sample(
        jax.random.PRNGKey(seed + 1), jnp.ones(4) / 4, n_requests, prompt_len
    )["tokens"]
    prompts = np.asarray(prompts, np.int32)
    return [
        Request(
            uid=r,
            prompt=prompts[r],
            max_new_tokens=gen_tokens,
            arrival_time=r * stagger,
        )
        for r in range(n_requests)
    ]


def serve_continuous(
    arch: str,
    *,
    smoke: bool = True,
    num_slots: int = 4,
    n_requests: int = 8,
    prompt_len: int = 32,
    gen_tokens: int = 32,
    window: int = 0,
    use_kernel: bool = False,
    prefill: str = "chunked",
    batch_prefill: bool = True,
    bucket_prefill: bool = True,
    paged_decode: bool = True,
    donate_cache: bool = True,
    paged_cache: bool = True,
    page_size: int = 16,
    num_pages: int = 0,
    long_requests: bool = False,
    watermark_pages: int = 0,
    prefix_cache: bool = True,
    prefix_cache_pages: int = 0,
    kv_dtype: str = "fp",
    host_pages: int = 0,
    swap: bool = True,
    num_shards: int = 0,
    draft: str | None = None,
    spec_tokens: int = 0,
    sampling: SamplingParams | None = None,
    seed: int = 0,
    stagger: float = 0.0,
    max_wall_s: float = 0.0,
    requests: list[Request] | None = None,
    log_fn=print,
) -> dict:
    """Build a model + engine, serve a synthetic trace, report throughput.

    ``requests`` replaces the synthetic trace (``n_requests`` prompts of
    ``prompt_len`` tokens, ``gen_tokens`` each) with the caller's own; the
    engine is then sized and warmed for their lengths.

    The serving CLI defaults to the PAGED cache (``--no-paged-cache``
    restores per-slot contiguous rings) — output is token-identical either
    way; paged mode additionally reports pool occupancy and preemptions.
    ``num_shards > 0`` serves tensor-parallel on a ``model``-axis mesh over
    that many devices (bitwise token-identical to the unsharded engine).
    ``draft`` names a second (cheap) config for speculative decoding: it
    proposes ``spec_tokens`` tokens per slot per round and the target
    verifies them in one batched dispatch — greedy output stays bitwise
    identical to the non-speculative engine."""
    cfg = get_smoke_config(arch) if smoke else get_config(arch)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(seed))
    draft_model = draft_params = None
    if draft is not None:
        dcfg = get_smoke_config(draft) if smoke else get_config(draft)
        draft_model = build_model(dcfg)
        # the draft seeds from the SAME stream: --draft <arch> with the
        # target's own arch gives identical params, the ~100% acceptance
        # probe configuration serve_bench --spec-probe exploits
        draft_params = draft_model.init(jax.random.PRNGKey(seed))
    if requests is None:
        requests = make_requests(
            cfg, n_requests=n_requests, prompt_len=prompt_len,
            gen_tokens=gen_tokens, seed=seed, stagger=stagger,
        )
    prompt_lens = sorted({len(r.prompt) for r in requests})
    engine = ServeEngine(
        model,
        params,
        num_slots=num_slots,
        max_seq=max(len(r.prompt) + r.max_new_tokens for r in requests),
        window=window,
        use_kernel=use_kernel,
        prefill=prefill,
        batch_prefill=batch_prefill,
        bucket_prefill=bucket_prefill,
        paged_decode=paged_decode,
        donate_cache=donate_cache,
        mesh=make_serve_mesh(num_shards) if num_shards > 0 else None,
        paged_cache=paged_cache,
        page_size=page_size,
        num_pages=num_pages,
        long_requests=long_requests,
        watermark_pages=watermark_pages,
        prefix_cache=prefix_cache,
        prefix_cache_pages=prefix_cache_pages,
        kv_dtype=kv_dtype,
        host_pages=host_pages,
        swap=swap,
        draft_model=draft_model,
        draft_params=draft_params,
        spec_tokens=spec_tokens,
        seed=seed,
        max_wall_s=max_wall_s,
    )
    if sampling is not None and not sampling.is_greedy:
        for r in requests:
            # distinct stream per request even under a shared CLI seed
            r.sampling = dataclasses.replace(
                sampling,
                seed=None if sampling.seed is None else sampling.seed + r.uid,
            )
    # trace prefill + decode outside the measured window so the reported
    # throughput/latency are steady-state, not jit compilation
    engine.warm(prompt_lens, gen_tokens=min(2, gen_tokens), sampling=sampling)
    t0 = time.time()
    outs = engine.run(requests, realtime=stagger > 0)
    wall = time.time() - t0
    total = sum(len(o.tokens) for o in outs)
    lat = [o.latency for o in outs] or [0.0]
    result = {
        "arch": cfg.name,
        "num_slots": num_slots,
        "n_requests": len(requests),
        "prompt_len": prompt_lens[-1],
        "gen_tokens": max(r.max_new_tokens for r in requests),
        "window": window,
        "use_kernel": use_kernel,
        "prefill": prefill,
        "batch_prefill": engine.batch_prefill,
        "bucket_prefill": engine.bucket_prefill,
        "paged_decode": engine.paged_decode,
        "donate_cache": engine.donate_cache,
        "paged_cache": engine.paged_cache,
        "shards": engine.num_shards,
        "mesh_axes": (
            dict(engine.mesh.shape) if engine.mesh is not None else None
        ),
        "prefix_cache": engine.prefix_cache,
        "kv_dtype": engine.kv_dtype,
        "draft": None if draft_model is None else draft_model.cfg.name,
        "spec_tokens": engine.spec_tokens,
        "prefill_tokens": engine.prefill_tokens,
        "sampling": None if sampling is None else dataclasses.asdict(sampling),
        "engine_steps": engine.steps,
        "prefill_dispatches": engine.prefill_dispatches,
        "compiles": engine.compiles,
        "pool": engine.pool_stats,
        "wall_seconds": wall,
        "tokens_per_second": total / max(wall, 1e-9),
        "generated": [o.tokens for o in outs],
        "slots": [o.slot for o in outs],
        "latency_p50": float(np.percentile(lat, 50)),
        "latency_p95": float(np.percentile(lat, 95)),
    }
    pool_line = ""
    if engine.paged_cache:
        ps = result["pool"]
        pool_line = (
            f", pool occ mean {ps['occupancy_mean']:.0%} / "
            f"max {ps['occupancy_max']:.0%} over "
            f"{ps['allocatable_pages']} pages, "
            f"{ps['preemptions']} preemptions"
        )
        if engine.mesh is not None:
            pool_line += f", {ps['shards']}-shard mesh"
        if engine.prefix_cache:
            pool_line += (
                f", prefix hit {ps['prefix_hit_rate']:.0%} "
                f"({ps['prefix_hit_pages']} pages, "
                f"{ps['cow_copies']} CoW)"
            )
        if ps["kv_dtype"] != "fp":
            pool_line += f", kv {ps['kv_dtype']}"
        if ps["swap_enabled"]:
            pool_line += (
                f", swap {ps['swapped_out_pages']}↓/"
                f"{ps['swapped_in_pages']}↑ pages"
            )
        if ps["spec_enabled"]:
            pool_line += (
                f", spec k={ps['spec_tokens']} accept "
                f"{ps['spec_accept_rate']:.0%}, "
                f"{ps['spec_dispatches_per_token']:.2f} dispatch/tok"
            )
    log_fn(
        f"{cfg.name}: {result['n_requests']} reqs × "
        f"{result['gen_tokens']} tok over "
        f"{num_slots} slots in {engine.steps} steps "
        f"+ {engine.prefill_dispatches} prefill dispatches, {wall:.2f}s "
        f"({result['tokens_per_second']:.1f} tok/s, "
        f"p50 {result['latency_p50']:.2f}s p95 {result['latency_p95']:.2f}s"
        f"{pool_line})"
    )
    return result
