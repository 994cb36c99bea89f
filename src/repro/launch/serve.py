"""Serving CLI: single-batch oracle path and the continuous-batching engine.

``serve_batch`` is the sequential reference path (one fixed batch, lockstep
teacher-forced prefill + greedy decode) — it is the oracle the engine's
continuous-batching output is pinned against token-for-token. The
``--continuous`` mode dispatches to ``launch/engine.py``: slot-based
admission, interleaved/chunked prefill (batched multi-slot by default —
every request admitted in a scheduling round shares ONE prefill forward),
EOS/max-token retirement with immediate backfill, and per-request
temperature/top-k/top-p sampling (--temperature 0 = greedy). Both support
the Pallas flash-decode kernel (--use-kernel, interpret mode on CPU) and
sliding-window ring caches. Continuous mode serves from the shared PAGED
KV pool by default (--page-size/--num-pages tune it, --no-paged-cache
restores per-slot contiguous rings): sequences are bounded by pool pages
instead of a per-slot max_seq, and an undersized pool oversubscribes
memory with watermark admission + youngest-slot preemption. On top of the
pool, SHARED-PREFIX caching is default-on (--no-prefix-cache disables,
--prefix-cache-pages caps the index): retired prompts' full pages are
indexed in a radix trie and later requests with a common prefix alias the
same physical pages, prefilling only their uncached suffix — same tokens,
a fraction of the prefill FLOPs. Slots default to ring-equivalent logical
width; --long-requests widens every slot's page table to the whole pool.
--kv-dtype int8 stores pool pages quantized (per-token-slot per-kv-head
fp32 scales, dequantized inside the attend kernels) for ~4x the resident
sequences per HBM byte; --host-pages N adds a host-RAM tier under the
pool — preempted slots swap pages out and restore them with one copy
instead of recomputing, and evicted prefix pages demote/promote through
the same tier (--no-swap keeps only the prefix half).
Continuous mode also serves TENSOR-PARALLEL (--mesh N): attention heads and
the KV pool's kv-head slices split over an N-device ``model`` mesh through
``shard_map``, bitwise token-identical to the single-device engine; on CPU
pair it with --num-devices N (host-device override, set before jax inits).
--draft ARCH --spec-tokens K turns on speculative decoding: a cheap draft
model proposes K lookahead tokens per slot per round and the target
verifies all of them in ONE batched suffix-prefill dispatch — up to K+1
tokens emitted per target forward, greedy output bitwise identical to the
plain engine.
With --replicas N the trace is served through the fault-tolerant router
(``launch/router.py``): prefix-affinity + occupancy placement over N
engine replicas, SLO-aware preemption, and token-exact failover — inject
failures with --fault kill:R@S / stall:R@S / slow:R@S@SEC to watch
in-flight requests migrate without changing a single output token.

    # oracle (single fixed batch)
    PYTHONPATH=src python -m repro.launch.serve --arch stablelm-1.6b \
        --batch 4 --prompt-len 32 --gen 32

    # continuous batching (slot pool + request queue)
    PYTHONPATH=src python -m repro.launch.serve --continuous \
        --arch stablelm-1.6b --slots 4 --requests 8 --stagger 0.05

    # tensor-parallel serving on a 2-shard CPU mesh
    PYTHONPATH=src python -m repro.launch.serve --continuous \
        --arch stablelm-1.6b --mesh 2 --num-devices 2
"""
from __future__ import annotations

import argparse
import os
import sys
import time


def _apply_num_devices_flag() -> None:
    """Honor ``--num-devices N`` BEFORE the jax import below — jax locks the
    host device count at first init (the constraint dryrun.py documents), so
    argparse in main() would see it too late. Argparse still owns the flag's
    help text and value; this peek only mirrors it into XLA_FLAGS."""
    argv = sys.argv[1:]
    n = 0
    for i, a in enumerate(argv):
        if a == "--num-devices" and i + 1 < len(argv):
            try:
                n = int(argv[i + 1])
            except ValueError:
                return  # argparse will report the bad value
        elif a.startswith("--num-devices="):
            try:
                n = int(a.split("=", 1)[1])
            except ValueError:
                return
    flags = os.environ.get("XLA_FLAGS", "")
    if n > 0 and "--xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={n}"
        ).strip()


_apply_num_devices_flag()

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, get_smoke_config
from repro.data import SyntheticCorpus
from repro.models import build_model
from repro.models import attention
from repro.utils.compile_cache import enable_compile_cache


def serve_batch(
    arch: str,
    *,
    smoke: bool = True,
    batch: int = 4,
    prompt_len: int = 32,
    gen_tokens: int = 32,
    window: int = 0,
    use_kernel: bool = False,
    greedy: bool = True,
    seed: int = 0,
    log_fn=print,
) -> dict:
    cfg = get_smoke_config(arch) if smoke else get_config(arch)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(seed))
    corpus = SyntheticCorpus(vocab_size=cfg.vocab_size, n_domains=4, noise=0.0)
    prompts = corpus.sample(
        jax.random.PRNGKey(seed + 1), jnp.ones(4) / 4, batch, prompt_len
    )["tokens"]

    attention.set_decode_kernel(use_kernel)
    try:
        max_seq = prompt_len + gen_tokens
        t0 = time.time()
        if cfg.arch_type == "audio":
            audio = jax.random.normal(
                jax.random.PRNGKey(seed + 2),
                (batch, cfg.encoder_seq, cfg.d_model), cfg.dtype,
            )
            cache = model.init_cache(
                params, {"tokens": prompts, "audio_embeds": audio}, max_seq,
                window=window,
            )
            # teacher-force the prompt through decode (whisper has no prefill)
            dec = jax.jit(lambda p, c, t: model.decode(p, c, t, window=window))
            logits = None
            for i in range(prompt_len):
                cache, logits = dec(params, cache, prompts[:, i : i + 1])
        else:
            # build cache sized for the full generation, then teacher-force
            cache = model.init_cache(params, {"tokens": prompts}, max_seq, window=window)
            dec = jax.jit(lambda p, c, t: model.decode(p, c, t, window=window))
            logits = None
            for i in range(prompt_len):
                cache, logits = dec(params, cache, prompts[:, i : i + 1])
        t_prefill = time.time() - t0

        generated = []
        tok = jnp.argmax(logits[:, : cfg.vocab_size], axis=-1)[:, None]
        t0 = time.time()
        for _ in range(gen_tokens):
            generated.append(tok)
            cache, logits = dec(params, cache, tok)
            tok = jnp.argmax(logits[:, : cfg.vocab_size], axis=-1)[:, None]
        t_gen = time.time() - t0
    finally:
        attention.set_decode_kernel(False)

    gen = jnp.concatenate(generated, axis=1)
    result = {
        "arch": cfg.name,
        "batch": batch,
        "prompt_len": prompt_len,
        "gen_tokens": gen_tokens,
        "window": window,
        "use_kernel": use_kernel,
        "prefill_seconds": t_prefill,
        "decode_seconds": t_gen,
        "tokens_per_second": batch * gen_tokens / max(t_gen, 1e-9),
        "generated": np.asarray(gen).tolist(),
    }
    log_fn(
        f"{cfg.name}: prefill {prompt_len} tok in {t_prefill:.2f}s; "
        f"generated {gen_tokens} tok/seq × {batch} seqs in {t_gen:.2f}s "
        f"({result['tokens_per_second']:.1f} tok/s)"
    )
    return result


def main(argv=None):
    enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--arch", default="stablelm-1.6b")
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--full", dest="smoke", action="store_false")
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--window", type=int, default=0,
                    help="sliding-window span (0 = full attention)")
    ap.add_argument("--use-kernel", action="store_true",
                    help="Pallas flash-decode kernel (interpret mode on CPU)")
    ap.add_argument("--seed", type=int, default=0)
    # oracle mode
    ap.add_argument("--batch", type=int, default=4,
                    help="[oracle] fixed lockstep batch size")
    # continuous mode
    ap.add_argument("--continuous", action="store_true",
                    help="continuous-batching engine instead of the oracle")
    ap.add_argument("--slots", type=int, default=4,
                    help="[continuous] KV-cache slot pool size")
    ap.add_argument("--requests", type=int, default=8,
                    help="[continuous] number of queued requests")
    ap.add_argument("--prefill", choices=("chunked", "interleaved"),
                    default="chunked", help="[continuous] prompt admission mode")
    ap.add_argument("--no-batch-prefill", dest="batch_prefill",
                    action="store_false",
                    help="[continuous] one prefill dispatch per request "
                    "instead of one per admission round")
    ap.add_argument("--no-bucket-prefill", dest="bucket_prefill",
                    action="store_false",
                    help="[continuous] disable shape-bucketed admission "
                    "rounds (compile one prefill per distinct round shape)")
    ap.add_argument("--no-paged-decode", dest="paged_decode",
                    action="store_false",
                    help="[continuous] with --use-kernel, use the unpaged "
                    "flash-decode kernel (full-ring attention per slot)")
    ap.add_argument("--no-donate-cache", dest="donate_cache",
                    action="store_false",
                    help="[continuous] functionally copy the KV cache "
                    "through each step instead of donating it in place")
    ap.add_argument("--no-paged-cache", dest="paged_cache",
                    action="store_false",
                    help="[continuous] per-slot contiguous ring KV caches "
                    "instead of the shared paged pool + page tables "
                    "(restores the prompt+gen <= max_seq admission guard)")
    ap.add_argument("--page-size", type=int, default=16,
                    help="[continuous] tokens per physical KV page "
                    "(paged cache)")
    ap.add_argument("--num-pages", type=int, default=0,
                    help="[continuous] total physical pages incl. the "
                    "reserved scratch page (0 = ring-equivalent capacity); "
                    "undersize to oversubscribe memory — decode OOM "
                    "preempts the youngest slot")
    ap.add_argument("--watermark-pages", type=int, default=0,
                    help="[continuous] free pages admission must leave in "
                    "reserve while other slots are live (paged cache; "
                    "0 = pack the pool and rely on preemption)")
    ap.add_argument("--long-requests", action="store_true",
                    help="[continuous] give every slot whole-pool logical "
                    "width (table entries for all allocatable pages) "
                    "instead of the ring-equivalent default — serves "
                    "requests longer than num_slots would split, at "
                    "num_slots× the per-step jnp gather cost")
    # default=None distinguishes "user explicitly asked" (--prefix-cache,
    # validated below — an impossible config is an error, not a silent
    # no-op) from the advertised default-on (None → enabled when the
    # config supports it, with the engine logging why when it can't)
    ap.add_argument("--no-prefix-cache", dest="prefix_cache",
                    action="store_false", default=None,
                    help="[continuous] disable shared-prefix KV reuse "
                    "(paged cache): every request prefills its full "
                    "prompt instead of mapping cached prefix pages and "
                    "prefilling only the uncached suffix")
    ap.add_argument("--prefix-cache", dest="prefix_cache",
                    action="store_true",
                    help="[continuous] require shared-prefix KV reuse "
                    "(default on with the paged cache when the config "
                    "supports it; explicit use errors out on a config "
                    "that can never honor it)")
    ap.add_argument("--prefix-cache-pages", type=int, default=0,
                    help="[continuous] cap on pool pages the prefix index "
                    "may pin (0 = the pool's allocatable capacity); "
                    "entries are LRU-evicted under pool pressure")
    ap.add_argument("--kv-dtype", choices=("fp", "int8"), default="fp",
                    help="[continuous] KV pool storage dtype (paged cache): "
                    "int8 stores pages quantized with per-token-slot per-"
                    "kv-head fp32 scales and dequantizes inside the attend "
                    "— ~4x the resident sequences per HBM byte vs fp32 "
                    "pools at near-identical output quality")
    ap.add_argument("--host-pages", type=int, default=0,
                    help="[continuous] host-RAM page budget for the tiered "
                    "KV cache (paged cache; 0 = off): preempted slots swap "
                    "their pages to host and restore with one copy instead "
                    "of recomputing, and LRU-evicted prefix pages demote/"
                    "promote through the same tier")
    ap.add_argument("--no-swap", dest="swap", action="store_false",
                    help="[continuous] with --host-pages, keep prefix "
                    "demote/promote but resume preemptions by recompute "
                    "instead of swap-in")
    ap.add_argument("--draft", default=None, metavar="ARCH",
                    help="[continuous] speculative decoding: config name of "
                    "the cheap DRAFT model that proposes --spec-tokens "
                    "lookahead tokens per slot per round, verified by the "
                    "target in one batched dispatch; greedy output stays "
                    "bitwise identical to the non-speculative engine")
    ap.add_argument("--spec-tokens", type=int, default=0,
                    help="[continuous] draft lookahead depth k per round "
                    "(requires --draft; an accepted round emits up to k+1 "
                    "tokens for one target dispatch)")
    ap.add_argument("--stagger", type=float, default=0.0,
                    help="[continuous] inter-arrival spacing in seconds")
    ap.add_argument("--replicas", type=int, default=1,
                    help="[continuous] serve through a fault-tolerant "
                    "router over this many engine replicas (prefix-"
                    "affinity + occupancy routing, token-exact failover); "
                    "1 = single engine, no router")
    ap.add_argument("--fault", action="append", default=None,
                    metavar="KIND:R@S",
                    help="[router] inject a fault: kill:R@S / stall:R@S / "
                    "slow:R@S@SEC (replica R at its own step S); "
                    "repeatable — specs compose one FaultPlan")
    ap.add_argument("--max-wall-s", type=float, default=0.0,
                    help="[continuous] per-request wall-clock watchdog: "
                    "retire a slot that exceeds this with a structured "
                    "timeout result (0 = off)")
    ap.add_argument("--mesh", type=int, default=0,
                    help="[continuous] serve tensor-parallel over this many "
                    "model-axis shards (0 = single device); n_heads and "
                    "n_kv_heads must divide by it; output is bitwise "
                    "token-identical to the unsharded engine")
    ap.add_argument("--num-devices", type=int, default=0,
                    help="force this many host platform devices "
                    "(--xla_force_host_platform_device_count, applied "
                    "before jax initializes — CPU mesh simulation)")
    # sampling (0 temperature = greedy; per-request streams derive from
    # --seed + uid so every request samples independently)
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="[continuous] sampling temperature (0 = greedy)")
    ap.add_argument("--top-k", type=int, default=0,
                    help="[continuous] keep the k most likely tokens (0 = off)")
    ap.add_argument("--top-p", type=float, default=1.0,
                    help="[continuous] nucleus sampling mass (1.0 = off)")
    args = ap.parse_args(argv)
    if args.mesh > 0:
        if not args.continuous:
            ap.error("--mesh requires --continuous (tensor-parallel serving "
                     "is an engine path)")
        if len(jax.devices()) < args.mesh:
            ap.error(
                f"--mesh {args.mesh} needs {args.mesh} devices, found "
                f"{len(jax.devices())}; pass --num-devices {args.mesh} "
                "(CPU host-device override) or run on a larger host"
            )
    if args.replicas > 1 and not args.continuous:
        ap.error("--replicas requires --continuous (the router fronts "
                 "continuous-batching engine replicas)")
    if args.replicas > 1 and args.mesh > 0:
        ap.error("--replicas with --mesh is not supported yet: the router "
                 "builds single-device replicas (data-parallel across "
                 "replicas, not tensor-parallel within one)")
    if args.fault and args.replicas <= 1:
        ap.error("--fault requires --replicas > 1 (fault injection is a "
                 "router harness; a single engine has nowhere to fail "
                 "over to)")
    if args.temperature <= 0 and (args.top_k > 0 or args.top_p < 1.0):
        ap.error("--top-k/--top-p require --temperature > 0 "
                 "(temperature 0 is greedy decoding)")
    if args.temperature > 0 and not args.continuous:
        ap.error("sampling flags require --continuous "
                 "(the serve_batch oracle is greedy by construction)")
    if args.prefix_cache:  # explicit --prefix-cache: fail fast, not silent
        blockers = []
        if not args.continuous:
            blockers.append("batch mode (use --continuous)")
        if not args.paged_cache:
            blockers.append(
                "--no-paged-cache (prefix sharing rides the page table)"
            )
        if args.window > 0:
            blockers.append(
                f"--window {args.window} (sliding-window ring wraps; "
                "prefix pages would be overwritten)"
            )
        if args.prefill == "interleaved":
            blockers.append(
                "--prefill interleaved (suffix rounds need chunked "
                "batched admission)"
            )
        if blockers:
            ap.error(
                "--prefix-cache cannot be honored by this config: "
                + "; ".join(blockers)
            )
    # same fail-fast contract as --prefix-cache: a flag the engine would
    # have to silently ignore is a config error, not a degraded run
    if args.kv_dtype != "fp":
        blockers = []
        if not args.continuous:
            blockers.append("batch mode (use --continuous)")
        if not args.paged_cache:
            blockers.append(
                "--no-paged-cache (int8 KV quantizes POOL pages; the "
                "contiguous ring cache stays fp)"
            )
        if args.replicas > 1:
            blockers.append(
                "--replicas (router replicas build fp pools; int8 "
                "replica pools are not wired yet)"
            )
        if blockers:
            ap.error(
                f"--kv-dtype {args.kv_dtype} cannot be honored by this "
                "config: " + "; ".join(blockers)
            )
    if args.host_pages > 0:
        blockers = []
        if not args.continuous:
            blockers.append("batch mode (use --continuous)")
        if not args.paged_cache:
            blockers.append(
                "--no-paged-cache (the host tier backs the page pool)"
            )
        if args.mesh > 0:
            blockers.append(
                f"--mesh {args.mesh} (KV pool is sharded; the host tier "
                "assumes a single-device pool)"
            )
        if args.replicas > 1:
            blockers.append(
                "--replicas (router replicas manage their own pools; "
                "per-replica host tiers are not wired yet)"
            )
        if blockers:
            ap.error(
                f"--host-pages {args.host_pages} cannot be honored by "
                "this config: " + "; ".join(blockers)
            )
    if args.draft is not None or args.spec_tokens > 0:
        blockers = []
        if args.draft is None:
            blockers.append("--spec-tokens without --draft (the lookahead "
                            "depth needs a draft model to propose it)")
        if args.spec_tokens <= 0:
            blockers.append("--draft without --spec-tokens >= 1 (a draft "
                            "with no lookahead depth proposes nothing)")
        if not args.continuous:
            blockers.append("batch mode (use --continuous)")
        if not args.paged_cache:
            blockers.append(
                "--no-paged-cache (k-token verify rides the suffix-"
                "prefill path over the page table)"
            )
        if args.prefill == "interleaved":
            blockers.append(
                "--prefill interleaved (the verify dispatch needs chunked "
                "batched admission)"
            )
        if args.window > 0:
            blockers.append(
                f"--window {args.window} (verify positions assume the "
                "full-context page layout)"
            )
        if args.mesh > 0:
            blockers.append(
                f"--mesh {args.mesh} (the draft runs single-device; "
                "sharded verify is not wired yet)"
            )
        if args.replicas > 1:
            blockers.append(
                "--replicas (router replicas do not build draft models yet)"
            )
        if blockers:
            ap.error(
                "speculative decoding cannot be honored by this config: "
                + "; ".join(blockers)
            )
    if args.continuous:
        from repro.launch.engine import serve_continuous
        from repro.launch.sampling import SamplingParams

        sampling = None
        if args.temperature > 0:
            sampling = SamplingParams(
                temperature=args.temperature, top_k=args.top_k,
                top_p=args.top_p, seed=args.seed,
            )
        if args.replicas > 1:
            from repro.launch.router import (
                parse_fault_spec, serve_router_continuous,
            )

            return serve_router_continuous(
                args.arch, smoke=args.smoke, replicas=args.replicas,
                num_slots=args.slots, n_requests=args.requests,
                prompt_len=args.prompt_len, gen_tokens=args.gen,
                window=args.window, use_kernel=args.use_kernel,
                paged_cache=args.paged_cache, page_size=args.page_size,
                num_pages=args.num_pages,
                watermark_pages=args.watermark_pages,
                prefix_cache=args.prefix_cache is not False,
                sampling=sampling,
                fault_plan=(
                    parse_fault_spec(args.fault) if args.fault else None
                ),
                seed=args.seed, stagger=args.stagger,
                max_wall_s=args.max_wall_s,
            )
        return serve_continuous(
            args.arch, smoke=args.smoke, num_slots=args.slots,
            n_requests=args.requests, prompt_len=args.prompt_len,
            gen_tokens=args.gen, window=args.window,
            use_kernel=args.use_kernel, prefill=args.prefill,
            batch_prefill=args.batch_prefill,
            bucket_prefill=args.bucket_prefill,
            paged_decode=args.paged_decode,
            donate_cache=args.donate_cache,
            paged_cache=args.paged_cache,
            page_size=args.page_size,
            num_pages=args.num_pages,
            long_requests=args.long_requests,
            watermark_pages=args.watermark_pages,
            prefix_cache=args.prefix_cache is not False,  # None = default on
            prefix_cache_pages=args.prefix_cache_pages,
            kv_dtype=args.kv_dtype,
            host_pages=args.host_pages,
            swap=args.swap,
            num_shards=args.mesh,
            draft=args.draft,
            spec_tokens=args.spec_tokens,
            sampling=sampling,
            seed=args.seed, stagger=args.stagger,
            max_wall_s=args.max_wall_s,
        )
    return serve_batch(
        args.arch, smoke=args.smoke, batch=args.batch,
        prompt_len=args.prompt_len, gen_tokens=args.gen,
        window=args.window, use_kernel=args.use_kernel, seed=args.seed,
    )


if __name__ == "__main__":
    main()
