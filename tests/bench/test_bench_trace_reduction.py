"""The reduction from a trace to numbers, on a small hand-made trace whose
answers can be worked out by hand, and the benchmark's operation counts
against the compiled programs' counts (``utils/hlo.py``) at a small size."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import flops
from bench import trace as tracing

# one chip, window [0, 10] s:
#   ops  matmul 1-3, _paged_kernel 2-4 (overlaps), all-reduce 6-7.5,
#        fusion 7-8 (hides half of the all-reduce), matmul 9-11 (clipped);
#        a layer loop (while) 1-8 that spans them and is not work itself
#   host bench.step 0.5-5.5, inside it engine.decode 1-2; bench.idle 5.5-9
TRACE = {
    "ops": [[
        ("while.5 (s32[]", 1.0, 8.0),
        ("matmul", 1.0, 3.0),
        ("_paged_kernel.1", 2.0, 4.0),
        ("all-reduce.3", 6.0, 7.5),
        ("fusion.2 bf16[8]", 7.0, 8.0),
        ("copy.7 bf16[8]", 4.5, 4.6),
        ("fusion.9 bf16[8]", 4.6, 4.7),
        ("matmul", 9.0, 11.0),
    ]],
    "modules": [[("jit__decode_fn", 1.0, 4.0), ("jit__decode_fn", 9.0, 11.0)]],
    "host": [
        (tracing.WINDOW_SPAN, 0.0, 10.0),
        ("bench.step", 0.5, 5.5),
        ("engine.decode", 1.0, 2.0),
        ("bench.idle", 5.5, 9.0),
    ],
    "window": (0.0, 10.0),
}


def test_busy_union_and_idle_share():
    # busy: [1,4] + [4.5,4.7] + [6,8] + [9,10] = 6.2 s of 10
    assert tracing.busy_mean(TRACE) == pytest.approx(6.2)
    assert tracing.idle_share(TRACE) == pytest.approx(0.38)
    assert tracing.union([("a", 1.0, 2.0), ("b", 1.5, 3.0), ("c", 4.0, 5.0)]) == [
        (1.0, 3.0), (4.0, 5.0)]


def test_per_kernel_and_program_time():
    assert tracing.op_seconds(TRACE, "_paged_kernel") == (pytest.approx(2.0), 1)
    # an op is matched by its instruction's name, not by its operands
    tr = {**TRACE, "ops": [[("fusion.3 bf16[4]", 0.0, 1.0)]]}
    assert tracing.op_name("%fusion.3 = bf16[4]{0} fusion(%paged_decode.1)") == "fusion.3 bf16[4]"
    assert tracing.op_seconds(tr, "paged_decode") == (0, 0)
    secs, n = tracing.module_seconds(TRACE, "_decode_fn")
    assert (secs, n) == (pytest.approx(4.0), 2)    # second one clipped to 1 s


def test_exposed_collective():
    # all-reduce 6-7.5, of which 7-7.5 overlaps the fusion: 1 s exposed
    assert tracing.exposed_collective(TRACE["ops"][0], TRACE["window"]) == pytest.approx(1.0)


def test_gaps_labelled_by_innermost_host_span():
    gaps = tracing.idle_gaps(TRACE)
    # gaps [0,1] (mid 0.5: bench.step opens at 0.5), [4,4.5] and [4.7,6]
    # (bench.step), [8,9] (bench.idle)
    assert [(k, round(v, 6)) for k, v in gaps] == [
        ("bench.step", 1.0), ("bench.step", 0.5), ("bench.step", 1.3),
        ("bench.idle", 1.0)]
    top = tracing.breakdown(TRACE)
    assert top["idle_gaps"][0] == ["bench.step", pytest.approx(1.3)]
    assert top["device_ops"][0] == ["matmul", pytest.approx(3.0)]
    assert not any(name.startswith("while") for name, _ in top["device_ops"])


def test_reads_a_recorded_profiler_trace(tmp_path):
    """The capture and ``load`` find the benchmark's host spans and window
    in a real profiler trace (CPU: no device plane to read)."""
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    tracing.start(str(tmp_path))
    with jax.profiler.TraceAnnotation(tracing.WINDOW_SPAN):
        with jax.profiler.TraceAnnotation("bench.step"):
            f(x).block_until_ready()
    tracing.stop()
    tr = tracing.load(str(tmp_path))
    names = {h[0] for h in tr["host"]}
    assert {tracing.WINDOW_SPAN, "bench.step"} <= names
    lo, hi = tr["window"]
    step = [h for h in tr["host"] if h[0] == "bench.step"][0]
    assert lo <= step[1] <= step[2] <= hi


SMALL = {"hidden_size": 64, "intermediate_size": 160, "num_hidden_layers": 2,
         "num_attention_heads": 4, "num_key_value_heads": 4, "vocab_size": 256,
         "torch_dtype": "bfloat16"}


def _hlo_flops(fn, *args):
    from repro.utils.hlo import analyze

    return analyze(jax.jit(fn).lower(*args).compile().as_text()).flops


def test_paged_decode_flops_match_compiled_reference():
    """All rows full: the jnp oracle computes exactly what is needed."""
    from repro.kernels.ref import paged_decode_ref

    b, c, h, hd = 3, 48, 4, 16
    q = jnp.ones((b, h, 1, hd), jnp.bfloat16)
    kv = jnp.ones((b, c, h, hd), jnp.bfloat16)
    got = _hlo_flops(lambda q, k, v: paged_decode_ref(q, k, v, c - 1, 0), q, kv, kv)
    cfg = {**SMALL, "num_hidden_layers": 1, "hidden_size": h * hd,
           "num_attention_heads": h, "num_key_value_heads": h}
    want, byt = flops.paged_decode(cfg, [c] * b, page=16)
    assert want == got
    # bytes: every live key and value once, the query and the output
    assert byt == 2 * kv.nbytes + 2 * q.nbytes


def test_suffix_prefill_flops_match_compiled_reference():
    """The oracle scores every (query, key) pair; what the kernel needs is
    the causal part. The difference is exactly the masked triangle."""
    from repro.kernels.ref import suffix_prefill_ref

    n, s, h, hd, page, w = 2, 8, 4, 16, 16, 3
    start = w * page
    q = jnp.ones((n, s, h, 1, hd), jnp.bfloat16)
    ks = jnp.ones((n, s, h, hd), jnp.bfloat16)
    pool = jnp.ones((n * w + 1, page, h, hd), jnp.bfloat16)
    table = jnp.arange(1, n * w + 1, dtype=jnp.int32).reshape(n, w)
    starts = jnp.full((n,), start, jnp.int32)
    got = _hlo_flops(
        lambda q, k, v, pk, pv: suffix_prefill_ref(q, k, v, pk, pv, table, starts),
        q, ks, ks, pool, pool)
    cfg = {**SMALL, "num_hidden_layers": 1, "hidden_size": h * hd,
           "num_attention_heads": h, "num_key_value_heads": h}
    want, byt = flops.suffix_prefill(cfg, [(start, s)] * n, page)
    triangle = n * 4 * h * hd * s * (s - 1) // 2
    assert got - want == triangle
    kv_tok = 2 * h * hd * 2
    assert byt == n * (w * page * kv_tok + s * (kv_tok + 2 * h * hd * 2))


def test_decode_step_flops_match_compiled_program():
    """One jnp decode step over a full paged cache: projections, output
    head and attention over every cached key, row by row."""
    from bench import adapters
    from repro.models import build_model

    cfg = {**SMALL, "program_arch": "mistral-nemo-12b", "rope_theta": 1e6,
           "rms_norm_eps": 1e-5, "tie_word_embeddings": False,
           "hidden_act": "silu", "num_key_value_heads": 2, "head_dim": 32}
    model = build_model(adapters.program_config(cfg))
    params = model.init(jax.random.PRNGKey(0))
    slots, page, width = 2, 16, 4
    cache = model.init_paged_cache(params, slots, slots * width + 1, page, width)
    cache["table"] = jnp.arange(1, slots * width + 1, dtype=jnp.int32).reshape(slots, width)
    cache["pos"] = jnp.full((slots,), width * page - 1, jnp.int32)
    tok = jnp.zeros((slots, 1), jnp.int32)
    got = _hlo_flops(lambda p, c, t: model.decode(p, c, t)[1], params, cache, tok)
    want = slots * flops.decode_token_flops(cfg, width * page)
    assert got == want


def test_roofline_picks_the_binding_side():
    peaks = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert flops.roofline_seconds(1000.0, 50.0, peaks) == (10.0, "compute")
    assert flops.roofline_seconds(100.0, 50.0, peaks) == (5.0, "memory")
    np.testing.assert_allclose(flops.prefill_flops(SMALL, 0, 1),
                               flops.decode_token_flops(SMALL, 1))
