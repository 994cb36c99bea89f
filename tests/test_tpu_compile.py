"""The serving attention kernels compile natively for a TPU v5e.

Interpret mode (the rest of the suite) accepts block shapes the TPU
lowering refuses, so these tests compile every serving kernel with
``interpret=False`` for one device of a DESCRIBED ``v5e:2x2`` topology, at
stablelm-1.6b widths (32 kv heads of head_dim 64, bf16, page 16), and the
paged decode also at Mistral-NeMo's (8 kv heads of 128). Nothing
runs: a compile that passes here proves the Mosaic lowering and the
compiler accept the kernel, and each test checks the kernel is really in
the program (``tpu_custom_call``) under its stable ``pallas_call`` name,
the name a profiler trace gives the kernel's op.

The topology is described inside a module-scoped fixture (never at import:
only one process at a time may load the TPU library, and test workers
import every test file), and the persistent compilation cache is off
around these compiles — an entry written for a described chip cannot be
read back on a host without one."""
import functools
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.flash_prefill import flash_prefill
from repro.kernels.flash_suffix_prefill import suffix_prefill
from repro.kernels.paged_decode import paged_decode, walks_pages
from repro.kernels.quantize import int8_encode, int8_roundtrip
from repro.kernels.swa_decode import swa_decode

CFG = get_config("stablelm-1.6b")
HKV = CFG.n_kv_heads
G = CFG.n_heads // CFG.n_kv_heads
HD = CFG.resolved_head_dim
PAGE = 16
N_PAGES = 1025            # 16k-token pool + scratch page 0
SLOTS = 4
TABLE_W = 64              # 1k logical tokens per slot
PROMPT = 1024
SUFFIX = 256


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        jax.config.update("jax_enable_compilation_cache", prev)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _shape(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compiled_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


def _kernel_names(text: str) -> set[str]:
    """Instruction names of the program's Pallas custom calls, their
    ``.N`` suffix dropped."""
    return {
        re.match(r"\s*(?:ROOT )?%([\w-]+?)(?:\.\d+)? = ", line).group(1)
        for line in text.splitlines()
        if 'custom_call_target="tpu_custom_call"' in line
    }


def _decode_args(sh, kv_dtype):
    q = _shape(sh, (SLOTS, HKV, G, HD), CFG.dtype)
    pool = _shape(sh, (N_PAGES, PAGE, HKV, HD), kv_dtype)
    pos = _shape(sh, (SLOTS,), jnp.int32)
    table = _shape(sh, (SLOTS, TABLE_W), jnp.int32)
    scale = _shape(sh, (N_PAGES, PAGE, HKV), jnp.float32)
    return q, pool, pos, table, scale


def _suffix_args(sh, kv_dtype):
    q = _shape(sh, (SLOTS, SUFFIX, HKV, G, HD), CFG.dtype)
    kv = _shape(sh, (SLOTS, SUFFIX, HKV, HD), CFG.dtype)
    pool = _shape(sh, (N_PAGES, PAGE, HKV, HD), kv_dtype)
    table = _shape(sh, (SLOTS, TABLE_W), jnp.int32)
    starts = _shape(sh, (SLOTS,), jnp.int32)
    scale = _shape(sh, (N_PAGES, PAGE, HKV), jnp.float32)
    return q, kv, pool, table, starts, scale


def test_paged_decode_table_fp(one_chip):
    q, pool, pos, table, _ = _decode_args(one_chip, CFG.dtype)
    fn = functools.partial(paged_decode, interpret=False)
    text = _compiled_text(
        lambda q, k, v, p, t: fn(q, k, v, p, table=t), q, pool, pool, pos, table
    )
    assert "tpu_custom_call" in text
    assert _kernel_names(text) == {"paged_decode"}


def test_paged_decode_table_int8(one_chip):
    q, pool, pos, table, scale = _decode_args(one_chip, jnp.int8)
    fn = functools.partial(paged_decode, interpret=False)
    text = _compiled_text(
        lambda q, k, v, p, t, ks, vs: fn(
            q, k, v, p, table=t, k_scale=ks, v_scale=vs
        ),
        q, pool, pool, pos, table, scale, scale,
    )
    assert "tpu_custom_call" in text
    assert _kernel_names(text) == {"paged_decode"}


# Mistral-NeMo's decode shapes, the benchmark's serving cell: 32 slots,
# 8 kv heads of 128 (G 4), page 16, a 256-page table over 2,753 pages.
# A lane-aligned head_dim takes the page walk (grid (B,), page DMAs).
NEMO_SLOTS, NEMO_HKV, NEMO_G, NEMO_HD = 32, 8, 4, 128
NEMO_TABLE_W, NEMO_PAGES = 256, 2753


@pytest.mark.parametrize("kv_dtype", [jnp.bfloat16, jnp.int8], ids=["fp", "int8"])
def test_paged_decode_table_nemo(one_chip, kv_dtype):
    quant = kv_dtype == jnp.int8
    assert walks_pages(PAGE, NEMO_HKV, NEMO_HD, quant)
    q = _shape(one_chip, (NEMO_SLOTS, NEMO_HKV, NEMO_G, NEMO_HD), jnp.bfloat16)
    pool = _shape(one_chip, (NEMO_PAGES, PAGE, NEMO_HKV, NEMO_HD), kv_dtype)
    pos = _shape(one_chip, (NEMO_SLOTS,), jnp.int32)
    table = _shape(one_chip, (NEMO_SLOTS, NEMO_TABLE_W), jnp.int32)
    scale = _shape(one_chip, (NEMO_PAGES, PAGE, NEMO_HKV), jnp.float32)
    fn = functools.partial(paged_decode, interpret=False)
    if quant:
        text = _compiled_text(
            lambda q, k, v, p, t, ks, vs: fn(
                q, k, v, p, table=t, k_scale=ks, v_scale=vs
            ),
            q, pool, pool, pos, table, scale, scale,
        )
    else:
        text = _compiled_text(
            lambda q, k, v, p, t: fn(q, k, v, p, table=t),
            q, pool, pool, pos, table,
        )
    assert "tpu_custom_call" in text
    assert _kernel_names(text) == {"paged_decode"}


def test_paged_decode_ring(one_chip):
    q = _shape(one_chip, (SLOTS, HKV, G, HD), CFG.dtype)
    ring = _shape(one_chip, (SLOTS, PROMPT, HKV, HD), CFG.dtype)
    pos = _shape(one_chip, (SLOTS,), jnp.int32)
    text = _compiled_text(
        functools.partial(paged_decode, interpret=False), q, ring, ring, pos
    )
    assert "tpu_custom_call" in text
    assert _kernel_names(text) == {"paged_decode"}


def test_swa_decode(one_chip):
    q = _shape(one_chip, (SLOTS, HKV, G, HD), CFG.dtype)
    ring = _shape(one_chip, (SLOTS, PROMPT, HKV, HD), CFG.dtype)
    pos = _shape(one_chip, (SLOTS,), jnp.int32)
    text = _compiled_text(
        functools.partial(swa_decode, interpret=False, window=256),
        q, ring, ring, pos,
    )
    assert "tpu_custom_call" in text
    assert _kernel_names(text) == {"swa_decode"}


def test_flash_prefill(one_chip):
    q = _shape(one_chip, (1, PROMPT, HKV, G, HD), CFG.dtype)
    kv = _shape(one_chip, (1, PROMPT, HKV, HD), CFG.dtype)
    text = _compiled_text(
        functools.partial(flash_prefill, interpret=False), q, kv, kv
    )
    assert "tpu_custom_call" in text
    assert _kernel_names(text) == {"flash_prefill"}


def test_suffix_prefill_fp(one_chip):
    q, kv, pool, table, starts, _ = _suffix_args(one_chip, CFG.dtype)
    fn = functools.partial(suffix_prefill, prefix_width=32, interpret=False)
    text = _compiled_text(fn, q, kv, kv, pool, pool, table, starts)
    assert "tpu_custom_call" in text
    assert _kernel_names(text) == {"suffix_prefill"}


def test_suffix_prefill_int8(one_chip):
    q, kv, pool, table, starts, scale = _suffix_args(one_chip, jnp.int8)
    fn = functools.partial(suffix_prefill, prefix_width=32, interpret=False)
    text = _compiled_text(
        lambda *a: fn(*a[:7], pool_k_scale=a[7], pool_v_scale=a[8]),
        q, kv, kv, pool, pool, table, starts, scale, scale,
    )
    assert "tpu_custom_call" in text
    assert _kernel_names(text) == {"suffix_prefill"}


@pytest.mark.parametrize("fn, name", [
    (int8_encode, "int8_encode"), (int8_roundtrip, "int8_roundtrip"),
])
def test_int8_wire_kernels(one_chip, fn, name):
    """The federated int8 wire codec: 4,096 blocks of 256 values."""
    x = _shape(one_chip, (4096, 256), jnp.float32)
    text = _compiled_text(functools.partial(fn, interpret=False), x)
    assert "tpu_custom_call" in text
    assert _kernel_names(text) == {name}
