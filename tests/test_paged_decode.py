"""Paged length-aware decode kernel (kernels/paged_decode.py) validation.

The contract has two layers, both pinned here:

* BITWISE: the paged kernel skips only pages whose every slot is invalid
  under the ring mask, and a fully-masked chunk contributes exactly zero to
  the online-softmax state — so paged output == unpaged ``swa_decode``
  output bit for bit, across no-wrap / exact-fit / wrap / multi-wrap,
  sliding-window and full attention, scalar and per-slot positions. The jnp
  paged oracle (``ref.paged_decode_ref``) is likewise bitwise equal to the
  plain oracle (``ref.swa_decode_ref``) — its extra live-span mask is a
  subset of the slots the ring mask already kills.
* NUMERIC: paged kernel vs. the jnp oracle within flash-attention
  tolerance (online softmax reassociates the reduction).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from tests._hypothesis_compat import given, settings, st

from repro.kernels import ops, ref
from repro.kernels.paged_decode import paged_decode, pages_per_block, walks_pages
from repro.kernels.quantize import kv_quant
from repro.kernels.swa_decode import swa_decode

# (cap, positions, window) covering every ring regime in one batch:
# no-wrap (pos+1 < cap), exact-fit (pos+1 == cap), wrap (cap <= pos < 2cap),
# multi-wrap (pos >= 2cap), and the first token (pos == 0). Caps <= 512 are
# SINGLE-page (auto page == cap): they pin the degenerate grid. The
# cap-1024 entries split into 2 auto pages, so rows with pos < 512 really
# take the skip path (index-map clamp + pl.when gate + the pages >= 1
# clip at pos == 0) — without them no bitwise pin would ever execute a
# skipped page.
CASES = [
    (256, [0, 10, 255, 300, 1000], 0),     # full attention
    (256, [0, 10, 255, 300, 1000], 64),    # sliding window < cap
    (512, [3, 511, 512, 700, 1537], 128),  # window, incl. exact-fit + wraps
    (128, [0, 64, 127, 128, 900], 128),    # window == cap (engine layout)
    (1024, [0, 10, 511, 512, 1023, 1024, 2500], 0),   # multi-page skipping
    (1024, [0, 10, 511, 512, 1023, 1024, 2500], 256),  # … with a window
]


def _rand(key, cap, n, hkv=2, g=4, hd=64, dtype=jnp.float32):
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (n, hkv, g, hd), dtype)
    kc = jax.random.normal(ks[1], (n, cap, hkv, hd), dtype)
    vc = jax.random.normal(ks[2], (n, cap, hkv, hd), dtype)
    return q, kc, vc


class TestPagedBitwise:
    @pytest.mark.parametrize("cap,poss,window", CASES)
    def test_kernel_bitwise_matches_unpaged_kernel(self, cap, poss, window):
        """Page skipping must be invisible: same bits as full-ring streaming."""
        q, kc, vc = _rand(jax.random.PRNGKey(cap + window), cap, len(poss))
        pos = jnp.asarray(poss, jnp.int32)
        paged = paged_decode(q, kc, vc, pos, window)
        unpaged = swa_decode(q, kc, vc, pos, window)
        np.testing.assert_array_equal(np.asarray(paged), np.asarray(unpaged))

    @pytest.mark.parametrize("cap,poss,window", CASES)
    def test_ref_bitwise_matches_plain_ref(self, cap, poss, window):
        """The jnp paged oracle's live-span mask changes nothing."""
        q, kc, vc = _rand(jax.random.PRNGKey(7 * cap + window), cap, len(poss))
        pos = jnp.asarray(poss, jnp.int32)
        a = ref.paged_decode_ref(q, kc, vc, pos, window)
        b = ref.swa_decode_ref(q, kc, vc, pos, window)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_scalar_pos_broadcasts(self):
        """Lockstep batches (scalar pos) take the same paged path."""
        cap = 128
        q, kc, vc = _rand(jax.random.PRNGKey(3), cap, 3)
        for pos in (0, 40, 127, 128, 500):
            paged = paged_decode(q, kc, vc, jnp.asarray(pos), 0)
            unpaged = swa_decode(q, kc, vc, jnp.asarray(pos), 0)
            np.testing.assert_array_equal(np.asarray(paged), np.asarray(unpaged))


class TestPagedVsOracle:
    @pytest.mark.parametrize("cap,poss,window", CASES)
    def test_kernel_close_to_ref(self, cap, poss, window):
        q, kc, vc = _rand(jax.random.PRNGKey(13 * cap + window), cap, len(poss))
        pos = jnp.asarray(poss, jnp.int32)
        out = paged_decode(q, kc, vc, pos, window)
        expected = ref.swa_decode_ref(q, kc, vc, pos, window)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(expected), rtol=3e-5, atol=3e-5
        )

    def test_bf16(self):
        cap = 128
        q, kc, vc = _rand(jax.random.PRNGKey(9), cap, 2, dtype=jnp.bfloat16)
        pos = jnp.asarray([17, 400], jnp.int32)
        out = paged_decode(q, kc, vc, pos, 64)
        unpaged = swa_decode(q, kc, vc, pos, 64)
        np.testing.assert_array_equal(
            np.asarray(out, np.float32), np.asarray(unpaged, np.float32)
        )

    def test_explicit_page_size(self):
        """A non-default page size partitions differently but values match —
        chunk boundaries never change which slots are valid. page=64 over a
        256-ring is 4 pages, so the row at pos=30 skips three of them."""
        cap = 256
        q, kc, vc = _rand(jax.random.PRNGKey(21), cap, 2)
        pos = jnp.asarray([30, 700], jnp.int32)
        a = paged_decode(q, kc, vc, pos, 0, page=64)
        b = paged_decode(q, kc, vc, pos, 0, page=256)
        ora = ref.swa_decode_ref(q, kc, vc, pos, 0)
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(ora), rtol=3e-5, atol=3e-5
        )
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=3e-5, atol=3e-5
        )

    def test_skipped_pages_bitwise_per_depth(self):
        """Direct pin on the skip machinery: at cap 1024 (2 auto pages), a
        batch whose rows live in 1 vs 2 pages must equal, bit for bit, the
        unpaged kernel AND solo single-row runs of themselves (page counts
        of OTHER rows can't leak across rows)."""
        cap = 1024
        q, kc, vc = _rand(jax.random.PRNGKey(33), cap, 4)
        pos = jnp.asarray([7, 500, 600, 1500], jnp.int32)  # 1,1,2,2 pages
        batched = paged_decode(q, kc, vc, pos, 0)
        unpaged = swa_decode(q, kc, vc, pos, 0)
        np.testing.assert_array_equal(np.asarray(batched), np.asarray(unpaged))
        for r in range(4):
            solo = paged_decode(
                q[r : r + 1], kc[r : r + 1], vc[r : r + 1], pos[r : r + 1], 0
            )
            np.testing.assert_array_equal(
                np.asarray(solo[0]), np.asarray(batched[r])
            )

    @given(pos=st.integers(0, 2000), window=st.sampled_from([0, 32, 128]))
    @settings(max_examples=20, deadline=None)
    def test_property_ring_positions(self, pos, window):
        """Paged kernel == unpaged kernel for arbitrary ring positions."""
        key = jax.random.PRNGKey(pos + 31 * window)
        q = jax.random.normal(key, (1, 1, 2, 64))
        kc = jax.random.normal(jax.random.fold_in(key, 1), (1, 128, 1, 64))
        vc = jax.random.normal(jax.random.fold_in(key, 2), (1, 128, 1, 64))
        paged = paged_decode(q, kc, vc, jnp.asarray(pos), window)
        unpaged = swa_decode(q, kc, vc, jnp.asarray(pos), window)
        np.testing.assert_array_equal(np.asarray(paged), np.asarray(unpaged))


class TestOpsRouting:
    def test_paged_flag_routes_kernel_and_ref(self):
        cap = 128
        q, kc, vc = _rand(jax.random.PRNGKey(5), cap, 2)
        pos = jnp.asarray([9, 300], jnp.int32)
        k_paged = ops.swa_decode_attention(
            q, kc, vc, pos, 0, use_kernel=True, paged=True
        )
        k_plain = ops.swa_decode_attention(q, kc, vc, pos, 0, use_kernel=True)
        r_paged = ops.swa_decode_attention(q, kc, vc, pos, 0, paged=True)
        r_plain = ops.swa_decode_attention(q, kc, vc, pos, 0)
        np.testing.assert_array_equal(np.asarray(k_paged), np.asarray(k_plain))
        np.testing.assert_array_equal(np.asarray(r_paged), np.asarray(r_plain))


# ---------------------------------------------------------- page-table mode
def _scatter_to_pool(kc, vc, page, key):
    """Re-lay a contiguous (B, C, Hkv, hd) cache as a shared page pool with
    a RANDOM page placement: pool (1 + B·C/page, page, Hkv, hd) whose page
    0 is scratch, plus the (B, T) table mapping each row's logical pages to
    their scattered physical homes."""
    b, cap, hkv, hd = kc.shape
    t_w = cap // page
    flat_k = kc.reshape(b * t_w, page, hkv, hd)
    flat_v = vc.reshape(b * t_w, page, hkv, hd)
    perm = jax.random.permutation(key, b * t_w)
    dest = 1 + perm
    pool_shape = (1 + b * t_w, page, hkv, hd)
    pool_k = jnp.zeros(pool_shape, kc.dtype).at[dest].set(flat_k)
    pool_v = jnp.zeros(pool_shape, kc.dtype).at[dest].set(flat_v)
    table = dest.reshape(b, t_w).astype(jnp.int32)
    return pool_k, pool_v, table


# every CASES cap splits into pages of 64 — small enough that several
# logical pages exist (real skipping + indirection) at every cap
TABLE_PAGE = 64
# head widths that take each table-mode path: a lane-aligned head_dim walks
# the live pages in multi-page DMA blocks, a narrower one keeps the (B, T)
# grid of one page a step
WALK_HD, GRID_HD = 128, 64


def _block_tokens(q, pool, table):
    """Tokens in one compute block of the table kernel at these shapes: the
    page size at which the contiguous kernel associates the online softmax
    alike."""
    _, hkv, _, hd = q.shape
    page = pool.shape[1]
    if not walks_pages(page, hkv, hd, quant=False):
        return page
    return page * pages_per_block(table.shape[1], page, hkv, hd, q.dtype)


class TestTableMode:
    @pytest.mark.parametrize("cap,poss,window", CASES)
    def test_table_kernel_bitwise_matches_contiguous_kernel(
        self, cap, poss, window
    ):
        """Scattered physical placement must be invisible BIT FOR BIT
        against the contiguous paged kernel at the page size of the table
        kernel's compute block (same chunk partitioning → same
        online-softmax association)."""
        q, kc, vc = _rand(
            jax.random.PRNGKey(3 * cap + window), cap, len(poss), hd=WALK_HD
        )
        pos = jnp.asarray(poss, jnp.int32)
        pool_k, pool_v, table = _scatter_to_pool(
            kc, vc, TABLE_PAGE, jax.random.PRNGKey(cap)
        )
        out = paged_decode(q, pool_k, pool_v, pos, window, table=table)
        expected = paged_decode(
            q, kc, vc, pos, window, page=_block_tokens(q, pool_k, table)
        )
        np.testing.assert_array_equal(np.asarray(out), np.asarray(expected))

    @pytest.mark.parametrize("cap,poss,window", CASES)
    def test_grid_kernel_bitwise_matches_contiguous_kernel(
        self, cap, poss, window
    ):
        """The (B, T) grid path (head_dim off the lane width) blocks one
        page a step: bitwise the contiguous kernel at the same page size."""
        q, kc, vc = _rand(
            jax.random.PRNGKey(3 * cap + window), cap, len(poss), hd=GRID_HD
        )
        pos = jnp.asarray(poss, jnp.int32)
        pool_k, pool_v, table = _scatter_to_pool(
            kc, vc, TABLE_PAGE, jax.random.PRNGKey(cap)
        )
        assert _block_tokens(q, pool_k, table) == TABLE_PAGE
        out = paged_decode(q, pool_k, pool_v, pos, window, table=table)
        expected = paged_decode(q, kc, vc, pos, window, page=TABLE_PAGE)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(expected))

    @pytest.mark.parametrize("cap,poss,window", CASES)
    def test_table_ref_bitwise_matches_plain_ref(self, cap, poss, window):
        """The jnp table oracle (gather pages → plain ring oracle) equals
        the plain oracle on the contiguous original."""
        q, kc, vc = _rand(jax.random.PRNGKey(5 * cap + window), cap, len(poss))
        pos = jnp.asarray(poss, jnp.int32)
        pool_k, pool_v, table = _scatter_to_pool(
            kc, vc, TABLE_PAGE, jax.random.PRNGKey(cap + 1)
        )
        a = ref.paged_table_decode_ref(q, pool_k, pool_v, pos, table, window)
        b = ref.swa_decode_ref(q, kc, vc, pos, window)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    @pytest.mark.parametrize("cap,poss,window", CASES)
    def test_table_kernel_close_to_oracle(self, cap, poss, window):
        q, kc, vc = _rand(
            jax.random.PRNGKey(11 * cap + window), cap, len(poss), hd=WALK_HD
        )
        pos = jnp.asarray(poss, jnp.int32)
        pool_k, pool_v, table = _scatter_to_pool(
            kc, vc, TABLE_PAGE, jax.random.PRNGKey(cap + 2)
        )
        out = paged_decode(q, pool_k, pool_v, pos, window, table=table)
        expected = ref.paged_table_decode_ref(
            q, pool_k, pool_v, pos, table, window
        )
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(expected), rtol=3e-5, atol=3e-5
        )

    @pytest.mark.parametrize("cap,poss,window", CASES)
    def test_grid_kernel_close_to_oracle(self, cap, poss, window):
        q, kc, vc = _rand(
            jax.random.PRNGKey(11 * cap + window), cap, len(poss), hd=GRID_HD
        )
        pos = jnp.asarray(poss, jnp.int32)
        pool_k, pool_v, table = _scatter_to_pool(
            kc, vc, TABLE_PAGE, jax.random.PRNGKey(cap + 2)
        )
        out = paged_decode(q, pool_k, pool_v, pos, window, table=table)
        expected = ref.paged_table_decode_ref(
            q, pool_k, pool_v, pos, table, window
        )
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(expected), rtol=3e-5, atol=3e-5
        )

    def test_unallocated_tail_entries_never_read(self):
        """Table entries beyond a row's live span may point ANYWHERE (the
        engine leaves them at scratch page 0): the index-map clamp + the
        live-page gate mean they must not change a single bit."""
        cap, page = 256, 64
        q, kc, vc = _rand(jax.random.PRNGKey(41), cap, 3, hd=WALK_HD)
        pos = jnp.asarray([10, 100, 150], jnp.int32)  # 1, 2, 3 live pages
        pool_k, pool_v, table = _scatter_to_pool(
            kc, vc, page, jax.random.PRNGKey(42)
        )
        live_pages = np.asarray((np.minimum(np.asarray(pos) + 1, cap) + page - 1) // page)
        wild = np.array(table)
        for r, lp in enumerate(live_pages):
            wild[r, lp:] = 0  # scratch — what the engine actually does
        a = paged_decode(q, pool_k, pool_v, pos, 0, table=table)
        b = paged_decode(q, pool_k, pool_v, pos, 0, table=jnp.asarray(wild))
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_rows_share_pool_without_leaking(self):
        """Two rows with interleaved physical pages: each row's solo run
        equals its batched row — page placement of OTHER rows can't leak."""
        cap, page = 512, 64
        q, kc, vc = _rand(jax.random.PRNGKey(51), cap, 2, hd=WALK_HD)
        pos = jnp.asarray([200, 700], jnp.int32)
        pool_k, pool_v, table = _scatter_to_pool(
            kc, vc, page, jax.random.PRNGKey(52)
        )
        batched = paged_decode(q, pool_k, pool_v, pos, 0, table=table)
        for r in range(2):
            solo = paged_decode(
                q[r : r + 1], pool_k, pool_v, pos[r : r + 1], 0,
                table=table[r : r + 1],
            )
            np.testing.assert_array_equal(
                np.asarray(solo[0]), np.asarray(batched[r])
            )

    def test_rows_aliasing_shared_prefix_pages_bitwise(self):
        """PREFIX SHARING at the kernel layer: two rows whose tables alias
        the SAME physical pages for a common prefix must read bit-for-bit
        what they read from private duplicated copies — aliasing is pure
        placement, and table mode already tolerates arbitrary placement,
        so no kernel change is needed (this pins that claim)."""
        cap, page, shared_pages = 256, 64, 2
        q, kc, vc = _rand(jax.random.PRNGKey(71), cap, 2, hd=WALK_HD)
        # duplicate the shared-prefix CONTENT into both rows' caches
        kc = kc.at[1, : shared_pages * page].set(kc[0, : shared_pages * page])
        vc = vc.at[1, : shared_pages * page].set(vc[0, : shared_pages * page])
        pos = jnp.asarray([150, 230], jnp.int32)  # both past the prefix
        pool_k, pool_v, table = _scatter_to_pool(
            kc, vc, page, jax.random.PRNGKey(72)
        )
        aliased = jnp.asarray(table).at[1, :shared_pages].set(
            table[0, :shared_pages]
        )  # row 1's prefix now points at row 0's physical pages
        for tab in (table, aliased):
            out = paged_decode(q, pool_k, pool_v, pos, 0, table=tab)
            ref_out = ref.paged_table_decode_ref(
                q, pool_k, pool_v, pos, tab, 0
            )
            np.testing.assert_allclose(
                np.asarray(out), np.asarray(ref_out), rtol=3e-5, atol=3e-5
            )
        a = paged_decode(q, pool_k, pool_v, pos, 0, table=table)
        b = paged_decode(q, pool_k, pool_v, pos, 0, table=aliased)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        ra = ref.paged_table_decode_ref(q, pool_k, pool_v, pos, table, 0)
        rb = ref.paged_table_decode_ref(q, pool_k, pool_v, pos, aliased, 0)
        np.testing.assert_array_equal(np.asarray(ra), np.asarray(rb))

    def test_ops_routes_table_mode(self):
        cap, page = 128, 64
        q, kc, vc = _rand(jax.random.PRNGKey(61), cap, 2, hd=WALK_HD)
        pos = jnp.asarray([9, 300], jnp.int32)
        pool_k, pool_v, table = _scatter_to_pool(
            kc, vc, page, jax.random.PRNGKey(62)
        )
        k_out = ops.swa_decode_attention(
            q, pool_k, pool_v, pos, 0, use_kernel=True, table=table
        )
        r_out = ops.swa_decode_attention(q, pool_k, pool_v, pos, 0, table=table)
        plain = ref.swa_decode_ref(q, kc, vc, pos, 0)
        np.testing.assert_array_equal(np.asarray(r_out), np.asarray(plain))
        np.testing.assert_allclose(
            np.asarray(k_out), np.asarray(plain), rtol=3e-5, atol=3e-5
        )

    @given(pos=st.integers(0, 2000), window=st.sampled_from([0, 32, 128]))
    @settings(max_examples=15, deadline=None)
    def test_property_table_ring_positions(self, pos, window):
        """Arbitrary ring positions: table kernel == contiguous paged
        kernel at the compute block's page size, scattered placement and
        all."""
        key = jax.random.PRNGKey(pos + 131 * window)
        cap, page = 256, 16
        # 4 kv heads of 128 in f32: 8-page blocks, two to the table
        q = jax.random.normal(key, (1, 4, 2, WALK_HD))
        kc = jax.random.normal(jax.random.fold_in(key, 1), (1, cap, 4, WALK_HD))
        vc = jax.random.normal(jax.random.fold_in(key, 2), (1, cap, 4, WALK_HD))
        pool_k, pool_v, table = _scatter_to_pool(
            kc, vc, page, jax.random.fold_in(key, 3)
        )
        a = paged_decode(
            q, pool_k, pool_v, jnp.asarray(pos), window, table=table
        )
        b = paged_decode(
            q, kc, vc, jnp.asarray(pos), window,
            page=_block_tokens(q, pool_k, table),
        )
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ------------------------------------------------- page walk: block edges
# Page 16 x 8 kv heads x 128 is 64 KiB a page in f32, so the walk blocks a
# few pages (``pages_per_block``) and a 32-page table is several blocks;
# page·Hkv = 128 lets the int8 pool's scale planes walk too.
WALK_PAGE, WALK_HKV, WALK_T = 16, 8, 32
WALK_PPB = pages_per_block(WALK_T, WALK_PAGE, WALK_HKV, WALK_HD, jnp.float32)
# live span of the probed rows → (pos, window)
SPANS = {
    "one_page": (5, 0),
    "block_edge": (WALK_PPB * WALK_PAGE - 1, 0),
    "block_edge_plus_1": (WALK_PPB * WALK_PAGE, 0),
    "full_table_wrapped": (WALK_T * WALK_PAGE + 100, 64),
}


def _walk_pool(key, n, quant):
    """q, (k, v) pools (int8 with scales when ``quant``), their fp
    mirror, and a scattered (n, WALK_T) table over 1 + n·WALK_T pages."""
    ks = jax.random.split(key, 4)
    shape = (1 + n * WALK_T, WALK_PAGE, WALK_HKV, WALK_HD)
    q = jax.random.normal(ks[0], (n, WALK_HKV, 2, WALK_HD))
    pk = jax.random.normal(ks[1], shape)
    pv = jax.random.normal(ks[2], shape)
    table = (1 + jax.random.permutation(ks[3], n * WALK_T)).reshape(n, WALK_T)
    table = table.astype(jnp.int32)
    if not quant:
        return q, (pk, pv), (pk, pv), table
    (qk, sk), (qv, sv) = kv_quant(pk), kv_quant(pv)
    deq = (ref.dequant_pool_ref(qk, sk), ref.dequant_pool_ref(qv, sv))
    return q, (qk, qv, sk, sv), deq, table


def _decode(q, pools, pos, window, table):
    k, v, *scales = pools
    kw = dict(zip(("k_scale", "v_scale"), scales))
    return paged_decode(q, k, v, pos, window, table=table, **kw)


class TestTableWalk:
    def test_shapes_walk_several_blocks(self):
        assert walks_pages(WALK_PAGE, WALK_HKV, WALK_HD, quant=True)
        assert 1 < WALK_PPB < WALK_T

    @pytest.mark.parametrize("quant", [False, True], ids=["fp", "int8"])
    @pytest.mark.parametrize("span", list(SPANS))
    def test_live_span_bitwise(self, span, quant):
        """Rows whose live span is one page, exactly a block, a block and
        one page, and the whole (wrapped) table, between single-page rows
        so that each row starts on either buffer: bitwise the contiguous
        kernel at the block's page size (fp) or the fp walk over the
        dequantized pool (int8), and close to the oracle."""
        p, window = SPANS[span]
        q, pools, (dk, dv), table = _walk_pool(
            jax.random.PRNGKey(len(span)), 3, quant
        )
        pos = jnp.asarray([p, 3, p], jnp.int32)
        out = _decode(q, pools, pos, window, table)
        cap = WALK_T * WALK_PAGE
        kc = dk[table].reshape(3, cap, WALK_HKV, WALK_HD)
        vc = dv[table].reshape(3, cap, WALK_HKV, WALK_HD)
        expected = paged_decode(
            q, kc, vc, pos, window, page=WALK_PPB * WALK_PAGE
        )
        if quant:
            np.testing.assert_array_equal(
                np.asarray(out), np.asarray(_decode(q, (dk, dv), pos, window, table))
            )
        np.testing.assert_array_equal(np.asarray(out), np.asarray(expected))
        oracle = ref.paged_table_decode_ref(q, dk, dv, pos, table, window)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(oracle), rtol=3e-5, atol=3e-5
        )


def _poison_dead_pages(pools, table, pos, cap):
    """NaN in every pool page no row has live (the scale planes of an int8
    pool), and every table entry past a row's live span sent to scratch
    page 0, which is dead too."""
    page = pools[0].shape[1]
    live_pages = np.minimum((np.minimum(np.asarray(pos) + 1, cap) + page - 1) // page,
                            table.shape[1])
    tab = np.array(table)
    for r, n in enumerate(live_pages):
        tab[r, n:] = 0
    live = np.zeros(pools[0].shape[0], bool)
    live[np.concatenate([tab[r, :n] for r, n in enumerate(live_pages)])] = True
    dead = jnp.asarray(~live)
    poisoned = [
        jnp.where(dead.reshape((-1,) + (1,) * (x.ndim - 1)), jnp.nan, x)
        if jnp.issubdtype(x.dtype, jnp.floating) else x
        for x in pools
    ]
    return poisoned, jnp.asarray(tab)


@pytest.mark.parametrize("path", ["walk-fp", "walk-int8", "grid-fp"])
def test_poisoned_dead_pages_never_read(path):
    """No dead page reaches the kernel's arithmetic: with NaN in every page
    that no row has live, the output is bitwise unchanged and finite."""
    hd = GRID_HD if path == "grid-fp" else WALK_HD
    quant = path == "walk-int8"
    key = jax.random.PRNGKey(91)
    if hd == WALK_HD:
        q, pools, _, table = _walk_pool(key, 4, quant)
        page, t_w = WALK_PAGE, WALK_T
    else:
        page, t_w = 16, WALK_T
        q, kc, vc = _rand(key, page * t_w, 4, hkv=WALK_HKV, hd=hd)
        pk, pv, table = _scatter_to_pool(kc, vc, page, jax.random.fold_in(key, 1))
        pools = (pk, pv)
    cap = page * t_w
    # one page, a block edge and one, most of the table, and a wrapped row
    pos = jnp.asarray([2, WALK_PPB * page, cap - 20, cap + 37], jnp.int32)
    window = 0
    clean = _decode(q, pools, pos, window, table)
    poisoned, tab = _poison_dead_pages(pools, table, pos, cap)
    assert bool(jnp.isnan(poisoned[-1]).any())
    out = _decode(q, poisoned, pos, window, tab)
    assert bool(jnp.isfinite(out).all())
    np.testing.assert_array_equal(np.asarray(out), np.asarray(clean))
