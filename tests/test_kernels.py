"""Per-kernel validation: pallas_call (interpret=True) vs ref.py oracles,
swept over shapes and dtypes (assignment requirement)."""

import pytest

pytest.importorskip("jax")  # accelerator dep is optional for the numpy core

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.sparse import bsr_from_dense, random_sparse
from repro.kernels.bsr_spmm.ops import prepare_bsr_operands, bsr_spmm
from repro.kernels.bsr_spmm.ref import bsr_spmm_fused_ref
from repro.kernels.decode_attention.decode_attention import decode_attention
from repro.kernels.decode_attention.ops import decode_mha, decode_mha_paged
from repro.kernels.decode_attention.ref import decode_attention_ref
from repro.kernels.flash_attention.ops import mha
from repro.kernels.moe_gmm.moe_gmm import grouped_swiglu
from repro.kernels.moe_gmm.ops import moe_gmm, row_tile, tile_layout
from repro.kernels.moe_gmm.ref import moe_gmm_ref
from repro.kernels.flash_attention.ref import flash_attention_ref
from repro.kernels.ssd_scan.ops import ssd
from repro.kernels.ssd_scan.ref import ssd_scan_ref

TOL = {jnp.float32: dict(rtol=1e-5, atol=1e-5),
       jnp.bfloat16: dict(rtol=2e-2, atol=2e-2)}


class TestBsrSpmm:
    @pytest.mark.parametrize("n,bm,bn,batch", [
        (256, 32, 32, 64), (512, 64, 32, 128), (128, 16, 16, 32),
    ])
    def test_matches_ref_random(self, n, bm, bn, batch):
        rng = np.random.default_rng(0)
        csr = random_sparse(n, n, 16, rng)
        bsr = bsr_from_dense(csr.to_dense(), (bm, bn))
        blocks, cols = prepare_bsr_operands(bsr)
        x = jnp.asarray(rng.standard_normal((n, batch)), jnp.float32)
        got = bsr_spmm(blocks, cols, x, bias=-0.3, clip=32.0,
                       interpret=True)
        want = bsr_spmm_fused_ref(blocks, cols, x, bias=-0.3, clip=32.0)
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)

    def test_graphchallenge_layer(self):
        """Kernel == dense oracle on an actual butterfly layer + epilogue."""
        from repro.data.graphchallenge import make_sparse_dnn, make_inputs

        net = make_sparse_dnn(256, n_layers=1, seed=3)
        x = make_inputs(256, 64, seed=4)
        bsr = bsr_from_dense(net.layers[0].to_dense(), (32, 32))
        blocks, cols = prepare_bsr_operands(bsr)
        got = bsr_spmm(blocks, cols, jnp.asarray(x), bias=net.bias,
                       interpret=True)
        from repro.data.graphchallenge import relu_bias_threshold
        want = relu_bias_threshold(net.layers[0].to_dense() @ x, net.bias)
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)

    def test_batch_panels(self):
        rng = np.random.default_rng(5)
        csr = random_sparse(128, 128, 8, rng)
        bsr = bsr_from_dense(csr.to_dense(), (32, 32))
        blocks, cols = prepare_bsr_operands(bsr)
        x = jnp.asarray(rng.standard_normal((128, 256)), jnp.float32)
        got = bsr_spmm(blocks, cols, x, bias=0.0, interpret=True)
        want = bsr_spmm_fused_ref(blocks, cols, x, bias=0.0)
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


class TestFleetMegakernel:
    """The per-device fleet megakernel: the interpreted Pallas grid
    (``force_grid=True``, the lowering the compiled TPU dispatch shares
    BlockSpecs with) must agree bitwise with the vectorized host lowering
    the CPU backends route through, and both with the per-worker kernel."""

    def _fleet(self, p=3, nbr=2, k=3, bm=8, bn=8, n=48, b=6, seed=0):
        rng = np.random.default_rng(seed)
        blocks = rng.standard_normal((p, nbr, k, bm, bn)).astype(np.float32)
        counts = rng.integers(1, k + 1, (p, nbr)).astype(np.int32)
        for pi in range(p):          # zero the padding blocks beyond counts
            for r in range(nbr):
                blocks[pi, r, counts[pi, r]:] = 0.0
        cols = rng.integers(0, n // bn, (p, nbr, k)).astype(np.int32)
        cols[blocks.sum(axis=(-1, -2)) == 0.0] = 0
        x = rng.standard_normal((p, n, b)).astype(np.float32)
        return tuple(jnp.asarray(a) for a in (blocks, cols, counts, x))

    def test_grid_matches_host_lowering_bitwise(self):
        from repro.kernels.bsr_spmm.bsr_spmm import bsr_spmm_fleet_megakernel

        blocks, cols, counts, x = self._fleet()
        host = np.asarray(bsr_spmm_fleet_megakernel(
            blocks, cols, counts, x, bias=-0.2, batch_block=6))
        grid = np.asarray(bsr_spmm_fleet_megakernel(
            blocks, cols, counts, x, bias=-0.2, batch_block=6,
            force_grid=True))
        np.testing.assert_array_equal(host, grid)

    def test_count_bounded_grid_matches_static(self):
        """The compiled kernel's K loop stops at each row's ``counts``: run
        under the interpreter with NaN in every padding block, it must
        never read them and must equal the static-K host lowering over
        zero padding."""
        from repro.kernels.bsr_spmm.bsr_spmm import bsr_spmm_fleet_megakernel

        blocks, cols, counts, x = self._fleet()
        b = x.shape[2]
        want = np.asarray(bsr_spmm_fleet_megakernel(
            blocks, cols, counts, x, bias=-0.2, batch_block=b))
        k = np.arange(blocks.shape[2])
        pad = k[None, None, :] >= np.asarray(counts)[..., None]
        poisoned = jnp.where(jnp.asarray(pad)[..., None, None], jnp.nan,
                             blocks)
        got = bsr_spmm_fleet_megakernel(
            poisoned, cols, counts, x, bias=-0.2, batch_block=b,
            interpret=True, force_grid=True)
        np.testing.assert_array_equal(np.asarray(got), want)

    def test_matches_per_worker_kernel(self):
        """Each worker's panel through the megakernel equals its standalone
        ``bsr_spmm`` dispatch (same padded operands)."""
        from repro.kernels.bsr_spmm.bsr_spmm import bsr_spmm_fleet_megakernel

        blocks, cols, counts, x = self._fleet(seed=7)
        y = np.asarray(bsr_spmm_fleet_megakernel(
            blocks, cols, counts, x, bias=-0.1, batch_block=6))
        for w in range(blocks.shape[0]):
            want = bsr_spmm(blocks[w], cols[w], x[w], bias=-0.1,
                            batch_block=6, interpret=True)
            np.testing.assert_allclose(y[w], np.asarray(want),
                                       rtol=1e-6, atol=1e-6)


class TestFlashAttention:
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("B,H,KV,S,D", [
        (2, 4, 4, 256, 64),    # MHA
        (2, 8, 2, 256, 64),    # GQA
        (1, 4, 4, 512, 128),   # longer, wide head
    ])
    def test_matches_ref(self, dtype, B, H, KV, S, D):
        ks = jax.random.split(jax.random.key(0), 3)
        q = jax.random.normal(ks[0], (B, H, S, D), dtype)
        k = jax.random.normal(ks[1], (B, KV, S, D), dtype)
        v = jax.random.normal(ks[2], (B, KV, S, D), dtype)
        got = mha(q, k, v, causal=True, block_q=128, block_k=128)
        want = flash_attention_ref(q, k, v, causal=True)
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(want, np.float32),
            **TOL[dtype])

    def test_non_causal(self):
        ks = jax.random.split(jax.random.key(1), 3)
        q = jax.random.normal(ks[0], (1, 2, 128, 64), jnp.float32)
        k = jax.random.normal(ks[1], (1, 2, 256, 64), jnp.float32)
        v = jax.random.normal(ks[2], (1, 2, 256, 64), jnp.float32)
        got = mha(q, k, v, causal=False)
        want = flash_attention_ref(q, k, v, causal=False)
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)

    def test_block_shape_invariance(self):
        ks = jax.random.split(jax.random.key(2), 3)
        q = jax.random.normal(ks[0], (1, 2, 512, 64), jnp.float32)
        k = jax.random.normal(ks[1], (1, 2, 512, 64), jnp.float32)
        v = jax.random.normal(ks[2], (1, 2, 512, 64), jnp.float32)
        a = mha(q, k, v, block_q=128, block_k=128)
        b = mha(q, k, v, block_q=256, block_k=64)
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5)


class TestDecodeAttention:
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("B,H,KV,S,D,length", [
        (2, 8, 2, 1024, 64, 1000),
        (4, 4, 4, 2048, 128, 2048),
        (1, 16, 2, 512, 64, 77),     # ragged valid prefix
    ])
    def test_matches_ref(self, dtype, B, H, KV, S, D, length):
        ks = jax.random.split(jax.random.key(0), 3)
        q = jax.random.normal(ks[0], (B, H, D), dtype)
        kc = jax.random.normal(ks[1], (B, KV, S, D), dtype)
        vc = jax.random.normal(ks[2], (B, KV, S, D), dtype)
        got_o, got_lse = decode_mha(q, kc, vc, length, block_k=256)
        want_o, want_lse = decode_attention_ref(q, kc, vc, length)
        np.testing.assert_allclose(
            np.asarray(got_o, np.float32), np.asarray(want_o, np.float32),
            **TOL[dtype])
        np.testing.assert_allclose(got_lse, want_lse,
                                   rtol=2e-2 if dtype == jnp.bfloat16 else 1e-4,
                                   atol=2e-2 if dtype == jnp.bfloat16 else 1e-4)

    def test_split_kv_combine_equals_full(self):
        """Sharded partials + lse combine ≡ attention over the full cache."""
        from repro.models.attention import decode_attention as ref_chunked

        ks = jax.random.split(jax.random.key(3), 3)
        B, H, KV, S, D = 2, 4, 2, 1024, 64
        q = jax.random.normal(ks[0], (B, H, D), jnp.float32)
        kc = jax.random.normal(ks[1], (B, KV, S, D), jnp.float32)
        vc = jax.random.normal(ks[2], (B, KV, S, D), jnp.float32)
        full_o, _ = decode_mha(q, kc, vc, S)
        # two halves as if seq-sharded on two devices
        o1, l1 = decode_mha(q, kc[:, :, :512], vc[:, :, :512], 512)
        o2, l2 = decode_mha(q, kc[:, :, 512:], vc[:, :, 512:], 512)
        m = np.maximum(l1, l2)
        w1, w2 = np.exp(l1 - m), np.exp(l2 - m)
        combined = (np.asarray(o1) * w1[..., None] + np.asarray(o2) * w2[..., None]) / (
            (w1 + w2)[..., None])
        np.testing.assert_allclose(combined, full_o, rtol=1e-5, atol=1e-5)


# The paged pool as the scheduler keeps it: [L, 1, KV, num_blocks, bk, D],
# page 0 the zero null page.
PAGED_L, PAGED_KV, PAGED_G, PAGED_D, PAGED_BK, PAGED_W = 2, 2, 2, 32, 8, 4
PAGED_NB = 2 + 4 * PAGED_W


def _paged_case(rng, dtype, lengths):
    """A pool, the slots' queries and tables: each slot's valid pages drawn
    from a shuffled free list (scrambled physical order), the rest of its
    table the null page."""
    shape = (PAGED_L, 1, PAGED_KV, PAGED_NB, PAGED_BK, PAGED_D)
    kp = rng.standard_normal(shape).astype(np.float32)
    vp = rng.standard_normal(shape).astype(np.float32)
    kp[:, :, :, 0] = vp[:, :, :, 0] = 0.0
    free = list(rng.permutation(np.arange(2, PAGED_NB)))
    tables = np.zeros((len(lengths), PAGED_W), np.int32)
    for s, n in enumerate(lengths):
        for j in range(-(-n // PAGED_BK)):
            tables[s, j] = free.pop()
    q = rng.standard_normal((len(lengths), PAGED_KV * PAGED_G, PAGED_D))
    return (jnp.asarray(q, dtype), jnp.asarray(kp, dtype),
            jnp.asarray(vp, dtype), tables)


def _gathered(pages, layer, table):
    """One slot's contiguous [1, KV, W * bk, D] cache of one layer."""
    x = pages[layer, 0][:, table]                    # [KV, W, bk, D]
    return x.reshape(1, PAGED_KV, PAGED_W * PAGED_BK, PAGED_D)


def _assert_paged_matches_gathered(q, kp, vp, tables, lengths, layer):
    out, lse = decode_mha_paged(q, kp, vp, jnp.asarray(tables),
                                jnp.asarray(lengths, jnp.int32), layer,
                                interpret=True)
    for s, n in enumerate(lengths):
        want_o, want_lse = decode_attention(
            q[s:s + 1], _gathered(kp, layer, tables[s]),
            _gathered(vp, layer, tables[s]), n, block_k=PAGED_BK,
            interpret=True)
        np.testing.assert_array_equal(np.asarray(out[s:s + 1]),
                                      np.asarray(want_o), err_msg=f"slot {s}")
        np.testing.assert_array_equal(np.asarray(lse[s:s + 1]),
                                      np.asarray(want_lse),
                                      err_msg=f"slot {s}")


class TestPagedDecodeAttention:
    """The paged split-K kernel against ``decode_attention`` on the cache
    the block tables describe, gathered: bitwise equal, since it runs the
    same blocks and skips only wholly masked ones."""

    BK, CAP = PAGED_BK, PAGED_W * PAGED_BK

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("lengths", [
        (1,), (BK - 1,), (BK,), (BK + 1,), (CAP,),
        (1, BK + 1, CAP, BK - 1),       # 4 slots of different lengths
    ], ids=["1", "bk-1", "bk", "bk+1", "full", "4-slots"])
    @pytest.mark.parametrize("layer", [0, PAGED_L - 1])
    def test_bitwise_equal_to_gathered(self, dtype, lengths, layer):
        rng = np.random.default_rng(len(lengths) * 100 + lengths[0])
        q, kp, vp, tables = _paged_case(rng, dtype, lengths)
        _assert_paged_matches_gathered(q, kp, vp, tables, lengths, layer)

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("offset", [0, PAGED_BK - 1])
    def test_new_token_written_into_its_page(self, dtype, offset):
        """The decode step's order: each slot's new token is written into
        its page (``KVBlockPool.write_token``), then attended over; the same
        as writing it into the gathered cache."""
        from repro.serving.kv_pool import KVBlockPool

        pos = np.array([PAGED_BK + offset, offset, 2 * PAGED_BK + offset,
                        3 * PAGED_BK + offset], np.int32)
        lengths = tuple(int(p) + 1 for p in pos)
        rng = np.random.default_rng(offset)
        q, kp, vp, tables = _paged_case(rng, dtype, lengths)
        layer = 1
        new = jnp.asarray(rng.standard_normal(
            (len(pos), 1, PAGED_KV, PAGED_D)), dtype)
        page = jnp.asarray(tables[np.arange(len(pos)), pos // PAGED_BK])
        off = jnp.asarray(pos % PAGED_BK)
        kp2 = KVBlockPool.write_token(kp, layer, page, off, new)
        vp2 = KVBlockPool.write_token(vp, layer, page, off, -new)
        for s, p in enumerate(pos):
            got = _gathered(kp2, layer, tables[s])[0, :, p]
            np.testing.assert_array_equal(np.asarray(got),
                                          np.asarray(new[s, 0]))
        _assert_paged_matches_gathered(q, kp2, vp2, tables, lengths, layer)


def _gmm_case(rng, dtype, sizes, rows, d=128, f=256):
    E = len(sizes)
    x = jnp.asarray(rng.standard_normal((rows, d)), dtype)
    w = lambda *s: jnp.asarray(rng.standard_normal(s) * s[-2] ** -0.5, dtype)
    return (x, jnp.asarray(sizes, jnp.int32), w(E, d, f), w(E, d, f),
            w(E, f, d))


class TestMoeGmm:
    """The grouped expert matmul in the interpreter against its oracle:
    each row through its own expert's SwiGLU FFN, rows past the groups
    zero."""

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("sizes,rows", [
        ([3, 0, 17, 1, 9], 37),          # an empty group, sizes off the tile
        ([0, 0, 24, 0], 24),             # every row in one group
        ([16, 32, 0, 16], 64),           # sizes on the tile
        ([1, 1, 1, 1, 1, 1, 1, 1], 12),  # one row each, rows to spare
        ([0, 0, 0], 6),                  # no row at all
    ])
    def test_matches_ref(self, dtype, sizes, rows):
        x, gs, wg, wu, wd = _gmm_case(np.random.default_rng(len(sizes)),
                                      dtype, sizes, rows)
        got = moe_gmm(x, gs, wg, wu, wd, interpret=True)
        want = moe_gmm_ref(x, gs, wg, wu, wd)
        assert got.dtype == jnp.float32
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   **TOL[dtype])
        np.testing.assert_array_equal(np.asarray(got)[sum(sizes):], 0.0)

    def test_bf16_operands_accumulate_in_f32(self):
        """bf16 in, f32 sums: the result equals the f32 computation on the
        same bf16 values (the gated product rounded to bf16), not a bf16
        accumulation."""
        x, gs, wg, wu, wd = _gmm_case(np.random.default_rng(3), jnp.bfloat16,
                                      [20, 12], 32, d=512)
        got = np.asarray(moe_gmm(x, gs, wg, wu, wd, interpret=True))
        up = lambda a: a.astype(jnp.float32)
        e = np.repeat(np.arange(2), [20, 12])
        g = np.einsum("md,mdf->mf", up(x), up(wg)[e])
        u = np.einsum("md,mdf->mf", up(x), up(wu)[e])
        h = np.asarray(jnp.asarray(g / (1 + np.exp(-g)) * u, jnp.bfloat16),
                       np.float32)
        want = np.einsum("mf,mfd->md", h, up(wd)[e])
        # f32 sums differ only in their order; a bf16 result would not
        # hold this tolerance
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)
        as_bf16 = np.asarray(jnp.asarray(want, jnp.bfloat16), np.float32)
        assert np.abs(as_bf16 - want).max() > 1e-3

    def test_layer_of_a_stack(self):
        """Given a layer, the weights are a stack of layers and the call
        uses that layer's experts."""
        x, gs, wg, wu, wd = _gmm_case(np.random.default_rng(6), jnp.float32,
                                      [5, 0, 7], 12)
        stack = lambda w: jnp.stack([w * 2.0, w, w * 3.0])
        want = moe_gmm_ref(x, gs, wg, wu, wd)
        for interpret in (True, None):
            got = moe_gmm(x, gs, stack(wg), stack(wu), stack(wd), layer=1,
                          interpret=interpret)
            np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                       **TOL[jnp.float32])

    def test_rows_do_not_depend_on_other_rows(self):
        """A row's result is the same bits whatever other rows share the
        call: the expert layer's stream equals the solo oracle."""
        x, gs, wg, wu, wd = _gmm_case(np.random.default_rng(4), jnp.bfloat16,
                                      [5, 0, 7], 12)
        full = np.asarray(moe_gmm(x, gs, wg, wu, wd, interpret=True))
        alone = np.asarray(moe_gmm(x[5:6], jnp.asarray([0, 0, 1], jnp.int32),
                                   wg, wu, wd, interpret=True))
        np.testing.assert_array_equal(full[5], alone[0])

    def test_layout_reads_each_used_expert_once(self):
        """Every group is padded to whole tiles of one expert; the used
        tiles of an expert are consecutive, so its weights are fetched
        once, and an expert with no rows owns no tile."""
        sizes = np.array([3, 0, 17, 1, 0, 9], np.int32)
        rows = 40
        tm = row_tile(rows, len(sizes))
        n_tiles, src, dst, te, n_used = tile_layout(jnp.asarray(sizes), rows,
                                                    tm)
        te, n_used = np.asarray(te), int(n_used[0])
        used = te[:n_used]
        assert n_used == sum(-(-s // tm) for s in sizes)
        assert list(dict.fromkeys(used)) == [0, 2, 3, 5]
        assert (np.diff(used) >= 0).all() and (te[n_used:] == used[-1]).all()
        # each sorted row lands once, in a tile of its own expert
        dst = np.asarray(dst)[: sizes.sum()]
        assert len(set(dst)) == len(dst)
        e = np.repeat(np.arange(len(sizes)), sizes)
        np.testing.assert_array_equal(te[dst // tm], e)
        np.testing.assert_array_equal(np.asarray(src)[dst],
                                      np.arange(sizes.sum()))

    @pytest.mark.parametrize("rows,experts,tm", [
        (192, 32, 16), (1536, 32, 64), (12288, 32, 128), (6, 8, 16)])
    def test_row_tile(self, rows, experts, tm):
        assert row_tile(rows, experts) == tm

    def test_kernel_grid_matches_ref(self):
        """The kernel itself over a hand-made layout and a stack of two
        layers' experts, reading layer 1's in place: unused tiles give
        zeros."""
        x, gs, wg, wu, wd = _gmm_case(np.random.default_rng(5), jnp.float32,
                                      [16, 16], 48)
        stack = lambda w: jnp.stack([w * 0 + 7.0, w])
        got = grouped_swiglu(x, jnp.asarray([0, 1, 1], jnp.int32),
                             jnp.asarray([2], jnp.int32),
                             jnp.asarray([1], jnp.int32), stack(wg),
                             stack(wu), stack(wd), tm=16, interpret=True)
        want = moe_gmm_ref(x, gs, wg, wu, wd)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   **TOL[jnp.float32])


class TestSsdScan:
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("B,H,G,L,P,N,chunk", [
        (2, 4, 1, 256, 32, 16, 64),
        (1, 4, 2, 512, 64, 32, 128),
        (2, 2, 2, 128, 32, 64, 128),   # single chunk
    ])
    def test_matches_ref(self, dtype, B, H, G, L, P, N, chunk):
        ks = jax.random.split(jax.random.key(0), 4)
        x = jax.random.normal(ks[0], (B, H, L, P), dtype)
        dt = jax.nn.softplus(jax.random.normal(ks[1], (B, H, L))).astype(jnp.float32)
        A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.3)
        Bm = jax.random.normal(ks[3], (B, G, L, N), dtype)
        Cm = jax.random.normal(jax.random.key(9), (B, G, L, N), dtype)
        got_y, got_s = ssd(x, dt, A, Bm, Cm, chunk=chunk)
        want_y, want_s = ssd_scan_ref(x, dt, A, Bm, Cm, chunk=chunk)
        tol = TOL[dtype]
        np.testing.assert_allclose(
            np.asarray(got_y, np.float32), np.asarray(want_y, np.float32), **tol)
        np.testing.assert_allclose(
            np.asarray(got_s, np.float32), np.asarray(want_s, np.float32),
            rtol=tol["rtol"] * 5, atol=tol["atol"] * 5)

    def test_state_carry_across_chunks(self):
        """Final state must match a sequential per-token recurrence."""
        B, H, G, L, P, N = 1, 2, 1, 64, 16, 8
        ks = jax.random.split(jax.random.key(7), 4)
        x = jax.random.normal(ks[0], (B, H, L, P), jnp.float32)
        dt = jax.nn.softplus(jax.random.normal(ks[1], (B, H, L)))
        A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.3)
        Bm = jax.random.normal(ks[3], (B, G, L, N), jnp.float32)
        Cm = jax.random.normal(jax.random.key(8), (B, G, L, N), jnp.float32)
        _, s_kernel = ssd(x, dt, A, Bm, Cm, chunk=32)
        # sequential oracle
        s = np.zeros((B, H, P, N), np.float32)
        for t in range(L):
            a = np.exp(np.asarray(dt[:, :, t]) * np.asarray(A)[None])
            s = s * a[..., None, None] + np.einsum(
                "bh,bn,bhp->bhpn", np.asarray(dt[:, :, t]),
                np.asarray(Bm[:, 0, t]), np.asarray(x[:, :, t]))
        np.testing.assert_allclose(s_kernel, s, rtol=1e-4, atol=1e-4)
