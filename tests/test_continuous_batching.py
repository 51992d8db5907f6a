"""Continuous batching: differential scheduler parity + paged-pool properties.

The contract under test (PR 8): every request served through the
continuous-batching scheduler (``ServingEngine.generate_stream`` /
``serving.scheduler.RequestScheduler``) produces tokens and final-step
logits **bitwise equal** to the same request served alone through the static
``generate`` oracle at equal cache capacity — across attention backends ×
model families × ragged prompt lengths × staggered arrival orders.  The
bitwise bar holds because vmap-of-B=1 decode is bit-identical to solo B=1
decode under XLA, and masked cache positions contribute exactly +0.0
regardless of the stale values reused pool pages hold.

Also here:
* hypothesis property tests for ``BlockAllocator``/``KVBlockPool`` (no
  double allocation, no freed-page reads, pool drains to empty; block-table
  → flat-cache round-trip exact);
* the no-retrace regression: admissions/retirements inside one slot bucket
  never recompile the jitted decode step (PR 4 ``_cache_size`` harness),
  with a detector self-test;
* scheduler beats the padded-static-batch baseline on slot-step efficiency
  for ragged streams (deterministic step counts, the quantity the
  ``serving_cb_*`` bench rows gate);
* a mesh-marked forced-4-device sweep of the sequence-sharded scheduler
  (``make test-mesh``).
"""

import dataclasses
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _hypothesis_compat import given, settings, st
from repro.configs import get_config
from repro.core.backends import (
    ChunkedLseAttention, KVCacheLayout, PallasSplitKAttention)
from repro.models.registry import cache_specs, get_model
from repro.configs.base import ShapeConfig
from repro.serving.engine import ServingEngine
from repro.serving.kv_pool import (
    BlockAllocator, KVBlockPool, PoolExhausted, RESERVED_BLOCKS, SINK_BLOCK,
    split_cache)
from repro.serving.scheduler import Request, RequestScheduler

BLOCK_K = 4          # tiny kernel block so pool pages + 4-way shards stay legal
NUM_SLOTS = 2

FAMILY_ARCHS = {
    "transformer": "internlm2-1.8b",
    "moe": "deepseek-moe-16b",
    "hybrid": "zamba2-7b",
    "encdec": "seamless-m4t-medium",
}

# backends per family: the paged families (transformer, moe) sweep all
# three; the others get the oracle + the compiled kernel (chunked-lse
# shares the vmap-level bitwise proof with dense-ref).
BACKENDS = {
    "transformer": ("dense-ref", "chunked-lse", "pallas-splitk"),
    "moe": ("dense-ref", "chunked-lse", "pallas-splitk"),
    "hybrid": ("dense-ref", "pallas-splitk"),
    "encdec": ("dense-ref", "chunked-lse", "pallas-splitk"),
}


def _backend(name):
    if name == "pallas-splitk":
        return PallasSplitKAttention(block_k=BLOCK_K)
    if name == "chunked-lse":
        return ChunkedLseAttention(kv_chunk=3)
    return name                      # "dense-ref" via the registry


def _family_cfg(family):
    # moe serves dropless; its tests pick a routing-tie-free init (same
    # reasoning as tests/test_sharded_decode.py)
    return get_config(FAMILY_ARCHS[family]).reduced()


def _mk_requests(cfg, rng, n, arrivals):
    """Ragged prompts (2..7) and budgets (1..4) with per-family extras."""
    reqs = []
    for i in range(n):
        extra = None
        if cfg.family == "vlm":
            extra = {"extra_embeds": rng.standard_normal(
                (1, cfg.frontend_tokens, cfg.d_model)).astype(np.float32)}
        elif cfg.family == "encdec":
            extra = {"frames": rng.standard_normal(
                (1, cfg.frontend_tokens, cfg.d_model)).astype(np.float32)}
        reqs.append(Request(
            rid=i,
            prompt=rng.integers(0, cfg.vocab_size,
                                (int(rng.integers(2, 8)),)).astype(np.int32),
            max_new_tokens=int(rng.integers(1, 5)),
            extra=extra,
            arrival=int(arrivals[i]),
        ))
    return reqs


def _stream_capacity(eng, reqs):
    need = max(np.asarray(r.prompt).reshape(-1).shape[0] + r.max_new_tokens
               + (eng.cfg.frontend_tokens or 0) for r in reqs)
    return eng.cache_layout(need).padded_len(need)


ENGINE_CASES = [(fam, be) for fam in FAMILY_ARCHS for be in BACKENDS[fam]]


@pytest.fixture(scope="module", params=ENGINE_CASES,
                ids=[f"{f}-{b}" for f, b in ENGINE_CASES])
def diff_case(request):
    """(engine, requests, oracle) for one family × backend cell.

    The oracle result per request is the static ``generate`` at
    ``max_len = slot capacity`` — the scheduler and the oracle then run the
    same reduction shapes, which is what makes bitwise comparison fair."""
    family, backend = request.param
    cfg = _family_cfg(family)
    eng = ServingEngine(cfg, seed=1 if family == "moe" else 0,
                        attn_backend=_backend(backend))
    rng = np.random.default_rng(7)
    reqs = _mk_requests(cfg, rng, 4, arrivals=np.zeros(4, int))
    cap = _stream_capacity(eng, reqs)
    oracle = {}
    for r in reqs:
        ref = eng.generate(np.asarray(r.prompt)[None], r.max_new_tokens,
                           extra=r.extra, max_len=cap)
        oracle[r.rid] = (ref.tokens[0], ref.prefill_logits[0])
    return eng, reqs, cap, oracle


ARRIVAL_ORDERS = {
    "together": lambda n: [0] * n,
    "staggered": lambda n: list(range(n)),
    "reversed": lambda n: list(range(n - 1, -1, -1)),
}


class TestDifferentialParity:
    """Scheduler output ≡ solo static oracle, bitwise."""

    @pytest.mark.parametrize("order", sorted(ARRIVAL_ORDERS))
    def test_stream_matches_solo_oracle(self, diff_case, order):
        eng, base_reqs, cap, oracle = diff_case
        arrivals = ARRIVAL_ORDERS[order](len(base_reqs))
        reqs = [dataclasses.replace(r, arrival=a)
                for r, a in zip(base_reqs, arrivals)]
        results = eng.generate_stream(reqs, num_slots=NUM_SLOTS,
                                      max_request_len=cap)
        assert sorted(r.rid for r in results) == sorted(r.rid for r in reqs)
        for res in results:
            ref_tokens, ref_logits = oracle[res.rid]
            np.testing.assert_array_equal(
                res.tokens, ref_tokens,
                err_msg=f"rid={res.rid} order={order}")
            assert np.array_equal(res.final_logits, ref_logits), \
                f"rid={res.rid} order={order}: logits not bitwise"

    def test_mid_stream_admission_reuses_freed_pages(self, diff_case):
        """More requests than the pool holds at once: retirements must free
        pages that later admissions reuse — and stale page contents must not
        leak into any request's logits (bitwise vs the oracle)."""
        eng, base_reqs, cap, oracle = diff_case
        # two waves of the same requests under new rids: wave 2 decodes on
        # pages wave 1 dirtied
        wave2 = [dataclasses.replace(r, rid=r.rid + len(base_reqs),
                                     arrival=3) for r in base_reqs]
        results = eng.generate_stream(list(base_reqs) + wave2,
                                      num_slots=NUM_SLOTS,
                                      max_request_len=cap)
        assert len(results) == 2 * len(base_reqs)
        for res in results:
            ref_tokens, ref_logits = oracle[res.rid % len(base_reqs)]
            np.testing.assert_array_equal(res.tokens, ref_tokens)
            assert np.array_equal(res.final_logits, ref_logits)


def _dense_stream(cfg, n=5, seed=11):
    rng = np.random.default_rng(seed)
    return [Request(rid=i,
                    prompt=rng.integers(0, cfg.vocab_size,
                                        (int(rng.integers(2, 8)),))
                    .astype(np.int32),
                    max_new_tokens=int(rng.integers(1, 6)),
                    arrival=int(rng.integers(0, 3)))
            for i in range(n)]


def _scheduler(eng, reqs, model=None, **kw):
    cap = _stream_capacity(eng, reqs)
    return RequestScheduler(model or eng.model, eng.params, eng._prefill,
                            num_slots=NUM_SLOTS, slot_capacity=cap,
                            layout=eng.cache_layout(cap), **kw)


class TestPagedStep:
    """Which families decode through block tables, and that doing so
    changes no bit of what the gathering step serves."""

    @pytest.mark.parametrize("backend", BACKENDS["transformer"])
    def test_dense_decodes_every_step_paged(self, backend):
        cfg = _family_cfg("transformer")
        eng = ServingEngine(cfg, attn_backend=_backend(backend))
        reqs = _dense_stream(cfg)
        sched = _scheduler(eng, reqs)
        sched.run(reqs)
        assert sched.paged and sched.steps_run > 0
        assert sched.paged_steps == sched.steps_run
        # the kernel fetches pages up to each slot's last valid one; the
        # jnp backends gather the whole table
        assert sched.decode_positions <= sched.read_positions \
            <= sched.capacity_positions
        if backend == "pallas-splitk":
            assert sched.read_positions < sched.capacity_positions
        else:
            assert sched.read_positions == sched.capacity_positions

    @pytest.mark.parametrize("backend", BACKENDS["transformer"])
    def test_paged_step_matches_gather_step_bitwise(self, backend):
        cfg = _family_cfg("transformer")
        eng = ServingEngine(cfg, attn_backend=_backend(backend))
        reqs = _dense_stream(cfg, n=6, seed=5)
        paged = {r.rid: r for r in _scheduler(eng, reqs).run(reqs)}
        gather = _scheduler(eng, reqs, model=dataclasses.replace(
            eng.model, decode_paged=None))
        assert not gather.paged
        for r in gather.run(reqs):
            np.testing.assert_array_equal(r.tokens, paged[r.rid].tokens)
            assert np.array_equal(r.final_logits, paged[r.rid].final_logits)

    @pytest.mark.parametrize("backend", BACKENDS["moe"])
    def test_moe_decodes_every_step_paged(self, backend):
        """The expert layer rides the same paged step: every step reads
        the pool through the block tables, and the counters count the
        expert rows of every prefill and of each step's occupied slots."""
        cfg = _family_cfg("moe")
        eng = ServingEngine(cfg, seed=1, attn_backend=_backend(backend))
        reqs = _mk_requests(cfg, np.random.default_rng(7), 4,
                            arrivals=[0, 0, 1, 2])
        sched = _scheduler(eng, reqs)
        sched.run(reqs)
        assert sched.paged and sched.steps_run > 0
        assert sched.paged_steps == sched.steps_run
        n_moe = cfg.n_layers - cfg.first_dense_layers
        prompt = sum(len(r.prompt) for r in reqs)
        assert sched.device_counters["moe_rows"] == (
            prompt + sched.tokens_emitted) * n_moe * cfg.experts_per_token
        if backend == "pallas-splitk":
            assert sched.read_positions < sched.capacity_positions

    @pytest.mark.parametrize("family", ["hybrid", "encdec"])
    def test_other_families_gather(self, family):
        cfg = _family_cfg(family)
        eng = ServingEngine(cfg)
        reqs = _mk_requests(cfg, np.random.default_rng(7), 2,
                            arrivals=[0, 0])
        sched = _scheduler(eng, reqs)
        sched.run(reqs)
        assert not sched.paged and sched.steps_run > 0
        assert sched.paged_steps == 0
        assert sched.read_positions == sched.capacity_positions

    def test_sequence_sharded_step_gathers(self):
        from repro.launch.mesh import make_mesh

        cfg = _family_cfg("transformer")
        eng = ServingEngine(cfg)
        reqs = _dense_stream(cfg, n=3)
        sched = _scheduler(eng, reqs, mesh=make_mesh((1,), ("seq",)))
        sched.run(reqs)
        assert not sched.paged and sched.steps_run > 0
        assert sched.paged_steps == 0


class TestSchedulerEfficiency:
    def test_ragged_stream_beats_padded_static_batching(self):
        """The quantity the ``serving_cb_*`` bench rows gate, asserted
        strictly: on a ragged stream, continuous batching spends fewer
        slot-steps than padding static batches of the same width (every
        slot in a static batch decodes until the batch max)."""
        cfg = _family_cfg("transformer")
        eng = ServingEngine(cfg, attn_backend=_backend("pallas-splitk"))
        rng = np.random.default_rng(11)
        budgets = [1, 8, 1, 8, 1, 8]
        reqs = [Request(rid=i,
                        prompt=rng.integers(0, cfg.vocab_size,
                                            (4,)).astype(np.int32),
                        max_new_tokens=b)
                for i, b in enumerate(budgets)]
        cap = _stream_capacity(eng, reqs)
        layout = eng.cache_layout(cap)
        sched = RequestScheduler(eng.model, eng.params, eng._prefill,
                                 num_slots=NUM_SLOTS, slot_capacity=cap,
                                 layout=layout)
        sched.run(reqs)
        continuous_slot_steps = sched.steps_run * NUM_SLOTS
        static_slot_steps = sum(
            max(budgets[i:i + NUM_SLOTS]) * NUM_SLOTS
            for i in range(0, len(budgets), NUM_SLOTS))
        assert sched.tokens_emitted == sum(budgets)
        assert continuous_slot_steps < static_slot_steps, \
            (continuous_slot_steps, static_slot_steps)

    def test_oversized_request_rejected_up_front(self):
        """A request that can never fit a slot fails loudly at submission,
        not after spinning through the step budget."""
        cfg = _family_cfg("transformer")
        eng = ServingEngine(cfg)
        layout = eng.cache_layout(8)
        sched = RequestScheduler(eng.model, eng.params, eng._prefill,
                                 num_slots=2,
                                 slot_capacity=layout.padded_len(8),
                                 layout=layout)
        rng = np.random.default_rng(0)
        big = Request(rid=0, prompt=rng.integers(0, cfg.vocab_size,
                                                 (4,)).astype(np.int32),
                      max_new_tokens=64)
        with pytest.raises(ValueError, match="slot_capacity"):
            sched.run([big])


# ---------------------------------------------------------------------------
# KVBlockPool / BlockAllocator properties
# ---------------------------------------------------------------------------


class TestBlockAllocatorProperties:
    @settings(max_examples=20, deadline=None)
    @given(seed=st.integers(min_value=0, max_value=99999),
           num_blocks=st.integers(min_value=3, max_value=64))
    def test_random_interleavings_keep_invariants(self, seed, num_blocks):
        """Random admit/retire interleavings: a live page is never handed
        out again, frees reject non-live pages, and the pool returns to
        fully free once every request retires."""
        rng = np.random.default_rng(seed)
        alloc = BlockAllocator(num_blocks)
        total_free = alloc.free_blocks
        live = {}                                   # rid -> page list
        ever = set()
        for step in range(40):
            if live and rng.random() < 0.45:
                rid = list(live)[int(rng.integers(len(live)))]
                alloc.free(live.pop(rid))
            else:
                n = int(rng.integers(1, 4))
                if n > alloc.free_blocks:
                    with pytest.raises(PoolExhausted):
                        alloc.alloc(n)
                    continue
                ids = alloc.alloc(n)
                flat = [b for pages in live.values() for b in pages]
                assert not set(ids) & set(flat), "double allocation"
                assert all(b >= RESERVED_BLOCKS for b in ids), \
                    "reserved page handed out"
                live[step] = ids
                ever.update(ids)
        for pages in live.values():
            alloc.free(pages)
        assert alloc.free_blocks == total_free
        assert alloc.live_blocks == 0
        # double free of anything previously live must be rejected
        if ever:
            with pytest.raises(ValueError):
                alloc.free([next(iter(ever))])

    def test_freed_page_never_read_by_live_request(self):
        """The scheduler-level form of 'never read a freed block': inactive
        slots' writes land in the sink page, so a page freed and re-handed
        to a live request is only ever written by its new owner."""
        layout = KVCacheLayout(block_k=2)
        template = {"k": jnp.zeros((1, 1, 2, 8, 3)),    # [L,B,KV,S,D]
                    "v": jnp.zeros((1, 1, 2, 8, 3)),
                    "length": jnp.zeros((), jnp.int32)}
        from repro.models.kvcache import seq_axis_tree

        axes = seq_axis_tree(template)
        pool = KVBlockPool.build(template, axes, layout, num_blocks=12)
        cache = {"k": jnp.arange(1 * 1 * 2 * 8 * 3, dtype=jnp.float32)
                 .reshape(1, 1, 2, 8, 3) + 1.0,
                 "v": jnp.zeros((1, 1, 2, 8, 3)), "length": None}
        table = pool.admit(split_cache(cache, axes)[0], 8)
        owned = np.asarray(table[:4], np.int32)
        # a retired slot (active=False) writing at any position must only
        # touch the sink page
        before = np.asarray(pool.buffers["k"][..., owned, :, :])
        chunks = {"k": jnp.full((1, 1, 1, 2, 3), -7.0),
                  "v": jnp.full((1, 1, 1, 2, 3), -7.0), "length": None}
        tables = jnp.asarray(np.stack([table]), jnp.int32)
        new = pool.scatter_token(pool.buffers, chunks, tables,
                                 jnp.asarray([5], jnp.int32),
                                 jnp.asarray([False]))
        # pool leaves are [L, B, KV, num_blocks, block_k, D]
        np.testing.assert_array_equal(
            np.asarray(new["k"][..., owned, :, :]), before)
        assert np.all(np.asarray(new["k"][..., SINK_BLOCK, 1, :]) == -7.0)


class TestBlockTableRoundTrip:
    @settings(max_examples=15, deadline=None)
    @given(seed=st.integers(min_value=0, max_value=99999),
           block_k=st.integers(min_value=1, max_value=5),
           n_blocks_req=st.integers(min_value=1, max_value=6))
    def test_admit_gather_is_exact(self, seed, block_k, n_blocks_req):
        """block-table → flat-cache round trip: admit a random cache into
        randomly interleaved physical pages, read each page back where the
        table puts it and gather through the table, and get the original
        buffer back bit-for-bit (beyond the request's own pages the gather
        reads the zero null page)."""
        rng = np.random.default_rng(seed)
        layout = KVCacheLayout(block_k=block_k)
        width = 6
        S_slot = width * block_k
        shape = (2, 1, 2, S_slot, 3)                 # [L,B,KV,S,D]
        template = {"k": jnp.zeros(shape), "v": jnp.zeros(shape),
                    "length": jnp.zeros((), jnp.int32)}
        from repro.models.kvcache import seq_axis_tree

        axes = seq_axis_tree(template)
        pool = KVBlockPool.build(template, axes, layout,
                                 num_blocks=RESERVED_BLOCKS + 3 * width)
        # fragment the free list so this admit lands on interleaved pages
        for _ in range(int(rng.integers(0, 4))):
            ids = pool.allocator.alloc(int(rng.integers(1, 4)))
            if rng.random() < 0.5:
                pool.allocator.free(ids)
        cache = {"k": jnp.asarray(rng.standard_normal(shape), jnp.float32),
                 "v": jnp.asarray(rng.standard_normal(shape), jnp.float32),
                 "length": None}
        table = pool.admit(cache, n_blocks_req * block_k)
        valid = n_blocks_req * block_k
        # each page holds its block_k positions where the table says:
        # [L, B, KV, num_blocks, block_k, D]
        for j in range(n_blocks_req):
            page = slice(j * block_k, (j + 1) * block_k)
            for leaf in ("k", "v"):
                np.testing.assert_array_equal(
                    np.asarray(pool.buffers[leaf][..., table[j], :, :]),
                    np.asarray(cache[leaf][..., page, :]))
        got = pool.gather(pool.buffers, jnp.asarray(table[None], jnp.int32))
        for leaf in ("k", "v"):
            np.testing.assert_array_equal(
                np.asarray(got[leaf][0, ..., :valid, :]),
                np.asarray(cache[leaf][..., :valid, :]))
            # table tail is the null page → exact zeros
            assert np.all(np.asarray(got[leaf][0, ..., valid:, :]) == 0.0)

    def test_scatter_then_gather_reads_back_written_token(self):
        layout = KVCacheLayout(block_k=3)
        shape = (1, 1, 2, 9, 4)
        template = {"k": jnp.zeros(shape), "v": jnp.zeros(shape),
                    "length": jnp.zeros((), jnp.int32)}
        from repro.models.kvcache import seq_axis_tree

        axes = seq_axis_tree(template)
        pool = KVBlockPool.build(template, axes, layout, num_blocks=10)
        cache = {"k": jnp.zeros(shape), "v": jnp.zeros(shape), "length": None}
        table = pool.admit(cache, 9)
        rng = np.random.default_rng(0)
        for pos in (0, 2, 3, 8):                    # block edges + interior
            chunk = {"k": jnp.asarray(rng.standard_normal((1, 1, 1, 2, 4)),
                                      jnp.float32),
                     "v": jnp.zeros((1, 1, 1, 2, 4)), "length": None}
            pool.buffers = pool.scatter_token(
                pool.buffers, chunk, jnp.asarray(table[None], jnp.int32),
                jnp.asarray([pos], jnp.int32), jnp.asarray([True]))
            got = pool.gather(pool.buffers,
                              jnp.asarray(table[None], jnp.int32))
            np.testing.assert_array_equal(
                np.asarray(got["k"][0, ..., pos, :]),
                np.asarray(chunk["k"][0]))


# ---------------------------------------------------------------------------
# cache_seq_axes classification (drives what the pool owns)
# ---------------------------------------------------------------------------


class TestCacheSeqAxes:
    @pytest.mark.parametrize("arch,family", [
        ("internlm2-1.8b", "dense"), ("deepseek-moe-16b", "moe"),
        ("zamba2-7b", "hybrid"), ("seamless-m4t-medium", "encdec"),
        ("mamba2-370m", "ssm"),
    ])
    def test_classification_per_family(self, arch, family):
        cfg = get_config(arch).reduced()
        model = get_model(cfg)
        cache = cache_specs(cfg, ShapeConfig("smoke", 1, 8, "decode"),
                            abstract=True)
        axes = model.cache_seq_axes(cache)
        flat = {jax.tree_util.keystr(p): v
                for p, v in jax.tree_util.tree_flatten_with_path(
                    axes, is_leaf=lambda x: x is None)[0]}
        growing = sorted(k for k, v in flat.items() if v == -2)
        resident = sorted(k for k, v in flat.items() if v is None)
        if family == "ssm":
            assert not growing and resident
        else:
            assert growing, flat
            assert "['length']" in flat and flat["['length']"] is None
        if family == "dense":
            assert growing == ["['k']", "['v']"]
        if family == "encdec":
            assert all("kc" not in k and "vc" not in k for k in growing)
            assert any("kc" in k for k in resident)
        if family == "hybrid":
            assert any("kv" in k for k in growing)
            assert all("states" not in k for k in growing)


# ---------------------------------------------------------------------------
# no-retrace regression (PR 4 _cache_size harness)
# ---------------------------------------------------------------------------


class TestNoRetrace:
    def test_detector_self_test(self):
        """The retrace counter must actually count: a fresh jit traces once
        per distinct input shape."""
        f = jax.jit(lambda x: x * 2)
        f(jnp.zeros((2,)))
        n0 = f._cache_size()
        f(jnp.ones((2,)))                    # same shape → cache hit
        assert f._cache_size() == n0
        f(jnp.zeros((3,)))                   # new shape → one new trace
        assert f._cache_size() == n0 + 1

    def test_admission_and_retirement_never_retrace(self):
        """Nine requests churning through three slots (staggered arrivals,
        mixed budgets): the jitted decode step traces exactly once."""
        cfg = _family_cfg("transformer")
        eng = ServingEngine(cfg, attn_backend=_backend("pallas-splitk"))
        rng = np.random.default_rng(3)
        reqs = [Request(rid=i,
                        prompt=rng.integers(0, cfg.vocab_size,
                                            (int(rng.integers(2, 8)),))
                        .astype(np.int32),
                        max_new_tokens=int(rng.integers(1, 6)),
                        arrival=int(rng.integers(0, 6)))
                for i in range(9)]
        cap = _stream_capacity(eng, reqs)
        sched = RequestScheduler(eng.model, eng.params, eng._prefill,
                                 num_slots=3, slot_capacity=cap,
                                 layout=eng.cache_layout(cap))
        res = sched.run(reqs)
        assert len(res) == 9
        assert sched._step_fn._cache_size() == 1, \
            "admission/retirement retraced the decode step"
        assert sched.pool.allocator.live_blocks == 0


# ---------------------------------------------------------------------------
# forced 4-device sharded-scheduler sweep (`make test-mesh`)
# ---------------------------------------------------------------------------


@pytest.mark.mesh
@pytest.mark.slow
def test_multi_device_sharded_scheduler_parity():
    """Forced 4-device host platform: the sequence-sharded scheduler
    (shard_map over the paged leaves' S axis, ``decode_partial`` +
    ``combine_split_kv`` under vmap) serves the same stream as the
    unsharded scheduler — tokens equal, logits inside the PR 4 multi-shard
    envelope — over 1/2/4-device meshes."""
    script = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import numpy as np, jax
        from repro.configs import get_config
        from repro.core.backends import PallasSplitKAttention
        from repro.launch.mesh import make_mesh
        from repro.serving.engine import ServingEngine
        from repro.serving.scheduler import Request

        assert len(jax.devices()) == 4, jax.devices()
        rng = np.random.default_rng(0)
        cfg = get_config("internlm2-1.8b").reduced()
        eng = ServingEngine(cfg, attn_backend=PallasSplitKAttention(block_k=4))
        reqs = [Request(rid=i,
                        prompt=rng.integers(0, cfg.vocab_size,
                                            (int(rng.integers(2, 7)),))
                        .astype(np.int32),
                        max_new_tokens=int(rng.integers(1, 5)),
                        arrival=int(rng.integers(0, 3)))
                for i in range(5)]
        CAP = 16                                # 4 shards x block_k=4
        ref = {r.rid: r for r in eng.generate_stream(
            list(reqs), num_slots=2, max_request_len=CAP)}
        for d in (1, 2, 4):
            mesh = make_mesh((d,), ("seq",))
            got = eng.generate_stream(list(reqs), num_slots=2,
                                      max_request_len=CAP, mesh=mesh)
            assert sorted(r.rid for r in got) == sorted(ref)
            tol = 1e-6 if d == 1 else 2e-2
            for r in got:
                assert np.array_equal(ref[r.rid].tokens, r.tokens), (d, r.rid)
                assert np.allclose(r.final_logits, ref[r.rid].final_logits,
                                   rtol=tol, atol=tol), (d, r.rid)
        print("SHARDED_SCHEDULER_OK")
    """)
    pythonpath = os.pathsep.join(
        p for p in ("src", os.environ.get("PYTHONPATH", "")) if p
    )
    env = dict(os.environ, PYTHONPATH=pythonpath)
    env.pop("XLA_FLAGS", None)  # the script pins its own device count
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True,
                         cwd=os.path.dirname(os.path.dirname(__file__)),
                         timeout=600)
    assert "SHARDED_SCHEDULER_OK" in out.stdout, out.stderr[-3000:]
