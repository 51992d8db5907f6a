"""Differential parity harness for the decode-attention backend registry.

Sweeps every registered :class:`AttentionBackend` against the ``dense-ref``
oracle at two levels:

* **op level** — raw ``decode(q, k_cache, v_cache, cache_len)`` over dtype ×
  ragged ``cache_len`` edge cases (1, block_k−1, block_k, block_k+1, S), on
  the canonical kernel-native ``[B, KV, S, D]`` cache layout (PR 4: the
  capacity is padded to a ``block_k`` multiple at prefill, so ``S`` here is
  pre-padded and ``pallas-splitk`` *rejects* non-multiple capacities instead
  of silently re-padding per step);
* **model level** — every decoding family's full ``decode_step`` (dense
  transformer, MoE, hybrid shared-attention, enc-dec self+cross) with the
  cache ``length`` forced to the same edge set, asserting logits parity
  within per-dtype tolerances.

Plus property tests (``_hypothesis_compat``) that the chunked-LSE scan is
invariant to the kv-chunk size, registry-unification checks, and the
``decode_mha`` jit-cache regression tests (no retrace across steps with a
growing ``cache_len``; platform-resolved ``interpret`` default).
"""

import pytest

pytest.importorskip("jax")  # accelerator dep is optional for the numpy core

import jax
import jax.numpy as jnp
import numpy as np
from _hypothesis_compat import given, settings, st

from repro.configs import get_config
from repro.core.backends import (
    ATTENTION_BACKEND_NAMES,
    ChunkedLseAttention,
    PallasSplitKAttention,
    get_backend,
)
from repro.models import encdec, hybrid, moe, transformer
from repro.models.registry import get_model, input_specs
from repro.configs.base import ShapeConfig

# Small block so the edge sweep brackets a real block boundary without
# padding tiny smoke caches to 512.
BLOCK_K = 8
CAP = 16                       # decode cache capacity in the family sweep

TOL = {jnp.float32: dict(rtol=1e-5, atol=1e-5),
       jnp.bfloat16: dict(rtol=2e-2, atol=2e-2)}

# One arch per decoding family (ssm has no decode attention).
FAMILY_ARCHS = {
    "transformer": "internlm2-1.8b",
    "moe": "deepseek-moe-16b",
    "hybrid": "zamba2-7b",
    "encdec": "seamless-m4t-medium",
}


def _backend(name):
    """Registered backend configured for tiny smoke shapes."""
    if name == "pallas-splitk":
        return PallasSplitKAttention(block_k=BLOCK_K)
    if name == "chunked-lse":
        return ChunkedLseAttention(kv_chunk=BLOCK_K)  # force a multi-chunk scan
    return get_backend("attention", name)


def _edge_cache_lens(cap: int, block_k: int = BLOCK_K):
    """Ragged valid-prefix edges: 0/1, the block_k boundary, full cache."""
    lens = {0, 1, block_k - 1, block_k, block_k + 1, cap - 1}
    return sorted(l for l in lens if 0 <= l < cap)


# ---------------------------------------------------------------------------
# op level
# ---------------------------------------------------------------------------


class TestOpParity:
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("backend", ATTENTION_BACKEND_NAMES)
    def test_matches_dense_ref_across_cache_lens(self, backend, dtype):
        rng = np.random.default_rng(0)
        # capacity pre-padded to a BLOCK_K multiple (the prefill layout
        # contract); raggedness lives in cache_len, swept below
        B, H, KV, S, D = 2, 4, 2, 24, 16
        q = jnp.asarray(rng.standard_normal((B, 1, H, D)), dtype)
        k = jnp.asarray(rng.standard_normal((B, KV, S, D)), dtype)
        v = jnp.asarray(rng.standard_normal((B, KV, S, D)), dtype)
        ref_be = get_backend("attention", "dense-ref")
        be = _backend(backend)
        for cache_len in (1, BLOCK_K - 1, BLOCK_K, BLOCK_K + 1, 20, S):
            want = ref_be.decode(q, k, v, cache_len)
            got = be.decode(q, k, v, cache_len)
            assert got.shape == (B, 1, H, D) and got.dtype == q.dtype
            np.testing.assert_allclose(
                np.asarray(got, np.float32), np.asarray(want, np.float32),
                err_msg=f"{backend} cache_len={cache_len}", **TOL[dtype])

    def test_splitk_rejects_unpadded_capacity(self):
        """The per-step re-pad is gone by design: a capacity that violates
        the backend's KVCacheLayout must fail loudly, not silently copy."""
        rng = np.random.default_rng(0)
        B, H, KV, S, D = 1, 2, 2, 20, 8          # 20 % BLOCK_K(8) != 0
        q = jnp.asarray(rng.standard_normal((B, 1, H, D)), jnp.float32)
        k = jnp.asarray(rng.standard_normal((B, KV, S, D)), jnp.float32)
        be = PallasSplitKAttention(block_k=BLOCK_K)
        assert be.cache_layout(S).padded_len(S) == 24
        with pytest.raises(ValueError, match="not a multiple of"):
            be.decode(q, k, k, 5)

    @pytest.mark.parametrize("backend", ATTENTION_BACKEND_NAMES)
    def test_traced_cache_len_under_jit(self, backend):
        """cache_len must be a traced operand, not a static recompile key."""
        rng = np.random.default_rng(1)
        B, H, KV, S, D = 1, 4, 4, 16, 8
        q = jnp.asarray(rng.standard_normal((B, 1, H, D)), jnp.float32)
        k = jnp.asarray(rng.standard_normal((B, KV, S, D)), jnp.float32)
        v = jnp.asarray(rng.standard_normal((B, KV, S, D)), jnp.float32)
        be = _backend(backend)
        f = jax.jit(lambda cl: be.decode(q, k, v, cl))
        ref_be = get_backend("attention", "dense-ref")
        for cl in (1, 5, S):
            np.testing.assert_allclose(
                np.asarray(f(jnp.asarray(cl, jnp.int32))),
                np.asarray(ref_be.decode(q, k, v, cl)),
                rtol=1e-5, atol=1e-5)

    @settings(max_examples=15, deadline=None)
    @given(
        kv_chunk=st.sampled_from([1, 2, 3, 5, 8, 16, 24, 64]),
        cache_len=st.integers(min_value=1, max_value=24),
        seed=st.integers(min_value=0, max_value=9999),
    )
    def test_property_chunked_lse_chunk_size_invariant(self, kv_chunk,
                                                       cache_len, seed):
        """The chunked-LSE scan is a tiling of the same softmax: its output
        must be invariant to kv_chunk (and equal to the dense oracle)."""
        from repro.models.attention import decode_attention, decode_attention_dense

        rng = np.random.default_rng(seed)
        B, H, KV, S, D = 2, 4, 2, 24, 8
        q = jnp.asarray(rng.standard_normal((B, 1, H, D)), jnp.float32)
        k = jnp.asarray(rng.standard_normal((B, KV, S, D)), jnp.float32)
        v = jnp.asarray(rng.standard_normal((B, KV, S, D)), jnp.float32)
        got = decode_attention(q, k, v, cache_len=jnp.asarray(cache_len),
                               kv_chunk=kv_chunk)
        want = decode_attention_dense(q, k, v, cache_len=cache_len)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# model level — every decoding family's decode_step
# ---------------------------------------------------------------------------


def _family_fixture(family):
    """(params, token, cache, decode_fn_factory) for one family."""
    cfg = get_config(FAMILY_ARCHS[family]).reduced()
    model = get_model(cfg)
    params = model.init(jax.random.key(0))
    shape = ShapeConfig("smoke", 8, 2, "prefill")
    batch = input_specs(cfg, shape, abstract=False, seed=0)
    logits, cache = jax.jit(lambda p, b: model.prefill(p, b, CAP))(params, batch)
    token = jnp.argmax(logits, axis=-1).astype(jnp.int32)

    mod = {"transformer": transformer, "moe": moe, "hybrid": hybrid,
           "encdec": encdec}[family]

    def decode_fn(be):
        return jax.jit(lambda p, t, c: mod.decode_step(p, t, c, cfg,
                                                       attn_backend=be))

    return params, token, cache, decode_fn


@pytest.fixture(scope="module", params=sorted(FAMILY_ARCHS))
def family_case(request):
    return request.param, _family_fixture(request.param)


# Logits tolerance per KV-cache dtype.  With an fp32 cache every backend
# computes the softmax end-to-end in fp32 and the per-step attention outputs
# round to identical bf16 activations — measured diff is exactly 0.0 across
# all four families; 1e-4 leaves platform headroom.  With a bf16 cache the
# backends round the probability row at different points (before vs after
# normalization), and the MoE router amplifies that to ~2.3e-2 on worst-case
# logits — the same mechanism behind the kimi-k2 decode-drift regression
# (``test_models_smoke``).
FAMILY_TOL = {jnp.float32: dict(rtol=1e-4, atol=1e-4),
              jnp.bfloat16: dict(rtol=3e-2, atol=3e-2)}


def _cache_as(cache, dtype):
    cast = (lambda a: a.astype(dtype)
            if hasattr(a, "dtype") and a.dtype == jnp.bfloat16 else a)
    return jax.tree.map(cast, cache)


class TestModelParity:
    @pytest.mark.parametrize("cache_dtype", [jnp.bfloat16, jnp.float32],
                             ids=["bf16", "fp32"])
    @pytest.mark.parametrize("backend",
                             [n for n in ATTENTION_BACKEND_NAMES
                              if n != "dense-ref"])
    def test_decode_step_logits_match_dense_ref(self, family_case, backend,
                                                cache_dtype):
        family, (params, token, cache, decode_fn) = family_case
        ref_fn = decode_fn(get_backend("attention", "dense-ref"))
        got_fn = decode_fn(_backend(backend))
        base = _cache_as(cache, cache_dtype)
        for cache_len in _edge_cache_lens(CAP):
            c = dict(base, length=jnp.asarray(cache_len, jnp.int32))
            ref_logits, ref_cache = ref_fn(params, token, c)
            got_logits, got_cache = got_fn(params, token, c)
            np.testing.assert_allclose(
                np.asarray(got_logits, np.float32),
                np.asarray(ref_logits, np.float32),
                err_msg=f"{family}/{backend} cache_len={cache_len}",
                **FAMILY_TOL[cache_dtype])
            assert int(got_cache["length"]) == cache_len + 1

    def test_engine_tokens_identical_across_backends(self):
        """End-to-end: greedy generation is backend-invariant."""
        from repro.serving.engine import ServingEngine

        cfg = get_config("internlm2-1.8b").reduced()
        rng = np.random.default_rng(3)
        prompts = rng.integers(0, cfg.vocab_size, size=(2, 6)).astype(np.int32)
        outs = {}
        for name in ATTENTION_BACKEND_NAMES:
            eng = ServingEngine(cfg, seed=0, attn_backend=_backend(name))
            outs[name] = eng.generate(prompts, max_new_tokens=4).tokens
        for name, toks in outs.items():
            np.testing.assert_array_equal(toks, outs["dense-ref"], err_msg=name)


# ---------------------------------------------------------------------------
# registry unification + routing
# ---------------------------------------------------------------------------


class TestRegistry:
    def test_attention_names_registered(self):
        assert set(ATTENTION_BACKEND_NAMES) == {
            "dense-ref", "chunked-lse", "pallas-splitk"}
        for name in ATTENTION_BACKEND_NAMES:
            assert get_backend("attention", name).name == name

    def test_defaults_per_kind(self):
        assert get_backend("attention", None).name == "dense-ref"
        assert get_backend("compute", None).name == "numpy-fast"
        # legacy one-argument form still means a compute backend
        assert get_backend("numpy-csr").name == "numpy-csr"
        assert get_backend(None).name == "numpy-fast"

    def test_instances_pass_through(self):
        be = ChunkedLseAttention(kv_chunk=64)
        assert get_backend("attention", be) is be
        assert be.state_key == "chunked-lse:kc64"

    def test_wrong_kind_instance_rejected_at_resolution(self):
        from repro.core.backends import NumpyFastBackend

        with pytest.raises(TypeError, match="not a attention backend"):
            get_backend("attention", NumpyFastBackend())
        with pytest.raises(TypeError, match="not a compute backend"):
            get_backend("compute", ChunkedLseAttention())

    def test_unknown_names_rejected(self):
        with pytest.raises(ValueError, match="unknown attention backend"):
            get_backend("attention", "flash-decoding-v3")
        with pytest.raises(ValueError, match="unknown compute backend"):
            get_backend("compute", "cuda-cusparse")
        with pytest.raises(ValueError, match="unknown backend kind"):
            get_backend("communication", "nccl")

    def test_router_picks_by_platform_and_cache(self):
        from repro.serving.router import route_attention_backend

        cfg = get_config("internlm2-1.8b").reduced()
        assert route_attention_backend(cfg, platform="tpu") == "pallas-splitk"
        assert route_attention_backend(cfg, max_len=32_768,
                                       platform="cpu") == "chunked-lse"
        assert route_attention_backend(cfg, max_len=512,
                                       platform="cpu") == "dense-ref"
        ssm = get_config("mamba2-370m").reduced()
        assert route_attention_backend(ssm, platform="tpu") == "dense-ref"

    def test_engine_auto_routes(self):
        from repro.serving.engine import ServingEngine
        from repro.serving.router import route_attention_backend

        cfg = get_config("internlm2-1.8b").reduced()
        eng = ServingEngine(cfg, seed=0)
        assert eng.attn_backend.name == route_attention_backend(cfg)
        assert eng.attn_backend.name == (
            "pallas-splitk" if jax.default_backend() == "tpu" else "dense-ref")

    def test_route_decode_plan_bundles_layout(self):
        """The router's DecodePlan carries the KVCacheLayout the backend's
        caches must be allocated with (block_k padding for splitk, identity
        for the view-based backends)."""
        from repro.core.backends import SPLITK_BLOCK_K_TABLE
        from repro.serving.router import route_decode_plan

        cfg = get_config("internlm2-1.8b").reduced()
        tpu = route_decode_plan(cfg, max_len=1000, platform="tpu")
        assert tpu.attn_backend == "pallas-splitk"
        assert tpu.cache_layout.block_k == 128          # table: ≤1024 → 128
        assert tpu.cache_layout.padded_len(1000) == 1024
        cpu = route_decode_plan(cfg, max_len=512, platform="cpu")
        assert cpu.attn_backend == "dense-ref"
        assert cpu.cache_layout.block_k == 1
        assert cpu.cache_layout.padded_len(512) == 512
        assert SPLITK_BLOCK_K_TABLE[0][1] == 64  # table shape sanity

    def test_engine_cache_layout_follows_backend(self):
        from repro.core.backends import KVCacheLayout
        from repro.serving.engine import ServingEngine

        cfg = get_config("internlm2-1.8b").reduced()
        eng = ServingEngine(cfg, seed=0,
                            attn_backend=PallasSplitKAttention(block_k=BLOCK_K))
        assert eng.cache_layout(20) == KVCacheLayout(block_k=BLOCK_K)
        assert eng.cache_layout(20).padded_len(20) == 24
        ref = ServingEngine(cfg, seed=0)
        assert ref.cache_layout(20).padded_len(20) == 20


# ---------------------------------------------------------------------------
# decode_mha jit-cache regressions (interpret default + no retrace)
# ---------------------------------------------------------------------------


class TestDecodeMhaJitCache:
    def test_interpret_default_resolved_from_platform(self):
        from repro.kernels import resolve_interpret

        # this suite runs on CPU/GPU hosts; on a real TPU the default flips
        assert resolve_interpret(None) == (jax.default_backend() != "tpu")

    def test_no_retrace_across_growing_cache_len(self):
        """One compiled entry serves the whole decode loop: cache_len is a
        traced operand, so steps 1..N hit the same jit cache entry."""
        from repro.kernels.decode_attention.ops import (
            decode_mha, decode_mha_cache_size)

        rng = np.random.default_rng(0)
        B, H, KV, S, D = 1, 4, 2, 32, 8
        q = jnp.asarray(rng.standard_normal((B, H, D)), jnp.float32)
        k = jnp.asarray(rng.standard_normal((B, KV, S, D)), jnp.float32)
        v = jnp.asarray(rng.standard_normal((B, KV, S, D)), jnp.float32)
        decode_mha(q, k, v, jnp.asarray(1, jnp.int32), block_k=BLOCK_K)
        size_after_first = decode_mha_cache_size()
        for cache_len in range(2, 12):
            decode_mha(q, k, v, jnp.asarray(cache_len, jnp.int32),
                       block_k=BLOCK_K)
        assert decode_mha_cache_size() == size_after_first

    def test_backend_decode_no_retrace(self):
        """Same property through the pallas-splitk backend (native cache)."""
        from repro.kernels.decode_attention.ops import decode_mha_cache_size

        rng = np.random.default_rng(1)
        be = PallasSplitKAttention(block_k=BLOCK_K)
        B, H, KV, S, D = 1, 2, 2, 24, 8   # capacity = layout.padded_len(20)
        q = jnp.asarray(rng.standard_normal((B, 1, H, D)), jnp.float32)
        k = jnp.asarray(rng.standard_normal((B, KV, S, D)), jnp.float32)
        v = jnp.asarray(rng.standard_normal((B, KV, S, D)), jnp.float32)
        be.decode(q, k, v, 1)
        size_after_first = decode_mha_cache_size()
        for cache_len in range(2, 8):
            be.decode(q, k, v, cache_len)
        assert decode_mha_cache_size() == size_after_first
