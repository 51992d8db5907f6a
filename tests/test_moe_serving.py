"""deepseek-moe-16b on the serving path, against plain float32 equations.

At the reduced size on the CPU, with seeded weights: the published gate
(softmax over every expert, top k taken unnormalised) beside kimi-k2's
renormalised one; the dropless expert layer where capacity dispatch
drops; one chip's share of the experts tied to the uncut layer; and
prefill then paged decode through ``RequestScheduler`` against a full
forward pass of the equations below, which import nothing of the
program's model code.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import moe
from repro.models.registry import get_model
from repro.serving.engine import ServingEngine
from repro.serving.scheduler import Request, RequestScheduler

HI = jax.lax.Precision.HIGHEST


def _cfg(**kw):
    return dataclasses.replace(get_config("deepseek-moe-16b").reduced(), **kw)


# ---------------------------------------------------------------------------
# the reference equations (float32, every product at full precision)


def _rms(x, w, eps):
    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _swiglu(x, wg, wu, wd):
    g = jnp.dot(x, wg, precision=HI)
    return jnp.dot(jax.nn.silu(g) * jnp.dot(x, wu, precision=HI), wd,
                   precision=HI)


def ref_expert_layer(p, x, cfg, held=None, experts_dtype=jnp.float32):
    """The expert layer for tokens x [T, d]: softmax over all experts, the
    top k, weights renormalised only where the config says so; the held
    experts' part (``held`` = [e0, e1), weights ``p`` of those experts),
    and the shared experts where ``held`` is None."""
    e0, e1 = held or (0, cfg.n_experts)
    probs = jax.nn.softmax(jnp.dot(x, p["router"], precision=HI), -1)
    w, idx = jax.lax.top_k(probs, cfg.experts_per_token)
    if cfg.moe_norm_topk:
        w = w / w.sum(-1, keepdims=True)
    out = jnp.zeros_like(x)
    low = lambda a: a.astype(experts_dtype).astype(jnp.float32)
    for e in range(e0, e1):
        gate = jnp.sum(jnp.where(idx == e, w, 0.0), -1)
        y = _swiglu(low(x), low(p["w_gate"][e - e0]), low(p["w_up"][e - e0]),
                    low(p["w_down"][e - e0]))
        out = out + gate[:, None] * y
    if held is None and cfg.n_shared_experts:
        s = p["shared"]
        out = out + _swiglu(x, s["wi_gate"], s["wi_up"], s["wo"])
    return out


def ref_logits(params, seq, cfg, kv_dtype=jnp.float32,
               experts_dtype=jnp.float32):
    """Logits [S, V] of the whole sequence; ``kv_dtype`` / ``experts_dtype``
    round the cached K and V / the experts' operands (the mutations the
    comparison must catch)."""
    S, H, Dh = len(seq), cfg.n_heads, cfg.d_head
    x = params["embed"][jnp.asarray(seq)]
    inv = 1.0 / cfg.rope_theta ** (jnp.arange(0, Dh, 2) / Dh)
    ang = jnp.arange(S)[:, None] * inv[None]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]

    def rope(t):
        a, b = t[..., : Dh // 2], t[..., Dh // 2:]
        return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], -1)

    low = lambda a: a.astype(kv_dtype).astype(jnp.float32)
    mask = jnp.tril(jnp.ones((S, S), bool))
    stacks = [(params["dense_blocks"], True), (params["moe_blocks"], False)]
    for blocks, dense in stacks:
        for l in range(blocks["ln_attn"].shape[0]):
            b = jax.tree.map(lambda a: a[l], blocks)
            a = b["attn"]
            h = _rms(x, b["ln_attn"], cfg.norm_eps)
            q = rope(jnp.einsum("sd,dhk->shk", h, a["wq"], precision=HI))
            k = low(rope(jnp.einsum("sd,dhk->shk", h, a["wk"], precision=HI)))
            v = low(jnp.einsum("sd,dhk->shk", h, a["wv"], precision=HI))
            s = jnp.einsum("shk,thk->hst", q, k, precision=HI) / np.sqrt(Dh)
            pr = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), -1)
            o = jnp.einsum("hst,thk->shk", pr, v, precision=HI)
            x = x + jnp.einsum("shk,hkd->sd", o, a["wo"], precision=HI)
            h = _rms(x, b["ln_mlp"], cfg.norm_eps)
            if dense:
                m = b["mlp"]
                x = x + _swiglu(h, m["wi_gate"], m["wi_up"], m["wo"])
            else:
                x = x + ref_expert_layer(b["moe"], h, cfg,
                                         experts_dtype=experts_dtype)
    x = _rms(x, params["ln_f"], cfg.norm_eps)
    return jnp.dot(x, params["unembed"].T, precision=HI)


def _f32(params):
    return jax.tree.map(lambda a: a.astype(jnp.float32), params)


# ---------------------------------------------------------------------------
# the gate


def test_published_gate_takes_top_k_unnormalised():
    cfg = get_config("deepseek-moe-16b")
    assert not cfg.moe_norm_topk and cfg.norm_eps == 1e-6
    assert (cfg.n_experts, cfg.experts_per_token, cfg.moe_d_ff,
            cfg.n_shared_experts) == (64, 6, 1408, 2)
    logits = jax.random.normal(jax.random.key(0), (5, 64)) * 3
    w, idx = moe.route_topk(logits, 6, cfg.moe_norm_topk)
    probs = np.asarray(jax.nn.softmax(logits, -1))
    want_idx = np.argsort(-probs, -1)[:, :6]
    np.testing.assert_array_equal(np.asarray(idx), want_idx)
    np.testing.assert_allclose(np.asarray(w),
                               np.take_along_axis(probs, want_idx, -1),
                               rtol=1e-6)
    assert (np.asarray(w).sum(-1) < 1).all()


def test_kimi_gate_unchanged():
    """kimi-k2 keeps its renormalised gate, bit for bit: a softmax over
    the top k logits."""
    cfg = get_config("kimi-k2-1t-a32b")
    assert cfg.moe_norm_topk
    logits = jax.random.normal(jax.random.key(1), (7, 384))
    w, idx = moe.route_topk(logits, 8, cfg.moe_norm_topk)
    vals, want_idx = jax.lax.top_k(logits, 8)
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(want_idx))
    np.testing.assert_array_equal(np.asarray(w),
                                  np.asarray(jax.nn.softmax(vals, -1)))


# ---------------------------------------------------------------------------
# the expert layer


def _layer_params(cfg, seed=0):
    p = _f32(moe.init_moe_ffn(jax.random.key(seed), cfg))
    return jax.tree.map(lambda a: a * 4.0, p)    # logits spread like trained


def test_dropless_when_every_token_picks_one_expert():
    """Every token's top expert is expert 0: capacity dispatch drops most
    of them; the serving layer computes them all and matches the
    reference."""
    cfg = _cfg()
    p = _layer_params(cfg)
    p["router"] = p["router"].at[:, 0].set(0.0)
    x = jax.random.normal(jax.random.key(2), (1, 24, cfg.d_model))
    x = x.at[..., 0].set(40.0)
    p["router"] = p["router"].at[0, 0].set(1.0)
    idx = jax.lax.top_k(jnp.dot(x[0], p["router"], precision=HI), 2)[1]
    assert (np.asarray(idx[:, 0]) == 0).all()
    _, metrics = moe.moe_ffn(p, x, cfg)
    assert float(metrics["drop_frac"]) > 0.3
    with jax.default_matmul_precision("highest"):
        out, counts = moe.moe_ffn_serve(p, x, cfg)
    want = ref_expert_layer(p, x[0], cfg)
    np.testing.assert_allclose(np.asarray(out[0]), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    assert int(counts[0]) == 24 * cfg.experts_per_token


@pytest.mark.parametrize("norm_topk", [False, True])
def test_shares_add_up_to_the_uncut_layer(norm_topk):
    """Two chips' shares, experts [0, E/2) and [E/2, E): each routes over
    all E and computes its own experts' part; the two parts plus the
    shared experts, counted once, give the uncut reference layer."""
    cfg = _cfg(moe_norm_topk=norm_topk)
    E = cfg.n_experts
    p = _layer_params(cfg, seed=3)
    x = jax.random.normal(jax.random.key(4), (2, 9, cfg.d_model))
    parts = []
    with jax.default_matmul_precision("highest"):
        for e0, e1 in ((0, E // 2), (E // 2, E)):
            # the held part alone: the shared experts are added once below
            share_cfg = dataclasses.replace(cfg, held_experts=(e0, e1),
                                            n_shared_experts=0)
            share = {"router": p["router"],
                     **{k: p[k][e0:e1] for k in ("w_gate", "w_up", "w_down")}}
            out, counts = moe.moe_ffn_serve(share, x, share_cfg)
            parts.append(out)
            want = ref_expert_layer(share, x.reshape(-1, cfg.d_model), cfg,
                                    held=(e0, e1))
            np.testing.assert_allclose(
                np.asarray(out).reshape(-1, cfg.d_model), np.asarray(want),
                rtol=1e-5, atol=1e-5)
            assert 0 < int(counts[1]) <= E // 2
        whole, _ = moe.moe_ffn_serve(p, x, cfg)
    shared = _swiglu(x.reshape(-1, cfg.d_model), p["shared"]["wi_gate"],
                     p["shared"]["wi_up"], p["shared"]["wo"])
    total = (parts[0] + parts[1]).reshape(-1, cfg.d_model) + shared
    uncut = ref_expert_layer(p, x.reshape(-1, cfg.d_model), cfg)
    np.testing.assert_allclose(np.asarray(total), np.asarray(uncut),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(whole).reshape(-1, cfg.d_model),
                               np.asarray(uncut), rtol=1e-5, atol=1e-5)


def test_held_share_model_routes_over_all_experts():
    """A model that holds half the experts keeps the router at every
    expert and holds only its own experts' weights."""
    cfg = _cfg(held_experts=(4, 8))
    params = get_model(cfg).init(jax.random.key(0))
    blk = params["moe_blocks"]["moe"]
    assert blk["router"].shape[-1] == cfg.n_experts == 8
    assert blk["w_gate"].shape[1] == blk["w_down"].shape[1] == 4


# ---------------------------------------------------------------------------
# prefill, then paged decode through the scheduler, against the equations

# Both sides compute in float32; with every product at full precision they
# differ only in the order of their sums, by about 1e-6 of the logits'
# scale (|logits| ~ 1) after the reduced model's four layers.  3e-5 keeps
# ten times that as room, and a cache or an expert layer rounded to bf16
# moves the logits by 1e-3 or more (the mutation cases below).
LOGIT_TOL = 3e-5


def _served(cfg, params, backend="dense-ref"):
    eng = ServingEngine(cfg, params=params, attn_backend=backend)
    rng = np.random.default_rng(11)
    reqs = [Request(rid=i, prompt=rng.integers(0, cfg.vocab_size, (n,))
                    .astype(np.int32), max_new_tokens=m)
            for i, (n, m) in enumerate([(7, 5), (3, 6), (12, 2), (5, 4)])]
    cap = eng.cache_layout(16).padded_len(16)
    sched = RequestScheduler(eng.model, eng.params, eng._prefill,
                             num_slots=2, slot_capacity=cap,
                             layout=eng.cache_layout(cap))
    with jax.default_matmul_precision("highest"):
        results = sched.run(reqs)
    return sched, reqs, {r.rid: r for r in results}


def _widest_gap(cfg, params, reqs, results, **mutation):
    gap = 0.0
    for r in reqs:
        res = results[r.rid]
        seq = np.concatenate([r.prompt, res.tokens])
        want = ref_logits(params, seq, cfg, **mutation)[-1]
        got = res.final_logits[: cfg.vocab_size]
        gap = max(gap, float(np.abs(got - np.asarray(want)).max()))
    return gap


@pytest.fixture(scope="module")
def served_f32():
    cfg = _cfg()
    params = _f32(get_model(cfg).init(jax.random.key(5)))
    return cfg, params, _served(cfg, params)


def test_paged_decode_matches_reference(served_f32):
    cfg, params, (sched, reqs, results) = served_f32
    assert sched.paged and sched.paged_steps == sched.steps_run > 0
    with jax.default_matmul_precision("highest"):
        assert _widest_gap(cfg, params, reqs, results) <= LOGIT_TOL


@pytest.mark.parametrize("mutation", [{"kv_dtype": jnp.bfloat16},
                                      {"experts_dtype": jnp.bfloat16}],
                         ids=["bf16_cache", "bf16_experts"])
def test_tolerance_catches_bf16_where_f32_is_stated(served_f32, mutation):
    """The served f32 logits against the equations with the cache, or the
    expert layer, rounded to bf16: beyond the tolerance, so a program
    that computed them so would fail the comparison."""
    cfg, params, (sched, reqs, results) = served_f32
    with jax.default_matmul_precision("highest"):
        assert _widest_gap(cfg, params, reqs, results,
                           **mutation) > 10 * LOGIT_TOL


def test_counters_count_the_held_rows(served_f32):
    """``moe_rows`` counts every held assignment the kernel computed, of
    the prefills and of each decode step's occupied slots (a vacant slot
    is neither computed nor counted), and ``moe_groups_read`` the experts
    read per call."""
    cfg, params, (sched, reqs, results) = served_f32
    n_moe = cfg.n_layers - cfg.first_dense_layers
    k = cfg.experts_per_token
    prompt = sum(len(r.prompt) for r in reqs)
    rows = sched.device_counters["moe_rows"]
    assert rows == (prompt + sched.tokens_emitted) * n_moe * k
    assert sched.tokens_emitted < sched.steps_run * sched.num_slots
    calls = (len(reqs) + sched.steps_run) * n_moe
    assert calls <= sched.device_counters["moe_groups_read"] \
        <= calls * cfg.n_experts
