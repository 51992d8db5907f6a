"""Plain float32 reference of a DeepSeekMoE decoder, one chip's share of
its experts (deepseek-moe-16b).

Per layer, from the published description (arXiv:2401.06066 and the
model's ``config.json``): RMSNorm, multi-head attention as the dense
reference has it (Q/K/V projections, rotary embedding on the two halves of
each head, causal softmax, output projection, residual); RMSNorm, then
the feed-forward and residual.  The first ``first_k_dense_replace``
layers have a dense SwiGLU MLP.  Every other layer has the expert layer:
the gate is a softmax over all routed experts (the published count, the
router's width) and each token takes its ``num_experts_per_tok`` largest,
their weights used as they are (``norm_topk_prob`` false) or renormalised
to sum to 1; every routed expert is a SwiGLU MLP of width
``moe_intermediate_size``, and the ``n_shared_experts`` shared experts are
one always-on SwiGLU MLP of ``n_shared_experts`` times that width.  The
layer adds the shared MLP and, over the experts this chip holds
(``held_experts``), each token's gate weight times that expert's output;
what the other experts would add is left out, as the program leaves it
out.  No token is dropped.  A final RMSNorm and the output head give the
logits.

Everything runs in float32 with every product at ``Precision.HIGHEST``.
It reads the weight arrays the benchmark made
(``bench/systems/moe_decoder.make_params``) and imports nothing of the
program; the dense reference's helpers serve it.  ``quant="fp8"`` is the
control: every matmul operand rounded to float8 e4m3, as the dense
reference's control does.
"""

from __future__ import annotations

from functools import lru_cache
from typing import Optional

import numpy as np

from bench.refs.dense_decoder import _fp8


def dims(config: dict) -> dict:
    D = int(config["hidden_size"])
    H = int(config["num_attention_heads"])
    e0, e1 = config["held_experts"]
    return {"L": int(config["num_hidden_layers"]), "D": D, "H": H,
            "KV": int(config["num_key_value_heads"]), "Dh": D // H,
            "F": int(config["intermediate_size"]),
            "f": int(config["moe_intermediate_size"]),
            "E": int(config["published"]["n_routed_experts"]),
            "e0": int(e0), "held": int(e1) - int(e0),
            "k": int(config["num_experts_per_tok"]),
            "shared": int(config["n_shared_experts"]),
            "first_dense": int(config["first_k_dense_replace"]),
            "norm_topk": bool(config["norm_topk_prob"]),
            "V": int(config["vocab_size"]),
            "theta": float(config["rope_theta"]),
            "eps": float(config["rms_norm_eps"]),
            "tied": bool(config["tie_word_embeddings"])}


def forward(params, seq, d: dict, quant: Optional[str] = None):
    """Final normed hidden states ``[S, D]`` (f32) for token ids ``seq``."""
    import jax
    import jax.numpy as jnp

    P = jax.lax.Precision.HIGHEST
    f32 = lambda a: a.astype(jnp.float32)
    if quant is None:
        W = f32
        A = lambda a: a
    elif quant == "fp8":
        W = lambda w: _fp8(f32(w), axis=None)
        A = lambda a: _fp8(a, axis=tuple(range(1, a.ndim)))
    else:
        raise ValueError(f"unknown quant {quant!r}")
    S = seq.shape[0]
    H, KV, Dh, eps = d["H"], d["KV"], d["Dh"], d["eps"]
    G = H // KV

    def rms(x, w):
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
            * f32(w)

    inv = 1.0 / (d["theta"] ** (jnp.arange(0, Dh, 2, dtype=jnp.float32)
                                / Dh))
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]

    def rope(t):                                     # [S, heads, Dh]
        t1, t2 = t[..., : Dh // 2], t[..., Dh // 2:]
        return jnp.concatenate([t1 * cos - t2 * sin, t1 * sin + t2 * cos], -1)

    causal = jnp.tril(jnp.ones((S, S), bool))

    def swiglu(h, wg, wu, wd):
        g = jnp.einsum("sd,df->sf", h, W(wg), precision=P)
        u = jnp.einsum("sd,df->sf", h, W(wu), precision=P)
        return jnp.einsum("sf,fd->sd", A(jax.nn.silu(g) * u), W(wd),
                          precision=P)

    def attention(x, blk):
        a = blk["attn"]
        h = A(rms(x, blk["ln_attn"]))
        q = rope(jnp.einsum("sd,dhk->shk", h, W(a["wq"]), precision=P))
        k = rope(jnp.einsum("sd,dhk->shk", h, W(a["wk"]), precision=P))
        v = jnp.einsum("sd,dhk->shk", h, W(a["wv"]), precision=P)
        s = jnp.einsum("sngk,tnk->ngst", q.reshape(S, KV, G, Dh), k,
                       precision=P) / np.sqrt(Dh)
        p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        o = jnp.einsum("ngst,tnk->sngk", p, v, precision=P).reshape(S, H, Dh)
        return x + jnp.einsum("shk,hkd->sd", A(o), W(a["wo"]), precision=P)

    def dense_layer(x, blk):
        x = attention(x, blk)
        m = blk["mlp"]
        h = A(rms(x, blk["ln_mlp"]))
        return x + swiglu(h, m["wi_gate"], m["wi_up"], m["wo"]), None

    def moe_layer(x, blk):
        x = attention(x, blk)
        m = blk["moe"]
        h = A(rms(x, blk["ln_mlp"]))
        probs = jax.nn.softmax(
            jnp.einsum("sd,de->se", h, W(m["router"]), precision=P), -1)
        w, idx = jax.lax.top_k(probs, d["k"])
        if d["norm_topk"]:
            w = w / w.sum(-1, keepdims=True)
        # each token's gate weight on each held expert (0 where unchosen)
        held = d["e0"] + jnp.arange(d["held"])
        gate = jnp.sum(jnp.where(idx[:, :, None] == held, w[:, :, None],
                                 0.0), axis=1)                  # [S, held]

        def expert(acc, e):
            y = swiglu(h, m["w_gate"][e], m["w_up"][e], m["w_down"][e])
            return acc + gate[:, e, None] * y, None

        routed, _ = jax.lax.scan(expert, jnp.zeros_like(x),
                                 jnp.arange(d["held"]))
        s = m["shared"]
        return x + routed + swiglu(h, s["wi_gate"], s["wi_up"], s["wo"]), None

    x = f32(jnp.take(params["embed"], seq, axis=0))
    if d["first_dense"]:
        x, _ = jax.lax.scan(dense_layer, x, params["dense_blocks"])
    x, _ = jax.lax.scan(moe_layer, x, params["moe_blocks"])
    return rms(x, params["ln_f"]), W, A


def _logits(params, h, d, W, A):
    import jax
    import jax.numpy as jnp

    table = params["embed"] if d["tied"] else params["unembed"]
    return jnp.einsum("sd,vd->sv", A(h), W(table[: d["V"]]),
                      precision=jax.lax.Precision.HIGHEST)


@lru_cache(maxsize=None)
def _gap_fn(key: tuple, control: bool):
    """Jitted ``(params, seq, targets) -> gaps [S]``, as the dense
    reference's."""
    import jax
    import jax.numpy as jnp

    d = dict(key)

    def fn(params, seq, targets):
        h, W, A = forward(params, seq, d)
        logits = _logits(params, h, d, W, A)
        best = logits.max(-1)
        if control:
            hq, Wq, Aq = forward(params, seq, d, quant="fp8")
            targets = jnp.argmax(_logits(params, hq, d, Wq, Aq), -1)
        picked = jnp.take_along_axis(
            logits, jnp.clip(targets, 0, d["V"] - 1)[:, None], -1)[:, 0]
        return best - picked

    return jax.jit(fn)


def _gaps(params, config, prompt, tokens, capacity, control):
    import jax.numpy as jnp

    d = dims(config)
    prompt = np.asarray(prompt, np.int32).reshape(-1)
    tokens = np.asarray(tokens, np.int32).reshape(-1)
    S, n = len(prompt), len(tokens)
    if n == 0 or S + n > capacity:
        raise ValueError(f"{S} prompt + {n} served tokens do not fit "
                         f"capacity {capacity}")
    if not control and ((tokens < 0) | (tokens >= d["V"])).any():
        return np.full(n, np.inf, np.float32)
    seq = np.zeros(capacity, np.int32)
    seq[:S], seq[S:S + n] = prompt, tokens
    targets = np.zeros(capacity, np.int32)
    targets[S - 1:S - 1 + n] = tokens
    fn = _gap_fn(tuple(sorted(d.items())), control)
    gaps = np.asarray(fn(params, jnp.asarray(seq), jnp.asarray(targets)))
    return gaps[S - 1:S - 1 + n]


def served_token_gaps(params, config, prompt, tokens, capacity):
    """For each served token, the reference's best logit at its position
    minus the reference's logit of that token.  The sequence is padded to
    ``capacity`` so one program serves every request; causal attention
    keeps the padding from reaching the served positions, and no token's
    routing depends on another's."""
    return _gaps(params, config, prompt, tokens, capacity, control=False)


def control_gaps(params, config, prompt, tokens, capacity):
    """The control: at each served position, the gap of the token the fp8
    control puts first, on the same prompt and served tokens."""
    return _gaps(params, config, prompt, tokens, capacity, control=True)
