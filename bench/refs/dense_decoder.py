"""Plain float32 reference of a dense GQA decoder (InternLM2 / Llama form).

Per layer, from the published description: RMSNorm, Q/K/V projections,
rotary position embedding on the two halves of each head (theta from the
configuration), causal softmax attention with each group of
``heads / kv_heads`` query heads sharing one K/V head, output projection
and residual; RMSNorm, SwiGLU MLP (``silu(x Wg) * (x Wu)``, then ``Wd``)
and residual.  A final RMSNorm and the output head give the logits over the
vocabulary.

Everything runs in float32 with every product at ``Precision.HIGHEST``; the
bf16 weights are cast to float32 one layer at a time inside the layer scan,
so the reference fits beside the served weights.  It imports nothing of the
program: it reads the weight arrays the benchmark made, in the layout the
benchmark gave them (``bench/systems/dense_decoder.make_params``).

``quant="fp8"`` is the control: the same forward with every matmul operand
rounded to float8 e4m3 (weights per tensor, activations per row, each
scaled to the format's range), the precision a later change might be
tempted to serve in.
"""

from __future__ import annotations

from functools import lru_cache
from typing import Optional

import numpy as np

FP8_MAX = 448.0


def dims(config: dict) -> dict:
    D = int(config["hidden_size"])
    H = int(config["num_attention_heads"])
    return {"L": int(config["num_hidden_layers"]), "D": D, "H": H,
            "KV": int(config["num_key_value_heads"]), "Dh": D // H,
            "F": int(config["intermediate_size"]),
            "V": int(config["vocab_size"]),
            "theta": float(config["rope_theta"]),
            "eps": float(config["rms_norm_eps"]),
            "tied": bool(config["tie_word_embeddings"])}


def _fp8(a, axis):
    import jax.numpy as jnp

    scale = jnp.max(jnp.abs(a), axis=axis, keepdims=True) / FP8_MAX
    scale = jnp.where(scale > 0, scale, 1.0)
    return (a / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


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

    def layer(x, blk):
        a, m = blk["attn"], blk["mlp"]
        h = A(rms(x, blk["ln_attn"]))
        q = rope(jnp.einsum("sd,dhk->shk", h, W(a["wq"]), precision=P))
        k = rope(jnp.einsum("sd,dhk->shk", h, W(a["wk"]), precision=P))
        v = jnp.einsum("sd,dhk->shk", h, W(a["wv"]), precision=P)
        s = jnp.einsum("sngk,tnk->ngst", q.reshape(S, KV, G, Dh), k,
                       precision=P) / np.sqrt(Dh)
        p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        o = jnp.einsum("ngst,tnk->sngk", p, v, precision=P).reshape(S, H, Dh)
        x = x + jnp.einsum("shk,hkd->sd", A(o), W(a["wo"]), precision=P)
        h = A(rms(x, blk["ln_mlp"]))
        g = jnp.einsum("sd,df->sf", h, W(m["wi_gate"]), precision=P)
        u = jnp.einsum("sd,df->sf", h, W(m["wi_up"]), precision=P)
        x = x + jnp.einsum("sf,fd->sd", A(jax.nn.silu(g) * u), W(m["wo"]),
                           precision=P)
        return x, None

    x = f32(jnp.take(params["embed"], seq, axis=0))
    x, _ = jax.lax.scan(layer, x, params["blocks"])
    return rms(x, params["ln_f"]), W, A


def _logits(params, h, d, W, A):
    import jax
    import jax.numpy as jnp

    table = params["embed"] if d["tied"] else params["unembed"]
    return jnp.einsum("sd,vd->sv", A(h), W(table[: d["V"]]),
                      precision=jax.lax.Precision.HIGHEST)


@lru_cache(maxsize=None)
def _gap_fn(key: tuple, control: bool):
    """Jitted ``(params, seq, targets) -> gaps [S]``.  Without ``control``:
    the reference's best logit minus its logit of ``targets`` at each
    position.  With it: the same gap for the token the fp8 control puts
    first at each position."""
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
    minus the reference's logit of that token (0 where the reference agrees
    with the greedy choice).  The sequence is padded to ``capacity`` so one
    program serves every request; causal attention keeps the padding from
    reaching the served positions."""
    return _gaps(params, config, prompt, tokens, capacity, control=False)


def control_gaps(params, config, prompt, tokens, capacity):
    """The control: at each served position, the gap of the token the fp8
    control puts first, on the same prompt and served tokens."""
    return _gaps(params, config, prompt, tokens, capacity, control=True)
