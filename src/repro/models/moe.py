"""Mixture-of-Experts decoder (kimi-k2-1t, deepseek-moe-16b).

Routing: a softmax gate over every routed expert and its top k, the k
weights renormalised (kimi-k2) or used as they are (deepseek-moe,
``cfg.moe_norm_topk``); optional shared experts always active, and a dense
first layer (``cfg.first_dense_layers``).

Training dispatches **grouped sort-based with static capacity** — the
TPU-native adaptation of the paper's send-only-needed-rows insight
(DESIGN.md §3):

* tokens are split into ``dp_groups`` groups aligned with the data-parallel
  sharding, so the sort that ranks tokens within each expert never crosses a
  shard boundary;
* each expert accepts at most ``C = ceil(T_group·k/E · capacity_factor)``
  tokens per group (overflow drops, standard capacity-based MoE);
* expert compute is a dense einsum over the [E, C, d] dispatch buffer, which
  shards cleanly over the ``model`` (expert) axis; the gather/scatter between
  token space and expert space is where XLA inserts the all-to-all — the
  collective analogue of the FSI point-to-point exchange.

Aux: load-balance loss (Switch-style) returned in metrics.

Serving is **dropless** (:func:`moe_ffn_serve`): every token goes to its
top k over all experts, and the layer computes exactly the assignments
that land on the experts it holds (``cfg.held_experts``, one chip's share
of an expert-parallel deployment; all of them by default), grouped by
expert through the grouped matmul kernel (``kernels/moe_gmm``).  What the
absent experts would add is left out; shared experts are computed here.
A token's output depends only on the token, whatever else shares the step.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core.backends import KVCacheLayout, get_backend
from repro.kernels.moe_gmm.ops import moe_gmm
from repro.models import layers as L
from repro.models.attention import chunked_causal_attention
from repro.models.kvcache import pad_kv_to_layout
from repro.models import transformer as TF

PyTree = Any
ACC = jnp.float32

# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------


def held_experts(cfg: ModelConfig) -> Tuple[int, int]:
    """[first, stop) of the routed experts this model holds."""
    return cfg.held_experts or (0, cfg.n_experts)


def init_moe_ffn(key, cfg: ModelConfig) -> PyTree:
    d, f, E = cfg.d_model, cfg.moe_d_ff, cfg.n_experts
    e0, e1 = held_experts(cfg)
    ks = jax.random.split(key, 5)
    p = {
        "router": L.dense_init(ks[0], (d, E), dtype=jnp.float32),
        "w_gate": L.dense_init(ks[1], (e1 - e0, d, f)),
        "w_up": L.dense_init(ks[2], (e1 - e0, d, f)),
        "w_down": L.dense_init(ks[3], (e1 - e0, f, d), in_axis_size=f),
    }
    if cfg.n_shared_experts:
        p["shared"] = L.init_mlp(ks[4], d, f * cfg.n_shared_experts)
    return p


MOE_AXES = {
    "router": ("embed", "experts_unsharded"),
    "w_gate": ("experts", "embed", "expert_ffn"),
    "w_up": ("experts", "embed", "expert_ffn"),
    "w_down": ("experts", "expert_ffn", "embed"),
}


def init_block(key, cfg: ModelConfig, dense: bool) -> PyTree:
    k1, k2 = jax.random.split(key)
    blk = {
        "ln_attn": L.init_rms_norm(cfg.d_model),
        "attn": L.init_attention(
            k1, cfg.d_model, cfg.eff_heads, cfg.eff_kv_heads, cfg.d_head,
            qkv_bias=cfg.qkv_bias,
        ),
        "ln_mlp": L.init_rms_norm(cfg.d_model),
    }
    if dense:
        blk["mlp"] = L.init_mlp(k2, cfg.d_model, cfg.d_ff)
    else:
        blk["moe"] = init_moe_ffn(k2, cfg)
    return blk


def init(key, cfg: ModelConfig) -> PyTree:
    keys = jax.random.split(key, cfg.n_layers + 2)
    dense_blocks = [
        init_block(keys[i], cfg, dense=True) for i in range(cfg.first_dense_layers)
    ]
    moe_blocks = [
        init_block(keys[i], cfg, dense=False)
        for i in range(cfg.first_dense_layers, cfg.n_layers)
    ]
    params = {
        "embed": L.init_embedding(keys[-2], cfg.padded_vocab(), cfg.d_model),
        "dense_blocks": (
            jax.tree.map(lambda *xs: jnp.stack(xs), *dense_blocks)
            if dense_blocks else None
        ),
        "moe_blocks": jax.tree.map(lambda *xs: jnp.stack(xs), *moe_blocks),
        "ln_f": L.init_rms_norm(cfg.d_model),
    }
    if not cfg.tie_embeddings:
        params["unembed"] = L.init_embedding(keys[-1], cfg.padded_vocab(), cfg.d_model)
    return params


# ---------------------------------------------------------------------------
# routing + dispatch
# ---------------------------------------------------------------------------


def route_topk(logits: jnp.ndarray, k: int, normalize: bool = True
               ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """[T, E] → (weights [T, k], idx [T, k]): the k largest of the softmax
    over all E experts.  ``normalize`` renormalises their weights to sum to
    1 (a softmax over the k chosen logits); otherwise they are the
    probabilities as they are (DeepSeekMoE's ``norm_topk_prob`` false)."""
    if normalize:
        vals, idx = jax.lax.top_k(logits, k)
        return jax.nn.softmax(vals.astype(ACC), axis=-1), idx
    w, idx = jax.lax.top_k(jax.nn.softmax(logits.astype(ACC), axis=-1), k)
    return w, idx


def _route(p: PyTree, xf: jnp.ndarray, cfg: ModelConfig):
    """Router logits [T, E] (f32) and the gate (weights, idx [T, k])."""
    logits = jnp.einsum("td,de->te", xf.astype(ACC), p["router"],
                        preferred_element_type=ACC)
    return (logits,) + route_topk(logits, cfg.experts_per_token,
                                  cfg.moe_norm_topk)


def _dispatch_tables(e_flat: jnp.ndarray, E: int, C: int):
    """Sort-based capacity dispatch within one token group.

    e_flat: [A] expert id per assignment (A = T_group·k).
    Returns (slot_token [E, C] indices into A, slot_valid [E, C]).
    """
    A = e_flat.shape[0]
    order = jnp.argsort(e_flat)                      # stable-ish grouping
    sorted_e = e_flat[order]
    # rank of each sorted entry within its expert segment
    seg_start = jnp.searchsorted(sorted_e, jnp.arange(E), side="left")
    rank = jnp.arange(A) - seg_start[sorted_e]
    keep = rank < C
    # scatter sorted assignment positions into the [E, C] table; dropped
    # entries aim at column C, which is out of bounds → mode="drop"
    table = jnp.full((E, C), A, dtype=jnp.int32)     # A = sentinel (invalid)
    table = table.at[sorted_e, jnp.where(keep, rank, C)].set(
        order.astype(jnp.int32), mode="drop"
    )
    valid = table < A
    return jnp.where(valid, table, 0), valid


def moe_ffn(
    p: PyTree, x: jnp.ndarray, cfg: ModelConfig, dp_groups: int = 1,
) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """x [B, S, d] → (out [B, S, d], metrics)."""
    B, S, d = x.shape
    E, k = cfg.n_experts, cfg.experts_per_token
    xf = x.reshape(B * S, d)
    T = B * S
    dp_groups = max(1, min(dp_groups, T))
    while T % dp_groups:
        dp_groups -= 1
    Tg = T // dp_groups
    C = max(1, int(-(-Tg * k // E) * cfg.moe_capacity_factor))

    logits, w, idx = _route(p, xf, cfg)               # [T,k]

    # group-local dispatch (vmapped over dp groups — sort never crosses the
    # data-parallel shard boundary)
    idx_g = idx.reshape(dp_groups, Tg * k)
    tables, valids = jax.vmap(lambda e: _dispatch_tables(e, E, C))(idx_g)
    # translate group-local assignment position → global token id + gate w
    w_g = w.reshape(dp_groups, Tg * k)
    token_of_assign = (
        jnp.arange(dp_groups * Tg * k, dtype=jnp.int32).reshape(dp_groups, Tg * k)
        // k
    )
    slot_token = jnp.take_along_axis(
        token_of_assign, tables.reshape(dp_groups, E * C), axis=1
    ).reshape(dp_groups, E, C)
    slot_w = jnp.take_along_axis(
        w_g, tables.reshape(dp_groups, E * C), axis=1
    ).reshape(dp_groups, E, C)
    slot_w = jnp.where(valids, slot_w, 0.0)

    # gather tokens → [E, G·C, d] so the expert einsum shards over E; the
    # token→expert gather (and the scatter back) is where the partitioner
    # emits the all-to-all — the collective analogue of the FSI exchange
    xe = xf[slot_token.transpose(1, 0, 2).reshape(E, dp_groups * C)]
    xe = L.constrain(xe, "model", None, None)
    gate = jnp.einsum("ecd,edf->ecf", xe, p["w_gate"], preferred_element_type=ACC)
    up = jnp.einsum("ecd,edf->ecf", xe, p["w_up"], preferred_element_type=ACC)
    h = L.constrain((jax.nn.silu(gate) * up).astype(x.dtype), "model", None, None)
    oe = jnp.einsum("ecf,efd->ecd", h, p["w_down"],
                    preferred_element_type=L.TP_PSUM_DTYPE)
    oe = L.constrain(oe, "model", None, None)

    wts = slot_w.transpose(1, 0, 2).reshape(E, dp_groups * C)
    out = jnp.zeros((T, d), ACC).at[
        slot_token.transpose(1, 0, 2).reshape(E, dp_groups * C)
    ].add(oe * wts[..., None])
    out = L.constrain(out, "dp", None)

    if cfg.n_shared_experts:
        out = out + L.mlp(p["shared"], x).reshape(T, d).astype(ACC)

    # Switch-style load-balance loss (scatter-add, no [T,k,E] one-hot)
    probs = jax.nn.softmax(logits, axis=-1)
    counts = jnp.zeros((E,), ACC).at[idx.reshape(-1)].add(1.0)
    frac_tokens = counts / (T * k)
    frac_probs = probs.mean(axis=0)
    lb_loss = E * jnp.sum(frac_tokens * frac_probs)
    drop_frac = 1.0 - valids.mean()
    return out.reshape(B, S, d).astype(x.dtype), {
        "lb_loss": lb_loss, "drop_frac": drop_frac,
    }


# ---------------------------------------------------------------------------
# shard_map expert-parallel dispatch (§Perf structural optimization)
# ---------------------------------------------------------------------------

# Opt-in: EXPERIMENTS.md §Perf B5 showed XLA's propagation cannot infer a
# comm-minimal expert schedule from pjit specs.  This variant states it
# explicitly with shard_map: activations are replicated over the model axis
# (as TP already leaves them), every rank routes identically, gathers ONLY
# its local experts' tokens (a pure-local gather — the paper's "send only
# the rows the owner needs"), computes, and a single psum of the [T, d]
# output is the only cross-device traffic — one all-reduce per MoE layer,
# same as a dense TP block.
MOE_EP_SHARDMAP = False


def set_moe_ep_shardmap(on: bool) -> None:
    global MOE_EP_SHARDMAP
    MOE_EP_SHARDMAP = on


def _moe_ffn_local(p_local, x, cfg: ModelConfig, e0, E_local: int):
    """Route against all E experts; evaluate only experts [e0, e0+E_local)."""
    B, S, d = x.shape
    E, k = cfg.n_experts, cfg.experts_per_token
    xf = x.reshape(B * S, d)
    T = B * S
    C = max(1, int(-(-T * k // E) * cfg.moe_capacity_factor))

    logits, w, idx = _route(p_local, xf, cfg)

    # keep only assignments owned by this rank; foreign ones → sentinel
    e_rel = idx.reshape(-1) - e0
    mine = (e_rel >= 0) & (e_rel < E_local)
    e_flat = jnp.where(mine, e_rel, E_local).astype(jnp.int32)
    table, valid = _dispatch_tables(e_flat, E_local + 1, C)
    table, valid = table[:E_local], valid[:E_local]

    token_of_assign = jnp.arange(T * k, dtype=jnp.int32) // k
    slot_token = jnp.where(valid, token_of_assign[table], 0)
    slot_w = jnp.where(valid, w.reshape(-1)[table], 0.0)

    xe = xf[slot_token]                              # local gather only
    gate = jnp.einsum("ecd,edf->ecf", xe, p_local["w_gate"],
                      preferred_element_type=ACC)
    up = jnp.einsum("ecd,edf->ecf", xe, p_local["w_up"],
                    preferred_element_type=ACC)
    h = (jax.nn.silu(gate) * up).astype(x.dtype)
    oe = jnp.einsum("ecf,efd->ecd", h, p_local["w_down"],
                    preferred_element_type=ACC)
    out = jnp.zeros((T, d), ACC).at[slot_token].add(oe * slot_w[..., None])

    counts = jnp.zeros((E,), ACC).at[idx.reshape(-1)].add(1.0)
    lb = E * jnp.sum((counts / (T * k)) * jax.nn.softmax(logits, -1).mean(0))
    return out.reshape(B, S, d), lb


def held_part(p: PyTree, xf: jnp.ndarray, w: jnp.ndarray, idx: jnp.ndarray,
              e0, layer=None, rows_valid=None
              ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """The part of the routed experts' output that the experts held here
    give: tokens ``xf`` [T, d] routed to ``idx`` [T, k] with gate weights
    ``w``, the experts ``[e0, e0 + E_held)`` holding ``p``'s weights (a
    stack of layers' [L, E_held, ...] given ``layer``, which the kernel
    reads in place).  ``rows_valid`` (bool [T], all by default) marks the
    tokens that are real; the others (a decode step's vacant slots) are
    neither computed nor counted, and their output is zero.
    Dropless: every assignment of a real token to a held expert is
    computed, grouped by expert through the grouped matmul; assignments to
    other experts are left out.  Returns (out f32 [T, d], counts int32
    [2]: the held assignments computed and the held experts with at least
    one, i.e. the experts whose weights the call read)."""
    T, d = xf.shape
    k = idx.shape[1]
    n_held = p["w_gate"].shape[-3]
    with jax.named_scope("moe.route"):
        rel = idx.reshape(-1) - e0
        held = (rel >= 0) & (rel < n_held)
        if rows_valid is not None:
            held = held & jnp.repeat(rows_valid, k)
        expert = jnp.where(held, rel, n_held)
        order = jnp.argsort(expert, stable=True)
        sizes = jnp.zeros((n_held,), jnp.int32).at[expert].add(1, mode="drop")
        rows = xf[order // k]
    with jax.named_scope("moe.experts"):
        y = moe_gmm(rows, sizes, p["w_gate"], p["w_up"], p["w_down"], layer)
    with jax.named_scope("moe.combine"):
        y = jnp.zeros_like(y).at[order].set(y).reshape(T, k, d)
        out = jnp.sum(y * w[..., None].astype(ACC), axis=1)
    counts = jnp.stack([sizes.sum(), jnp.count_nonzero(sizes)])
    return out, counts.astype(jnp.int32)


def moe_ffn_serve(p: PyTree, x: jnp.ndarray, cfg: ModelConfig, layer=None,
                  rows_valid=None) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """The serving expert layer: x [B, S, d] → (out [B, S, d], counts of
    :func:`held_part`).  Every token is routed to its top k over all
    ``n_experts``; the held experts' part and the shared experts make the
    output.  Given ``layer``, ``p``'s routed experts are the stack of
    every expert layer's; ``rows_valid`` (bool [B * S]) as
    :func:`held_part` takes it."""
    B, S, d = x.shape
    xf = x.reshape(B * S, d)
    with jax.named_scope("moe.route"):
        _, w, idx = _route(p, xf, cfg)
    out, counts = held_part(p, xf, w, idx, held_experts(cfg)[0], layer,
                            rows_valid)
    if cfg.n_shared_experts:
        with jax.named_scope("moe.experts"):
            out = out + L.mlp(p["shared"], x).reshape(B * S, d).astype(ACC)
    return out.reshape(B, S, d).astype(x.dtype), counts


def moe_ffn_shardmap(p: PyTree, x: jnp.ndarray, cfg: ModelConfig):
    """Explicit EP schedule via shard_map (requires an active shard ctx)."""
    from jax.sharding import PartitionSpec as P

    ctx = L.shard_ctx()
    mesh, dp, model_axis = ctx["mesh"], ctx["dp"], ctx["model"]
    msize = mesh.shape[model_axis]
    E_local = cfg.n_experts // msize
    dp_spec = tuple(dp) if dp else None

    def body(x_loc, router, w_gate, w_up, w_down):
        e0 = jax.lax.axis_index(model_axis) * E_local
        out, lb = _moe_ffn_local(
            {"router": router, "w_gate": w_gate, "w_up": w_up,
             "w_down": w_down},
            x_loc, cfg, e0, E_local,
        )
        out = jax.lax.psum(out.astype(ACC), model_axis)
        return out.astype(x_loc.dtype), lb

    out, lb = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(dp_spec, None, None), P(), P(model_axis, None, None),
                  P(model_axis, None, None), P(model_axis, None, None)),
        out_specs=(P(dp_spec, None, None), P()), check_vma=False,
    )(x, p["router"], p["w_gate"], p["w_up"], p["w_down"])

    if cfg.n_shared_experts:  # shared experts stay on the dense TP path
        out = out + L.mlp(p["shared"], x)
    return out, {"lb_loss": lb, "drop_frac": jnp.zeros(())}


def moe_ffn_dispatch(p, x, cfg: ModelConfig, dp_groups: int = 1):
    ctx = L.shard_ctx()
    if (MOE_EP_SHARDMAP and ctx["mesh"] is not None and ctx["model"]
            and cfg.n_experts % ctx["mesh"].shape[ctx["model"]] == 0):
        return moe_ffn_shardmap(p, x, cfg)
    return moe_ffn(p, x, cfg, dp_groups)


# ---------------------------------------------------------------------------
# blocks / forward / loss
# ---------------------------------------------------------------------------


def block_train(block: PyTree, x, cfg: ModelConfig, positions, dp_groups: int):
    x = TF._attn_train(block, x, cfg, positions)
    h = L.rms_norm(x, block["ln_mlp"], cfg.norm_eps)
    if "mlp" in block and block.get("mlp") is not None:
        return x + L.mlp(block["mlp"], h), jnp.zeros((), ACC)
    out, metrics = moe_ffn_dispatch(block["moe"], h, cfg, dp_groups)
    return x + out, metrics["lb_loss"]


def forward(
    params: PyTree, tokens: jnp.ndarray, cfg: ModelConfig,
    dp_groups: int = 1,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    x = L.embed_tokens(params["embed"], tokens)
    B, S, _ = x.shape
    positions = jnp.arange(S)[None, :].repeat(B, axis=0)
    lb_total = jnp.zeros((), ACC)

    if params.get("dense_blocks") is not None:
        def dense_body(h, blk):
            h2, _ = block_train(blk, h, cfg, positions, dp_groups)
            return h2, None
        if cfg.remat:
            dense_body = jax.checkpoint(dense_body, prevent_cse=False)
        x, _ = jax.lax.scan(dense_body, x, params["dense_blocks"])

    def moe_body(carry, blk):
        h, lb = carry
        h2, lb2 = block_train(blk, h, cfg, positions, dp_groups)
        return (h2, lb + lb2), None

    if cfg.remat:
        moe_body = jax.checkpoint(moe_body, prevent_cse=False)
    (x, lb_total), _ = jax.lax.scan(moe_body, (x, lb_total), params["moe_blocks"])

    x = L.rms_norm(x, params["ln_f"], cfg.norm_eps)
    table = params["embed"] if cfg.tie_embeddings else params["unembed"]
    return L.unembed(x, table), lb_total


def loss_fn(params: PyTree, batch, cfg: ModelConfig, dp_groups: int = 1,
            lb_coeff: float = 0.01) -> jnp.ndarray:
    logits, lb = forward(params, batch["tokens"], cfg, dp_groups)
    ce = L.cross_entropy_loss(logits[:, :-1], batch["labels"][:, 1:],
                              batch.get("mask"))
    n_moe = cfg.n_layers - cfg.first_dense_layers
    return ce + lb_coeff * lb / max(1, n_moe)


# ---------------------------------------------------------------------------
# serving
# ---------------------------------------------------------------------------


def _stacked_blocks(params):
    """The layer stacks present, in order: the dense stack (if any), then
    the moe stack (if any)."""
    return [params[name] for name in ("dense_blocks", "moe_blocks")
            if params.get(name) is not None]


# The expert layer's device counters (:func:`held_part`), in order.
COUNTERS = ("moe_rows", "moe_groups_read")
NO_COUNTS = np.zeros((len(COUNTERS),), np.int32)


_EXPERTS = ("w_gate", "w_up", "w_down")


def _split_experts(blocks: PyTree) -> Tuple[PyTree, Optional[PyTree]]:
    """A stack of layers without its routed experts' weights, which a
    layer scan would slice (a copy per layer, as the kernel cannot fuse
    the slice), and those weights, whole, for the kernel to read its
    layer's in place.  (blocks, None) for the dense stack."""
    if "moe" not in blocks:
        return blocks, None
    moe = blocks["moe"]
    rest = {k: v for k, v in moe.items() if k not in _EXPERTS}
    return {**blocks, "moe": rest}, {k: moe[k] for k in _EXPERTS}


def _ffn(block: PyTree, h: jnp.ndarray, cfg: ModelConfig, experts, layer,
         rows_valid=None) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """A serving layer's feed-forward with its residual: the dense MLP in
    the dense stack, the expert layer in the moe stack (``experts``, the
    stack's routed experts, of which layer ``layer``'s; ``rows_valid`` as
    :func:`held_part` takes it).  Returns (h, the expert layer's counts,
    zero for a dense layer)."""
    hm = L.rms_norm(h, block["ln_mlp"], cfg.norm_eps)
    if experts is None:
        return h + L.mlp(block["mlp"], hm), NO_COUNTS
    out, counts = moe_ffn_serve({**block["moe"], **experts}, hm, cfg, layer,
                                rows_valid)
    return h + out, counts


def _prefill_layers(stacks, x: jnp.ndarray, cfg: ModelConfig, max_len: int,
                    layout: KVCacheLayout):
    """Prefill ``x`` [B, S, d] through each stack; returns (x, the stacks'
    KV caches, the expert layers' counts summed)."""
    B, S, _ = x.shape
    positions = jnp.arange(S)[None, :].repeat(B, axis=0)
    caches, counts = [], NO_COUNTS

    for blocks in stacks:
        blocks, experts = _split_experts(blocks)

        def body(carry, inp):
            h, acc = carry
            blk, layer = inp
            hn = L.rms_norm(h, blk["ln_attn"], cfg.norm_eps)
            q, k, v = L.qkv_project(blk["attn"], hn)
            q = L.apply_rope(q, positions, cfg.rope_theta)
            k = L.apply_rope(k, positions, cfg.rope_theta)
            o = chunked_causal_attention(q, k, v)
            h = h + L.out_project(blk["attn"], o, h.dtype)
            h, c = _ffn(blk, h, cfg, experts, layer)
            return (h, acc + c), (pad_kv_to_layout(k, max_len, layout),
                                  pad_kv_to_layout(v, max_len, layout))

        if cfg.remat:
            body = jax.checkpoint(body, prevent_cse=False)
        (x, counts), (ks, vs) = jax.lax.scan(
            body, (x, counts), (blocks, _layers(blocks)))
        caches.append({"k": ks, "v": vs})
    return x, caches, counts


def _decode_layers(stacks, kv_stacks, x: jnp.ndarray, pos, attn,
                   seq_shard_axes, cfg: ModelConfig):
    """One decode position through each stack over contiguous caches;
    returns (x, the updated stacks, the expert layers' counts summed)."""
    positions = jnp.full((x.shape[0], 1), pos, dtype=jnp.int32)
    new_stacks, counts = [], NO_COUNTS
    for blocks, kv in zip(stacks, kv_stacks):
        blocks, experts = _split_experts(blocks)

        def body(carry, inp):
            h, acc = carry
            blk, layer, k_cache, v_cache = inp
            hn = L.rms_norm(h, blk["ln_attn"], cfg.norm_eps)
            q, k, v = L.qkv_project(blk["attn"], hn)
            q = L.apply_rope(q, positions, cfg.rope_theta)
            k = L.apply_rope(k, positions, cfg.rope_theta)
            o, k_cache, v_cache = TF._decode_attn(
                attn, q, k, v, k_cache, v_cache, pos, seq_shard_axes)
            h = h + L.out_project(blk["attn"], o.astype(h.dtype), h.dtype)
            h, c = _ffn(blk, h, cfg, experts, layer)
            return (h, acc + c), (k_cache, v_cache)

        (x, counts), (ks, vs) = jax.lax.scan(
            body, (x, counts), (blocks, _layers(blocks), kv["k"], kv["v"]))
        new_stacks.append({"k": ks, "v": vs})
    return x, new_stacks, counts


def _layers(blocks: PyTree) -> jnp.ndarray:
    """Each layer's index in its stack, for a layer scan."""
    n = jax.tree.leaves(blocks)[0].shape[0]
    return jnp.arange(n, dtype=jnp.int32)


def _head(params: PyTree, x: jnp.ndarray, cfg: ModelConfig) -> jnp.ndarray:
    x = L.rms_norm(x, params["ln_f"], cfg.norm_eps)
    table = params["embed"] if cfg.tie_embeddings else params["unembed"]
    return L.unembed(x, table)


def prefill_counted(params: PyTree, tokens: jnp.ndarray, cfg: ModelConfig,
                    max_len: int, layout: KVCacheLayout = KVCacheLayout()
                    ) -> Tuple[jnp.ndarray, PyTree, jnp.ndarray]:
    """The prompt through every layer: (logits, the cache, each stack's KV
    in the activations' type, and the expert layers' counts over the
    prompt, int32 in the order of :data:`COUNTERS`)."""
    x = L.embed_tokens(params["embed"], tokens)
    x, caches, counts = _prefill_layers(_stacked_blocks(params), x, cfg,
                                        max_len, layout)
    cache = {"stacks": caches, "length": jnp.asarray(x.shape[1], jnp.int32)}
    return _head(params, x[:, -1:], cfg), cache, counts


def prefill(params: PyTree, tokens: jnp.ndarray, cfg: ModelConfig,
            max_len: int,
            layout: KVCacheLayout = KVCacheLayout()) -> Tuple[jnp.ndarray, PyTree]:
    """:func:`prefill_counted` without the counts."""
    logits, cache, _ = prefill_counted(params, tokens, cfg, max_len, layout)
    return logits, cache


def decode_step(params: PyTree, token: jnp.ndarray, cache: PyTree,
                cfg: ModelConfig, attn_backend=None, seq_shard_axes=None,
                layout: Optional[KVCacheLayout] = None) -> Tuple[jnp.ndarray, PyTree]:
    attn = get_backend("attention", attn_backend)
    if layout is not None:
        layout.check_capacity(int(cache["stacks"][-1]["k"].shape[3]))
    x = L.embed_tokens(params["embed"], token)
    pos = cache["length"]
    x, new_stacks, _ = _decode_layers(
        _stacked_blocks(params), cache["stacks"], x, pos, attn,
        seq_shard_axes, cfg)
    return _head(params, x, cfg), {"stacks": new_stacks, "length": pos + 1}


def decode_step_paged(params: PyTree, token: jnp.ndarray, cache: PyTree,
                      tables: jnp.ndarray, write, active: jnp.ndarray,
                      cfg: ModelConfig, *, attn_backend=None
                      ) -> Tuple[jnp.ndarray, PyTree, jnp.ndarray]:
    """One decode step of ``slots`` requests over the paged KV pool, as
    :func:`repro.models.transformer.decode_step_paged` with the expert
    layer as the moe stack's feed-forward.  ``cache["stacks"]`` holds each
    stack's pool buffers; ``active`` (bool [slots]) marks the slots that
    hold a request, and the expert layer computes the routed experts of
    those only.  Returns (logits, the cache, the expert layers' counts
    over the step)."""
    attn = get_backend("attention", attn_backend)
    x = L.embed_tokens(params["embed"], token)
    pos = cache["length"]
    new_stacks, counts = [], NO_COUNTS
    for blocks, kv in zip(_stacked_blocks(params), cache["stacks"]):
        blocks, experts = _split_experts(blocks)
        x, ks, vs, counts = TF.paged_layers(
            blocks, x, kv["k"], kv["v"], tables, pos, write, attn, cfg,
            lambda block, h, layer: _ffn(block, h, cfg, experts, layer,
                                         active),
            counts)
        new_stacks.append({"k": ks, "v": vs})
    new_cache = {**cache, "stacks": new_stacks, "length": pos + 1}
    return _head(params, x, cfg), new_cache, counts


# ---------------------------------------------------------------------------
# pipeline stages (serverless LM executor)
# ---------------------------------------------------------------------------
#
# Global layer index ``l`` maps to ``dense_blocks[l]`` for
# ``l < cfg.first_dense_layers`` and ``moe_blocks[l - first_dense_layers]``
# otherwise.  A stage slices each stack it straddles; running the full scans
# as consecutive sub-scans over contiguous slices executes the same per-layer
# ops in the same order, so chained stages reproduce the monolithic numerics.


def _stage_stacks(cfg: ModelConfig, start: int, stop: int):
    """(dense_range, moe_range) a [start, stop) slice covers — either may be
    empty.  The moe range is stack-local (offset by first_dense_layers)."""
    fd = cfg.first_dense_layers
    dense = (start, min(stop, fd))
    m = (max(start, fd) - fd, stop - fd)
    return (dense if dense[1] > dense[0] else None,
            m if m[1] > m[0] else None)


def slice_stage_params(params: PyTree, spec, cfg: ModelConfig) -> PyTree:
    dense_r, moe_r = _stage_stacks(cfg, spec.start, spec.stop)
    out: Dict[str, Any] = {
        "dense_blocks": (
            jax.tree.map(lambda a: a[dense_r[0]:dense_r[1]],
                         params["dense_blocks"]) if dense_r else None
        ),
        "moe_blocks": (
            jax.tree.map(lambda a: a[moe_r[0]:moe_r[1]], params["moe_blocks"])
            if moe_r else None
        ),
    }
    if spec.has_embed:
        out["embed"] = params["embed"]
    if spec.has_head:
        out["ln_f"] = params["ln_f"]
        if "unembed" in params:
            out["unembed"] = params["unembed"]
        elif not spec.has_embed:
            out["embed"] = params["embed"]  # tied head needs the table
    return out


def stage_prefill(
    sp: PyTree, spec, x_in: jnp.ndarray, cfg: ModelConfig, max_len: int,
    layout: KVCacheLayout = KVCacheLayout(),
) -> Tuple[jnp.ndarray, PyTree]:
    """One stage of ``prefill`` — token ids [B, S] in on the embedding stage,
    hidden states [B, S, d] otherwise; logits [B, 1, V] out on the head
    stage.  The stage's KV stacks stay resident in its cache."""
    x = L.embed_tokens(sp["embed"], x_in) if spec.has_embed else x_in
    x, caches, _ = _prefill_layers(_stacked_blocks(sp), x, cfg, max_len,
                                   layout)
    cache = {"stacks": caches, "length": jnp.asarray(x.shape[1], jnp.int32)}
    if spec.has_head:
        return _head(sp, x[:, -1:], cfg), cache
    return x, cache


def stage_decode_step(
    sp: PyTree, spec, x_in: jnp.ndarray, cache: PyTree, cfg: ModelConfig,
    *, attn_backend=None,
) -> Tuple[jnp.ndarray, PyTree]:
    """One stage of ``decode_step`` — token [B, 1] in on the embedding stage,
    hidden [B, 1, d] otherwise; logits [B, 1, V] out on the head stage."""
    attn = get_backend("attention", attn_backend)
    x = L.embed_tokens(sp["embed"], x_in) if spec.has_embed else x_in
    pos = cache["length"]
    x, new_stacks, _ = _decode_layers(_stacked_blocks(sp), cache["stacks"],
                                      x, pos, attn, None, cfg)
    new_cache = {"stacks": new_stacks, "length": pos + 1}
    if spec.has_head:
        return _head(sp, x, cfg), new_cache
    return x, new_cache


def cache_seq_axes(cache):
    """Growing-KV sequence axes: every ``k``/``v`` leaf inside ``stacks``
    pages into the KV pool (seq axis -2); ``length`` stays slot-resident.
    See :func:`repro.models.kvcache.seq_axis_tree`."""
    from repro.models.kvcache import seq_axis_tree

    return seq_axis_tree(cache)
