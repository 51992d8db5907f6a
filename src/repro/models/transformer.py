"""Dense decoder-only transformer (internlm2 / llama3.2 / minicpm / codeqwen,
and the LM backbone of internvl2).

Layer params are stacked along a leading ``layers`` axis and the blocks run
under ``jax.lax.scan`` — keeps the HLO size O(1) in depth, which matters when
compiling 61-81 layer models against a 512-device mesh.  Activation
rematerialization wraps the scan body (``cfg.remat``).

The vlm family reuses this module: ``extra_embeds`` (precomputed patch/frame
embeddings from the stub frontend) are prepended to the token embeddings.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core.backends import KVCacheLayout, get_backend
from repro.models import layers as L
from repro.models.attention import (
    chunked_causal_attention,
    sharded_decode_attend,
)
from repro.models.kvcache import pad_kv_to_layout

PyTree = Any


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------


def init_block(key, cfg: ModelConfig) -> PyTree:
    k1, k2 = jax.random.split(key)
    return {
        "ln_attn": L.init_rms_norm(cfg.d_model),
        "attn": L.init_attention(
            k1, cfg.d_model, cfg.eff_heads, cfg.eff_kv_heads, cfg.d_head,
            qkv_bias=cfg.qkv_bias,
        ),
        "ln_mlp": L.init_rms_norm(cfg.d_model),
        "mlp": L.init_mlp(k2, cfg.d_model, cfg.d_ff),
    }


def init(key, cfg: ModelConfig) -> PyTree:
    keys = jax.random.split(key, cfg.n_layers + 2)
    blocks = [init_block(keys[i], cfg) for i in range(cfg.n_layers)]
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *blocks)
    params = {
        "embed": L.init_embedding(keys[-2], cfg.padded_vocab(), cfg.d_model),
        "blocks": stacked,
        "ln_f": L.init_rms_norm(cfg.d_model),
    }
    if not cfg.tie_embeddings:
        params["unembed"] = L.init_embedding(keys[-1], cfg.padded_vocab(), cfg.d_model)
    return params


# ---------------------------------------------------------------------------
# block application
# ---------------------------------------------------------------------------


def _attn_train(block: PyTree, x: jnp.ndarray, cfg: ModelConfig,
                positions: jnp.ndarray) -> jnp.ndarray:
    h = L.rms_norm(x, block["ln_attn"], cfg.norm_eps)
    q, k, v = L.qkv_project(block["attn"], h)
    q = L.apply_rope(q, positions, cfg.rope_theta)
    k = L.apply_rope(k, positions, cfg.rope_theta)
    o = chunked_causal_attention(q, k, v)
    return x + L.out_project(block["attn"], o, x.dtype)


def _mlp_apply(block: PyTree, x: jnp.ndarray, cfg: ModelConfig) -> jnp.ndarray:
    h = L.rms_norm(x, block["ln_mlp"], cfg.norm_eps)
    return x + L.mlp(block["mlp"], h)


def block_train(block: PyTree, x: jnp.ndarray, cfg: ModelConfig,
                positions: jnp.ndarray) -> jnp.ndarray:
    return _mlp_apply(block, _attn_train(block, x, cfg, positions), cfg)


# ---------------------------------------------------------------------------
# forward (teacher-forced) + loss
# ---------------------------------------------------------------------------


def forward(
    params: PyTree, tokens: jnp.ndarray, cfg: ModelConfig,
    extra_embeds: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    """tokens [B, S] (+ optional prepended embeddings) → logits [B, S', V]."""
    x = L.embed_tokens(params["embed"], tokens)
    if extra_embeds is not None:
        x = jnp.concatenate([extra_embeds.astype(x.dtype), x], axis=1)
    B, S, _ = x.shape
    positions = jnp.arange(S)[None, :].repeat(B, axis=0)

    def body(h, block):
        return block_train(block, h, cfg, positions), None

    if cfg.remat:
        body = jax.checkpoint(body, prevent_cse=False)
    x, _ = jax.lax.scan(body, x, params["blocks"])
    x = L.rms_norm(x, params["ln_f"], cfg.norm_eps)
    table = params["embed"] if cfg.tie_embeddings else params["unembed"]
    return L.unembed(x, table)


def loss_fn(params: PyTree, batch: Dict[str, jnp.ndarray], cfg: ModelConfig) -> jnp.ndarray:
    logits = forward(params, batch["tokens"], cfg,
                     extra_embeds=batch.get("extra_embeds"))
    n_extra = batch["extra_embeds"].shape[1] if batch.get("extra_embeds") is not None else 0
    if n_extra:
        logits = logits[:, n_extra:]
    return L.cross_entropy_loss(logits[:, :-1], batch["labels"][:, 1:],
                                batch.get("mask"))


# ---------------------------------------------------------------------------
# serving: prefill + decode
# ---------------------------------------------------------------------------


def prefill(
    params: PyTree, tokens: jnp.ndarray, cfg: ModelConfig, max_len: int,
    extra_embeds: Optional[jnp.ndarray] = None,
    layout: KVCacheLayout = KVCacheLayout(),
) -> Tuple[jnp.ndarray, PyTree]:
    """Run the prompt, build the kernel-native [B, KV, S, D] KV cache with
    capacity ``layout.padded_len(max_len)`` (see ``models.kvcache``)."""
    x = L.embed_tokens(params["embed"], tokens)
    if extra_embeds is not None:
        x = jnp.concatenate([extra_embeds.astype(x.dtype), x], axis=1)
    B, S, _ = x.shape
    positions = jnp.arange(S)[None, :].repeat(B, axis=0)

    def body(h, block):
        hn = L.rms_norm(h, block["ln_attn"], cfg.norm_eps)
        q, k, v = L.qkv_project(block["attn"], hn)
        q = L.apply_rope(q, positions, cfg.rope_theta)
        k = L.apply_rope(k, positions, cfg.rope_theta)
        o = chunked_causal_attention(q, k, v)
        h = h + L.out_project(block["attn"], o, h.dtype)
        h = _mlp_apply(block, h, cfg)
        k_pad = pad_kv_to_layout(k, max_len, layout)
        v_pad = pad_kv_to_layout(v, max_len, layout)
        return h, (k_pad, v_pad)

    if cfg.remat:
        body = jax.checkpoint(body, prevent_cse=False)
    x, (ks, vs) = jax.lax.scan(body, x, params["blocks"])
    x = L.rms_norm(x, params["ln_f"], cfg.norm_eps)
    table = params["embed"] if cfg.tie_embeddings else params["unembed"]
    logits = L.unembed(x[:, -1:], table)
    cache = {"k": ks, "v": vs, "length": jnp.asarray(S, jnp.int32)}
    return logits, cache


def _decode_attn(attn, q, k, v, k_cache, v_cache, pos, seq_shard_axes):
    """Shared per-layer decode-attention step over the kernel-native cache.

    Inserts the new token's KV and dispatches the backend.  Replicated
    caches (``seq_shard_axes=None``) write at the global position and decode
    locally.  Sequence-sharded caches (inside a shard_map binding the named
    axes over the cache's S dim) write on the shard owning ``pos``, run the
    backend's split-KV form over the local slice with the shard-local valid
    prefix, and lse-combine partials across shards — so ``pallas-splitk``
    (and every other backend) serves sharded fleets, not just single-device
    decode.  Returns (o [B,1,H,D], k_cache, v_cache).
    """
    B, _, KV, D = k.shape
    kt = k.astype(k_cache.dtype).reshape(B, KV, 1, D)
    vt = v.astype(v_cache.dtype).reshape(B, KV, 1, D)
    if seq_shard_axes is None:
        k_cache = jax.lax.dynamic_update_slice(k_cache, kt, (0, 0, pos, 0))
        v_cache = jax.lax.dynamic_update_slice(v_cache, vt, (0, 0, pos, 0))
        o = attn.decode(q, k_cache, v_cache, cache_len=pos + 1)
        return o, k_cache, v_cache
    return sharded_decode_attend(attn, q, kt, vt, k_cache, v_cache, pos,
                                 seq_shard_axes)


def decode_step(
    params: PyTree, token: jnp.ndarray, cache: PyTree, cfg: ModelConfig,
    *, seq_shard_axes=None, attn_backend=None,
    layout: Optional[KVCacheLayout] = None,
) -> Tuple[jnp.ndarray, PyTree]:
    """One decode step.  token [B, 1] → logits [B, 1, V].

    ``seq_shard_axes``: mesh axis name(s) the KV cache's sequence dim is
    sharded over — the new token's KV is inserted on the owning shard and
    partial attention outputs are lse-combined across the axes (split-KV
    decode).  None means the cache is sequence-replicated locally.

    ``attn_backend``: :class:`repro.core.backends.AttentionBackend` name or
    instance; ``None`` resolves to ``dense-ref``, the oracle.

    ``layout``: the :class:`KVCacheLayout` the cache was allocated with —
    when given, the (local) cache capacity is checked against its padding
    rule at trace time.
    """
    attn = get_backend("attention", attn_backend)
    if layout is not None:
        layout.check_capacity(int(cache["k"].shape[3]))
    x = L.embed_tokens(params["embed"], token)
    B = x.shape[0]
    pos = cache["length"]
    positions = jnp.full((B, 1), pos, dtype=jnp.int32)

    def body(carry, inp):
        h = carry
        block, k_cache, v_cache = inp
        hn = L.rms_norm(h, block["ln_attn"], cfg.norm_eps)
        q, k, v = L.qkv_project(block["attn"], hn)
        q = L.apply_rope(q, positions, cfg.rope_theta)
        k = L.apply_rope(k, positions, cfg.rope_theta)
        o, k_cache, v_cache = _decode_attn(
            attn, q, k, v, k_cache, v_cache, pos, seq_shard_axes)
        h = h + L.out_project(block["attn"], o.astype(h.dtype), h.dtype)
        h = _mlp_apply(block, h, cfg)
        return h, (k_cache, v_cache)

    x, (ks, vs) = jax.lax.scan(body, x, (params["blocks"], cache["k"], cache["v"]))
    x = L.rms_norm(x, params["ln_f"], cfg.norm_eps)
    table = params["embed"] if cfg.tie_embeddings else params["unembed"]
    logits = L.unembed(x, table)
    new_cache = {"k": ks, "v": vs, "length": cache["length"] + 1}
    return logits, new_cache


def decode_step_paged(
    params: PyTree, token: jnp.ndarray, cache: PyTree, tables: jnp.ndarray,
    write, cfg: ModelConfig, *, attn_backend=None,
) -> Tuple[jnp.ndarray, PyTree]:
    """One decode step of ``slots`` requests over the paged KV pool, each at
    its own position.  token [slots, 1] → logits [slots, 1, V].

    ``cache``: ``{"k", "v"}`` the pool's buffers ``[L, 1, KV, num_blocks,
    block_k, D]`` (``serving/kv_pool.py``), ``"length"`` int32 [slots], each
    slot's position.  ``tables``: int32 [slots, W], each slot's pages.
    ``write(leaf, layer, new [slots, 1, KV, D])`` puts each slot's new K or
    V row at its position in its page and returns the leaf; each layer
    writes before it attends, so attention sees the new token.  The
    backend's ``decode_paged`` reads the pages through ``tables``.
    """
    attn = get_backend("attention", attn_backend)
    x = L.embed_tokens(params["embed"], token)
    pos = cache["length"]
    positions = pos[:, None]

    def body(carry, inp):
        h, k_pages, v_pages = carry
        block, layer = inp
        hn = L.rms_norm(h, block["ln_attn"], cfg.norm_eps)
        q, k, v = L.qkv_project(block["attn"], hn)
        q = L.apply_rope(q, positions, cfg.rope_theta)
        k = L.apply_rope(k, positions, cfg.rope_theta)
        k_pages = write(k_pages, layer, k)
        v_pages = write(v_pages, layer, v)
        o = attn.decode_paged(q, k_pages, v_pages, tables, pos + 1, layer)
        h = h + L.out_project(block["attn"], o.astype(h.dtype), h.dtype)
        h = _mlp_apply(block, h, cfg)
        return (h, k_pages, v_pages), None

    n_layers = cache["k"].shape[0]
    (x, ks, vs), _ = jax.lax.scan(
        body, (x, cache["k"], cache["v"]),
        (params["blocks"], jnp.arange(n_layers, dtype=jnp.int32)))
    x = L.rms_norm(x, params["ln_f"], cfg.norm_eps)
    table = params["embed"] if cfg.tie_embeddings else params["unembed"]
    logits = L.unembed(x, table)
    return logits, {**cache, "k": ks, "v": vs, "length": pos + 1}


def param_count(cfg: ModelConfig) -> int:
    return cfg.param_count()


# ---------------------------------------------------------------------------
# pipeline stages (serverless LM executor)
# ---------------------------------------------------------------------------
#
# A stage is a contiguous slice ``[spec.start, spec.stop)`` of the stacked
# blocks, optionally with the embedding (first stage) and the final norm +
# unembed (last stage).  Running the full scan as consecutive sub-scans over
# contiguous slices executes the exact same per-layer ops in the exact same
# order, so the chained stages reproduce the monolithic model's numerics —
# the wire ships activations as float32, which round-trips bf16 exactly.


def slice_stage_params(params: PyTree, spec) -> PyTree:
    """Materialize the parameter subtree stage ``spec`` keeps resident."""
    out: Dict[str, Any] = {
        "blocks": jax.tree.map(lambda a: a[spec.start:spec.stop],
                               params["blocks"]),
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


def _unembed_last(sp: PyTree, x: jnp.ndarray, cfg: ModelConfig) -> jnp.ndarray:
    x = L.rms_norm(x, sp["ln_f"], cfg.norm_eps)
    table = sp["embed"] if cfg.tie_embeddings else sp["unembed"]
    return L.unembed(x, table)


def stage_prefill(
    sp: PyTree, spec, x_in: jnp.ndarray, cfg: ModelConfig, max_len: int,
    extra_embeds: Optional[jnp.ndarray] = None,
    layout: KVCacheLayout = KVCacheLayout(),
) -> Tuple[jnp.ndarray, PyTree]:
    """One stage of ``prefill``.  ``x_in`` is the token ids [B, S] on the
    embedding stage, the previous stage's hidden states [B, S, d] otherwise.
    Returns (hidden [B, S, d] — or last-position logits [B, 1, V] on the head
    stage) plus this stage's resident KV cache."""
    if spec.has_embed:
        x = L.embed_tokens(sp["embed"], x_in)
        if extra_embeds is not None:
            x = jnp.concatenate([extra_embeds.astype(x.dtype), x], axis=1)
    else:
        x = x_in
    B, S, _ = x.shape
    positions = jnp.arange(S)[None, :].repeat(B, axis=0)

    def body(h, block):
        hn = L.rms_norm(h, block["ln_attn"], cfg.norm_eps)
        q, k, v = L.qkv_project(block["attn"], hn)
        q = L.apply_rope(q, positions, cfg.rope_theta)
        k = L.apply_rope(k, positions, cfg.rope_theta)
        o = chunked_causal_attention(q, k, v)
        h = h + L.out_project(block["attn"], o, h.dtype)
        h = _mlp_apply(block, h, cfg)
        k_pad = pad_kv_to_layout(k, max_len, layout)
        v_pad = pad_kv_to_layout(v, max_len, layout)
        return h, (k_pad, v_pad)

    if cfg.remat:
        body = jax.checkpoint(body, prevent_cse=False)
    x, (ks, vs) = jax.lax.scan(body, x, sp["blocks"])
    cache = {"k": ks, "v": vs, "length": jnp.asarray(S, jnp.int32)}
    if spec.has_head:
        return _unembed_last(sp, x[:, -1:], cfg), cache
    return x, cache


def stage_decode_step(
    sp: PyTree, spec, x_in: jnp.ndarray, cache: PyTree, cfg: ModelConfig,
    *, attn_backend=None,
) -> Tuple[jnp.ndarray, PyTree]:
    """One stage of ``decode_step``.  ``x_in`` is the new token [B, 1] on the
    embedding stage, the previous stage's hidden state [B, 1, d] otherwise.
    Returns (hidden [B, 1, d] — or logits [B, 1, V] on the head stage) plus
    the updated stage cache."""
    attn = get_backend("attention", attn_backend)
    x = L.embed_tokens(sp["embed"], x_in) if spec.has_embed else x_in
    B = x.shape[0]
    pos = cache["length"]
    positions = jnp.full((B, 1), pos, dtype=jnp.int32)

    def body(carry, inp):
        h = carry
        block, k_cache, v_cache = inp
        hn = L.rms_norm(h, block["ln_attn"], cfg.norm_eps)
        q, k, v = L.qkv_project(block["attn"], hn)
        q = L.apply_rope(q, positions, cfg.rope_theta)
        k = L.apply_rope(k, positions, cfg.rope_theta)
        o, k_cache, v_cache = _decode_attn(
            attn, q, k, v, k_cache, v_cache, pos, None)
        h = h + L.out_project(block["attn"], o.astype(h.dtype), h.dtype)
        h = _mlp_apply(block, h, cfg)
        return h, (k_cache, v_cache)

    x, (ks, vs) = jax.lax.scan(body, x, (sp["blocks"], cache["k"], cache["v"]))
    new_cache = {"k": ks, "v": vs, "length": cache["length"] + 1}
    if spec.has_head:
        return _unembed_last(sp, x, cfg), new_cache
    return x, new_cache


def cache_seq_axes(cache):
    """Growing-KV sequence axes for the continuous-batching scheduler:
    ``k``/``v`` page into the KV pool (seq axis -2), ``length`` stays
    slot-resident.  See :func:`repro.models.kvcache.seq_axis_tree`."""
    from repro.models.kvcache import seq_axis_tree

    return seq_axis_tree(cache)
