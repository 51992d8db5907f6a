"""Uniform model API over all families.

``get_model(cfg)`` returns a :class:`ModelApi` with init / loss_fn / forward /
prefill / decode_step — the single entry point used by the trainer, the
serving engine and the dry-run.  ``input_specs`` builds either concrete
batches (smoke tests) or ShapeDtypeStructs (dry-run) per (arch × shape),
including the stub frontend embeddings for vlm/audio archs.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig, ShapeConfig
from repro.models import encdec, hybrid, mamba2, moe, transformer

PyTree = Any


@dataclasses.dataclass
class ModelApi:
    cfg: ModelConfig
    init: Callable[..., PyTree]
    loss_fn: Callable[..., jnp.ndarray]
    forward: Callable[..., jnp.ndarray]
    prefill: Callable[..., Tuple[jnp.ndarray, PyTree]]
    decode_step: Callable[..., Tuple[jnp.ndarray, PyTree]]
    # cache pytree → matching pytree of Optional[int]: the sequence axis of
    # every growing KV leaf (paged by serving/kv_pool.py), None for
    # slot-resident state.  vmap-in_axes convention: traverse the result with
    # is_leaf=lambda x: x is None.
    cache_seq_axes: Callable[[PyTree], PyTree] = None
    # Batched decode over the paged KV pool, one position per slot:
    # ``(params, tokens [slots, 1], cache, tables [slots, W], write, active
    # [slots]) -> (logits [slots, 1, V], cache, counts)`` where ``cache``
    # holds the pool's buffers at the paged leaves and per-slot state
    # elsewhere, and ``counts`` is the step's device counters (None where
    # the family names none).  None where the family decodes gathered
    # per-slot caches only.
    decode_paged: Optional[Callable[..., Tuple[jnp.ndarray, PyTree, Any]]] = None
    # The decode-attention backend ``decode_paged`` calls.
    attn_backend: Any = None
    # Names of the family's device counters: the int32 vector, in this
    # order, that ``prefill_counted`` and ``decode_paged`` return beside
    # their outputs.
    counters: Tuple[str, ...] = ()
    # ``prefill`` that also returns the prompt's counters: ``(params, batch,
    # max_len) -> (logits, cache, counts)``.  None where there are none.
    prefill_counted: Optional[Callable[..., Tuple[jnp.ndarray, PyTree, Any]]] = None


def get_model(cfg: ModelConfig, attn_backend=None) -> ModelApi:
    """Build the family's :class:`ModelApi`.

    ``attn_backend`` — :class:`repro.core.backends.AttentionBackend` name or
    instance used by every decode step of the attention-bearing families
    (``None`` → ``dense-ref``, the oracle).  Resolved once here so all jitted
    decode closures share a single static instance.  The backend's
    :class:`KVCacheLayout` (the kernel-native [B, KV, S, D] cache layout +
    block_k padding rule) is derived from the static ``max_len`` at prefill
    trace time and threaded into every family's ``prefill``; decode closures
    accept the family's extra kwargs (``seq_shard_axes=...`` for the
    sequence-sharded split-KV branch) as pass-through.
    """
    from repro.core.backends import cache_layout_for, get_backend

    fam = cfg.family
    attn = get_backend("attention", attn_backend) if fam != "ssm" else None
    layout = lambda max_len: cache_layout_for(attn, max_len)
    if fam in ("dense",):
        return ModelApi(
            cfg=cfg,
            init=lambda key: transformer.init(key, cfg),
            loss_fn=lambda p, b: transformer.loss_fn(p, b, cfg),
            forward=lambda p, b: transformer.forward(p, b["tokens"], cfg),
            prefill=lambda p, b, max_len: transformer.prefill(
                p, b["tokens"], cfg, max_len, layout=layout(max_len)),
            decode_step=lambda p, t, c, **kw: transformer.decode_step(
                p, t, c, cfg, attn_backend=attn, **kw),
            cache_seq_axes=transformer.cache_seq_axes,
            decode_paged=lambda p, t, c, tables, write, active: (
                *transformer.decode_step_paged(p, t, c, tables, write, cfg,
                                               attn_backend=attn), None),
            attn_backend=attn,
        )
    if fam == "vlm":
        return ModelApi(
            cfg=cfg,
            init=lambda key: transformer.init(key, cfg),
            loss_fn=lambda p, b: transformer.loss_fn(p, b, cfg),
            forward=lambda p, b: transformer.forward(
                p, b["tokens"], cfg, extra_embeds=b["extra_embeds"]),
            prefill=lambda p, b, max_len: transformer.prefill(
                p, b["tokens"], cfg, max_len, extra_embeds=b["extra_embeds"],
                layout=layout(max_len)),
            decode_step=lambda p, t, c, **kw: transformer.decode_step(
                p, t, c, cfg, attn_backend=attn, **kw),
            cache_seq_axes=transformer.cache_seq_axes,
            decode_paged=lambda p, t, c, tables, write, active: (
                *transformer.decode_step_paged(p, t, c, tables, write, cfg,
                                               attn_backend=attn), None),
            attn_backend=attn,
        )
    if fam == "moe":
        return ModelApi(
            cfg=cfg,
            init=lambda key: moe.init(key, cfg),
            loss_fn=lambda p, b, dp_groups=1: moe.loss_fn(p, b, cfg, dp_groups),
            forward=lambda p, b, dp_groups=1: moe.forward(
                p, b["tokens"], cfg, dp_groups)[0],
            prefill=lambda p, b, max_len: moe.prefill(
                p, b["tokens"], cfg, max_len, layout=layout(max_len)),
            decode_step=lambda p, t, c, **kw: moe.decode_step(
                p, t, c, cfg, attn_backend=attn, **kw),
            cache_seq_axes=moe.cache_seq_axes,
            decode_paged=lambda p, t, c, tables, write, active:
                moe.decode_step_paged(p, t, c, tables, write, active, cfg,
                                      attn_backend=attn),
            attn_backend=attn,
            counters=moe.COUNTERS,
            prefill_counted=lambda p, b, max_len: moe.prefill_counted(
                p, b["tokens"], cfg, max_len, layout=layout(max_len)),
        )
    if fam == "ssm":
        return ModelApi(
            cfg=cfg,
            init=lambda key: mamba2.init(key, cfg),
            loss_fn=lambda p, b: mamba2.loss_fn(p, b, cfg),
            forward=lambda p, b: mamba2.forward(p, b["tokens"], cfg),
            prefill=lambda p, b, max_len=0: mamba2.prefill(
                p, b["tokens"], cfg, max_len),
            decode_step=lambda p, t, c: mamba2.decode_step(p, t, c, cfg),
            cache_seq_axes=mamba2.cache_seq_axes,
        )
    if fam == "hybrid":
        return ModelApi(
            cfg=cfg,
            init=lambda key: hybrid.init(key, cfg),
            loss_fn=lambda p, b: hybrid.loss_fn(p, b, cfg),
            forward=lambda p, b: hybrid.forward(p, b["tokens"], cfg),
            prefill=lambda p, b, max_len: hybrid.prefill(
                p, b["tokens"], cfg, max_len, layout=layout(max_len)),
            decode_step=lambda p, t, c, **kw: hybrid.decode_step(
                p, t, c, cfg, attn_backend=attn, **kw),
            cache_seq_axes=hybrid.cache_seq_axes,
        )
    if fam == "encdec":
        return ModelApi(
            cfg=cfg,
            init=lambda key: encdec.init(key, cfg),
            loss_fn=lambda p, b: encdec.loss_fn(p, b, cfg),
            forward=lambda p, b: encdec.forward(p, b, cfg),
            prefill=lambda p, b, max_len: encdec.prefill(
                p, b, cfg, max_len, layout=layout(max_len)),
            decode_step=lambda p, t, c, **kw: encdec.decode_step(
                p, t, c, cfg, attn_backend=attn, **kw),
            cache_seq_axes=encdec.cache_seq_axes,
        )
    raise ValueError(fam)


# ---------------------------------------------------------------------------
# pipeline stages — the serverless LM executor's per-stage API
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class StageModel:
    """Per-stage functions for the pipeline-parallel serverless executor.

    ``slice_params(params, spec)`` materializes the subtree a
    :class:`repro.core.partitioner.StageSpec` keeps worker-resident;
    ``prefill(stage_params, spec, x_in, max_len)`` and
    ``decode_step(stage_params, spec, x_in, stage_cache)`` run one stage —
    token ids in on the embedding stage, the previous stage's hidden states
    otherwise; logits out on the head stage.  The stage's KV cache never
    crosses a stage boundary."""

    cfg: ModelConfig
    slice_params: Callable[..., PyTree]
    prefill: Callable[..., Tuple[jnp.ndarray, PyTree]]
    decode_step: Callable[..., Tuple[jnp.ndarray, PyTree]]


def get_stage_model(cfg: ModelConfig, attn_backend=None) -> StageModel:
    """Stage-executor functions for ``cfg``'s family.

    Supported families: ``dense``/``vlm`` (transformer) and ``moe``.  The
    recurrent families (ssm/hybrid) and the encoder-decoder keep state shapes
    that the contiguous-layer-slice planner does not cover yet."""
    from repro.core.backends import cache_layout_for, get_backend

    fam = cfg.family
    if fam not in ("dense", "vlm", "moe"):
        raise ValueError(
            f"pipeline stages are not supported for family {fam!r} "
            f"(supported: dense, vlm, moe)")
    attn = get_backend("attention", attn_backend)
    layout = lambda max_len: cache_layout_for(attn, max_len)
    if fam in ("dense", "vlm"):
        return StageModel(
            cfg=cfg,
            slice_params=lambda p, spec: transformer.slice_stage_params(p, spec),
            prefill=lambda sp, spec, x, max_len, extra=None:
                transformer.stage_prefill(
                    sp, spec, x, cfg, max_len, extra_embeds=extra,
                    layout=layout(max_len)),
            decode_step=lambda sp, spec, x, c:
                transformer.stage_decode_step(
                    sp, spec, x, c, cfg, attn_backend=attn),
        )
    return StageModel(
        cfg=cfg,
        slice_params=lambda p, spec: moe.slice_stage_params(p, spec, cfg),
        prefill=lambda sp, spec, x, max_len, extra=None:
            moe.stage_prefill(sp, spec, x, cfg, max_len, layout=layout(max_len)),
        decode_step=lambda sp, spec, x, c:
            moe.stage_decode_step(sp, spec, x, c, cfg, attn_backend=attn),
    )


# ---------------------------------------------------------------------------
# input specs — concrete batches or ShapeDtypeStructs per (arch × shape)
# ---------------------------------------------------------------------------


def input_specs(
    cfg: ModelConfig,
    shape: ShapeConfig,
    abstract: bool = True,
    seed: int = 0,
) -> Dict[str, Any]:
    """Batch stand-ins for a (arch × shape) cell.

    ``abstract=True`` → ShapeDtypeStructs (dry-run: weak-type-correct,
    shardable, no allocation).  ``abstract=False`` → concrete random arrays
    (smoke tests / examples).

    train:   {"tokens" [B,S], "labels" [B,S], (+frontend embeds)}
    prefill: {"tokens" [B,S], ...}
    decode:  {"token" [B,1]} — the KV cache of length seq_len is built
             separately by ``cache_specs``.
    """
    B, S = shape.global_batch, shape.seq_len
    tok_t = jnp.int32

    def arr(shp, dtype):
        if abstract:
            return jax.ShapeDtypeStruct(shp, dtype)
        rng = np.random.default_rng(seed)
        if dtype == jnp.int32:
            return jnp.asarray(
                rng.integers(0, max(2, cfg.vocab_size or 2), size=shp), dtype)
        return jnp.asarray(rng.standard_normal(shp), dtype)

    if shape.kind == "train":
        if cfg.family == "encdec":
            return {
                "frames": arr((B, cfg.frontend_tokens, cfg.d_model), jnp.bfloat16),
                "tokens": arr((B, S), tok_t),
                "labels": arr((B, S), tok_t),
            }
        batch = {"tokens": arr((B, S), tok_t), "labels": arr((B, S), tok_t)}
        if cfg.family == "vlm":
            batch["extra_embeds"] = arr(
                (B, cfg.frontend_tokens, cfg.d_model), jnp.bfloat16)
        return batch

    if shape.kind == "prefill":
        if cfg.family == "encdec":
            return {
                "frames": arr((B, cfg.frontend_tokens, cfg.d_model), jnp.bfloat16),
                "tokens": arr((B, S), tok_t),
            }
        if cfg.family == "vlm":
            # image tokens occupy the front of the context window: the text
            # prompt shrinks so prefix+prompt == seq_len == cache capacity
            return {
                "tokens": arr((B, S - cfg.frontend_tokens), tok_t),
                "extra_embeds": arr(
                    (B, cfg.frontend_tokens, cfg.d_model), jnp.bfloat16),
            }
        return {"tokens": arr((B, S), tok_t)}

    # decode: one new token against a seq_len cache
    return {"token": arr((B, 1), tok_t)}


def cache_specs(
    cfg: ModelConfig, shape: ShapeConfig, abstract: bool = True,
) -> PyTree:
    """KV/SSM cache stand-ins of capacity ``shape.seq_len`` for decode cells.

    Attention KV arrays use the kernel-native ``[..., B, KV, S, D]`` layout
    (``models.kvcache`` / ``repro.core.backends.KVCacheLayout``); the
    capacity here is exactly ``seq_len`` — the identity layout, since the
    dry-run decodes through the ``dense-ref`` oracle.
    """
    B, S = shape.global_batch, shape.seq_len
    kv_dt = jnp.bfloat16

    def arr(shp, dtype):
        if abstract:
            return jax.ShapeDtypeStruct(shp, dtype)
        return jnp.zeros(shp, dtype)

    def scalar_len(fill=None):
        if abstract:
            return jax.ShapeDtypeStruct((), jnp.int32)
        return jnp.asarray(S - 1 if fill is None else fill, jnp.int32)

    if cfg.family in ("dense", "vlm"):
        Lr = cfg.n_layers
        return {
            "k": arr((Lr, B, cfg.eff_kv_heads, S, cfg.d_head), kv_dt),
            "v": arr((Lr, B, cfg.eff_kv_heads, S, cfg.d_head), kv_dt),
            "length": scalar_len(),
        }
    if cfg.family == "moe":
        stacks = []
        if cfg.first_dense_layers:
            stacks.append({
                "k": arr((cfg.first_dense_layers, B, cfg.eff_kv_heads, S, cfg.d_head), kv_dt),
                "v": arr((cfg.first_dense_layers, B, cfg.eff_kv_heads, S, cfg.d_head), kv_dt),
            })
        n_moe = cfg.n_layers - cfg.first_dense_layers
        stacks.append({
            "k": arr((n_moe, B, cfg.eff_kv_heads, S, cfg.d_head), kv_dt),
            "v": arr((n_moe, B, cfg.eff_kv_heads, S, cfg.d_head), kv_dt),
        })
        return {"stacks": stacks, "length": scalar_len()}
    if cfg.family == "ssm":
        Lr = cfg.n_layers
        gn = cfg.ssm_groups * cfg.ssm_state
        Km1 = cfg.conv_kernel - 1
        return {
            "conv": {
                "x": arr((Lr, B, Km1, cfg.d_inner), kv_dt),
                "B": arr((Lr, B, Km1, gn), kv_dt),
                "C": arr((Lr, B, Km1, gn), kv_dt),
            },
            "ssm": arr((Lr, B, cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state),
                       jnp.float32),
            "length": scalar_len(),
        }
    if cfg.family == "hybrid":
        from repro.models.hybrid import _group_sizes

        n_full, g, tail = _group_sizes(cfg)
        gn = cfg.ssm_groups * cfg.ssm_state
        Km1 = cfg.conv_kernel - 1

        def conv_dict(lead):
            return {
                "x": arr(lead + (B, Km1, cfg.d_inner), kv_dt),
                "B": arr(lead + (B, Km1, gn), kv_dt),
                "C": arr(lead + (B, Km1, gn), kv_dt),
            }

        kv = (
            arr((n_full, B, cfg.eff_kv_heads, S, cfg.d_head), kv_dt),
            arr((n_full, B, cfg.eff_kv_heads, S, cfg.d_head), kv_dt),
        )
        states = (
            conv_dict((n_full, g)),
            arr((n_full, g, B, cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state),
                jnp.float32),
        )
        cache = {"kv": kv, "states": states, "length": scalar_len()}
        if tail:
            cache["tail_kv"] = (
                arr((B, cfg.eff_kv_heads, S, cfg.d_head), kv_dt),
                arr((B, cfg.eff_kv_heads, S, cfg.d_head), kv_dt),
            )
            cache["tail_state"] = (
                conv_dict((tail,)),
                arr((tail, B, cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state),
                    jnp.float32),
            )
        else:
            cache["tail_kv"] = None
            cache["tail_state"] = None
        return cache
    if cfg.family == "encdec":
        Lr = cfg.n_layers
        Ssrc = cfg.frontend_tokens
        return {
            "k": arr((Lr, B, cfg.eff_kv_heads, S, cfg.d_head), kv_dt),
            "v": arr((Lr, B, cfg.eff_kv_heads, S, cfg.d_head), kv_dt),
            "kc": arr((Lr, B, cfg.eff_kv_heads, Ssrc, cfg.d_head), kv_dt),
            "vc": arr((Lr, B, cfg.eff_kv_heads, Ssrc, cfg.d_head), kv_dt),
            "length": scalar_len(),
            "src_length": scalar_len(fill=Ssrc),
        }
    raise ValueError(cfg.family)
