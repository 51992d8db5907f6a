"""Sharding rules: params, batches and caches → PartitionSpecs.

Parameter rules are *name + trailing-dims* based: each parameter name maps to
a spec for its trailing semantic dims, and any extra leading dims (the
stacked-layer axis, zamba's [group, layer] axes) get ``None`` — so one table
covers every family.

Policies (DESIGN.md §6):

* weights: TP over ``model`` (heads / ffn / experts / ssd-heads); optional
  FSDP shards the non-TP dim over ``data`` (``cfg_fsdp=True`` for the models
  whose optimizer+grads exceed HBM otherwise);
* GQA with ``n_kv_heads`` not divisible by the model axis: KV projections
  stay replicated on the head dim (they are small) — scores still shard over
  Q heads;
* train/prefill activations: batch over ``(pod, data)``;
* decode KV cache: batch over dp axes when divisible, **sequence over
  model** (split-KV decode); long_500k (batch=1) puts sequence over
  (data, model) — 512k/512 = 1k per chip.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig, ShapeConfig
from repro.launch.mesh import MeshAxes

PyTree = Any


# ---------------------------------------------------------------------------
# parameter specs
# ---------------------------------------------------------------------------


def _tail_rules(cfg: ModelConfig, ax: MeshAxes, fsdp: bool):
    """name → trailing-dims spec (entries may be None / axis name / tuple)."""
    m = ax.model
    f = ax.dp if (fsdp and ax.dp) else None
    kv_ok = m is not None and cfg.n_kv_heads and (
        _padded_kv_heads(cfg) % ax.model_size == 0
    )
    heads_ok = m is not None and cfg.n_heads and (
        _padded_heads(cfg) % ax.model_size == 0
    )
    hm = m if heads_ok else None
    km = m if kv_ok else None
    return {
        # attention
        "wq": (f, hm, None),
        "wk": (f, km, None),
        "wv": (f, km, None),
        "wo@3": (hm, None, f),          # attn out-proj [H, dh, D]
        "bq": (hm, None),
        "bk": (km, None),
        "bv": (km, None),
        # mlp
        "wi_gate": (f, m),
        "wi_up": (f, m),
        "wo@2": (m, f),                 # mlp out-proj [F, D]
        # embeddings (vocab-sharded; a d_model-sharded variant was explored
        # in §Perf iteration 4 — better temp, worse collectives — and is
        # selectable by editing this rule)
        "embed": (m, f),
        "unembed": (m, f),
        # moe
        "router": (f, None),
        "w_gate": (m, f, None),
        "w_up": (m, f, None),
        "w_down": (m, None, f),
        # mamba2
        "in_z": (f, m),
        "in_x": (f, m),
        "in_B": (f, None),
        "in_C": (f, None),
        "in_dt": (f, None),
        "conv_x_w": (None, m),
        "conv_x_b": (m,),
        "conv_B_w": (None, None),
        "conv_B_b": (None,),
        "conv_C_w": (None, None),
        "conv_C_b": (None,),
        "A_log": (m,),
        "dt_bias": (m,),
        "D": (m,),
        "norm": (m,),                   # mamba RMSNorm over d_inner
        "out_proj": (m, f),
    }


def _padded_heads(cfg: ModelConfig) -> int:
    return cfg.eff_heads


def _padded_kv_heads(cfg: ModelConfig) -> int:
    return cfg.eff_kv_heads


def _leaf_name(path) -> str:
    for entry in reversed(path):
        if isinstance(entry, jax.tree_util.DictKey):
            return str(entry.key)
    return ""


def zero_param_pspecs(cfg: ModelConfig, params_shape: PyTree,
                      ax: MeshAxes) -> PyTree:
    """ZeRO-3 / pure-DP strategy (the §Perf beyond-paper optimization):

    the batch shards over *every* mesh axis and parameters shard on their
    first divisible dim over the whole mesh — XLA all-gathers a layer's
    weights just-in-time and reduce-scatters its grads, so the per-step
    collective volume is O(params) instead of O(activations·layers), which
    wins whenever the model is small relative to the token batch.
    """
    all_axes = tuple(ax.dp) + ((ax.model,) if ax.model else ())
    # leading dims of scan-stacked parameter trees are the layer axis — the
    # lax.scan slices one layer per step, so sharding that dim would force a
    # full re-gather every iteration (measured: 5-15× collective blow-up;
    # EXPERIMENTS.md §Perf iteration 1)
    stacked_keys = {"blocks", "moe_blocks", "dense_blocks", "enc_blocks",
                    "dec_blocks", "tail"}

    def spec_for(path, leaf):
        shape = leaf.shape
        names = {str(e.key) for e in path
                 if isinstance(e, jax.tree_util.DictKey)}
        skip = 0
        if names & stacked_keys:
            skip = 1
        if "groups" in names:       # zamba: [group, layer, ...]
            skip = 2
        if not shape or max(shape) < 1024:   # tiny tensors stay replicated
            return P(*([None] * len(shape)))
        spec = [None] * len(shape)
        # shard the largest divisible non-stacked dim over the whole mesh
        order = sorted(range(skip, len(shape)), key=lambda i: -shape[i])
        for i in order:
            keep = _divisible_prefix(all_axes, shape[i], ax)
            if keep and len(keep) == len(all_axes):
                spec[i] = keep if len(keep) > 1 else keep[0]
                break
        else:
            for i in order:
                keep = _divisible_prefix(all_axes, shape[i], ax)
                if keep:
                    spec[i] = keep if len(keep) > 1 else keep[0]
                    break
        return P(*spec)

    return jax.tree_util.tree_map_with_path(spec_for, params_shape)


def param_pspecs(cfg: ModelConfig, params_shape: PyTree, ax: MeshAxes,
                 fsdp: bool = False, strategy: str = "tp") -> PyTree:
    """PartitionSpec tree matching ``params_shape`` (a ShapeDtypeStruct tree).

    ``strategy="tp"`` — the baseline: tensor parallelism over ``model``
    (+ optional FSDP on the non-TP dim).  ``strategy="zero"`` — ZeRO-3 pure
    DP (see :func:`zero_param_pspecs`).

    ``ssm-heads over model`` requires divisibility; when it fails (reduced
    smoke configs on 1 device) everything degrades to replication because
    mesh axes are absent.
    """
    if strategy == "zero":
        return zero_param_pspecs(cfg, params_shape, ax)
    rules = _tail_rules(cfg, ax, fsdp)
    mamba_head_ok = ax.model is None or not cfg.ssm_heads or (
        cfg.ssm_heads % ax.model_size == 0
    )
    inner_ok = ax.model is None or not cfg.ssm_heads or (
        cfg.d_inner % ax.model_size == 0
    )

    def spec_for(path, leaf):
        name = _leaf_name(path)
        ndim = len(leaf.shape)
        key = name
        if name == "wo":
            key = f"wo@{min(ndim, 3) if ndim >= 3 else 2}"
            # stacked blocks add leading dims; attn wo tail is 3 dims,
            # mlp wo tail is 2 — disambiguate via trailing size heuristic:
            # attn wo trailing dims are [H, dh, D]; mlp wo is [F, D].
            key = "wo@3" if _looks_like_attn_wo(cfg, leaf.shape) else "wo@2"
        tail = rules.get(key)
        if tail is None:
            return P()
        # drop model-axis sharding for ssm tensors when heads don't divide
        if name in ("A_log", "dt_bias", "D") and not mamba_head_ok:
            tail = (None,) * len(tail)
        if name in ("in_z", "in_x", "conv_x_w", "conv_x_b", "norm",
                    "out_proj") and not inner_ok:
            tail = tuple(a if a != ax.model else None for a in tail)
        if len(tail) > ndim:
            tail = tail[-ndim:]
        spec = (None,) * (ndim - len(tail)) + tuple(tail)
        # never try to shard a dim the axis size doesn't divide
        spec = _drop_indivisible(spec, leaf.shape, ax)
        return P(*spec)

    return jax.tree_util.tree_map_with_path(spec_for, params_shape)


def _looks_like_attn_wo(cfg: ModelConfig, shape) -> bool:
    if len(shape) < 3:
        return False
    h, dh, d = shape[-3:]
    return dh == cfg.d_head and d == cfg.d_model


def _drop_indivisible(spec, shape, ax: MeshAxes):
    out = []
    for s, dim in zip(spec, shape):
        if s is None:
            out.append(None)
            continue
        size = ax.axis_size(s)
        out.append(s if size and dim % size == 0 else None)
    return tuple(out)


# ---------------------------------------------------------------------------
# batch / cache specs
# ---------------------------------------------------------------------------


def _dp_for(batch: int, ax: MeshAxes) -> Optional[Tuple[str, ...]]:
    """Largest prefix of dp axes whose product divides the batch."""
    dims: Tuple[str, ...] = ()
    prod = 1
    for a in ax.dp:
        if batch % (prod * ax.axis_size(a)) == 0:
            dims = dims + (a,)
            prod *= ax.axis_size(a)
    return dims if dims else None


def batch_pspecs(cfg: ModelConfig, shape: ShapeConfig, batch_tree: PyTree,
                 ax: MeshAxes) -> PyTree:
    dp = _dp_for(shape.global_batch, ax)

    def spec_for(path, leaf):
        ndim = len(leaf.shape)
        return P(dp, *([None] * (ndim - 1)))

    return jax.tree_util.tree_map_with_path(spec_for, batch_tree)


def cache_pspecs(cfg: ModelConfig, shape: ShapeConfig, cache_tree: PyTree,
                 ax: MeshAxes) -> PyTree:
    """Decode-cache specs: mirror registry.cache_specs structurally.

    KV arrays [..., B, KV, S, dh] (kernel-native layout): B→dp, S→model
    (+leftover dp when B=1) — the split-KV decode sharding.
    SSM states [..., B, H, P, N]: H→model when divisible.
    Conv tails [..., B, K-1, C]: C→model for the x-conv when divisible.
    """
    B = shape.global_batch
    dp = _dp_for(B, ax)
    used_dp = set(dp or ())
    free_dp = tuple(a for a in ax.dp if a not in used_dp)
    seq_axes: Tuple[str, ...] = tuple(free_dp) + ((ax.model,) if ax.model else ())

    def kv_spec(leaf, s_dim_size):
        ndim = len(leaf.shape)
        # [..., B, KV, S, dh]
        lead = ndim - 4
        seq = _divisible_prefix(seq_axes, s_dim_size, ax)
        return P(*([None] * lead), dp, None, seq if seq else None, None)

    def ssm_spec(leaf):
        ndim = len(leaf.shape)
        # [..., B, H, P, N]
        lead = ndim - 4
        h = leaf.shape[-3]
        m = ax.model if ax.model and h % ax.model_size == 0 else None
        return P(*([None] * lead), dp, m, None, None)

    def conv_spec(leaf):
        ndim = len(leaf.shape)
        # [..., B, K-1, C]
        lead = ndim - 3
        c = leaf.shape[-1]
        m = ax.model if ax.model and c % ax.model_size == 0 else None
        return P(*([None] * lead), dp, None, m)

    def spec_for(path, leaf):
        if not hasattr(leaf, "shape") or len(leaf.shape) == 0:
            return P()
        names = [str(e.key) for e in path if isinstance(e, jax.tree_util.DictKey)]
        name = names[-1] if names else ""
        ndim = len(leaf.shape)
        if name in ("k", "v", "kc", "vc") or (
            "kv" in names and ndim >= 4
        ) or ("tail_kv" in names and ndim >= 4):
            return kv_spec(leaf, leaf.shape[-2])
        if name == "ssm" or ("states" in names and ndim >= 4 and
                             leaf.shape[-1] == cfg.ssm_state):
            return ssm_spec(leaf)
        if name in ("x", "B", "C") or "conv" in names:
            return conv_spec(leaf)
        if "tail_state" in names:
            return ssm_spec(leaf) if leaf.shape[-1] == cfg.ssm_state else conv_spec(leaf)
        return P()

    return jax.tree_util.tree_map_with_path(spec_for, cache_tree)


def _divisible_prefix(axes: Tuple[str, ...], dim: int, ax: MeshAxes):
    out: Tuple[str, ...] = ()
    prod = 1
    for a in axes:
        if dim % (prod * ax.axis_size(a)) == 0:
            out = out + (a,)
            prod *= ax.axis_size(a)
    return out


def to_named(mesh, spec_tree: PyTree) -> PyTree:
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s),
        spec_tree,
        is_leaf=lambda x: isinstance(x, P),
    )
