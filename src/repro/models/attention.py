"""Attention score computation: chunked (flash-style) softmax streaming.

Three entry points:

* :func:`chunked_causal_attention` — training/prefill.  Never materializes
  the full [Sq, Sk] score matrix: scans KV chunks with running (max, sum,
  acc) — the pure-jnp flash algorithm, and the oracle for the Pallas
  ``flash_attention`` kernel.
* :func:`decode_attention` — single-query attention against a KV cache,
  scanning KV chunks (the oracle for the ``decode_attention`` kernel).  When
  the cache is sequence-sharded across devices, partial (acc, lse) pairs are
  psum-combined by the caller (split-KV / flash-decoding).
* :func:`full_attention` — naive reference for tests.

Decode caches use the **kernel-native** layout ``[B, KV, S, D]`` (the
``kernels/decode_attention`` block layout) end-to-end: every decode entry
point here consumes that layout directly, so the Pallas split-KV kernel, the
dense oracle and the chunked scan all read the same buffers without a
per-step re-layout (prefill writes the cache in this layout once).

All math accumulates in fp32.
"""

from __future__ import annotations

from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

ACC = jnp.float32
NEG_INF = -1e30


def _gqa_scores(q, k):
    """q [B,Sq,H,D], k [B,Sk,KV,D] → scores [B,KV,G,Sq,Sk] (H = KV·G)."""
    B, Sq, H, D = q.shape
    KV = k.shape[2]
    G = H // KV
    qg = q.reshape(B, Sq, KV, G, D)
    return jnp.einsum("bqkgd,bskd->bkgqs", qg, k, preferred_element_type=ACC)


def full_attention(
    q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
    causal: bool = True, q_offset: int = 0,
) -> jnp.ndarray:
    """Naive reference (materializes scores) — test oracle only."""
    B, Sq, H, D = q.shape
    KV = k.shape[2]
    Sk = k.shape[1]
    scores = _gqa_scores(q, k) / jnp.sqrt(D).astype(ACC)
    if causal:
        qpos = jnp.arange(Sq) + q_offset
        kpos = jnp.arange(Sk)
        mask = qpos[:, None] >= kpos[None, :]
        scores = jnp.where(mask[None, None, None], scores, NEG_INF)
    w = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgqs,bskd->bqkgd", w.astype(v.dtype), v,
                     preferred_element_type=ACC)
    return out.reshape(B, Sq, H, D).astype(q.dtype)


def chunked_causal_attention(
    q: jnp.ndarray,             # [B, Sq, H, D]
    k: jnp.ndarray,             # [B, Sk, KV, D]
    v: jnp.ndarray,             # [B, Sk, KV, D]
    q_chunk: int = 512,
    kv_chunk: int = 1024,
    causal: bool = True,
    q_offset: int = 0,          # global position of q[0] (prefill continuation)
) -> jnp.ndarray:
    """Flash-style attention: O(Sq·Sk) compute, O(chunk²) memory."""
    B, Sq, H, D = q.shape
    Sk, KV = k.shape[1], k.shape[2]
    G = H // KV
    q_chunk = min(q_chunk, Sq)
    kv_chunk = min(kv_chunk, Sk)
    n_q = -(-Sq // q_chunk)
    n_k = -(-Sk // kv_chunk)
    # pad to whole chunks
    q_pad = n_q * q_chunk - Sq
    k_pad = n_k * kv_chunk - Sk
    if q_pad:
        q = jnp.pad(q, ((0, 0), (0, q_pad), (0, 0), (0, 0)))
    if k_pad:
        k = jnp.pad(k, ((0, 0), (0, k_pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, k_pad), (0, 0), (0, 0)))
    qs = q.reshape(B, n_q, q_chunk, KV, G, D).transpose(1, 0, 3, 4, 2, 5)
    ks = k.reshape(B, n_k, kv_chunk, KV, D).transpose(1, 0, 3, 2, 4)
    vs = v.reshape(B, n_k, kv_chunk, KV, D).transpose(1, 0, 3, 2, 4)
    scale = 1.0 / jnp.sqrt(D).astype(ACC)

    kv_valid = (jnp.arange(n_k * kv_chunk) < Sk).reshape(n_k, kv_chunk)

    def q_body(qi, q_blk):
        # q_blk [B, KV, G, q_chunk, D]
        m0 = jnp.full((B, KV, G, q_chunk), NEG_INF, ACC)
        l0 = jnp.zeros((B, KV, G, q_chunk), ACC)
        a0 = jnp.zeros((B, KV, G, q_chunk, D), ACC)

        def kv_body(carry, inp):
            m, l, acc = carry
            kj, k_blk, v_blk, valid = inp
            s = jnp.einsum("bkgqd,bksd->bkgqs", q_blk, k_blk,
                           preferred_element_type=ACC) * scale
            if causal:
                qpos = qi * q_chunk + jnp.arange(q_chunk) + q_offset
                kpos = kj * kv_chunk + jnp.arange(kv_chunk)
                mask = (qpos[:, None] >= kpos[None, :]) & valid[None, :]
            else:
                mask = jnp.broadcast_to(valid[None, :], (q_chunk, kv_chunk))
            s = jnp.where(mask[None, None, None], s, NEG_INF)
            m_new = jnp.maximum(m, s.max(axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + p.sum(axis=-1)
            acc_new = acc * corr[..., None] + jnp.einsum(
                "bkgqs,bksd->bkgqd", p.astype(v_blk.dtype), v_blk,
                preferred_element_type=ACC,
            )
            return (m_new, l_new, acc_new), None

        (m, l, acc), _ = jax.lax.scan(
            kv_body, (m0, l0, a0), (jnp.arange(n_k), ks, vs, kv_valid)
        )
        return acc / jnp.maximum(l, 1e-30)[..., None]

    # Checkpoint per q-chunk: naive autodiff through the kv scan would stash
    # every chunk's probability block — O(Sq·Sk) residuals, exactly what
    # flash attention exists to avoid.  Rematerializing per q-chunk bounds
    # backward residuals to one chunk row.
    q_body = jax.checkpoint(q_body, prevent_cse=False)
    outs = jax.lax.map(lambda args: q_body(*args), (jnp.arange(n_q), qs))
    # outs [n_q, B, KV, G, q_chunk, D] → [B, Sq, H, D]
    out = outs.transpose(1, 0, 4, 2, 3, 5).reshape(B, n_q * q_chunk, H, D)
    return out[:, :Sq].astype(q.dtype)


def decode_attention(
    q: jnp.ndarray,             # [B, 1, H, D] — one new token
    k_cache: jnp.ndarray,       # [B, KV, S, D] (local shard if seq-sharded)
    v_cache: jnp.ndarray,       # [B, KV, S, D]
    cache_len: Optional[jnp.ndarray] = None,  # valid prefix length (≤ S)
    kv_chunk: int = 2048,
    return_lse: bool = False,
) -> jnp.ndarray | Tuple[jnp.ndarray, jnp.ndarray]:
    """Streaming single-token attention over the kernel-native KV cache.

    With ``return_lse=True`` returns the *normalized* partial output plus its
    logsumexp, so a sequence-sharded caller combines partials across devices
    as an lse-weighted average:
        w_i = exp(lse_i - max_i lse_i);  out = psum(w_i·out_i) / psum(w_i)
    — the split-KV / flash-decoding scheme.
    """
    B, _, H, D = q.shape
    KV, S = k_cache.shape[1], k_cache.shape[2]
    G = H // KV
    kv_chunk = min(kv_chunk, S)
    n_k = -(-S // kv_chunk)
    pad = n_k * kv_chunk - S
    if pad:
        k_cache = jnp.pad(k_cache, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v_cache = jnp.pad(v_cache, ((0, 0), (0, 0), (0, pad), (0, 0)))
    ks = k_cache.reshape(B, KV, n_k, kv_chunk, D).transpose(2, 0, 1, 3, 4)
    vs = v_cache.reshape(B, KV, n_k, kv_chunk, D).transpose(2, 0, 1, 3, 4)
    qg = q.reshape(B, KV, G, D)
    scale = 1.0 / jnp.sqrt(D).astype(ACC)
    if cache_len is None:
        cache_len = jnp.asarray(S, jnp.int32)

    def body(carry, inp):
        m, l, acc = carry
        kj, k_blk, v_blk = inp
        s = jnp.einsum("bkgd,bksd->bkgs", qg, k_blk,
                       preferred_element_type=ACC) * scale
        kpos = kj * kv_chunk + jnp.arange(kv_chunk)
        valid = kpos < cache_len
        s = jnp.where(valid[None, None, None], s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(axis=-1)
        acc_new = acc * corr[..., None] + jnp.einsum(
            "bkgs,bksd->bkgd", p.astype(v_blk.dtype), v_blk,
            preferred_element_type=ACC,
        )
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((B, KV, G), NEG_INF, ACC)
    l0 = jnp.zeros((B, KV, G), ACC)
    a0 = jnp.zeros((B, KV, G, D), ACC)
    (m, l, acc), _ = jax.lax.scan(body, (m0, l0, a0), (jnp.arange(n_k), ks, vs))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    if return_lse:
        lse = m + jnp.log(jnp.maximum(l, 1e-30))
        return out.reshape(B, 1, H, D), lse.reshape(B, 1, H)
    return out.reshape(B, 1, H, D).astype(q.dtype)


def decode_attention_dense(
    q: jnp.ndarray,             # [B, 1, H, D]
    k_cache: jnp.ndarray,       # [B, KV, S, D]
    v_cache: jnp.ndarray,       # [B, KV, S, D]
    cache_len,                  # valid prefix length
    return_lse: bool = False,
) -> jnp.ndarray | Tuple[jnp.ndarray, jnp.ndarray]:
    """Single-token attention over the full cache, no chunking.

    Under pjit this is the *sequence-shardable* decode path: the scores
    einsum contracts the sharded S dim, so the partitioner emits masked
    partial softmax + all-reduce — exactly split-KV decode, chosen by the
    compiler instead of hand-written scans (which would reshape the sharded
    dim and force all-gathers).  Memory is fine because Sq = 1.

    ``return_lse=True`` returns ``(out [B,1,H,D] fp32 normalized partial,
    lse [B,1,H])`` for the explicit shard_map split-KV combine
    (:func:`combine_split_kv`).
    """
    B, _, H, D = q.shape
    KV, S = k_cache.shape[1], k_cache.shape[2]
    G = H // KV
    qg = q.reshape(B, 1, KV, G, D)
    scale = 1.0 / jnp.sqrt(D).astype(ACC)
    s = jnp.einsum("bqkgd,bksd->bkgqs", qg, k_cache,
                   preferred_element_type=ACC) * scale
    valid = jnp.arange(S) < cache_len
    s = jnp.where(valid[None, None, None, None, :], s, NEG_INF)
    m = s.max(axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    l = p.sum(axis=-1, keepdims=True)
    # Contract into the dot's natural [b, k, g, q, d] order and transpose
    # after: XLA:CPU refuses some bf16 x bf16 -> f32 dots whose output
    # transpose is folded into them ("Unsupported element type for
    # DotThunk").
    out = jnp.einsum("bkgqs,bksd->bkgqd", (p / jnp.maximum(l, 1e-30)).astype(v_cache.dtype),
                     v_cache, preferred_element_type=ACC).transpose(0, 3, 1, 2, 4)
    if return_lse:
        lse = (m + jnp.log(jnp.maximum(l, 1e-30)))[..., 0, 0]  # [B, KV, G]
        return out.reshape(B, 1, H, D), lse.reshape(B, 1, H)
    return out.reshape(B, 1, H, D).astype(q.dtype)


def combine_split_kv(
    out: jnp.ndarray,           # [B, 1, H, D] normalized local partial
    lse: jnp.ndarray,           # [B, 1, H] local logsumexp
    axis_names,
) -> jnp.ndarray:
    """Cross-device combine for sequence-sharded decode (inside shard_map).

    Shards with no valid positions contribute ``lse ≈ -inf`` → weight 0, so
    ragged ``cache_len`` never poisons the merge.  The combine is associative
    in exact arithmetic; shard-count invariance in fp32 is property-tested in
    ``tests/test_sharded_decode.py``.
    """
    m = jax.lax.pmax(lse, axis_names)
    w = jnp.exp(lse - m)
    num = jax.lax.psum(out * w[..., None], axis_names)
    den = jax.lax.psum(w, axis_names)
    return num / jnp.maximum(den[..., None], 1e-30)


def combine_split_kv_stacked(outs: jnp.ndarray, lses: jnp.ndarray) -> jnp.ndarray:
    """Host-side mirror of :func:`combine_split_kv` over a leading shard
    axis: ``outs [n, B, 1, H, D]``, ``lses [n, B, 1, H]`` → ``[B, 1, H, D]``.
    Used by the shard-count-invariance property tests and single-process
    split-KV emulation (the math is identical; ``psum``/``pmax`` become
    ``sum``/``max`` over axis 0)."""
    m = lses.max(axis=0)
    w = jnp.exp(lses - m)
    num = (outs * w[..., None]).sum(axis=0)
    den = w.sum(axis=0)
    return num / jnp.maximum(den[..., None], 1e-30)


def seq_shard_bounds(axis_names, s_local: int):
    """(offset, shard index) of this device's KV-cache sequence slice.

    Valid only inside a ``shard_map``/manual region where ``axis_names`` are
    bound.  Multiple axes compose row-major (the order the cache's S dim was
    sharded over), matching ``PartitionSpec((a, b))`` layout.
    """
    names = (axis_names if isinstance(axis_names, (tuple, list))
             else (axis_names,))
    idx = jnp.zeros((), jnp.int32)
    for a in names:
        idx = idx * jax.lax.psum(1, a) + jax.lax.axis_index(a)
    return idx * s_local, idx


def insert_kv_local(cache: jnp.ndarray, update: jnp.ndarray, local_pos,
                    owned) -> jnp.ndarray:
    """Write a one-token KV update into a shard-local ``[B, KV, S_loc, D]``
    cache at ``local_pos``, as a no-op on shards that don't own the global
    position (``owned`` False): the surrounding values are read back and
    re-written so the buffer is bit-unchanged."""
    start = (0, 0, jnp.asarray(local_pos, jnp.int32), 0)
    cur = jax.lax.dynamic_slice(cache, start, update.shape)
    return jax.lax.dynamic_update_slice(
        cache, jnp.where(owned, update, cur), start)


def sharded_decode_attend(attn, q, k_new, v_new, k_cache, v_cache, pos,
                          axis_names):
    """The sequence-sharded decode op, start to finish (inside shard_map):
    insert the new token's ``[B, KV, 1, D]`` KV on the shard owning global
    position ``pos``, run the backend's split-KV form over the local slice
    with the shard-local valid prefix, and lse-combine partials across
    ``axis_names``.  Returns ``(o [B,1,H,D] fp32, k_cache, v_cache)``.
    This is THE hot-path recipe — the model families, the op-level parity
    tests and the ``decode_sharded_*`` bench all call it, so they can never
    drift apart."""
    s_local = k_cache.shape[2]
    offset, _ = seq_shard_bounds(axis_names, s_local)
    local_pos = jnp.clip(pos - offset, 0, s_local - 1)
    owned = (pos >= offset) & (pos - offset < s_local)
    k_cache = insert_kv_local(k_cache, k_new, local_pos, owned)
    v_cache = insert_kv_local(v_cache, v_new, local_pos, owned)
    local_len = jnp.clip(pos + 1 - offset, 0, s_local)
    o, lse = attn.decode_partial(q, k_cache, v_cache, cache_len=local_len)
    return combine_split_kv(o, lse, axis_names), k_cache, v_cache
