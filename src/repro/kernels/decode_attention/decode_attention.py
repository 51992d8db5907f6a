"""Split-KV flash-decode Pallas kernel (decode_32k / long_500k serve path).

One new token attends to a long KV cache.  Grid: (batch·kv_heads, n_kv
blocks); the q vector (all G query heads of one KV head) stays in VMEM while
KV blocks stream; (m, l, acc) scratch carries the running softmax across the
sequential kv sweep; the final block normalizes and writes.

On the production mesh the cache's sequence dim is sharded: each device runs
this kernel over its LOCAL shard and the partial (out, lse) pairs combine
via the lse-weighted average (``models.attention.combine_split_kv``) — the
kernel therefore also emits the lse.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret

NEG_INF = -1e30


def _kernel(len_ref, q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr,
            acc_scr, *, bk: int, n_k: int, scale: float):
    kj = pl.program_id(1)

    @pl.when(kj == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32)            # [G, D]
    k = k_ref[0, 0].astype(jnp.float32)            # [bk, D]
    v = v_ref[0, 0].astype(jnp.float32)
    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale  # [G, bk]
    kpos = kj * bk + jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1)
    s = jnp.where(kpos < len_ref[0, 0], s, NEG_INF)
    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_new = l_scr[...] * corr + p.sum(axis=-1, keepdims=True)
    acc_scr[...] = acc_scr[...] * corr + jnp.dot(
        p, v, preferred_element_type=jnp.float32)
    m_scr[...] = m_new
    l_scr[...] = l_new

    @pl.when(kj == n_k - 1)
    def _finish():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / l).astype(o_ref.dtype)
        lse_ref[0, 0] = m_scr[...] + jnp.log(l)


def decode_attention(
    q: jnp.ndarray,        # [B, H, D] — one token's query heads
    k_cache: jnp.ndarray,  # [B, KV, S, D] (local shard)
    v_cache: jnp.ndarray,  # [B, KV, S, D]
    cache_len: jnp.ndarray,  # int32 [] — valid prefix
    block_k: int = 512,
    interpret: bool | None = None,
):
    """Returns (out [B, H, D], lse [B, H]) — normalized partials + lse.

    ``interpret=None`` resolves from the platform (interpreter off-TPU only).
    """
    interpret = resolve_interpret(interpret)
    B, H, D = q.shape
    KV, S = k_cache.shape[1], k_cache.shape[2]
    G = H // KV
    bk = min(block_k, S)
    assert S % bk == 0
    n_k = S // bk
    grid = (B * KV, n_k)
    scale = 1.0 / (D ** 0.5)
    qg = q.reshape(B, KV, G, D)
    # [1, 1] rather than [1]: under the scheduler's vmap over slots the
    # SMEM block gains a leading squeezed dim, and the chip's tiling rule
    # accepts a block only when its last two dims span the array.
    lens = jnp.broadcast_to(jnp.asarray(cache_len, jnp.int32), (1, 1))

    out, lse = pl.pallas_call(
        functools.partial(_kernel, bk=bk, n_k=n_k, scale=scale),
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, G, D), lambda bh, kj: (bh // KV, bh % KV, 0, 0)),
            pl.BlockSpec((1, 1, bk, D), lambda bh, kj: (bh // KV, bh % KV, kj, 0)),
            pl.BlockSpec((1, 1, bk, D), lambda bh, kj: (bh // KV, bh % KV, kj, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, G, D), lambda bh, kj: (bh // KV, bh % KV, 0, 0)),
            # lse as a [G, 1] column: its block spans the last two dims
            pl.BlockSpec((1, 1, G, 1),
                         lambda bh, kj: (bh // KV, bh % KV, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, KV, G, D), q.dtype),
            jax.ShapeDtypeStruct((B, KV, G, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, D), jnp.float32),
        ],
        interpret=interpret,
    )(lens, qg, k_cache, v_cache)
    return out.reshape(B, H, D), lse.reshape(B, H)
