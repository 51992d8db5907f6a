"""Split-KV flash-decode Pallas kernel (decode_32k / long_500k serve path).

One new token attends to a long KV cache.  Grid: (batch·kv_heads, n_kv
blocks); the q vector (all G query heads of one KV head) stays in VMEM while
KV blocks stream; (m, l, acc) scratch carries the running softmax across the
sequential kv sweep; the final block normalizes and writes.

On the production mesh the cache's sequence dim is sharded: each device runs
this kernel over its LOCAL shard and the partial (out, lse) pairs combine
via the lse-weighted average (``models.attention.combine_split_kv``) — the
kernel therefore also emits the lse.

:func:`paged_decode_attention` runs the same per-block math over the
continuous-batching scheduler's paged KV pool, reading each slot's pages
through its block table instead of a contiguous cache.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret

NEG_INF = -1e30


def _attend_block(q, k, v, kpos0, length, m_scr, l_scr, acc_scr, *,
                  bk: int, scale: float):
    """One KV block's online-softmax update of (m, l, acc): q ``[G, D]``
    against the ``[bk, D]`` block at positions ``kpos0 ..``, of which those
    below ``length`` are valid.  A block with none valid leaves the three
    exactly as they were (its scores are -1e30, its weights exactly 0)."""
    q = q.astype(jnp.float32)
    k = k.astype(jnp.float32)
    v = v.astype(jnp.float32)
    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale  # [G, bk]
    kpos = kpos0 + jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1)
    s = jnp.where(kpos < length, s, NEG_INF)
    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_new = l_scr[...] * corr + p.sum(axis=-1, keepdims=True)
    acc_scr[...] = acc_scr[...] * corr + jnp.dot(
        p, v, preferred_element_type=jnp.float32)
    m_scr[...] = m_new
    l_scr[...] = l_new


def _init_scratch(m_scr, l_scr, acc_scr):
    m_scr[...] = jnp.full_like(m_scr, NEG_INF)
    l_scr[...] = jnp.zeros_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)


def _normalized(m_scr, l_scr, acc_scr):
    """The swept blocks' (normalized output [G, D], lse [G, 1])."""
    l = jnp.maximum(l_scr[...], 1e-30)
    return acc_scr[...] / l, m_scr[...] + jnp.log(l)


def _kernel(len_ref, q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr,
            acc_scr, *, bk: int, n_k: int, scale: float):
    kj = pl.program_id(1)

    @pl.when(kj == 0)
    def _init():
        _init_scratch(m_scr, l_scr, acc_scr)

    _attend_block(q_ref[0, 0], k_ref[0, 0], v_ref[0, 0], kj * bk,
                  len_ref[0, 0], m_scr, l_scr, acc_scr, bk=bk, scale=scale)

    @pl.when(kj == n_k - 1)
    def _finish():
        o, lse = _normalized(m_scr, l_scr, acc_scr)
        o_ref[0, 0] = o.astype(o_ref.dtype)
        lse_ref[0, 0] = lse


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


def _paged_kernel(pages_ref, lens_ref, layer_ref, q_ref, k_ref, v_ref, o_ref,
                  lse_ref, m_scr, l_scr, acc_scr, *, bk: int, width: int,
                  kv: int, scale: float):
    del layer_ref                      # read by the index maps only
    slot, j = pl.program_id(0), pl.program_id(1)
    length = lens_ref[slot]
    # one (m, l, acc) per KV head: the heads of a page share its grid step
    heads = [(m_scr.at[h], l_scr.at[h], acc_scr.at[h]) for h in range(kv)]

    @pl.when(j == 0)
    def _init():
        for scr in heads:
            _init_scratch(*scr)

    # Pages past the slot's last valid one would be wholly masked, which
    # leaves (m, l, acc) exactly unchanged: skip them.
    @pl.when(j * bk < length)
    def _page():
        for h, scr in enumerate(heads):
            _attend_block(q_ref[0, h], k_ref[0, 0, h, 0], v_ref[0, 0, h, 0],
                          j * bk, length, *scr, bk=bk, scale=scale)

    @pl.when(j == width - 1)
    def _finish():
        for h, scr in enumerate(heads):
            o, lse = _normalized(*scr)
            o_ref[0, h] = o.astype(o_ref.dtype)
            lse_ref[0, h] = lse


def paged_decode_attention(
    q: jnp.ndarray,        # [slots, H, D] — each slot's new token's queries
    k_pages: jnp.ndarray,  # [L, 1, KV, num_blocks, bk, D] — the paged pool
    v_pages: jnp.ndarray,  # [L, 1, KV, num_blocks, bk, D]
    tables: jnp.ndarray,   # int32 [slots, W] — each slot's physical pages
    lengths: jnp.ndarray,  # int32 [slots] — valid positions, each >= 1
    layer: jnp.ndarray,    # int32 [] — the layer of the pool to read
    interpret: bool | None = None,
):
    """Split-K decode attention that reads K/V pages straight from the
    paged pool through each slot's block table.

    Grid (slots, table width): a grid step DMAs one page of one layer, the
    ``(bk, D)`` block of every KV head, from wherever the table puts it, so
    no gathered per-slot cache exists.  (With a grid step per head, each
    step's fixed cost matched its DMA's time: at ``lm_code``'s shapes on a
    v5e that kernel took twice as long.)  Past a slot's last valid page
    the index stays on that page (the pipeline fetches nothing new) and the
    step is skipped.  Per head and block the math is
    :func:`decode_attention`'s with ``block_k = bk``, so both give the same
    bits on the same positions.  Returns (out [slots, H, D], lse [slots,
    H]).
    """
    interpret = resolve_interpret(interpret)
    slots, H, D = q.shape
    KV, bk = k_pages.shape[2], k_pages.shape[4]
    W = tables.shape[1]
    G = H // KV
    scale = 1.0 / (D ** 0.5)
    lengths = jnp.asarray(lengths, jnp.int32)
    last = jnp.maximum(lengths - 1, 0) // bk
    # The page each grid step fetches, flattened (SMEM pads a 2-D array's
    # minor dim to 128 words).
    pages = jnp.take_along_axis(
        tables.astype(jnp.int32),
        jnp.minimum(jnp.arange(W)[None, :], last[:, None]), axis=1
    ).reshape(-1)
    layer = jnp.reshape(jnp.asarray(layer, jnp.int32), (1,))
    qg = q.reshape(slots, KV, G, D)

    def kv_map(s, j, pages, lens, layer):
        return (layer[0], 0, 0, pages[s * W + j], 0, 0)

    def q_map(s, j, pages, lens, layer):
        return (s, 0, 0, 0)

    out, lse = pl.pallas_call(
        functools.partial(_paged_kernel, bk=bk, width=W, kv=KV, scale=scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(slots, W),
            in_specs=[
                pl.BlockSpec((1, KV, G, D), q_map),
                pl.BlockSpec((1, 1, KV, 1, bk, D), kv_map),
                pl.BlockSpec((1, 1, KV, 1, bk, D), kv_map),
            ],
            out_specs=[
                pl.BlockSpec((1, KV, G, D), q_map),
                pl.BlockSpec((1, KV, G, 1), q_map),
            ],
            scratch_shapes=[
                pltpu.VMEM((KV, G, 1), jnp.float32),
                pltpu.VMEM((KV, G, 1), jnp.float32),
                pltpu.VMEM((KV, G, D), jnp.float32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((slots, KV, G, D), q.dtype),
            jax.ShapeDtypeStruct((slots, KV, G, 1), jnp.float32),
        ],
        interpret=interpret,
    )(pages, lengths, layer, qg, k_pages, v_pages)
    return out.reshape(slots, H, D), lse.reshape(slots, H)
