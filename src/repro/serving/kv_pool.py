"""Block-granular paged KV pool for the continuous-batching scheduler.

The PR 4 :class:`repro.core.backends.KVCacheLayout` already pads every cache
capacity to a ``block_k`` multiple at prefill, so the kernel-native
``[..., B, KV, S, D]`` buffers are born block-aligned — paging falls out of
the existing blocks.  This module turns that alignment into an allocator:

* :class:`BlockAllocator` — host-side free-list over ``num_blocks`` physical
  pages.  Requests allocate ``layout.blocks_for(prompt + max_new)`` pages at
  admission and free them at retirement; pages are reused defrag-free (a
  block table makes any scatter of physical pages look contiguous to the
  decode step).
* :class:`KVBlockPool` — the device side: one buffer per *growing* KV leaf
  of the family cache (``ModelApi.cache_seq_axes`` classifies leaves), laid
  out ``[*rest, num_blocks, block_k, D]`` where the per-slot leaf is
  ``[*rest, S, D]``: the page axis sits where the sequence axis was, so a
  ``(block_k, D)`` page of one layer and head is one tile-aligned block a
  kernel can DMA, and admission is a reshape.  The paged decode step reads
  pages through the block tables (``ModelApi.decode_paged``) and writes
  each slot's new token with :meth:`KVBlockPool.write_token`; the other
  families' step rebuilds contiguous per-slot caches with ``gather`` and
  writes the new token back with ``scatter_token``.

Two physical pages are reserved:

* block 0 — **null**: pads short block tables to the fixed table width.  It
  is never allocated and never written, so it stays zero; reads of it land
  at positions ≥ the request's ``length`` and are exactly masked out by the
  decode attention (score → -1e30 → probability exactly 0.0).
* block 1 — **sink**: inactive slots' per-step writes are redirected here so
  a retired slot can never corrupt a page that was freed and re-allocated to
  a live request.  Its content is garbage by design and never read by an
  active slot.

Bitwise note: the differential suite (``tests/test_continuous_batching.py``)
holds the scheduler to *bitwise* logit equality with the solo static oracle.
That is only possible because masked positions contribute exactly +0.0 to
the attention sum regardless of the stale values a reused page holds — the
mask is applied to scores before the softmax, so stale K produces a -1e30
score (probability exactly 0.0) and stale V is multiplied by that exact
zero.  Freed-page reuse therefore needs no zeroing.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.backends import KVCacheLayout

PyTree = Any

NULL_BLOCK = 0
SINK_BLOCK = 1
RESERVED_BLOCKS = 2

__all__ = ["BlockAllocator", "KVBlockPool", "PoolExhausted",
           "NULL_BLOCK", "SINK_BLOCK", "RESERVED_BLOCKS",
           "split_cache", "merge_cache"]


class PoolExhausted(RuntimeError):
    """Raised when an admission asks for more pages than are free."""


class BlockAllocator:
    """Host-side free-list over the pool's physical pages.

    Invariants (property-tested in ``tests/test_continuous_batching.py``):
    a page is never handed out twice while live, ``free`` rejects pages that
    are not live, and after every request retires the pool is back to fully
    free.  Reserved pages (null/sink) are never allocated.
    """

    def __init__(self, num_blocks: int, reserved: int = RESERVED_BLOCKS):
        if num_blocks <= reserved:
            raise ValueError(
                f"pool needs more than the {reserved} reserved blocks, "
                f"got num_blocks={num_blocks}")
        self.num_blocks = int(num_blocks)
        self.reserved = int(reserved)
        # LIFO free-list, seeded so pages are first handed out in ascending
        # id order (makes failures reproducible).
        self._free: List[int] = list(range(num_blocks - 1, reserved - 1, -1))
        self._live: set = set()

    @property
    def free_blocks(self) -> int:
        return len(self._free)

    @property
    def live_blocks(self) -> int:
        return len(self._live)

    def alloc(self, n: int) -> List[int]:
        if n <= 0:
            raise ValueError(f"alloc({n})")
        if n > len(self._free):
            raise PoolExhausted(
                f"need {n} pages, only {len(self._free)} free "
                f"(pool={self.num_blocks}, live={len(self._live)})")
        ids = [self._free.pop() for _ in range(n)]
        self._live.update(ids)
        return ids

    def free(self, ids: Sequence[int]) -> None:
        for b in ids:
            if b not in self._live:
                raise ValueError(
                    f"double free / free of unallocated block {b}")
            self._live.discard(b)
            self._free.append(b)


def _is_none(x) -> bool:
    return x is None


def split_cache(cache: PyTree, seq_axes: PyTree) -> Tuple[PyTree, PyTree]:
    """Split a family cache into (paged, slot_resident) by ``seq_axes``.

    Both halves keep the full tree structure; the complementary leaves are
    ``None`` (vmap-in_axes convention — traverse with ``is_leaf``)."""
    paged = jax.tree_util.tree_map(
        lambda ax, leaf: leaf if ax is not None else None,
        seq_axes, cache, is_leaf=_is_none)
    resident = jax.tree_util.tree_map(
        lambda ax, leaf: None if ax is not None else leaf,
        seq_axes, cache, is_leaf=_is_none)
    return paged, resident


def merge_cache(paged: PyTree, resident: PyTree, seq_axes: PyTree) -> PyTree:
    """Inverse of :func:`split_cache`."""
    return jax.tree_util.tree_map(
        lambda ax, p, r: p if ax is not None else r,
        seq_axes, paged, resident, is_leaf=_is_none)


@dataclasses.dataclass
class KVBlockPool:
    """Device-side paged storage for the growing KV leaves of one family.

    ``buffers`` mirrors the cache tree structure with ``None`` at
    slot-resident leaves; each paged leaf is ``[*rest, num_blocks, block_k,
    D]`` for a per-slot leaf of shape ``[*rest, S, D]`` (seq axis -2).
    ``table_width`` fixes the block-table width (`S_slot = table_width *
    block_k` is the static capacity every gathered per-slot cache has), so
    admission/retirement never changes a traced shape.
    """

    layout: KVCacheLayout
    num_blocks: int
    table_width: int
    seq_axes: PyTree
    buffers: PyTree
    allocator: BlockAllocator

    @classmethod
    def build(cls, slot_cache_template: PyTree, seq_axes: PyTree,
              layout: KVCacheLayout, num_blocks: int) -> "KVBlockPool":
        """Allocate pool buffers for one slot's cache template (a B=1 cache
        pytree or ShapeDtypeStructs) whose paged leaves have the pool's slot
        capacity ``S_slot`` at axis -2."""
        bk = max(1, int(layout.block_k))
        widths = set()

        def mk(ax, leaf):
            if ax is None:
                return None
            s = leaf.shape[-2]
            layout.check_capacity(s)
            widths.add(s // bk)
            return jnp.zeros(leaf.shape[:-2] + (num_blocks, bk)
                             + leaf.shape[-1:], leaf.dtype)

        buffers = jax.tree_util.tree_map(mk, seq_axes, slot_cache_template,
                                         is_leaf=_is_none)
        if len(widths) > 1:
            raise ValueError(
                f"paged leaves disagree on capacity: {sorted(widths)} blocks")
        # Attention-free families (ssm) have no growing KV: a zero-width
        # pool whose admit/retire/gather/scatter degrade to no-ops.
        width = widths.pop() if widths else 0
        return cls(layout=layout, num_blocks=num_blocks,
                   table_width=width, seq_axes=seq_axes,
                   buffers=buffers,
                   allocator=BlockAllocator(num_blocks))

    # -- host-side admission/retirement -----------------------------------

    def admit(self, cache: PyTree, max_len: int) -> np.ndarray:
        """Allocate pages for a request needing capacity ``max_len`` and copy
        its prefilled KV into them.  Returns the request's block table
        (int32 ``[table_width]``, padded with the null block)."""
        if self.table_width == 0:
            return np.zeros((0,), np.int32)
        n = self.layout.blocks_for(max_len)
        if n > self.table_width:
            raise ValueError(
                f"request needs {n} pages but tables hold {self.table_width}")
        ids = self.allocator.alloc(n)
        self.buffers = _write_pages(self.buffers, cache,
                                    jnp.asarray(ids, jnp.int32))
        table = np.full((self.table_width,), NULL_BLOCK, np.int32)
        table[:n] = ids
        return table

    def retire(self, table: np.ndarray, n_blocks: int) -> None:
        """Free a retired request's pages (the first ``n_blocks`` table
        entries; the rest are null padding)."""
        self.allocator.free([int(b) for b in table[:n_blocks]])

    # -- jit-side reads and writes ----------------------------------------

    def gather(self, buffers: PyTree, tables: jnp.ndarray) -> PyTree:
        """Rebuild contiguous per-slot caches from block tables.

        ``tables``: int32 ``[slots, table_width]``.  Returns the paged half
        of the cache tree with a leading slot axis: ``[slots, *rest, S_slot,
        D]`` per leaf.  Pure gather — safe inside jit/vmap tracing.
        """

        def g(ax, buf):
            if ax is None:
                return None
            x = jnp.take(buf, tables, axis=-3)   # [*rest, slots, W, bk, D]
            x = x.reshape(x.shape[:-4] + (x.shape[-4], -1, x.shape[-1]))
            return jnp.moveaxis(x, -3, 0)        # [slots, *rest, S, D]

        return jax.tree_util.tree_map(g, self.seq_axes, buffers,
                                      is_leaf=_is_none)

    def token_index(self, tables: jnp.ndarray, positions: jnp.ndarray,
                    active: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """The physical page and the offset in it where each slot's token
        at ``positions[slot]`` goes.  Inactive slots are redirected to the
        sink page so they can never touch a re-allocated one.  Two active
        slots never collide (they own disjoint pages); sink collisions are
        harmless because the sink is never read."""
        bk = max(1, int(self.layout.block_k))
        # Clip so a long-vacant slot's (discarded) position can't index past
        # the table; active positions are < capacity by allocation.
        block_ix = jnp.clip(positions // bk, 0, tables.shape[1] - 1)
        page = tables[jnp.arange(tables.shape[0]), block_ix]
        return jnp.where(active, page, SINK_BLOCK), positions % bk

    @staticmethod
    def write_token(leaf: jnp.ndarray, layer, page: jnp.ndarray,
                    off: jnp.ndarray, new: jnp.ndarray) -> jnp.ndarray:
        """Write each slot's new row ``new [slots, *rest', D]`` into layer
        ``layer`` of a pool leaf ``[L, *rest', num_blocks, block_k, D]`` at
        ``(page, off)`` from :meth:`token_index`: the paged decode step's
        one write per layer, in place on the donated pool."""
        with jax.named_scope("serve.pool_scatter"):
            return _put_tokens(leaf, page, off, new, lead=(layer,))

    def scatter_token(self, buffers: PyTree, chunks: PyTree,
                      tables: jnp.ndarray, positions: jnp.ndarray,
                      active: jnp.ndarray) -> PyTree:
        """Write each slot's newly decoded KV chunk to its physical page
        (:meth:`token_index`).

        ``chunks``: paged tree with per-slot leaves ``[slots, *rest, D]``
        (the gathered decode step's write at ``positions[slot]``, already
        extracted from the gathered cache).
        """
        if self.table_width == 0:
            return buffers
        page, off = self.token_index(tables, positions, active)

        def s(ax, buf, chunk):
            if ax is None:
                return buf
            return _put_tokens(buf, page, off, chunk)

        return jax.tree_util.tree_map(s, self.seq_axes, buffers, chunks,
                                      is_leaf=_is_none)


def _put_tokens(buf: jnp.ndarray, page: jnp.ndarray, off: jnp.ndarray,
                rows: jnp.ndarray, lead: Tuple = ()) -> jnp.ndarray:
    """Write ``rows[slot]`` (``[*rest, D]``) at ``(page[slot], off[slot])``
    of a pool leaf ``[*lead dims, *rest, num_blocks, block_k, D]``, below
    the indices ``lead``.  One ``dynamic_update_slice`` a slot: it updates
    the buffer in place in whatever layout the buffer has, where a scatter
    over the page and offset axes has the compiler relayout the whole
    leaf around it."""
    rest = buf.shape[len(lead):-3]
    for slot in range(rows.shape[0]):
        row = rows[slot].reshape((1,) * len(lead) + rest + (1, 1, -1))
        start = tuple(lead) + (0,) * len(rest) + (page[slot], off[slot], 0)
        buf = jax.lax.dynamic_update_slice(buf, row.astype(buf.dtype), start)
    return buf


@functools.partial(jax.jit, donate_argnums=(0,))
def _write_pages(buffers: PyTree, cache: PyTree, ids: jnp.ndarray) -> PyTree:
    """Copy the first ``len(ids)`` pages' worth of each paged leaf of a
    prefilled per-slot cache (``[*rest, S, D]``) into pages ``ids`` of the
    pool, in place on the donated buffers."""
    n = ids.shape[0]

    def write(buf, leaf):
        if buf is None:
            return None
        bk = buf.shape[-2]
        x = leaf[..., : n * bk, :]
        x = x.reshape(x.shape[:-2] + (n, bk, x.shape[-1]))
        return buf.at[..., ids, :, :].set(x.astype(buf.dtype))

    return jax.tree_util.tree_map(write, buffers, cache, is_leaf=_is_none)
