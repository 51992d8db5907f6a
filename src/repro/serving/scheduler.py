"""Continuous-batching request scheduler over a paged KV pool.

The static ``ServingEngine.generate`` pads one batch to one length and shares
one ``cache_len`` across every request — the ragged-``cache_len`` gap noted
since PR 4.  This module replaces that posture with real admission control
(the granularity Barrak & Ksontini show dominates serverless batch cost, and
the paper's §V-B buffering made per-request):

* a fixed-slot decode batch (``num_slots``): one jitted, donated decode step
  whose shapes never change, so admitting or retiring a request is a pure
  array update — **zero retraces** (gated by the retrace-counter test);
* KV in the :class:`KVBlockPool`, reached through block tables, so a
  request's pages are scattered physically but contiguous logically
  (defrag-free reuse).  Where the model offers a paged decode
  (``ModelApi.decode_paged``: the dense and vlm families) and the step is
  not sequence-sharded, attention reads the pages in place and the step
  writes only each slot's new token; every other step gathers each slot's
  cache and scatters the new token back;
* per-slot ``length`` — the paged decode takes one position per slot, and
  on the gather path the vmap over slots turns every family's scalar
  ``length`` into one length per request *without touching family decode
  signatures*, which is what closes the shared-``cache_len`` gap;
* requests admitted mid-decode as slots free up, retired the step their
  token budget completes; admission order is FIFO over (arrival, rid).

Bitwise contract: each request's tokens and final-step logits are bitwise
equal (fp32 cache math) to the same request served alone through the static
``generate`` oracle at equal cache capacity — the batched step is
bit-identical to solo B=1 decode on XLA, masked positions contribute
exactly +0.0 regardless of stale pool-page contents (see ``kv_pool.py``),
and the pages a paged read skips are exactly those masked positions.
``tests/test_continuous_batching.py`` holds this across backends × families
× arrival orders.

The sequence-sharded variant wraps the same per-slot body in ``shard_map``
over the paged leaves' S axis, reusing the PR 4 ``decode_partial`` +
``combine_split_kv`` machinery (``seq_shard_axes``) the sharded-decode suite
already gates.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.backends import KVCacheLayout
from repro.core.spans import span, spanned
from repro.serving.kv_pool import (
    KVBlockPool, RESERVED_BLOCKS, merge_cache, split_cache)

PyTree = Any


@dataclasses.dataclass
class Request:
    """One generation request in the stream."""

    rid: int
    prompt: np.ndarray                 # [S_prompt] int32
    max_new_tokens: int
    extra: Optional[Dict[str, np.ndarray]] = None  # vlm embeds / encdec frames
    arrival: int = 0                   # earliest scheduler step for admission


@dataclasses.dataclass
class RequestResult:
    """Per-request output, directly comparable to the static oracle:
    ``tokens`` matches ``GenerationResult.tokens[r]`` and ``final_logits``
    matches ``GenerationResult.prefill_logits[r]`` (the last decode step's
    logits, the field the static path reports)."""

    rid: int
    tokens: np.ndarray                 # [max_new_tokens] int32
    final_logits: np.ndarray           # [vocab] — last decode step's logits
    prompt_len: int
    admitted_step: int
    finished_step: int


@dataclasses.dataclass
class _Slot:
    request: Request
    table: np.ndarray
    n_blocks: int
    tokens: List[int]
    admitted_step: int
    prefix: int                        # cache positions the prefill wrote


class RequestScheduler:
    """Continuous batching over ``num_slots`` fixed decode slots.

    ``model`` is a :class:`repro.models.registry.ModelApi`; ``prefill_fn``
    is a jitted ``(params, batch, max_len_static) -> (logits, cache)`` (the
    engine shares its own).  ``slot_capacity`` is the static per-slot cache
    capacity — every admitted request prefills at this capacity so gathered
    shapes are constant; it must be a ``layout.block_k`` multiple.
    ``num_blocks=None`` sizes the pool for full occupancy (every slot
    holding a maximal request) plus the two reserved pages.

    ``mesh``/``axis_name`` switch the decode step to the sequence-sharded
    variant (shard_map over the gathered leaves' S axis), which keeps the
    gather: its shards split each slot's contiguous cache.
    """

    def __init__(self, model, params: PyTree, prefill_fn: Callable,
                 num_slots: int, slot_capacity: int,
                 layout: Optional[KVCacheLayout] = None,
                 num_blocks: Optional[int] = None,
                 mesh=None, axis_name: str = "seq"):
        self.model = model
        self.params = params
        self._prefill = prefill_fn
        self.num_slots = int(num_slots)
        self.layout = layout or KVCacheLayout()
        self.layout.check_capacity(slot_capacity)
        self.slot_capacity = int(slot_capacity)
        if num_blocks is None:
            num_blocks = (RESERVED_BLOCKS + self.num_slots
                          * self.layout.blocks_for(slot_capacity))
        self.mesh = mesh
        self.axis_name = axis_name

        if model.cache_seq_axes is None:
            raise ValueError(
                f"family {model.cfg.family!r} exposes no cache_seq_axes")

        # Build the pool + stacked slot state from one template prefill
        # (shapes only matter; a 1-token prompt is the cheapest trace).
        template = self._template_cache()
        self.seq_axes = model.cache_seq_axes(template)
        self.pool = KVBlockPool.build(template, self.seq_axes, self.layout,
                                      num_blocks)
        self._resident = jax.tree_util.tree_map(
            lambda ax, leaf: (None if ax is not None else
                              jnp.zeros((self.num_slots,) + leaf.shape,
                                        leaf.dtype)),
            self.seq_axes, template, is_leaf=lambda x: x is None)
        # [slots, 1, 1]: vmap strips the slot axis, leaving each family the
        # [B=1, 1] token shape its decode_step expects.
        self._tokens = jnp.zeros((self.num_slots, 1, 1), jnp.int32)
        self._tables = np.zeros((self.num_slots, self.pool.table_width),
                                np.int32)
        self._active = np.zeros((self.num_slots,), bool)
        # Device copies of the host-authoritative tables/active mask: only
        # admission/retirement changes them, so steady-state decode steps
        # reuse the same device buffers instead of re-uploading every step.
        self._tables_dev = jnp.asarray(self._tables)
        self._active_dev = jnp.asarray(self._active)
        self._slots: List[Optional[_Slot]] = [None] * self.num_slots
        self.paged = _takes_paged_step(model, mesh)
        self._step_fn = self._build_step()
        # Attention reads up to each slot's last valid page, or the whole
        # table where the backend gathers it.
        self._reads_valid_pages = self.paged and getattr(
            model.attn_backend, "skips_invalid_pages", False)
        self.steps_run = 0          # decode steps executed (bench: utilization)
        self.tokens_emitted = 0
        self.paged_steps = 0        # of them, served through block tables
        # Per decode step, summed: the cache positions the active slots
        # attend over, the num_slots x slot_capacity positions the slots'
        # tables span, and the positions in the pages attention fetched.
        # Each step's three also ride on its ``serve.step`` span
        # (``valid=``, ``capacity=``, ``read=``).
        self.decode_positions = 0
        self.capacity_positions = 0
        self.read_positions = 0

    # ------------------------------------------------------------------ #

    def _build_step(self):
        return build_step(self.model, self.pool, self.seq_axes,
                          mesh=self.mesh, axis_name=self.axis_name)

    def _template_cache(self) -> PyTree:
        batch = {"tokens": jnp.zeros((1, 1), jnp.int32)}
        batch.update(self._template_extra())
        _, cache = self._prefill(self.params, batch, self.slot_capacity)
        return cache

    def _template_extra(self) -> Dict[str, jnp.ndarray]:
        cfg = self.model.cfg
        if cfg.family == "vlm":
            return {"extra_embeds": jnp.zeros(
                (1, cfg.frontend_tokens, cfg.d_model), jnp.bfloat16)}
        if cfg.family == "encdec":
            return {"frames": jnp.zeros(
                (1, cfg.frontend_tokens, cfg.d_model), jnp.bfloat16)}
        return {}

    # ------------------------------------------------------------------ #
    # host-side admission / retirement

    def _admit(self, req: Request, step_idx: int) -> None:
        free = [i for i in range(self.num_slots) if not self._active[i]]
        slot = free[0]
        prompt = np.asarray(req.prompt, np.int32).reshape(1, -1)
        batch: Dict[str, Any] = {"tokens": jnp.asarray(prompt)}
        if req.extra:
            batch.update({k: jnp.asarray(v) for k, v in req.extra.items()})
        with span("serve.prefill", rid=req.rid):
            logits, cache = self._prefill(self.params, batch,
                                          self.slot_capacity)
        frontend = self.model.cfg.frontend_tokens or 0
        need = prompt.shape[1] + req.max_new_tokens + frontend
        n_blocks = (self.layout.blocks_for(need)
                    if self.pool.table_width else 0)
        paged, resident = split_cache(cache, self.seq_axes)
        with span("serve.pool_admit", rid=req.rid):
            table = self.pool.admit(paged, need)  # may raise PoolExhausted
        self._tables[slot] = table
        self._resident = jax.tree_util.tree_map(
            lambda ax, st, leaf: (st if ax is not None else
                                  st.at[slot].set(leaf.astype(st.dtype))),
            self.seq_axes, self._resident, cache,
            is_leaf=lambda x: x is None)
        first = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)  # [1,1]
        self._tokens = self._tokens.at[slot].set(first)
        self._active[slot] = True
        self._tables_dev = jnp.asarray(self._tables)
        self._active_dev = jnp.asarray(self._active)
        # The vlm's frontend embeddings lead its prompt in the cache.
        prefix = prompt.shape[1] + (
            frontend if self.model.cfg.family == "vlm" else 0)
        self._slots[slot] = _Slot(request=req, table=table,
                                  n_blocks=n_blocks, tokens=[],
                                  admitted_step=step_idx, prefix=prefix)

    def _can_admit(self, req: Request) -> bool:
        if not (~self._active).any():
            return False
        need = (len(np.asarray(req.prompt).reshape(-1)) + req.max_new_tokens
                + (self.model.cfg.frontend_tokens or 0))
        return self.layout.blocks_for(need) <= self.pool.allocator.free_blocks

    def _retire(self, slot: int, final_logits: np.ndarray,
                step_idx: int, results: List[RequestResult]) -> None:
        st = self._slots[slot]
        self.pool.retire(st.table, st.n_blocks)
        results.append(RequestResult(
            rid=st.request.rid,
            tokens=np.asarray(st.tokens, np.int32),
            final_logits=np.asarray(final_logits),
            prompt_len=int(np.asarray(st.request.prompt).reshape(-1).shape[0]),
            admitted_step=st.admitted_step,
            finished_step=step_idx,
        ))
        self._active[slot] = False
        self._active_dev = jnp.asarray(self._active)
        self._slots[slot] = None
        # Park the vacant slot at length 0 so its (discarded) decode work
        # stays in-bounds no matter how long it idles.
        self._resident = {**self._resident,
                          "length": self._resident["length"].at[slot].set(0)}

    def _read_positions(self, lengths: Sequence[int]) -> int:
        """Cache positions in the pages one step's attention fetches: each
        active slot's pages up to its last valid one and a vacant slot's
        first page, or every slot's whole table."""
        if not self._reads_valid_pages:
            return self.num_slots * self.slot_capacity
        bk = self.layout.block_k
        vacant = self.num_slots - len(lengths)
        return bk * (vacant + sum(self.layout.blocks_for(n)
                                  for n in lengths))

    # ------------------------------------------------------------------ #

    @spanned("serve.run")
    def run(self, requests: Sequence[Request],
            max_steps: Optional[int] = None) -> List[RequestResult]:
        """Serve the whole stream; returns results ordered by completion."""
        queue = sorted(requests, key=lambda r: (r.arrival, r.rid))
        for r in queue:
            need = (len(np.asarray(r.prompt).reshape(-1)) + r.max_new_tokens
                    + (self.model.cfg.frontend_tokens or 0))
            if need > self.slot_capacity:
                raise ValueError(
                    f"request {r.rid} needs capacity {need} > slot_capacity "
                    f"{self.slot_capacity}; raise max_request_len")
        results: List[RequestResult] = []
        step_idx = 0
        budget = max_steps if max_steps is not None else (
            sum(r.max_new_tokens for r in queue) + len(queue)
            + max((r.arrival for r in queue), default=0) + 8)
        while queue or self._active.any():
            if step_idx > budget:
                raise RuntimeError(
                    f"scheduler exceeded {budget} steps "
                    f"({len(results)}/{len(queue) + len(results)} done)")
            # FIFO admission of every arrived request that fits right now.
            while queue and queue[0].arrival <= step_idx \
                    and self._can_admit(queue[0]):
                req = queue.pop(0)
                with span("serve.admit", rid=req.rid):
                    self._admit(req, step_idx)
            if not self._active.any():
                step_idx += 1           # idle tick: waiting on a future arrival
                continue
            with span("serve.token_wait"):
                input_tokens = np.asarray(self._tokens)[:, 0, 0]
            lengths = [st.prefix + len(st.tokens) + 1
                       for st in self._slots if st is not None]
            valid = sum(lengths)
            capacity = self.num_slots * self.slot_capacity
            read = self._read_positions(lengths)
            self.decode_positions += valid
            self.capacity_positions += capacity
            self.read_positions += read
            with span("serve.step", step=self.steps_run, valid=valid,
                      capacity=capacity, read=read):
                logits, next_tok, self._resident, self.pool.buffers = \
                    self._step_fn(self.params, self._tokens, self._resident,
                                  self.pool.buffers, self._tables_dev,
                                  self._active_dev)
            self._tokens = next_tok
            self.steps_run += 1
            self.paged_steps += self.paged
            logits_np = None
            for slot in range(self.num_slots):
                st = self._slots[slot]
                if st is None:
                    continue
                st.tokens.append(int(input_tokens[slot]))
                self.tokens_emitted += 1
                if len(st.tokens) == st.request.max_new_tokens:
                    with span("serve.retire", rid=st.request.rid):
                        if logits_np is None:
                            logits_np = np.asarray(logits)
                        self._retire(slot, logits_np[slot], step_idx,
                                     results)
            step_idx += 1
        return results


def _takes_paged_step(model, mesh) -> bool:
    return model.decode_paged is not None and mesh is None


def build_step(model, pool: KVBlockPool, seq_axes: PyTree, mesh=None,
               axis_name: str = "seq") -> Callable:
    """The scheduler's jitted decode step over every slot, ``(params,
    tokens [slots, 1, 1], resident, buffers, tables, active) -> (logits
    [slots, V], next tokens [slots, 1, 1], resident, buffers)``, with the
    slot-resident state and the pool's buffers donated.

    Where ``model.decode_paged`` exists and ``mesh`` is None, attention
    reads the pool's pages through ``tables`` and the step writes only each
    slot's new token (:meth:`KVBlockPool.write_token`).  Otherwise it
    gathers every slot's cache, runs the family's ``decode_step`` vmapped
    over slots (in ``shard_map`` over the gathered S axis on a mesh), and
    scatters each slot's new token back.
    """

    def sample(logits):
        # logits [slots, 1, V].  The greedy argmax matches the static
        # path's per-request `argmax(logits[:, -1:], -1)` elementwise.
        with jax.named_scope("serve.sample"):
            return jnp.argmax(logits[:, None, -1:, :], axis=-1) \
                .astype(jnp.int32)                       # [slots, 1, 1]

    def paged_step(params, tokens, resident, buffers, tables, active):
        page, off = pool.token_index(tables, resident["length"], active)

        def write(leaf, layer, new):
            return pool.write_token(leaf, layer, page, off, new)

        with jax.named_scope("serve.decode"):
            logits, cache = model.decode_paged(
                params, tokens[:, 0], merge_cache(buffers, resident, seq_axes),
                tables, write)
        buffers, resident = split_cache(cache, seq_axes)
        return logits[:, -1], sample(logits), resident, buffers

    def chunks_at(paged: PyTree, positions: jnp.ndarray) -> PyTree:
        """Per-slot KV written this step: slice seq position p from each
        paged leaf ([slots, *rest, S, D] → [slots, *rest, D])."""
        def one(ax, leaf):
            if ax is None:
                return None
            def slot_slice(x, p):
                sl = jax.lax.dynamic_slice_in_dim(x, p, 1, axis=-2)
                return jnp.squeeze(sl, axis=-2)
            return jax.vmap(slot_slice)(leaf, positions)
        return jax.tree_util.tree_map(one, seq_axes, paged,
                                      is_leaf=lambda x: x is None)

    def gather_step(params, tokens, resident, buffers, tables, active):
        positions = resident["length"]                    # [slots]
        with jax.named_scope("serve.pool_gather"):
            paged = pool.gather(buffers, tables)

        def per_slot(tok, res, pg, **kw):
            cache = merge_cache(pg, res, seq_axes)
            logits, new_cache = model.decode_step(params, tok, cache, **kw)
            new_pg, new_res = split_cache(new_cache, seq_axes)
            return logits, new_res, new_pg

        if mesh is None:
            with jax.named_scope("serve.decode"):
                logits, new_res, new_paged = jax.vmap(per_slot)(
                    tokens, resident, paged)
        else:
            from jax.sharding import PartitionSpec as P

            def pspec(ax, leaf):
                if ax is None:
                    return P()
                nd = leaf.ndim                # [slots, *rest, S, D]
                return P(*([None] * (nd - 2)), axis_name, None)

            paged_specs = jax.tree_util.tree_map(
                pspec, seq_axes, paged, is_leaf=lambda x: x is None)
            res_specs = jax.tree_util.tree_map(lambda _: P(), resident)

            body = jax.shard_map(
                lambda p, t, r, g: jax.vmap(
                    lambda tok, res, pg: per_slot(
                        tok, res, pg, seq_shard_axes=axis_name))(t, r, g),
                mesh=mesh,
                in_specs=(P(), P(), res_specs, paged_specs),
                out_specs=(P(), res_specs, paged_specs),
                check_vma=False,
            )
            with jax.named_scope("serve.decode"):
                logits, new_res, new_paged = body(params, tokens,
                                                  resident, paged)

        with jax.named_scope("serve.pool_scatter"):
            chunks = chunks_at(new_paged, positions)
            buffers = pool.scatter_token(buffers, chunks,
                                         tables, positions, active)
        logits = logits[:, 0]                            # [slots, 1, V]
        return logits[:, -1], sample(logits), new_res, buffers

    # Donate the big rotating state: slot-resident stacks + pool pages.
    step = paged_step if _takes_paged_step(model, mesh) else gather_step
    return jax.jit(step,
                   donate_argnums=(2, 3))
