"""Pluggable backends for the two serving hot paths: worker SpMM and decode
attention.

**Compute backends** (:class:`ComputeBackend`) execute the FSI per-layer
SpMM.  Every simulated Lambda runs the same inner loop per layer: a sparse
matrix–panel product ``z = W_local @ x_buf`` followed by the GraphChallenge
epilogue ``y = clip(relu(z + bias), 0, 32)``.  The *billed* cost of that work
is fixed by :class:`repro.faas.worker.ComputeModel` (FLOPs → Lambda-seconds),
but the *host* wall-clock of the simulator is whatever backend actually runs
the numbers.  This module makes that choice pluggable:

* ``numpy-csr``  — the seed's ``np.add.at`` scatter-add CSR SpMM, kept
  verbatim as the bit-exact oracle.
* ``numpy-fast`` — segment formulation (uniform-row batched matmul with a
  ``np.add.reduceat`` ragged fallback); same math, 5-30x faster on
  GraphChallenge shapes.
* ``pallas-bsr`` — the MXU-tiled Pallas kernel in ``kernels/bsr_spmm``:
  offline ``bsr_from_csr(pad=True)`` + ``padded()`` artifact prep per
  worker-layer, jit-cached fused bias+ReLU+clip dispatch, and a fleet mode
  that stacks every worker's panel so ONE vmapped device dispatch serves the
  whole simulated fleet per layer.
* ``pallas-bsr-sharded`` — the same fleet panel laid out over a real device
  mesh: the stacked worker axis is sharded over a 1-D ``worker`` mesh axis
  (``launch.mesh.make_worker_mesh``) and each layer dispatches through
  ``jax.shard_map``, so simulated workers map 1:1
  (or blocked P/D) onto devices — the paper's "one worker ≈ one isolated
  compute unit" execution model.  The default ``dispatch="fused"`` runs ONE
  fleet-megakernel ``pallas_call`` per device (worker index folded into the
  grid, per-panel block counts bounding the K loop);
  ``dispatch="vmap"`` keeps the PR 3 vmap-within-shard body as the parity
  baseline.  P not divisible by the device count is padded with zero
  workers.

Backends only change how the arithmetic is executed — FLOP charging, message
accounting and memory high-water marks are computed by the caller from the
CSR shard itself, so billed cost is identical across backends by
construction (asserted in ``tests/test_backends.py``).

**Attention backends** (:class:`AttentionBackend`) execute the serving
engine's per-step decode attention — the second hot path under the paper's
batch-serving posture (§V-B).  Every decoding model family dispatches its
single-token attention through one of:

* ``dense-ref``     — ``models.attention.decode_attention_dense``, the
  no-chunking oracle (sequence-shardable under pjit);
* ``chunked-lse``   — the streaming ``models.attention.decode_attention``
  scan (bounded memory for very long caches);
* ``pallas-splitk`` — the split-KV Pallas kernel ``kernels/decode_attention``
  via the jit-cached ``decode_mha`` wrapper, with the cache padded to a
  ``block_k`` multiple picked from an autotune table.

All three take ``(q [B,1,H,D], k_cache [B,KV,S,D], v_cache [B,KV,S,D],
cache_len)`` — the **kernel-native** cache layout, with the capacity ``S``
padded at prefill per the backend's :class:`KVCacheLayout` — and return
``[B,1,H,D]`` in ``q.dtype``.  Because the cache is already in the kernel's
layout, ``pallas-splitk`` dispatches with zero per-step re-layout (no
``moveaxis``/``pad`` — asserted on the jaxpr in
``tests/test_sharded_decode.py``), and the other backends read the same
buffers through views.  Each backend also exposes ``decode_partial`` — the
``(out, lse)`` split-KV form — which the families' sequence-sharded decode
branch combines across shards via ``models.attention.combine_split_kv``.
Logits parity across backends and model families is asserted in
``tests/test_attention_backends.py`` and (sharded) ``tests/test_sharded_decode.py``.

Both registries resolve through one entry point: ``get_backend(kind, name)``
with ``kind in {"compute", "attention"}``; the legacy one-argument form
``get_backend(name)`` keeps meaning a compute backend.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Protocol, Sequence, Tuple, Union

import numpy as np

from repro.core.sparse import CSRMatrix, bsr_from_csr
from repro.data.graphchallenge import ACTIVATION_CLIP, relu_bias_threshold

__all__ = [
    "ComputeBackend",
    "NumpyCsrBackend",
    "NumpyFastBackend",
    "PallasBsrBackend",
    "PallasBsrShardedBackend",
    "AttentionBackend",
    "KVCacheLayout",
    "cache_layout_for",
    "DenseRefAttention",
    "ChunkedLseAttention",
    "PallasSplitKAttention",
    "BACKEND_NAMES",
    "ATTENTION_BACKEND_NAMES",
    "get_backend",
]


class ComputeBackend(Protocol):
    """One worker-layer SpMM + fused epilogue, with optional fleet batching."""

    name: str

    def prepare(self, W: CSRMatrix) -> Any:
        """Offline per-worker-layer artifact prep (unbilled, like the paper's
        a-priori partitioning/map construction)."""
        ...

    def apply(self, state: Any, x: np.ndarray, bias: float) -> np.ndarray:
        """``clip(relu(W @ x + bias), 0, 32)`` for one worker."""
        ...

    def fleet_prepare_all(
        self, layer_states: Sequence[Sequence[Any]]
    ) -> Optional[List[Any]]:
        """Optional: stack per-layer states [layer][worker] into one batched
        panel per layer.  ``None`` means no fleet mode (per-worker apply)."""
        ...

    def fleet_apply(
        self, fleet_state: Any, xs: Sequence[np.ndarray], bias: float
    ) -> List[np.ndarray]:
        """One dispatch for the whole fleet's layer-k panels."""
        ...


class _NumpyBackend:
    @property
    def state_key(self) -> str:
        return self.name

    def prepare(self, W: CSRMatrix) -> CSRMatrix:
        return W

    def fleet_prepare_all(self, layer_states):
        return None

    def fleet_apply(self, fleet_state, xs, bias):  # pragma: no cover
        raise NotImplementedError(f"{self.name} has no fleet mode")


class NumpyCsrBackend(_NumpyBackend):
    """Seed behavior: scatter-add CSR SpMM (the parity oracle)."""

    name = "numpy-csr"

    def apply(self, state: CSRMatrix, x: np.ndarray, bias: float) -> np.ndarray:
        return relu_bias_threshold(state.matmul_dense_scatter(x), bias)


class NumpyFastBackend(_NumpyBackend):
    """Segment-reduce CSR SpMM — no ``np.add.at``."""

    name = "numpy-fast"

    def apply(self, state: CSRMatrix, x: np.ndarray, bias: float) -> np.ndarray:
        return relu_bias_threshold(state.matmul_dense_fast(x), bias)


@dataclasses.dataclass
class _PallasLayerState:
    """Offline-prepared padded-BSR operands for one worker-layer shard."""

    blocks: np.ndarray      # f32[NBR, K, bm, bn]
    cols: np.ndarray        # i32[NBR, K]
    counts: np.ndarray      # i32[NBR] true blocks per row (BSR indptr diff)
    m: int                  # true output rows (unpadded)
    n: int                  # true input rows (unpadded)
    n_pad: int              # padded input height = NBC * bn


@dataclasses.dataclass
class _PallasFleetState:
    """One layer's fleet panel: every worker's operands padded to common
    [P, NBRmax, Kmax, bm, bn] so a single batched dispatch covers the fleet
    (``counts`` carries each panel row's true block depth so the fused
    megakernel's K loop skips the fleet-global padding)."""

    blocks: Any             # device f32[P, NBR, K, bm, bn]
    cols: Any               # device i32[P, NBR, K]
    counts: Any             # device i32[P, NBR]
    m: List[int]
    n: List[int]
    n_pad: int


class PallasBsrBackend:
    """MXU-tiled BSR SpMM via ``kernels/bsr_spmm`` (fused bias+ReLU+clip).

    ``interpret=None`` (the default) resolves from the platform: compiled
    MXU dispatch on TPU, the Pallas interpreter on every other host.
    """

    name = "pallas-bsr"

    def __init__(
        self,
        block_shape: Tuple[int, int] = (32, 32),
        batch_block: int = 128,
        interpret: Optional[bool] = None,
        clip: float = ACTIVATION_CLIP,
    ):
        from repro.kernels import resolve_interpret

        self.block_shape = block_shape
        self.batch_block = batch_block
        self.interpret = resolve_interpret(interpret)
        self.clip = clip

    @property
    def state_key(self) -> str:
        bm, bn = self.block_shape
        return f"{self.name}:{bm}x{bn}:bb{self.batch_block}:i{self.interpret}:c{self.clip}"

    # -- shape helpers -------------------------------------------------------

    def _bb(self, batch: int) -> int:
        """Largest legal batch panel: the kernel requires bb | batch."""
        return self.batch_block if batch % self.batch_block == 0 else batch

    # -- per-worker path -----------------------------------------------------

    def prepare(self, W: CSRMatrix) -> _PallasLayerState:
        bsr = bsr_from_csr(W, self.block_shape, pad=True)
        blocks, cols, counts = bsr.padded()
        return _PallasLayerState(
            blocks=blocks.astype(np.float32),
            cols=cols,
            counts=counts.astype(np.int32),
            m=W.nrows,
            n=W.ncols,
            n_pad=bsr.shape[1],
        )

    def apply(self, state: _PallasLayerState, x: np.ndarray, bias: float) -> np.ndarray:
        import jax.numpy as jnp

        from repro.kernels.bsr_spmm.ops import bsr_spmm

        batch = x.shape[1]
        if state.m == 0 or batch == 0:
            return np.zeros((state.m, batch), dtype=np.float32)
        xp = np.zeros((state.n_pad, batch), dtype=np.float32)
        xp[: state.n] = x
        y = bsr_spmm(
            jnp.asarray(state.blocks),
            jnp.asarray(state.cols),
            jnp.asarray(xp),
            bias=float(bias),
            clip=self.clip,
            batch_block=self._bb(batch),
            interpret=self.interpret,
        )
        return np.asarray(y)[: state.m]

    # -- fleet path ----------------------------------------------------------

    def _fleet_maxima(self, layer_states):
        """(nbr_max, k_max, n_pad_max) over every worker-layer state, or
        ``None`` when the fleet is empty — padding everything to these maxima
        lets one jit-compiled shape serve every layer."""
        all_states = [s for layer in layer_states for s in layer]
        if not all_states:
            return None
        bn = self.block_shape[1]
        return (
            max(1, max(s.blocks.shape[0] for s in all_states)),
            max(1, max(s.blocks.shape[1] for s in all_states)),
            max(bn, max(s.n_pad for s in all_states)),
        )

    def _stack_layer(self, states, p_rows: int, nbr_max: int, k_max: int):
        """Stack one layer's per-worker operands into [p_rows, ...] host
        panels (rows beyond ``len(states)`` stay zero — inert pad workers,
        whose ``counts`` of 0 also keep the fused megakernel's K loop off
        them entirely)."""
        bm, bn = self.block_shape
        blocks = np.zeros((p_rows, nbr_max, k_max, bm, bn), dtype=np.float32)
        cols = np.zeros((p_rows, nbr_max, k_max), dtype=np.int32)
        counts = np.zeros((p_rows, nbr_max), dtype=np.int32)
        for i, s in enumerate(states):
            nbr, k = s.blocks.shape[:2]
            blocks[i, :nbr, :k] = s.blocks
            cols[i, :nbr, :k] = s.cols
            counts[i, :nbr] = s.counts
        return blocks, cols, counts

    def fleet_prepare_all(
        self, layer_states: Sequence[Sequence[_PallasLayerState]]
    ) -> List[_PallasFleetState]:
        """Pad every worker-layer operand to the fleet-and-depth-global maxima
        so each layer's dispatch shares one jit-compiled shape."""
        import jax.numpy as jnp

        maxima = self._fleet_maxima(layer_states)
        if maxima is None:
            return []
        nbr_max, k_max, n_pad_max = maxima
        out: List[_PallasFleetState] = []
        for states in layer_states:
            blocks, cols, counts = self._stack_layer(
                states, len(states), nbr_max, k_max)
            out.append(
                _PallasFleetState(
                    blocks=jnp.asarray(blocks),
                    cols=jnp.asarray(cols),
                    counts=jnp.asarray(counts),
                    m=[s.m for s in states],
                    n=[s.n for s in states],
                    n_pad=n_pad_max,
                )
            )
        return out

    def fleet_apply(
        self, fleet_state: _PallasFleetState, xs: Sequence[np.ndarray], bias: float
    ) -> List[np.ndarray]:
        import jax.numpy as jnp

        from repro.kernels.bsr_spmm.ops import bsr_spmm_fleet

        P = len(xs)
        batch = xs[0].shape[1]
        X = np.zeros((P, fleet_state.n_pad, batch), dtype=np.float32)
        for i, x in enumerate(xs):
            X[i, : x.shape[0]] = x
        y = np.asarray(
            bsr_spmm_fleet(
                fleet_state.blocks,
                fleet_state.cols,
                jnp.asarray(X),
                bias=float(bias),
                clip=self.clip,
                batch_block=self._bb(batch),
                interpret=self.interpret,
            )
        )
        return [y[i, : fleet_state.m[i]] for i in range(P)]


@dataclasses.dataclass
class _PallasShardedFleetState(_PallasFleetState):
    """Fleet panel whose worker axis is padded to a device-count multiple and
    laid out over the ``worker`` mesh axis (blocks/cols live device-resident
    under a NamedSharding from prepare time on)."""

    p_pad: int = 0          # padded worker count (multiple of mesh axis size)


class PallasBsrShardedBackend(PallasBsrBackend):
    """``pallas-bsr`` fleet mode over a real device mesh via ``shard_map``.

    The per-worker-layer artifacts are identical to :class:`PallasBsrBackend`
    (inherited ``prepare``/``apply``); only the fleet dispatch differs: the
    stacked [P, ...] panel is sharded over a 1-D ``worker`` mesh axis and
    every device runs the Pallas BSR body for its block of P/D workers —
    simulated Lambdas map onto devices the way the paper (and FMI-style
    serverless collectives) assume one worker maps onto one isolated compute
    unit.  When P is not divisible by the device count the panel is padded
    with all-zero workers whose outputs are never read.

    ``dispatch`` picks the per-device execution:

    * ``"fused"`` (default) — the fleet megakernel: ONE ``pallas_call`` per
      device whose grid walks that device's P/D worker panels (worker index
      folded into the grid, per-panel block counts bounding the K loop) —
      no vmap, no XLA re-entry between workers.
    * ``"vmap"`` — the PR 3 dispatch (``jax.vmap`` of the single-worker
      Pallas body inside each shard), kept as the parity baseline and the
      fallback when a kernel-level issue needs bisecting.

    Both dispatches are bitwise-identical on the produced panels (the fused
    K loop only skips all-zero padding terms; asserted in
    ``tests/test_sharded_fleet.py``).

    ``mesh`` defaults to every visible device
    (:func:`repro.launch.mesh.make_worker_mesh`); pass an explicit mesh — or
    use ``run_fsi(..., mesh=...)`` — to pin the layout.  On CPU-only hosts
    multi-device meshes come from
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N``.
    """

    name = "pallas-bsr-sharded"

    def __init__(
        self,
        block_shape: Tuple[int, int] = (32, 32),
        batch_block: int = 128,
        interpret: Optional[bool] = None,
        clip: float = ACTIVATION_CLIP,
        mesh: Any = None,
        axis_name: str = "worker",
        dispatch: str = "fused",
    ):
        super().__init__(block_shape=block_shape, batch_block=batch_block,
                         interpret=interpret, clip=clip)
        if dispatch not in ("fused", "vmap"):
            raise ValueError(
                f"dispatch must be 'fused' or 'vmap', got {dispatch!r}")
        self._mesh = mesh
        self.axis_name = axis_name
        self.dispatch = dispatch

    @property
    def mesh(self):
        if self._mesh is None:
            from repro.launch.mesh import make_worker_mesh

            self._mesh = make_worker_mesh(axis_name=self.axis_name)
        return self._mesh

    def with_mesh(self, mesh) -> "PallasBsrShardedBackend":
        """A copy of this backend pinned to ``mesh`` (the hook ``run_fsi``
        uses to thread an explicit mesh through backend selection)."""
        return PallasBsrShardedBackend(
            block_shape=self.block_shape, batch_block=self.batch_block,
            interpret=self.interpret, clip=self.clip, mesh=mesh,
            axis_name=self.axis_name, dispatch=self.dispatch,
        )

    @property
    def n_devices(self) -> int:
        return int(self.mesh.shape[self.axis_name])

    @property
    def state_key(self) -> str:
        return (f"{super().state_key}:d{self.n_devices}:{self.axis_name}"
                f":{self.dispatch}")

    def _sharding(self):
        from jax.sharding import NamedSharding, PartitionSpec

        return NamedSharding(self.mesh, PartitionSpec(self.axis_name))

    def fleet_prepare_all(
        self, layer_states: Sequence[Sequence[_PallasLayerState]]
    ) -> List[_PallasShardedFleetState]:
        """Stack + pad the worker axis to a device-count multiple and place
        the panels over the mesh at prepare time (offline, unbilled) so no
        layer dispatch pays a host→device reshard for the weights."""
        import jax

        maxima = self._fleet_maxima(layer_states)
        if maxima is None:
            return []
        nbr_max, k_max, n_pad_max = maxima
        D = self.n_devices
        sharding = self._sharding()
        out: List[_PallasShardedFleetState] = []
        for states in layer_states:
            P = len(states)
            p_pad = -(-P // D) * D
            blocks, cols, counts = self._stack_layer(
                states, p_pad, nbr_max, k_max)
            out.append(
                _PallasShardedFleetState(
                    blocks=jax.device_put(blocks, sharding),
                    cols=jax.device_put(cols, sharding),
                    counts=jax.device_put(counts, sharding),
                    m=[s.m for s in states],
                    n=[s.n for s in states],
                    n_pad=n_pad_max,
                    p_pad=p_pad,
                )
            )
        return out

    def fleet_apply(
        self, fleet_state: _PallasShardedFleetState, xs: Sequence[np.ndarray],
        bias: float,
    ) -> List[np.ndarray]:
        import jax

        from repro.kernels.bsr_spmm.ops import (
            bsr_spmm_fleet_fused_sharded,
            bsr_spmm_fleet_sharded,
        )

        P = len(xs)
        batch = xs[0].shape[1]
        X = np.zeros((fleet_state.p_pad, fleet_state.n_pad, batch),
                     dtype=np.float32)
        for i, x in enumerate(xs):
            X[i, : x.shape[0]] = x
        Xd = jax.device_put(X, self._sharding())
        if self.dispatch == "fused":
            y = bsr_spmm_fleet_fused_sharded(
                fleet_state.blocks, fleet_state.cols, fleet_state.counts, Xd,
                mesh=self.mesh, axis_name=self.axis_name, bias=float(bias),
                clip=self.clip, batch_block=self._bb(batch),
                interpret=self.interpret,
            )
        else:
            y = bsr_spmm_fleet_sharded(
                fleet_state.blocks, fleet_state.cols, Xd,
                mesh=self.mesh, axis_name=self.axis_name, bias=float(bias),
                clip=self.clip, batch_block=self._bb(batch),
                interpret=self.interpret,
            )
        y = np.asarray(y)
        return [y[i, : fleet_state.m[i]] for i in range(P)]


# ---------------------------------------------------------------------------
# decode-attention backends (serving per-step hot path)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class KVCacheLayout:
    """Canonical decode KV-cache layout descriptor.

    Every decoding family allocates its cache **kernel-native** —
    ``[..., B, KV, S, D]`` with the sequence capacity ``S`` padded up to a
    ``block_k`` multiple at prefill — so the per-step decode dispatch never
    re-lays the cache out (the old ``moveaxis``+``pad`` in the splitk path).
    ``block_k`` is the padding quantum: 1 for the view-based backends
    (dense-ref / chunked-lse accept any capacity), the kernel's KV block
    size for ``pallas-splitk``.  The descriptor is resolved once per serving
    configuration (``AttentionBackend.cache_layout(max_len)`` /
    ``router.route_decode_plan``) and threaded ``ServingEngine`` →
    ``get_model`` → family ``prefill``/``decode_step``.
    """

    block_k: int = 1

    def padded_len(self, max_len: int) -> int:
        """Cache capacity for a requested ``max_len``: the next ``block_k``
        multiple (identity when ``block_k == 1``)."""
        bk = max(1, int(self.block_k))
        return -(-max(int(max_len), 1) // bk) * bk

    def check_capacity(self, seq_cap: int) -> None:
        if seq_cap % max(1, int(self.block_k)):
            raise ValueError(
                f"KV cache capacity {seq_cap} is not a multiple of "
                f"block_k={self.block_k}; pad the cache at prefill with "
                f"KVCacheLayout.padded_len (ServingEngine does this)")

    def blocks_for(self, max_len: int) -> int:
        """Number of ``block_k``-sized pages a sequence of up to ``max_len``
        tokens occupies — the allocation unit of the paged KV pool
        (``serving/kv_pool.py``): a request holds ``blocks_for(prompt +
        max_new)`` pages for its lifetime and frees them at retirement."""
        return self.padded_len(max_len) // max(1, int(self.block_k))


def cache_layout_for(backend, max_len: int) -> KVCacheLayout:
    """The :class:`KVCacheLayout` a backend instance wants for a cache of
    capacity ``max_len`` (identity layout for duck-typed externals)."""
    fn = getattr(backend, "cache_layout", None)
    return fn(max_len) if fn is not None else KVCacheLayout()


class AttentionBackend(Protocol):
    """Single-token decode attention over a preallocated KV cache.

    Implementations must be pure jax-traceable callables so the serving
    engine can close over one instance inside its jitted ``decode_step``:
    the backend choice is static, ``cache_len`` is traced.  Caches arrive in
    the canonical :class:`KVCacheLayout` — ``[B, KV, S, D]`` with ``S``
    already padded per ``cache_layout(max_len)``.
    """

    name: str

    def cache_layout(self, max_len: int) -> KVCacheLayout:
        """Layout (padding rule) this backend needs for capacity ``max_len``."""
        ...

    def decode(
        self,
        q: Any,          # [B, 1, H, D] — one new token's query heads
        k_cache: Any,    # [B, KV, S, D] cache padded to capacity S
        v_cache: Any,    # [B, KV, S, D]
        cache_len: Any,  # valid prefix length (traced scalar or int)
    ) -> Any:
        """Returns attention output [B, 1, H, D] in ``q.dtype``."""
        ...

    def decode_partial(
        self, q: Any, k_cache: Any, v_cache: Any, cache_len: Any
    ) -> Any:
        """Split-KV form over a (possibly shard-local) cache slice: returns
        ``(out [B,1,H,D] fp32 normalized partial, lse [B,1,H] fp32)`` for the
        cross-shard ``combine_split_kv`` merge."""
        ...


class DenseRefAttention:
    """``decode_attention_dense`` — the parity oracle for the registry.

    No chunking: the scores einsum contracts the full (masked) cache, which
    is also the sequence-shardable formulation under pjit (split-KV chosen by
    the compiler).
    """

    name = "dense-ref"

    @property
    def state_key(self) -> str:
        return self.name

    def cache_layout(self, max_len: int) -> KVCacheLayout:
        return KVCacheLayout(block_k=1)

    def decode(self, q, k_cache, v_cache, cache_len):
        from repro.models.attention import decode_attention_dense

        return decode_attention_dense(q, k_cache, v_cache, cache_len)

    def decode_partial(self, q, k_cache, v_cache, cache_len):
        from repro.models.attention import decode_attention_dense

        return decode_attention_dense(q, k_cache, v_cache, cache_len,
                                      return_lse=True)


class ChunkedLseAttention:
    """Streaming KV-chunk scan with running (max, sum, acc) — bounded memory
    for very long caches; chunk size is a numerics-invariant tile knob
    (property-tested in ``tests/test_attention_backends.py``)."""

    name = "chunked-lse"

    def __init__(self, kv_chunk: int = 2048):
        self.kv_chunk = kv_chunk

    @property
    def state_key(self) -> str:
        return f"{self.name}:kc{self.kv_chunk}"

    def cache_layout(self, max_len: int) -> KVCacheLayout:
        return KVCacheLayout(block_k=1)

    def decode(self, q, k_cache, v_cache, cache_len):
        from repro.models.attention import decode_attention

        return decode_attention(
            q, k_cache, v_cache, cache_len=cache_len, kv_chunk=self.kv_chunk
        ).astype(q.dtype)

    def decode_partial(self, q, k_cache, v_cache, cache_len):
        from repro.models.attention import decode_attention

        return decode_attention(
            q, k_cache, v_cache, cache_len=cache_len, kv_chunk=self.kv_chunk,
            return_lse=True,
        )


# (padded cache length upper bound, block_k) — smallest block that keeps the
# kv sweep ≥ a few blocks deep without padding tiny caches to 512.
SPLITK_BLOCK_K_TABLE: Tuple[Tuple[Optional[int], int], ...] = (
    (256, 64),
    (1024, 128),
    (4096, 256),
    (None, 512),
)


class PallasSplitKAttention:
    """Split-KV flash-decode Pallas kernel via the jit-cached ``decode_mha``.

    The cache arrives **already kernel-native**: ``[B, KV, S, D]`` with ``S``
    a ``block_k`` multiple (the layout :meth:`cache_layout` asks prefill to
    allocate), so the dispatch is a straight ``decode_mha`` call — the old
    per-step ``moveaxis``+``pad`` re-layout is gone (jaxpr-asserted in
    ``tests/test_sharded_decode.py``).  Padded positions sit beyond
    ``cache_len`` so the in-kernel mask zeroes them.  ``block_k`` comes from
    :data:`SPLITK_BLOCK_K_TABLE` unless pinned, and ``interpret=None`` defers
    to the platform default (compiled on TPU, interpreter elsewhere).  Since
    ``S`` is fixed for the lifetime of a cache, the jit cache is hit on every
    step while ``cache_len`` grows (asserted in the parity harness).
    """

    name = "pallas-splitk"

    def __init__(self, block_k: Optional[int] = None,
                 interpret: Optional[bool] = None):
        import jax  # gate the optional accelerator dep at construction time

        del jax
        self.block_k = block_k
        self.interpret = interpret

    @property
    def state_key(self) -> str:
        return f"{self.name}:bk{self.block_k}:i{self.interpret}"

    def block_k_for(self, seq_cap: int) -> int:
        if self.block_k is not None:
            return self.block_k
        for bound, bk in SPLITK_BLOCK_K_TABLE:
            if bound is None or seq_cap <= bound:
                return bk
        raise AssertionError("unreachable")  # pragma: no cover

    def cache_layout(self, max_len: int) -> KVCacheLayout:
        # The autotune table is bucketed on bounds that are multiples of
        # their own block_k, so padded_len never crosses into a bucket with
        # a different block size: block_k_for(padded) == block_k_for(max_len).
        return KVCacheLayout(block_k=self.block_k_for(max(int(max_len), 1)))

    def decode(self, q, k_cache, v_cache, cache_len):
        out, _ = self.decode_partial(q, k_cache, v_cache, cache_len)
        return out.astype(q.dtype)

    def decode_partial(self, q, k_cache, v_cache, cache_len):
        import jax.numpy as jnp

        from repro.kernels.decode_attention.ops import decode_mha

        S = k_cache.shape[2]
        self.cache_layout(S).check_capacity(S)  # no silent per-step re-pad
        bk = self.block_k_for(S)
        B, _, H, D = q.shape
        out, lse = decode_mha(
            q.reshape(B, H, D), k_cache, v_cache,
            jnp.asarray(cache_len, jnp.int32),
            block_k=bk, interpret=self.interpret,
        )
        return out[:, None], lse[:, None]


# ---------------------------------------------------------------------------
# unified registry
# ---------------------------------------------------------------------------


_REGISTRY: Dict[str, type] = {
    NumpyCsrBackend.name: NumpyCsrBackend,
    NumpyFastBackend.name: NumpyFastBackend,
    PallasBsrBackend.name: PallasBsrBackend,
    PallasBsrShardedBackend.name: PallasBsrShardedBackend,
}
BACKEND_NAMES = tuple(_REGISTRY)

_ATTENTION_REGISTRY: Dict[str, type] = {
    DenseRefAttention.name: DenseRefAttention,
    ChunkedLseAttention.name: ChunkedLseAttention,
    PallasSplitKAttention.name: PallasSplitKAttention,
}
ATTENTION_BACKEND_NAMES = tuple(_ATTENTION_REGISTRY)

# kind → (registry, (default off-TPU, default on TPU), label, duck-type
# method an instance of the kind must expose — catches a wrong-kind instance
# at resolution time instead of an AttributeError deep inside a jit trace).
# The defaults: the host path or oracle on CPU hosts, the device kernel on a
# TPU.
_KINDS = {
    "compute": (_REGISTRY, ("numpy-fast", "pallas-bsr-sharded"),
                "compute backend", "apply"),
    "attention": (_ATTENTION_REGISTRY, ("dense-ref", "pallas-splitk"),
                  "attention backend", "decode"),
}


def _on_tpu() -> bool:
    try:
        import jax
    except ImportError:
        return False
    return jax.default_backend() == "tpu"


_LEGACY = object()  # sentinel: one-argument get_backend(name) = compute


def get_backend(kind, name=_LEGACY):
    """Resolve a backend by ``(kind, name)`` — the single entry point for
    both registries.

    ``get_backend("compute", "numpy-fast")`` / ``get_backend("attention",
    "pallas-splitk")``.  ``name=None`` resolves from ``jax.default_backend()``:
    the fleet megakernel (``pallas-bsr-sharded``) and the split-K decode
    kernel (``pallas-splitk``) on TPU, ``numpy-fast`` and the ``dense-ref``
    oracle on every other host.  Instances pass through unchanged, so
    callers can hand in a pre-configured backend (e.g.
    ``ChunkedLseAttention(kv_chunk=256)``).

    The legacy one-argument form ``get_backend(name_or_instance)`` still
    means a compute backend (every PR 1 call site).
    """
    if name is _LEGACY:
        kind, name = "compute", kind
    if kind not in _KINDS:
        raise ValueError(
            f"unknown backend kind {kind!r}; options: {tuple(_KINDS)}"
        )
    registry, defaults, label, duck_method = _KINDS[kind]
    if name is None:
        name = defaults[_on_tpu()]
    if not isinstance(name, str):
        if not callable(getattr(name, duck_method, None)):
            raise TypeError(
                f"{name!r} is not a {label}: missing .{duck_method}()"
            )
        return name
    try:
        return registry[name]()
    except KeyError:
        raise ValueError(
            f"unknown {label} {name!r}; options: {tuple(registry)}"
        ) from None
    except ImportError as e:  # pallas-* without jax installed
        raise ImportError(
            f"backend {name!r} needs jax; install it or use {defaults[0]!r}"
        ) from e
