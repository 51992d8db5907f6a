"""Jitted public wrappers around the BSR SpMM Pallas kernel.

Every wrapper takes ``interpret=None``, resolved from the platform: compiled
on TPU, the Pallas interpreter elsewhere.

``bsr_spmm``       — one worker-layer dispatch (jit-cached per shape/bias).
``bsr_spmm_fleet`` — the whole simulated fleet in one device dispatch: a
                     vmap over a leading worker axis of stacked padded-BSR
                     operands (see ``core.backends.PallasBsrBackend``).
``bsr_spmm_fleet_sharded`` — the same fleet panel laid out over a device
                     mesh: ``shard_map`` splits the worker axis across the
                     mesh's ``worker`` axis and each device runs a vmap of
                     the Pallas BSR body over its block of P/D workers (the
                     PR 3 dispatch, kept as the ``dispatch="vmap"`` fallback
                     and perf baseline).
``bsr_spmm_fleet_fused``   — the fleet megakernel on one device: ONE
                     ``pallas_call`` whose grid walks every worker's row
                     blocks (worker index folded into the grid), with the
                     per-panel block counts bounding the K loop.
``bsr_spmm_fleet_fused_sharded`` — the megakernel per mesh device: shard_map
                     splits the worker axis and each device runs a single
                     fused grid over its P/D worker panels — no vmap, no XLA
                     re-entry between workers
                     (``core.backends.PallasBsrShardedBackend`` default).
"""

from __future__ import annotations

from functools import lru_cache, partial
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.sparse import BSRMatrix
from repro.kernels import resolve_interpret
from repro.kernels.bsr_spmm.bsr_spmm import (
    bsr_spmm_fleet_megakernel,
    bsr_spmm_fused,
)

__all__ = ["sparse_layer_apply", "prepare_bsr_operands", "bsr_spmm",
           "bsr_spmm_fleet", "bsr_spmm_fleet_sharded",
           "bsr_spmm_fleet_fused", "bsr_spmm_fleet_fused_sharded"]


def prepare_bsr_operands(bsr: BSRMatrix):
    """Padded (blocks, cols) device arrays from an offline BSR matrix."""
    blocks, cols, _ = bsr.padded()
    return jnp.asarray(blocks, jnp.float32), jnp.asarray(cols, jnp.int32)


@partial(jax.jit, static_argnames=("bias", "clip", "batch_block", "interpret"))
def bsr_spmm(blocks, cols, x, *, bias: float, clip: float = 32.0,
             batch_block: int = 128, interpret: Optional[bool] = None):
    return bsr_spmm_fused(blocks, cols, x, bias=bias, clip=clip,
                          batch_block=batch_block, interpret=interpret)


@partial(jax.jit, static_argnames=("bias", "clip", "batch_block", "interpret"))
def bsr_spmm_fleet(blocks, cols, x, *, bias: float, clip: float = 32.0,
                   batch_block: int = 128, interpret: Optional[bool] = None):
    """Batched dispatch: blocks [P, NBR, K, bm, bn], cols [P, NBR, K],
    x [P, N, B] → y [P, NBR*bm, B].  One compile serves every layer when the
    operands are padded to fleet-global maxima."""
    return jax.vmap(
        lambda b, c, xx: bsr_spmm_fused(
            b, c, xx, bias=bias, clip=clip, batch_block=batch_block,
            interpret=interpret,
        )
    )(blocks, cols, x)


@lru_cache(maxsize=None)
def _fleet_sharded_fn(mesh, axis_name: str, bias: float, clip: float,
                      batch_block: int, interpret: bool):
    """Jit-cached shard_map dispatch for one (mesh, scalars) configuration.

    The mesh and every static knob are part of the cache key, so a fixed
    fleet layout compiles once and every layer's dispatch is a cache hit
    (the operands are padded to fleet-global maxima upstream).
    """
    from jax.sharding import PartitionSpec as P

    def local(blocks, cols, x):
        # Per-device body: this device's block of P/D workers, each worker a
        # full Pallas BSR SpMM + fused epilogue.  No cross-device collectives
        # — workers are independent, exactly the paper's isolation model.
        return jax.vmap(
            lambda b, c, xx: bsr_spmm_fused(
                b, c, xx, bias=bias, clip=clip, batch_block=batch_block,
                interpret=interpret,
            )
        )(blocks, cols, x)

    spec = P(axis_name)  # shard the leading worker axis; trailing dims whole
    return jax.jit(
        jax.shard_map(local, mesh=mesh, in_specs=(spec, spec, spec),
                      out_specs=spec, check_vma=False)
    )


def bsr_spmm_fleet_sharded(blocks, cols, x, *, mesh, axis_name: str = "worker",
                           bias: float, clip: float = 32.0,
                           batch_block: int = 128, interpret: Optional[bool] = None):
    """Mesh-sharded fleet dispatch: blocks [P, NBR, K, bm, bn], cols
    [P, NBR, K], x [P, N, B] → y [P, NBR*bm, B], with P divisible by the
    mesh's ``axis_name`` size (pad with zero workers upstream otherwise).
    Each device executes the Pallas BSR body for its contiguous block of
    workers; there is no cross-device communication inside a layer."""
    fn = _fleet_sharded_fn(mesh, axis_name, float(bias), float(clip),
                           int(batch_block), resolve_interpret(interpret))
    return fn(blocks, cols, x)


@partial(jax.jit, static_argnames=("bias", "clip", "batch_block", "interpret"))
def bsr_spmm_fleet_fused(blocks, cols, counts, x, *, bias: float,
                         clip: float = 32.0, batch_block: int = 128,
                         interpret: Optional[bool] = None):
    """Fused fleet dispatch on one device: blocks [P, NBR, K, bm, bn], cols
    [P, NBR, K], counts i32[P, NBR], x [P, N, B] → y [P, NBR*bm, B] through a
    single ``pallas_call`` (grid = worker panels × batch panels)."""
    return bsr_spmm_fleet_megakernel(
        blocks, cols, counts, x, bias=bias, clip=clip,
        batch_block=batch_block, interpret=interpret,
    )


@lru_cache(maxsize=None)
def _fleet_fused_sharded_fn(mesh, axis_name: str, bias: float, clip: float,
                            batch_block: int, interpret: bool):
    """Jit-cached shard_map dispatch of the fleet megakernel: one fused
    Pallas grid per device instead of a vmap over that device's workers."""
    from jax.sharding import PartitionSpec as P

    def local(blocks, cols, counts, x):
        # Per-device body: ONE pallas_call streaming this device's block of
        # P/D worker panels (worker index = leading grid dimension).  No
        # cross-device collectives — workers are independent, exactly the
        # paper's isolation model.
        return bsr_spmm_fleet_megakernel(
            blocks, cols, counts, x, bias=bias, clip=clip,
            batch_block=batch_block, interpret=interpret,
        )

    spec = P(axis_name)  # shard the leading worker axis; trailing dims whole
    return jax.jit(
        jax.shard_map(local, mesh=mesh, in_specs=(spec, spec, spec, spec),
                      out_specs=spec, check_vma=False)
    )


def bsr_spmm_fleet_fused_sharded(blocks, cols, counts, x, *, mesh,
                                 axis_name: str = "worker", bias: float,
                                 clip: float = 32.0, batch_block: int = 128,
                                 interpret: Optional[bool] = None):
    """Mesh-sharded megakernel dispatch: same operand contract as
    ``bsr_spmm_fleet_fused`` with P divisible by the mesh's ``axis_name``
    size (pad with zero workers upstream otherwise — their ``counts`` are 0
    so the K loop never touches them).  Each device executes one fused
    Pallas grid over its contiguous block of workers."""
    fn = _fleet_fused_sharded_fn(mesh, axis_name, float(bias), float(clip),
                                 int(batch_block),
                                 resolve_interpret(interpret))
    return fn(blocks, cols, counts, x)


def sparse_layer_apply(bsr: BSRMatrix, x, bias: float, clip: float = 32.0,
                       interpret: Optional[bool] = None):
    """One GraphChallenge layer: y = clip(relu(W·x + b), 0, clip)."""
    blocks, cols = prepare_bsr_operands(bsr)
    return bsr_spmm(blocks, cols, jnp.asarray(x, jnp.float32),
                    bias=bias, clip=clip, interpret=interpret)
