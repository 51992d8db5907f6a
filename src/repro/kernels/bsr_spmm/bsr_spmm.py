"""Fused BSR SpMM + bias + ReLU + clip — the GraphChallenge layer op.

Hardware adaptation (DESIGN.md §3/§7): the paper's Lambda workers run
scalar-granular CSR SpMM on CPUs; the MXU wants dense tiles, so the weight
sparsity pattern is snapped to an (bm × bn) block grid offline
(``core.sparse.bsr_from_csr``) and the kernel multiplies only the nonzero
blocks.  GraphChallenge RadiX-Net butterflies are 32-wide digit windows, so
blocks capture the structure with near-zero fill-in when bn ≤ 32·stride.

Layout (padded BSR, built offline):
  blocks  f32[NBR, K, bm, bn]   dense nonzero blocks, zero-padded to K/row
  cols    i32[NBR, K]           block-column ids (0 for padding — safe)
  x       f32[N, B]             dense activations (batch panel)
  y       f32[M, B]             y = clip(relu(Wx + bias), 0, clip)

Grid: (batch-panels, row-blocks).  The block-column ids (and the fleet's
per-row block counts) are scalar-prefetched into SMEM, where they feed the
``pl.ds`` starts and ``fori_loop`` bounds; the K nonzero blocks of one
row-block stream into VMEM via the BlockSpec, and x panels are sliced by
block column id from the full-x VMEM block, which stays resident across the
inner row-block sweep.  N·bb·4B must fit VMEM.

The block products run at ``Precision.HIGHEST``: f32 on the MXU otherwise
rounds x to bf16, and GraphChallenge activations are not bf16-exact.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret

_HIGHEST = jax.lax.Precision.HIGHEST


def _block_dot(wb, xb):
    return jnp.dot(wb, xb, precision=_HIGHEST,
                   preferred_element_type=jnp.float32)


def _vmem_limit(n: int, bb: int, k_max: int, bm: int, bn: int) -> int:
    """Scoped-VMEM budget for one grid step, double-buffered: the resident
    x panel [n, bb], one row-block's K weight blocks (minor dim padded to
    128 lanes) and the output tile, plus 4 MiB of headroom.  Never below
    the chip's 16 MiB default; a shape past the 128 MiB of v5e VMEM is left
    to the compiler to refuse."""
    lanes = lambda d: -(-d // 128) * 128
    need = 2 * 4 * (n * lanes(bb) + k_max * bm * lanes(bn) + bm * lanes(bb))
    return max(16 << 20, need + (4 << 20))


def _kernel(cols_ref, blocks_ref, x_ref, y_ref, *, bn: int, k_max: int,
            bias: float, clip: float):
    r = pl.program_id(1)
    bm = blocks_ref.shape[2]
    bb = y_ref.shape[1]

    def body(i, acc):
        c = pl.multiple_of(cols_ref[r, i] * bn, bn)
        return acc + _block_dot(blocks_ref[0, i], x_ref[pl.ds(c, bn), :])

    acc = jax.lax.fori_loop(0, k_max, body, jnp.zeros((bm, bb), jnp.float32))
    y_ref[...] = jnp.clip(acc + bias, 0.0, clip)


def _fleet_kernel(counts_ref, cols_ref, blocks_ref, x_ref, y_ref, *, nbr: int,
                  k_max: int, bn: int, bias: float, clip: float):
    """One (worker, batch-panel, row-block) cell of the fleet megakernel.

    The K loop runs to the row's REAL block count (``counts_ref``, the BSR
    indptr diff), so it skips the fleet-global K padding.  ``counts`` and
    ``cols`` arrive flattened: SMEM pads a 2-D array's minor dim to 128
    words, which overflows its 1 MiB at N=65536.  Padding blocks
    are all-zero, so the bound only drops exact +0.0 terms and the result
    matches :func:`_fleet_host_lowering` bitwise on CPU hosts (asserted in
    ``tests/test_kernels.py``).
    """
    row = pl.program_id(0) * nbr + pl.program_id(2)   # flat (worker, row)
    bm = blocks_ref.shape[3]
    bb = y_ref.shape[2]

    def body(i, acc):
        c = pl.multiple_of(cols_ref[row * k_max + i] * bn, bn)
        return acc + _block_dot(blocks_ref[0, 0, i], x_ref[0, pl.ds(c, bn), :])

    acc = jax.lax.fori_loop(0, counts_ref[row], body,
                            jnp.zeros((bm, bb), jnp.float32))
    y_ref[0] = jnp.clip(acc + bias, 0.0, clip)


def _fleet_host_lowering(blocks, cols, x, bias: float, clip: float):
    """The megakernel math for the whole device shard as straight XLA ops —
    the CPU lowering.

    One fancy gather of every referenced x block row plus K batched matmuls
    accumulated in ascending-k order, the same per-block contraction and
    k-sum order as the kernel.  The Pallas interpreter pays ~1ms of staging
    per grid cell on CPU hosts, so interpreted dispatch routes here; bitwise
    parity with the interpreted Pallas grid is asserted in
    ``tests/test_kernels.py``.
    """
    p, nbr, k_max, bm, bn = blocks.shape
    b = x.shape[2]
    offs = jax.lax.broadcasted_iota(jnp.int32, (p, nbr, k_max, bn), 3)
    idx = (cols * bn)[..., None] + offs                    # [P, NBR, K, bn]
    xg = x[jnp.arange(p)[:, None], idx.reshape(p, -1)]     # [P, NBR·K·bn, B]
    xg = xg.reshape(p, nbr, k_max, bn, b)
    acc = jnp.zeros((p, nbr, bm, b), jnp.float32)
    for i in range(k_max):  # ascending k — the sequential accumulation order
        acc = acc + jax.lax.dot_general(
            blocks[:, :, i], xg[:, :, i],
            (((3,), (2,)), ((0, 1), (0, 1))),
            precision=_HIGHEST, preferred_element_type=jnp.float32)
    return jnp.clip(acc.reshape(p, nbr * bm, b) + bias, 0.0, clip)


def bsr_spmm_fleet_megakernel(
    blocks: jnp.ndarray,   # [P, NBR, K, bm, bn] — stacked worker panels
    cols: jnp.ndarray,     # [P, NBR, K] int32
    counts: jnp.ndarray,   # [P, NBR] int32 — real blocks per panel row
    x: jnp.ndarray,        # [P, N, B]
    bias: float,
    clip: float = 32.0,
    batch_block: int = 128,
    interpret: Optional[bool] = None,
    force_grid: bool = False,
) -> jnp.ndarray:
    """The whole worker fleet (or one device's shard of it) in ONE
    ``pallas_call``: the grid walks the device's worker panels (leading grid
    dimension = worker index) × batch panels × row blocks, so every panel
    flows through the kernel without re-entering XLA (or a vmap batching
    rule) between workers.

    The per-worker BSR structure arrives device-local from
    ``fleet_prepare_all``: padded ``blocks``/``cols`` panels concatenated
    along the worker axis plus ``counts`` (the per-row true block count, the
    BSR indptr diff) which bounds the K loops.

    ``interpret=None`` resolves from the platform: compiled on TPU, and on
    other hosts the interpreted dispatch routes through
    :func:`_fleet_host_lowering` — the same math as vectorized XLA ops —
    because the interpreter's per-grid-cell staging dominates at fleet grid
    sizes.  ``force_grid=True`` runs the interpreted Pallas grid itself (the
    parity tests do).  Returns ``y [P, NBR*bm, B]``.
    """
    interpret = resolve_interpret(interpret)
    p, nbr, k_max, bm, bn = blocks.shape
    p2, n, b = x.shape
    assert p == p2, (p, p2)
    if interpret and not force_grid:
        return _fleet_host_lowering(blocks, cols, x, bias, clip)
    bb = min(batch_block, b)
    assert b % bb == 0, "batch_block (clamped to batch) must divide batch"
    return pl.pallas_call(
        functools.partial(_fleet_kernel, nbr=nbr, k_max=k_max, bn=bn,
                          bias=bias, clip=clip),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,                                   # counts, cols
            grid=(p, b // bb, nbr),
            in_specs=[
                pl.BlockSpec((1, 1, k_max, bm, bn),
                             lambda w, j, r, *_: (w, r, 0, 0, 0)),   # blocks
                pl.BlockSpec((1, n, bb), lambda w, j, r, *_: (w, 0, j)),  # x
            ],
            out_specs=pl.BlockSpec((1, bm, bb),
                                   lambda w, j, r, *_: (w, r, j)),
        ),
        out_shape=jax.ShapeDtypeStruct((p, nbr * bm, b), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_vmem_limit(n, bb, k_max, bm, bn)),
        interpret=interpret,
    )(counts.reshape(-1), cols.reshape(-1), blocks, x)


def bsr_spmm_fused(
    blocks: jnp.ndarray,   # [NBR, K, bm, bn]
    cols: jnp.ndarray,     # [NBR, K] int32
    x: jnp.ndarray,        # [N, B]
    bias: float,
    clip: float = 32.0,
    batch_block: int = 128,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    interpret = resolve_interpret(interpret)
    nbr, k_max, bm, bn = blocks.shape
    n, b = x.shape
    bb = min(batch_block, b)
    assert b % bb == 0, "batch_block (clamped to batch) must divide batch"
    return pl.pallas_call(
        functools.partial(_kernel, bn=bn, k_max=k_max, bias=bias, clip=clip),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,                                   # cols
            grid=(b // bb, nbr),
            in_specs=[
                pl.BlockSpec((1, k_max, bm, bn),
                             lambda j, i, _: (i, 0, 0, 0)),          # blocks
                pl.BlockSpec((n, bb), lambda j, i, _: (0, j)),       # x panel
            ],
            out_specs=pl.BlockSpec((bm, bb), lambda j, i, _: (i, j)),
        ),
        out_shape=jax.ShapeDtypeStruct((nbr * bm, b), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_vmem_limit(n, bb, k_max, bm, bn)),
        interpret=interpret,
    )(cols, blocks, x)
