"""Grouped expert matmul: the SwiGLU expert FFN over rows grouped by expert.

The expert layer sorts the rows it computes by expert; this kernel runs
each group through its own expert's weights.  Rows are laid out in tiles
of ``tm``, each group padded to whole tiles (``ops.py`` builds the layout),
so every tile belongs to one expert.  Grid: one step per tile.  A step
takes its tile's ``[tm, d]`` rows and its expert's whole ``[d, f]`` gate
and up and ``[f, d]`` down blocks, and writes ``silu(x Wg) * (x Wu) Wd``
for the tile: bf16 operands, f32 sums, the gated product rounded to the
operands' type before ``Wd``.

The weights are the whole stack of a model's expert layers, ``[L, E, d,
f]``, and the layer is a scalar: the kernel reads its layer's experts in
place, so no per-layer copy of the expert weights is made.  The tiles of
one expert are consecutive, so the expert's weight blocks keep their
index from one step to the next and the pipeline fetches them once per
call; an expert with no rows owns no tile, and its weights are never
fetched.  Steps past the last used tile keep the last expert's index
(nothing new is fetched) and write zeros.
"""

from __future__ import annotations


import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret


def _kernel(tile_expert_ref, n_used_ref, layer_ref, x_ref, wg_ref, wu_ref,
            wd_ref, o_ref):
    del tile_expert_ref, layer_ref     # read by the index maps only
    i = pl.program_id(0)

    @pl.when(i < n_used_ref[0])
    def _tile():
        x = x_ref[...]
        g = jnp.dot(x, wg_ref[0, 0], preferred_element_type=jnp.float32)
        u = jnp.dot(x, wu_ref[0, 0], preferred_element_type=jnp.float32)
        h = (jax.nn.silu(g) * u).astype(wd_ref.dtype)
        o_ref[...] = jnp.dot(h, wd_ref[0, 0],
                             preferred_element_type=jnp.float32)

    @pl.when(i >= n_used_ref[0])
    def _unused():
        o_ref[...] = jnp.zeros_like(o_ref)


def _vmem_limit(tm: int, d: int, f: int, itemsize: int) -> int:
    """Scoped-VMEM budget, double-buffered: one expert's three weight
    blocks, the row tile and the f32 output tile, the f32 gate, up and
    gated product, plus 8 MiB of headroom.  Never below the 32 MiB
    default."""
    weights = 3 * d * f * itemsize
    tiles = tm * d * (itemsize + 4)
    return max(32 << 20, 2 * (weights + tiles) + 3 * tm * f * 4 + (8 << 20))


def grouped_swiglu(x_tiles, tile_expert, n_used, layer, w_gate, w_up, w_down,
                   *, tm: int, interpret: bool | None = None):
    """x_tiles [n_tiles * tm, d], tiles of rows each of one expert;
    tile_expert int32 [n_tiles], the expert of each tile (past the used
    tiles, the last used tile's); n_used int32 [1], the tiles in use;
    layer int32 [1]; weights [L, E, d, f], [L, E, d, f], [L, E, f, d].
    Returns f32 [n_tiles * tm, d], zero in the unused tiles."""
    interpret = resolve_interpret(interpret)
    M, d = x_tiles.shape
    f = w_gate.shape[-1]
    n_tiles = M // tm
    assert n_tiles * tm == M

    def rows(i, te, nu, layer):
        return (i, 0)

    def expert(i, te, nu, layer):
        return (layer[0], te[i], 0, 0)

    return pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,               # tile_expert, n_used, layer
            grid=(n_tiles,),
            in_specs=[
                pl.BlockSpec((tm, d), rows),
                pl.BlockSpec((1, 1, d, f), expert),
                pl.BlockSpec((1, 1, d, f), expert),
                pl.BlockSpec((1, 1, f, d), expert),
            ],
            out_specs=pl.BlockSpec((tm, d), rows),
        ),
        out_shape=jax.ShapeDtypeStruct((M, d), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_vmem_limit(tm, d, f, w_gate.dtype.itemsize)),
        interpret=interpret,
    )(tile_expert, n_used, layer, x_tiles, w_gate, w_up, w_down)
