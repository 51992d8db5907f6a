"""The grouped expert matmul as the expert layer calls it.

:func:`moe_gmm` takes the rows an expert layer computes, sorted by expert,
and the size of each expert's group.  On a TPU it lays the groups out in
whole tiles and runs the Pallas kernel (``moe_gmm.py``) under the jitted
wrapper ``_moe_gmm_jit``, after which the device trace names the kernel's
custom call; elsewhere it runs the jnp oracle (``ref.py``), as the
platform decides for the repository's other kernels.  ``interpret=True``
runs the kernel in the Pallas interpreter (the kernel tests).

Each row's result depends only on that row and its expert's weights, so
a token's output does not depend on which other rows share the call.
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.moe_gmm.moe_gmm import grouped_swiglu
from repro.kernels.moe_gmm.ref import moe_gmm_ref

__all__ = ["moe_gmm", "row_tile", "tile_layout"]


def row_tile(rows: int, experts: int) -> int:
    """Rows a kernel tile holds: about the rows one expert gets when
    ``rows`` spread over ``experts``, a power of two in 16 (bf16's
    sublane tile) to 128 (the MXU's side)."""
    per = max(1, -(-rows // experts))
    return min(128, max(16, 1 << (per - 1).bit_length()))


def tile_layout(group_sizes, rows: int, tm: int):
    """Where each sorted row goes when every group is padded to whole
    tiles of ``tm``.  Returns (n_tiles, src [n_tiles * tm] the sorted row
    each tiled row takes, ``rows`` where it takes none; dst [rows] the
    tiled row each sorted row lands on; tile_expert [n_tiles]; n_used
    [1])."""
    E = group_sizes.shape[0]
    gs = group_sizes.astype(jnp.int32)
    n_tiles = -(-(rows + min(E, rows) * (tm - 1)) // tm)
    padded = -(-gs // tm) * tm
    pad_end = jnp.cumsum(padded)
    pad_start = pad_end - padded
    end = jnp.cumsum(gs)
    start = end - gs
    n_used = pad_end[-1] // tm
    t = jnp.arange(n_tiles, dtype=jnp.int32)
    last = jnp.searchsorted(pad_end, jnp.maximum(n_used - 1, 0) * tm,
                            side="right")
    tile_expert = jnp.where(
        t < n_used, jnp.searchsorted(pad_end, t * tm, side="right"), last)
    tile_expert = jnp.minimum(tile_expert, E - 1).astype(jnp.int32)
    p = jnp.arange(n_tiles * tm, dtype=jnp.int32)
    e = tile_expert[p // tm]
    local = p - pad_start[e]
    src = jnp.where((p // tm < n_used) & (local < gs[e]), start[e] + local,
                    rows)
    r = jnp.arange(rows, dtype=jnp.int32)
    g = jnp.minimum(jnp.searchsorted(end, r, side="right"), E - 1)
    dst = jnp.where(r < end[-1], pad_start[g] + r - start[g], n_tiles * tm)
    return n_tiles, src, dst, tile_expert, n_used.reshape(1)


# The device trace names the kernel's custom call after this wrapper; the
# ``moe_gmm_roofline`` reader matches the ``_moe_gmm_jit`` prefix.
@partial(jax.jit, static_argnames=("tm", "interpret"))
def _moe_gmm_jit(x_tiles, tile_expert, n_used, layer, w_gate, w_up, w_down,
                 *, tm: int, interpret: bool):
    return grouped_swiglu(x_tiles, tile_expert, n_used, layer, w_gate, w_up,
                          w_down, tm=tm, interpret=interpret)


@partial(jax.jit, static_argnames=("tm", "interpret"))
def _grouped(x, group_sizes, layer, w_gate, w_up, w_down, *, tm: int,
             interpret: bool):
    rows, d = x.shape
    n_tiles, src, dst, tile_expert, n_used = tile_layout(group_sizes, rows,
                                                         tm)
    x_tiles = jnp.concatenate([x, jnp.zeros((1, d), x.dtype)])[src]
    layer = jnp.reshape(jnp.asarray(layer, jnp.int32), (1,))
    # A call with no rows reads no expert's weights.
    y = jax.lax.cond(
        n_used[0] > 0,
        lambda: _moe_gmm_jit(x_tiles, tile_expert, n_used, layer, w_gate,
                             w_up, w_down, tm=tm, interpret=interpret),
        lambda: jnp.zeros((n_tiles * tm, d), jnp.float32))
    return jnp.concatenate([y, jnp.zeros((1, d), y.dtype)])[dst]


def moe_gmm(x, group_sizes, w_gate, w_up, w_down, layer=None, *,
            interpret: Optional[bool] = None):
    """x [M, d] rows sorted by expert, group_sizes int32 [E] (rows past the
    groups give zeros), weights [E, d, f], [E, d, f], [E, f, d] -> f32
    [M, d]: each row through its expert's SwiGLU FFN.  Given ``layer``,
    the weights are a stack of layers, [L, E, d, f] ..., and the call uses
    that layer's, read in place."""
    if layer is None:
        w_gate, w_up, w_down, layer = (w_gate[None], w_up[None],
                                       w_down[None], 0)
    if interpret is None and jax.default_backend() != "tpu":
        return moe_gmm_ref(x, group_sizes, w_gate[layer], w_up[layer],
                           w_down[layer])
    tm = row_tile(x.shape[0], group_sizes.shape[0])
    return _grouped(x, group_sizes, layer, w_gate, w_up, w_down, tm=tm,
                    interpret=bool(interpret))
