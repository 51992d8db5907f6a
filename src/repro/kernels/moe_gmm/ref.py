"""Oracle for the grouped expert matmul."""

from __future__ import annotations

import jax
import jax.numpy as jnp


def moe_gmm_ref(x, group_sizes, w_gate, w_up, w_down):
    """x [M, d] rows sorted by expert, ``group_sizes`` int32 [E] (row
    ``r`` belongs to the expert whose group holds it; rows past the last
    group give zeros), weights [E, d, f], [E, d, f], [E, f, d] ->
    ``silu(x Wg) * (x Wu)`` then ``Wd``, each row with its own expert's
    weights, f32 [M, d].  Products in the operands' type, sums in f32,
    the gated product rounded to the operands' type before ``Wd``."""
    M = x.shape[0]
    E = group_sizes.shape[0]
    ends = jnp.cumsum(group_sizes)
    expert = jnp.searchsorted(ends, jnp.arange(M), side="right")
    valid = expert < E
    expert = jnp.minimum(expert, E - 1)
    f32 = jnp.float32
    g = jnp.einsum("md,mdf->mf", x, w_gate[expert], preferred_element_type=f32)
    u = jnp.einsum("md,mdf->mf", x, w_up[expert], preferred_element_type=f32)
    h = (jax.nn.silu(g) * u).astype(x.dtype)
    y = jnp.einsum("mf,mfd->md", h, w_down[expert], preferred_element_type=f32)
    return jnp.where(valid[:, None], y, 0.0)
