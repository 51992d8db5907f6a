"""The work a DeepSeekMoE decoder's cut needs, counted from its shapes and
from the program's own counts of the expert rows it computed (every
routed assignment to a held expert, none dropped, so the rows are the
work the routing asks for, not a choice of the implementation)."""

from __future__ import annotations

from typing import Dict


def _per_token(d: dict) -> float:
    """Matmul FLOPs per token outside attention's scores and the routed
    experts: every layer's Q, K, V and output projections, the dense
    layers' MLP, and each expert layer's router and shared experts."""
    D, H, KV, Dh, F, f = (d[k] for k in ("D", "H", "KV", "Dh", "F", "f"))
    n_moe = d["L"] - d["first_dense"]
    proj = 2.0 * (D * H * Dh + 2 * D * KV * Dh + H * Dh * D)
    return (d["L"] * proj + d["first_dense"] * 2.0 * 3 * D * F
            + n_moe * 2.0 * (D * d["E"] + 3 * D * f * d["shared"]))


def decode_token(d: dict, length: int) -> Dict[str, float]:
    """The forward FLOPs of one decode token, its cache holding ``length``
    valid positions, without the routed experts: :func:`_per_token`,
    attention over the ``length`` positions and the output head."""
    return {"forward_flops": (_per_token(d) + d["L"] * 4.0 * d["H"] * d["Dh"]
                              * length + 2.0 * d["D"] * d["V"])}


def prefill(d: dict, prompt: int) -> Dict[str, float]:
    """The forward FLOPs of prefilling ``prompt`` tokens without the routed
    experts: every token's :func:`_per_token`, causal attention and the
    output head at the last position."""
    ctx = prompt * (prompt + 1) / 2.0
    return {"forward_flops": (_per_token(d) * prompt
                              + d["L"] * 4.0 * d["H"] * d["Dh"] * ctx
                              + 2.0 * d["D"] * d["V"])}


def experts(d: dict, rows: float, groups_read: float) -> Dict[str, float]:
    """The grouped expert kernel over ``rows`` held assignments that read
    ``groups_read`` experts' weights, summed over its calls: FLOPs ``6 d
    f`` a row (gate, up and down projections); bytes each read expert's
    three ``d x f`` bf16 matrices, plus each row in and out in bf16."""
    D, f = d["D"], d["f"]
    return {"moe_gmm_flops": 6.0 * D * f * rows,
            "moe_gmm_bytes": 3.0 * D * f * 2 * groups_read
            + rows * D * 2 * 2}
