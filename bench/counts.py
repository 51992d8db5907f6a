"""The work an algorithm needs, counted from its shapes: operations and
bytes per kernel call, and the forward FLOPs of a model.  Padding that an
implementation adds (BSR blocks, cache capacity, idle slots) is never
counted, so a count stays the same whatever implements the kernel."""

from __future__ import annotations

from typing import Dict, Sequence


def fsi_call(layer_nnz: Sequence[int], neurons: int,
             batch: int) -> Dict[str, float]:
    """One Graph Challenge inference call over every layer: per layer,
    FLOPs ``2 nnz batch`` (a multiply and an add per nonzero and input)
    and bytes ``8 nnz`` (f32 value and int32 column id) plus the f32
    activations read and written, ``2 N batch 4``."""
    nnz = float(sum(layer_nnz))
    L = len(layer_nnz)
    return {"flops": 2.0 * nnz * batch,
            "bytes": 8.0 * nnz + L * 2.0 * neurons * batch * 4.0}


def _per_token(d: dict) -> float:
    """Matmul FLOPs per token in one layer: Q, K, V and output projections
    and the SwiGLU MLP."""
    D, H, KV, Dh, F = (d[k] for k in ("D", "H", "KV", "Dh", "F"))
    return 2.0 * (D * H * Dh + 2 * D * KV * Dh + H * Dh * D + 3 * D * F)


def decode_token(d: dict, length: int) -> Dict[str, float]:
    """One decode step of one request whose cache holds ``length`` valid
    positions once the new token is written.

    The decode-attention kernel runs once per layer: FLOPs ``4 H len Dh``
    (scores and weighted values), bytes the valid K and V (bf16) plus q and
    the output (bf16) and the per-head lse (f32).  The forward FLOPs are
    every matmul of the model on the token: projections and MLP, attention
    over the ``length`` positions, and the output head."""
    L, D, H, KV, Dh, V = (d[k] for k in ("L", "D", "H", "KV", "Dh", "V"))
    return {
        "decode_attn_flops": L * 4.0 * H * Dh * length,
        "decode_attn_bytes": L * (2.0 * KV * Dh * 2 * length
                                  + H * Dh * 2 * 2 + H * 4),
        "forward_flops": (L * (_per_token(d) + 4.0 * H * Dh * length)
                          + 2.0 * D * V),
    }


def prefill(d: dict, prompt: int) -> Dict[str, float]:
    """The forward FLOPs of prefilling ``prompt`` tokens: every matmul on
    every token, causal attention (position ``j`` over ``j + 1`` keys) and
    the output head at the last position."""
    L, D, H, Dh, V = (d[k] for k in ("L", "D", "H", "Dh", "V"))
    ctx = prompt * (prompt + 1) / 2.0
    return {"forward_flops": (L * (_per_token(d) * prompt
                                   + 4.0 * H * Dh * ctx) + 2.0 * D * V)}


def roofline_pct(flops: float, nbytes: float, seconds: float,
                 peak_flops: float, peak_bw: float) -> float:
    """Share of its roofline a kernel reached: the least time the chip
    could take (the larger of FLOPs over peak FLOP/s and bytes over peak
    bandwidth) over the measured kernel time, in percent."""
    return 100.0 * max(flops / peak_flops, nbytes / peak_bw) / seconds
