"""Pallas kernels for the serving hot paths (see README.md)."""

from __future__ import annotations

from typing import Optional


def resolve_interpret(interpret: Optional[bool] = None) -> bool:
    """``None`` → the platform default: the Pallas interpreter off-TPU,
    compiled dispatch on a TPU.  An explicit flag passes through."""
    if interpret is None:
        import jax

        return jax.default_backend() != "tpu"
    return bool(interpret)
