"""Jitted wrapper for the split-KV decode kernel.

``interpret=None`` (the default) resolves from the platform: compiled MXU
dispatch on TPU, the Pallas interpreter everywhere else.  Benchmarks and the
``pallas-splitk`` attention backend inherit the right mode instead of the old
``interpret=True`` leaking interpreter dispatch onto real hardware.

The jitted inner function is keyed on (shapes, block_k, interpret) only —
``cache_len`` is a traced operand — so a decode loop over a fixed-capacity
cache compiles once and is cache-hit on every subsequent step
(``decode_mha_cache_size`` exposes the trace count for tests).
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax

from repro.kernels import resolve_interpret
from repro.kernels.decode_attention.decode_attention import decode_attention

__all__ = ["decode_mha", "decode_mha_cache_size"]


@partial(jax.jit, static_argnames=("block_k", "interpret"))
def _decode_mha_jit(q, k_cache, v_cache, cache_len, *, block_k: int,
                    interpret: bool):
    return decode_attention(q, k_cache, v_cache, cache_len,
                            block_k=block_k, interpret=interpret)


def decode_mha(q, k_cache, v_cache, cache_len, *, block_k: int = 512,
               interpret: Optional[bool] = None):
    return _decode_mha_jit(q, k_cache, v_cache, cache_len,
                           block_k=block_k,
                           interpret=resolve_interpret(interpret))


def decode_mha_cache_size() -> int:
    """Number of traced entries in the jit cache (retrace regression tests)."""
    return _decode_mha_jit._cache_size()
