"""Compiles for a described TPU v5e, with no chip attached.

The Pallas kernels on the served paths, and the continuous-batching decode
step, compiled at real widths by the chip's own compiler: it refuses
misaligned blocks and VMEM/SMEM overuse that interpret mode never sees.
Nothing runs, so nothing here says anything about results or times.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and the test workers each
import this file.
"""

from __future__ import annotations

import os
from functools import partial

import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from repro.core.backends import SPLITK_BLOCK_K_TABLE  # noqa: E402


@pytest.fixture(scope="module")
def one_chip():
    """A SingleDeviceSharding on chip 0 of a described v5e:2x2, with the
    persistent compilation cache off (a described-chip compile is written
    to it but cannot be read back without a chip)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    from jax.sharding import SingleDeviceSharding

    mp = pytest.MonkeyPatch()
    mp.setenv("TPU_LOG_DIR", "disabled")
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was_on)
    cc.reset_cache()
    mp.undo()


def _compile(sharding, fn, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()   # the kernel is there
    return compiled


# Graph Challenge fleet panels at P=4, bounded above: every row block may
# reference all 32 columns of its radix window in distinct blocks (K=32),
# and a worker's input panel may span all N rows.  N=1024 is the smoke's
# net; at the paper's largest, N=65536, the resident x panel takes 32 MiB
# of VMEM per buffer, and the raised scoped-VMEM limit still admits it.
@pytest.mark.parametrize("N", [1024, 16384, 65536])
def test_fleet_megakernel(one_chip, N):
    from repro.kernels.bsr_spmm.bsr_spmm import bsr_spmm_fleet_megakernel

    P, batch, bm, K = 4, 256, 32, 32
    nbr = N // P // bm
    f32, i32 = jnp.float32, jnp.int32
    _compile(one_chip,
             partial(bsr_spmm_fleet_megakernel, bias=-0.3, interpret=False),
             ((P, nbr, K, bm, bm), f32), ((P, nbr, K), i32), ((P, nbr), i32),
             ((P, N, batch), f32))


def test_per_worker_bsr_spmm(one_chip):
    from repro.kernels.bsr_spmm.bsr_spmm import bsr_spmm_fused

    nbr, K, bm, n, batch = 8, 32, 32, 1024, 256
    _compile(one_chip, partial(bsr_spmm_fused, bias=-0.3, interpret=False),
             ((nbr, K, bm, bm), jnp.float32), ((nbr, K), jnp.int32),
             ((n, batch), jnp.float32))


# internlm2-1.8b decode: 16 query heads over 8 KV heads (G=2), d_head 128,
# bf16 cache, one capacity per SPLITK_BLOCK_K_TABLE bucket.
H, KV, D = 16, 8, 128
BUCKETS = [(bound or 8192, bk) for bound, bk in SPLITK_BLOCK_K_TABLE]


@pytest.mark.parametrize("cap,block_k", BUCKETS)
def test_decode_attention_bucket(one_chip, cap, block_k):
    from repro.kernels.decode_attention.decode_attention import (
        decode_attention)

    bf16 = jnp.bfloat16
    _compile(one_chip,
             partial(decode_attention, block_k=block_k, interpret=False),
             ((1, H, D), bf16), ((1, KV, cap, D), bf16),
             ((1, KV, cap, D), bf16), ((), jnp.int32))


def test_decode_attention_vmapped_over_slots(one_chip):
    """The way ``RequestScheduler`` calls it: vmapped over slots, B=1 each,
    one ``cache_len`` per slot."""
    from repro.kernels.decode_attention.decode_attention import (
        decode_attention)

    slots, cap, bf16 = 4, 1024, jnp.bfloat16
    _compile(one_chip,
             jax.vmap(partial(decode_attention, block_k=128,
                              interpret=False)),
             ((slots, 1, H, D), bf16), ((slots, 1, KV, cap, D), bf16),
             ((slots, 1, KV, cap, D), bf16), ((slots,), jnp.int32))


def test_internlm2_slot_decode_step(one_chip):
    """The continuous-batching decode step at published widths: the model's
    decode_step vmapped over 4 slots with compiled ``pallas-splitk``."""
    from repro.configs import get_config
    from repro.core.backends import PallasSplitKAttention
    from repro.models.registry import get_model

    cfg = get_config("internlm2-1.8b")
    model = get_model(cfg, attn_backend=PallasSplitKAttention(interpret=False))
    params = jax.eval_shape(model.init, jax.random.key(0))
    cap = 640
    slot_cache = jax.eval_shape(
        lambda p: model.prefill(p, {"tokens": jnp.zeros((1, 1), jnp.int32)},
                                cap)[1], params)
    place = lambda t, lead=(): jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(lead + a.shape, a.dtype,
                                       sharding=one_chip), t)
    step = jax.jit(jax.vmap(model.decode_step, in_axes=(None, 0, 0)))
    compiled = step.lower(
        place(params),
        jax.ShapeDtypeStruct((4, 1, 1), jnp.int32, sharding=one_chip),
        place(slot_cache, (4,))).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16e9
