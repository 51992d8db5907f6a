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


@pytest.mark.parametrize("cap,block_k", BUCKETS)
def test_paged_decode_attention_bucket(one_chip, cap, block_k):
    """The paged kernel as the decode step calls it: 4 slots over a pool of
    internlm2-1.8b's 24 layers, a page one block of each bucket."""
    from repro.kernels.decode_attention.ops import decode_mha_paged

    slots, L, bf16 = 4, 24, jnp.bfloat16
    width = cap // block_k
    pool = (L, 1, KV, 2 + slots * width, block_k, D)
    compiled = _compile(
        one_chip, partial(decode_mha_paged, interpret=False),
        ((slots, H, D), bf16), (pool, bf16), (pool, bf16),
        ((slots, width), jnp.int32), ((slots,), jnp.int32), ((), jnp.int32))
    # the roofline reader finds the kernel by its wrapper's name
    assert "_decode_mha_jit_paged" in compiled.as_text()


def test_internlm2_paged_decode_step(one_chip):
    """The scheduler's paged decode step at ``lm_code``'s shapes: 4 slots,
    capacity 3328 in pages of 256, 54 pages, compiled ``pallas-splitk``.
    Its scratch stays below one gathered cache of every slot, and below
    one leaf of the pool (a scatter over the page axes had the compiler
    relayout a whole leaf around it), so no copy of the pool or of a
    per-slot cache hides in it."""
    from repro.configs import get_config
    from repro.core.backends import PallasSplitKAttention
    from repro.models.registry import get_model
    from repro.serving.kv_pool import BlockAllocator, KVBlockPool
    from repro.serving.scheduler import build_step

    cfg = get_config("internlm2-1.8b")
    attn = PallasSplitKAttention(interpret=False)
    model = get_model(cfg, attn_backend=attn)
    slots, cap, num_blocks = 4, 3328, 54
    layout = attn.cache_layout(cap)
    assert layout.block_k == 256
    params = jax.eval_shape(model.init, jax.random.key(0))
    template = jax.eval_shape(
        lambda p: model.prefill(p, {"tokens": jnp.zeros((1, 1), jnp.int32)},
                                cap)[1], params)
    axes = model.cache_seq_axes(template)
    buffers = jax.eval_shape(
        lambda t: KVBlockPool.build(t, axes, layout, num_blocks).buffers,
        template)
    pool = KVBlockPool(layout=layout, num_blocks=num_blocks,
                       table_width=cap // layout.block_k, seq_axes=axes,
                       buffers=None, allocator=BlockAllocator(num_blocks))
    place = lambda t: jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        t)
    sds = lambda s, d: jax.ShapeDtypeStruct(s, d, sharding=one_chip)
    resident = {"k": None, "v": None, "length": sds((slots,), jnp.int32)}
    compiled = build_step(model, pool, axes).lower(
        place(params), sds((slots, 1, 1), jnp.int32), resident,
        place(buffers), sds((slots, pool.table_width), jnp.int32),
        sds((slots,), jnp.bool_)).compile()
    assert "_decode_mha_jit_paged" in compiled.as_text()
    gathered = (slots * 2 * cfg.n_layers * cfg.n_kv_heads * cap * cfg.d_head
                * 2)
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < gathered
    assert temp < buffers["k"].size * buffers["k"].dtype.itemsize
