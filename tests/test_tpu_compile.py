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
def v5e():
    """A described v5e:2x2 topology, with the persistent compilation cache
    off (a described-chip compile is written to it but cannot be read
    back without a chip)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

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
    yield topo
    jax.config.update("jax_enable_compilation_cache", was_on)
    cc.reset_cache()
    mp.undo()


@pytest.fixture(scope="module")
def one_chip(v5e):
    """A SingleDeviceSharding on chip 0 of the described v5e:2x2."""
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(v5e.devices[0])


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


def test_deepseek_moe_paged_decode_step(one_chip, monkeypatch):
    """The scheduler's paged step for deepseek-moe-16b at ``dsmoe_conv``'s
    shapes: the published widths, 7 layers (the dense one and six expert
    layers), 32 of the 64 experts held, 32 slots at capacity 2560, with
    compiled ``pallas-splitk`` and the grouped expert kernel.  Its scratch
    stays below one gathered cache of every slot, so no whole-cache copy
    hides in it, nor a copy of the expert weights."""
    import dataclasses

    from repro.configs import get_config
    from repro.core.backends import PallasSplitKAttention
    from repro.kernels.moe_gmm import ops as gmm_ops
    from repro.models import moe
    from repro.models.registry import get_model
    from repro.serving.kv_pool import BlockAllocator, KVBlockPool
    from repro.serving.scheduler import build_step

    # the model's kernel path, which off a TPU takes the jnp oracle
    monkeypatch.setattr(moe, "moe_gmm",
                        partial(gmm_ops.moe_gmm, interpret=False))
    cfg = dataclasses.replace(get_config("deepseek-moe-16b"), n_layers=7,
                              held_experts=(0, 32))
    attn = PallasSplitKAttention(interpret=False)
    model = get_model(cfg, attn_backend=attn)
    slots, cap = 32, 2560
    layout = attn.cache_layout(cap)
    width = cap // layout.block_k
    num_blocks = 2 + slots * width
    params = jax.eval_shape(model.init, jax.random.key(0))
    template = jax.eval_shape(
        lambda p: model.prefill(p, {"tokens": jnp.zeros((1, 1), jnp.int32)},
                                cap)[1], params)
    axes = model.cache_seq_axes(template)
    buffers = jax.eval_shape(
        lambda t: KVBlockPool.build(t, axes, layout, num_blocks).buffers,
        template)
    pool = KVBlockPool(layout=layout, num_blocks=num_blocks,
                       table_width=width, seq_axes=axes, buffers=None,
                       allocator=BlockAllocator(num_blocks))
    place = lambda t: jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        t)
    sds = lambda s, d: jax.ShapeDtypeStruct(s, d, sharding=one_chip)
    resident = {"stacks": [{"k": None, "v": None}, {"k": None, "v": None}],
                "length": sds((slots,), jnp.int32),
                "counts": sds((len(model.counters),), jnp.int32)}
    compiled = build_step(model, pool, axes).lower(
        place(params), sds((slots, 1, 1), jnp.int32), resident,
        place(buffers), sds((slots, width), jnp.int32),
        sds((slots,), jnp.bool_)).compile()
    text = compiled.as_text()
    assert "_decode_mha_jit_paged" in text and "_moe_gmm_jit" in text
    gathered = (slots * 2 * cfg.n_layers * cfg.n_kv_heads * cap * cfg.d_head
                * 2)
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < gathered
    # nor a copy of a layer's expert weights (a layer scan slicing them
    # for the kernel, which cannot fuse the slice, made one per layer)
    assert temp < 32 * cfg.d_model * cfg.moe_d_ff * 2


# deepseek-moe-16b's experts: 6 expert layers, d 2048, f 1408, 32 held; a
# decode step of 32 slots (192 assignments) and a prefill of 2048 tokens
# (12288), one layer of the stack read in place.
@pytest.mark.parametrize("rows", [192, 12288])
def test_moe_gmm(one_chip, rows):
    from repro.kernels.moe_gmm.ops import _grouped, row_tile

    L, E, d, f, bf16 = 6, 32, 2048, 1408, jnp.bfloat16
    compiled = _compile(
        one_chip, partial(_grouped, tm=row_tile(rows, E), interpret=False),
        ((rows, d), bf16), ((E,), jnp.int32), ((), jnp.int32),
        ((L, E, d, f), bf16), ((L, E, d, f), bf16), ((L, E, f, d), bf16))
    assert "_moe_gmm_jit" in compiled.as_text()
    # no copy of a layer's expert weights: scratch below one layer's three
    assert compiled.memory_analysis().temp_size_in_bytes < 3 * E * d * f * 2


def test_moe_ep_train_grad(v5e, monkeypatch):
    """Training's opt-in expert parallelism (``moe_ffn_shardmap``, the
    dryrun's ``ep`` strategy) differentiates and compiles over the four
    described chips, each holding a quarter of the experts.  The grouped
    expert kernel, which has no transpose, is put in place of the model's
    jnp path, so a training path that reached it would fail here."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.configs import get_config
    from repro.kernels.moe_gmm import ops as gmm_ops
    from repro.models import layers as L
    from repro.models import moe

    monkeypatch.setattr(moe, "moe_gmm",
                        partial(gmm_ops.moe_gmm, interpret=False))
    cfg = get_config("deepseek-moe-16b").reduced()
    mesh = Mesh(np.array(v5e.devices).reshape(1, 4), ("data", "model"))
    rep = NamedSharding(mesh, P())
    place = lambda t: jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=rep), t)
    params = jax.eval_shape(lambda: moe.init_moe_ffn(jax.random.key(0), cfg))
    x = jax.ShapeDtypeStruct((4, 16, cfg.d_model), jnp.bfloat16, sharding=rep)

    def loss(p, x):
        out, aux = moe.moe_ffn_shardmap(p, x, cfg)
        return out.astype(jnp.float32).sum() + aux["lb_loss"]

    L.set_shard_ctx(mesh, ("data",), "model")
    try:
        compiled = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
            place(params), x).compile()
    finally:
        L.set_shard_ctx()
    text = compiled.as_text()
    assert "all-reduce" in text and "tpu_custom_call" not in text
