"""Fault tolerance: checkpoint/restart determinism, elastic resharding,
async save integrity, gradient compression convergence, and FaaS channel
failure paths (duplicate delivery / out-of-order chunk arrival)."""

import dataclasses
import os
import subprocess
import sys
import textwrap

import pytest

pytest.importorskip("jax")  # accelerator dep is optional for the numpy core

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ShapeConfig, get_config
from repro.training import checkpoint as ckpt
from repro.training.trainer import Trainer, TrainerConfig


def _mk_trainer(tmp, steps=6, ckpt_every=2, compress=False, seed=0,
                arch="llama3.2-1b", async_ckpt=False, stop_after=0):
    cfg = get_config(arch).reduced()
    shape = ShapeConfig("t", seq_len=16, global_batch=4, kind="train")
    tcfg = TrainerConfig(total_steps=steps, ckpt_every=ckpt_every,
                         ckpt_dir=tmp, compress_grads=compress,
                         async_ckpt=async_ckpt, stop_after=stop_after)
    return Trainer(cfg, shape, tcfg, seed=seed)


class TestCheckpoint:
    def test_roundtrip(self, tmp_path):
        tree = {"a": jnp.arange(12.0).reshape(3, 4),
                "b": {"c": jnp.ones((2,), jnp.int32)}}
        ckpt.save(str(tmp_path), 3, tree)
        restored, step = ckpt.restore(str(tmp_path), tree)
        assert step == 3
        np.testing.assert_array_equal(restored["a"], tree["a"])
        np.testing.assert_array_equal(restored["b"]["c"], tree["b"]["c"])

    def test_crc_detects_corruption(self, tmp_path):
        tree = {"w": jnp.ones((8, 8))}
        path = ckpt.save(str(tmp_path), 1, tree)
        # corrupt the single leaf file
        for name in os.listdir(path):
            if name.endswith(".npy"):
                arr = np.load(os.path.join(path, name))
                arr[0] += 1
                np.save(os.path.join(path, name), arr)
        with pytest.raises(IOError):
            ckpt.restore(str(tmp_path), tree)

    def test_keeps_latest(self, tmp_path):
        tree = {"w": jnp.ones(3)}
        for s in (1, 2, 3):
            ckpt.save(str(tmp_path), s, tree)
        assert ckpt.latest_steps(str(tmp_path)) == [1, 2, 3]
        _, step = ckpt.restore(str(tmp_path), tree)
        assert step == 3


class TestRestartDeterminism:
    def test_resume_bitwise_identical(self, tmp_path):
        """Uninterrupted run ≡ crash-at-step-4 + restart (same final params)."""
        full = _mk_trainer(str(tmp_path / "full"), steps=6)
        hist_full = full.fit()

        crash_dir = str(tmp_path / "crash")
        # crash mid-run: same 6-step schedule, killed after step 4
        part = _mk_trainer(crash_dir, steps=6, ckpt_every=2, stop_after=4)
        part.fit()
        resumed = _mk_trainer(crash_dir, steps=6, ckpt_every=2)
        hist_res = resumed.fit(resume=True)

        flat_a = jax.tree.leaves(full.params)
        flat_b = jax.tree.leaves(resumed.params)
        for a, b in zip(flat_a, flat_b):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        # loss history continues where it left off
        assert hist_res["step"][0] == 4
        np.testing.assert_allclose(hist_full["loss"][4:], hist_res["loss"],
                                   rtol=1e-6)

    def test_pipeline_step_keyed(self):
        from repro.data.pipeline import PipelineSpec

        cfg = get_config("llama3.2-1b").reduced()
        shape = ShapeConfig("t", 16, 4, "train")
        p1 = PipelineSpec(cfg, shape, seed=0)
        p2 = PipelineSpec(cfg, shape, seed=0)
        np.testing.assert_array_equal(p1.batch(7)["tokens"], p2.batch(7)["tokens"])
        assert not np.array_equal(p1.batch(7)["tokens"], p1.batch(8)["tokens"])
        # host-sharded slice == slice of the global batch
        full = p1.batch(3)["tokens"]
        np.testing.assert_array_equal(p1.batch(3, lo=1, hi=3)["tokens"], full[1:3])


class TestElasticResharding:
    def test_restore_onto_multi_device_mesh(self, tmp_path):
        """Checkpoint written on 1 device restores sharded onto 8 devices
        (subprocess with a forced 8-device CPU topology)."""
        t = _mk_trainer(str(tmp_path), steps=2, ckpt_every=2)
        t.fit()
        script = textwrap.dedent(f"""
            import os
            os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
            import jax, numpy as np
            from repro.configs import ShapeConfig, get_config
            from repro.models.registry import get_model
            from repro.training import checkpoint as ckpt
            from repro.training.optimizer import get_optimizer
            from repro.distributed.sharding import param_pspecs, to_named
            from repro.launch.mesh import make_mesh, MeshAxes

            cfg = get_config("llama3.2-1b").reduced()
            model = get_model(cfg)
            params = model.init(jax.random.key(0))
            opt = get_optimizer(cfg)
            opt_state = opt.init(params)
            mesh = make_mesh((2, 4), ("data", "model"))
            ax = MeshAxes(mesh)
            pspecs = param_pspecs(cfg, jax.eval_shape(lambda: params), ax)
            sh = {{"params": to_named(mesh, pspecs), "opt": None}}
            state, step = ckpt.restore(
                r"{tmp_path}", {{"params": params, "opt": opt_state}},
                shardings=None)
            # reshard params explicitly onto the 8-device mesh
            resharded = jax.tree.map(
                lambda a, s: jax.device_put(np.asarray(a), s),
                state["params"], to_named(mesh, pspecs),
                is_leaf=lambda x: hasattr(x, "shape") and not isinstance(x, dict))
            n_sharded = sum(
                len(a.sharding.device_set) > 1 for a in jax.tree.leaves(resharded))
            assert n_sharded > 0, "nothing was sharded"
            print("ELASTIC_OK", step, n_sharded)
        """)
        env = dict(os.environ, PYTHONPATH="src")
        out = subprocess.run([sys.executable, "-c", script], env=env,
                             capture_output=True, text=True, cwd="/root/repo",
                             timeout=300)
        assert "ELASTIC_OK" in out.stdout, out.stderr[-2000:]


class TestAsyncCheckpoint:
    def test_async_equals_sync(self, tmp_path):
        t_sync = _mk_trainer(str(tmp_path / "s"), steps=4, ckpt_every=2)
        t_sync.fit()
        t_async = _mk_trainer(str(tmp_path / "a"), steps=4, ckpt_every=2,
                              async_ckpt=True)
        t_async.fit()
        a, _ = ckpt.restore(str(tmp_path / "s"),
                            {"params": t_sync.params, "opt": t_sync.opt_state})
        b, _ = ckpt.restore(str(tmp_path / "a"),
                            {"params": t_async.params, "opt": t_async.opt_state})
        for x, y in zip(jax.tree.leaves(a["params"]), jax.tree.leaves(b["params"])):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


class TestGradientCompression:
    def test_wire_savings_and_convergence(self, tmp_path):
        from repro.distributed.compression import Int8Compressor

        base = _mk_trainer(str(tmp_path / "fp"), steps=8, ckpt_every=100)
        hist_fp = base.fit()
        comp = _mk_trainer(str(tmp_path / "q8"), steps=8, ckpt_every=100,
                           compress=True)
        hist_q8 = comp.fit()
        # int8 path converges: loss drops and stays within 10% of fp32 path
        assert hist_q8["loss"][-1] < hist_q8["loss"][0]
        assert abs(hist_q8["loss"][-1] - hist_fp["loss"][-1]) < 0.1 * hist_fp["loss"][-1] + 0.35
        fp32_b, int8_b = Int8Compressor.wire_bytes(base.params)
        assert int8_b < 0.27 * fp32_b

    def test_quantize_roundtrip_error_feedback(self):
        from repro.distributed.compression import (
            Int8Compressor, dequantize_int8, quantize_int8)

        rng = np.random.default_rng(0)
        g = jnp.asarray(rng.standard_normal((64, 64)), jnp.float32)
        q, s = quantize_int8(g)
        err = g - dequantize_int8(q, s)
        assert float(jnp.abs(err).max()) <= float(s) * 0.5 + 1e-6
        # error feedback: two-step quantized sum ≈ true sum
        comp = Int8Compressor()
        e = comp.init({"g": g})
        total = jnp.zeros_like(g)
        for _ in range(4):
            quant, e = comp.compress({"g": g}, e)
            total = total + comp.decompress(quant)["g"]
        np.testing.assert_allclose(total / 4, g, atol=float(s))


class TestCompressedPsum:
    def test_matches_fp32_psum_subprocess(self):
        """int8 shard_map psum ≈ fp32 psum on an 8-device mesh."""
        script = textwrap.dedent("""
            import os
            os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
            import jax, jax.numpy as jnp, numpy as np
            from jax.sharding import PartitionSpec as P
            from repro.distributed.compression import compressed_psum
            from repro.launch.mesh import make_mesh

            mesh = make_mesh((8,), ("data",))
            x = jax.random.normal(jax.random.key(0), (8, 64, 64), jnp.float32)

            def f(x_loc):
                return compressed_psum(x_loc[0], "data")

            got = jax.jit(jax.shard_map(
                f, mesh=mesh, in_specs=P("data"), out_specs=P(),
                check_vma=False))(x)
            want = x.sum(axis=0)
            scale = float(jnp.max(jnp.abs(x))) / 127.0
            np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                       atol=8 * scale)
            print("PSUM_OK")
        """)
        env = dict(os.environ, PYTHONPATH="src")
        out = subprocess.run([sys.executable, "-c", script], env=env,
                             capture_output=True, text=True, cwd="/root/repo",
                             timeout=300)
        assert "PSUM_OK" in out.stdout, out.stderr[-2000:]


# ---------------------------------------------------------------------------
# FaaS channel failure paths: duplicate delivery + out-of-order arrival
# ---------------------------------------------------------------------------

from repro.core.cost_model import AWS_PRICING
from repro.core.fsi import (
    FleetRecvBuffers,
    finish_layer,
    fsi_object_recv,
    fsi_object_recv_fleet,
    fsi_object_send_and_local,
    fsi_queue_recv,
    fsi_queue_recv_fleet,
    fsi_queue_send_and_local,
    prepare_worker_artifacts,
)
from repro.core.partitioner import partition_network
from repro.core.send_recv import build_comm_plans
from repro.data.graphchallenge import dense_inference, make_inputs, make_sparse_dnn
from repro.faas.object_service import ObjectFabric
from repro.faas.queue_service import QueueFabric
from repro.faas.simulator import LatencyModel, run_fsi
from repro.faas.worker import ComputeModel, EventLedger, WorkerState

# tiny cap forces multi-chunk sends so chunk ordering/duplication matters
SMALL_PRICING = dataclasses.replace(AWS_PRICING, max_publish_payload=1600)


class DuplicatingQueueFabric(QueueFabric):
    """At-least-once SQS: every published message is delivered twice, the
    duplicate arriving later (visibility-timeout style redelivery)."""

    def publish_batch(self, topic, entries, at_time, *, ledger_at=None):
        done = super().publish_batch(topic, entries, at_time,
                                     ledger_at=ledger_at)
        dup_led = None if ledger_at is None else ledger_at + 0.5
        return super().publish_batch(topic, entries, done + 0.5,
                                     ledger_at=dup_led)


class ReorderingQueueFabric(QueueFabric):
    """Deliveries within a poll window come back in reverse order."""

    def poll(self, worker, at_time, long_poll=True, max_messages=10):
        now, msgs = super().poll(worker, at_time, long_poll, max_messages)
        return now, list(reversed(msgs))


class DuplicatingReorderingQueueFabric(DuplicatingQueueFabric,
                                       ReorderingQueueFabric):
    pass


class DuplicatingObjectFabric(ObjectFabric):
    """Every object is PUT twice (idempotent overwrite of the same key) and
    LISTed twice (eventual-consistency style duplicate listing)."""

    def put_obj(self, layer, src, target, blob, at_time, *, ledger_at=None):
        done = super().put_obj(layer, src, target, blob, at_time,
                               ledger_at=ledger_at)
        dup_led = None if ledger_at is None else ledger_at + 0.5
        return super().put_obj(layer, src, target, blob, done,
                               ledger_at=dup_led)

    def list_files(self, layer, worker, at_time):
        now, handles = super().list_files(layer, worker, at_time)
        return now, handles + handles


class ReorderingObjectFabric(ObjectFabric):
    """LIST returns handles in reverse key order and multipart objects carry
    their chunks in reverse arrival order."""

    def put_multipart(self, layer, src, target, blobs, at_time, *,
                      ledger_at=None):
        return super().put_multipart(layer, src, target,
                                     list(reversed(blobs)), at_time,
                                     ledger_at=ledger_at)

    def list_files(self, layer, worker, at_time):
        now, handles = super().list_files(layer, worker, at_time)
        return now, list(reversed(handles))


QUEUE_FAULTS = {
    "duplicate": DuplicatingQueueFabric,
    "out-of-order": ReorderingQueueFabric,
    "duplicate+out-of-order": DuplicatingReorderingQueueFabric,
}
OBJECT_FAULTS = {
    "duplicate": DuplicatingObjectFabric,
    "out-of-order": ReorderingObjectFabric,
}


class TestChannelFailurePaths:
    """Payload reassembly must be idempotent: the FSI recv loops key every
    write by global row id and every completion by (src, seq), so redelivered
    or reordered chunks change nothing but billing noise.  Both drain paths
    — the per-worker loops and the fleet drain's one vectorized scatter —
    run the same fault fabrics (they share ``_queue_drain_one`` /
    ``_object_drain_one``, and this parametrization keeps it that way)."""

    P = 3

    @pytest.fixture(scope="class")
    def case(self):
        net = make_sparse_dnn(64, n_layers=2, seed=5)
        x0 = make_inputs(64, 12, seed=6)
        partition = partition_network(net.layers, self.P, method="hgp", seed=0)
        plans = build_comm_plans(net.layers, partition)
        artifacts = prepare_worker_artifacts(net.layers, partition, plans)
        return net, x0, artifacts, dense_inference(net, x0)

    def _run(self, case, channel, fabric, drain="perworker", ledger=False,
             eager=False):
        net, x0, artifacts, _ = case
        compute = ComputeModel()
        workers = [WorkerState(rank=m, memory_mb=2000,
                               ledger=(EventLedger(eager_poll=eager)
                                       if ledger else None))
                   for m in range(self.P)]
        self._last_workers = workers
        panels = [x0[artifacts[m].x0_rows].astype(np.float32)
                  for m in range(self.P)]
        for k in range(net.n_layers):
            arts = [artifacts[m].layers[k] for m in range(self.P)]
            bufs = []
            for m in range(self.P):
                if channel == "queue":
                    bufs.append(fsi_queue_send_and_local(
                        arts[m], panels[m], workers[m], fabric, compute))
                else:
                    bufs.append(fsi_object_send_and_local(
                        arts[m], panels[m], workers[m], fabric, compute,
                        max_object_part=1600))
            if drain == "fleet":
                fb = FleetRecvBuffers.allocate(arts, panels[0].shape[1])
                for m in range(self.P):
                    fb.views[m][:] = bufs[m]
                if channel == "queue":
                    bufs = fsi_queue_recv_fleet(arts, fb, workers, fabric,
                                                compute)
                else:
                    bufs = fsi_object_recv_fleet(arts, fb, workers, fabric,
                                                 compute)
                for m in range(self.P):
                    panels[m] = finish_layer(arts[m], bufs[m], workers[m],
                                             compute, net.bias)
            else:
                for m in range(self.P):
                    if channel == "queue":
                        bufs[m] = fsi_queue_recv(arts[m], bufs[m], workers[m],
                                                 fabric, compute)
                    else:
                        bufs[m] = fsi_object_recv(arts[m], bufs[m], workers[m],
                                                  fabric, compute)
                    panels[m] = finish_layer(arts[m], bufs[m], workers[m],
                                             compute, net.bias)
        order = np.argsort(np.concatenate(
            [artifacts[m].layers[-1].out_rows for m in range(self.P)]))
        return np.concatenate(panels)[order]

    @pytest.mark.parametrize("drain", ["perworker", "fleet"])
    @pytest.mark.parametrize("fault", sorted(QUEUE_FAULTS))
    def test_queue_reassembly_idempotent(self, case, fault, drain):
        fabric = QUEUE_FAULTS[fault](self.P, pricing=SMALL_PRICING)
        out = self._run(case, "queue", fabric, drain=drain)
        np.testing.assert_allclose(out, case[3], rtol=1e-4, atol=1e-4)

    @pytest.mark.parametrize("drain", ["perworker", "fleet"])
    @pytest.mark.parametrize("fault", sorted(OBJECT_FAULTS))
    def test_object_reassembly_idempotent(self, case, fault, drain):
        fabric = OBJECT_FAULTS[fault](self.P)
        out = self._run(case, "object", fabric, drain=drain)
        np.testing.assert_allclose(out, case[3], rtol=1e-4, atol=1e-4)

    def test_queue_faulty_fabric_drains_identical_across_paths(self, case):
        """Same duplicate+out-of-order fabric state, drained per-worker vs
        fleet: identical buffers AND identical billing counters — the
        (src, seq) dedupe lives in one shared loop."""
        results = {}
        for mode in ("perworker", "fleet"):
            fabric = DuplicatingReorderingQueueFabric(
                self.P, pricing=SMALL_PRICING)
            out = self._run(case, "queue", fabric, drain=mode)
            results[mode] = (out, dict(vars(fabric.metrics)))
        np.testing.assert_array_equal(results["perworker"][0],
                                      results["fleet"][0])
        assert results["perworker"][1] == results["fleet"][1]

    def test_queue_duplicate_of_first_chunk_does_not_retire_source(self, case):
        """Deterministic repro of the premature-retirement hazard: the first
        chunk of a two-chunk send is delivered twice BEFORE the second chunk
        arrives.  Naive per-source counting would hit ``total`` on the
        duplicate and drop the second chunk's rows; (src, seq) dedupe in
        ``fsi_queue_recv`` must keep the source pending."""
        from repro.faas.payload import pack_rows

        net, x0, artifacts, _ = case
        compute = ComputeModel()
        # find a (worker, layer, src) pair with a real transfer
        m, k, src = next(
            (m, k, src)
            for m in range(self.P)
            for k in range(net.n_layers)
            for src in artifacts[m].layers[k].recv_expect
        )
        art = artifacts[m].layers[k]
        src_art = artifacts[src].layers[k]
        rows = src_art.send_global[m]
        rng = np.random.default_rng(0)
        vals = rng.standard_normal((len(rows), 4)).astype(np.float32) + 1.0
        # force ≥ 2 chunks, then deliver [c0, c0, c1] in that order
        cap = max(128, (4 + 16) * (len(rows) // 2 + 1))
        chunks = pack_rows(k, src, rows, vals, cap)
        while len(chunks) < 2 and cap > 64:
            cap //= 2
            chunks = pack_rows(k, src, rows, vals, cap)
        assert len(chunks) >= 2, "case too small to split"
        fabric = QueueFabric(self.P, pricing=SMALL_PRICING)
        fabric.publish_batch(0, [(m, chunks[0])], at_time=0.0)
        fabric.publish_batch(0, [(m, chunks[0])], at_time=1.0)
        for i, c in enumerate(chunks[1:], start=2):
            fabric.publish_batch(0, [(m, c)], at_time=float(i))
        # a recv map reduced to this single source
        art_single = dataclasses.replace(
            art,
            recv_expect={src: art.recv_expect[src]},
            backend_states={},
        )
        worker = WorkerState(rank=m, memory_mb=2000)
        x_buf = np.zeros((len(art.needed_rows), 4), np.float32)
        x_buf = fsi_queue_recv(art_single, x_buf, worker, fabric, compute)
        pos = np.searchsorted(art.needed_rows, rows)
        np.testing.assert_array_equal(x_buf[pos], vals)

    # ---- overlapped-ledger coverage (drains interleaved with compute) ------

    @pytest.mark.parametrize("drain", ["perworker", "fleet"])
    @pytest.mark.parametrize("fault", sorted(QUEUE_FAULTS))
    def test_queue_faults_under_overlap_ledger(self, case, fault, drain):
        """Same fault fabrics with event-ledger workers: the (src, seq)
        dedupe must stay exact when the ledger re-times drains against
        in-flight sends, and the ledger timelines must come out sane (a
        redelivered stale chunk may only push the channel timeline forward,
        never unwind it)."""
        fabric = QUEUE_FAULTS[fault](self.P, pricing=SMALL_PRICING)
        out = self._run(case, "queue", fabric, drain=drain, ledger=True)
        np.testing.assert_allclose(out, case[3], rtol=1e-4, atol=1e-4)
        for w in self._last_workers:
            assert w.ledger.t_compute >= 0.0 and w.ledger.t_channel >= 0.0
            # overlapping can only remove serialization, never add work
            assert w.ledger.done <= w.abs_time + 1e-9

    @pytest.mark.parametrize("fault", sorted(OBJECT_FAULTS))
    def test_object_faults_under_overlap_ledger(self, case, fault):
        fabric = OBJECT_FAULTS[fault](self.P)
        out = self._run(case, "object", fabric, drain="fleet", ledger=True)
        np.testing.assert_allclose(out, case[3], rtol=1e-4, atol=1e-4)

    @pytest.mark.parametrize("drain", ["perworker", "fleet"])
    @pytest.mark.parametrize("fault", sorted(QUEUE_FAULTS))
    def test_queue_faults_under_eager_polling(self, case, fault, drain):
        """PR 9: eager polling re-times ledger receives against the faulty
        fabrics' redelivered/reordered stamps.  Outputs must stay exact, and
        every fabric counter must be bit-identical to the lazy-ledger run —
        eager is a ledger-only re-timing even when deliveries misbehave."""
        results = {}
        for eager in (False, True):
            fabric = QUEUE_FAULTS[fault](self.P, pricing=SMALL_PRICING)
            out = self._run(case, "queue", fabric, drain=drain, ledger=True,
                            eager=eager)
            results[eager] = (out, dict(vars(fabric.metrics)),
                              [w.ledger.done for w in self._last_workers])
        np.testing.assert_allclose(results[True][0], case[3],
                                   rtol=1e-4, atol=1e-4)
        np.testing.assert_array_equal(results[True][0], results[False][0])
        assert results[True][1] == results[False][1]   # counters identical
        # the eager reader can only see a chunk sooner, never later
        for e, l in zip(results[True][2], results[False][2]):
            assert e <= l + 1e-9

    @pytest.mark.parametrize("fault", sorted(OBJECT_FAULTS))
    def test_object_faults_under_eager_polling(self, case, fault):
        results = {}
        for eager in (False, True):
            fabric = OBJECT_FAULTS[fault](self.P)
            out = self._run(case, "object", fabric, drain="fleet",
                            ledger=True, eager=eager)
            results[eager] = (out, dict(vars(fabric.metrics)))
        np.testing.assert_allclose(results[True][0], case[3],
                                   rtol=1e-4, atol=1e-4)
        np.testing.assert_array_equal(results[True][0], results[False][0])
        assert results[True][1] == results[False][1]   # counters identical

    def test_queue_fault_billing_unchanged_by_ledger(self, case):
        """Attaching ledgers must not change a single fabric counter — the
        ledger is pure arithmetic riding along the phased schedule."""
        results = {}
        for with_ledger in (False, True):
            fabric = DuplicatingReorderingQueueFabric(
                self.P, pricing=SMALL_PRICING)
            out = self._run(case, "queue", fabric, drain="fleet",
                            ledger=with_ledger)
            results[with_ledger] = (out, dict(vars(fabric.metrics)))
        np.testing.assert_array_equal(results[False][0], results[True][0])
        assert results[False][1] == results[True][1]


class TestLmPipelineChannelFailures:
    """PR 7 satellite: the LM pipeline's activation hops and token loopback
    reuse the FSI drain loops, so (src, seq) dedupe + the monotone hop-tag
    stale-drop must keep tokens/logits exact under duplicate and reordered
    delivery on both fabrics — with the overlap ledger staying sane."""

    P = 3

    @pytest.fixture(scope="class")
    def lm_case(self):
        from repro.configs.base import get_config
        from repro.faas.lm_pipeline import build_stage_executors
        from repro.serving.engine import ServingEngine

        cfg = get_config("internlm2-1.8b").reduced()
        rng = np.random.default_rng(11)
        prompts = rng.integers(0, cfg.vocab_size, (2, 8), dtype=np.int32)
        engine = ServingEngine(cfg, seed=0)
        ref = engine.generate(prompts, max_new_tokens=2)
        executors = build_stage_executors(cfg, engine.params, self.P)
        return cfg, prompts, engine.params, ref, executors

    def _run(self, lm_case, channel, fabric):
        from repro.faas.lm_pipeline import run_lm_pipeline

        cfg, prompts, params, _, executors = lm_case
        return run_lm_pipeline(cfg, prompts, params, max_new_tokens=2,
                               P=self.P, channel=channel,
                               executors=executors, fabric=fabric)

    def _check(self, r, ref, ledger_bound=True):
        np.testing.assert_array_equal(r.tokens, ref.tokens)
        np.testing.assert_allclose(r.logits, ref.prefill_logits, atol=3e-2)
        if ledger_bound:
            # redelivery may only push clocks forward, never unwind them
            assert r.metrics["overlap_makespan_s"] <= \
                r.metrics["phased_makespan_s"] + 1e-9

    @pytest.mark.parametrize("fault", sorted(QUEUE_FAULTS))
    def test_queue_faults_keep_pipeline_exact(self, lm_case, fault):
        # tiny payload cap forces multi-chunk prefill hops, so chunk
        # reordering/duplication has something to corrupt
        fabric = QUEUE_FAULTS[fault](self.P, pricing=SMALL_PRICING)
        self._check(self._run(lm_case, "queue", fabric), lm_case[3])

    @pytest.mark.parametrize("fault", sorted(OBJECT_FAULTS))
    def test_object_faults_keep_pipeline_exact(self, lm_case, fault):
        fabric = OBJECT_FAULTS[fault](self.P)
        # the duplicating object fabric stamps its redelivery +0.5s on the
        # LEDGER timeline only (same asymmetry the FSI object-fault test
        # accepts), so the ledger ≤ phased bound is out of scope here
        self._check(self._run(lm_case, "object", fabric), lm_case[3],
                    ledger_bound=(fault != "duplicate"))

    def test_duplicates_change_billing_not_results(self, lm_case):
        """At-least-once delivery doubles what the FABRIC carries (raw bytes
        exactly 2x: every publish re-published), but the receive-side
        (src, seq) dedupe retires every duplicate — tokens and logits match
        the clean run bit-for-bit, and only billing grows."""
        clean = self._run(lm_case, "queue",
                          QueueFabric(self.P, pricing=SMALL_PRICING))
        noisy = self._run(lm_case, "queue",
                          DuplicatingQueueFabric(self.P,
                                                 pricing=SMALL_PRICING))
        np.testing.assert_array_equal(clean.tokens, noisy.tokens)
        np.testing.assert_array_equal(clean.logits, noisy.logits)
        assert noisy.raw_exchange_bytes == 2 * clean.raw_exchange_bytes
        assert noisy.stats.publish_units == 2 * clean.stats.publish_units
        assert noisy.stats.sqs_api_calls >= clean.stats.sqs_api_calls


class TestStragglersUnderOverlap:
    """Straggler slowdown + re-invoke must work when the reported clocks come
    from the overlapped ledger: charge counts stay bit-identical to the
    phased oracle and the output still matches the dense reference."""

    def _case(self):
        net = make_sparse_dnn(128, n_layers=4, seed=7)
        x0 = make_inputs(128, 16, seed=8)
        return net, x0, dense_inference(net, x0)

    def test_reinvoke_stragglers_overlap_vs_phased(self):
        net, x0, oracle = self._case()
        lat = LatencyModel(straggler_prob=0.5, straggler_slowdown=6.0)
        runs = {
            ov: run_fsi(net, x0, P=4, channel="queue", memory_mb=3000,
                        latency=lat, reinvoke_stragglers=True,
                        straggler_timeout=2.0, overlap=ov)
            for ov in (True, False)
        }
        a, b = runs[True], runs[False]
        np.testing.assert_allclose(a.output, oracle, rtol=1e-4, atol=1e-4)
        np.testing.assert_array_equal(a.output, b.output)
        assert vars(a.stats).keys() == vars(b.stats).keys()
        for f, va in vars(a.stats).items():
            if f == "mean_runtime_s":
                continue  # durations legitimately differ between clock models
            assert va == vars(b.stats)[f], f
        assert a.metrics == b.metrics
        assert a.makespan <= b.makespan + 1e-12

    def test_straggler_slowdown_dilates_overlap_makespan(self):
        # at this scale a layer's compute is ~µs against ~40ms channel hops,
        # so the slowdown must be extreme before it can dominate the ledger
        net, x0, _ = self._case()
        base = run_fsi(net, x0, P=4, channel="queue", memory_mb=3000)
        lat = LatencyModel(straggler_prob=0.9, straggler_slowdown=5e4)
        slow = run_fsi(net, x0, P=4, channel="queue", memory_mb=3000,
                       latency=lat)
        assert slow.makespan > base.makespan
