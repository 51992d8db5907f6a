"""Whole runs on the CPU at a small size: sound, they come out correct;
with the timed path broken underneath, ``correct`` comes out false.  One
case for each fault a cell can have: a step that returns its state
unchanged, half of the batch left out, the exchange between workers left
out, and a token or an answer altered where it is produced."""

import numpy as np
import pytest

from bench.tests import tiny

LM_CELLS = ["lm_code"]
GC_CELLS = ["gc1k_queue_p4", "gc1k_object_p16"]


@pytest.mark.parametrize("workload", LM_CELLS)
def test_lm_sound_run_is_correct(workload):
    res = tiny.run(workload)
    assert res["correct"] is True, res
    assert res["failed"] == 0 and res["attempted"] > 0
    assert list(res)[-1] == "checks"


@pytest.mark.parametrize("workload", GC_CELLS)
def test_gc_sound_run_is_correct(workload, device_defaults):
    res = tiny.run(workload)
    assert res["correct"] is True, res
    assert res["checks"]["mismatched_outputs"]["value"] == 0.0


def _break_step(monkeypatch, broken):
    from repro.serving.scheduler import RequestScheduler

    build = RequestScheduler._build_step

    def patched(self):
        return broken(build(self))

    monkeypatch.setattr(RequestScheduler, "_build_step", patched)


def test_lm_token_altered(monkeypatch):
    vocab = tiny.LM["vocab_size"]

    def broken(step):
        def s(*args):
            logits, tok, res, buf = step(*args)
            return logits, (tok + 1) % vocab, res, buf
        return s

    _break_step(monkeypatch, broken)
    assert tiny.run("lm_code")["correct"] is False


def test_lm_step_returns_state_unchanged(monkeypatch):
    import jax
    import jax.numpy as jnp

    def broken(step):
        def s(params, tokens, resident, buffers, tables, active):
            keep = jax.tree.map(jnp.copy, (resident, buffers))
            logits, tok, _, _ = step(params, tokens, resident, buffers,
                                     tables, active)
            return (logits, tok) + keep
        return s

    _break_step(monkeypatch, broken)
    assert tiny.run("lm_code")["correct"] is False


def test_lm_half_of_the_batch_left_out(monkeypatch):
    from repro.serving.scheduler import RequestScheduler

    run = RequestScheduler.run
    monkeypatch.setattr(RequestScheduler, "run",
                        lambda self, reqs, **kw: run(
                            self, list(reqs)[: len(reqs) // 2], **kw))
    res = tiny.run("lm_code")
    assert res["correct"] is False and res["failed"] > 0


def _break_fleet_apply(monkeypatch, broken):
    from repro.core.backends import PallasBsrShardedBackend

    apply = PallasBsrShardedBackend.fleet_apply

    def patched(self, state, xs, bias):
        return broken(apply(self, state, xs, bias), xs)

    monkeypatch.setattr(PallasBsrShardedBackend, "fleet_apply", patched)


@pytest.mark.parametrize("workload", GC_CELLS)
def test_gc_answer_altered(workload, device_defaults, monkeypatch):
    def broken(outs, xs):
        outs = [o.copy() for o in outs]
        outs[0][0, 0] += 1.0
        return outs

    _break_fleet_apply(monkeypatch, broken)
    assert tiny.run(workload)["correct"] is False


def test_gc_step_returns_state_unchanged(device_defaults, monkeypatch):
    """Each layer hands on the activations it was given."""
    def broken(outs, xs):
        return [x[: o.shape[0]] if x.shape[0] >= o.shape[0] else o
                for o, x in zip(outs, xs)]

    _break_fleet_apply(monkeypatch, broken)
    assert tiny.run("gc1k_queue_p4")["correct"] is False


@pytest.mark.parametrize("workload,drain", [
    ("gc1k_queue_p4", "fsi_queue_recv_fleet"),
    ("gc1k_object_p16", "fsi_object_recv_fleet")])
def test_gc_exchange_left_out(workload, drain, device_defaults, monkeypatch):
    """Workers drain their channel but drop what it carried."""
    from repro.faas import simulator

    recv = getattr(simulator, drain)

    def broken(arts, bufs, *args):
        before = bufs.flat.copy()
        views = recv(arts, bufs, *args)
        bufs.flat[...] = before
        return views

    monkeypatch.setattr(simulator, drain, broken)
    assert tiny.run(workload)["correct"] is False


def test_gc_half_of_the_batch_left_out(device_defaults, monkeypatch):
    from repro.faas import simulator

    run_fsi = simulator.run_fsi

    def broken(net, x0, **kw):
        half = x0.shape[1] // 2
        res = run_fsi(net, np.ascontiguousarray(x0[:, :half]), **kw)
        out = np.zeros((x0.shape[0], x0.shape[1]), np.float32)
        out[:, :half] = res.output
        res.output = out
        return res

    monkeypatch.setattr(simulator, "run_fsi", broken)
    assert tiny.run("gc1k_queue_p4")["correct"] is False
