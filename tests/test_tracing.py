"""The program's spans and counters: the fleet call and the serving loop
traced on the CPU, the trace read back with ``jax.profiler.ProfileData``.

Names, counts, nesting and identifiers of the host spans; the scheduler's
decode-position counters, and the per-step positions its ``serve.step``
spans carry, against a hand count; and results bitwise equal
with the profiler on and off."""

from __future__ import annotations

import dataclasses
from collections import Counter, defaultdict
from pathlib import Path

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from repro.configs import get_config  # noqa: E402
from repro.data.graphchallenge import make_inputs, make_sparse_dnn  # noqa: E402
from repro.faas.simulator import (  # noqa: E402
    FaultPlan,
    clear_fleet_cache,
    fleet_cache_info,
    run_fsi,
)
from repro.serving.engine import ServingEngine  # noqa: E402
from repro.serving.scheduler import Request, RequestScheduler  # noqa: E402

N_LAYERS = 4


def traced(fn, trace_dir):
    """``fn()`` under the profiler; returns its result and the program's
    spans, by host thread: ``(name, start, end, stats)``."""
    jax.profiler.start_trace(str(trace_dir))
    try:
        out = fn()
    finally:
        jax.profiler.stop_trace()
    (path,) = Path(trace_dir).rglob("*.xplane.pb")
    pd = jax.profiler.ProfileData.from_file(str(path))
    threads = defaultdict(list)
    for plane in pd.planes:
        if plane.name != "/host:CPU":
            continue
        for i, line in enumerate(plane.lines):
            for e in line.events:
                if e.name.startswith(("fsi.", "payload.", "serve.")):
                    threads[i].append((e.name, e.start_ns,
                                       e.start_ns + e.duration_ns,
                                       dict(e.stats)))
    return out, threads


def only_thread(threads):
    (spans,) = threads.values()
    return spans


def inside(spans, child: str, parent: str) -> bool:
    """Every ``child`` span lies within some ``parent`` span."""
    parents = [(a, b) for n, a, b, _ in spans if n == parent]
    kids = [(a, b) for n, a, b, _ in spans if n == child]
    return bool(kids) and all(any(pa <= a and b <= pb for pa, pb in parents)
                              for a, b in kids)


@pytest.fixture(scope="module")
def gc_case():
    # Called with a random partition below: the hypergraph partitioner
    # cuts this small net nowhere, and then no worker sends.
    return make_sparse_dnn(128, n_layers=N_LAYERS, seed=0), \
        make_inputs(128, 8, seed=1)


@pytest.mark.parametrize("channel,cached", [
    ("queue", False), ("object", False), ("queue", True),
], ids=["queue", "object", "queue-cached"])
def test_fleet_call_spans(gc_case, channel, cached, tmp_path):
    """A cold call, and one that finds the net's prepared fleet: the
    preparation spans open once either way, ``fsi.prepare`` says which."""
    net, x0 = gc_case
    call = lambda: run_fsi(net, x0, P=4, channel=channel,  # noqa: E731
                           memory_mb=2000, partition_method="random")
    clear_fleet_cache()
    if cached:
        call()
    res, threads = traced(call, tmp_path)
    assert fleet_cache_info().hits == int(cached)
    spans = only_thread(threads)
    n = Counter(name for name, *_ in spans)
    assert n["fsi.call"] == 1
    for once in ("fsi.partition", "fsi.plans", "fsi.prepare"):
        assert n[once] == 1, once
    for once in ("fsi.partition", "fsi.plans", "fsi.prepare"):
        assert inside(spans, once, "fsi.call"), once
    assert [st["cached"] for name, _, _, st in spans
            if name == "fsi.prepare"] == [int(cached)]
    assert n["fsi.layer"] == N_LAYERS
    for per_layer in ("fsi.apply", "fsi.finish"):
        assert n[per_layer] == N_LAYERS, per_layer
    for some in ("fsi.send", "fsi.local", "fsi.recv", "fsi.publish",
                 "payload.compress", "payload.decompress"):
        assert n[some] >= N_LAYERS, some
    assert sorted(st["layer"] for name, _, _, st in spans
                  if name == "fsi.layer") == list(range(N_LAYERS))
    for child, parent in [("payload.compress", "fsi.send"),
                          ("fsi.publish", "fsi.send"),
                          ("fsi.send", "fsi.layer"),
                          ("fsi.local", "fsi.layer"),
                          ("payload.decompress", "fsi.recv"),
                          ("fsi.recv", "fsi.layer"),
                          ("fsi.apply", "fsi.layer"),
                          ("fsi.finish", "fsi.layer"),
                          ("fsi.layer", "fsi.call"),
                          ("fsi.prepare", "fsi.call")]:
        assert inside(spans, child, parent), (child, parent)

    untraced = call()
    np.testing.assert_array_equal(res.output, untraced.output)
    assert res.metrics == untraced.metrics
    assert res.wire_exchange_bytes == untraced.wire_exchange_bytes


@pytest.mark.parametrize("path", [
    {"channel": "queue", "channel_batching": False},
    {"channel": "object", "faults": FaultPlan(kills=((1, 2, "send"),))},
], ids=["per-worker", "chaos"])
def test_fleet_call_per_worker_path_spans(gc_case, path, tmp_path):
    """Without fleet batching, and on the crash-fault path, each worker
    sends, overlaps and drains on its own, under the same names."""
    net, x0 = gc_case
    _, threads = traced(lambda: run_fsi(net, x0, P=4, memory_mb=2000,
                                        partition_method="random", **path),
                        tmp_path)
    spans = only_thread(threads)
    n = Counter(name for name, *_ in spans)
    assert n["fsi.layer"] == N_LAYERS
    assert n["fsi.apply"] == N_LAYERS
    for per_worker in ("fsi.send", "fsi.local", "fsi.recv"):
        assert n[per_worker] == 4 * N_LAYERS, per_worker
    for child, parent in [("payload.compress", "fsi.send"),
                          ("fsi.send", "fsi.layer"),
                          ("fsi.recv", "fsi.layer"),
                          ("fsi.apply", "fsi.layer"),
                          ("fsi.finish", "fsi.layer")]:
        assert inside(spans, child, parent), (child, parent)


@pytest.fixture(scope="module")
def engine():
    return ServingEngine(get_config("internlm2-1.8b").reduced(), seed=0)


# Three requests through two slots: the third waits for the second to
# retire.  (rid, prompt length, new tokens)
STREAM = [(0, 3, 3), (1, 5, 2), (2, 4, 4)]


def make_scheduler(engine):
    cap = engine.cache_layout(16).padded_len(16)
    return RequestScheduler(engine.model, engine.params, engine._prefill,
                            num_slots=2, slot_capacity=cap,
                            layout=engine.cache_layout(16))


def stream(engine):
    rng = np.random.default_rng(7)
    return [Request(rid=rid, prompt=rng.integers(
                0, engine.cfg.vocab_size, size=(s,)).astype(np.int32),
                    max_new_tokens=new)
            for rid, s, new in STREAM]


def test_scheduler_spans_and_counters(engine, tmp_path):
    sched = make_scheduler(engine)
    results, threads = traced(lambda: sched.run(stream(engine)), tmp_path)
    spans = only_thread(threads)
    n = Counter(name for name, *_ in spans)
    assert n["serve.run"] == 1
    assert n["serve.step"] == sched.steps_run
    assert n["serve.token_wait"] == sched.steps_run
    for per_request in ("serve.admit", "serve.prefill", "serve.pool_admit",
                        "serve.retire"):
        assert n[per_request] == len(STREAM), per_request
    for name in ("serve.admit", "serve.prefill", "serve.retire"):
        assert sorted(st["rid"] for nm, _, _, st in spans if nm == name) \
            == [0, 1, 2], name
    steps = [st for nm, _, _, st in spans if nm == "serve.step"]
    assert [st["step"] for st in steps] == list(range(sched.steps_run))
    for child, parent in [("serve.prefill", "serve.admit"),
                          ("serve.pool_admit", "serve.admit"),
                          ("serve.admit", "serve.run"),
                          ("serve.step", "serve.run"),
                          ("serve.token_wait", "serve.run"),
                          ("serve.retire", "serve.run")]:
        assert inside(spans, child, parent), (child, parent)

    # Hand count.  Step 0: r0 attends over 3 + 1, r1 over 5 + 1.  Step 1:
    # 3 + 2 and 5 + 2; r1 retires.  Step 2: r2 takes its slot, 3 + 3 and
    # 4 + 1; r0 retires.  Steps 3-5: r2 alone, 4 + 2, 4 + 3, 4 + 4.
    assert sched.steps_run == 6
    assert [st["valid"] for st in steps] == [4 + 6, 5 + 7, 6 + 5, 6, 7, 8]
    assert {st["capacity"] for st in steps} == {2 * sched.slot_capacity}
    assert sched.decode_positions == (4 + 6) + (5 + 7) + (6 + 5) + 6 + 7 + 8
    assert sched.capacity_positions == 6 * 2 * sched.slot_capacity
    # dense-ref reads each slot's whole table on the paged step
    assert sched.paged_steps == sched.steps_run
    assert [st["read"] for st in steps] == [2 * sched.slot_capacity] * 6
    assert sched.read_positions == sched.capacity_positions
    assert sched.tokens_emitted == 3 + 2 + 4

    untraced = make_scheduler(engine).run(stream(engine))
    for a, b in zip(sorted(results, key=lambda r: r.rid),
                    sorted(untraced, key=lambda r: r.rid)):
        np.testing.assert_array_equal(a.tokens, b.tokens)
        np.testing.assert_array_equal(a.final_logits, b.final_logits)


def test_decode_step_scopes(engine):
    """The jitted step carries the pool, decode and sampling scopes as op
    metadata, which the device trace reports per operation.  The dense
    family's step is paged: it writes the new token under
    ``serve.pool_scatter`` and gathers nothing."""
    sched = make_scheduler(engine)
    args = (sched.params, sched._tokens, sched._resident, sched.pool.buffers,
            sched._tables_dev, sched._active_dev)
    text = sched._step_fn.lower(*args).as_text(debug_info=True)
    for scope in ("serve.pool_scatter", "serve.decode", "serve.sample"):
        assert scope in text, scope
    assert "serve.pool_gather" not in text
    # the gathering step (the other families, the sharded variant)
    gather = dataclasses.replace(engine.model, decode_paged=None)
    sched = RequestScheduler(gather, engine.params, engine._prefill,
                             num_slots=2, slot_capacity=sched.slot_capacity,
                             layout=sched.layout)
    args = (sched.params, sched._tokens, sched._resident, sched.pool.buffers,
            sched._tables_dev, sched._active_dev)
    text = sched._step_fn.lower(*args).as_text(debug_info=True)
    for scope in ("serve.pool_gather", "serve.pool_scatter", "serve.decode",
                  "serve.sample"):
        assert scope in text, scope


def test_moe_step_scopes():
    """deepseek-moe's paged step: the expert layer's route, experts and
    combine scopes beside the step's own, and no gather."""
    engine = ServingEngine(get_config("deepseek-moe-16b").reduced(), seed=1)
    sched = make_scheduler(engine)
    args = (sched.params, sched._tokens, sched._resident, sched.pool.buffers,
            sched._tables_dev, sched._active_dev)
    text = sched._step_fn.lower(*args).as_text(debug_info=True)
    for scope in ("moe.route", "moe.experts", "moe.combine", "serve.decode",
                  "serve.pool_scatter"):
        assert scope in text, scope
    assert "serve.pool_gather" not in text
