"""The reduction of the program's spans and device scopes, on traces built
by hand that mix program spans with Python-frame events."""

import pytest

from bench import span_reduce as sr
from bench import trace_reduce as tr
from bench.trace_reduce import Event


def hand_trace():
    # Window [0, 100] ns.  Device busy [10, 20], [40, 45], [90, 120].
    ops = [Event("fusion.1", 10, 20), Event("copy.2", 40, 45),
           Event("while.3", 90, 120)]
    host = [Event("bench.window", 0, 100),
            Event("fsi.call", -10, 60),          # begins before the window
            Event("fsi.layer", 5, 30),
            Event("fsi.send", 6, 28),
            Event("payload.compress", 7, 9),
            Event("__unknown__compress", 7, 9),  # a Python frame inside
            Event("payload.compress", 8, 12),    # overlaps the first
            Event("_fsi.py:389__fleet_local_overlap", 21, 27),
            Event("fsi.layer", 30, 55),
            Event("fsi.apply", 31, 50),
            Event("payload.compress", 56, 59),   # between the layers
            Event("fsi.call", 60, 130),          # runs past the window
            Event("_array.py:631__value", 62, 75),
            Event("fsi.finish", 78, 88)]
    other = [Event("fsi.call", 0, 100)]          # another thread
    return tr.Trace({"/device:TPU:0": ops}, {"/device:TPU:0": []},
                    {"python#0": host, "other#1": other})


def test_span_seconds_union_clipped_to_window():
    s = sr.span_seconds(hand_trace())
    assert s["fsi.call"] == pytest.approx(100e-9)       # [0,60] + [60,100]
    assert s["payload.compress"] == pytest.approx(8e-9)  # [7,12] + [56,59]
    assert s["fsi.layer"] == pytest.approx(50e-9)
    assert s["bench.window"] == pytest.approx(100e-9)
    assert "__unknown__compress" not in s


def test_span_seconds_under_another_span():
    t = hand_trace()
    # the codec's spans of the fleet call alone: [56,59] lies between
    # two calls, [130,140] after the second
    t.host_threads["python#0"] += [Event("payload.compress", 130, 140)]
    t.host_threads["python#0"][0] = Event("bench.window", 0, 150)
    t.host_threads["python#0"][1] = Event("fsi.call", -10, 55)
    s = sr.span_seconds(t, under="fsi.call")
    assert s["payload.compress"] == pytest.approx(5e-9)  # [7,12]
    assert s["fsi.call"] == pytest.approx(125e-9)       # [0,55] + [60,130]
    assert s["fsi.apply"] == pytest.approx(19e-9)
    assert sr.span_seconds(t, under="serve.run") == {}


def test_span_count_counts_starts_in_window():
    c = sr.span_count(hand_trace())
    assert c["fsi.call"] == 1               # the one that began at -10 not
    assert c["payload.compress"] == 3
    assert c["fsi.layer"] == 2
    assert "_array.py:631__value" not in c


def test_idle_by_span_skips_python_frames():
    idle = sr.idle_by_span(hand_trace())
    # gaps [0,10] mid 5 -> fsi.layer (opens at 5); [20,40] mid 30 ->
    # fsi.layer (the second, 30-55); [45,90] mid 67.5 -> fsi.call (60-130),
    # not the Python frame (62-75) inside it.
    assert idle == pytest.approx({"fsi.layer": 30e-9, "fsi.call": 45e-9})
    # trace_reduce names that gap by the frame, and keeps doing so.
    assert tr.reduce(hand_trace(), "bench.window").idle_by_host == \
        pytest.approx({"fsi.layer": 30e-9, "_array.py:631__value": 45e-9})


def test_idle_without_any_program_span():
    t = tr.Trace({"/device:TPU:0": [Event("f", 10, 20)]}, {},
                 {"p#0": [Event("bench.window", 0, 30),
                          Event("_frame", 0, 30)]})
    # the harness's window is itself a program span
    assert sr.idle_by_span(t) == pytest.approx({"bench.window": 20e-9})
    # a window that is not: the gaps no program span covers
    t.host_threads["p#0"] = [Event("window", 0, 30), Event("_frame", 0, 30),
                             Event("serve.step", 25, 30)]
    assert sr.idle_by_span(t, "window") == pytest.approx(
        {sr.NO_SPAN: 10e-9, "serve.step": 10e-9})


def test_scope_seconds_union_of_ops_under_a_scope():
    # A recorded op list: a while and the body ops it runs, both under
    # serve.decode; copies under the pool's scopes, one of them inherited
    # (a relayout the compiler inserted); one op under no scope.
    trace = tr.Trace({}, {}, {"p#0": [Event("bench.window", 0, 100)]})
    ops = [sr.ScopedOp(Event("while.18 (while)", 10, 60), "serve.decode"),
           sr.ScopedOp(Event("fusion.5 (fusion)", 20, 30), "serve.decode"),
           sr.ScopedOp(Event("fusion.87 (fusion)", 0, 8),
                       "serve.pool_gather"),
           sr.ScopedOp(Event("copy.44 (copy)", 62, 70), "serve.pool_gather",
                       inherited=True),
           sr.ScopedOp(Event("fusion.9 (fusion)", 70, 72),
                       "serve.pool_scatter"),
           sr.ScopedOp(Event("copy.45 (copy)", 95, 110),
                       "serve.pool_gather"),
           sr.ScopedOp(Event("reshape.1 (reshape)", 75, 80))]
    st = sr.SpanTrace(trace, {"/device:TPU:0": ops})
    assert sr.scope_seconds(st, sr.DEVICE_SCOPES) == pytest.approx(
        {"serve.decode": 50e-9, "serve.pool_gather": 21e-9,
         "serve.pool_scatter": 2e-9})
    assert sr.scope_seconds(st, sr.DEVICE_SCOPES, inherited=False) == \
        pytest.approx({"serve.decode": 50e-9, "serve.pool_gather": 13e-9,
                       "serve.pool_scatter": 2e-9})
    assert sr.in_scope("jit(step)/serve.sample/argmax", "serve.sample")
    assert not sr.in_scope("jit(step)/serve.samples/argmax", "serve.sample")


def ins(iid, name, op_name="", *operands):
    return sr.Instruction(iid, name, op_name, tuple(operands))


# The decode step's pool relayout as the chip compiles it: the gather's
# fusion, a tuple element and a bitcast of it, the compiler's concatenation
# of two halves and its relayout copy, none of which carry an op_name, then
# the decode loop that reads the copy.
STEP = [ins(1, "buffers.1"),
        ins(2, "tables.1"),
        ins(3, "fusion.87", "jit(step)/serve.pool_gather/gather", 1, 2),
        ins(4, "get-tuple-element.5", "", 3),
        ins(5, "bitcast.4", "", 4),
        ins(6, "pad_maximum_fusion.2", "", 5, 5),
        ins(7, "copy.44", "", 6),
        ins(8, "while.18", "jit(step)/serve.decode/vmap()/while", 7),
        ins(9, "copy.113", "", 1),              # the pool, to the scatter
        ins(10, "fusion.9", "jit(step)/serve.pool_scatter/scatter", 9, 8),
        ins(11, "add.3", "", 3, 8),             # both: no one scope
        ins(12, "constant.1"),                  # nothing either way
        ins(13, "fusion.2", "jit(step)/serve.samples/argmax", 8)]


def test_instruction_scopes_walk_the_data():
    sc = sr.instruction_scopes(STEP, sr.DEVICE_SCOPES)
    assert sc["fusion.87"] == ("serve.pool_gather", False)
    assert sc["while.18"] == ("serve.decode", False)
    for relayout in ("bitcast.4", "pad_maximum_fusion.2", "copy.44"):
        assert sc[relayout] == ("serve.pool_gather", True), relayout
    # nothing scoped behind it: forward, to the scatter that reads it
    assert sc["copy.113"] == ("serve.pool_scatter", True)
    assert sc["tables.1"] == ("serve.pool_gather", True)
    assert sc["buffers.1"] == ("", False)      # read by gather and scatter
    assert sc["add.3"] == ("", False)
    assert sc["constant.1"] == ("", False)
    assert sc["fusion.2"] == ("serve.decode", True)   # not a scope: walks
    # a program with no scope anywhere
    plain = sr.instruction_scopes(STEP[:2] + [ins(3, "fusion.3", "x/add", 1)],
                                  sr.DEVICE_SCOPES)
    assert set(plain.values()) == {("", False)}


def test_scope_ops_by_program_and_instruction():
    by_program = {
        "jit_step(11)": sr.instruction_scopes(STEP, sr.DEVICE_SCOPES),
        "jit_prefill(12)": {"copy.44": ("", False)},
        "jit_other(13)": {"copy.44": ("serve.decode", False)},
        "jit_other(14)": {"copy.44": ("serve.decode", False)}}
    modules = [Event("jit_step(11)", 0, 50), Event("jit_prefill(12)", 50, 80),
               Event("jit_step(99)", 100, 150),   # ids differ: by its name
               Event("jit_other(15)", 200, 250)]  # two such: no match
    ops = [Event("copy.44 (copy)", 10, 20), Event("copy.44 (copy)", 55, 60),
           Event("copy.44 (copy)", 110, 120),
           Event("copy.44 (copy)", 210, 220),
           Event("fusion.9 (fusion)", 85, 90),    # between programs
           Event("while.18 (while)", 20, 45)]
    got = [(o.scope, o.inherited)
           for o in sr.scope_ops(ops, modules, by_program)]
    assert got == [("serve.pool_gather", True), ("", False),
                   ("serve.pool_gather", True), ("", False), ("", False),
                   ("serve.decode", False)]


def test_protobuf_reader_round_trip():
    # A hand-serialized XSpace: one device plane, skipped, and the
    # metadata plane with one program and a stat that is not one.
    def varint(n):
        out = b""
        while True:
            b, n = n & 0x7F, n >> 7
            out += bytes([b | (0x80 if n else 0)])
            if not n:
                return out

    def field(num, value):
        if isinstance(value, int):
            return varint(num << 3) + varint(value)
        return varint(num << 3 | 2) + varint(len(value)) + value

    def instr(iid, name, op_name, operands):
        packed = b"".join(varint(o) for o in operands)
        return (field(1, name.encode()) + field(2, b"copy")
                + field(7, field(1, b"copy") + field(2, op_name.encode()))
                + field(35, iid) + (field(36, packed) if packed else b""))

    comp = field(1, b"main") + field(2, instr(1, "p.1", "", [])) \
        + field(2, instr(2, "copy.2", "jit(step)/serve.decode/x", [1])) \
        + field(2, instr(300, "add.3", "", [1, 2]))
    hlo = field(1, field(1, b"jit_step") + field(3, comp))
    stat_md = field(5, field(1, 7) + field(2, field(1, 7)
                                             + field(2, b"Hlo Proto")))
    other_md = field(5, field(1, 8) + field(2, field(1, 8)
                                              + field(2, b"Other")))
    event_md = field(4, field(1, 1) + field(2, field(1, 1)
                     + field(2, b"jit_step(5)")
                     + field(5, field(1, 7) + field(6, hlo))))
    skip_md = field(4, field(1, 2) + field(2, field(1, 2)
                    + field(2, b"not a program")
                    + field(5, field(1, 8) + field(6, b"\x00\x01"))))
    meta = field(2, b"/host:metadata") + event_md + skip_md + stat_md \
        + other_md
    dev = field(1, 3) + field(2, b"/device:TPU:0") + field(3, b"\x08\x01")
    xspace = field(1, dev) + field(1, meta)
    progs = sr.programs(xspace)
    assert list(progs) == ["jit_step(5)"]
    assert progs["jit_step(5)"] == [
        ins(1, "p.1"), ins(2, "copy.2", "jit(step)/serve.decode/x", 1),
        ins(300, "add.3", "", 1, 2)]


def test_programs_of_a_cpu_trace(tmp_path):
    # The profiler keeps each program it ran, scopes and all, on the CPU
    # as on the chip.
    jax = pytest.importorskip("jax")
    import jax.numpy as jnp

    def f(x, idx):
        with jax.named_scope("serve.pool_gather"):
            y = jnp.take(x, idx, axis=0)
        return (y * 2).sum(0)

    fn = jax.jit(f)
    args = (jnp.ones((64, 128)), jnp.arange(8))
    fn(*args).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    fn(*args).block_until_ready()
    jax.profiler.stop_trace()
    (path,) = tmp_path.rglob("*.xplane.pb")
    progs = {k: v for k, v in sr.programs(path.read_bytes()).items()
             if k.startswith("jit_f(")}
    (instrs,) = progs.values()
    scopes = sr.instruction_scopes(instrs, sr.DEVICE_SCOPES)
    assert any(sr.in_scope(i.op_name, "serve.pool_gather") for i in instrs)
    assert ("serve.pool_gather", False) in scopes.values()


def test_step_positions_in_the_window():
    trace = tr.Trace({}, {}, {"p#0": [Event("bench.window", 10, 100)]})
    st = sr.SpanTrace(trace, {}, [(5.0, {"step": 0, "valid": 7,
                                         "capacity": 10}),
                                  (20.0, {"step": 1, "valid": 8,
                                          "capacity": 10}),
                                  (60.0, {"step": 2, "valid": 1,
                                          "capacity": 10}),
                                  (100.0, {"step": 3, "valid": 9,
                                           "capacity": 10})])
    assert sr.step_positions(st) == (9.0, 20.0)
    # a program whose steps carry no positions
    st.steps = [(20.0, {"step": 1})]
    assert sr.step_positions(st) == (0.0, 0.0)


def test_layer_metrics_fleet_call():
    spans = {"fsi.call": 4.0, "fsi.partition": 0.2, "fsi.plans": 0.3,
             "fsi.prepare": 0.5, "fsi.send": 1.5, "payload.compress": 1.0,
             "fsi.local": 0.25, "fsi.recv": 0.5, "fsi.apply": 0.1}
    m = sr.layer_metrics(spans, {"fsi.call": 2}, {}, {}, busy_s=0.05)
    assert m == pytest.approx({"fsi.prepare_ms": 500.0,
                               "fsi.compress_ms": 500.0,
                               "fsi.channel_ms": 625.0,
                               "fsi.apply_ms": 50.0})


def test_layer_metrics_serving():
    m = sr.layer_metrics(
        {}, {"serve.step": 10},
        {"serve.token_wait": 0.015, "serve.step": 0.005, "serve.admit": 1.0},
        {"serve.pool_gather": 0.3, "serve.pool_scatter": 0.1,
         "serve.decode": 2.0}, busy_s=4.0, positions=(450.0, 1000.0))
    assert m == pytest.approx({"lm.pool_copy_pct": 10.0,
                               "lm.kv_valid_pct": 45.0,
                               "lm.step_gap_ms": 2.0})


def test_layer_metrics_absent_inputs_give_nothing():
    # A trace of a program without spans, scopes or step positions.
    assert sr.layer_metrics({"bench.window": 3.0}, {"bench.window": 1},
                            {"no program span": 1.0}, {}, busy_s=1.0) == {}
    # Calls without the spans inside them, steps without positions.
    assert sr.layer_metrics({"fsi.call": 1.0}, {"fsi.call": 1}, {}, {},
                            busy_s=1.0, positions=(5.0, 0.0)) == {}
