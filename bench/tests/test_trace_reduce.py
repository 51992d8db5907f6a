"""The reduction from a trace to busy time, kernel sums and idle gaps, on
a trace built by hand with known answers."""

import pytest

from bench import trace_reduce as tr
from bench.trace_reduce import Event


def hand_trace():
    # window [0, 100] ns on the host; device busy [10, 30] (two overlapping
    # ops), [50, 60], [70, 80] and [95, 110] (clipped to 100).
    ops = [Event("fusion.1", 10, 20), Event("fusion.2", 15, 30),
           Event("copy.3", 50, 60), Event("_fleet_kernel", 70, 80),
           Event("_fleet_kernel", 95, 110), Event("before", -20, -5)]
    modules = [Event("jit_prefill", 10, 30), Event("jit_step", 50, 110)]
    host = [Event("bench.window", 0, 100), Event("bench.fsi_call", 5, 65),
            Event("PjitFunction(step)", 40, 49),
            Event("bench.fsi_call", 66, 100)]
    other = [Event("lock", 0, 200)]
    return tr.Trace({"/device:TPU:0": ops}, {"/device:TPU:0": modules},
                    {"python#0": host, "other#1": other})


def test_busy_union_kernel_sums_and_gaps():
    r = tr.reduce(hand_trace(), "bench.window")
    assert r.window_s == pytest.approx(100e-9)
    # union: 20 + 10 + 10 + 5
    assert r.busy_s == pytest.approx(45e-9)
    assert r.idle_pct == pytest.approx(55.0)
    assert r.op_total(r"_fleet_kernel") == pytest.approx(15e-9)
    assert r.op_total(r"^fusion") == pytest.approx(25e-9)
    assert r.module_total("prefill") == pytest.approx(20e-9)
    assert r.module_total("step") == pytest.approx(50e-9)
    # gaps [0,10] mid 5 -> fsi_call; [30,50] mid 40 -> PjitFunction;
    # [60,70] mid 65 -> fsi_call (covers its end); [80,95] mid 87.5 ->
    # the second fsi_call; never the other thread's span
    assert r.idle_by_host == pytest.approx(
        {"bench.fsi_call": 35e-9, "PjitFunction(step)": 20e-9})
    b = r.breakdown()
    assert b["device_ops"][0][0] == "fusion.2"
    assert b["idle_gaps"][0] == ["bench.fsi_call", pytest.approx(35e-9)]


def test_union_and_gaps():
    assert tr.union([(5, 7), (1, 3), (2, 4), (7, 9), (10, 10)]) == \
        [(1, 4), (5, 9)]
    assert tr.gaps([(1, 4), (5, 9)], 0, 12) == [(0, 1), (4, 5), (9, 12)]
    assert tr.gaps([], 0, 3) == [(0, 3)]


def test_innermost_nested_spans():
    spans = [Event("outer", 0, 100), Event("mid", 10, 50),
             Event("inner", 20, 30), Event("late", 60, 70)]
    assert tr.innermost(spans, [25, 40, 55, 65, 150]) == \
        ["inner", "mid", "outer", "late", None]


CHIP_OPS = {
    # device operations as a v5e trace names them (HLO text, cut short)
    "fleet": '%local.1 = f32[4,256,256]{2,1,0:T(8,128)} custom-call(s32[32]'
             '{0:T(128)S(1)} %reshape.2, f32[4,256,256]{2,1,0:T(8,128)} '
             '%x.1), custom_call_target="tpu_custom_call", '
             'frontend_attributes={kernel_metadata={}}',
    "decode": '%_decode_mha_jit.5 = (bf16[16,1,8,2,128]{4,3,2,1,0}, f32[16,'
              '1,8,2,1]{4,3,2,1,0}) custom-call(s32[16,1,1]{2,1,0} %copy.101'
              '), custom_call_target="tpu_custom_call"',
    "reshape": '%reshape.3 = s32[256]{0:T(256)S(1)} reshape(s32[4,8,8]'
               '{2,1,0:T(8,128)} %cols.1)',
}


def test_short_names_of_chip_operations():
    assert tr.short_name(CHIP_OPS["fleet"]) == \
        "local.1 (custom-call tpu_custom_call)"
    assert tr.short_name(CHIP_OPS["decode"]) == \
        "_decode_mha_jit.5 (custom-call tpu_custom_call)"
    assert tr.short_name(CHIP_OPS["reshape"]) == "reshape.3 (reshape)"
    assert tr.short_name("jit_step(63365928)") == "jit_step(63365928)"


def test_readers_find_the_kernels_and_programs_by_their_chip_names():
    from bench import run_cell

    ops = [Event(tr.short_name(CHIP_OPS["fleet"]), 0, 400),
           Event(tr.short_name(CHIP_OPS["decode"]), 500, 700),
           Event(tr.short_name(CHIP_OPS["reshape"]), 700, 800)]
    modules = [Event("jit__lambda(4238336120293041266)", 0, 400),
               Event("jit_step(6336592859411770175)", 500, 800)]
    host = [Event("bench.window", 0, 1000)]
    trace = tr.Trace({"/device:TPU:0": ops}, {"/device:TPU:0": modules},
                     {"main#0": host})
    peaks = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e9}
    ctx = run_cell._Context(
        trace=tr.reduce(trace, "bench.window"),
        counts={"bsr_flops": 100.0, "bsr_bytes": 100.0,
                "decode_attn_flops": 0.0, "decode_attn_bytes": 50.0,
                "forward_flops": 300.0, "slots": 4.0},
        counters={"steps_run": 10.0, "tokens_emitted": 30.0},
        window_s=1e-6, peaks=peaks)
    read = {m: run_cell._reader(m)(ctx) for m in (
        "bsr_fleet_roofline", "decode_attn_roofline", "lm.prefill_busy_pct",
        "lm.slot_occupancy_pct", "lm.device_idle_pct", "mfu.lm")}
    # 100 B at 1 GB/s is 100 ns of the kernel's 400 ns
    assert read["bsr_fleet_roofline"] == pytest.approx(25.0)
    # 50 B is 50 ns of the kernel's 200 ns
    assert read["decode_attn_roofline"] == pytest.approx(25.0)
    # the prefill program ran 400 of the 700 busy ns
    assert read["lm.prefill_busy_pct"] == pytest.approx(100 * 400 / 700)
    assert read["lm.slot_occupancy_pct"] == pytest.approx(75.0)
    assert read["lm.device_idle_pct"] == pytest.approx(30.0)
    # 300 FLOP in 1 us is 3e8 FLOP/s of a 1e12 peak
    assert read["mfu.lm"] == pytest.approx(0.03)


def test_a_reader_with_nothing_to_read_returns_nothing():
    from bench import run_cell

    trace = tr.Trace({"/device:TPU:0": [Event("fusion.1", 0, 10)]}, {},
                     {"main#0": [Event("bench.window", 0, 20)]})
    ctx = run_cell._Context(trace=tr.reduce(trace, "bench.window"),
                            counts={}, counters={}, window_s=2e-8,
                            peaks={})
    for m in ("bsr_fleet_roofline", "decode_attn_roofline",
              "lm.slot_occupancy_pct"):
        assert run_cell._reader(m)(ctx) is None


def test_missing_window_or_device_is_an_error():
    t = hand_trace()
    with pytest.raises(KeyError):
        tr.reduce(t, "no.such.span")
    with pytest.raises(ValueError):
        tr.reduce(tr.Trace({}, {}, t.host_threads), "bench.window")
