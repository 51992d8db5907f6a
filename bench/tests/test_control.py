"""The control comes out not correct: the plain reference in the program's
place, computed one precision lower, at a size a test run can hold.  On
the chip the same readings are taken at each cell's own size by
``bench/control.py``."""

import contextlib

import pytest

from bench.systems import dense_decoder, graphchallenge
from bench.tests import tiny


def _served(system, config, mix, seed):
    cell = system.Cell(config, mix, seed,
                       lambda name: contextlib.nullcontext())
    cell.setup()
    cell.run_unit()
    cell.release()
    return cell


@pytest.mark.parametrize("seed", [1, 2**31 + 3])
def test_lm_fp8_control_fails_the_token_gap(seed):
    """Six layers at width 1024: the served bf16 tokens sit within the
    limit of the f32 reference's best, the fp8 control's beyond it."""
    limit = tiny.limit("lm_code", "token_gap")
    cell = _served(dense_decoder, tiny.LM_CONTROL, tiny.LM_CONTROL_MIX, seed)
    (check,) = cell.check({"token_gap": limit})
    assert check["value"] <= limit
    assert dense_decoder.control(cell)["control_fp8"] > limit


@pytest.mark.parametrize("seed", [1, 2**31 + 3])
def test_gc_control_readings(seed, device_defaults):
    """N=1024 over 40 layers, a batch of 256: the fleet's outputs equal the
    oracle's; with the oracle's activations rounded to bfloat16, and to
    fp8, some input's outputs cross the point where its path turns."""
    cell = _served(graphchallenge, tiny.GC_CONTROL,
                   tiny.gc_mix("queue", 4, batch=256), seed)
    (check,) = cell.check({"mismatched_outputs": 0})
    assert check["value"] == 0
    readings = graphchallenge.control(cell)
    assert readings["control_bfloat16"] > 0
    assert readings["control_float8_e4m3fn"] > readings["control_bfloat16"]
