"""The deepseek-moe cell at a small size on the CPU: the plain reference
against the program, whole runs that come out correct, planted faults
that turn ``correct`` false (an expert's part dropped, a renormalised
gate, a token altered), and the fp8 control beyond the limit."""

from __future__ import annotations

import contextlib
import io
import json

import numpy as np
import pytest

from bench import run_cell, traffic_gen
from bench.refs import moe_decoder as ref
from bench.systems import moe_decoder
from bench.tests import tiny

# deepseek-moe's cut at a width (1024) at which logits spread as the real
# model's do, with its proportions: heads of 128, the expert width 0.69 of
# the hidden, 64 routed experts of which 32 are held, top 6 unnormalised,
# 2 shared, a dense first layer.
MOE = {"name": "tiny-moe", "kind": "moe_decoder", "hidden_size": 1024,
       "num_hidden_layers": 3, "num_attention_heads": 8,
       "num_key_value_heads": 8, "intermediate_size": 2048,
       "moe_intermediate_size": 704, "n_routed_experts": 32,
       "published": {"n_routed_experts": 64}, "held_experts": [0, 32],
       "num_experts_per_tok": 6, "n_shared_experts": 2,
       "first_k_dense_replace": 1, "norm_topk_prob": False,
       "vocab_size": 4000, "rope_theta": 10000.0, "rms_norm_eps": 1e-6,
       "tie_word_embeddings": False}
# Answers long enough (8-24 tokens, 60 checked) that a fault shows.
MIX = {"kind": "lm_offline", "requests_per_batch": 6, "num_slots": 4,
       "prompt_len": {"choice": [16, 32]}, "output_len": {"uniform": [8, 24]},
       "capacity": 64, "check_tokens": 60, "check_requests": 4}
# Where the control is read: deeper, so fp8 rounding shows.
MOE_CONTROL = dict(MOE, num_hidden_layers=6)
CONTROL_MIX = MIX
# Where an expert layer's part is dropped: 16 routed experts, 8 held, so
# the top 6 hold most of the gate and the held part most of the layer.
MOE_16 = dict(MOE, n_routed_experts=8, published={"n_routed_experts": 16},
              held_experts=[0, 8])


def run(workload: str, seed: int = 2**31 + 7, config: dict = MOE) -> dict:
    def load(bench, wl):
        limits = json.loads((tiny.ROOT / "bench" / "limits"
                             / f"{wl['name']}.json").read_text())
        if wl["config"] == "deepseek-moe-16b":
            return config, MIX, limits
        return tiny.load(bench, wl)

    out = io.StringIO()
    rc = run_cell.run(tiny.bench(), workload, seed, 0.3, False,
                      require_tpu=False, load=load, out=out)
    assert rc == 0, rc
    return json.loads(out.getvalue().strip().splitlines()[-1])


def test_reference_matches_the_program_f32():
    """With f32 weights and every product at full precision, the program's
    prefill and dropless decode agree with the reference's full forward
    pass to f32 rounding: each greedy token is the reference's argmax."""
    import jax
    import jax.numpy as jnp

    from repro.serving.engine import ServingEngine

    params = moe_decoder.make_params(MOE, seed=5)
    p32 = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    prompts = traffic_gen.rng_for(5, 9).integers(
        0, MOE["vocab_size"], (2, 12)).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        eng = ServingEngine(moe_decoder.program_config(MOE), params=p32,
                            attn_backend="dense-ref")
        gen = eng.generate(prompts, max_new_tokens=5)
    d = ref.dims(MOE)
    for b in range(2):
        gaps = ref.served_token_gaps(p32, MOE, prompts[b], gen.tokens[b], 64)
        assert gaps.max() <= 1e-5, gaps
        seq = np.concatenate([prompts[b], gen.tokens[b]])
        h, W, A = ref.forward(p32, jnp.asarray(seq), d)
        logits = np.asarray(ref._logits(p32, h, d, W, A))
        np.testing.assert_allclose(gen.prefill_logits[b, :d["V"]],
                                   logits[-1], atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("workload,config", [
    ("dsmoe_conv", MOE), ("dsmoe_conv", MOE_16),
    ("dsmoe_conv", MOE_CONTROL), ("lm_conv", None)],
    ids=["dsmoe_conv", "dsmoe_conv-16", "dsmoe_conv-6-layers", "lm_conv"])
def test_sound_run_is_correct(workload, config):
    res = run(workload, config=config)
    assert res["correct"] is True, res
    assert res["failed"] == 0 and res["attempted"] > 0


def test_counters_count_every_held_row():
    """The cell's counters: the held assignments of the window's prefills
    and of its steps' occupied slots, and at most every held expert per
    call."""
    cell = moe_decoder.Cell(MOE, MIX, 3, lambda n: contextlib.nullcontext())
    cell.setup()
    cell.run_unit()
    c = cell.counters()
    prompts, _ = traffic_gen.lm_sizes(MIX)
    n_moe, k = 2, 6
    assert 0 < c["moe_rows"] <= (sum(prompts) + c["tokens_emitted"]) \
        * n_moe * k
    calls = (len(prompts) + c["steps_run"]) * n_moe
    assert 0 < c["moe_groups_read"] <= calls * 32


def _patch_layer(monkeypatch, broken):
    from repro.models import moe

    real = moe.held_part
    monkeypatch.setattr(moe, "held_part",
                        lambda *a, **kw: broken(*real(*a, **kw)))


def test_expert_part_dropped(monkeypatch):
    """The expert layer hands on only the shared experts' output."""
    _patch_layer(monkeypatch, lambda out, counts: (out * 0, counts))
    assert run("dsmoe_conv", config=MOE_16)["correct"] is False


def test_renormalised_gate(monkeypatch):
    """The top 6 of 64 experts hold a small share of the gate:
    renormalising them scales the routed part up, in each of five expert
    layers."""
    real = moe_decoder.program_config
    monkeypatch.setattr(
        moe_decoder, "program_config",
        lambda config: __import__("dataclasses").replace(
            real(config), moe_norm_topk=True))
    assert run("dsmoe_conv", config=MOE_CONTROL)["correct"] is False


def test_token_altered(monkeypatch):
    from repro.serving.scheduler import RequestScheduler

    build = RequestScheduler._build_step

    def patched(self):
        step = build(self)

        def s(*args):
            logits, tok, res, buf = step(*args)
            return logits, (tok + 1) % MOE["vocab_size"], res, buf
        return s

    monkeypatch.setattr(RequestScheduler, "_build_step", patched)
    assert run("dsmoe_conv")["correct"] is False


@pytest.mark.parametrize("seed", [1, 2**31 + 3])
def test_fp8_control_fails_the_token_gap(seed):
    """Six layers at width 1024: the served bf16 tokens sit within the
    limit of the f32 reference's best, the fp8 control's beyond it."""
    limit = tiny.limit("dsmoe_conv", "token_gap")
    cell = moe_decoder.Cell(MOE_CONTROL, CONTROL_MIX, seed,
                            lambda name: contextlib.nullcontext())
    cell.setup()
    cell.run_unit()
    cell.release()
    (check,) = cell.check({"token_gap": limit})
    assert check["value"] <= limit
    assert moe_decoder.control(cell)["control_fp8"] > limit


def test_counts_by_hand():
    """The expert kernel's and the cut's counts against hand arithmetic."""
    from bench import moe_counts

    d = {"L": 2, "D": 4, "H": 2, "KV": 2, "Dh": 2, "F": 8, "f": 3, "E": 8,
         "shared": 2, "first_dense": 1, "V": 10}
    c = moe_counts.experts(d, rows=5, groups_read=2)
    # 6 d f a row; each read expert's three d x f bf16 matrices, each row
    # in and out in bf16
    assert c["moe_gmm_flops"] == 6 * 4 * 3 * 5
    assert c["moe_gmm_bytes"] == 3 * 4 * 3 * 2 * 2 + 5 * 4 * 2 * 2
    # per token: 2 layers' projections 2 * 4 * 16, the dense layer's MLP
    # 2 * 3 * 4 * 8, the expert layer's router 2 * 4 * 8 and shared MLP
    # 2 * 3 * 4 * 6
    per_token = 2 * 2 * 4 * 16 + 2 * 3 * 4 * 8 + 2 * 4 * 8 + 2 * 3 * 4 * 6
    assert moe_counts.decode_token(d, length=3)["forward_flops"] == (
        per_token + 2 * 4 * 2 * 2 * 3 + 2 * 4 * 10)
    assert moe_counts.prefill(d, prompt=3)["forward_flops"] == (
        3 * per_token + 2 * 4 * 2 * 2 * 6 + 2 * 4 * 10)
