"""The plain references against the program at a small size on the CPU:
the copied Graph Challenge generator and oracle bit for bit, and the f32
decoder reference against ``ServingEngine`` prefill and decode logits."""

import numpy as np
import pytest

from bench import traffic_gen
from bench.refs import dense_decoder, graphchallenge
from bench.systems.dense_decoder import make_params, program_config
from bench.tests import tiny


@pytest.mark.parametrize("neurons", [64, 1024])
def test_butterfly_and_oracle_match_the_program(neurons):
    """The copied butterfly, with the windows where the repository's
    generator puts them (3 bits further each layer), and the oracle equal
    the repository's bit for bit."""
    from repro.data.graphchallenge import dense_inference, make_sparse_dnn

    cfg = dict(tiny.GC, neurons=neurons, layers=12)
    room = int(np.log2(neurons)) - 4
    ours = [graphchallenge.butterfly_cols(neurons, (3 * k) % room)
            for k in range(12)]
    theirs = make_sparse_dnn(neurons, n_layers=12, seed=3, mode="radix")
    for cols, W in zip(ours, theirs.layers):
        assert np.array_equal(cols.reshape(-1), W.indices)
        assert np.array_equal(np.diff(W.indptr), np.full(neurons, 32))
        assert (W.data == np.float32(cfg["weight"])).all()
    assert theirs.bias == cfg["bias"]
    x0 = traffic_gen.fsi_inputs({"batch": 8, "density": 0.3}, 11, 0, neurons)
    assert np.array_equal(graphchallenge.dense_inference(cfg, ours, x0),
                          dense_inference(theirs, x0))


@pytest.mark.parametrize("neurons,offsets", [
    (64, [0, 1]), (1024, [0, 5]), (4096, [0, 5, 7]),
    (65536, [0, 5, 10, 11])])
def test_radix_net_mixes_every_bit(neurons, offsets):
    """The benchmark's net puts the window on each 5-bit digit in turn, so
    that within one round every output depends on every input."""
    assert graphchallenge.radix_offsets(neurons, 2 * len(offsets)) \
        == offsets * 2
    if neurons > 4096:
        return
    reach = np.eye(neurons, dtype=bool)
    for o in offsets:
        cols = graphchallenge.butterfly_cols(neurons, o)
        reach = reach[cols].any(axis=1)
    assert reach.all()


def test_decoder_reference_matches_serving_engine_f32():
    """With f32 weights and every product at full precision, the program's
    prefill and decode steps agree with the reference's full forward pass
    to f32 rounding: the greedy tokens are the reference's argmax and the
    last step's logits match."""
    import jax
    import jax.numpy as jnp

    from repro.serving.engine import ServingEngine

    cfg = tiny.LM
    params = make_params(cfg, seed=5)
    p32 = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    prompts = traffic_gen.rng_for(5, 9).integers(
        0, cfg["vocab_size"], (2, 12)).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        eng = ServingEngine(program_config(cfg), params=p32,
                            attn_backend="dense-ref")
        gen = eng.generate(prompts, max_new_tokens=5)
    d = dense_decoder.dims(cfg)
    for b in range(2):
        seq = np.concatenate([prompts[b], gen.tokens[b]])
        gaps = dense_decoder.served_token_gaps(p32, cfg, prompts[b],
                                               gen.tokens[b], 24)
        assert gaps.max() <= 1e-5, gaps
        h, W, A = dense_decoder.forward(p32, jnp.asarray(seq), d)
        logits = np.asarray(dense_decoder._logits(p32, h, d, W, A))
        np.testing.assert_allclose(gen.prefill_logits[b, :d["V"]],
                                   logits[-1], atol=2e-5, rtol=2e-5)


def test_served_bf16_tokens_sit_near_the_reference_top():
    """The served (bf16) path's greedy tokens against the f32 reference:
    small gaps, and an out-of-vocabulary token reads as infinite."""
    from repro.serving.engine import ServingEngine

    cfg = tiny.LM
    params = make_params(cfg, seed=6)
    prompts = traffic_gen.rng_for(6, 9).integers(
        0, cfg["vocab_size"], (1, 10)).astype(np.int32)
    gen = ServingEngine(program_config(cfg), params=params,
                        attn_backend="dense-ref").generate(
                            prompts, max_new_tokens=6)
    gaps = dense_decoder.served_token_gaps(params, cfg, prompts[0],
                                           gen.tokens[0], 24)
    assert gaps.max() < 0.1, gaps
    bad = gen.tokens[0].copy()
    bad[2] = cfg["vocab_size"]
    assert np.isinf(dense_decoder.served_token_gaps(
        params, cfg, prompts[0], bad, 24)).all()
