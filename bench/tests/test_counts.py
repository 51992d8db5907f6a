"""The operation and byte counts against hand arithmetic."""

import json

import pytest

from bench import counts
from bench.refs import dense_decoder, graphchallenge
from bench.tests.tiny import ROOT


def test_fsi_call_at_the_published_size():
    cfg = json.loads((ROOT / "bench/configs/gc-n1024-l120.json").read_text())
    nnz = [c.size for c in graphchallenge.make_net(cfg)]
    assert nnz == [1024 * 32] * 120
    c = counts.fsi_call(nnz, 1024, 256)
    # 2 * (120 * 32768 nonzeros) * 256 inputs
    assert c["flops"] == 2 * 3_932_160 * 256
    # 8 B per nonzero + per layer 2 * 1024 * 256 f32 activations
    assert c["bytes"] == 8 * 3_932_160 + 120 * 2 * 1024 * 256 * 4


def test_decode_token_and_prefill_small():
    d = {"L": 1, "D": 4, "H": 2, "KV": 1, "Dh": 2, "F": 8, "V": 10}
    c = counts.decode_token(d, length=4)
    assert c["decode_attn_flops"] == 4 * 2 * 2 * 4
    # K and V: 2 * KV * Dh * 2 B * 4 positions; q + out 2 * H * Dh * 2 B;
    # lse H * 4 B
    assert c["decode_attn_bytes"] == 2 * 1 * 2 * 2 * 4 + 16 + 8
    # per token 2 * (wq 16 + wk, wv 16 + wo 16 + mlp 96) = 288, attention
    # 4 * H * Dh * 4, head 2 * D * V
    assert c["forward_flops"] == 288 + 16 * 4 + 80
    # three prompt tokens: 3 * 288, attention over 1 + 2 + 3 keys, one head
    assert counts.prefill(d, 3)["forward_flops"] == 3 * 288 + 16 * 6 + 80


def test_decode_token_internlm2_widths():
    cfg = json.loads((ROOT / "bench/configs/internlm2-1.8b.json").read_text())
    d = dense_decoder.dims(cfg)
    c = counts.decode_token(d, length=513)
    per_layer = 2 * (2048 * 2048 + 2 * 2048 * 1024 + 2048 * 2048
                     + 3 * 2048 * 8192)
    # 3.02 GFLOP of matmuls per token over the 24 layers
    assert per_layer * 24 == pytest.approx(3.02e9, rel=1e-2)
    assert c["forward_flops"] == 24 * (per_layer + 4 * 16 * 128 * 513) \
        + 2 * 2048 * 92544
    assert c["decode_attn_flops"] == 24 * 4 * 16 * 128 * 513
    # 24 layers of 8 KV heads x 128 x bf16 K and V over 513 positions
    assert c["decode_attn_bytes"] == 24 * (2 * 8 * 128 * 2 * 513
                                           + 16 * 128 * 4 + 16 * 4)


def test_roofline_pct():
    # 1 GFLOP and 1 GB: bandwidth-bound at 1 / 819 s on a v5e
    pct = counts.roofline_pct(1e9, 1e9, 2 / 819, 197e12, 819e9)
    assert pct == pytest.approx(50.0)
