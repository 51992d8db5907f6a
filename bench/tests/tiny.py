"""Small stand-ins for the cells, so a whole run can be driven on the CPU.

``load`` replaces ``run_cell.load_cell``: the real workload entries of
``BENCHMARK.json`` with a configuration and a mix small enough for a test.
The decoder keeps a width (1024) at which its logits spread as the real
model's do, so the token-gap limit means the same; the Graph Challenge net
keeps its depth high enough (40 layers) that its activations saturate at 0
or 32, as at the real depth, so the comparison with the oracle is exact
there too."""

from __future__ import annotations

import io
import json
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]

LM = {"name": "tiny-decoder", "kind": "dense_decoder", "hidden_size": 1024,
      "num_hidden_layers": 2, "num_attention_heads": 8,
      "num_key_value_heads": 4, "intermediate_size": 2048,
      "vocab_size": 4000,
      "rope_theta": 10000.0, "rms_norm_eps": 1e-5,
      "tie_word_embeddings": False}
GC = {"name": "tiny-gc", "kind": "graphchallenge", "neurons": 64,
      "layers": 40, "nnz_per_row": 32, "weight": 0.0625, "bias": -0.3,
      "activation_clip": 32.0}
LM_MIX = {"kind": "lm_offline", "requests_per_batch": 6, "num_slots": 4,
          "prompt_len": {"choice": [8, 16]}, "output_len": {"uniform": [2, 6]},
          "capacity": 24, "check_tokens": 12, "check_requests": 3}


# Where the controls are read: deep enough that fp8 rounding shows.
LM_CONTROL = dict(LM, num_hidden_layers=6)
LM_CONTROL_MIX = dict(LM_MIX, prompt_len={"choice": [16, 32]},
                      output_len={"uniform": [8, 24]}, capacity=64,
                      check_tokens=60, check_requests=4)
GC_CONTROL = dict(GC, neurons=1024)


def limit(workload: str, name: str) -> float:
    return float(json.loads((ROOT / "bench" / "limits"
                             / f"{workload}.json").read_text())[name])


def gc_mix(channel: str, P: int, batch: int = 16) -> dict:
    return {"kind": "fsi_batches", "batch": batch, "density": 0.3, "P": P,
            "channel": channel, "check_calls": 2}


# The object-channel cell is not in BENCHMARK.json (its runs spread too
# widely on one chip); its mix and limit are kept, and the tests drive it.
OBJECT_CELL = {"name": "gc1k_object_p16", "config": "gc-n1024-l120",
               "traffic": "batch256_object_p16", "chips": 1}


def bench() -> dict:
    b = json.loads((ROOT / "BENCHMARK.json").read_text())
    if all(w["name"] != OBJECT_CELL["name"] for w in b["workloads"]):
        b["workloads"].append(OBJECT_CELL)
    return b


def load(bench: dict, wl: dict):
    limits = json.loads((ROOT / "bench" / "limits"
                         / f"{wl['name']}.json").read_text())
    if wl["config"].startswith("internlm2"):
        return LM, LM_MIX, limits
    from bench import traffic_gen

    mix = traffic_gen.load_mix(wl["traffic"])
    return GC, gc_mix(mix["channel"], min(int(mix["P"]), 4)), limits


def run(workload: str, seed: int = 2**31 + 7, seconds: float = 0.3,
        trace: bool = False) -> dict:
    """Drive one run of ``workload`` on the CPU; returns its result line."""
    from bench import run_cell

    out = io.StringIO()
    rc = run_cell.run(bench(), workload, seed, seconds, trace,
                      require_tpu=False, load=load, out=out)
    assert rc == 0, rc
    return json.loads(out.getvalue().strip().splitlines()[-1])
