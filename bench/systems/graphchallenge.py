"""Graph Challenge inference through the program's serverless fleet.

Set-up generates the net, hands it to the program as its CSR layers,
partitions it once (the paper's offline hypergraph partitioning) and warms
the fleet with one call.  The window calls ``run_fsi`` back to back, each
call on fresh inputs from ``(seed, call index)``, and counts the edges
traversed: inputs times the net's nonzeros.  The check compares a sample of
the window's own outputs, element by element, with the plain oracle.
"""

from __future__ import annotations

import sys
import time
from typing import Dict, List

import numpy as np

from bench import counts, traffic_gen
from bench.refs import graphchallenge as ref

E2E_METRIC = "fsi_edges_per_s"
PARTITION_SEED = 0


class Cell:
    e2e_metric = E2E_METRIC

    def __init__(self, config: dict, mix: dict, seed: int, spans):
        self.config, self.mix, self.seed, self.spans = config, mix, seed, spans
        self.N = int(config["neurons"])
        self.outputs: List[np.ndarray] = []
        self.attempted = 0
        self.failed = 0

    def setup(self) -> None:
        from repro.core.partitioner import partition_network
        from repro.core.sparse import CSRMatrix
        from repro.data.graphchallenge import GraphChallengeNet
        from repro.faas.simulator import run_fsi
        from repro.launch.mesh import make_worker_mesh

        t0 = time.perf_counter()
        cfg, N = self.config, self.N
        self.cols = ref.make_net(cfg)
        k = int(cfg["nnz_per_row"])
        layers = [CSRMatrix(shape=(N, N),
                            indptr=np.arange(N + 1, dtype=np.int64) * k,
                            indices=c.reshape(-1).copy(),
                            data=np.full(N * k, cfg["weight"], np.float32))
                  for c in self.cols]
        self.net = GraphChallengeNet(neurons=N, layers=layers,
                                     bias=float(cfg["bias"]))
        self.layer_nnz = [c.size for c in self.cols]
        self.P, self.channel = int(self.mix["P"]), str(self.mix["channel"])
        self.partition = partition_network(layers, self.P, method="hgp",
                                           seed=PARTITION_SEED)
        t_part = time.perf_counter() - t0
        self.mesh = make_worker_mesh(1)
        self.run_fsi = run_fsi
        # Warm-up: one call at the window's shapes, on inputs no window
        # call sees.
        self._call(traffic_gen.fsi_inputs(self.mix, self.seed, 0, N,
                                          warm=True))
        print(f"graphchallenge: net and partition {t_part:.2f} s, warm call "
              f"{time.perf_counter() - t0 - t_part:.2f} s", file=sys.stderr,
              flush=True)

    def _call(self, x0: np.ndarray):
        res = self.run_fsi(self.net, x0, P=self.P, channel=self.channel,
                           partition=self.partition, mesh=self.mesh)
        return res.output

    def run_unit(self) -> float:
        """One ``run_fsi`` call; returns the edges it traversed."""
        x0 = traffic_gen.fsi_inputs(self.mix, self.seed, len(self.outputs),
                                    self.N)
        self.attempted += 1
        with self.spans("bench.fsi_call"):
            out = self._call(x0)
        self.outputs.append(out)
        if out.shape != x0.shape:
            self.failed += 1
            return 0.0
        return float(x0.shape[1] * sum(self.layer_nnz))

    def arm_trace(self, tracer) -> None:
        """The traced slice is the whole window: it begins now."""
        self._trace_first = len(self.outputs)
        tracer.begin()

    def trace_counts(self) -> Dict[str, float]:
        """What the calls of the traced slice needed."""
        calls = len(self.outputs) - self._trace_first
        c = counts.fsi_call(self.layer_nnz, self.N, int(self.mix["batch"]))
        return {"bsr_flops": calls * c["flops"],
                "bsr_bytes": calls * c["bytes"],
                "forward_flops": calls * c["flops"]}

    def counters(self) -> Dict[str, float]:
        return {}

    def release(self) -> None:
        self.net = self.partition = None

    def sample(self) -> List[int]:
        """The window's calls the check compares, drawn from the seed."""
        n = len(self.outputs)
        rng = traffic_gen.rng_for(self.seed, 3)
        return sorted(rng.permutation(n)[: int(self.mix["check_calls"])])

    def check(self, limits: dict) -> List[dict]:
        """Output elements of the sampled calls that differ from the
        oracle's (an exact comparison)."""
        bad, compared = 0, 0
        for i in self.sample():
            x0 = traffic_gen.fsi_inputs(self.mix, self.seed, int(i), self.N)
            want = ref.dense_inference(self.config, self.cols, x0)
            got = self.outputs[i]
            if got.shape != want.shape:
                bad += want.size
            else:
                bad += int(np.count_nonzero(got != want))
            compared += 1
        return [{"name": "mismatched_outputs",
                 "value": float(bad) if compared else float("inf"),
                 "limit": float(limits["mismatched_outputs"]),
                 "calls": compared}]


def control(cell) -> dict:
    """The check's number for the control, on the same sample: the output
    elements that the oracle, with its activations rounded to bfloat16 (and,
    one step further, to float8 e4m3), gets wrong."""
    out = {}
    for quant in ("bfloat16", "float8_e4m3fn"):
        bad = 0
        for i in cell.sample():
            x0 = traffic_gen.fsi_inputs(cell.mix, cell.seed, int(i), cell.N)
            want = ref.dense_inference(cell.config, cell.cols, x0)
            got = ref.dense_inference(cell.config, cell.cols, x0,
                                      control=quant)
            bad += int(np.count_nonzero(got != want))
        out[f"control_{quant}"] = float(bad)
    return out
