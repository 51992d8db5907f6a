"""Paper Fig. 6 / Table II analogue: per-sample runtime and cost of
FSD-Inf-Serial / FSD-Inf-Queue / FSD-Inf-Object across worker counts.

Scaled-down GraphChallenge configs (N, L, batch are reduced for CPU wall
time; the simulator's latency/cost models are the paper-scale ones, so the
qualitative crossovers — serial best at small N, queue cheapest comms at
high P, object costs growing linearly with P — are directly comparable).

Also benchmarks the worker compute backends (PR 1):

* ``spmm_*`` rows time one GraphChallenge layer's SpMM per formulation —
  the seed's ``np.add.at`` scatter vs the segment/batched-matmul
  ``matmul_dense_fast`` — and report the speedup.
* ``fsi_backend_*`` rows run the full queue pipeline per backend and report
  host wall-clock (billed µs/query is backend-invariant by design).

And the mesh-sharded paper-scale fleet path (PR 3, fused rows PR 5):

* ``fsi_sharded_*`` rows sweep P≥64 fleets through the
  ``pallas-bsr-sharded`` backend with the PR 3 semantics — vmap-within-shard
  dispatch + the per-worker channel hot path — at paper-scale neuron counts
  (quick: N=1024; full adds N=16384).
* ``fsi_sharded_fused_*`` rows run the same cases through the per-device
  fleet megakernel + batched channel defaults, recording
  ``speedup_vs_vmap`` and bitwise ``ulp_exact`` parity against the vmap
  row.  ``paper_scale=True`` (``make bench PAPER_SCALE=1`` /
  ``make bench-paper``) adds the full N=65536 GraphChallenge size — both
  rows, with a wall-clock ``budget_s`` recorded — which no longer
  densifies shards offline (``bsr_from_csr`` builds BSR straight from CSR
  block coordinates since PR 4).

And the pipeline-parallel LM serving path (PR 7):

* ``lm_pipeline_{queue,object}_P{2,4}`` rows decode a reduced model-zoo
  config over the serverless stage pipeline (``run_lm_pipeline``) and track
  billed ms/token, $ per 1K tokens, and the overlap-vs-phased
  ``counters_identical`` differential-oracle bit.

And the continuous-batching serving path (PR 8):

* ``serving_cb_{static,continuous}_S{slots}`` rows serve one mixed-budget
  request stream through the padded-static batcher and the paged-pool
  ``RequestScheduler`` at equal slot count.  The gated
  ``per_token_ms``/``tokens_per_s`` pair is modeled from decode slot-step
  counts (deterministic scheduling efficiency); ``wall_tokens_per_s`` rides
  along informationally, and the continuous row's ``beats_static`` bit
  records the strict win.

And the per-layer-hop attack (PR 9):

* ``fsi_{queue,object}_eager_P{2,4,8}`` rows compare eager ledger polling
  (the new default) against the PR 6 blocked-reader ledger and the phased
  oracle — three billed clocks per row, charge counts bit-identical;
* ``fsi_warm_P8`` runs the warm-pool provisioning policy, with the
  pre-request GB-seconds billed explicitly in ``warm_pool_usd``;
* ``lm_pipeline_auto_P{2,4}`` rows run the per-boundary channel autotuner
  (``channel="auto"``) and record the chosen plan string.

And the crash-fault recovery path (PR 10):

* ``fsi_chaos_{queue,object}_P4`` rows run ``run_fsi`` under a seeded
  ``FaultPlan`` that kills one worker per phase: every run must recover to
  the bitwise fault-free output (``output_equal``), with the re-invocations,
  visibility-timeout redeliveries, and checkpoint traffic billed on the
  ``recovery`` cost line;
* ``fsi_recovery_overhead_P4`` arms a zero-fault plan and records the
  checkpointing makespan overhead plus the ``counters_identical`` bit —
  arming chaos must not move a single main-fabric charge count.

And the sequence-sharded decode path (PR 4):

* ``decode_sharded_*`` rows time one split-KV decode step — shard-local
  token insert + ``pallas-splitk`` ``decode_partial`` + the
  ``combine_split_kv`` lse merge, inside shard_map — per shard count over
  the host's devices, so ``BENCH_fsi.json`` tracks the sharded serving hot
  path alongside the single-device ``decode_attn_*`` rows.
"""

from __future__ import annotations

import time
from typing import List, Sequence

import numpy as np

from repro.core.backends import get_backend
from repro.data.graphchallenge import dense_inference, make_inputs, make_sparse_dnn
from repro.faas.simulator import run_fsi


def bench_spmm_kernels(net, x0, repeats: int = 5) -> List[dict]:
    """Per-layer SpMM microbench on THIS config's first layer: seed
    scatter-add vs the fast formulations (shared timing helper with
    ``bench_roofline``, which sweeps its own canonical shape)."""
    from benchmarks.bench_roofline import time_spmm_variants

    W = net.layers[0]
    x = x0.astype(np.float32)
    flops = 2.0 * W.nnz * x.shape[1]
    rows = []
    base = None
    for name, t in time_spmm_variants(W, x, net.bias, repeats):
        if t is None:
            rows.append(dict(name=f"spmm_{name}", us_per_call="",
                             note="jax not installed"))
            continue
        base = base or t
        rows.append(dict(name=f"spmm_{name}", us_per_call=t * 1e6,
                         gflops=flops / t / 1e9,
                         speedup_vs_seed=round(base / t, 2)))
    return rows


def bench_backends(net, x0, oracle, P: int = 8,
                   backends: Sequence[str] = ("numpy-csr", "numpy-fast",
                                              "pallas-bsr")) -> List[dict]:
    """Full queue pipeline per compute backend: host wall-clock comparison."""
    rows = []
    base_wall = None
    for b in backends:
        try:
            get_backend(b)
        except ImportError:
            rows.append(dict(name=f"fsi_backend_{b}", us_per_call="",
                             note="jax not installed"))
            continue
        t0 = time.perf_counter()
        r = run_fsi(net, x0, P=P, channel="queue", memory_mb=4000,
                    compute_backend=b)
        wall = time.perf_counter() - t0
        assert np.allclose(r.output, oracle, rtol=1e-4, atol=1e-4)
        if base_wall is None:
            base_wall = wall
        rows.append(dict(
            name=f"fsi_backend_{b}", P=P,
            per_sample_ms=r.per_sample_ms(x0.shape[1]),
            cost_usd=r.cost.total, wall_s=round(wall, 4),
            wall_ms=round(wall * 1e3, 2),
            wall_speedup_vs_csr=round(base_wall / wall, 2),
        ))
    return rows


def bench_overlap(net, x0, oracle, workers=(2, 4, 8)) -> List[dict]:
    """Overlapped layer pipeline vs the phased differential oracle.

    Each ``fsi_{channel}_overlap_P{P}`` row runs ``run_fsi`` twice — the
    event-ledger clocks (``overlap=True``, the default) and the strict-sum
    phased clocks (``overlap=False``) — and records both billed times, the
    speedup, and ``counters_identical``: whether every charge count (publish
    units, SQS calls, S3 requests, wire/raw bytes, fabric metrics) was
    bit-identical between the two clock models, as the ledger design
    guarantees by construction."""
    rows: List[dict] = []
    batch = x0.shape[1]
    count_stats = ("publish_units", "bytes_sns_to_sqs", "sqs_api_calls",
                   "s3_puts", "s3_gets", "s3_lists")
    for P in workers:
        for ch in ("queue", "object"):
            t0 = time.perf_counter()
            r_ov = run_fsi(net, x0, P=P, channel=ch, memory_mb=4000,
                           overlap=True)
            r_ph = run_fsi(net, x0, P=P, channel=ch, memory_mb=4000,
                           overlap=False)
            wall = time.perf_counter() - t0
            assert np.allclose(r_ov.output, oracle, rtol=1e-4, atol=1e-4)
            identical = (
                all(getattr(r_ov.stats, f) == getattr(r_ph.stats, f)
                    for f in count_stats)
                and r_ov.wire_exchange_bytes == r_ph.wire_exchange_bytes
                and r_ov.raw_exchange_bytes == r_ph.raw_exchange_bytes
                and r_ov.metrics == r_ph.metrics
            )
            rows.append(dict(
                name=f"fsi_{ch}_overlap_P{P}", P=P,
                per_sample_ms=r_ov.per_sample_ms(batch),
                phased_per_sample_ms=r_ph.per_sample_ms(batch),
                speedup_vs_phased=round(r_ph.makespan / r_ov.makespan, 3),
                counters_identical=bool(identical),
                cost_usd=r_ov.cost.total,
                comms_usd=r_ov.cost.communication,
                wall_s=round(wall, 4), wall_ms=round(wall * 1e3, 2),
            ))
    return rows


def bench_eager_warm(net, x0, oracle, workers=(2, 4, 8)) -> List[dict]:
    """Eager polling and warm-pool provisioning vs their off switches (PR 9).

    ``fsi_{channel}_eager_P{P}`` rows run ``run_fsi`` three ways — eager
    ledger polling (the default), ``eager_poll=False`` (the PR 6 blocked-
    reader ledger), and the strict-sum phased oracle — and record all three
    billed times plus ``counters_identical``: every charge count and the
    phased makespan bit-identical between eager and lazy, as the ledger-only
    re-timing guarantees.  ``fsi_warm_P8`` runs the warm-pool policy (fleet
    pre-invoked, weights pre-loaded before the request epoch) and surfaces
    the explicit pre-request GB-seconds bill in ``warm_pool_usd``."""
    rows: List[dict] = []
    batch = x0.shape[1]
    count_stats = ("publish_units", "bytes_sns_to_sqs", "sqs_api_calls",
                   "s3_puts", "s3_gets", "s3_lists")

    def counts_identical(a, b) -> bool:
        return (all(getattr(a.stats, f) == getattr(b.stats, f)
                    for f in count_stats)
                and a.wire_exchange_bytes == b.wire_exchange_bytes
                and a.raw_exchange_bytes == b.raw_exchange_bytes
                and a.metrics["phased_makespan_s"]
                == b.metrics["phased_makespan_s"])

    for P in workers:
        for ch in ("queue", "object"):
            t0 = time.perf_counter()
            r_eager = run_fsi(net, x0, P=P, channel=ch, memory_mb=4000)
            r_lazy = run_fsi(net, x0, P=P, channel=ch, memory_mb=4000,
                             eager_poll=False)
            wall = time.perf_counter() - t0
            assert np.allclose(r_eager.output, oracle, rtol=1e-4, atol=1e-4)
            rows.append(dict(
                name=f"fsi_{ch}_eager_P{P}", P=P,
                per_sample_ms=r_eager.per_sample_ms(batch),
                lazy_per_sample_ms=r_lazy.per_sample_ms(batch),
                phased_per_sample_ms=(
                    r_eager.metrics["phased_makespan_s"] / batch * 1e3),
                speedup_vs_lazy=round(r_lazy.makespan / r_eager.makespan, 3),
                counters_identical=counts_identical(r_eager, r_lazy),
                cost_usd=r_eager.cost.total,
                comms_usd=r_eager.cost.communication,
                wall_s=round(wall, 4), wall_ms=round(wall * 1e3, 2),
            ))

    P = max(workers)
    t0 = time.perf_counter()
    r_warm = run_fsi(net, x0, P=P, channel="queue", memory_mb=4000,
                     warm_pool=True)
    r_warm_ph = run_fsi(net, x0, P=P, channel="queue", memory_mb=4000,
                        warm_pool=True, overlap=False)
    wall = time.perf_counter() - t0
    assert np.allclose(r_warm.output, oracle, rtol=1e-4, atol=1e-4)
    rows.append(dict(
        name=f"fsi_warm_P{P}", P=P,
        per_sample_ms=r_warm.per_sample_ms(batch),
        phased_per_sample_ms=r_warm_ph.per_sample_ms(batch),
        warm_pool_usd=r_warm.cost.warm_pool,
        warm_pool_provision_s=r_warm.metrics["warm_pool_provision_s"],
        counters_identical=bool(
            counts_identical(r_warm, r_warm_ph)
            and r_warm.metrics == r_warm_ph.metrics),
        cost_usd=r_warm.cost.total,
        comms_usd=r_warm.cost.communication,
        wall_s=round(wall, 4), wall_ms=round(wall * 1e3, 2),
    ))
    return rows


def bench_chaos(net, x0, oracle, P: int = 4) -> List[dict]:
    """Crash-fault recovery under seeded chaos (PR 10).

    ``fsi_chaos_{queue,object}_P4``: one worker killed at each crash phase
    (send / compute / drain, spread across layers and workers) — the fleet
    re-invokes, restores panels from durable checkpoints, redelivers or
    re-GETs the lost inputs, and must land on the bitwise fault-free output
    with the recovery spend on its own auditable cost line.
    ``fsi_recovery_overhead_P4``: the price of *arming* a plan that never
    fires — checkpoint serialization on the clock, checkpoint tariffs on the
    recovery line, and zero drift in any main-fabric charge count."""
    from repro.faas.chaos import FaultPlan

    rows: List[dict] = []
    batch = x0.shape[1]
    count_stats = ("publish_units", "bytes_sns_to_sqs", "sqs_api_calls",
                   "s3_puts", "s3_gets", "s3_lists")
    kills = ((1, 0, "send"), (2, 1, "compute"), (0, 2, "drain"))
    for ch in ("queue", "object"):
        t0 = time.perf_counter()
        base = run_fsi(net, x0, P=P, channel=ch, memory_mb=4000)
        r = run_fsi(net, x0, P=P, channel=ch, memory_mb=4000,
                    faults=FaultPlan(kills=kills))
        wall = time.perf_counter() - t0
        assert np.allclose(r.output, oracle, rtol=1e-4, atol=1e-4)
        rows.append(dict(
            name=f"fsi_chaos_{ch}_P{P}", P=P,
            per_sample_ms=r.per_sample_ms(batch),
            output_equal=bool(np.array_equal(r.output, base.output)),
            n_reinvokes=r.metrics["n_reinvokes"],
            redeliveries=float(r.metrics.get("redeliveries", 0.0)),
            recovery_usd=r.cost.recovery,
            cost_usd=r.cost.total,
            comms_usd=r.cost.communication,
            wall_s=round(wall, 4), wall_ms=round(wall * 1e3, 2),
        ))
    t0 = time.perf_counter()
    base = run_fsi(net, x0, P=P, channel="queue", memory_mb=4000)
    armed = run_fsi(net, x0, P=P, channel="queue", memory_mb=4000,
                    faults=FaultPlan())
    wall = time.perf_counter() - t0
    rows.append(dict(
        name=f"fsi_recovery_overhead_P{P}", P=P,
        per_sample_ms=armed.per_sample_ms(batch),
        overhead_pct=round(
            (armed.makespan / base.makespan - 1.0) * 100.0, 4),
        counters_identical=bool(
            all(getattr(armed.stats, f) == getattr(base.stats, f)
                for f in count_stats)
            and np.array_equal(armed.output, base.output)),
        checkpoint_puts=armed.metrics["checkpoint_puts"],
        recovery_usd=armed.cost.recovery,
        cost_usd=armed.cost.total,
        comms_usd=armed.cost.communication,
        wall_s=round(wall, 4), wall_ms=round(wall * 1e3, 2),
    ))
    return rows


def bench_lm_pipeline_auto(arch: str = "internlm2-1.8b", workers=(2, 4),
                           batch: int = 2, prompt_len: int = 12,
                           max_new: int = 4) -> List[dict]:
    """Per-boundary channel autotune over the LM stage pipeline (PR 9).

    ``lm_pipeline_auto_P{P}`` rows run ``run_lm_pipeline(channel="auto")``
    — queue vs object chosen per stage boundary (and for the token
    loopback) from ``activation_hop_cost`` over the boundary's activation
    bytes — against the phased oracle, recording the standard LM-pipeline
    contract plus the chosen plan string."""
    try:
        import jax  # noqa: F401
    except ModuleNotFoundError:
        return [dict(name=f"lm_pipeline_auto_P{P}", us_per_call="",
                     note="jax not installed")
                for P in workers]

    from repro.configs.base import get_config
    from repro.faas.lm_pipeline import build_stage_executors, run_lm_pipeline
    from repro.serving.engine import ServingEngine

    cfg = get_config(arch).reduced()
    rng = np.random.default_rng(0)
    prompts = rng.integers(0, cfg.vocab_size, (batch, prompt_len),
                           dtype=np.int32)
    engine = ServingEngine(cfg, seed=0)
    ref = engine.generate(prompts, max_new_tokens=max_new)
    count_stats = ("publish_units", "bytes_sns_to_sqs", "sqs_api_calls",
                   "s3_puts", "s3_gets", "s3_lists")
    rows: List[dict] = []
    for P in workers:
        executors = build_stage_executors(cfg, engine.params, P)
        t0 = time.perf_counter()
        r_ov = run_lm_pipeline(cfg, prompts, engine.params,
                               max_new_tokens=max_new, P=P, channel="auto",
                               executors=executors, overlap=True)
        r_ph = run_lm_pipeline(cfg, prompts, engine.params,
                               max_new_tokens=max_new, P=P, channel="auto",
                               executors=executors, overlap=False)
        wall = time.perf_counter() - t0
        assert np.array_equal(r_ov.tokens, ref.tokens)
        identical = (
            all(getattr(r_ov.stats, f) == getattr(r_ph.stats, f)
                for f in count_stats)
            and r_ov.wire_exchange_bytes == r_ph.wire_exchange_bytes
            and r_ov.raw_exchange_bytes == r_ph.raw_exchange_bytes
            and r_ov.metrics["chosen_channel_plan"]
            == r_ph.metrics["chosen_channel_plan"]
        )
        rows.append(dict(
            name=f"lm_pipeline_auto_P{P}", P=P, arch=cfg.name,
            per_token_ms=r_ov.per_token_ms,
            phased_per_token_ms=r_ph.per_token_ms,
            usd_per_1k_tokens=r_ov.usd_per_1k_tokens,
            counters_identical=bool(identical),
            chosen_channel_plan=r_ov.metrics["chosen_channel_plan"],
            speedup_vs_phased=round(r_ph.makespan / r_ov.makespan, 3),
            cost_usd=r_ov.cost.total,
            comms_usd=r_ov.cost.communication,
            wall_s=round(wall, 4), wall_ms=round(wall * 1e3, 2),
        ))
    return rows


def bench_lm_pipeline(arch: str = "internlm2-1.8b", workers=(2, 4),
                      batch: int = 2, prompt_len: int = 12,
                      max_new: int = 4) -> List[dict]:
    """Pipeline-parallel LM serving over the FaaS fabric (PR 7).

    Each ``lm_pipeline_{channel}_P{P}`` row decodes a reduced model-zoo
    config through ``run_lm_pipeline`` — the layer stack split into P stage
    workers, activations and the token loopback on the channel — twice
    (event-ledger vs strict-sum phased clocks, same differential oracle as
    ``bench_overlap``), recording billed ms per generated token, $ per 1K
    tokens, and the ``counters_identical`` bit.  Tokens must match the
    on-device ``ServingEngine`` exactly."""
    try:
        import jax  # noqa: F401
    except ModuleNotFoundError:
        return [dict(name=f"lm_pipeline_{ch}_P{P}", us_per_call="",
                     note="jax not installed")
                for P in workers for ch in ("queue", "object")]

    from repro.configs.base import get_config
    from repro.faas.lm_pipeline import build_stage_executors, run_lm_pipeline
    from repro.serving.engine import ServingEngine

    cfg = get_config(arch).reduced()
    rng = np.random.default_rng(0)
    prompts = rng.integers(0, cfg.vocab_size, (batch, prompt_len),
                           dtype=np.int32)
    engine = ServingEngine(cfg, seed=0)
    ref = engine.generate(prompts, max_new_tokens=max_new)
    count_stats = ("publish_units", "bytes_sns_to_sqs", "sqs_api_calls",
                   "s3_puts", "s3_gets", "s3_lists")
    rows: List[dict] = []
    for P in workers:
        executors = build_stage_executors(cfg, engine.params, P)
        for ch in ("queue", "object"):
            t0 = time.perf_counter()
            r_ov = run_lm_pipeline(cfg, prompts, engine.params,
                                   max_new_tokens=max_new, P=P, channel=ch,
                                   executors=executors, overlap=True)
            r_ph = run_lm_pipeline(cfg, prompts, engine.params,
                                   max_new_tokens=max_new, P=P, channel=ch,
                                   executors=executors, overlap=False)
            wall = time.perf_counter() - t0
            assert np.array_equal(r_ov.tokens, ref.tokens)
            identical = (
                all(getattr(r_ov.stats, f) == getattr(r_ph.stats, f)
                    for f in count_stats)
                and r_ov.wire_exchange_bytes == r_ph.wire_exchange_bytes
                and r_ov.raw_exchange_bytes == r_ph.raw_exchange_bytes
            )
            rows.append(dict(
                name=f"lm_pipeline_{ch}_P{P}", P=P, arch=cfg.name,
                per_token_ms=r_ov.per_token_ms,
                phased_per_token_ms=r_ph.per_token_ms,
                usd_per_1k_tokens=r_ov.usd_per_1k_tokens,
                counters_identical=bool(identical),
                speedup_vs_phased=round(r_ph.makespan / r_ov.makespan, 3),
                cost_usd=r_ov.cost.total,
                comms_usd=r_ov.cost.communication,
                wire_kb=r_ov.wire_exchange_bytes / 1e3,
                wall_s=round(wall, 4), wall_ms=round(wall * 1e3, 2),
            ))
    return rows


def bench_serving_cb(arch: str = "internlm2-1.8b", num_slots: int = 2,
                     prompt_len: int = 6,
                     budgets=(1, 6, 1, 6, 2, 5)) -> List[dict]:
    """Continuous batching vs padded static batching at equal slot count
    (PR 8).

    A mixed-budget stream (equal prompt lengths, ragged ``max_new``) is
    served two ways: the ``RequestScheduler`` (paged KV pool, per-slot
    admission/retirement) and the static baseline — batches of ``num_slots``
    requests each padded to its batch's max budget, the only way
    ``ServingEngine.generate`` takes them.  Both run at the same slot
    capacity so the decode step costs the same per slot-step, which makes
    slot-step counts the apples-to-apples unit.

    The gated metrics, ``per_token_ms`` and its reciprocal ``tokens_per_s``,
    are *modeled* (deterministic): decode steps × the per-slot step time
    ``2 · active_params / peak_bf16_flops`` ÷ tokens delivered, i.e. pure
    scheduling efficiency with host/tracing noise excluded (at the
    bench's toy scale the host wall-clock is dominated by per-step paged
    gather/scatter overhead that real-scale decode matmuls amortize away).
    ``wall_tokens_per_s`` / ``wall_ms`` are measured host wall-clock
    (post-warmup) and stay informational — never gated.  ``beats_static``
    on the continuous row records the acceptance bit: continuous sustained
    throughput strictly above the padded-static baseline.  Tokens must
    match the static baseline exactly (prompts are equal-length within a
    batch, so static has no padding pollution and both paths are bitwise
    against the same solo oracle)."""
    try:
        import jax  # noqa: F401
    except ModuleNotFoundError:
        return [dict(name=f"serving_cb_{kind}_S{num_slots}", per_token_ms="",
                     note="jax not installed")
                for kind in ("static", "continuous")]

    from repro.configs.base import get_config
    from repro.core.cost_model import TPU_V5E
    from repro.serving.engine import ServingEngine
    from repro.serving.scheduler import Request, RequestScheduler

    cfg = get_config(arch).reduced()
    engine = ServingEngine(cfg, seed=0)
    rng = np.random.default_rng(0)
    prompts = rng.integers(0, cfg.vocab_size, (len(budgets), prompt_len),
                           dtype=np.int32)
    reqs = [Request(rid=i, prompt=prompts[i], max_new_tokens=int(b))
            for i, b in enumerate(budgets)]
    total_tokens = int(sum(budgets))
    cap_need = prompt_len + max(budgets) + (cfg.frontend_tokens or 0)
    layout = engine.cache_layout(cap_need)
    cap = layout.padded_len(cap_need)

    # Modeled per-slot decode-step time on the reference chip.
    step_s = 2.0 * cfg.active_param_count() / TPU_V5E.peak_bf16_flops

    # -- static baseline: batches of num_slots, padded to the batch max ----
    def run_static():
        toks = {}
        for i in range(0, len(budgets), num_slots):
            batch = list(range(i, min(i + num_slots, len(budgets))))
            out = engine.generate(prompts[batch],
                                  max_new_tokens=max(budgets[j]
                                                     for j in batch),
                                  max_len=cap)
            for row, j in enumerate(batch):
                toks[j] = out.tokens[row, :budgets[j]]
        return toks

    static_tokens = run_static()                      # warmup (traces jit)
    t0 = time.perf_counter()
    run_static()
    static_wall = time.perf_counter() - t0
    static_steps = sum(max(budgets[i:i + num_slots])
                       for i in range(0, len(budgets), num_slots))
    static_slot_steps = static_steps * num_slots

    # -- continuous: the scheduler over the same stream --------------------
    sched = RequestScheduler(engine.model, engine.params, engine._prefill,
                             num_slots=num_slots, slot_capacity=cap,
                             layout=layout)
    results = sched.run(reqs)                         # warmup (traces step)
    cont_steps = sched.steps_run
    t0 = time.perf_counter()
    sched.run(reqs)
    cont_wall = time.perf_counter() - t0
    cont_slot_steps = cont_steps * num_slots

    for r in results:
        assert np.array_equal(r.tokens, static_tokens[r.rid]), \
            f"scheduler tokens diverge from static baseline (rid={r.rid})"
    assert sched.tokens_emitted == 2 * total_tokens   # both runs counted

    def mk(kind, slot_steps, steps, wall):
        per_token_ms = steps * step_s * 1e3 / total_tokens
        return dict(
            name=f"serving_cb_{kind}_S{num_slots}", arch=cfg.name,
            num_slots=num_slots, requests=len(budgets), tokens=total_tokens,
            slot_steps=slot_steps, per_token_ms=round(per_token_ms, 9),
            tokens_per_s=round(1e3 / per_token_ms, 1),
            wall_tokens_per_s=round(total_tokens / wall, 2),
            wall_s=round(wall, 4), wall_ms=round(wall * 1e3, 2),
        )

    static_row = mk("static", static_slot_steps, static_steps, static_wall)
    cont_row = mk("continuous", cont_slot_steps, cont_steps, cont_wall)
    cont_row["speedup_vs_static"] = round(static_steps / cont_steps, 3)
    cont_row["beats_static"] = bool(
        cont_row["per_token_ms"] < static_row["per_token_ms"])
    return [static_row, cont_row]


def bench_sharded_fleet(
    cases: Sequence[tuple] = ((64, 1024, 4, 16),),
    paper_scale: bool = False,
    paper_budget_s: float = 60.0,
) -> List[dict]:
    """Paper-scale fleet sweep (P≥64, §VI neuron counts) through the
    mesh-sharded backend.  ``cases`` are (P, neurons, layers, batch) tuples;
    each runs the full queue pipeline with the fleet panel sharded over a
    ``worker`` mesh built from every visible device (1 on a plain CPU host;
    set ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` before jax
    init for a wider host mesh).

    Each case produces TWO rows sharing one a-priori partition (hypergraph
    partitioning is offline per the paper, so it is excluded from both
    walls):

    * ``fsi_sharded_*`` — the PR 3 semantics: ``dispatch="vmap"`` within
      each shard + the per-worker channel hot path;
    * ``fsi_sharded_fused_*`` — the per-device fleet megakernel + the
      batched channel hot path (the run_fsi defaults), with
      ``speedup_vs_vmap`` and an ``ulp_exact`` bitwise-parity flag against
      the vmap row's output.

    ``paper_scale`` adds the full N=65536 GraphChallenge size — both rows,
    so the fused row's ``speedup_vs_vmap`` is measured where the megakernel
    matters most — with a wall-clock budget recorded in the fused row.
    """
    rows: List[dict] = []
    try:
        get_backend("pallas-bsr-sharded")
    except ImportError:
        pairs = list(cases) + ([(64, 65536, 1, 4)] if paper_scale else [])
        names = [f"fsi_sharded_P{p}_N{n}" for p, n, _, _ in pairs]
        names += [f"fsi_sharded_fused_P{p}_N{n}" for p, n, _, _ in pairs]
        return [dict(name=n, us_per_call="", note="jax not installed")
                for n in names]
    import jax

    from repro.core.backends import PallasBsrShardedBackend
    from repro.core.partitioner import partition_network
    from repro.launch.mesh import make_worker_mesh

    mesh = make_worker_mesh()

    def one_case(P, N, L, batch, budget_s=None):
        net = make_sparse_dnn(N, n_layers=L, seed=0)
        x0 = make_inputs(N, batch, seed=1)
        oracle = dense_inference(net, x0)
        partition = partition_network(net.layers, P, method="hgp", seed=0)
        out: List[dict] = []
        vmap_backend = PallasBsrShardedBackend(mesh=mesh, dispatch="vmap")
        t0 = time.perf_counter()
        r_vmap = run_fsi(net, x0, P=P, channel="queue", memory_mb=4000,
                         compute_backend=vmap_backend, mesh=mesh,
                         partition=partition, channel_batching=False)
        wall_vmap = time.perf_counter() - t0
        assert np.allclose(r_vmap.output, oracle, rtol=1e-4, atol=1e-4)
        out.append(dict(
            name=f"fsi_sharded_P{P}_N{N}", P=P, neurons=N, layers=L,
            devices=len(jax.devices()),
            per_sample_ms=r_vmap.per_sample_ms(batch),
            cost_usd=r_vmap.cost.total,
            comms_usd=r_vmap.cost.communication,
            wire_mb=r_vmap.wire_exchange_bytes / 1e6,
            wall_s=round(wall_vmap, 4),
            wall_ms=round(wall_vmap * 1e3, 2),
        ))
        t0 = time.perf_counter()
        r = run_fsi(net, x0, P=P, channel="queue", memory_mb=4000,
                    compute_backend="pallas-bsr-sharded", mesh=mesh,
                    partition=partition)
        wall = time.perf_counter() - t0
        assert np.allclose(r.output, oracle, rtol=1e-4, atol=1e-4)
        row = dict(
            name=f"fsi_sharded_fused_P{P}_N{N}", P=P, neurons=N, layers=L,
            devices=len(jax.devices()),
            per_sample_ms=r.per_sample_ms(batch),
            cost_usd=r.cost.total,
            comms_usd=r.cost.communication,
            wire_mb=r.wire_exchange_bytes / 1e6,
            wall_s=round(wall, 4),
            # billed per_sample_ms is backend-invariant by design, so the
            # fused kernel's real win only shows in wall-clock
            wall_ms=round(wall * 1e3, 2),
            speedup_vs_vmap=round(wall_vmap / wall, 2),
            ulp_exact=bool(np.array_equal(r.output, r_vmap.output)),
        )
        if budget_s is not None:
            row["budget_s"] = budget_s
            row["within_budget"] = bool(wall <= budget_s)
        out.append(row)
        return out

    for P, N, L, batch in cases:
        rows.extend(one_case(P, N, L, batch))
    if paper_scale:
        # the headline gate: both dispatches at the full GraphChallenge
        # N=65536 — the sweep the megakernel + batched channels un-block
        rows.extend(one_case(64, 65536, 1, 4, budget_s=paper_budget_s))
    return rows


def bench_sharded_decode(batch: int = 4, heads: int = 8, kv_heads: int = 2,
                         seq: int = 1024, d_head: int = 64,
                         repeats: int = 10) -> List[dict]:
    """µs/step for one sequence-sharded split-KV decode step per shard count.

    The cache is kernel-native ``[B, KV, S, D]`` (S a block_k multiple per
    the PR 4 layout) and sharded over a 1-D ``seq`` mesh axis; every shard
    inserts the new token iff it owns the position, runs ``pallas-splitk``
    over its local slice, and partials merge via ``combine_split_kv`` — the
    decode analogue of the ``fsi_sharded_*`` fleet rows."""
    try:
        import jax
        import jax.numpy as jnp
    except ModuleNotFoundError:
        return [dict(name="decode_sharded_splitk_d1", us_per_call="",
                     note="jax not installed")]

    import numpy as np
    from jax.sharding import PartitionSpec as P

    from repro.core.backends import PallasSplitKAttention
    from repro.launch.mesh import make_mesh
    from repro.models.attention import sharded_decode_attend

    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((batch, 1, heads, d_head)), jnp.bfloat16)
    k = jnp.asarray(rng.standard_normal((batch, kv_heads, seq, d_head)), jnp.bfloat16)
    v = jnp.asarray(rng.standard_normal((batch, kv_heads, seq, d_head)), jnp.bfloat16)
    k_new = jnp.asarray(rng.standard_normal((batch, kv_heads, 1, d_head)), jnp.bfloat16)
    v_new = jnp.asarray(rng.standard_normal((batch, kv_heads, 1, d_head)), jnp.bfloat16)
    pos = jnp.asarray(seq - seq // 8, jnp.int32)
    be = PallasSplitKAttention()
    flops = 2.0 * 2.0 * batch * heads * int(pos + 1) * d_head

    rows = []
    n_dev = len(jax.devices())
    for d in (1, 2, 4, 8):
        if d > n_dev or seq % d or (seq // d) % be.block_k_for(seq // d):
            continue
        mesh = make_mesh((d,), ("seq",))

        def body(q, k, v, pos):
            # the exact production recipe — shared with the model families
            o, _, _ = sharded_decode_attend(be, q, k_new, v_new, k, v, pos,
                                            "seq")
            return o

        kv_spec = P(None, None, "seq", None)
        f = jax.jit(jax.shard_map(
            body, mesh=mesh, in_specs=(P(), kv_spec, kv_spec, P()),
            out_specs=P(), check_vma=False))
        np.asarray(f(q, k, v, pos))  # warmup: trace + compile
        t0 = time.perf_counter()
        for _ in range(repeats):
            np.asarray(f(q, k, v, pos))
        t = (time.perf_counter() - t0) / repeats
        rows.append(dict(
            name=f"decode_sharded_splitk_d{d}",
            us_per_call=round(t * 1e6, 1),
            gflops=round(flops / t / 1e9, 3),
            shards=d, batch=batch, heads=heads, kv_heads=kv_heads,
            seq=seq, d_head=d_head,
        ))
    return rows


def run(neurons=512, layers=24, batch=64, workers=(2, 4, 8, 16),
        backends=("numpy-csr", "numpy-fast", "pallas-bsr"),
        sharded_cases=((64, 1024, 4, 16), (64, 16384, 2, 8)),
        paper_scale=False, paper_budget_s=60.0) -> List[dict]:
    net = make_sparse_dnn(neurons, n_layers=layers, seed=0)
    x0 = make_inputs(neurons, batch, seed=1)
    oracle = dense_inference(net, x0)
    rows = bench_spmm_kernels(net, x0)
    t0 = time.perf_counter()
    r = run_fsi(net, x0, channel="serial")
    wall = time.perf_counter() - t0
    assert np.allclose(r.output, oracle, rtol=1e-4, atol=1e-4)
    rows.append(dict(name="fsi_serial", P=1,
                     per_sample_ms=r.per_sample_ms(batch),
                     cost_usd=r.cost.total, comms_usd=0.0, wall_s=wall,
                     wall_ms=round(wall * 1e3, 2)))
    for P in workers:
        for ch in ("queue", "object"):
            t0 = time.perf_counter()
            r = run_fsi(net, x0, P=P, channel=ch, memory_mb=4000)
            wall = time.perf_counter() - t0
            assert np.allclose(r.output, oracle, rtol=1e-4, atol=1e-4)
            rows.append(dict(
                name=f"fsi_{ch}_P{P}", P=P,
                per_sample_ms=r.per_sample_ms(batch),
                cost_usd=r.cost.total,
                comms_usd=r.cost.communication,
                wire_mb=r.wire_exchange_bytes / 1e6,
                wall_s=wall,
                wall_ms=round(wall * 1e3, 2),
            ))
    rows.extend(bench_overlap(net, x0, oracle))
    rows.extend(bench_eager_warm(net, x0, oracle,
                                 workers=tuple(p for p in workers if p <= 8)))
    rows.extend(bench_chaos(net, x0, oracle))
    rows.extend(bench_lm_pipeline())
    rows.extend(bench_lm_pipeline_auto())
    rows.extend(bench_serving_cb())
    rows.extend(bench_backends(net, x0, oracle, P=max(workers),
                               backends=backends))
    rows.extend(bench_sharded_fleet(sharded_cases, paper_scale=paper_scale,
                                    paper_budget_s=paper_budget_s))
    rows.extend(bench_sharded_decode(seq=256 if neurons <= 256 else 1024))
    return rows
