#!/usr/bin/env python3
"""Smoke test of the three served paths on a TPU, through their entry points.

    python chip_smoke.py             # fsi, serve, fabric on one chip
    python chip_smoke.py --chips 4   # the paths that span chips, each against
                                     # its one-chip twin

Default phases (one device, ``jax.devices()[:1]``):

* ``fsi``    — ``run_fsi`` over the Graph Challenge net N=1024 x 120 layers
  (radix, seed 0), batch 256, P=4, queue channel, platform-resolved compute
  backend.  Checks the categories (samples with a nonzero final activation)
  against ``dense_inference`` and one layer's block products at 1e-4.
* ``serve``  — internlm2-1.8b at its published widths, bf16 parameters from
  the seed: ``generate_stream`` over 8 requests (prompts 64-512 tokens, 32
  new tokens, 4 slots) and one ``generate`` batch of 4.  Checks that every
  token is in the vocabulary, one decode-attention call against
  ``dense-ref`` (3e-2), and the first decode step's logits against a
  ``dense-ref`` engine on the same parameters and a float32 reference.
* ``fabric`` — ``run_lm_pipeline`` at P=2 over the queue channel on the same
  parameters for 2 requests, against the device engine.

``--chips 4`` runs ``pallas-bsr-sharded`` at P=8 over a 4-device ``worker``
mesh against a 1-device mesh (bitwise), and ``generate_stream`` over a
4-device ``seq`` mesh against ``mesh=None`` (final logits, and the sharded
decode op at 2e-2), and asserts that each device holds its own shard.

Model logits after 24 bf16 layers are gated by their distance to the
float32 reference, which may be at most ``NOISE_MARGIN`` times the oracle's
(see ``_logits_check``).

Each phase prints one JSON line: resolved backends and ``interpret`` flags,
host wall seconds split into compile (XLA compile or persistent-cache read,
from ``jax.monitoring``) and the rest, and its checks.  The last line is
``{"ok": true, "device": {...}}``.  With no TPU, or without the repository's
``src/`` beside it, the script exits non-zero and prints no result.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
import traceback
from pathlib import Path

ROOT = Path(__file__).resolve().parent
SEED = 0
OP_TOL = 3e-2           # bf16 envelope of one decode-attention call
SHARDED_TOL = 2e-2      # sequence-sharded decode op vs one device
NOISE_MARGIN = 1.5      # model logits: error vs f32, over the oracle's
FSI_TOL = 1e-4          # one layer's block products vs the dense oracle


FAILED = []              # one message per failed check or phase


def _check(cond: bool, msg: str) -> None:
    """Record a failed check and go on, so one run shows every failure."""
    if not cond:
        FAILED.append(msg)
        print(f"chip_smoke: CHECK FAILED: {msg}", file=sys.stderr, flush=True)


class _CompileClock:
    """Host wall seconds JAX spends before a program runs, from
    ``jax.monitoring``: tracing and lowering to MLIR (``trace_lower``,
    summed per jitted function, so nested traces count more than once; the
    persistent cache cannot save these) and the backend compile, which on a
    persistent-cache hit is the cache read (``backend_compile``).  Also
    counts the persistent cache's hits and misses.  Run time is timed
    apart, on a second call (``_warm_host_s``)."""

    DURATIONS = {"/jax/core/compile/jaxpr_trace_duration": "trace_lower",
                 "/jax/core/compile/jaxpr_to_mlir_module_duration":
                     "trace_lower",
                 "/jax/core/compile/backend_compile_duration":
                     "backend_compile"}
    COUNTS = {"/jax/compilation_cache/cache_hits": "cache_hits",
              "/jax/compilation_cache/cache_misses": "cache_misses"}

    def __init__(self):
        import jax

        self.seen = dict.fromkeys(("trace_lower", "backend_compile",
                                   "cache_hits", "cache_misses"), 0)
        jax.monitoring.register_event_duration_secs_listener(self._on_time)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_time(self, event, duration, **_):
        if event in self.DURATIONS:
            self.seen[self.DURATIONS[event]] += duration

    def _on_event(self, event, **_):
        if event in self.COUNTS:
            self.seen[self.COUNTS[event]] += 1

    def snapshot(self):
        return dict(self.seen)


def _warm_host_s(fn):
    """Host wall seconds of a repeat call of ``fn``, its programs compiled.
    The served entry points return host arrays, so the device has
    finished when it returns."""
    t0 = time.perf_counter()
    fn()
    return time.perf_counter() - t0


def _run_phase(clock, name, fn, *args):
    """Run one phase and print its line; a phase that raises is a failure,
    and the next phases still run.  Returns what the phase hands on, or
    None if it raised."""
    n_failed, c0, t0 = len(FAILED), clock.snapshot(), time.perf_counter()
    try:
        info = fn(*args)
    except Exception:
        traceback.print_exc()
        FAILED.append(f"phase {name} raised")
        return None
    wall = time.perf_counter() - t0
    spent = {k: v - c0[k] for k, v in clock.snapshot().items()}
    line = {"phase": name, **info.pop("_report", {}),
            "checks_passed": len(FAILED) == n_failed,
            "host_wall_s": wall,
            "host_trace_lower_s": spent["trace_lower"],
            "host_backend_compile_s": spent["backend_compile"],
            "persistent_cache_hits": spent["cache_hits"],
            "persistent_cache_misses": spent["cache_misses"]}
    print(json.dumps(line, default=float), flush=True)
    return info


# --------------------------------------------------------------------------
# default phases: one chip
# --------------------------------------------------------------------------


def _categories(x):
    import numpy as np

    return np.flatnonzero((x > 0).any(axis=0))


def phase_fsi():
    import numpy as np

    from repro.core.backends import get_backend
    from repro.data.graphchallenge import (
        GraphChallengeNet, dense_inference, make_inputs, make_sparse_dnn)
    from repro.faas.simulator import run_fsi
    from repro.launch.mesh import make_worker_mesh

    N, depth, batch, P = 1024, 120, 256, 4
    backend = get_backend("compute", None)
    _check(backend.name == "pallas-bsr-sharded" and backend.interpret is False,
           f"compute default resolved to {backend.state_key}")
    mesh = make_worker_mesh(1)
    net = make_sparse_dnn(N, n_layers=depth, seed=SEED, mode="radix")
    x0 = make_inputs(N, batch, seed=SEED)
    fsi = lambda: run_fsi(net, x0, P=P, channel="queue", mesh=mesh)
    res = fsi()
    warm_s = _warm_host_s(fsi)
    want = dense_inference(net, x0)
    out = res.output
    _check(out.shape == want.shape and np.isfinite(out).all(),
           f"fsi output {out.shape} not finite / not {want.shape}")
    cats, want_cats = _categories(out), _categories(want)
    cats_equal = bool(np.array_equal(cats, want_cats))
    _check(cats_equal,
           f"categories differ: {len(cats)} vs {len(want_cats)}")
    # One layer from a panel that is not bf16-exact (after 10 layers): the
    # block products' f32 precision, before depth amplifies rounding.
    x10 = dense_inference(GraphChallengeNet(N, net.layers[:10], net.bias), x0)
    one = GraphChallengeNet(N, net.layers[10:11], net.bias)
    one_err = float(np.abs(run_fsi(one, x10, P=P, channel="queue",
                                   mesh=mesh).output
                           - dense_inference(one, x10)).max())
    _check(one_err <= FSI_TOL, f"one-layer max abs error {one_err} > {FSI_TOL}")
    return {"_report": {
        "backend": backend.name, "interpret": backend.interpret,
        "mesh_devices": int(mesh.devices.size), "N": N, "layers": depth,
        "batch": batch, "P": P, "channel": "queue",
        "categories": int(len(cats)), "categories_equal": cats_equal,
        "max_abs_err_depth120": float(np.abs(out - want).max()),
        "elements_off_1e-4": int((np.abs(out - want) > FSI_TOL).sum()),
        "one_layer_max_abs_err": one_err, "run_fsi_warm_host_s": warm_s}}


def _vocab_ok(tokens, vocab):
    import numpy as np

    t = np.asarray(tokens)
    return bool(((t >= 0) & (t < vocab)).all())


def _f32_reference(cfg, params, run):
    """``run(engine)`` on the plain float32 reference: the same parameters
    cast to f32, ``dense-ref`` attention, every dot at full f32 precision."""
    import jax
    import jax.numpy as jnp

    from repro.serving.engine import ServingEngine

    p32 = jax.tree.map(lambda a: a.astype(jnp.float32)
                       if a.dtype == jnp.bfloat16 else a, params)
    with jax.default_matmul_precision("highest"):
        return run(ServingEngine(cfg, params=p32, attn_backend="dense-ref"))


def _logits_check(what, got, oracle, ref32):
    """Model-level logits gate.  After 24 bf16 layers, any two correct
    orders of the same sums land a few 1e-2 apart (two XLA attention
    oracles differ as much as the kernel does), so a fixed bf16 tolerance
    between two bf16 runs cannot tell a fault from rounding.  The gate is
    instead: ``got`` is no farther from the float32 reference than
    ``oracle`` is, up to ``NOISE_MARGIN``; a fault is off by far more."""
    import numpy as np

    e_got = float(np.abs(got - ref32).max())
    e_oracle = float(np.abs(oracle - ref32).max())
    _check(np.isfinite(got).all() and e_got <= NOISE_MARGIN * e_oracle,
           f"{what}: max abs err vs f32 reference {e_got}, oracle's "
           f"{e_oracle}")
    return {"max_abs_err_vs_oracle": float(np.abs(got - oracle).max()),
            "max_abs_err_vs_f32": e_got,
            "oracle_max_abs_err_vs_f32": e_oracle,
            "logits_absmax_f32": float(np.abs(ref32).max())}


def _decode_op_err(attn, H, KV, D):
    """One decode-attention call at the model's decode shapes, bf16 cache
    with ragged valid lengths: ``attn`` against ``dense-ref``."""
    import jax.numpy as jnp
    import numpy as np

    from repro.core.backends import get_backend

    rng = np.random.default_rng(SEED)
    B, cap = 4, 1024
    q, k, v = (jnp.asarray(rng.standard_normal(s), jnp.bfloat16)
               for s in ((B, 1, H, D), (B, KV, cap, D), (B, KV, cap, D)))
    ref = get_backend("attention", "dense-ref")
    errs = []
    for n in (1, 129, cap):
        a = np.asarray(attn.decode(q, k, v, n), np.float32)
        b = np.asarray(ref.decode(q, k, v, n), np.float32)
        errs.append(float(np.abs(a - b).max()))
    return max(errs)


def phase_serve():
    import numpy as np

    from repro.configs import get_config
    from repro.kernels import resolve_interpret
    from repro.serving.engine import ServingEngine
    from repro.serving.scheduler import Request

    cfg = get_config("internlm2-1.8b")
    V = cfg.vocab_size
    eng = ServingEngine(cfg, seed=SEED)
    attn = eng.attn_backend
    interpret = (resolve_interpret(attn.interpret)
                 if attn.name == "pallas-splitk" else None)
    _check(attn.name == "pallas-splitk" and interpret is False,
           f"attention default resolved to {attn.state_key}")
    rng = np.random.default_rng(SEED)
    lens = (64, 512, 128, 256, 64, 512, 128, 256)
    reqs = [Request(rid=i, prompt=rng.integers(0, V, n).astype(np.int32),
                    max_new_tokens=32) for i, n in enumerate(lens)]
    stream = eng.generate_stream(reqs, num_slots=4)
    _check(sorted(r.rid for r in stream) == list(range(len(reqs))),
           "stream lost requests")
    _check(all(len(r.tokens) == 32 and _vocab_ok(r.tokens, V)
               for r in stream), "stream tokens out of vocabulary")
    S = 128
    prompts = rng.integers(0, V, (4, S)).astype(np.int32)
    generate = lambda: eng.generate(prompts, max_new_tokens=32)
    gen = generate()
    warm_s = _warm_host_s(generate)
    _check(gen.tokens.shape == (4, 32) and _vocab_ok(gen.tokens, V),
           "generate tokens out of vocabulary")

    op_err = _decode_op_err(attn, cfg.n_heads, cfg.n_kv_heads, cfg.d_head)
    _check(op_err <= OP_TOL, f"decode op vs dense-ref: max abs err {op_err}")
    # First decode step: pallas-splitk and the dense-ref oracle on the same
    # parameters, at a capacity that is a block_k multiple so that both
    # prefill one shape.
    cap = 192
    first = lambda e: e.generate(prompts, max_new_tokens=1,
                                 max_len=cap).prefill_logits
    got = first(eng)
    oracle = first(ServingEngine(cfg, params=eng.params,
                                 attn_backend="dense-ref"))
    ref32 = _f32_reference(cfg, eng.params, first)
    logits = _logits_check("first-step logits, pallas-splitk", got, oracle,
                           ref32)
    return {"engine": eng, "prompts": prompts, "ref32": ref32, "_report": {
        "attn_backend": attn.name,
        "interpret": interpret, "model": cfg.name, "d_model": cfg.d_model, "layers": cfg.n_layers,
        "vocab": V, "param_dtype": str(eng.params["embed"].dtype),
        "stream_requests": len(stream), "stream_slots": 4,
        "stream_prompt_lens": list(lens), "new_tokens": 32,
        "generate_batch": 4, "generate_warm_host_s": warm_s,
        "decode_op_max_abs_err_vs_dense_ref": op_err,
        "first_step_logits": logits}}


def phase_fabric(eng, prompts, ref32):
    import numpy as np

    from repro.faas.lm_pipeline import run_lm_pipeline
    from repro.serving.router import route_attention_backend

    # One decode step: with random weights the top logits nearly tie, so a
    # bf16 rounding difference can flip a later greedy token and steer the
    # two runs apart; the first step's logits compare like for like.
    cfg, new = eng.cfg, 1
    two = prompts[:2]
    dev = eng.generate(two, max_new_tokens=new)
    pipeline = lambda: run_lm_pipeline(cfg, two, eng.params,
                                       max_new_tokens=new, P=2,
                                       channel="queue")
    fab = pipeline()
    warm_s = _warm_host_s(pipeline)
    logits = _logits_check("fabric first-step logits", fab.logits,
                           dev.prefill_logits, ref32[:2])
    return {"_report": {
        "attn_backend": route_attention_backend(cfg), "P": 2,
        "channel": "queue", "requests": 2, "new_tokens": new,
        "run_lm_pipeline_warm_host_s": warm_s,
        "tokens_equal_device": bool(np.array_equal(fab.tokens, dev.tokens)),
        "logits_equal_device": bool(np.array_equal(fab.logits,
                                                   dev.prefill_logits)),
        "first_step_logits": logits}}


# --------------------------------------------------------------------------
# --chips 4: what exists only across chips, against its one-chip twin
# --------------------------------------------------------------------------


def _own_shards(arr, n):
    devs = [s.device for s in arr.addressable_shards]
    return len(set(devs)) == n and len(devs) == n


def phase_fsi_4():
    import numpy as np

    from repro.core.backends import PallasBsrShardedBackend
    from repro.data.graphchallenge import make_inputs, make_sparse_dnn
    from repro.faas.simulator import run_fsi
    from repro.launch.mesh import make_worker_mesh

    class Probe(PallasBsrShardedBackend):
        """Keeps the placed fleet panels so their shards can be inspected."""

        def fleet_prepare_all(self, layer_states):
            self.states = super().fleet_prepare_all(layer_states)
            return self.states

    N, depth, batch, P = 1024, 120, 256, 8
    net = make_sparse_dnn(N, n_layers=depth, seed=SEED, mode="radix")
    x0 = make_inputs(N, batch, seed=SEED)
    one, four = (Probe(mesh=make_worker_mesh(d)) for d in (1, 4))
    _check(one.interpret is False, "pallas-bsr-sharded resolved interpret")
    y1 = run_fsi(net, x0, P=P, channel="queue", compute_backend=one).output
    fsi4 = lambda: run_fsi(net, x0, P=P, channel="queue",
                           compute_backend=four)
    y4 = fsi4().output
    warm_s = _warm_host_s(fsi4)
    same = bool(np.array_equal(y1, y4))
    _check(same, "4-device fleet differs from 1-device")
    blocks = four.states[0].blocks
    _check(_own_shards(blocks, 4)
           and all(s.data.shape[0] == P // 4
                   for s in blocks.addressable_shards),
           "fleet panels are not one shard per device")
    return {"_report": {
        "backend": four.name, "interpret": four.interpret, "P": P,
        "mesh_devices": [1, 4], "bitwise_equal": same,
        "run_fsi_4dev_warm_host_s": warm_s,
        "shard_devices": sorted(str(s.device)
                                for s in blocks.addressable_shards)}}


def phase_serve_4():
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec

    from repro.configs import get_config
    from repro.launch.mesh import make_mesh
    from repro.models.attention import sharded_decode_attend
    from repro.serving.engine import ServingEngine
    from repro.serving.scheduler import Request

    cfg = get_config("internlm2-1.8b")
    V = cfg.vocab_size
    eng = ServingEngine(cfg, seed=SEED)
    rng = np.random.default_rng(SEED)
    # One decode step per request: its logits are comparable across meshes
    # without a sampled token (a near-tie flips after a few steps) steering
    # the two runs apart.  6 requests over 4 slots: admission after a retire.
    lens = (64, 256, 128, 448, 192, 320)
    reqs = [Request(rid=i, prompt=rng.integers(0, V, n).astype(np.int32),
                    max_new_tokens=1) for i, n in enumerate(lens)]
    cap = 512                     # 4 shards of 128, each a block_k multiple
    mesh = make_mesh((4,), ("seq",))

    def stream(e, **kw):
        res = sorted(e.generate_stream(reqs, num_slots=4,
                                       max_request_len=cap, **kw),
                     key=lambda r: r.rid)
        return (np.stack([r.final_logits for r in res]),
                np.stack([r.tokens for r in res]))

    one, one_tokens = stream(eng)
    four, four_tokens = stream(eng, mesh=mesh)
    warm_s = _warm_host_s(lambda: stream(eng, mesh=mesh))
    ref32, _ = _f32_reference(cfg, eng.params, stream)
    logits = _logits_check("seq-sharded stream logits", four, one, ref32)

    # The op the sharded step runs, at internlm2's decode shape, with the
    # cache laid over the mesh: one S/4 shard per device.
    H, KV, D = cfg.n_heads, cfg.n_kv_heads, cfg.d_head
    k, v = (jnp.asarray(rng.standard_normal((1, KV, cap, D)), jnp.bfloat16)
            for _ in range(2))
    q = jnp.asarray(rng.standard_normal((1, 1, H, D)), jnp.bfloat16)
    kn, vn = (jnp.asarray(rng.standard_normal((1, KV, 1, D)), jnp.bfloat16)
              for _ in range(2))
    pos = jnp.asarray(cap - 100, jnp.int32)
    spec = PartitionSpec(None, None, "seq", None)

    def attend(m):
        body = lambda q, k, v, p: sharded_decode_attend(
            eng.attn_backend, q, kn, vn, k, v, p, "seq")[0]
        f = jax.jit(jax.shard_map(body, mesh=m, in_specs=(
            PartitionSpec(), spec, spec, PartitionSpec()),
            out_specs=PartitionSpec(), check_vma=False))
        ks = jax.device_put(k, NamedSharding(m, spec))
        return ks, np.asarray(f(q, ks, jax.device_put(v, NamedSharding(
            m, spec)), pos))

    k4, o4 = attend(mesh)
    _, o1 = attend(make_mesh((1,), ("seq",)))
    _check(_own_shards(k4, 4)
           and all(s.data.shape[2] == cap // 4 for s in k4.addressable_shards),
           "KV cache is not one S/4 shard per device")
    op_err = float(np.abs(o4 - o1).max())
    _check(op_err <= SHARDED_TOL, f"sharded decode op err {op_err}")
    return {"_report": {
        "attn_backend": eng.attn_backend.name, "mesh_devices": [1, 4],
        "requests": len(reqs), "slot_capacity": cap,
        "tokens_equal": bool(np.array_equal(four_tokens, one_tokens)),
        "stream_4dev_warm_host_s": warm_s,
        "final_logits": logits, "op_max_abs_err": op_err,
        "shard_devices": sorted(str(s.device) for s in k4.addressable_shards)}}


# --------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the multi-chip paths and their twins")
    args = ap.parse_args(argv)

    sys.path.insert(0, str(ROOT / "src"))
    try:
        from repro.launch import compile_cache
    except ImportError as e:
        print(f"chip_smoke: the repository's src/ is not beside this script "
              f"({e})", file=sys.stderr)
        return 2
    if not Path(compile_cache.__file__).resolve().is_relative_to(ROOT / "src"):
        print(f"chip_smoke: imported repro from {compile_cache.__file__}, "
              f"not from {ROOT / 'src'}", file=sys.stderr)
        return 2

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX platform: "
              f"{devices[0].platform})", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} devices, "
              f"found {len(devices)}", file=sys.stderr)
        return 1
    print(json.dumps({"compile_cache": compile_cache.use_compile_cache()}),
          flush=True)
    clock = _CompileClock()
    if args.chips == 4:
        _run_phase(clock, "fsi_4chip", phase_fsi_4)
        _run_phase(clock, "serve_4chip", phase_serve_4)
    else:
        _run_phase(clock, "fsi", phase_fsi)
        served = _run_phase(clock, "serve", phase_serve)
        if served is None:
            FAILED.append("phase fabric not run: serve raised")
        else:
            _run_phase(clock, "fabric", phase_fabric, served["engine"],
                       served["prompts"], served["ref32"])
    if FAILED:
        print(f"chip_smoke: FAILED: {len(FAILED)} check(s): "
              + "; ".join(FAILED), file=sys.stderr)
        return 1
    d = jax.devices()
    print(json.dumps({"ok": True, "device": {
        "platform": d[0].platform, "kind": d[0].device_kind,
        "count": len(d)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
