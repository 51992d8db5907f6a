import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

For each cell this driver:

1. builds the production mesh (16×16 single-pod, or 2×16×16 multi-pod);
2. derives parameter / optimizer / batch / cache PartitionSpecs;
3. ``jax.jit(step).lower(**ShapeDtypeStructs).compile()`` — nothing is ever
   allocated; success proves the sharding config is coherent end-to-end;
4. prints ``memory_analysis()`` (fits-in-HBM evidence) and
   ``cost_analysis()`` (FLOPs/bytes), and parses the compiled HLO for
   collective operand bytes;
5. emits the three roofline terms for EXPERIMENTS.md §Roofline.

Usage:
    python -m repro.launch.dryrun --arch internlm2-1.8b --shape train_4k
    python -m repro.launch.dryrun --all [--multi-pod] --json out.json
"""

import argparse
import dataclasses
import json
import re
import sys
import time
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import SHAPES, get_config, get_shape, list_archs
from repro.core.cost_model import TPU_V5E
from repro.distributed.costing import (
    analytic_hbm_bytes,
    collective_bytes,
    traced_flops,
)
from repro.distributed.sharding import (
    batch_pspecs,
    cache_pspecs,
    param_pspecs,
    to_named,
)
from repro.launch.mesh import MeshAxes, make_production_mesh
from repro.models.registry import cache_specs, get_model, input_specs
from repro.training.optimizer import get_optimizer
from repro.training.train_state import make_train_step
from jax.sharding import NamedSharding, PartitionSpec as P


def tree_bytes(tree) -> float:
    return float(sum(
        np.prod(l.shape, initial=1.0) * np.dtype(l.dtype).itemsize
        for l in jax.tree.leaves(tree)
        if hasattr(l, "shape")
    ))

# archs whose quadratic attention rules out the 512k decode cell (the shape
# sheet's own rule); recorded as SKIP in the sweep output.
LONG_CONTEXT_ARCHS = ("zamba2-7b", "mamba2-370m")

# Gradient-accumulation microbatch counts per train cell.  Measured finding
# (EXPERIMENTS.md §Perf): XLA's wide-loop buffer assignment keeps every
# microbatch's remat stash live simultaneously on this backend, so
# microbatching *increases* temp memory — default is therefore 1, and the
# hillclimb explores per-device batch via the pod axis instead.
TRAIN_MICROBATCHES = {}

@dataclasses.dataclass
class CellReport:
    arch: str
    shape: str
    mesh: str
    status: str                      # ok | skip | error
    note: str = ""
    compile_s: float = 0.0
    flops_per_device: float = 0.0    # jaxpr-traced, global / n_dev
    hbm_bytes_per_device: float = 0.0  # analytic minimal traffic
    hlo_flops_per_device: float = 0.0  # raw XLA number (while bodies ×1)
    hlo_bytes_per_device: float = 0.0
    collective_bytes: Optional[Dict[str, float]] = None
    collective_total: float = 0.0
    argument_bytes: float = 0.0
    output_bytes: float = 0.0
    temp_bytes: float = 0.0
    compute_term_s: float = 0.0
    memory_term_s: float = 0.0
    collective_term_s: float = 0.0
    bottleneck: str = ""
    model_flops: float = 0.0
    model_flops_ratio: float = 0.0
    fits_hbm: bool = True

    def to_dict(self):
        return dataclasses.asdict(self)


def _should_skip(arch: str, shape_name: str) -> Optional[str]:
    cfg = get_config(arch)
    if shape_name == "long_500k" and arch not in LONG_CONTEXT_ARCHS:
        return ("full-attention arch: 512k decode requires sub-quadratic "
                "attention (shape-sheet rule; DESIGN.md §5)")
    return None


def model_flops_for(cfg, shape) -> float:
    """MODEL_FLOPS: 6·N·D for train, 2·N·D for inference (N = active params)."""
    n = cfg.active_param_count()
    if shape.kind == "train":
        return 6.0 * n * shape.tokens
    if shape.kind == "prefill":
        return 2.0 * n * shape.tokens
    return 2.0 * n * shape.global_batch  # decode: one token per sequence


def build_cell(arch: str, shape_name: str, mesh, strategy: str = "tp") -> Any:
    """Returns (jitted_fn, raw_fn, args tuple of ShapeDtypeStructs, aux).

    ``strategy``: "tp" (baseline) or "zero" (§Perf ZeRO-3 pure-DP)."""
    cfg = get_config(arch)
    shape = get_shape(shape_name)
    ax = MeshAxes(mesh)
    n_dev = mesh.size
    model = get_model(cfg)
    fsdp = cfg.param_count() * 2 > 8e9  # params above ~8GB must shard 2D
    pshape = jax.eval_shape(model.init, jax.random.key(0))
    pspecs = param_pspecs(cfg, pshape, ax, fsdp=fsdp, strategy=strategy)
    if strategy == "zero":
        from repro.models import layers as _L

        ax = ax.as_pure_dp()        # batch over every axis; no TP axis
        _L.set_shard_ctx(mesh, ax.dp, None)
    if strategy == "bf16coll":
        from repro.models import layers as _L
        import jax.numpy as _jnp

        _L.set_tp_psum_dtype(_jnp.bfloat16)
    else:
        from repro.models import layers as _L
        import jax.numpy as _jnp

        _L.set_tp_psum_dtype(_jnp.float32)
    from repro.models import moe as _moe

    _moe.set_moe_ep_shardmap(strategy == "ep")
    param_bytes_dev = tree_bytes(pshape) / n_dev
    tokens_dev = shape.tokens / n_dev

    if shape.kind == "train":
        opt = get_optimizer(cfg)
        oshape = jax.eval_shape(opt.init, pshape)
        ospecs = opt.state_pspecs(pspecs, pshape)
        batch = input_specs(cfg, shape, abstract=True)
        bspecs = batch_pspecs(cfg, shape, batch, ax)
        mb = TRAIN_MICROBATCHES.get(arch, 1)
        if cfg.family == "moe":
            loss = lambda p, b: model.loss_fn(p, b, dp_groups=ax.dp_size)
        else:
            loss = model.loss_fn
        step = make_train_step(loss, opt, microbatches=mb,
                               grad_shardings=to_named(mesh, pspecs))
        jf = jax.jit(
            step,
            in_shardings=(to_named(mesh, pspecs), to_named(mesh, ospecs),
                          to_named(mesh, bspecs)),
            donate_argnums=(0, 1),
        )
        n_blocks = cfg.n_layers + cfg.n_encoder_layers
        aux = {
            "param_bytes_dev": param_bytes_dev,
            "opt_bytes_dev": tree_bytes(oshape) / n_dev,
            "stash_bytes_dev": n_blocks * tokens_dev * cfg.d_model * 2.0,
            "cache_bytes_dev": 0.0,
            "io_bytes_dev": tree_bytes(batch) / n_dev,
        }
        return jf, step, (pshape, oshape, batch), aux

    if shape.kind == "prefill":
        batch = input_specs(cfg, shape, abstract=True)
        bspecs = batch_pspecs(cfg, shape, batch, ax)
        max_len = shape.seq_len  # cache capacity = prompt length here
        fn = lambda p, b: model.prefill(p, b, max_len)
        cache_shape = cache_specs(cfg, shape, abstract=True)
        cspecs = cache_pspecs(cfg, shape, cache_shape, ax)
        _, cache_struct = jax.eval_shape(fn, pshape, batch)
        cspecs_aligned = _align_specs(cache_struct, cspecs, cfg, shape, ax)
        jf = jax.jit(
            fn,
            in_shardings=(to_named(mesh, pspecs), to_named(mesh, bspecs)),
            out_shardings=(None, to_named(mesh, cspecs_aligned)),
        )
        aux = {
            "param_bytes_dev": param_bytes_dev,
            "opt_bytes_dev": 0.0,
            "stash_bytes_dev": 2 * tokens_dev * cfg.d_model * 2.0,
            "cache_bytes_dev": tree_bytes(cache_struct) / n_dev,
            "io_bytes_dev": tree_bytes(batch) / n_dev,
        }
        return jf, fn, (pshape, batch), aux

    # decode
    batch = input_specs(cfg, shape, abstract=True)
    cache = cache_specs(cfg, shape, abstract=True)
    cspecs = cache_pspecs(cfg, shape, cache, ax)
    tok_spec = batch_pspecs(cfg, shape, batch, ax)
    fn = model.decode_step
    jf = jax.jit(
        fn,
        in_shardings=(to_named(mesh, pspecs), to_named(mesh, tok_spec["token"]),
                      to_named(mesh, cspecs)),
        out_shardings=(None, to_named(mesh, cspecs)),
        donate_argnums=(2,),
    )
    aux = {
        "param_bytes_dev": param_bytes_dev,
        "opt_bytes_dev": 0.0,
        "stash_bytes_dev": 0.0,
        "cache_bytes_dev": tree_bytes(cache) / n_dev,
        "io_bytes_dev": tree_bytes(batch) / n_dev,
    }
    return jf, fn, (pshape, batch["token"], cache), aux


def _align_specs(struct, spec_tree, cfg, shape, ax):
    """Prefill cache structure may differ from registry.cache_specs (it *is*
    the same by construction); fall back to replicated for any mismatch."""
    try:
        jax.tree.map(lambda a, b: None, struct, spec_tree)
        return spec_tree
    except Exception:
        return jax.tree.map(lambda _: P(), struct)


def run_cell(arch: str, shape_name: str, multi_pod: bool = False,
             mesh=None, verbose: bool = True,
             strategy: str = "tp") -> CellReport:
    mesh_name = ("2x16x16" if multi_pod else "16x16") + (
        "" if strategy == "tp" else f"+{strategy}")
    skip = _should_skip(arch, shape_name)
    if skip:
        return CellReport(arch=arch, shape=shape_name, mesh=mesh_name,
                          status="skip", note=skip)
    mesh = mesh if mesh is not None else make_production_mesh(multi_pod=multi_pod)
    cfg = get_config(arch)
    shape = get_shape(shape_name)
    n_dev = mesh.size
    t0 = time.time()
    try:
        from repro.models import layers as _L

        ax0 = MeshAxes(mesh)
        _L.set_shard_ctx(mesh, ax0.dp, ax0.model)
        jf, raw_fn, args, aux = build_cell(arch, shape_name, mesh,
                                           strategy=strategy)
        with mesh:
            lowered = jf.lower(*args)
            compiled = lowered.compile()
        dt = time.time() - t0
        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis() or {}
        if isinstance(cost, (list, tuple)):  # jax ≤ 0.4.x: one dict per device
            cost = cost[0] if cost else {}
        hlo_flops = float(cost.get("flops", 0.0)) / n_dev
        hlo_bytes = float(cost.get("bytes accessed", 0.0)) / n_dev
        # jaxpr-traced global flops (correct across scan bodies)
        flops = traced_flops(raw_fn, *args) / n_dev
        byts = analytic_hbm_bytes(kind=shape.kind, **aux)
        coll, coll_total = collective_bytes(compiled.as_text())
        compute_term = flops / TPU_V5E.peak_bf16_flops
        memory_term = byts / TPU_V5E.hbm_bandwidth
        # per-device collective bytes over 3 usable ICI links per direction
        collective_term = coll_total / (3 * TPU_V5E.ici_link_bandwidth)
        terms = {"compute": compute_term, "memory": memory_term,
                 "collective": collective_term}
        bottleneck = max(terms, key=terms.get)
        arg_b = float(getattr(mem, "argument_size_in_bytes", 0))
        out_b = float(getattr(mem, "output_size_in_bytes", 0))
        tmp_b = float(getattr(mem, "temp_size_in_bytes", 0))
        mf = model_flops_for(cfg, shape)
        report = CellReport(
            arch=arch, shape=shape_name, mesh=mesh_name, status="ok",
            compile_s=dt,
            flops_per_device=flops, hbm_bytes_per_device=byts,
            hlo_flops_per_device=hlo_flops, hlo_bytes_per_device=hlo_bytes,
            collective_bytes=coll, collective_total=coll_total,
            argument_bytes=arg_b, output_bytes=out_b, temp_bytes=tmp_b,
            compute_term_s=compute_term, memory_term_s=memory_term,
            collective_term_s=collective_term, bottleneck=bottleneck,
            model_flops=mf,
            model_flops_ratio=(mf / (flops * n_dev)) if flops else 0.0,
            fits_hbm=(arg_b + out_b + tmp_b) <= TPU_V5E.hbm_bytes,
        )
        if verbose:
            print(f"[{arch} × {shape_name} × {mesh_name}] OK "
                  f"compile={dt:.1f}s flops/dev={flops:.3e} "
                  f"hbm_bytes/dev={byts:.3e} coll/dev={coll_total:.3e}")
            print(f"  memory_analysis: args={arg_b/1e9:.2f}GB out={out_b/1e9:.2f}GB "
                  f"temp={tmp_b/1e9:.2f}GB fits_hbm={report.fits_hbm}")
            print(f"  roofline terms (s): compute={compute_term:.4f} "
                  f"memory={memory_term:.4f} collective={collective_term:.4f} "
                  f"→ {bottleneck}-bound; model_flops_ratio="
                  f"{report.model_flops_ratio:.2f}")
        return report
    except Exception as e:  # noqa: BLE001 — report, don't crash the sweep
        import traceback
        note = f"{type(e).__name__}: {e}"
        if verbose:
            print(f"[{arch} × {shape_name} × {mesh_name}] ERROR {note}")
            traceback.print_exc()
        return CellReport(arch=arch, shape=shape_name, mesh=mesh_name,
                          status="error", note=note[:2000],
                          compile_s=time.time() - t0)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None, choices=list(SHAPES) + [None])
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--json", default=None)
    args = ap.parse_args(argv)

    archs = list_archs() if (args.all or not args.arch) else [args.arch]
    shapes = list(SHAPES) if (args.all or not args.shape) else [args.shape]
    meshes = [False, True] if args.both_meshes else [args.multi_pod]

    reports = []
    for mp in meshes:
        mesh = make_production_mesh(multi_pod=mp)
        for arch in archs:
            for shape in shapes:
                reports.append(run_cell(arch, shape, multi_pod=mp, mesh=mesh))
    ok = sum(r.status == "ok" for r in reports)
    sk = sum(r.status == "skip" for r in reports)
    er = sum(r.status == "error" for r in reports)
    print(f"\n=== dry-run sweep: {ok} ok / {sk} skip / {er} error ===")
    if args.json:
        with open(args.json, "w") as f:
            json.dump([r.to_dict() for r in reports], f, indent=1)
        print(f"wrote {args.json}")
    return 0 if er == 0 else 1


if __name__ == "__main__":
    sys.exit(main())
