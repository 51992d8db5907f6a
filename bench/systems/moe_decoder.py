"""Offline batch serving of a DeepSeekMoE decoder's cut, one chip's share
of its experts, through the program's continuous-batching scheduler.

As ``dense_decoder`` serves a dense decoder, with the moe family's
weights and configuration: set-up makes the weights on the device from
the seed, builds one ``ServingEngine`` and one ``RequestScheduler`` the
way ``ServingEngine.generate_stream`` builds it, and warms every shape
the mix can draw; the window hands the scheduler whole batches; the check
runs the plain f32 reference (``bench/refs/moe_decoder.py``) over a sample
of the finished requests.  The scheduler's counters ``moe_rows`` and
``moe_groups_read`` give the work of the grouped expert kernel.
"""

from __future__ import annotations

import sys
import time
from typing import Dict, List

import numpy as np

from bench import counts, moe_counts, traffic_gen
from bench.refs import moe_decoder as ref
from bench.systems import dense_decoder

E2E_METRIC = dense_decoder.E2E_METRIC


def program_config(config: dict):
    """The program's ``ModelConfig`` for a configuration file."""
    from repro.configs.base import ModelConfig

    d = ref.dims(config)
    return ModelConfig(
        name=config["name"], family="moe", n_layers=d["L"], d_model=d["D"],
        n_heads=d["H"], n_kv_heads=d["KV"], d_head=d["Dh"], d_ff=d["F"],
        vocab_size=d["V"], n_experts=d["E"], n_shared_experts=d["shared"],
        experts_per_token=d["k"], moe_d_ff=d["f"],
        first_dense_layers=d["first_dense"], moe_norm_topk=d["norm_topk"],
        held_experts=(d["e0"], d["e0"] + d["held"]), rope_theta=d["theta"],
        norm_eps=d["eps"], tie_embeddings=d["tied"])


def make_params(config: dict, seed: int):
    """Weights in the layout the program's moe family serves, made on the
    device in one jitted call, in bf16: the dense layers' stack, then the
    expert layers' stack with the router at every routed expert's output
    and the held experts' weights.  The vocabulary is padded to a multiple
    of 256 with zero rows."""
    import jax
    import jax.numpy as jnp

    d = ref.dims(config)
    D, H, KV, Dh, V = d["D"], d["H"], d["KV"], d["Dh"], d["V"]
    Vp = -(-V // 256) * 256
    dt = jnp.bfloat16

    def init(key):
        ks = iter(jax.random.split(key, 32))

        def normal(shape, scale):
            return (jax.random.normal(next(ks), shape, jnp.float32)
                    * scale).astype(dt)

        def norm(shape):
            return (1.0 + 0.05 * jax.random.normal(next(ks), shape,
                                                   jnp.float32)).astype(dt)

        def table():
            return normal((Vp, D), 0.02).at[V:].set(0)

        def mlp(lead, F):
            return {"wi_gate": normal(lead + (D, F), D ** -0.5),
                    "wi_up": normal(lead + (D, F), D ** -0.5),
                    "wo": normal(lead + (F, D), F ** -0.5)}

        def block(n):
            return {"ln_attn": norm((n, D)),
                    "attn": {"wq": normal((n, D, H, Dh), D ** -0.5),
                             "wk": normal((n, D, KV, Dh), D ** -0.5),
                             "wv": normal((n, D, KV, Dh), D ** -0.5),
                             "wo": normal((n, H, Dh, D), (H * Dh) ** -0.5)},
                    "ln_mlp": norm((n, D))}

        n_moe, f, E, held = (d["L"] - d["first_dense"], d["f"], d["E"],
                             d["held"])
        moe = {"router": normal((n_moe, D, E), D ** -0.5),
               "w_gate": normal((n_moe, held, D, f), D ** -0.5),
               "w_up": normal((n_moe, held, D, f), D ** -0.5),
               "w_down": normal((n_moe, held, f, D), f ** -0.5),
               "shared": mlp((n_moe,), f * d["shared"])}
        params = {"embed": table(),
                  "dense_blocks": (dict(block(d["first_dense"]),
                                        mlp=mlp((d["first_dense"],), d["F"]))
                                   if d["first_dense"] else None),
                  "moe_blocks": dict(block(n_moe), moe=moe),
                  "ln_f": norm((D,))}
        if not d["tied"]:
            params["unembed"] = table()
        return params

    key = jax.random.key(int(traffic_gen.rng_for(seed, 0).integers(2**31)))
    params = jax.jit(init)(key)
    jax.block_until_ready(params)
    return params


class Cell(dense_decoder.Cell):
    """One configuration under one offline-batch mix."""

    def __init__(self, config: dict, mix: dict, seed: int, spans):
        super().__init__(config, mix, seed, spans)
        self.dims = ref.dims(config)
        self.moe0: Dict[str, int] = {}    # the counters when tracing began

    def setup(self) -> None:
        import jax.numpy as jnp

        from repro.serving.engine import ServingEngine
        from repro.serving.kv_pool import split_cache
        from repro.serving.scheduler import Request, RequestScheduler

        self.Request = Request
        mix = self.mix
        t0 = time.perf_counter()
        self.params = make_params(self.config, self.seed)
        t_weights = time.perf_counter() - t0
        eng = ServingEngine(program_config(self.config), params=self.params)
        self.attn_backend = eng.attn_backend.name
        layout = eng.cache_layout(int(mix["capacity"]))
        cap = layout.padded_len(int(mix["capacity"]))
        self.block_k, self.capacity = int(layout.block_k), cap
        engine_prefill = eng._prefill

        def prefill(params, batch, max_len):
            if self.tracer is not None and self.tracer.active:
                self.trace_prompts.append(int(batch["tokens"].shape[1]))
            with self.spans("bench.prefill"):
                return engine_prefill(params, batch, max_len)

        self.sched = RequestScheduler(
            eng.model, eng.params, prefill, num_slots=int(mix["num_slots"]),
            slot_capacity=cap, layout=layout)
        self.engine = eng

        # Warm-up: every prompt length's prefill, the decode step and
        # retirement, and every page count admission can ask for.
        prompts, _ = traffic_gen.lm_sizes(mix)
        lengths = sorted(set(prompts))
        warm = [Request(rid=i, prompt=np.zeros(lengths[i % len(lengths)],
                                               np.int32), max_new_tokens=2)
                for i in range(max(self.sched.num_slots, len(lengths)))]
        self.sched.run(warm)
        cache = eng._prefill(self.params, {"tokens": jnp.zeros(
            (1, lengths[0]), jnp.int32)}, cap)[1]
        paged, _ = split_cache(cache, self.sched.seq_axes)
        for n in traffic_gen.lm_page_counts(mix, self.block_k):
            table = self.sched.pool.admit(paged, n * self.block_k)
            self.sched.pool.retire(table, n)
        del cache, paged
        # One whole batch of the mix itself, so the window's first batch is
        # not the first the program serves (its first real batch ran 1-2 s
        # slower in two of four runs on a TPU v5e).
        self.run_unit()
        self.done, self.attempted, self.failed = [], 0, 0
        print(f"moe_decoder: weights {t_weights:.2f} s, scheduler and "
              f"warm-up {time.perf_counter() - t0 - t_weights:.2f} s, "
              f"attention {self.attn_backend}, capacity {cap}, block "
              f"{self.block_k}", file=sys.stderr, flush=True)
        self.steps0 = self.sched.steps_run
        self.tokens0 = self.sched.tokens_emitted
        self.moe_base = dict(self.sched.device_counters)

    def arm_trace(self, tracer) -> None:
        self.moe0 = dict(self.sched.device_counters)
        super().arm_trace(tracer)

    def trace_counts(self) -> Dict[str, float]:
        """What the traced slice needed: the decode-attention kernel's
        FLOPs and bytes, the grouped expert kernel's from the scheduler's
        counters over the slice (whole batches), and the forward FLOPs of
        this cut: every matmul of every token, the routed experts' rows
        as counted."""
        d = self.dims
        out = {"decode_attn_flops": 0.0, "decode_attn_bytes": 0.0,
               "forward_flops": 0.0}
        for length in self.trace_lens:
            att = counts.decode_token(d, length)
            out["decode_attn_flops"] += att["decode_attn_flops"]
            out["decode_attn_bytes"] += att["decode_attn_bytes"]
            out["forward_flops"] += moe_counts.decode_token(
                d, length)["forward_flops"]
        for prompt in self.trace_prompts:
            out["forward_flops"] += moe_counts.prefill(
                d, prompt)["forward_flops"]
        now = self.sched.device_counters
        gmm = moe_counts.experts(
            d, now["moe_rows"] - self.moe0.get("moe_rows", 0),
            now["moe_groups_read"] - self.moe0.get("moe_groups_read", 0))
        out.update(gmm)
        out["forward_flops"] += gmm["moe_gmm_flops"]
        out["slots"] = float(self.mix["num_slots"])
        return out

    def counters(self) -> Dict[str, float]:
        out = super().counters()
        for name, n in self.sched.device_counters.items():
            out[name] = float(n - self.moe_base.get(name, 0))
        return out

    def check(self, limits: dict) -> List[dict]:
        """The widest gap by which a served token's reference logit lies
        below the reference's best, over the sample."""
        gaps = []
        n_tokens = 0
        for prompt, res in self.sample():
            g = ref.served_token_gaps(self.params, self.config, prompt,
                                      res.tokens, self.capacity)
            gaps.append(float(np.max(g)))
            n_tokens += len(res.tokens)
        limit = float(limits["token_gap"])
        widest = max(gaps) if gaps else float("inf")
        return [{"name": "token_gap", "value": widest, "limit": limit,
                 "tokens": n_tokens, "requests": len(gaps)}]


def control(cell) -> dict:
    """The check's number for the control, on the same sample: at each
    served position, the gap of the token that the reference with every
    matmul operand in fp8 puts first."""
    gaps = [float(np.max(ref.control_gaps(cell.params, cell.config, prompt,
                                          res.tokens, cell.capacity)))
            for prompt, res in cell.sample()]
    return {"control_fp8": max(gaps)}
