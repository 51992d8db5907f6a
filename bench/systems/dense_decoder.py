"""Offline batch serving of a dense decoder through the program's
continuous-batching scheduler.

Set-up makes the weights on the device from the seed, builds one
``ServingEngine`` and one ``RequestScheduler`` the way
``ServingEngine.generate_stream`` builds it (the engine's layout, the
``padded_len`` capacity, a pool sized for full occupancy), and warms every
shape the mix can draw.  The window hands the scheduler whole batches, back
to back, and counts the tokens they generate.  The check runs the plain f32
reference over a sample of the finished requests.
"""

from __future__ import annotations

import sys
import time
from typing import Dict, List

import numpy as np

from bench import counts, traffic_gen
from bench.refs import dense_decoder as ref

E2E_METRIC = "lm_tokens_per_s"


def program_config(config: dict):
    """The program's ``ModelConfig`` for a configuration file."""
    from repro.configs.base import ModelConfig

    return ModelConfig(
        name=config["name"], family="dense",
        n_layers=config["num_hidden_layers"],
        d_model=config["hidden_size"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        d_head=config["hidden_size"] // config["num_attention_heads"],
        d_ff=config["intermediate_size"],
        vocab_size=config["vocab_size"],
        rope_theta=float(config["rope_theta"]),
        norm_eps=float(config["rms_norm_eps"]),
        tie_embeddings=bool(config["tie_word_embeddings"]))


def make_params(config: dict, seed: int):
    """Weights in the layout the program's dense family serves, made on the
    device in one jitted call, in the type they are served in (bf16).  The
    vocabulary is padded to a multiple of 256 with zero rows, as a
    checkpoint loader pads it, so no padded id can win an argmax."""
    import jax
    import jax.numpy as jnp

    dims = ref.dims(config)
    L, D, H, KV, Dh, F, V = (dims[k] for k in ("L", "D", "H", "KV", "Dh",
                                              "F", "V"))
    Vp = -(-V // 256) * 256
    dt = jnp.bfloat16

    def init(key):
        ks = iter(jax.random.split(key, 16))

        def normal(shape, scale):
            return (jax.random.normal(next(ks), shape, jnp.float32)
                    * scale).astype(dt)

        def norm(shape):
            return (1.0 + 0.05 * jax.random.normal(next(ks), shape,
                                                   jnp.float32)).astype(dt)

        def table():
            t = normal((Vp, D), 0.02)
            return t.at[V:].set(0)

        params = {
            "embed": table(),
            "blocks": {
                "ln_attn": norm((L, D)),
                "attn": {"wq": normal((L, D, H, Dh), D ** -0.5),
                         "wk": normal((L, D, KV, Dh), D ** -0.5),
                         "wv": normal((L, D, KV, Dh), D ** -0.5),
                         "wo": normal((L, H, Dh, D), (H * Dh) ** -0.5)},
                "ln_mlp": norm((L, D)),
                "mlp": {"wi_gate": normal((L, D, F), D ** -0.5),
                        "wi_up": normal((L, D, F), D ** -0.5),
                        "wo": normal((L, F, D), F ** -0.5)},
            },
            "ln_f": norm((D,)),
        }
        if not config["tie_word_embeddings"]:
            params["unembed"] = table()
        return params

    key = jax.random.key(int(traffic_gen.rng_for(seed, 0).integers(2**31)))
    params = jax.jit(init)(key)
    jax.block_until_ready(params)
    return params


class Cell:
    """One configuration under one offline-batch mix."""

    e2e_metric = E2E_METRIC

    def __init__(self, config: dict, mix: dict, seed: int, spans):
        self.config, self.mix, self.seed, self.spans = config, mix, seed, spans
        self.dims = ref.dims(config)
        self.done: List[tuple] = []       # (prompt, RequestResult)
        self.tracer = None
        self.trace_lens: List[int] = []   # traced decode: valid positions
        self.trace_prompts: List[int] = []  # traced prefills: prompt length
        self.n_batches = 0
        self.attempted = 0
        self.failed = 0

    # -- set-up -----------------------------------------------------------

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
        # As ServingEngine.generate_stream builds its scheduler, once.
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

        # Warm-up: every prompt length at the slot capacity in every slot,
        # the decode step, retirement, and every page count admission can
        # ask the pool for.
        prompts, outs = traffic_gen.lm_sizes(mix)
        lengths = sorted(set(prompts))
        warm = [Request(rid=i, prompt=np.zeros(lengths[i % len(lengths)],
                                               np.int32), max_new_tokens=2)
                for i in range(max(self.sched.num_slots, len(lengths)))]
        self.sched.run(warm)
        _, cache = eng._prefill(self.params, {"tokens": jnp.zeros(
            (1, lengths[0]), jnp.int32)}, cap)
        paged, _ = split_cache(cache, self.sched.seq_axes)
        for n in traffic_gen.lm_page_counts(mix, self.block_k):
            table = self.sched.pool.admit(paged, n * self.block_k)
            self.sched.pool.retire(table, n)
        del cache, paged
        print(f"dense_decoder: weights {t_weights:.2f} s, scheduler and "
              f"warm-up {time.perf_counter() - t0 - t_weights:.2f} s, "
              f"attention {self.attn_backend}, capacity {cap}, block "
              f"{self.block_k}", file=sys.stderr, flush=True)
        self.steps0 = self.sched.steps_run
        self.tokens0 = self.sched.tokens_emitted

    # -- window -----------------------------------------------------------

    def run_unit(self) -> float:
        """Serve the next whole batch; returns its generated tokens."""
        reqs = traffic_gen.lm_batch(self.mix, self.seed, self.n_batches,
                                    self.dims["V"])
        base = self.n_batches * len(reqs)
        batch = [self.Request(rid=base + i, prompt=p, max_new_tokens=n)
                 for i, (p, n) in enumerate(reqs)]
        self.attempted += len(batch)
        with self.spans("bench.lm_batch"):
            results = self.sched.run(batch)
        by_rid = {r.rid: r for r in results}
        tokens = 0
        for req in batch:
            res = by_rid.get(req.rid)
            if res is None or len(res.tokens) != req.max_new_tokens:
                self.failed += 1
                continue
            self.done.append((req.prompt, res))
            tokens += len(res.tokens)
        self.n_batches += 1
        return float(tokens)

    def arm_trace(self, tracer) -> None:
        """Trace the whole window (at least one whole batch, its prefills
        and its decode steps) from now.  For each decode step the cache
        length of every active slot is read from the scheduler's host-side
        slots (no device sync), so the work the window needed can be
        counted."""
        sched, real = self.sched, self.sched._step_fn
        self.tracer = tracer

        def step(*args):
            if tracer.active:
                self.trace_lens.extend(
                    len(st.request.prompt) + len(st.tokens) + 1
                    for st in sched._slots if st is not None)
            return real(*args)

        sched._step_fn = step
        tracer.begin()

    def trace_counts(self) -> Dict[str, float]:
        """What the traced slice needed: the decode-attention kernel's FLOPs
        and bytes and the model's forward FLOPs."""
        out = {"decode_attn_flops": 0.0, "decode_attn_bytes": 0.0,
               "forward_flops": 0.0}
        for length in self.trace_lens:
            for k, v in counts.decode_token(self.dims, length).items():
                out[k] += v
        for prompt in self.trace_prompts:
            out["forward_flops"] += counts.prefill(
                self.dims, prompt)["forward_flops"]
        out["slots"] = float(self.mix["num_slots"])
        return out

    def counters(self) -> Dict[str, float]:
        return {"steps_run": float(self.sched.steps_run - self.steps0),
                "tokens_emitted": float(self.sched.tokens_emitted
                                        - self.tokens0)}

    # -- check ------------------------------------------------------------

    def release(self) -> None:
        """Free the program's serving state; the weights stay for the
        reference, which the benchmark made and owns."""
        self.sched = None
        self.engine = None

    def sample(self) -> List[tuple]:
        """The finished requests the check compares: the one with the most
        served tokens, then others drawn from the seed until the sample
        holds ``check_tokens`` served tokens or ``check_requests``
        requests."""
        if not self.done:
            return []
        order = sorted(range(len(self.done)),
                       key=lambda i: -len(self.done[i][1].tokens))
        rest = order[1:]
        rng = traffic_gen.rng_for(self.seed, 3)
        rest = [rest[i] for i in rng.permutation(len(rest))]
        pick = [order[0]]
        served = len(self.done[order[0]][1].tokens)
        for i in rest:
            if (served >= int(self.mix["check_tokens"])
                    or len(pick) >= int(self.mix["check_requests"])):
                break
            pick.append(i)
            served += len(self.done[i][1].tokens)
        return [self.done[i] for i in pick]

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
