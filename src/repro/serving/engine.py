"""Batched serving engine: prefill + decode loop over any assigned arch.

The engine mirrors the paper's batch-inference posture (§V-B: requests are
buffered and batched upstream; FSD processes the batch): prompts are padded
to a bucket, prefilled once, then decoded step-by-step with the KV/SSM cache.
Greedy sampling keeps tests deterministic.

``router.py`` decides the execution configuration (the paper's
Serial/Queue/Object choice, mapped to TPU slice sizing) before the engine
runs.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core.backends import KVCacheLayout, cache_layout_for, get_backend
from repro.models.registry import get_model

PyTree = Any


@dataclasses.dataclass
class GenerationResult:
    tokens: np.ndarray           # [B, max_new]
    prefill_logits: np.ndarray   # [B, vocab]
    steps: int
    # Set by the fabric engine: the full LmPipelineResult (billing stats,
    # dual-clock makespans, wire volumes).  None on the device path.
    fabric: Optional[Any] = None


class ServingEngine:
    def __init__(self, cfg: ModelConfig, params: Optional[PyTree] = None,
                 seed: int = 0, attn_backend=None,
                 engine: str = "device", pipeline_P: int = 2,
                 pipeline_channel: str = "queue"):
        """``attn_backend``: decode-attention backend name/instance routed to
        every model family's decode step (``repro.core.backends``).  ``None``
        asks the router (:func:`repro.serving.router.route_attention_backend`),
        which picks from the platform: ``pallas-splitk`` on TPU, the
        ``dense-ref`` oracle elsewhere.

        ``engine="fabric"`` serves over the serverless pipeline instead of
        on-device: the layer stack splits into ``pipeline_P`` stages whose
        activations travel the ``pipeline_channel`` fabric
        (:func:`repro.faas.lm_pipeline.run_lm_pipeline`); results carry the
        billing/clock telemetry in ``GenerationResult.fabric``."""
        self.cfg = cfg
        if attn_backend is None:
            from repro.serving.router import route_attention_backend

            attn_backend = route_attention_backend(cfg)
        if engine not in ("device", "fabric"):
            raise ValueError(f"unknown engine {engine!r}")
        self.engine = engine
        self.pipeline_P = pipeline_P
        self.pipeline_channel = pipeline_channel
        self._stage_executors: Optional[list] = None
        self.attn_backend = get_backend("attention", attn_backend)
        self.model = get_model(cfg, attn_backend=self.attn_backend)
        self.params = params if params is not None else self.model.init(
            jax.random.key(seed))
        # The scheduler's prefill, with the family's counters where it
        # names any (``ModelApi.prefill_counted``).
        self._prefill = jax.jit(self.model.prefill_counted
                                or self.model.prefill, static_argnums=(2,))
        self._decode = jax.jit(self.model.decode_step)

    def cache_layout(self, max_len: int) -> KVCacheLayout:
        """The layout the engine's caches use for a given capacity: prefill
        (via the ``get_model`` closure) allocates
        ``[B, KV, padded_len(max_len), D]`` buffers with it."""
        return cache_layout_for(self.attn_backend, max_len)

    def generate(
        self,
        prompts: np.ndarray,            # [B, S_prompt] int32
        max_new_tokens: int = 8,
        extra: Optional[Dict[str, np.ndarray]] = None,
        max_len: Optional[int] = None,
    ) -> GenerationResult:
        """``max_len`` overrides the cache capacity (default: exactly what
        the batch needs).  The continuous-batching differential suite pins
        it to the scheduler's slot capacity so the solo oracle and the
        scheduler run bitwise-identical reduction shapes."""
        B, S = prompts.shape
        if self.engine == "fabric":
            return self._generate_fabric(prompts, max_new_tokens, extra)
        if max_len is None:
            max_len = S + max_new_tokens + (self.cfg.frontend_tokens or 0)
        batch: Dict[str, Any] = {"tokens": jnp.asarray(prompts, jnp.int32)}
        if extra:
            batch.update({k: jnp.asarray(v) for k, v in extra.items()})
        logits, cache = self._prefill(self.params, batch, max_len)[:2]
        out_tokens = []
        token = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
        for _ in range(max_new_tokens):
            out_tokens.append(np.asarray(token)[:, 0])
            logits, cache = self._decode(self.params, token, cache)
            token = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
        return GenerationResult(
            tokens=np.stack(out_tokens, axis=1),
            prefill_logits=np.asarray(logits[:, 0]),
            steps=max_new_tokens,
        )

    def generate_stream(
        self,
        requests,                        # Sequence[scheduler.Request]
        num_slots: int = 4,
        max_request_len: Optional[int] = None,
        mesh=None,
        axis_name: str = "seq",
    ):
        """Serve a mixed-length request stream with continuous batching.

        Requests are admitted into ``num_slots`` fixed decode slots as they
        arrive and retired the step their token budget completes; KV lives
        in a block-granular paged pool (``serving/kv_pool.py``) so slots are
        reused defrag-free mid-decode.  Returns a list of
        :class:`repro.serving.scheduler.RequestResult`, each bitwise-equal
        (fp32 cache) to serving that request alone through :meth:`generate`
        at ``max_len=slot_capacity``.

        ``max_request_len`` bounds prompt+new+frontend over the stream
        (default: measured from ``requests``); ``mesh`` switches the decode
        step to the sequence-sharded shard_map variant over ``axis_name``.

        The fabric engine has no mid-batch admission point (stage workers
        hold per-batch KV), so it degrades to per-request static pipeline
        generates behind the same API.
        """
        from repro.serving.scheduler import RequestScheduler

        requests = list(requests)
        if self.engine == "fabric":
            return self._stream_fabric(requests)
        if max_request_len is None:
            max_request_len = max(
                (np.asarray(r.prompt).reshape(-1).shape[0]
                 + r.max_new_tokens + (self.cfg.frontend_tokens or 0))
                for r in requests)
        # The pool is sized exactly like route_serving_plan's policy, but
        # from the engine's *own* backend layout (the plan re-routes the
        # backend; an explicitly constructed engine must not switch).
        layout = self.cache_layout(max_request_len)
        cap = layout.padded_len(max_request_len)
        sched = RequestScheduler(
            self.model, self.params, self._prefill,
            num_slots=num_slots, slot_capacity=cap, layout=layout,
            mesh=mesh, axis_name=axis_name)
        return sched.run(requests)

    def _stream_fabric(self, requests):
        from repro.serving.scheduler import RequestResult

        results = []
        for step, req in enumerate(sorted(requests,
                                          key=lambda r: (r.arrival, r.rid))):
            prompt = np.asarray(req.prompt, np.int32).reshape(1, -1)
            res = self._generate_fabric(prompt, req.max_new_tokens, req.extra)
            results.append(RequestResult(
                rid=req.rid, tokens=res.tokens[0],
                final_logits=res.prefill_logits[0],
                prompt_len=prompt.shape[1],
                admitted_step=step, finished_step=step))
        return results

    def _generate_fabric(
        self,
        prompts: np.ndarray,
        max_new_tokens: int,
        extra: Optional[Dict[str, np.ndarray]],
    ) -> GenerationResult:
        # Lazy import: lm_pipeline pulls the FaaS stack; the device path
        # must not depend on it.
        from repro.faas.lm_pipeline import build_stage_executors, run_lm_pipeline

        if self._stage_executors is None:
            self._stage_executors = build_stage_executors(
                self.cfg, self.params, self.pipeline_P,
                attn_backend=self.attn_backend)
        res = run_lm_pipeline(
            self.cfg, prompts, self.params,
            max_new_tokens=max_new_tokens, P=self.pipeline_P,
            channel=self.pipeline_channel, attn_backend=self.attn_backend,
            extra=extra, executors=self._stage_executors,
        )
        return GenerationResult(tokens=res.tokens, prefill_logits=res.logits,
                                steps=max_new_tokens, fabric=res)
