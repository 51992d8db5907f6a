"""Serving launcher: reduced configs by default, ``--full-size`` for the
published widths (on a TPU the engine routes decode to ``pallas-splitk``).

    PYTHONPATH=src python -m repro.launch.serve --arch internlm2-1.8b \
        --batch 4 --prompt-len 16 --max-new 8
"""

from __future__ import annotations

import argparse

import numpy as np

from repro.configs import get_config
from repro.launch.compile_cache import use_compile_cache
from repro.serving.engine import ServingEngine


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="internlm2-1.8b")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--full-size", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    use_compile_cache()

    cfg = get_config(args.arch)
    if not args.full_size:
        cfg = cfg.reduced()
    engine = ServingEngine(cfg, seed=args.seed)
    rng = np.random.default_rng(args.seed)
    prompts = rng.integers(0, cfg.vocab_size,
                           size=(args.batch, args.prompt_len)).astype(np.int32)
    extra = {}
    if cfg.family == "vlm":
        extra["extra_embeds"] = rng.standard_normal(
            (args.batch, cfg.frontend_tokens, cfg.d_model)).astype(np.float32)
    if cfg.family == "encdec":
        extra["frames"] = rng.standard_normal(
            (args.batch, cfg.frontend_tokens, cfg.d_model)).astype(np.float32)
    result = engine.generate(prompts, max_new_tokens=args.max_new, extra=extra)
    print(f"[{args.arch}] generated {result.tokens.shape} tokens:")
    print(result.tokens)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
