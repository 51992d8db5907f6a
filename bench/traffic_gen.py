"""The one traffic generator: reads a mix file (``bench/traffic/<mix>.json``)
and draws a cell's inputs from ``--seed``.

Every unit of work (an offline batch of requests, or one call of the
fleet) is drawn from ``(seed, unit index)``.  Sizes are stratified: a batch
of ``n`` requests takes the ``n`` mid-quantiles ``(i + 0.5) / n`` of each
size distribution.  How the prompt and output lengths pair up and in what
order the requests come is drawn once, from a fixed stream, and is the same
in every batch of every seed: in an offline batch the order decides how the
requests pack into the decode slots, and so how many steps the batch takes.
The seed changes the token ids.  That keeps the work of a run fixed while
the inputs change.

Size distributions in a mix file:

* ``{"choice": [v0, v1, ...], "weights": [w0, w1, ...]}``
* ``{"uniform": [lo, hi]}``      (integers, both ends included)
* ``{"lognormal": [median, sigma], "clip": [lo, hi], "multiple": m}``:
  log-normal about ``median``, each size rounded up to a multiple of ``m``
  (default 1) and held to ``[lo, hi]``.
"""

from __future__ import annotations

import json
import math
import statistics
from pathlib import Path
from typing import List, Tuple

import numpy as np

TRAFFIC_DIR = Path(__file__).resolve().parent / "traffic"
ORDER_STREAM = 5    # the fixed stream that pairs and orders a batch's sizes


def load_mix(name: str) -> dict:
    return json.loads((TRAFFIC_DIR / f"{name}.json").read_text())


def rng_for(seed: int, *stream: int) -> np.random.Generator:
    """A generator for ``(seed, *stream)``; any whole ``seed``, negative or
    wider than 64 bits included."""
    words = [abs(int(seed)) >> (64 * i) & (2**64 - 1)
             for i in range(max(1, -(-abs(int(seed)).bit_length() // 64)))]
    return np.random.default_rng([int(seed < 0), *words, *stream])


def _quantile(dist: dict, u: float) -> int:
    if "choice" in dist:
        values = dist["choice"]
        w = np.asarray(dist.get("weights", [1] * len(values)), np.float64)
        cdf = np.cumsum(w / w.sum())
        return int(values[int(np.searchsorted(cdf, u, side="right"))])
    if "uniform" in dist:
        lo, hi = dist["uniform"]
        return int(min(hi, lo + math.floor(u * (hi - lo + 1))))
    if "lognormal" in dist:
        median, sigma = dist["lognormal"]
        lo, hi = dist["clip"]
        m = int(dist.get("multiple", 1))
        v = median * math.exp(sigma * statistics.NormalDist().inv_cdf(u))
        return int(min(hi, max(lo, m * math.ceil(v / m))))
    raise ValueError(f"unknown size distribution {dist!r}")


def stratified(dist: dict, n: int) -> List[int]:
    """The ``n`` mid-quantiles of ``dist``: the fixed multiset of sizes that
    every unit of a mix holds."""
    return [_quantile(dist, (i + 0.5) / n) for i in range(n)]


# --------------------------------------------------------------------------
# offline LM batches


def lm_sizes(mix: dict) -> Tuple[List[int], List[int]]:
    """(prompt lengths, output lengths) of one batch, before the seed's
    shuffle."""
    n = int(mix["requests_per_batch"])
    return stratified(mix["prompt_len"], n), stratified(mix["output_len"], n)


def lm_batch(mix: dict, seed: int, index: int,
             vocab: int) -> List[Tuple[np.ndarray, int]]:
    """Batch ``index`` of the seed: ``[(prompt int32 [S], max_new_tokens)]``
    with random token ids in ``[0, vocab)``.  The sizes and their order are
    the same for every ``seed`` and ``index``."""
    prompts, outs = lm_sizes(mix)
    order = rng_for(ORDER_STREAM)
    prompts = [prompts[i] for i in order.permutation(len(prompts))]
    outs = [outs[i] for i in order.permutation(len(outs))]
    rng = rng_for(seed, 1, index)
    return [(rng.integers(0, vocab, s, dtype=np.int32), int(o))
            for s, o in zip(prompts, outs)]


def lm_page_counts(mix: dict, block: int) -> List[int]:
    """Every page count (``ceil((prompt + output) / block)``) that a pairing
    of this mix's sizes can ask the KV pool for."""
    prompts, outs = lm_sizes(mix)
    return sorted({-(-(p + o) // block) for p in set(prompts)
                   for o in set(outs)})


# --------------------------------------------------------------------------
# Graph Challenge input batches


def fsi_inputs(mix: dict, seed: int, index: int, neurons: int,
               warm: bool = False) -> np.ndarray:
    """Call ``index`` of the seed: thresholded binary inputs ``x0`` of shape
    ``[neurons, batch]`` at the mix's density, as the Graph Challenge feeds
    its flattened, thresholded MNIST images.  ``warm`` draws the set-up's
    inputs, which no measured call sees."""
    rng = rng_for(seed, 4 if warm else 2, index)
    return (rng.random((neurons, int(mix["batch"])))
            < float(mix["density"])).astype(np.float32)
