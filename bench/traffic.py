"""Open-loop request traffic from a mix file and a seed.

A mix (``traffic/<name>.json``) gives lognormal prompt and output lengths
(median, sigma, clipped to [min, max]) and its arrivals: a burst of
requests due when traffic starts, then Poisson arrivals at
``rate_per_s`` (none where it is 0). Every seed gets the same multiset
of lengths and inter-arrival gaps (stratified quantiles of the
distributions), in an order drawn from the seed, and its own token ids.
So two seeds ask for the same work in another order, and one seed asks
for the same work every time.
"""
from __future__ import annotations

import math
import statistics
from typing import Dict, List

import numpy as np


def seed_rng(seed: int, stream: int) -> np.random.Generator:
    s = int(seed) % 2 ** 64
    return np.random.default_rng([s & 0xFFFFFFFF, s >> 32, stream])


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lengths(spec: Dict, n: int) -> np.ndarray:
    """The n stratified lognormal lengths of ``spec``, sorted."""
    z = np.array([statistics.NormalDist().inv_cdf(u) for u in _quantiles(n)])
    x = np.round(spec["median"] * np.exp(spec["sigma"] * z))
    return np.clip(x, spec["min"], spec["max"]).astype(np.int64)


def n_requests(mix: Dict, seconds: float) -> int:
    """How many requests a run of ``seconds`` can reach: the burst, and
    the arrivals over the longest warm-up and the window with a margin."""
    a = mix["arrivals"]
    if a["rate_per_s"] <= 0:
        return a["burst"]
    horizon = mix["window"]["max_warmup_s"] + seconds
    return a["burst"] + int(math.ceil(a["rate_per_s"] * horizon)) + 8


def requests(mix: Dict, seed: int, seconds: float,
             vocab: int) -> List[Dict]:
    """[{due_s, prompt, max_new}] in due order; ``due_s`` counts from the
    start of traffic."""
    n = n_requests(mix, seconds)
    order = seed_rng(seed, 1)
    prompts = order.permutation(lengths(mix["prompt_tokens"], n))
    outputs = order.permutation(lengths(mix["output_tokens"], n))
    a = mix["arrivals"]
    n_gaps = n - a["burst"]
    gaps = -np.log(1.0 - _quantiles(n_gaps)) / max(a["rate_per_s"], 1e-12)
    due = np.concatenate([np.zeros(a["burst"]),
                          np.cumsum(order.permutation(gaps))])
    ids = seed_rng(seed, 2)
    return [{"due_s": float(due[i]),
             "prompt": ids.integers(0, vocab, int(prompts[i])).tolist(),
             "max_new": int(outputs[i])} for i in range(n)]
