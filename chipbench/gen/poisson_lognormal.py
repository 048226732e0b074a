"""Open-loop requests: Poisson-like arrivals, lognormal prompt and
output lengths, uniform token ids.

Every seed gets the same work, in the same order: the number of
requests in the window is rate x seconds; the inter-arrival gaps are
that many quantiles of the exponential distribution, and the lengths
that many quantiles of their clipped lognormals, each list shuffled once
by the traffic file's ``schedule_seed``. The run's seed draws the token
ids. A time-to-first-token tail over a few dozen requests swings with
the order of arrivals by tens of percent, so the order is part of the
mix, not of the seed.
"""

from __future__ import annotations

import dataclasses
import statistics
from typing import Dict, List

import numpy as np


@dataclasses.dataclass
class Arrival:
    due: float               # seconds after the window opens
    prompt: np.ndarray       # int32 token ids
    max_new: int


def _lognormal_quantiles(spec: Dict, n: int) -> np.ndarray:
    z = np.array([statistics.NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)])
    x = spec["median"] * np.exp(spec["sigma"] * z)
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(int)


def requests_in_window(traffic: Dict, seconds: float) -> int:
    return max(1, int(round(traffic["arrivals"]["rate_per_s"] * seconds)))


def arrivals(traffic: Dict, seed: int, seconds: float, vocab: int) -> List[Arrival]:
    n = requests_in_window(traffic, seconds)
    order = np.random.default_rng(int(traffic["schedule_seed"]))
    gaps = order.permutation(-np.log1p(-(np.arange(n) + 0.5) / n))   # exponential quantiles
    # n arrivals inside [0, seconds): the window holds one more mean gap
    due = seconds * np.cumsum(gaps) / (gaps.sum() + gaps.mean())
    prompts = order.permutation(_lognormal_quantiles(traffic["prompt_len"], n))
    outputs = order.permutation(_lognormal_quantiles(traffic["output_len"], n))
    ids = np.random.default_rng([int(seed), 11])
    return [Arrival(float(t), ids.integers(0, vocab, size=int(p)).astype(np.int32), int(o))
            for t, p, o in zip(due, prompts, outputs)]
