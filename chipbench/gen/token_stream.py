"""Training batches: a copy of the program's ``TokenStream`` rule.

Each sequence starts at a random id and follows next = (a * cur + b) mod
V, replaced by a random id with probability ``noise``: structure that a
model learns within a run. V is the traffic's ``stream_vocab`` (the
first ids of the model's vocabulary), as text puts most of its mass on
a few thousand tokens; over a vocabulary of tens of thousands the rule
is a permutation that no short run learns, and the loss drifts up under
Adam's per-element steps.

Batches are worker-major, ``rows_per_worker`` rows for each of
``n_workers`` workers at the cell's beta; every row of every batch is
drawn afresh, so no two rows repeat. Batch i has a random stream of its
own, so the first batches are the same however many are drawn.
"""

from __future__ import annotations

from typing import Dict, List

import numpy as np


def _sequences(rng: np.random.Generator, n: int, seq_len: int, vocab: int,
               a: int, b: int, noise: float) -> np.ndarray:
    cur = rng.integers(0, vocab, size=(n, 1))
    out = [cur]
    for _ in range(seq_len):
        nxt = (a * cur + b) % vocab
        flip = rng.random(cur.shape) < noise
        rnd = rng.integers(0, vocab, size=cur.shape)
        cur = np.where(flip, rnd, nxt)
        out.append(cur)
    return np.concatenate(out, axis=1).astype(np.int32)


def rows_per_step(traffic: Dict) -> int:
    return traffic["n_workers"] * max(int(round(traffic["beta"] * traffic["rows_per_worker"])), 1)


def batches(traffic: Dict, seed: int, count: int) -> List[Dict[str, np.ndarray]]:
    """``count`` batches of ``inputs``/``labels`` (rows, seq_len) int32."""
    s = traffic["stream"]
    rows = rows_per_step(traffic)
    out = []
    for i in range(count):
        arr = _sequences(np.random.default_rng([int(seed), 7, i]), rows, traffic["seq_len"],
                         s["vocab"], s["a"], s["b"], s["noise"])
        out.append({"inputs": arr[:, :-1], "labels": arr[:, 1:]})
    return out
