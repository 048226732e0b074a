"""The harness, one module per traffic ``kind`` (``train``, ``serve``)."""

from __future__ import annotations

import dataclasses
import importlib
from typing import Dict, List, Tuple


@dataclasses.dataclass
class Check:
    """One number compared with its limit; passes when value <= limit."""

    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value <= self.limit


@dataclasses.dataclass
class RunResult:
    e2e: Dict[str, float]
    attempted: int
    failed: int
    checks: List[Check]
    record: Dict                       # what the per-layer readers need
    memory_peak_bytes: int
    notes: List[str] = dataclasses.field(default_factory=list)

    @property
    def correct(self) -> bool:
        return bool(self.checks) and all(c.ok for c in self.checks)


def for_kind(kind: str):
    return importlib.import_module(f"chipbench.harness.{kind}")


def gap(a: float, b: float, base: float) -> float:
    return abs(a - b) / base


def worst_leaf_gap(prog: List[float], ref: List[float], counted=None) -> Tuple[float, int]:
    """(largest |program norm - reference norm| over the leaves, measured
    against the larger of that leaf's reference norm and the median
    leaf's, and the index of that leaf). ``counted`` selects leaves."""
    import numpy as np

    idx = range(len(ref)) if counted is None else counted
    med = float(np.median([ref[i] for i in idx]))
    worst, where = 0.0, -1
    for i in idx:
        g = gap(prog[i], ref[i], max(ref[i], med, 1e-30))
        if g > worst:
            worst, where = g, i
    return worst, where
