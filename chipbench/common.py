"""Paths, cell lookup, the chip check, the compile cache and its clock.

Nothing here imports JAX at module level: ``run.py`` checks the cell's
files before it touches the device.
"""

from __future__ import annotations

import dataclasses
import json
import os
from pathlib import Path
from typing import Any, Dict, List

ROOT = Path(__file__).resolve().parents[1]
BENCH_DIR = ROOT / "chipbench"
SRC = ROOT / "src"
#: JAX's persistent compilation cache, at a fixed place in the checkout
#: (the path is part of the cache key; ``JAX_COMPILATION_CACHE_DIR`` wins).
CACHE_DIR = ROOT / ".jax_cache"


class BenchError(RuntimeError):
    """The cell cannot run here: a missing file, no chip, a config that
    differs from what the program runs."""


def load_json(path: Path) -> Any:
    try:
        return json.loads(Path(path).read_text())
    except FileNotFoundError:
        raise BenchError(f"missing file {path}") from None


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: Dict[str, Any]       # configs/<name>.json
    traffic: Dict[str, Any]      # traffic/<name>.json
    limits: Dict[str, Any]       # limits/<workload>.json
    bench: Dict[str, Any]        # BENCHMARK.json

    def metric_names(self, section: str) -> List[str]:
        """Names of the ``end_to_end`` or ``per_layer`` metrics this cell
        reports. A metric with a ``workloads`` key belongs to the cells
        it lists; a per-layer metric without one to every cell that
        reports the end-to-end metric it moves."""
        e2e = set(self.metric_names("end_to_end")) if section == "per_layer" else None
        out = []
        for m in self.bench[section]:
            if "workloads" in m:
                if self.name in m["workloads"]:
                    out.append(m["name"])
            elif e2e is None or m["moves"] in e2e:
                out.append(m["name"])
        return out

    def unit(self, metric: str) -> str:
        for section in ("end_to_end", "per_layer"):
            for m in self.bench[section]:
                if m["name"] == metric:
                    return m["unit"]
        raise BenchError(f"metric {metric} is not in BENCHMARK.json")


def load_cell(name: str, root: Path = ROOT) -> Cell:
    bench = load_json(root / "BENCHMARK.json")
    work = next((w for w in bench["workloads"] if w["name"] == name), None)
    if work is None:
        raise BenchError(f"no workload {name!r} in BENCHMARK.json")
    conf = next(c for c in bench["configs"] if c["name"] == work["config"])
    return Cell(
        name=name,
        chips=int(work["chips"]),
        config=load_json(root / conf["file"]),
        traffic=load_json(root / "chipbench" / "traffic" / f"{work['traffic']}.json"),
        limits=load_json(root / "chipbench" / "limits" / f"{name}.json"),
        bench=bench,
    )


def peaks_for(device_kind: str) -> Dict[str, float]:
    """The chip's published peaks (``peaks.json``); an unknown kind is an
    error, never a default."""
    table = load_json(BENCH_DIR / "peaks.json")
    if device_kind not in table["devices"]:
        raise BenchError(f"no peaks for device kind {device_kind!r} in peaks.json")
    return table["devices"][device_kind]


def require_chips(n: int):
    """The first ``n`` TPU devices, or BenchError: a run never falls back
    to the CPU."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise BenchError(f"needs a TPU, but JAX found platform "
                         f"{devs[0].platform!r} ({devs[0].device_kind})")
    if len(devs) < n:
        raise BenchError(f"needs {n} chips, JAX found {len(devs)}")
    return devs[:n]


def enable_compile_cache() -> str:
    """Persistent compilation cache in the checkout, keeping every program
    however quickly it compiled (the engine's small programs included)."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(CACHE_DIR)
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileClock:
    """Seconds JAX spends lowering and compiling (or reading compiled
    programs back from the persistent cache), and how many backend
    compiles ran, from its monitoring events."""

    EVENTS = (
        "/jax/core/compile/jaxpr_to_mlir_module_duration",
        "/jax/core/compile/backend_compile_duration",
    )

    def __init__(self):
        from jax import monitoring

        self.seconds = 0.0
        self.compiles = 0
        self.cache_hits = 0
        monitoring.register_event_duration_secs_listener(self._on_duration)
        monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, duration, **_):
        if event in self.EVENTS:
            self.seconds += duration
            if event == self.EVENTS[1]:
                self.compiles += 1

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def memory_peak_bytes(devices) -> int:
    """Peak bytes in use on the fullest of ``devices``."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices]
    return int(max(peaks))
