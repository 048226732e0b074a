"""From a profiler trace to the numbers the per-layer readers take.

``read_xplane`` turns the ``.xplane.pb`` that ``jax.profiler`` writes
into plain data: for each device, its XLA module executions and its
operations, and the benchmark's host spans (``bench.*``, ``engine.*``),
all in nanoseconds on the trace's clocks. The window is the host span
``bench.window``. The harness waits for the device before it opens the
window and before it closes it, so every device event in the trace
belongs to the window; device events are not clipped by the host span,
because the device's clock and the host's differ by about a
millisecond. ``reduce`` computes, per device and averaged over the
devices: busy time (the union of operation intervals), device time per
XLA module and per operation, collective time, and the idle gaps, each
named by the host span that was open while the device waited.
"""

from __future__ import annotations

import bisect
import re
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple

WINDOW_SPAN = "bench.window"
HOST_PREFIXES = ("bench.", "engine.")
COLLECTIVES = ("all-gather", "reduce-scatter", "all-reduce", "collective-permute",
               "all-to-all")
#: operations that contain others (a scan's loop): their time is their body's
CONTAINERS = ("while", "conditional", "call")
_ID = re.compile(r"\(\d+\)$")
_OP = re.compile(r"^%?([\w.\-]+)")


def find_xplane(trace_dir: Path) -> Path:
    found = sorted(Path(trace_dir).rglob("*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def module_name(name: str) -> str:
    """``jit_decode_tick(123)`` -> ``jit_decode_tick``."""
    return _ID.sub("", name.strip())


def op_name(name: str) -> str:
    """``%fusion.558 = (f32[...]) fusion(...)`` -> ``fusion.558``."""
    m = _OP.match(name.strip())
    return m.group(1) if m else name[:64]


def is_container(op: str) -> bool:
    return op.split(".")[0] in CONTAINERS


def read_xplane(path: Path) -> Dict:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    devices, host = [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            mods, ops = [], []
            for line in plane.lines:
                if line.name == "XLA Modules":
                    mods += [[e.start_ns, e.start_ns + e.duration_ns, module_name(e.name)]
                             for e in line.events]
                elif line.name == "XLA Ops":
                    ops += [[e.start_ns, e.start_ns + e.duration_ns, op_name(e.name)]
                            for e in line.events]
            if ops or mods:
                devices.append({"name": plane.name, "modules": mods, "ops": ops})
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host += [[e.start_ns, e.start_ns + e.duration_ns, e.name]
                         for e in line.events if e.name.startswith(HOST_PREFIXES)]
    devices.sort(key=lambda d: d["name"])
    return {"devices": devices, "host": sorted(host)}


def _union(intervals: Sequence[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def is_collective(op: str) -> bool:
    return any(c in op for c in COLLECTIVES)


def _owner(t: float, spans, starts, labels, default: str) -> str:
    """Label of the innermost (latest-opened) host span open at time
    ``t``; ``spans`` are sorted by start and ``starts`` are their starts."""
    i = bisect.bisect_right(starts, t) - 1
    while i >= 0:
        s, e, n = spans[i]
        if e >= t:
            return labels.get(i, n)
        i -= 1
    return default


def reduce(trace: Dict, span_labels: Optional[Dict[str, List[str]]] = None,
           unattributed: str = "host (unattributed)", top: int = 10) -> Dict:
    """Numbers of the traced window. ``span_labels`` renames host spans
    by occurrence: ``{"engine.step": ["engine.decode", ...]}`` gives the
    i-th ``engine.step`` span in the window the i-th label; an idle gap
    under no span is ``unattributed``."""
    windows = [h for h in trace["host"] if h[2] == WINDOW_SPAN]
    if not windows:
        raise ValueError(f"no {WINDOW_SPAN} span in the trace")
    t0, t1 = windows[0][0], windows[0][1]
    spans = [h for h in trace["host"] if h[2] != WINDOW_SPAN and h[0] >= t0 and h[1] <= t1]
    labels: Dict[int, str] = {}
    for name, names in (span_labels or {}).items():
        idx = [i for i, h in enumerate(spans) if h[2] == name]
        for i, label in zip(idx, names):
            labels[i] = label
    window = (t1 - t0) * 1e-9
    per_dev = []
    for dev in trace["devices"]:
        ops = [tuple(o) for o in dev["ops"] if not is_container(o[2])]
        mods = [tuple(m) for m in dev["modules"]]
        busy = _union([(s, e) for s, e, _ in ops])
        busy_s = sum(e - s for s, e in busy) * 1e-9
        module_s: Dict[str, float] = {}
        module_n: Dict[str, int] = {}
        for s, e, n in mods:
            module_s[n] = module_s.get(n, 0.0) + (e - s) * 1e-9
            module_n[n] = module_n.get(n, 0) + 1
        op_s: Dict[str, float] = {}
        mods_sorted = sorted(mods)
        j = 0
        for s, e, n in sorted(ops):
            while j + 1 < len(mods_sorted) and mods_sorted[j][1] < s:
                j += 1
            mod = (mods_sorted[j][2] if mods_sorted and mods_sorted[j][0] <= s <= mods_sorted[j][1]
                   else "?")
            key = f"{mod} {n}"
            op_s[key] = op_s.get(key, 0.0) + (e - s) * 1e-9
        coll = sum(e - s for s, e in _union([(s, e) for s, e, n in ops if is_collective(n)]))
        gaps, prev = [], t0
        for s, e in busy + [(t1, t1)]:
            s = min(s, t1)
            if s > prev:
                gaps.append((prev, s))
            prev = max(prev, e)
        per_dev.append({"busy_s": busy_s, "module_s": module_s, "module_n": module_n,
                        "op_s": op_s, "collective_s": coll * 1e-9, "gaps": gaps})
    if not per_dev:
        raise ValueError("no device operations in the trace")
    n = len(per_dev)
    module_s: Dict[str, float] = {}
    for d in per_dev:
        for k, v in d["module_s"].items():
            module_s[k] = module_s.get(k, 0.0) + v / n
    op_s: Dict[str, float] = {}
    for d in per_dev:
        for k, v in d["op_s"].items():
            op_s[k] = op_s.get(k, 0.0) + v / n
    idle_by: Dict[str, float] = {}
    starts = [h[0] for h in spans]
    for s, e in per_dev[0]["gaps"]:
        label = _owner((s + e) / 2, spans, starts, labels, unattributed)
        idle_by[label] = idle_by.get(label, 0.0) + (e - s) * 1e-9
    return {
        "window_s": window,
        "devices": n,
        "busy_s": sum(d["busy_s"] for d in per_dev) / n,
        "module_s": module_s,
        "module_n": per_dev[0]["module_n"],
        "collective_s": sum(d["collective_s"] for d in per_dev) / n,
        "top_ops": sorted(op_s.items(), key=lambda kv: -kv[1])[:top],
        "idle_gaps": sorted(idle_by.items(), key=lambda kv: -kv[1])[:top],
    }


def module_time(red: Dict, fragment: str) -> Tuple[float, int]:
    """(device seconds averaged over devices, executions on the first
    device) of the XLA modules whose name contains ``fragment``."""
    secs = sum(v for k, v in red["module_s"].items() if fragment in k)
    count = sum(v for k, v in red["module_n"].items() if fragment in k)
    return secs, count
