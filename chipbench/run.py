#!/usr/bin/env python3
"""Run one cell of ``BENCHMARK.json`` once on the chips of this machine.

    python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

With ``--trace 0`` the result carries the cell's end-to-end metrics; with
``--trace 1`` its per-layer metrics, read from a device trace of the
window's first seconds. The last line of stdout is one JSON object; the
numbers compared with their limits are the last lines of stderr and the
last key of that object. With no TPU, fewer chips than the cell needs,
or a file missing, it exits non-zero and prints no result.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from chipbench.common import (  # noqa: E402
    BENCH_DIR, ROOT, SRC, BenchError, CompileClock, enable_compile_cache, load_cell,
    peaks_for, require_chips,
)

TRACE_DIR = ROOT / ".chipbench_trace"


def read_metric(name: str, red, record, peaks):
    """The per-layer reader ``metrics/<name>.py``; None when it finds
    nothing to read."""
    path = BENCH_DIR / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"chipbench_metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(red, record, peaks)


def run_cell(cell, seed: int, seconds: float, trace: bool, devices, t0: float):
    """Everything a run does once the chips are found; returns the result
    line (a dict) and the checks."""
    from chipbench import trace as trace_mod
    from chipbench.harness import for_kind

    enable_compile_cache()
    clock = CompileClock()
    trace_dir = TRACE_DIR if trace else None
    res = for_kind(cell.traffic["kind"]).run(cell, seed, seconds, trace_dir, devices, clock, t0)
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind, "count": len(devices),
              "memory_peak_bytes": res.memory_peak_bytes}
    line = {"correct": res.correct, "attempted": res.attempted, "failed": res.failed}
    if trace:
        red = trace_mod.reduce(trace_mod.read_xplane(trace_mod.find_xplane(trace_dir)),
                               span_labels=res.record.get("span_labels"),
                               unattributed=res.record["unattributed"])
        peaks = peaks_for(dev.device_kind)
        metrics = {}
        for name in cell.metric_names("per_layer"):
            v = read_metric(name, red, res.record, peaks)
            if v is not None:
                metrics[name] = {"value": v, "unit": cell.unit(name)}
        device.update(busy_s=red["busy_s"], window_s=red["window_s"])
        line["metrics"] = metrics
        line["device"] = device
        line["breakdown"] = {"device_ops": [[k, v] for k, v in red["top_ops"]],
                             "idle_gaps": [[k, v] for k, v in red["idle_gaps"]]}
    else:
        line["metrics"] = {n: {"value": res.e2e[n], "unit": cell.unit(n)}
                           for n in cell.metric_names("end_to_end")}
        line["device"] = device
    line["checks"] = {c.name: {"value": c.value, "limit": c.limit} for c in res.checks}
    return line, res


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        cell = load_cell(args.workload)
        if not (SRC / "repro").is_dir():
            raise BenchError(f"no program at {SRC}: run from a checkout of the repository")
        sys.path.insert(0, str(SRC))
        devices = require_chips(cell.chips)
        peaks_for(devices[0].device_kind)
    except BenchError as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 2
    line, res = run_cell(cell, args.seed, args.seconds, bool(args.trace), devices, T_PROCESS)
    for note in res.notes:
        print(f"chipbench: {note}", file=sys.stderr)
    for c in res.checks:
        print(f"check {c.name}: {c.value!r} (limit {c.limit!r}) "
              f"{'ok' if c.ok else 'FAILED'}", file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
