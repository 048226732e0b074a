#!/usr/bin/env python3
"""The readings each limit of a ``train_fsdp4`` cell is set from
(``control.py`` does the one-chip cells).

    python3 chipbench/control_fsdp4.py --workload <name> --seeds 1,2,3 [--seconds S]

Run on the cell's chips, all seeds in one process: for each seed, one run
of the program (the set-up steps and a window of ``--seconds``, 0 by
default) gives the program's first-step readings against the float32
reference on the same mesh; then the control (the reference computed in
fp8) and the planted fault (``half_batch``: the mean over half of the
applied rows) are read against the same reference. A frozen state reads
1.0 in ``change1_gap`` by construction, and is not run. Prints one JSON line per seed, then the largest program
reading and the smallest control and fault readings of each number.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from chipbench.common import SRC, CompileClock, enable_compile_cache, load_cell, require_chips  # noqa: E402

FAULTS = ("half_batch",)


def seed_readings(cell, seed, seconds, devices, clock):
    from chipbench.harness import train_fsdp4 as h

    res = h.run(cell, seed, seconds, None, devices, clock, time.perf_counter())
    print("\n".join(res.notes), file=sys.stderr, flush=True)
    raw = res.record["readings"]
    ref, mesh = raw["reference"], h.make_mesh(cell, devices)
    out = {"program": dict(h.compare(raw["program"], ref),
                           **{c.name: c.value for c in res.checks})}
    for kind, kw in [("control", {"quant": "fp8"})] + [(f, {"fault": f}) for f in FAULTS]:
        out[kind] = h.compare(h.reference_readings(cell, seed, mesh, raw["shardings"], **kw), ref)
    out["e2e"] = res.e2e
    out["memory_peak_bytes"] = res.memory_peak_bytes
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=0.0)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(SRC))
    cell = load_cell(args.workload)
    devices = require_chips(cell.chips)
    enable_compile_cache()
    clock = CompileClock()
    rows = []
    for seed in [int(s) for s in args.seeds.split(",")]:
        row = seed_readings(cell, seed, args.seconds, devices, clock)
        row["seed"] = seed
        rows.append(row)
        print(json.dumps(row), flush=True)
    summary = {}
    for kind in ("program", "control") + FAULTS:
        for name in rows[0][kind]:
            vals = [r[kind][name] for r in rows]
            summary[f"{kind}.{name}"] = max(vals) if kind == "program" else min(vals)
    print(json.dumps({"summary": summary, "seeds": len(rows)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
