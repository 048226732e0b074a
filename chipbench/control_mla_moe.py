#!/usr/bin/env python3
"""The readings the served-gap limit of a ``serve_mla_moe`` cell is set
from (``control.py`` does the train and dense serve cells).

    python3 chipbench/control_mla_moe.py --workload <name> --seeds 1,2,3 [--seconds S] [--dump DIR]

Run on the chip, at the cell's own size, all seeds in one process: for
each seed a window of ``--seconds`` at the cell's load gives the
program's mean gap over every finished request (``serve_mla_moe.run``);
the control (the reference in fp8) is read by the same rule at the same
positions, and so is a fault that alters one served token in
``ALTER_EVERY``. Prints one JSON line per seed, then the largest program
reading and the smallest control and fault readings; ``--dump`` keeps
each seed's gaps (``gaps.<seed>.npz``).
"""

from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from chipbench.common import SRC, CompileClock, enable_compile_cache, load_cell, require_chips  # noqa: E402

KINDS = ("program", "control", "altered_token")


def serve_seed(cell, seed, seconds, devices, clock, dump=None):
    import numpy as np

    from chipbench.harness import serve_mla_moe

    res = serve_mla_moe.run(cell, seed, seconds, None, devices, clock, time.perf_counter(),
                            control=True)
    print("\n".join(res.notes), file=sys.stderr, flush=True)
    read = res.record["readings"]
    if dump is not None:
        dump.mkdir(parents=True, exist_ok=True)
        np.savez_compressed(dump / f"gaps.{seed}.npz", **res.record["gaps"])
    out = {k: {"served_gap_mean": read[k]} for k in KINDS}
    out["program"].update({c.name: c.value for c in res.checks}, widest=read["widest"])
    out.update(e2e=res.e2e, tokens=int(res.record["gaps"]["served"].size),
               memory_peak_bytes=res.memory_peak_bytes)
    del res
    gc.collect()
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=51.0)
    ap.add_argument("--dump", type=Path, default=None)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(SRC))
    cell = load_cell(args.workload)
    devices = require_chips(cell.chips)
    enable_compile_cache()
    clock = CompileClock()
    rows = []
    for seed in [int(s) for s in args.seeds.split(",")]:
        row = serve_seed(cell, seed, args.seconds, devices, clock, args.dump)
        row["seed"] = seed
        rows.append(row)
        print(json.dumps(row), flush=True)
    summary = {}
    for kind in KINDS:
        for name in rows[0][kind]:
            vals = [r[kind][name] for r in rows]
            summary[f"{kind}.{name}"] = max(vals) if kind == "program" else min(vals)
    print(json.dumps({"summary": summary, "seeds": len(rows)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
