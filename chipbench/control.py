#!/usr/bin/env python3
"""The readings each limit of ``limits/<workload>.json`` is set from.

    python3 chipbench/control.py --workload <name> --seeds 1,2,3 [--seconds S]

Run on the chip, at the cell's own size, all seeds in one process:

* training cells: for each seed, one run of the program (the set-up
  steps and a window of ``--seconds``, 0 by default) gives the program's
  readings against the float32 reference; then the control (the
  reference computed in fp8) and the planted faults (``half_batch``:
  the mean over half of the applied rows; ``frozen``: the update never
  applied) are read against the same reference;
* serving cells: for each seed, a window of ``--seconds`` at the cell's
  load gives the program's widest gap over the sampled requests; the
  control's widest gap is read with the same rule, over the same sample:
  at each position that produced a served token, the gap of the token
  fp8 ranks first there. A fault alters one served token.

Prints one JSON line per seed, then the largest program reading and the
smallest control and fault readings of each number.
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

TRAIN_FAULTS = ("half_batch", "frozen")


def train_seed(cell, seed, seconds, devices, clock):
    from chipbench.harness import train

    res = train.run(cell, seed, seconds, None, devices, clock, time.perf_counter())
    print("\n".join(res.notes), file=sys.stderr, flush=True)
    ref = res.record["readings"]["reference"]
    raw = {"program": res.record["readings"]["program"], "reference": ref}
    out = {"program": dict(train.compare(raw["program"], ref),
                           **{c.name: c.value for c in res.checks})}
    for kind, kw in [("control", {"quant": "fp8"})] + [(f, {"fault": f}) for f in TRAIN_FAULTS]:
        raw[kind] = train.reference_readings(cell, seed, devices, **kw)
        out[kind] = train.compare(raw[kind], ref)
    out["raw"] = raw
    return out


def serve_seed(cell, seed, seconds, devices, clock):
    from chipbench import reference
    from chipbench.harness import serve
    from chipbench.model import dims_of

    d = dims_of(cell.config)
    res = serve.run(cell, seed, seconds, None, devices, clock, time.perf_counter())
    print("\n".join(res.notes), file=sys.stderr, flush=True)
    t0 = time.perf_counter()
    params, sample = res.record["params"], res.record["sample"]
    ctl, fault = 0.0, 0.0
    for i, (prompt, toks) in enumerate(sample):
        _, g = reference.served_gaps(d, params, prompt, toks, control=True)
        ctl = max(ctl, float(g.max()))
        if i == 0:
            bad = list(toks)
            bad[len(bad) // 2] = (bad[len(bad) // 2] + 1) % d.vocab
            fault = float(reference.served_gaps(d, params, prompt, bad).max())
    print(f"control and fault read in {time.perf_counter() - t0:.1f} s", file=sys.stderr, flush=True)
    out = {"program": {c.name: c.value for c in res.checks},
           "control": {"served_gap": ctl}, "altered_token": {"served_gap": fault},
           "e2e": res.e2e, "tokens": int(sum(len(t) for _, t in sample))}
    del res, params, sample
    gc.collect()
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
    one = train_seed if cell.traffic["kind"] == "train" else serve_seed
    rows = []
    for seed in [int(s) for s in args.seeds.split(",")]:
        row = one(cell, seed, args.seconds, devices, clock)
        row["seed"] = seed
        rows.append(row)
        print(json.dumps(row), flush=True)
    summary = {}
    for kind in rows[0]:
        if kind in ("seed", "e2e", "tokens", "raw"):
            continue
        for name in rows[0][kind]:
            vals = [r[kind][name] for r in rows]
            summary[f"{kind}.{name}"] = max(vals) if kind == "program" else min(vals)
    print(json.dumps({"summary": summary, "seeds": len(rows)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
