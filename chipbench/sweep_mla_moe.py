#!/usr/bin/env python3
"""Find the highest arrival rate a ``serve_mla_moe`` cell sustains (its
knee), as ``sweep.py`` does for the dense serving cells.

    python3 chipbench/sweep_mla_moe.py --workload <cell> --rates 0.5,0.75,1 --seconds 30

One process, one set-up: for each rate it runs the cell's window at that
rate (same generator, same seed), then cancels what is left in the
engine (outputs of up to 1,536 tokens would take minutes to finish), and
prints the
requests due, the metrics, and the queue (requests waiting or
mid-prefill) averaged over each third of the window. A rate whose queue
grows from third to third is past the knee. Run on the chip; the knee
is written into the traffic file by hand, as a number.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from chipbench.common import SRC, CompileClock, enable_compile_cache, load_cell, require_chips  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(SRC))
    cell = load_cell(args.workload)
    require_chips(cell.chips)
    enable_compile_cache()
    clock = CompileClock()

    import numpy as np

    from repro.models import build_model

    from chipbench.gen import load as load_generator
    from chipbench.harness import serve
    from chipbench.model_mla_moe import dims_of, program_config
    from chipbench.tracing import Window
    from chipbench.weights_mla_moe import make_weights

    tr = cell.traffic
    cfg = program_config(cell.config)
    d = dims_of(cell.config)
    engine = serve.build_engine(build_model(cfg), make_weights(d, cfg.dtype, args.seed), tr)
    serve.warm(engine, tr, d.vocab)
    for rate in [float(r) for r in args.rates.split(",")]:
        trr = dict(tr, arrivals=dict(tr["arrivals"], rate_per_s=rate))
        arrivals = load_generator(tr["generator"]).arrivals(trr, args.seed, args.seconds, d.vocab)
        t = time.perf_counter()
        out = serve.drive(engine, arrivals, args.seconds, 600.0, Window(None), 0.0, clock)
        e2e, failed, note = serve.summarize(arrivals, out, args.seconds)
        for rid in engine.live_rids():        # empty the engine for the next rate
            engine.cancel(rid)
        thirds = []
        for k in range(3):
            lo, hi = k * args.seconds / 3, (k + 1) * args.seconds / 3
            q = [n for s, n in out["depth"] if lo <= s < hi]
            thirds.append(float(np.mean(q)) if q else 0.0)
        print(json.dumps({"rate": rate, "due": len(arrivals), "failed": failed,
                          "queue_by_third": thirds, "drain_s": out["t_end"] - args.seconds,
                          "wall_s": time.perf_counter() - t, **e2e}), flush=True)
        print(note, file=sys.stderr, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
