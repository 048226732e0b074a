#!/usr/bin/env python3
"""Record the small device trace that ``test_trace.py`` reads. Run on a
TPU, from the root of a checkout:

    python3 chipbench/tests/record_trace.py <out_dir>

Inside one ``bench.window`` span it runs ``jit(fixture_step)`` (a
4096 x 4096 bf16 matmul) ``CALLS`` times, each under a ``bench.batch``
span and waited for, with a ``bench.wait`` sleep of ``SLEEP_S`` after
each. It writes the trace to ``<out_dir>`` and prints the planes and
lines it holds and what ``chipbench.trace.reduce`` makes of it.
"""

from __future__ import annotations

import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

CALLS = 5
SLEEP_S = 0.02
N = 4096


def fixture_step(x):
    import jax.numpy as jnp

    return jnp.tanh(x @ x).astype(x.dtype)


def main(out: str) -> int:
    import jax
    import jax.numpy as jnp
    from jax.profiler import ProfileData, TraceAnnotation

    from chipbench import trace

    step = jax.jit(fixture_step)
    x = jnp.full((N, N), 0.01, jnp.bfloat16)
    x = step(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(out, profiler_options=opts)
    with TraceAnnotation(trace.WINDOW_SPAN):
        for _ in range(CALLS):
            with TraceAnnotation("bench.batch"):
                x = step(x)
                x.block_until_ready()
            with TraceAnnotation("bench.wait"):
                time.sleep(SLEEP_S)
    jax.profiler.stop_trace()
    path = trace.find_xplane(Path(out))
    pd = ProfileData.from_file(str(path))
    for plane in pd.planes:
        print("plane", plane.name)
        for line in plane.lines:
            evs = list(line.events)
            print("   line", repr(line.name), len(evs), [(e.name, e.start_ns, e.duration_ns)
                                                        for e in evs[:3]])
    red = trace.reduce(trace.read_xplane(path))
    print(json.dumps(red, indent=1, default=str))
    print("xplane", path, path.stat().st_size, "bytes")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
