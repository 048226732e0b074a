"""The program's own wall spans (``repro.*``) in a ``--trace 1`` run's
device trace, and the per-layer numbers read from them.

The program opens ``jax.profiler.TraceAnnotation`` spans around the train
loop's phases (``repro.train.*``) and the engine's (``repro.engine.*``):
the same profiler session records them and the device's operations, on
the same clock. ``trace.read_xplane`` keeps only the harness's host spans,
so this module reads the same ``.xplane.pb`` again for the ``repro.``
events and their stats (``step=``, ``rid=``). ``load`` gives them in
seconds from the open of ``bench.window``, those that start and end in
it, with the first device's idle gaps; a trace with no ``repro.`` span
(a program that opens none) gives None, and so every reader here.

The numbers:

* ``host_ms_train``: mean over the ``repro.train.step`` spans of the
  step's duration less its ``repro.train.wait``: the loop's serial host
  time a step, in which the chip waits for the next dispatch.
* ``host_ms_serve``: mean over the ``repro.engine.step`` spans that hold
  a prefill, decode or spec span of the step's duration less its
  ``repro.engine.sync`` spans.
* ``queue_waits_ms``: per submitted request, its first
  ``repro.engine.prefill`` span's start less its ``repro.engine.submit``
  span's start; a request with no prefill before the window closes
  counts to the close (censored, as TTFT counts a missing first token).

``idle_by_span`` names each idle gap by the innermost span open at its
midpoint, the program's and the harness's alike, as ``trace.reduce``
does with the harness's alone.
"""

from __future__ import annotations

import functools
import sys
import warnings
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple

from . import trace

@functools.lru_cache(maxsize=4)
def load(trace_dir: Optional[str] = None) -> Optional[Dict]:
    """``{"window_s", "spans": [[start_s, end_s, name, args]], "host":
    [[start_s, end_s, name]], "gaps": [[start_s, end_s]]}`` of the traced
    window, or None when there is no trace or no program span in it.
    ``trace_dir`` defaults to the one ``run.py`` writes."""
    if trace_dir is None:
        from .run import TRACE_DIR

        trace_dir = str(TRACE_DIR)
    try:
        path = trace.find_xplane(Path(trace_dir))
    except FileNotFoundError:
        return None
    spans = read_program_spans(path)
    if not spans:
        return None
    data = trace.read_xplane(path)
    windows = [h for h in data["host"] if h[2] == trace.WINDOW_SPAN]
    if not windows or not data["devices"]:
        return None
    t0, t1 = windows[0][0], windows[0][1]
    sec = lambda t: (t - t0) * 1e-9  # noqa: E731
    inside = lambda s, e: t0 <= s and e <= t1  # noqa: E731
    ops = [(s, e) for s, e, n in data["devices"][0]["ops"] if not trace.is_container(n)]
    gaps, prev = [], t0
    for s, e in trace._union(ops) + [(t1, t1)]:
        s = min(s, t1)
        if s > prev:
            gaps.append([sec(prev), sec(s)])
        prev = max(prev, e)
    return {
        "window_s": sec(t1),
        "spans": _nested_order([[sec(s), sec(e), n, a] for s, e, n, a in spans if inside(s, e)]),
        "host": _nested_order([[sec(s), sec(e), n] for s, e, n in data["host"]
                               if n != trace.WINDOW_SPAN and inside(s, e)]),
        "gaps": gaps,
    }


def read_program_spans(path: Path) -> List[list]:
    """``[start_ns, end_ns, name, args]`` of every ``repro.`` host event."""
    from jax.profiler import ProfileData

    out = []
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        for plane in ProfileData.from_file(str(path)).planes:
            if not plane.name.startswith("/host:"):
                continue
            for line in plane.lines:
                out += [[e.start_ns, e.start_ns + e.duration_ns, e.name, dict(e.stats)]
                        for e in line.events if e.name.startswith("repro.")]
    return out


def _nested_order(spans: List[list]) -> List[list]:
    """By start, a parent before a child that starts with it."""
    return sorted(spans, key=lambda s: (s[0], -s[1]))


def within(parent: Sequence, spans: Sequence[Sequence], name: str) -> List[Sequence]:
    """The spans called ``name`` that lie inside ``parent``."""
    return [s for s in spans if s[2] == name and s is not parent
            and parent[0] <= s[0] and s[1] <= parent[1]]


def phase_ms(steps: Sequence[Sequence], spans: Sequence[Sequence], name: str) -> float:
    """Milliseconds a step in the spans called ``name``."""
    total = sum(c[1] - c[0] for s in steps for c in within(s, spans, name))
    return 1e3 * total / len(steps)


def _mean(xs: List[float]) -> Optional[float]:
    return sum(xs) / len(xs) if xs else None


def ms_less(parent: Sequence, spans: Sequence[Sequence], name: str) -> float:
    """Milliseconds of ``parent`` less its spans called ``name``."""
    return 1e3 * (parent[1] - parent[0] - sum(c[1] - c[0] for c in within(parent, spans, name)))


def host_ms_train(spans: Sequence[Sequence]) -> Optional[float]:
    return _mean([ms_less(s, spans, "repro.train.wait")
                  for s in spans if s[2] == "repro.train.step"])


ENGINE_ACTIONS = ("repro.engine.prefill", "repro.engine.decode", "repro.engine.spec")


def engine_steps(spans: Sequence[Sequence]) -> List[Tuple[str, Sequence]]:
    """``(action, step span)`` of the engine steps that ran an action."""
    out = []
    for s in spans:
        if s[2] == "repro.engine.step":
            acts = [a for name in ENGINE_ACTIONS for a in within(s, spans, name)]
            if acts:
                out.append((min(acts, key=lambda a: a[0])[2], s))
    return out


def host_ms_serve(spans: Sequence[Sequence]) -> Optional[float]:
    return _mean([ms_less(s, spans, "repro.engine.sync") for _, s in engine_steps(spans)])


def queue_waits_ms(spans: Sequence[Sequence], window_s: float) -> List[Tuple[int, float, bool]]:
    """``(rid, wait ms, censored)`` of each request submitted in the window."""
    first: Dict[int, float] = {}
    for s in spans:
        if s[2] == "repro.engine.prefill" and "rid" in s[3]:
            first.setdefault(s[3]["rid"], s[0])
    out = []
    for s in spans:
        if s[2] == "repro.engine.submit" and "rid" in s[3]:
            rid = s[3]["rid"]
            t = first.get(rid)
            if t is not None and t >= s[0]:
                out.append((rid, 1e3 * (t - s[0]), False))
            else:
                out.append((rid, 1e3 * (window_s - s[0]), True))
    return out


def gap_owners(data: Dict, unattributed: str = "(no span)") -> List[Tuple[float, float, str]]:
    """``(start_s, end_s, owner)`` of each idle gap: the innermost span,
    program's or harness's, open at the gap's midpoint."""
    spans = _nested_order([list(s[:3]) for s in data["spans"]] + list(data["host"]))
    starts = [s[0] for s in spans]
    return [(a, b, trace._owner((a + b) / 2, spans, starts, {}, unattributed))
            for a, b in data["gaps"]]


def idle_by_span(data: Dict) -> Dict[str, float]:
    """Idle seconds by owner (``gap_owners``), largest first."""
    out: Dict[str, float] = {}
    for a, b, owner in gap_owners(data):
        out[owner] = out.get(owner, 0.0) + (b - a)
    return dict(sorted(out.items(), key=lambda kv: -kv[1]))


def note(text: str) -> None:
    print(f"chipbench: {text}", file=sys.stderr, flush=True)


def idle_note(data: Dict, prefix: str, besides: Sequence[str] = ()) -> str:
    """The idle breakdown, and the share of the idle time (less the spans
    named in ``besides``) that the spans starting with ``prefix`` name."""
    idle = idle_by_span(data)
    rest = {k: v for k, v in idle.items() if k not in besides}
    named = sum(v for k, v in rest.items() if k.startswith(prefix))
    total = sum(rest.values())
    share = 100.0 * named / total if total > 0 else 0.0
    parts = ", ".join(f"{k} {v:.6f}" for k, v in idle.items())
    largest = sorted(gap_owners(data), key=lambda g: g[0] - g[1])[:5]
    gaps = ", ".join(f"({1e3 * a:.3f}, {1e3 * (b - a):.3f}, {o})" for a, b, o in largest)
    return (f"idle s by innermost span: {parts}; {prefix}* names {share:.2f} % of "
            f"{total:.6f} s" + (f" outside {', '.join(besides)}" if besides else "")
            + f"; largest gaps (start ms, ms, owner): {gaps}")
