"""Mean host milliseconds an engine step: each ``repro.engine.step`` span
that holds a prefill, decode or spec span, its duration less its
``repro.engine.sync`` spans (the device-to-host reads, where the engine
waits on the chip), over the traced window. None where the program opens
no such span. A note on stderr gives the mean by action and phase, and
the idle breakdown by the program's spans."""

from chipbench import program_spans as ps

PHASES = ("schedule", "dispatch", "sync")


def read(red, rec, peaks):
    if rec.get("kind") != "serve":
        return None
    data = ps.load()
    if data is None:
        return None
    spans = data["spans"]
    value = ps.host_ms_serve(spans)
    if value is None:
        return None
    parts = []
    for action in ps.ENGINE_ACTIONS:
        steps = [s for a, s in ps.engine_steps(spans) if a == action]
        if not steps:
            continue
        host = sum(ps.ms_less(s, spans, "repro.engine.sync") for s in steps) / len(steps)
        phases = ", ".join(f"{p} {ps.phase_ms(steps, spans, f'repro.engine.{p}'):.4f}"
                           for p in PHASES)
        step_ms = 1e3 * sum(s[1] - s[0] for s in steps) / len(steps)
        parts.append(f"{action.split('.')[-1]}: {len(steps)} steps, host {host:.4f} ms, "
                     f"step {step_ms:.4f} ms ({phases})")
    ps.note(f"host_ms.serve: {value:.4f} ms a step; " + "; ".join(parts))
    ps.note(f"host_ms.serve: {ps.idle_note(data, 'repro.engine.', besides=('bench.wait',))}")
    return value
