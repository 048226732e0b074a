"""Mean host milliseconds a training step, in which the chip waits: each
``repro.train.step`` span's duration less its ``repro.train.wait`` (the
loss read, where the loop blocks on the step program), over the step
spans inside the traced window. None where the program opens no such
span. A note on stderr gives the mean of each phase, the idle breakdown
by the program's spans, and the idle time a traced step for comparison."""

from chipbench import program_spans as ps

PHASES = ("plan", "batch", "put", "dispatch", "wait", "control", "read")


def read(red, rec, peaks):
    if rec.get("kind") != "train":
        return None
    data = ps.load()
    if data is None:
        return None
    spans = data["spans"]
    value = ps.host_ms_train(spans)
    if value is None:
        return None
    steps = [s for s in spans if s[2] == "repro.train.step"]
    means = [f"{p} {ps.phase_ms(steps, spans, f'repro.train.{p}'):.4f}" for p in PHASES]
    traced = len(rec.get("applied_rows") or []) or len(steps)
    idle_s = red["window_s"] - red["busy_s"]
    per_step = sum(s[0] <= c[0] and c[1] <= s[1] for s in steps for c in spans) / len(steps)
    ps.note(f"host_ms.train: {len(steps)} step spans, {per_step:g} spans a step, host "
            f"{value:.4f} ms a step; phase ms a step: {', '.join(means)}; idle "
            f"{idle_s:.6f} s / {traced} traced steps = "
            f"{1e3 * idle_s / traced:.4f} ms a step")
    ps.note(f"host_ms.train: {ps.idle_note(data, 'repro.train.')}")
    return value
