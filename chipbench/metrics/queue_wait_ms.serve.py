"""Mean wall milliseconds from a request's submit to its first prefill
chunk: the start of its first ``repro.engine.prefill`` span less the
start of its ``repro.engine.submit`` span, over the requests submitted
in the traced window. A request with no prefill before the window closes
counts to the close, as TTFT counts a missing first token. None where the
program opens no such span. The per-request waits go to stderr."""

from chipbench import program_spans as ps


def read(red, rec, peaks):
    if rec.get("kind") != "serve":
        return None
    data = ps.load()
    if data is None:
        return None
    waits = ps.queue_waits_ms(data["spans"], data["window_s"])
    if not waits:
        return None
    ps.note("queue_wait_ms.serve: (rid, ms) " + ", ".join(
        f"({rid}, {ms:.3f}{' censored' if cut else ''})" for rid, ms, cut in waits))
    return sum(ms for _, ms, _ in waits) / len(waits)
