"""Mean device milliseconds per execution of the engine's jitted slot
prefill (one chunk of one request)."""

from chipbench.trace import module_time


def read(red, rec, peaks):
    secs, count = module_time(red, "slot_prefill_step")
    return 1e3 * secs / count if count else None
