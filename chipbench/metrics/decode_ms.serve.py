"""Mean device milliseconds per execution of the engine's decode tick
(one token for every live lane)."""

from chipbench.trace import module_time


def read(red, rec, peaks):
    secs, count = module_time(red, "decode_tick")
    return 1e3 * secs / count if count else None
