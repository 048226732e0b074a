"""Roofline share of the served step programs (prefill chunks and decode
ticks): the least time the chip could take for the traced steps, each
the larger of its required operations over the bf16 peak and its
required bytes over the HBM peak (weights once per call, the live KV
rows read and written; not the arena's capacity), over the device time
those programs took. Percent. Decode is bound by bytes, so this is a
roofline share of the whole served step."""

from chipbench.flops import decode_cost, prefill_cost
from chipbench.trace import module_time


def read(red, rec, peaks):
    if rec.get("kind") != "serve":
        return None
    d = rec["dims"]
    need = 0.0
    for kind, info in rec["steps"]:
        if kind == "prefill":
            f, b = prefill_cost(d, *info)
        elif kind == "decode" and info:
            f, b = decode_cost(d, info)
        else:
            continue
        need += max(f / peaks["bf16_flops_per_s"], b / peaks["hbm_bytes_per_s"])
    secs = module_time(red, "slot_prefill_step")[0] + module_time(red, "decode_tick")[0]
    return 100.0 * need / secs if secs > 0 and need > 0 else None
