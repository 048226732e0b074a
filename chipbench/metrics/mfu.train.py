"""Model FLOP utilization of the train step program: the operations the
traced steps required for their applied rows (forward and backward, no
recomputation), over the step program's device time averaged over the
cell's devices, times the chips, times the chip's bf16 peak. Percent."""

from chipbench.flops import train_step_flops
from chipbench.trace import module_time


def read(red, rec, peaks):
    if rec.get("kind") != "train":
        return None
    secs, count = module_time(red, "train_step")
    if not count or not rec["applied_rows"]:
        return None
    rows = rec["applied_rows"][:count]
    flops = sum(train_step_flops(rec["dims"], r, rec["seq_len"]) for r in rows)
    flops *= count / len(rows)
    return 100.0 * flops / (secs * red["devices"] * peaks["bf16_flops_per_s"])
