"""Roofline share of the served step programs (prefill chunks and decode
ticks) of a latent-attention, sparse-expert model: the least time the
chip could take for the traced steps, each the larger of its required
operations over the bf16 peak and its required bytes over the HBM peak
(``flops_mla_moe``: routed rows, not capacity rows; live latent rows;
weights once a call), over the device time those programs took. Percent.

The routed rows are the engine's own count: the ``moe_rows`` of the
``repro.engine.moe`` spans in the traced window, shared among the traced
steps in proportion to their tokens. Where the program opens no such
span, each step counts the share's expected load."""

from chipbench import program_spans as ps
from chipbench.flops_mla_moe import decode_cost, prefill_cost, routed_rows
from chipbench.model_mla_moe import MLAMoEDims
from chipbench.trace import module_time


def _tokens(kind, info):
    return info[1] if kind == "prefill" else len(info)


def read(red, rec, peaks):
    if rec.get("kind") != "serve" or not isinstance(rec.get("dims"), MLAMoEDims):
        return None
    d = rec["dims"]
    steps = [(k, i) for k, i in rec["steps"] if k == "prefill" or (k == "decode" and i)]
    expected = sum(routed_rows(d, _tokens(k, i)) for k, i in steps) * d.n_moe_layers
    data = ps.load()
    counted = (sum(int(s[3].get("moe_rows", 0)) for s in data["spans"]
                   if s[2] == "repro.engine.moe") if data is not None else 0)
    scale = counted / expected if counted > 0 and expected > 0 else 1.0
    need = 0.0
    for kind, info in steps:
        routed = scale * routed_rows(d, _tokens(kind, info))
        if kind == "prefill":
            f, b = prefill_cost(d, *info, routed=routed)
        else:
            f, b = decode_cost(d, info, routed=routed)
        need += max(f / peaks["bf16_flops_per_s"], b / peaks["hbm_bytes_per_s"])
    if counted > 0:
        ps.note(f"mfu.serve.mla_moe: {counted} counted held-expert rows, "
                f"{scale:.4f} x the share's expected load")
    secs = module_time(red, "slot_prefill_step")[0] + module_time(red, "decode_tick")[0]
    return 100.0 * need / secs if secs > 0 and need > 0 else None
