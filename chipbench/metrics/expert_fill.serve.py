"""Share of the held experts' computed rows that carry a real token's
choice, over the traced window: the sum of the engine's ``moe_rows``
over the sum of its ``moe_rows_computed`` (held experts x capacity x MoE
layers), both read from the ``repro.engine.moe`` spans. Percent. A
dropless buffer sizes each held expert for every token of the call, so
this reads near top_k / n_experts when every lane is live. None where the
program opens no such span."""

from chipbench import program_spans as ps


def read(red, rec, peaks):
    if rec.get("kind") != "serve":
        return None
    data = ps.load()
    if data is None:
        return None
    moe = [s[3] for s in data["spans"] if s[2] == "repro.engine.moe"]
    rows = sum(int(a.get("moe_rows", 0)) for a in moe)
    computed = sum(int(a.get("moe_rows_computed", 0)) for a in moe)
    if computed <= 0:
        return None
    ps.note(f"expert_fill.serve: {rows} held-expert rows of {computed} computed "
            f"over {len(moe)} reads")
    return 100.0 * rows / computed
