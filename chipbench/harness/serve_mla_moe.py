"""Serving cells of latent-attention, sparse-expert models: open-loop
requests into the program's paged ``ServeEngine``, as ``serve`` runs the
dense ones.

Set-up, the window and its metrics are ``serve``'s (``build_engine``,
``warm``, ``drive``, ``summarize``). What differs is the configuration
(``model_mla_moe``: the file's keys checked against the program, the
chip's share of the experts), the weights (``weights_mla_moe``) and the
reference that decides ``correct`` (``reference_mla_moe``, computed in
blocks at the published share). The record says ``kind: "serve"``, so
the serving cells' readers read it too.
"""

from __future__ import annotations

import gc
import time
from typing import Dict

import numpy as np

from ..common import memory_peak_bytes
from ..gen import load as load_generator
from ..model_mla_moe import dims_of, program_config
from ..tracing import Window
from ..weights_mla_moe import make_weights
from . import Check, RunResult
from .serve import build_engine, drive, summarize, warm


#: the planted fault alters one served token in this many
ALTER_EVERY = 32


def served_checks(d, params, engine_requests, arrivals, rid_of, tr: Dict,
                  control: bool = False) -> Dict:
    """Reference gaps of every served token of every finished request
    (``reference_mla_moe.served_gaps``), joined over the requests. The
    mean of the served tokens' gaps is compared: the widest gap is set by
    the rare positions where bfloat16's rounding flips a near-tie of the
    top-6 routing against the float32 reference, in the program and in
    the fp8 control alike, while the mean counts how often and how far
    the served token falls below the reference's best."""
    from .. import reference_mla_moe as reference

    t0 = time.perf_counter()
    done = [i for i, r in enumerate(rid_of)
            if r >= 0 and len(engine_requests[r].tokens) == arrivals[i].max_new]
    rows: Dict[str, list] = {}
    sample = []
    for i in done:
        toks = list(engine_requests[rid_of[i]].tokens)
        for k, g in reference.served_gaps(d, params, arrivals[i].prompt, toks, control).items():
            rows.setdefault(k, []).append(g)
        sample.append((arrivals[i].prompt, toks))
    gaps = {k: np.concatenate(v) for k, v in rows.items()}
    return {"gaps": gaps, "requests": len(sample), "tokens": sum(len(t) for _, t in sample),
            "sample": sample, "seconds": time.perf_counter() - t0}


def readings(gaps: Dict[str, np.ndarray]) -> Dict[str, float]:
    """``served_gap_mean`` of the program's tokens, of fp8's (when read)
    and of the program's with one token in ``ALTER_EVERY`` altered, each
    over the same positions, with the program's widest gap."""
    served = gaps.get("served", np.zeros(0))
    if not served.size:
        return {"program": float("inf"), "widest": float("inf"), "altered_token": 0.0}
    bad = served.copy()
    bad[::ALTER_EVERY] = gaps["altered"][::ALTER_EVERY]
    out = {"program": float(served.mean()), "widest": float(served.max()),
           "altered_token": float(bad.mean())}
    if "control" in gaps:
        out["control"] = float(gaps["control"].mean())
    return out


def run(cell, seed: int, seconds: float, trace_dir, devices, clock, t0: float,
        control: bool = False) -> RunResult:
    """One run of the cell; ``control=True`` also reads the fp8 control's
    gaps at the sample's positions (``control_mla_moe``)."""
    from repro.models import build_model

    tr = cell.traffic
    cfg = program_config(cell.config)
    d = dims_of(cell.config)
    model = build_model(cfg)
    params = make_weights(d, cfg.dtype, seed)
    engine = build_engine(model, params, tr)
    warm(engine, tr, d.vocab)
    arrivals = load_generator(tr["generator"]).arrivals(tr, seed, seconds, d.vocab)
    window = Window(trace_dir)
    out = drive(engine, arrivals, seconds, tr["drain_s"], window, tr["trace_seconds"], clock)
    peak = memory_peak_bytes(devices)
    setup_s = out["t_open"] - t0

    e2e, failed, note = summarize(arrivals, out, seconds)
    e2e["setup_s"] = setup_s
    s = engine.stats
    notes = [note, f"held-expert rows {s.moe_rows} of {s.moe_rows_computed} computed "
                   f"over the run (warm-up included)"]
    record = {"kind": "serve", "dims": d, "steps": out["steps"],
              "unattributed": "engine loop (unattributed)",
              "span_labels": {"engine.step": [f"engine.{k}" for k, _ in out["steps"]]}}

    # -- the reference, once the window has closed and the arena is freed --
    requests = {r: engine.request(r) for r in out["rid_of"] if r >= 0}
    del engine
    gc.collect()
    chk = served_checks(d, params, requests, arrivals, out["rid_of"], tr, control)
    read = readings(chk["gaps"])
    notes.append(f"checked every finished request: {chk['requests']} requests, "
                 f"{chk['tokens']} served tokens, in {chk['seconds']:.1f} s; widest gap "
                 f"{read['widest']:.4f}")
    record.update(sample=chk["sample"], params=params, gaps=chk["gaps"], readings=read)
    checks = [Check("served_gap_mean", read["program"], cell.limits["served_gap_mean"]),
              Check("sample_tokens_short", float(max(0, tr["check"]["min_tokens"] - chk["tokens"])),
                    0.0),
              Check("no_first_token", float(failed), 0.0),
              Check("window_compiles", float(out["window_compiles"]), 0.0)]
    return RunResult(e2e=e2e, attempted=len(arrivals), failed=failed, checks=checks,
                     record=record, memory_peak_bytes=peak, notes=notes)
