"""Serving cells: open-loop requests into the program's paged ``ServeEngine``.

Set-up makes the weights on the device (one jitted call), builds the
engine in the traffic file's geometry, and warms exactly the shapes the
traffic uses: one prompt per prefill bucket up to the chunk, one prompt
one token longer than a chunk (a continued chunk), and a decode tick.

The window: each request is submitted when it falls due on the wall
clock, stamped with the scheduler's current virtual time so that
admission is never held back by it (the virtual clock is never a timing
source). ``engine.step()`` runs in a loop; every new token is stamped
with the wall clock when ``step()`` returns. After the window closes the
loop runs on until every request due in it has its first token, up to
``drain_s``; the token rate and the gaps between tokens count only
tokens emitted inside the window (the drain has no arrivals, and its
lighter load would flatter the tail).
"""

from __future__ import annotations

import gc
import time
from typing import Dict, List

import numpy as np

from ..common import memory_peak_bytes
from ..gen import load as load_generator
from ..model import dims_of, program_config
from ..tracing import Window
from ..weights import make_weights
from . import Check, RunResult


def build_engine(model, params, tr: Dict):
    from repro.serve import Scheduler, ServeEngine

    return ServeEngine(
        model, params, n_slots=tr["slots"], max_len=tr["max_len"],
        scheduler=Scheduler(tr["slots"], prefill_chunk=tr["prefill_chunk"],
                            decode_per_prefill=tr["decode_per_prefill"]),
        block_size=tr["block_size"], arena_blocks=tr.get("arena_blocks"),
        prefill_bucket=tr["prefill_bucket"],
    )


def warm(engine, tr: Dict, vocab: int) -> None:
    """Compile (or load) every program the traffic's requests reach."""
    import jax

    lengths, b = [], tr["prefill_bucket"]
    while b < tr["prefill_chunk"]:
        lengths.append(b)
        b *= 2
    lengths += [tr["prefill_chunk"], tr["prefill_chunk"] + 1]
    rng = np.random.default_rng(0)
    for n in lengths:
        engine.submit(rng.integers(0, vocab, size=n).astype(np.int32), 2,
                      arrival=engine.sched.clock.now)
    while engine.step() != "done":
        pass
    jax.block_until_ready(engine.pool.caches)


def _percentile(x, q: float) -> float:
    return float(np.percentile(np.asarray(x, float), q, method="higher"))


def drive(engine, arrivals, seconds: float, drain_s: float, window: Window,
          trace_seconds: float, clock) -> Dict:
    """Run the open-loop window; returns per-request stamps and records."""
    import jax
    from jax.profiler import TraceAnnotation

    n = len(arrivals)
    rid_of: List[int] = [-1] * n
    stamps: Dict[int, List[float]] = {}
    lateness: List[float] = []
    steps: List[tuple] = []            # (kind, info) of the traced steps
    depth: List[tuple] = []            # (seconds, requests waiting or mid-prefill)
    owners = engine.pool.owner
    nxt, closed = 0, False
    window.start()
    t_open = time.perf_counter()
    c_open = clock.compiles + clock.cache_hits
    c_close = c_open
    while True:
        now = time.perf_counter() - t_open
        while nxt < n and arrivals[nxt].due <= now:
            a = arrivals[nxt]
            with TraceAnnotation("bench.submit"):
                rid = engine.submit(a.prompt, a.max_new, arrival=engine.sched.clock.now)
            rid_of[nxt] = rid
            stamps[rid] = []
            lateness.append(now - a.due)
            nxt += 1
        if window.active and now >= trace_seconds:
            jax.block_until_ready(engine.pool.caches)
            window.stop()
        if not closed and now >= seconds:
            closed = True
            c_close = clock.compiles + clock.cache_hits
        if closed and (now >= seconds + drain_s or all(stamps[r] for r in rid_of)):
            break
        if not engine.has_work:
            wait = (arrivals[nxt].due - now) if nxt < n else (seconds - now)
            with TraceAnnotation("bench.wait"):
                time.sleep(min(max(wait, 0.0), 0.0005))
            continue
        before = [r for r in owners if r is not None]
        prefill_tokens = engine.stats.prefill_tokens
        with TraceAnnotation("engine.step"):
            kind = engine.step()
        t = time.perf_counter() - t_open
        depth.append((t, len(engine.sched.waiting) + len(engine.sched.running)))
        owners = engine.pool.owner
        contexts = []
        for rid in set(before).union(r for r in owners if r is not None):
            if rid not in stamps:
                continue                       # a warm-up request
            req = engine.request(rid)
            new = len(req.tokens) - len(stamps[rid])
            if new > 0:
                stamps[rid].extend([t] * new)
                contexts.append(req.prompt_len + len(req.tokens) - 1)
        if window.active:
            if kind == "prefill":
                req = engine.request(engine.events[-1][2])
                n_tok = engine.stats.prefill_tokens - prefill_tokens
                steps.append(("prefill", (req.prefilled - n_tok, n_tok)))
            else:
                steps.append((kind, tuple(contexts)))
    t_end = time.perf_counter() - t_open
    window.stop()
    return {"rid_of": rid_of, "stamps": stamps, "lateness": lateness, "steps": steps,
            "depth": depth,
            "t_end": t_end, "window_compiles": c_close - c_open,
            "t_open": t_open}


def summarize(arrivals, out: Dict, seconds: float):
    """(end-to-end metrics, requests failed, a note) of one window. Time
    to first token runs from each request's due time; one with no first
    token by the end of the drain counts its whole wait, and as failed."""
    ttft, itl, failed, in_window = [], [], 0, 0
    for i, a in enumerate(arrivals):
        s = out["stamps"].get(out["rid_of"][i], [])
        if not s:
            failed += 1
        ttft.append(((s[0] if s else out["t_end"]) - a.due) * 1e3)
        inside = [x for x in s if x < seconds]
        itl += list(np.diff(inside) * 1e3)
        in_window += len(inside)
    e2e = {"serve_ttft_p90_ms": _percentile(ttft, 90),
           "serve_itl_p95_ms": _percentile(itl, 95) if itl else float(out["t_end"] * 1e3),
           "serve_tokens_per_s": in_window / seconds}
    late = out["lateness"] or [0.0]
    note = (f"window: {len(arrivals)} requests due, {failed} without a first token after "
            f"{out['t_end'] - seconds:.1f} s of drain; {in_window} tokens in the window; "
            f"{len(itl)} gaps; generator late by mean {1e3 * np.mean(late):.3f} ms, "
            f"max {1e3 * max(late):.3f} ms; {out['window_compiles']} compiles inside")
    return e2e, failed, note


def served_checks(d, params, engine_requests, arrivals, rid_of, tr: Dict, seed: int) -> Dict:
    """Widest reference gap over a seeded sample of the finished requests,
    the longest among them: requests are taken until the sample holds
    ``check.min_tokens`` served tokens and ``check.min_requests``
    requests, or every finished one."""
    from .. import reference

    t0 = time.perf_counter()
    done = [i for i, r in enumerate(rid_of)
            if r >= 0 and len(engine_requests[r].tokens) == arrivals[i].max_new]
    if not done:
        return {"gap": float("inf"), "requests": 0, "tokens": 0, "sample": [], "seconds": 0.0}
    rng = np.random.default_rng([int(seed), 13])
    longest = max(done, key=lambda i: len(arrivals[i].prompt) + arrivals[i].max_new)
    order = [longest] + [i for i in rng.permutation(done) if i != longest]
    chk = tr["check"]
    widest, n_tok, sample = 0.0, 0, []
    for i in order:
        if n_tok >= chk["min_tokens"] and len(sample) >= chk["min_requests"]:
            break
        toks = list(engine_requests[rid_of[i]].tokens)
        g = reference.served_gaps(d, params, arrivals[i].prompt, toks)
        widest = max(widest, float(g.max()))
        n_tok += len(toks)
        sample.append((arrivals[i].prompt, toks))
    return {"gap": widest, "requests": len(sample), "tokens": n_tok, "sample": sample,
            "finished": len(done), "seconds": time.perf_counter() - t0}


def run(cell, seed: int, seconds: float, trace_dir, devices, clock, t0: float) -> RunResult:
    from repro.models import build_model

    tr = cell.traffic
    cfg = program_config(cell.config)
    d = dims_of(cell.config)
    model = build_model(cfg)
    params = make_weights("serving", d, cfg.dtype, seed)
    engine = build_engine(model, params, tr)
    warm(engine, tr, d.vocab)
    arrivals = load_generator(tr["generator"]).arrivals(tr, seed, seconds, d.vocab)
    window = Window(trace_dir)
    out = drive(engine, arrivals, seconds, tr["drain_s"], window, tr["trace_seconds"], clock)
    peak = memory_peak_bytes(devices)
    setup_s = out["t_open"] - t0

    e2e, failed, note = summarize(arrivals, out, seconds)
    e2e["setup_s"] = setup_s
    notes = [note]
    record = {"kind": "serve", "dims": d, "steps": out["steps"],
              "unattributed": "engine loop (unattributed)",
              "span_labels": {"engine.step": [f"engine.{k}" for k, _ in out["steps"]]}}

    # -- the reference, once the window has closed and the arena is freed --
    requests = {r: engine.request(r) for r in out["rid_of"] if r >= 0}
    del engine
    gc.collect()
    chk = served_checks(d, params, requests, arrivals, out["rid_of"], tr, seed)
    notes.append(f"checked {chk['requests']} of {chk.get('finished', 0)} finished requests, "
                 f"{chk['tokens']} served tokens, in {chk['seconds']:.1f} s")
    record.update(sample=chk["sample"], params=params)
    checks = [Check("served_gap", chk["gap"], cell.limits["served_gap"]),
              Check("no_first_token", float(failed), 0.0),
              Check("window_compiles", float(out["window_compiles"]), 0.0)]
    return RunResult(e2e=e2e, attempted=len(arrivals), failed=failed, checks=checks,
                     record=record, memory_peak_bytes=peak, notes=notes)
