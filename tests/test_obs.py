"""Observability plane: tracer, metrics registry, decision log,
structured log — and their integration with the serve + train planes.

Pinned contracts (docs/observability.md):

* DISABLED is free and inert: ``NULL_OBS`` hands out no-op instruments,
  ``begin_span`` returns 0, nothing is recorded anywhere.
* The default trace export is a pure function of virtual execution —
  identical seeds produce BYTE-IDENTICAL JSON, chaos included, and
  tracing does not perturb greedy token streams.
* Span hygiene survives chaos: cancel, deadline expiry, failover, and
  migration all CLOSE the request span (and bump the matching counter);
  ``open_spans`` is empty after every clean run.
* ``validate_trace`` catches the failure modes it claims to: orphan
  ends, unclosed spans, inverted spans, negative durations,
  non-monotone per-track timestamps.
* Metrics are deterministic: the histogram's reservoir decimation uses
  no RNG; counters refuse negative increments; gauges track high-water.
* The decision log is bounded (drops are counted, never silent) and
  records on CHANGE only for repriced (gamma, hedge) plans.
"""

import json

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.delay_models import SimplifiedDelayModel
from repro.models import build_model
from repro.obs import (
    NULL_OBS,
    DecisionLog,
    MetricsRegistry,
    Observability,
    StructuredLog,
    Tracer,
    validate_trace,
)
from repro.runtime.faults import FaultEvent
from repro.serve import Frontend, Replica, ServeEngine, generate_offline

RNG = jax.random.PRNGKey(0)
MAX_LEN = 64
DELAY = SimplifiedDelayModel(lambda_y=2.0)


def _model():
    cfg = get_config("smollm-135m").reduced()
    model = build_model(cfg)
    return model, model.init(RNG)


def _prompts(vocab, n=8, seed=1):
    rng = np.random.default_rng(seed)
    out = []
    for i in range(n):
        p = int(rng.integers(4, 16))
        m = int(rng.integers(6, 14))
        out.append((rng.integers(0, vocab, size=p).astype(np.int32), m, i * 0.002))
    return out


def _chaos_run(model, params, obs):
    """3-replica plane, kill 1 mid-flight, rejoin later; returns token
    streams so callers can assert determinism alongside hygiene."""
    reqs = _prompts(model.cfg.vocab_size, n=8, seed=5)
    replicas = [
        Replica(i, model, params, n_slots=2, max_len=MAX_LEN,
                block_size=8, obs=obs)
        for i in range(3)
    ]
    fe = Frontend(
        replicas, DELAY, cost_per_replica=0.001,
        events=[FaultEvent(step=12, kind="fail", worker=1),
                FaultEvent(step=60, kind="rejoin", worker=1)],
        deadline=0.5, retry_budget=3, obs=obs,
    )
    gids = [fe.submit(p, m, arrival=a) for p, m, a in reqs]
    out = fe.run()
    assert fe.summary()["dropped"] == 0
    return [out[g].tokens for g in gids]


# ---------------------------------------------------------------------------
# Disabled mode: free and inert
# ---------------------------------------------------------------------------

def test_null_obs_is_inert():
    obs = NULL_OBS
    assert not obs.enabled
    assert obs.tracer.register_process("x") == 0
    sid = obs.tracer.begin_span("request", 0, 1.0)
    assert sid == 0
    obs.tracer.end_span(sid, 2.0)            # no-op, no raise
    obs.tracer.complete("decode", 0, 1.0, 2.0)
    obs.tracer.instant("cancel", 0, 1.0)
    obs.tracer.counter("occupancy", 0, 1.0, {"slots": 1})
    assert obs.tracer.events == [] and obs.tracer.open_spans == []

    c = obs.metrics.counter("a")
    c.inc(5)                                 # null instrument: writes vanish
    assert obs.metrics.snapshot() == {}
    # Null instruments are shared singletons — no per-name allocation.
    assert obs.metrics.counter("a") is obs.metrics.counter("b")
    assert obs.metrics.histogram("h") is obs.metrics.histogram("h2")

    obs.decisions.record("serve.gamma", {"gamma": 2}, {"p": 0.5})
    assert obs.decisions.to_jsonable()["entries"] == []

    rec = obs.log.emit("x", a=1)
    assert rec.kind == "x" and obs.log.records == []


def test_disabled_obs_engine_records_nothing():
    model, params = _model()
    eng = ServeEngine(model, params, n_slots=2, max_len=MAX_LEN)  # NULL_OBS
    prompt = np.arange(5, dtype=np.int32)
    eng.submit(prompt, 4)
    eng.run()
    assert eng.obs is NULL_OBS
    assert eng.obs.tracer.events == []
    assert eng.obs.metrics.snapshot() == {}


# ---------------------------------------------------------------------------
# Trace determinism + non-perturbation
# ---------------------------------------------------------------------------

def test_trace_byte_identical_across_identical_seeds():
    model, params = _model()
    obs1, obs2 = Observability(), Observability()
    s1 = _chaos_run(model, params, obs1)
    s2 = _chaos_run(model, params, obs2)
    assert s1 == s2
    j1, j2 = obs1.tracer.to_json(), obs2.tracer.to_json()
    assert j1 == j2, "identical seeds must export byte-identical traces"


def test_tracing_does_not_perturb_streams():
    model, params = _model()
    reqs = _prompts(model.cfg.vocab_size, n=8, seed=5)  # _chaos_run workload
    refs = [generate_offline(model, params, p, m, MAX_LEN)
            for p, m, _ in reqs]
    traced = _chaos_run(model, params, Observability())
    plain = _chaos_run(model, params, NULL_OBS)
    # Chaos + tracing vs untraced vs per-request offline: same bytes.
    assert traced == plain == refs


# ---------------------------------------------------------------------------
# Span hygiene under chaos
# ---------------------------------------------------------------------------

def test_chaos_closes_every_span_and_trace_validates():
    model, params = _model()
    obs = Observability()
    _chaos_run(model, params, obs)
    assert obs.tracer.open_spans == [], "spans leaked across kill-1-of-3"
    assert validate_trace(obs.tracer.events) == []
    # Chaos left its marks: fault instants + cancel counters exist.
    snap = obs.metrics.snapshot()
    assert snap["replica.fault.fail"] >= 1
    assert snap["replica.fault.rejoin"] >= 1
    names = {ev["name"] for ev in obs.tracer.events}
    assert {"request", "prefill", "decode", "fault", "dispatch"} <= names


def test_cancel_closes_span_and_counts():
    model, params = _model()
    obs = Observability()
    eng = ServeEngine(model, params, n_slots=2, max_len=MAX_LEN, obs=obs)
    rid = eng.submit(np.arange(6, dtype=np.int32), 8)
    eng.step()                               # prefill begins the lifecycle
    assert obs.tracer.open_spans == ["request"]
    eng.cancel(rid, reason="cancelled")
    assert obs.tracer.open_spans == []
    assert obs.metrics.snapshot()["engine.cancel.cancelled"] == 1
    ends = [ev for ev in obs.tracer.events if ev["ph"] == "e"]
    assert ends and ends[-1]["args"]["outcome"] == "cancelled"


def test_migration_closes_source_span_opens_dest_span():
    model, params = _model()
    obs = Observability()
    src = ServeEngine(model, params, n_slots=2, max_len=MAX_LEN, obs=obs,
                      obs_name="src")
    dst = ServeEngine(model, params, n_slots=2, max_len=MAX_LEN, obs=obs,
                      obs_name="dst")
    prompt = np.arange(6, dtype=np.int32)
    ref = generate_offline(model, params, prompt, 8, MAX_LEN)
    rid = src.submit(prompt, 8)
    for _ in range(3):
        src.step()
    ticket = src.export_request(rid)
    assert src.obs.tracer.open_spans == []   # "migrated" closed it...
    rid2 = dst.import_request(ticket)
    assert obs.tracer.open_spans == ["request"]   # ...and dest reopened
    out = dst.run()
    assert obs.tracer.open_spans == []
    assert out[rid2].tokens == ref
    snap = obs.metrics.snapshot()
    assert snap["engine.migrated_out"] == 1
    assert snap["engine.migrated_in"] == 1
    kinds = [ev["name"] for ev in obs.tracer.events if ev["ph"] == "i"]
    assert "migrate_out" in kinds and "migrate_in" in kinds


# ---------------------------------------------------------------------------
# validate_trace: the invariants actually trip
# ---------------------------------------------------------------------------

def test_validate_trace_catches_violations():
    ok = [
        {"ph": "b", "cat": "c", "name": "s", "pid": 1, "tid": 0, "id": 1,
         "ts": 1.0},
        {"ph": "e", "cat": "c", "name": "s", "pid": 1, "tid": 0, "id": 1,
         "ts": 2.0},
        {"ph": "X", "name": "x", "pid": 1, "tid": 0, "ts": 2.0, "dur": 1.0},
        {"ph": "i", "name": "i", "pid": 1, "tid": 0, "ts": 3.0, "s": "p"},
    ]
    assert validate_trace(ok) == []

    orphan = [{"ph": "e", "cat": "c", "name": "s", "pid": 1, "id": 9,
               "ts": 1.0}]
    assert any("orphan" in e for e in validate_trace(orphan))

    unclosed = [{"ph": "b", "cat": "c", "name": "s", "pid": 1, "id": 1,
                 "ts": 1.0}]
    assert any("unclosed" in e for e in validate_trace(unclosed))

    inverted = [
        {"ph": "b", "cat": "c", "name": "s", "pid": 1, "id": 1, "ts": 5.0},
        {"ph": "e", "cat": "c", "name": "s", "pid": 1, "id": 1, "ts": 1.0},
    ]
    assert any("before it begins" in e for e in validate_trace(inverted))

    negdur = [{"ph": "X", "name": "x", "pid": 1, "ts": 1.0, "dur": -0.5}]
    assert any("negative duration" in e for e in validate_trace(negdur))

    backwards = [
        {"ph": "X", "name": "x", "pid": 1, "tid": 0, "ts": 5.0, "dur": 1.0},
        {"ph": "i", "name": "i", "pid": 1, "tid": 0, "ts": 2.0, "s": "p"},
    ]
    assert any("non-monotone" in e for e in validate_trace(backwards))


def test_tracer_end_span_twice_raises():
    tr = Tracer()
    pid = tr.register_process("p")
    sid = tr.begin_span("s", pid, 1.0)
    tr.end_span(sid, 2.0)
    with pytest.raises(ValueError):
        tr.end_span(sid, 3.0)


# ---------------------------------------------------------------------------
# Metrics registry
# ---------------------------------------------------------------------------

def test_metrics_registry_basics():
    m = MetricsRegistry()
    c = m.counter("c")
    c.inc()
    c.inc(3)
    assert c.value == 4
    with pytest.raises(ValueError):
        c.inc(-1)
    assert m.counter("c") is c               # same name -> same instrument
    with pytest.raises(TypeError):
        m.gauge("c")                         # kind mismatch

    g = m.gauge("g")
    g.set(2.0)
    g.set(7.0)
    g.set(3.0)
    assert g.value == 3.0 and g.high_water == 7.0

    h = m.histogram("h")
    for v in [1.0, 2.0, 3.0, 4.0, 100.0]:
        h.observe(v)
    s = h.snapshot()
    assert s["count"] == 5 and s["max"] == 100.0 and s["min"] == 1.0
    assert h.percentile(50) == 3.0

    snap = m.snapshot()
    assert list(snap) == sorted(snap)
    assert snap["c"] == 4
    assert snap["g"] == {"value": 3.0, "high_water": 7.0}


def test_histogram_deterministic_under_decimation():
    def fill(seed):
        h = MetricsRegistry().histogram("h")
        rng = np.random.default_rng(seed)
        for v in rng.exponential(1.0, size=20_000):
            h.observe(float(v))
        return h

    h1, h2 = fill(3), fill(3)
    assert h1.snapshot() == h2.snapshot()    # no RNG in the reservoir
    assert h1.snapshot()["count"] == 20_000
    # Decimated percentile stays close to the exact one.
    exact = float(np.percentile(np.random.default_rng(3).exponential(
        1.0, size=20_000), 99))
    assert abs(h1.percentile(99) - exact) / exact < 0.1


def test_empty_histogram_snapshot_is_json_safe():
    h = MetricsRegistry().histogram("h")
    assert json.dumps(h.snapshot())          # "nan" strings, not float nan


# ---------------------------------------------------------------------------
# Decision log
# ---------------------------------------------------------------------------

def test_decision_log_bounded_with_counted_drops():
    d = DecisionLog(cap=10)
    for i in range(25):
        d.record("serve.gamma", {"gamma": i}, {"p": 0.5}, step=i)
    out = d.to_jsonable()
    assert len(out["entries"]) == 10
    assert out["dropped"] == 15
    assert [x["decision"]["gamma"] for x in out["entries"]] == list(range(10))


def test_spec_controller_records_gamma_changes_only():
    from repro.serve import SpecController
    from repro.serve.scheduler import CostModel

    obs = Observability()
    ctl = SpecController(gamma_max=4)
    ctl.obs = obs
    cost = CostModel()
    for _ in range(40):
        ctl.observe(3, 4)                    # high acceptance
        ctl.choose_gamma(cost)
    recs = obs.decisions.by_domain("serve.gamma")
    assert recs, "at least the first plan must be recorded"
    gammas = [r.decision["gamma"] for r in recs]
    assert all(a != b for a, b in zip(gammas, gammas[1:])), \
        "decision log must record on change only"
    assert {"p", "observations", "cost_per_token"} <= set(recs[0].inputs)


# ---------------------------------------------------------------------------
# Structured log
# ---------------------------------------------------------------------------

def test_structured_log_echo_is_a_view_of_records(capsys):
    log = StructuredLog(echo=True)
    log.emit("step", t=1.5, loss=0.25, k=3)
    log.emit("done", ok=True)
    out = capsys.readouterr().out.splitlines()
    assert out[0] == log.records[0].format()
    assert out[1] == log.records[1].format()
    assert log.last("step").fields["k"] == 3
    assert [r["kind"] for r in log.to_jsonable()] == ["step", "done"]


def test_structured_log_silent_still_records(capsys):
    log = StructuredLog(echo=False)
    log.emit("step", loss=1.0)
    assert capsys.readouterr().out == ""
    assert len(log.by_kind("step")) == 1


# ---------------------------------------------------------------------------
# Snapshot export
# ---------------------------------------------------------------------------

def test_observability_snapshot_roundtrip(tmp_path):
    model, params = _model()
    obs = Observability()
    _chaos_run(model, params, obs)
    path = tmp_path / "snap.json"
    obs.export_snapshot(str(path))
    snap = json.loads(path.read_text())
    assert snap["open_spans"] == []
    assert snap["trace_events"] == len(obs.tracer.events)
    assert "engine.generated_tokens" in snap["metrics"]
