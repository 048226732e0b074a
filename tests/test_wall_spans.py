"""Wall spans (``repro.obs.span``) in the train loop and the serve engine.

Pinned contracts (docs/observability.md, "Wall spans"):

* Each training step is one ``repro.train.step`` span (``step=`` its
  index) holding plan, batch, put, dispatch, wait and control in that
  order, with the two extra device reads nested in control.
* Every ``ServeEngine.submit`` is a ``repro.engine.submit`` span with its
  ``rid``; each request's first prefill span comes after its submit;
  every decode tick holds exactly one dispatch and one sync.
* The batcher is called from ``train``'s own frame, whose locals hold
  ``params``, ``opt_state``, ``mask`` and ``history``: a batcher may read
  the loop's state from there.
* Spans record only while a profiler trace runs, with their keyword
  arguments as event stats and their names unchanged.
"""

import sys
import warnings
from pathlib import Path

import jax
import numpy as np
from jax.profiler import ProfileData

from repro.configs import get_config
from repro.core import DiagnosticConfig, SimplifiedDelayModel, StrategyConfig
from repro.data import StagedBatcher, TokenStream
from repro.models import build_model
from repro.obs import span
from repro.optim.optimizers import get_optimizer
from repro.runtime.train_loop import TrainLoopConfig, train
from repro.serve import Scheduler, ServeEngine

TRAIN_PHASES = ["repro.train.plan", "repro.train.batch", "repro.train.put",
                "repro.train.dispatch", "repro.train.wait", "repro.train.control"]


def _profile(log_dir):
    """Profiler options for host spans only (no Python call tracing)."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    return jax.profiler.trace(str(log_dir), profiler_options=opts)


def _program_spans(log_dir):
    """``(start_ns, end_ns, name, stats)`` of every ``repro.`` host event,
    parents before the spans they hold."""
    path = sorted(Path(log_dir).rglob("*.xplane.pb"))[-1]
    out = []
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        for plane in ProfileData.from_file(str(path)).planes:
            if not plane.name.startswith("/host:"):
                continue
            for line in plane.lines:
                out += [(e.start_ns, e.start_ns + e.duration_ns, e.name, dict(e.stats))
                        for e in line.events if e.name.startswith("repro.")]
    return sorted(out, key=lambda s: (s[0], -s[1]))


def _inside(parent, spans, name=None):
    return [s for s in spans if s is not parent and parent[0] <= s[0] and s[1] <= parent[1]
            and (name is None or s[2] == name)]


def _named(spans, name):
    return [s for s in spans if s[2] == name]


def _train_setup(batcher_cls=None):
    cfg = get_config("smollm-135m").reduced(
        n_layers=2, d_model=64, n_heads=2, n_kv_heads=2, head_dim=32,
        d_ff=128, vocab_size=256, max_seq_len=64,
    )
    strategy = StrategyConfig(
        "adaptive_kbeta", n=4, s=4, k_max=2, beta_grid=(0.5, 1.0),
        diagnostic=DiagnosticConfig(kind="loss", min_iters=10 ** 6),
    )
    batcher = StagedBatcher(TokenStream(cfg.vocab_size, seed=0), n_workers=4,
                            global_batch=16, seq_len=32)
    if batcher_cls is not None:
        batcher = batcher_cls(batcher)
    return (build_model(cfg), get_optimizer("adamw"), strategy,
            SimplifiedDelayModel(lambda_y=1.0, x=0.05), batcher)


def test_train_step_spans_hold_each_phase_in_order(tmp_path):
    steps = 3
    with _profile(tmp_path):
        out = train(*_train_setup(), TrainLoopConfig(total_steps=steps, log_every=0))
    assert len(out["history"]) == steps
    spans = _program_spans(tmp_path)
    step_spans = _named(spans, "repro.train.step")
    assert [s[3] for s in step_spans] == [{"step": i} for i in range(steps)]
    for s in step_spans:
        phases = [c[2] for c in _inside(s, spans) if c[2] != "repro.train.read"]
        assert phases == TRAIN_PHASES
        control = _inside(s, spans, "repro.train.control")[0]
        assert len(_inside(control, spans, "repro.train.read")) == 2
    # nothing of the loop's runs outside a step
    assert all(any(c in _inside(s, spans) for s in step_spans)
               for c in spans if c[2] != "repro.train.step")


class _FrameCheckingBatcher:
    """Records, at each call, its caller's function name and whether the
    loop's state is among that frame's locals."""

    STATE = {"params", "opt_state", "mask", "history"}

    def __init__(self, inner):
        self.inner = inner
        self.stream = inner.stream
        self.calls = []

    def batch_for_stage(self, beta, n_workers=None):
        frame = sys._getframe(1)
        self.calls.append((frame.f_code.co_name, self.STATE <= frame.f_locals.keys()))
        return self.inner.batch_for_stage(beta, n_workers=n_workers)


def test_batcher_is_called_from_the_loop_frame():
    model, opt, strategy, delay, batcher = _train_setup(_FrameCheckingBatcher)
    train(model, opt, strategy, delay, batcher, TrainLoopConfig(total_steps=2, log_every=0))
    assert batcher.calls == [("train", True)] * 2


def _engine_run(log_dir):
    cfg = get_config("smollm-135m").reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    engine = ServeEngine(model, params, n_slots=3, max_len=64, block_size=8,
                         scheduler=Scheduler(3, prefill_chunk=16, decode_per_prefill=2))
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, cfg.vocab_size, size=int(n)).astype(np.int32)
               for n in (5, 30, 12, 20, 9)]
    with _profile(log_dir):
        for p in prompts[:3]:
            engine.submit(p, 6)
        for _ in range(4):
            engine.step()
        for p in prompts[3:]:
            engine.submit(p, 6)
        engine.run()
    assert all(len(engine.request(r).tokens) == 6 for r in range(len(prompts)))
    return len(prompts), _program_spans(log_dir)


def test_engine_spans_submit_prefill_decode(tmp_path):
    n, spans = _engine_run(tmp_path)
    submits = _named(spans, "repro.engine.submit")
    assert [s[3] for s in submits] == [{"rid": r} for r in range(n)]
    prefills = _named(spans, "repro.engine.prefill")
    assert len(prefills) > n                      # the 30- and 20-token prompts chunk
    for sub in submits:
        mine = [p for p in prefills if p[3] == sub[3]]
        assert mine and mine[0][0] >= sub[1]
    decodes = _named(spans, "repro.engine.decode")
    assert decodes
    for d in decodes:
        assert len(_inside(d, spans, "repro.engine.dispatch")) == 1
        assert len(_inside(d, spans, "repro.engine.sync")) == 1
        # paged GQA off the TPU gathers; 1 to 3 lanes of at most 64 rows
        assert d[3]["kv_path"] == "gather"
        assert 1 <= d[3]["live_rows"] <= 3 * 64
    # every action runs inside a step, after that step's schedule span
    for a in prefills + decodes:
        step = [s for s in _named(spans, "repro.engine.step") if a in _inside(s, spans)]
        assert len(step) == 1
        sched = _inside(step[0], spans, "repro.engine.schedule")
        assert len(sched) == 1 and sched[0][1] <= a[0]


def test_span_records_only_under_a_trace_with_its_args(tmp_path):
    with span("repro.test.outside", rid=1):
        pass
    with _profile(tmp_path):
        with span("repro.test.inside", rid=7, step=2):
            with span("repro.test.nested"):
                pass
    spans = _program_spans(tmp_path)
    assert [(s[2], s[3]) for s in spans] == [("repro.test.inside", {"rid": 7, "step": 2}),
                                            ("repro.test.nested", {})]
