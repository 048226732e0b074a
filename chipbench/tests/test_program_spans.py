"""The program's wall spans as the benchmark reads them: the three readers
(``host_ms.train``, ``host_ms.serve``, ``queue_wait_ms.serve``) on
hand-made spans whose answers are known, the idle breakdown by the
innermost span, and the spans' stats as a CPU trace records them."""

import jax
import pytest

from chipbench import program_spans as ps
from chipbench.common import BENCH_DIR
from chipbench.tests.test_metrics import PEAKS, reader

MS = 1e-3


def _red(window=0.1, busy=0.08):
    return {"window_s": window, "busy_s": busy, "devices": 1}


def _train_spans():
    """Two steps of 40 ms, 10 ms apart; in each the loss read takes 35 ms
    and the rest (plan, batch, put, dispatch, control) 5 ms."""
    out = []
    for i, t in enumerate((0.0, 0.05)):
        ms = lambda x: t + x * MS  # noqa: E731
        out += [[ms(0), ms(40), "repro.train.step", {"step": i}],
                [ms(0), ms(1), "repro.train.plan", {}],
                [ms(1), ms(1.5), "repro.train.batch", {}],
                [ms(1.5), ms(2), "repro.train.put", {}],
                [ms(2), ms(3), "repro.train.dispatch", {}],
                [ms(3), ms(38), "repro.train.wait", {}],
                [ms(38), ms(40), "repro.train.control", {}],
                [ms(38.5), ms(39), "repro.train.read", {}]]
    return ps._nested_order(out)


def _serve_spans():
    """rid 0 submitted at 0 and prefilled at 10 ms (step [10, 30], sync
    12 ms); a decode step [30, 45] with a 10 ms sync; rid 1 submitted at
    40 ms and prefilled at 60 ms (step [60, 70], no sync: a continued
    chunk); rid 2 submitted at 80 ms, never prefilled in the window."""
    ms = lambda a, b, n, **k: [a * MS, b * MS, n, k]  # noqa: E731
    return ps._nested_order([
        ms(0, 0.2, "repro.engine.submit", rid=0),
        ms(10, 30, "repro.engine.step"), ms(10, 11, "repro.engine.schedule"),
        ms(11, 30, "repro.engine.prefill", rid=0), ms(12, 14, "repro.engine.dispatch"),
        ms(16, 28, "repro.engine.sync"),
        ms(30, 45, "repro.engine.step"), ms(30, 31, "repro.engine.schedule"),
        ms(31, 45, "repro.engine.decode"), ms(32, 33, "repro.engine.dispatch"),
        ms(33, 43, "repro.engine.sync"),
        ms(40, 40.1, "repro.engine.submit", rid=1),
        ms(50, 51, "repro.engine.step"), ms(50, 51, "repro.engine.schedule"),
        ms(60, 70, "repro.engine.step"), ms(60, 61, "repro.engine.schedule"),
        ms(61, 70, "repro.engine.prefill", rid=1), ms(62, 64, "repro.engine.dispatch"),
        ms(80, 80.1, "repro.engine.submit", rid=2),
        ms(85, 90, "repro.engine.prefill", rid=0),     # a later chunk of rid 0
    ])


@pytest.fixture
def loaded(monkeypatch):
    def use(spans, gaps=(), host=()):
        data = {"window_s": 0.1, "spans": spans, "gaps": list(gaps), "host": list(host)}
        monkeypatch.setattr(ps, "load", lambda trace_dir=None: data)
    return use


def test_host_ms_train(loaded):
    loaded(_train_spans())
    rec = {"kind": "train", "applied_rows": [16, 16]}
    assert reader("host_ms.train")(_red(), rec, PEAKS) == pytest.approx(5.0)
    assert reader("host_ms.train")(_red(), {"kind": "serve"}, PEAKS) is None


def test_host_ms_serve(loaded):
    loaded(_serve_spans())
    # prefill step 20 - 12 = 8 ms, decode 15 - 10 = 5, continued chunk 10;
    # the step that ran no action is left out
    assert reader("host_ms.serve")(_red(), {"kind": "serve"}, PEAKS) == pytest.approx(23 / 3)
    assert reader("host_ms.serve")(_red(), {"kind": "train"}, PEAKS) is None


def test_queue_wait_censors_a_request_with_no_prefill(loaded):
    loaded(_serve_spans())
    assert ps.queue_waits_ms(_serve_spans(), 0.1) == [
        (0, pytest.approx(11.0), False), (1, pytest.approx(21.0), False),
        (2, pytest.approx(20.0), True)]
    value = reader("queue_wait_ms.serve")(_red(), {"kind": "serve"}, PEAKS)
    assert value == pytest.approx((11 + 21 + 20) / 3)


@pytest.mark.parametrize("name,kind", [("host_ms.train", "train"), ("host_ms.serve", "serve"),
                                       ("queue_wait_ms.serve", "serve")])
def test_readers_find_nothing_without_program_spans(monkeypatch, name, kind):
    monkeypatch.setattr(ps, "load", lambda trace_dir=None: None)
    assert reader(name)(_red(), {"kind": kind}, PEAKS) is None


def test_fixture_has_no_program_spans():
    assert ps.load(str(BENCH_DIR / "data")) is None


def test_idle_gaps_go_to_the_innermost_span():
    """Gaps at 1.4-2.4 ms (midpoint in put, inside step), 38.2-39.6 ms
    (midpoint in read, inside control, inside step), 40-50 ms (between
    steps: the harness's bench.batch), 99-100 ms (no span)."""
    data = {"spans": _train_spans(), "host": [[0.041, 0.049, "bench.batch"]],
            "gaps": [[1.4 * MS, 2.4 * MS], [38.2 * MS, 39.6 * MS], [40 * MS, 50 * MS],
                     [99 * MS, 100 * MS]]}
    idle = ps.idle_by_span(data)
    assert idle["repro.train.put"] == pytest.approx(1 * MS)
    assert idle["repro.train.read"] == pytest.approx(1.4 * MS)
    assert idle["bench.batch"] == pytest.approx(10 * MS)
    assert idle["(no span)"] == pytest.approx(1 * MS)


def test_program_spans_keep_their_stats(tmp_path):
    from repro.obs import span

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with jax.profiler.trace(str(tmp_path), profiler_options=opts):
        with span("bench.window"):
            with span("repro.engine.submit", rid=3):
                jax.numpy.ones(4).block_until_ready()
    from chipbench import trace

    spans = ps.read_program_spans(trace.find_xplane(tmp_path))
    assert [(s[2], s[3]) for s in spans] == [("repro.engine.submit", {"rid": 3})]
    assert spans[0][1] > spans[0][0]
