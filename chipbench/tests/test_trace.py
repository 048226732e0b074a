"""The trace reduction, on a small trace recorded on a TPU v5e and kept
in ``data/fixture.xplane.pb`` (``record_trace.py``: five calls of a
4096 x 4096 bf16 matmul program, each followed by a 20 ms sleep), and on
hand-made traces whose answers are known."""

import pytest

from chipbench import trace
from chipbench.common import BENCH_DIR

FIXTURE = BENCH_DIR / "data" / "fixture.xplane.pb"
CALLS, SLEEP_S = 5, 0.02


@pytest.fixture(scope="module")
def recorded():
    return trace.read_xplane(FIXTURE)


def test_fixture_planes(recorded):
    assert [d["name"] for d in recorded["devices"]] == ["/device:TPU:0"]
    mods = recorded["devices"][0]["modules"]
    assert [m[2] for m in mods] == ["jit_fixture_step"] * CALLS
    names = [h[2] for h in recorded["host"]]
    assert names.count("bench.window") == 1
    assert names.count("bench.batch") == CALLS and names.count("bench.wait") == CALLS
    assert all(not trace.is_container(o[2]) for o in recorded["devices"][0]["ops"])


def test_fixture_reduction(recorded):
    red = trace.reduce(recorded)
    secs, count = trace.module_time(red, "fixture_step")
    assert count == CALLS
    # a 4096^3 bf16 matmul is 137 GFLOP: 0.70 ms at 197 TFLOP/s; measured 0.75
    assert 0.7e-3 * CALLS < secs < 0.8e-3 * CALLS
    assert secs * 0.99 <= red["busy_s"] <= secs * 1.01
    assert red["window_s"] > CALLS * SLEEP_S
    assert red["busy_s"] < red["window_s"]
    assert red["collective_s"] == 0.0
    idle = dict(red["idle_gaps"])
    assert idle["bench.wait"] > 0.9 * CALLS * SLEEP_S
    assert sum(idle.values()) == pytest.approx(red["window_s"] - red["busy_s"], rel=0.02)


def _synthetic():
    """Two devices, window [0, 100]; module "jit_step" at [10, 40] on
    both, with an all-gather [10, 20] and a fusion [20, 40], and a while
    loop that contains them; host spans engine.step [6, 45] and [50, 75], labelled."""
    ms = 1_000_000
    dev = lambda: {"modules": [[10 * ms, 40 * ms, "jit_step"], [60 * ms, 70 * ms, "jit_step"]],
                   "ops": [[10 * ms, 40 * ms, "while.1"],
                           [10 * ms, 20 * ms, "all-gather.3"],
                           [20 * ms, 40 * ms, "fusion.7"],
                           [60 * ms, 70 * ms, "fusion.7"]]}
    return {"devices": [dict(dev(), name="/device:TPU:0"), dict(dev(), name="/device:TPU:1")],
            "host": [[0, 100 * ms, "bench.window"], [6 * ms, 45 * ms, "engine.step"],
                     [50 * ms, 75 * ms, "engine.step"], [80 * ms, 90 * ms, "bench.wait"]]}


def test_synthetic_reduction():
    red = trace.reduce(_synthetic(), span_labels={"engine.step": ["engine.prefill",
                                                                    "engine.decode"]},
                       unattributed="engine loop (unattributed)")
    assert red["devices"] == 2
    assert red["window_s"] == pytest.approx(0.1)
    assert red["busy_s"] == pytest.approx(0.04)
    assert red["collective_s"] == pytest.approx(0.01)
    assert trace.module_time(red, "step") == (pytest.approx(0.04), 2)
    ops = dict(red["top_ops"])
    assert ops["jit_step fusion.7"] == pytest.approx(0.03)
    assert ops["jit_step all-gather.3"] == pytest.approx(0.01)
    assert not any("while" in k for k in ops)
    idle = dict(red["idle_gaps"])
    # gaps: [0,10] unattributed, [40,60] midpoint 50 -> second step,
    # [70,100] midpoint 85 -> bench.wait
    assert idle["engine loop (unattributed)"] == pytest.approx(0.01)
    assert idle["engine.decode"] == pytest.approx(0.02)
    assert idle["bench.wait"] == pytest.approx(0.03)


def test_names():
    assert trace.module_name("jit_decode_tick(712)") == "jit_decode_tick"
    assert trace.op_name("%fusion.558 = (f32[32]{0}) fusion(%x), kind=kOutput") == "fusion.558"
    assert trace.is_collective("all-gather-start.2") and not trace.is_collective("fusion.1")
