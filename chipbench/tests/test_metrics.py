"""Each per-layer reader on a hand-made reduction whose answer is known,
and the benchmark's files as the harness reads them."""

import importlib.util
import json

import pytest

from chipbench import flops
from chipbench.common import BENCH_DIR, ROOT, load_cell
from chipbench.model import dims_of

PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def reader(name):
    spec = importlib.util.spec_from_file_location(name, BENCH_DIR / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _dims(name):
    return dims_of(json.loads((BENCH_DIR / "configs" / f"{name}.json").read_text()))


def _red(module_s, module_n, busy=8.0, window=10.0, devices=1, coll=0.0):
    return {"module_s": module_s, "module_n": module_n, "busy_s": busy, "window_s": window,
            "devices": devices, "collective_s": coll}


def test_mfu_train():
    d = _dims("smollm-135m")
    rec = {"kind": "train", "dims": d, "seq_len": 1024, "applied_rows": [16] * 5}
    red = _red({"jit_train_step": 5.0}, {"jit_train_step": 5})
    want = 100 * 5 * flops.train_step_flops(d, 16, 1024) / (5.0 * 197e12)
    assert reader("mfu.train")(red, rec, PEAKS) == pytest.approx(want)
    assert reader("mfu.train")(_red({}, {}), rec, PEAKS) is None


def test_shares():
    rec = {"kind": "train"}
    assert reader("idle_share.train")(_red({}, {}), rec, PEAKS) == pytest.approx(20.0)
    assert reader("idle_share.serve")(_red({}, {}), rec, PEAKS) is None
    four = _red({}, {}, busy=8.0, devices=4, coll=2.0)
    assert reader("idle_share.train")(four, rec, PEAKS) == pytest.approx(20.0)


def test_serve_readers():
    d = _dims("qwen2.5-3b")
    red = _red({"jit_slot_prefill_step": 0.2, "jit_decode_tick": 0.8},
               {"jit_slot_prefill_step": 4, "jit_decode_tick": 20})
    steps = [("prefill", (0, 512))] * 4 + [("decode", (1000, 2000))] * 20
    rec = {"kind": "serve", "dims": d, "steps": steps}
    assert reader("prefill_ms.serve")(red, rec, PEAKS) == pytest.approx(50.0)
    assert reader("decode_ms.serve")(red, rec, PEAKS) == pytest.approx(40.0)
    fp, bp = flops.prefill_cost(d, 0, 512)
    fd, bd = flops.decode_cost(d, (1000, 2000))
    need = 4 * max(fp / 197e12, bp / 819e9) + 20 * max(fd / 197e12, bd / 819e9)
    assert reader("mfu.serve")(red, rec, PEAKS) == pytest.approx(100 * need / 1.0)


def test_every_cell_loads_and_every_metric_has_a_reader():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for w in bench["workloads"]:
        cell = load_cell(w["name"])
        assert cell.metric_names("end_to_end")
        assert "setup_s" in cell.metric_names("end_to_end")
        assert cell.metric_names("per_layer")
    for m in bench["per_layer"]:
        assert (BENCH_DIR / "metrics" / f"{m['name']}.py").is_file()


def test_config_files_hold_what_the_program_runs():
    from chipbench.model import program_config

    for c in json.loads((ROOT / "BENCHMARK.json").read_text())["configs"]:
        program_config(json.loads((ROOT / c["file"]).read_text()))
