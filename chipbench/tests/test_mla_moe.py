"""The latent-attention, sparse-expert serving cell's pieces, on the CPU:
the reference on a case worked by hand, the counts against hand counts,
the harness kind ``serve_mla_moe`` on a tiny cell (the program passes,
the fp8 control and an altered token fail), its readers, and the
configuration file refused key by key."""

import copy
import importlib.util
import json
import math
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import flops_mla_moe as fl
from chipbench import reference_mla_moe as ref
from chipbench.common import BENCH_DIR, BenchError, CompileClock
from chipbench.harness import serve_mla_moe
from chipbench.model_mla_moe import dims_of, program_config
from chipbench.tests import tiny_mla_moe

PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def _moonlight():
    return json.loads((BENCH_DIR / "configs" / "moonlight-16b-a3b.json").read_text())


def reader(name):
    spec = importlib.util.spec_from_file_location(name, BENCH_DIR / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def test_reference_routes_a_case_worked_by_hand():
    """Four experts, top-2, one token: sigmoid scores 0.5, 0.731, 0.269,
    0.622 (logits 0, 1, -1, 0.5). The bias lifts expert 0 over expert 3 in
    the choice; the weights are the unbiased scores of experts 1 and 0,
    renormalised, times the scaling."""
    d = dims_of(dict(_moonlight(), n_routed_experts=2, published={"n_routed_experts": 4},
                     num_experts_per_tok=2, routed_scaling_factor=2.0,
                     expert_share={"held": 2, "offset": 0, "of": 4, "ranks": 2}))
    x = jnp.ones((1, 1))
    router = jnp.array([[0.0, 1.0, -1.0, 0.5]])
    experts, w = ref.route(d, x, router, jnp.array([0.2, 0.0, 0.0, 0.0]))
    s = 1 / (1 + math.exp(-1.0))
    assert sorted(np.asarray(experts[0]).tolist()) == [0, 1]
    got = dict(zip(np.asarray(experts[0]).tolist(), np.asarray(w[0]).tolist()))
    assert got[1] == pytest.approx(2.0 * s / (s + 0.5), rel=1e-6)
    assert got[0] == pytest.approx(2.0 * 0.5 / (s + 0.5), rel=1e-6)
    experts, _ = ref.route(d, x, router, jnp.zeros(4))
    assert sorted(np.asarray(experts[0]).tolist()) == [1, 3]


def test_reference_attends_one_token_to_itself():
    """One token: the softmax is 1 on itself, so attention is wo applied to
    that token's value, expanded from its normed latent by W_UV."""
    d = dims_of(tiny_mla_moe.TINY_MLA_MOE)
    keys = jax.random.split(jax.random.PRNGKey(0), 5)
    D, H, r = d.d_model, d.n_heads, d.kv_lora_rank
    a = {"wq": jax.random.normal(keys[0], (D, H, d.qk_head)),
         "wkv_a": jax.random.normal(keys[1], (D, r + d.qk_rope)),
         "kv_norm": {"scale": jnp.ones(r)},
         "wkv_b": jax.random.normal(keys[2], (r, H, d.qk_nope + d.v_head)),
         "wo": jax.random.normal(keys[3], (H, d.v_head, D))}
    x = np.asarray(jax.random.normal(keys[4], (1, D)), np.float64)
    c = x @ np.asarray(a["wkv_a"], np.float64)[:, :r]
    c = c / np.sqrt(np.mean(c * c) + ref.LATENT_EPS)
    v = np.einsum("sr,rhe->she", c, np.asarray(a["wkv_b"], np.float64)[..., d.qk_nope:])
    want = np.einsum("she,hed->sd", v, np.asarray(a["wo"], np.float64))
    got = ref._attention(d, None, jnp.asarray(x, jnp.float32), a)
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-4, atol=1e-3)


def test_counts_against_hand_counts():
    d = dims_of(_moonlight())
    # a MoE layer: attention 6,291,456 (wq) + 1,179,648 (wkv_a) + 512
    # (kv_norm) + 2,097,152 (wkv_b) + 4,194,304 (wo); 4,096 of norms;
    # router 131,072 and its bias 64; 8 experts of 8,650,752; the shared
    # SwiGLU of 2,816: 17,301,504
    moe_layer = 13_763_072 + 4_096 + 131_072 + 64 + 8 * 8_650_752 + 17_301_504
    dense_layer = 13_763_072 + 4_096 + 3 * 2048 * 11264
    total = 26 * moe_layer + dense_layer + 2 * 163840 * 2048 + 2048
    assert moe_layer == 100_405_824 and total == 3_364_615_296
    assert fl.param_count(d) == total
    from repro.configs import get_config
    from repro.models.model import count_params_analytic

    assert count_params_analytic(get_config("moonlight-16b-a3b")) == total
    assert fl.latent_bytes_per_token(d) == 31_104
    # one lane over 100 rows: attention 2 x (6,291,456 + 1,179,648 +
    # 4,194,304) a token and 2 x 16 x 320 a pair; the dense layer's
    # SwiGLU; a MoE layer's router, shared SwiGLU and 6 x 8 / 64 routed rows
    flops, nbytes = fl.decode_cost(d, [100])
    want = (27 * (2 * 11_665_408 + 2 * 100 * 16 * 320) + 2 * 3 * 2048 * 11264
            + 26 * (2 * (131_072 + 17_301_504) + 2 * 0.75 * 8_650_752) + 2 * 2048 * 163840)
    assert flops == pytest.approx(want)
    reached = 8 * (6 / 64)
    rest = total - 26 * 8 * 8_650_752 - 163840 * 2048
    assert nbytes == pytest.approx(2 * (rest + 2048 + 26 * reached * 8_650_752) + 31_104 * 101)
    # a chunk counts its real tokens and its pairs below the diagonal
    f, _ = fl.prefill_cost(d, 512, 256)
    assert f == pytest.approx(fl.forward_flops(d, 256, 256 * 512 + 256 * 257 // 2, 1))


def test_mfu_and_expert_fill_readers(monkeypatch):
    from chipbench import program_spans

    d = dims_of(_moonlight())
    red = {"module_s": {"jit_slot_prefill_step": 0.2, "jit_decode_tick": 0.8},
           "module_n": {"jit_slot_prefill_step": 4, "jit_decode_tick": 20},
           "busy_s": 1.0, "window_s": 2.0, "devices": 1, "collective_s": 0.0}
    steps = [("prefill", (0, 512))] * 4 + [("decode", (1000, 2000))] * 20
    rec = {"kind": "serve", "dims": d, "steps": steps}
    monkeypatch.setattr(program_spans, "load", lambda *a: None)
    fp, bp = fl.prefill_cost(d, 0, 512)
    fd, bd = fl.decode_cost(d, (1000, 2000))
    need = 4 * max(fp / 197e12, bp / 819e9) + 20 * max(fd / 197e12, bd / 819e9)
    assert reader("mfu.serve.mla_moe")(red, rec, PEAKS) == pytest.approx(100 * need / 1.0)
    assert reader("mfu.serve.mla_moe")(red, dict(rec, dims=object()), PEAKS) is None
    # with the engine's counts: 4 x 512 + 20 x 2 tokens expected to take
    # 6 x 8 / 64 rows each a layer; counted twice that, every step takes twice
    counted = 2 * (4 * 512 + 20 * 2) * 0.75 * 26
    spans = [[0.0, 0.0, "repro.engine.moe", {"moe_rows": counted / 2}]] * 2
    monkeypatch.setattr(program_spans, "load", lambda *a: {"spans": spans})
    fp, bp = fl.prefill_cost(d, 0, 512, routed=2 * 0.75 * 512)
    fd, bd = fl.decode_cost(d, (1000, 2000), routed=2 * 0.75 * 2)
    need = 4 * max(fp / 197e12, bp / 819e9) + 20 * max(fd / 197e12, bd / 819e9)
    assert reader("mfu.serve.mla_moe")(red, rec, PEAKS) == pytest.approx(100 * need / 1.0)
    # the chunk's 384 more rows a layer, 2 x 8,650,752 operations a row
    assert fp == pytest.approx(fl.prefill_cost(d, 0, 512)[0]
                               + 26 * 384 * 2 * fl.expert_params(d))
    moe = [[0.1 * i, 0.1 * i, "repro.engine.moe", {"moe_rows": 30, "moe_rows_computed": 320}]
           for i in range(3)]
    monkeypatch.setattr(program_spans, "load", lambda *a: {"spans": moe})
    assert reader("expert_fill.serve")(red, rec, PEAKS) == pytest.approx(100 * 90 / 960)
    monkeypatch.setattr(program_spans, "load", lambda *a: {"spans": []})
    assert reader("expert_fill.serve")(red, rec, PEAKS) is None


@pytest.mark.parametrize("key,value", [
    ("n_routed_experts", 16), ("moe_intermediate_size", 1024), ("num_experts_per_tok", 8),
    ("rms_norm_eps", 1e-6), ("q_lora_rank", 1536), ("kv_lora_rank", 256),
    ("routed_scaling_factor", 1.0), ("scoring_func", "softmax"), ("n_group", 8),
    ("topk_method", "greedy"), ("norm_topk_prob", False),
    ("published", {"n_routed_experts": 128}),
])
def test_config_refused_key_by_key(key, value):
    config = _moonlight()
    program_config(config)                      # the file as committed runs
    bad = copy.deepcopy(config)
    bad[key] = value
    with pytest.raises(BenchError):
        program_config(bad)


@pytest.fixture
def tiny_program(monkeypatch):
    monkeypatch.setattr(serve_mla_moe, "program_config", tiny_mla_moe.tiny_program_config)


def _serve(cell, seed, control=False):
    return serve_mla_moe.run(cell, seed, 2.0, None, jax.devices()[:1], CompileClock(),
                             time.perf_counter(), control=control)


def test_harness_passes_and_the_control_and_an_altered_token_fail(tiny_program, monkeypatch):
    from repro.serve import engine as engine_mod

    cell = tiny_mla_moe.serve_cell()
    res = _serve(cell, 8, control=True)
    assert res.correct, res.checks
    assert res.record["kind"] == "serve"
    read, gaps = res.record["readings"], res.record["gaps"]
    assert read["program"] == pytest.approx(float(gaps["served"].mean()))
    assert gaps["served"].size == sum(len(t) for _, t in res.record["sample"])
    for kind in ("control", "altered_token"):
        assert read[kind] > cell.limits["served_gap_mean"], (kind, read)
    # the reference's rows are one pass's: the served row alone reads the same
    d = dims_of(cell.config)
    p, t = res.record["sample"][0]
    np.testing.assert_array_equal(ref.served_gaps(d, res.record["params"], p, t)["served"],
                                  gaps["served"][:len(t)])

    real = engine_mod.ServeEngine._emit

    def altered(self, req, tok):
        if len(req.tokens) == 1:
            tok = (tok + 1) % self.model.cfg.vocab_size
        return real(self, req, tok)

    monkeypatch.setattr(engine_mod.ServeEngine, "_emit", altered)
    res = _serve(cell, 8)
    assert not res.correct and {c.name for c in res.checks if not c.ok} == {"served_gap_mean"}


def test_harness_fails_a_sample_short_of_its_tokens(tiny_program):
    res = _serve(tiny_mla_moe.serve_cell(check={"min_tokens": 10 ** 6}), 8)
    assert {c.name for c in res.checks if not c.ok} == {"sample_tokens_short"}
