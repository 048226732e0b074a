"""The shape-based counters against the program's own parameter count
and against counts done by hand."""

import json

import pytest

from chipbench import flops
from chipbench.common import BENCH_DIR
from chipbench.model import dims_of

CONFIGS = ["smollm-135m", "qwen2.5-3b"]


def _dims(name):
    return dims_of(json.loads((BENCH_DIR / "configs" / f"{name}.json").read_text()))


@pytest.mark.parametrize("name", CONFIGS)
def test_param_count_matches_the_program(name):
    from repro.configs import get_config

    assert flops.param_count(_dims(name)) == get_config(name).param_count()


def test_published_sizes():
    assert flops.param_count(_dims("qwen2.5-3b")) == 3_085_938_688
    assert flops.param_bytes(_dims("qwen2.5-3b")) == 6_171_877_376
    # 36 layers x K and V x 2 heads x 128 x 2 bytes
    assert flops.kv_bytes_per_token(_dims("qwen2.5-3b")) == 36_864


def test_train_flops_per_applied_token():
    d = _dims("smollm-135m")
    s = 2048
    per_token = flops.train_step_flops(d, 1, s) / s
    dense = 6 * (d.n_layers * flops.layer_matmul_params(d) + d.vocab * d.d_model)
    # causal attention: 3 x 4 x d x (S + 1) / 2 per token per layer
    attn = 3 * 4 * d.n_heads * d.head_dim * (s + 1) / 2 * d.n_layers
    assert per_token == pytest.approx(dense + attn, rel=1e-12)
    assert per_token == pytest.approx(1.02e9, rel=0.01)    # 6N plus causal attention
    assert flops.train_step_flops(d, 16, 1024) == 16 * flops.train_step_flops(d, 1, 1024)


def test_prefill_and_decode_cost():
    d = _dims("qwen2.5-3b")
    kv = flops.kv_bytes_per_token(d)
    # one decode lane at context c is a one-token prefill at start c - 1
    f1, b1 = flops.decode_cost(d, [100])
    f2, b2 = flops.prefill_cost(d, 99, 1)
    assert (f1, b1) == (f2, b2)
    f, b = flops.decode_cost(d, [10, 20, 30])
    assert b == flops.param_bytes(d) + kv * 60 + kv * 3
    assert f == 3 * (f1 - 4 * d.n_heads * d.head_dim * 100 * d.n_layers) + \
        4 * d.n_heads * d.head_dim * 60 * d.n_layers
    # a chunk of n after s prior rows attends n*s + n(n+1)/2 pairs
    f, b = flops.prefill_cost(d, 512, 512)
    head = 2 * d.d_model * d.vocab
    dense = 2 * 512 * d.n_layers * flops.layer_matmul_params(d)
    pairs = 512 * 512 + 512 * 513 // 2
    assert f == dense + head + 4 * d.n_heads * d.head_dim * pairs * d.n_layers
    assert b == flops.param_bytes(d) + kv * 1024 + kv * 512
