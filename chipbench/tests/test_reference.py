"""The reference and the weights against the program at a small size:
the copied initializer gives the program's weights bit for bit, and the
float32 reference computes the program's float32 loss and logits."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import reference, weights
from chipbench.gen import token_stream
from chipbench.model import dims_of
from chipbench.tests import tiny


@pytest.mark.parametrize("bias", [False, True])
def test_program_rule_is_the_programs_init(bias):
    from repro.models import build_model

    config = dict(tiny.TINY_CONFIG, attention_bias=bias)
    model = build_model(tiny.tiny_program_config(config))
    want = model.init(jax.random.PRNGKey(weights.program_seed(2 ** 31 + 5)))
    got = weights.make_weights("program", dims_of(config), "bfloat16", 2 ** 31 + 5)
    assert jax.tree.structure(want) == jax.tree.structure(got)
    for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got)):
        assert a.dtype == b.dtype and bool(jnp.array_equal(a, b))


def _f32_program(config):
    from repro.models import build_model

    cfg = tiny.tiny_program_config(dict(config, program={"dtype": "float32"}))
    return build_model(cfg)


def test_reference_loss_is_the_programs():
    cell = tiny.train_cell()
    d = dims_of(cell.config)
    model = _f32_program(cell.config)
    params = weights.make_weights("serving", d, "float32", 3)
    b = token_stream.batches(cell.traffic, 3, 1)[0]
    inp, lab = b["inputs"][:4], b["labels"][:4]
    with jax.default_matmul_precision("highest"):
        want, _ = model.train_loss(params, {"inputs": jnp.asarray(inp), "labels": jnp.asarray(lab)})
    got = reference._nll_sum(d, None, params, inp, lab) / inp.size
    assert float(got) == pytest.approx(float(want), rel=1e-5)


def test_served_gaps_of_the_programs_greedy_tokens_are_nought():
    """Greedy tokens of the float32 program read a gap of 0 (to float32
    rounding); the reference's own argmax reads exactly 0."""
    from repro.serve import generate_offline

    cell = tiny.serve_cell()
    d = dims_of(cell.config)
    model = _f32_program(cell.config)
    params = weights.make_weights("serving", d, "float32", 4)
    prompt = np.random.default_rng(0).integers(0, d.vocab, size=40).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        toks = generate_offline(model, params, prompt, 12, 128)
    gaps = reference.served_gaps(d, params, prompt, toks)
    assert gaps.shape == (12,) and float(gaps.max()) < 1e-4
    wrong = list(toks)
    wrong[5] = (wrong[5] + 1) % d.vocab
    assert float(reference.served_gaps(d, params, prompt, wrong)[5]) > 1e-3


def test_fp8_control_departs_from_the_reference():
    cell = tiny.serve_cell()
    d = dims_of(cell.config)
    params = weights.make_weights("serving", d, "bfloat16", 4)
    h = reference.hidden(d, None, params, jnp.arange(32)[None] % d.vocab)
    z = reference.logits(d, None, params, h)
    z8 = reference.logits(d, "fp8", params, reference.hidden(d, "fp8", params,
                                                              jnp.arange(32)[None] % d.vocab))
    rel = float(jnp.max(jnp.abs(z8 - z)) / jnp.max(jnp.abs(z)))
    assert 1e-3 < rel < 0.5
