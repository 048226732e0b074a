"""What decides ``correct``, at a size a test run holds: sound runs pass,
the control (the reference in fp8) fails a number, and so does each
fault planted in the program underneath a run that skips only the
harness's look for a chip.

The tiny cells' limits sit between what these seeds read for the
program and for the control: program at most 4.6e-5 (first loss),
3.0e-3 (gradient), 6.6e-4 (change); control at least 9.1e-4, 1.1e-2,
4.6e-3. The change after the first step reads at most 5.7e-4 for the
program and less for the control at this size (fp8 fails the gradient
instead); its limit catches the frozen state. Served gaps over every finished request (125 served tokens),
seeds 7-10: at most 0.0036 for the program, at least 0.113 for the
control read at the same positions; over a sample of some 25 tokens the
control read as little as 0.016 (seed 8), which is why the chat cell
reads thousands."""

import time

import jax
import numpy as np
import pytest

from chipbench.common import CompileClock
from chipbench.harness import serve, train
from chipbench.tests import tiny

SEEDS = [5, 6]


@pytest.fixture(autouse=True)
def tiny_program(monkeypatch):
    monkeypatch.setattr(train, "program_config", tiny.tiny_program_config)
    monkeypatch.setattr(serve, "program_config", tiny.tiny_program_config)


def _train(cell, seed):
    return train.run(cell, seed, 0.0, None, jax.devices()[:1], CompileClock(), time.perf_counter())


def _failed(res):
    return {c.name for c in res.checks if not c.ok}


@pytest.mark.parametrize("traffic,seed", [("train_late", SEEDS[0]), ("train_early", SEEDS[1])])
def test_program_passes_and_control_fails(traffic, seed):
    cell = tiny.train_cell(traffic)
    res = _train(cell, seed)
    assert res.correct, res.checks
    ref = res.record["readings"]["reference"]
    ctl = train.compare(train.reference_readings(cell, seed, jax.devices()[:1], quant="fp8"), ref)
    assert any(v > cell.limits[k] for k, v in ctl.items()), ctl


def test_frozen_state_fails(monkeypatch):
    from repro.runtime import train_loop

    real = train_loop.make_train_step

    def frozen(*a, **k):
        step = real(*a, **k)

        def unchanged(params, opt_state, batch):
            _, _, metrics = step(params, opt_state, batch)
            return params, opt_state, metrics
        return unchanged

    monkeypatch.setattr(train_loop, "make_train_step", frozen)
    res = _train(tiny.train_cell(), SEEDS[0])
    assert not res.correct and {"change1_gap", "change_gap"} <= _failed(res)


def test_half_batch_fails(monkeypatch):
    from repro.runtime import steps

    real = steps.masked_weighted_ce

    def half(logits, labels, mask=None, worker_mask=None):
        keep = (np.arange(labels.shape[0]) < labels.shape[0] // 2).astype(np.float32)
        mask = np.broadcast_to(keep[:, None], labels.shape)
        return real(logits, labels, mask, worker_mask)

    monkeypatch.setattr(steps, "masked_weighted_ce", half)
    res = _train(tiny.train_cell(), SEEDS[0])
    assert not res.correct and "first_loss_gap" in _failed(res)


def _serve(cell, seed):
    return serve.run(cell, seed, 3.0, None, jax.devices()[:1], CompileClock(), time.perf_counter())


def test_serving_control_fails():
    """The control read with the program's own rule: at the positions
    that produced the sampled requests' served tokens, the token fp8
    ranks first lies further below the reference's best than the limit."""
    from chipbench import reference
    from chipbench.model import dims_of

    cell = tiny.serve_cell()
    res = _serve(cell, 8)
    assert res.correct, res.checks
    d = dims_of(cell.config)
    ctl = max(float(reference.served_gaps(d, res.record["params"], p, t, control=True)[1].max())
              for p, t in res.record["sample"])
    assert ctl > cell.limits["served_gap"], ctl


def test_serving_passes_and_an_altered_token_fails(monkeypatch):
    from repro.serve import engine as engine_mod

    cell = tiny.serve_cell()
    res = _serve(cell, 7)
    assert res.correct, res.checks

    real = engine_mod.ServeEngine._emit

    def altered(self, req, tok):
        if len(req.tokens) == 1:
            tok = (tok + 1) % self.model.cfg.vocab_size
        return real(self, req, tok)

    monkeypatch.setattr(engine_mod.ServeEngine, "_emit", altered)
    res = _serve(cell, 7)
    assert not res.correct and _failed(res) == {"served_gap"}
