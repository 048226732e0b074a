"""End-to-end integration: the adaptive-(k, beta) train loop on a tiny LM.

Covers: learning progress, stage advancement (one compiled shape per
beta), fastest-k masking metrics, failure injection, checkpoint resume,
and gradient-accumulation equivalence.
"""

import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import DiagnosticConfig, SimplifiedDelayModel, StrategyConfig
from repro.data import StagedBatcher, TokenStream
from repro.models import build_model
from repro.optim.optimizers import get_optimizer
from repro.runtime.steps import make_train_step
from repro.runtime.train_loop import FaultEvent, TrainLoopConfig, train


def _tiny(**overrides):
    cfg = get_config("smollm-135m").reduced(
        n_layers=2, d_model=64, n_heads=2, n_kv_heads=2, head_dim=32,
        d_ff=128, vocab_size=256, max_seq_len=64, **overrides,
    )
    return cfg, build_model(cfg)


def _setup(n=4, global_batch=16, seq_len=32, **overrides):
    cfg, model = _tiny(**overrides)
    strategy = StrategyConfig(
        "adaptive_kbeta", n=n, s=global_batch // n, k_max=n // 2,
        beta_grid=(0.5, 1.0),
        diagnostic=DiagnosticConfig(kind="loss", rel_tol=0.05, min_iters=5,
                                    consecutive=2),
    )
    delay = SimplifiedDelayModel(lambda_y=1.0, x=0.05)
    batcher = StagedBatcher(TokenStream(cfg.vocab_size, seed=0), n_workers=n,
                            global_batch=global_batch, seq_len=seq_len)
    return cfg, model, strategy, delay, batcher


def test_loop_learns_and_advances_stages():
    cfg, model, strategy, delay, batcher = _setup()
    out = train(model, get_optimizer("adamw"), strategy, delay, batcher,
                TrainLoopConfig(total_steps=80, log_every=0))
    hist = out["history"]
    assert hist[-1]["loss"] < hist[0]["loss"] * 0.98
    stages = {(h["k"], h["beta"]) for h in hist}
    assert len(stages) >= 2, "controller must advance at least one stage"
    # one compiled program per distinct batch shape (per beta)
    assert 1 <= len(out["compiled_shapes"]) <= 2


def test_loop_failure_injection_reduces_n():
    cfg, model, strategy, delay, batcher = _setup()
    out = train(model, get_optimizer("adamw"), strategy, delay, batcher,
                TrainLoopConfig(total_steps=30, log_every=0,
                                fail_worker_at=10, fail_worker_id=2))
    assert out["controller"].cfg.n == 3
    # training continued and stayed finite after the failure
    assert np.isfinite([h["loss"] for h in out["history"]]).all()


def test_loop_checkpoint_resume_exact():
    cfg, model, strategy, delay, batcher = _setup()
    with tempfile.TemporaryDirectory() as d:
        out1 = train(model, get_optimizer("adamw"), strategy, delay, batcher,
                     TrainLoopConfig(total_steps=40, log_every=0,
                                     checkpoint_dir=d, checkpoint_every=20))
        out2 = train(model, get_optimizer("adamw"), strategy, delay, batcher,
                     TrainLoopConfig(total_steps=50, log_every=0,
                                     checkpoint_dir=d, checkpoint_every=20))
        assert out2["history"][0]["step"] == 40


def test_grad_accumulation_matches_direct():
    """accum_steps=2 must reproduce the single-batch gradient step."""
    cfg, model = _tiny()
    opt = get_optimizer("sgd")
    rng = jax.random.PRNGKey(0)
    params = model.init(rng, dtype_override="float32")
    opt_state = opt.init(params)
    n = 4
    B, S = 8, 16
    batch = {
        "inputs": jax.random.randint(rng, (B, S), 0, cfg.vocab_size),
        "labels": jax.random.randint(rng, (B, S), 0, cfg.vocab_size),
        "worker_mask": jnp.array([1.0, 0.0, 1.0, 1.0]),
        "lr": jnp.float32(0.1),
    }
    step1 = make_train_step(model, opt, clip_norm=None)
    step2 = make_train_step(model, opt, clip_norm=None, accum_steps=2)
    p1, _, m1 = step1(params, opt_state, batch)
    p2, _, m2 = step2(params, opt_state, batch)
    assert float(m1["loss"]) == pytest.approx(float(m2["loss"]), rel=1e-5)
    for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-5, rtol=1e-4)


def test_resume_replays_identical_history():
    """Exact resume: the resumed run's history must equal the
    uninterrupted run's tail field-for-field (loss, stage, sim-time,
    fleet) because controller state, tracker state, membership, and both
    RNG streams round-trip through the checkpoint."""
    cfg, model, strategy, delay, batcher = _setup()
    events = [FaultEvent(step=8, kind="slow", worker=1, factor=3.0),
              FaultEvent(step=15, kind="fail", worker=2),
              FaultEvent(step=32, kind="rejoin", worker=2)]
    with tempfile.TemporaryDirectory() as d:
        mk = lambda: TrainLoopConfig(total_steps=44, log_every=0,
                                     checkpoint_dir=d, checkpoint_every=20,
                                     events=events)
        out1 = train(model, get_optimizer("adamw"), strategy, delay, batcher,
                     mk())
        # Fresh everything: all live state must come from the checkpoint.
        cfg2, model2, strategy2, delay2, batcher2 = _setup()
        out2 = train(model2, get_optimizer("adamw"), strategy2, delay2,
                     batcher2, mk())
        tail = [h for h in out1["history"] if h["step"] >= 40]
        assert out2["history"][0]["step"] == 40
        assert len(out2["history"]) == len(tail)
        for a, b in zip(tail, out2["history"]):
            assert a == b, f"resume diverged at step {a['step']}"
        assert out2["controller"].cfg.n == out1["controller"].cfg.n
        np.testing.assert_array_equal(out2["alive"], out1["alive"])


def test_bf16_resume_replays_identical_history_and_params():
    """Published configs keep their parameters in bfloat16, which npz
    cannot name: the checkpoint must bring every leaf back bit-exact in
    its own dtype, or the resumed run drifts from the uninterrupted one."""
    _, model, strategy, delay, batcher = _setup(dtype="bfloat16")
    with tempfile.TemporaryDirectory() as d:
        mk = lambda: TrainLoopConfig(total_steps=30, log_every=0,
                                     checkpoint_dir=d, checkpoint_every=20)
        out1 = train(model, get_optimizer("adamw"), strategy, delay, batcher,
                     mk())
        _, model2, strategy2, delay2, batcher2 = _setup(dtype="bfloat16")
        out2 = train(model2, get_optimizer("adamw"), strategy2, delay2,
                     batcher2, mk())
    assert out2["history"][0]["step"] == 20
    assert out2["history"] == out1["history"][20:]
    p1, p2 = jax.tree.leaves(out1["params"]), jax.tree.leaves(out2["params"])
    assert {p.dtype for p in p2} == {jnp.dtype(jnp.bfloat16)}
    for a, b in zip(p1, p2):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))


def test_sharded_loop_places_state_on_every_device():
    """With a mesh, params and optimizer state are created sharded (FSDP
    over ``data``) and each worker-major batch is split over ``data``:
    no device holds the whole model, and the losses track the
    single-device run of the same seed."""
    from repro.launch.mesh import make_mesh

    mesh = make_mesh((4,), ("data",), devices=jax.devices()[:4])
    runs = []
    for m in (None, mesh):
        _, model, strategy, delay, batcher = _setup()
        runs.append(train(model, get_optimizer("adamw"), strategy, delay,
                          batcher, TrainLoopConfig(total_steps=12, log_every=0),
                          mesh=m))
    single, sharded = runs
    embed = sharded["params"]["embed"]
    assert len({s.device for s in embed.addressable_shards}) == 4
    assert all(s.data.size * 4 == embed.size for s in embed.addressable_shards)
    mu = sharded["opt_state"].m["embed"]
    assert all(s.data.size * 4 == mu.size for s in mu.addressable_shards)
    l1 = np.array([h["loss"] for h in single["history"]])
    l4 = np.array([h["loss"] for h in sharded["history"]])
    np.testing.assert_allclose(l4, l1, rtol=1e-4)
    assert [h["beta"] for h in single["history"]] == [
        h["beta"] for h in sharded["history"]]


def test_rejoin_restores_fleet_and_k_max():
    cfg, model, strategy, delay, batcher = _setup()
    out = train(model, get_optimizer("adamw"), strategy, delay, batcher,
                TrainLoopConfig(total_steps=30, log_every=0,
                                events=[FaultEvent(5, "fail", 1),
                                        FaultEvent(15, "rejoin", 1)]))
    ctrl = out["controller"]
    assert ctrl.cfg.n == strategy.n, "rejoin must restore n"
    assert ctrl.cfg.k_max == strategy.k_max, "rejoin must restore k_max cap"
    assert out["alive"].all()
    n_by_step = {h["step"]: h["n_workers"] for h in out["history"]}
    assert n_by_step[10] == strategy.n - 1
    assert n_by_step[20] == strategy.n


def test_loop_fits_delay_model_from_censored_telemetry_only():
    """oracle_to_controller=False: every (k, beta) decision prices off a
    model fitted purely from the k order statistics the loop waited for."""
    cfg, model, strategy, delay, batcher = _setup()
    out = train(model, get_optimizer("adamw"), strategy, delay, batcher,
                TrainLoopConfig(total_steps=80, log_every=0,
                                estimate_model=True,
                                oracle_to_controller=False))
    ctrl = out["controller"]
    assert ctrl.oracle_model is None
    assert sum(ctrl._rt_censored) > 0, "fastest-k telemetry must be censored"
    est = ctrl.current_model()
    assert est is not None
    # True lambda_y = 1.0; the censored fit must land in its vicinity
    # even though most workers' times were never observed.
    assert 0.5 < est.lambda_y < 2.0
    stages = {(h["k"], h["beta"]) for h in out["history"]}
    assert len(stages) >= 2, "fitted model must still drive stage advances"


def test_batcher_resizes_batch_for_current_fleet():
    cfg, model, strategy, delay, batcher = _setup(n=4, global_batch=16)
    full = batcher.batch_for_stage(1.0)["inputs"].shape[0]
    shrunk = batcher.batch_for_stage(1.0, n_workers=3)["inputs"].shape[0]
    assert full == 16
    assert shrunk == 12, "per-worker share stays fixed; batch tracks fleet"
    assert batcher.batch_shape(1.0, n_workers=3)[0] == 12
    with pytest.raises(ValueError):
        batcher.batch_for_stage(1.0, n_workers=0)


def test_straggler_demotion_in_loop():
    cfg, model, strategy, delay, batcher = _setup()

    class SlowWorker(SimplifiedDelayModel):
        def sample(self, rng, n, beta):
            z = super().sample(rng, n, beta)
            return np.concatenate([z[:1] * 12.0, z[1:]])

    slow = SlowWorker(lambda_y=1.0, x=0.05)
    out = train(model, get_optimizer("adamw"), strategy, slow, batcher,
                TrainLoopConfig(total_steps=40, log_every=0,
                                demote_after_ewma=6.0))
    assert out["controller"].cfg.n == 3, "persistent straggler demoted"
