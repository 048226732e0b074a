"""Runtime substrate: optimizers, checkpointing (atomic/async/resume),
data pipeline, collectives math, compression, telemetry, and the
end-to-end adaptive train loop with failure injection."""

import os
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import SimplifiedDelayModel, StrategyConfig
from repro.core.diagnostics import DiagnosticConfig
from repro.data import StagedBatcher, TokenStream
from repro.dist.collectives import example_weights, masked_weighted_ce
from repro.dist.compression import Int8Codec, ef_compress_tree
from repro.optim.optimizers import (
    adafactor,
    adamw,
    apply_updates,
    clip_by_global_norm,
    get_optimizer,
    momentum,
    sgd,
)
from repro.runtime.checkpoint import (
    CHECKPOINT_SCHEMA,
    CheckpointError,
    CheckpointManager,
)
from repro.runtime.telemetry import StragglerTracker


# ---------------------------------------------------------------------------
# Optimizers
# ---------------------------------------------------------------------------

def _quad_problem():
    w = {"a": jnp.array([3.0, -2.0]), "b": jnp.array([[1.5]])}

    def loss(p):
        return jnp.sum(p["a"] ** 2) + jnp.sum(p["b"] ** 2)

    return w, loss


@pytest.mark.parametrize("name", ["sgd", "momentum", "adamw", "adafactor"])
def test_optimizers_descend(name):
    params, loss = _quad_problem()
    opt = get_optimizer(name)
    state = opt.init(params)
    l0 = float(loss(params))
    for _ in range(60):
        grads = jax.grad(loss)(params)
        updates, state = opt.update(grads, state, params, jnp.float32(0.05))
        params = apply_updates(params, updates)
    assert float(loss(params)) < l0 * 0.2


def test_adafactor_factored_memory_shape():
    params = {"w": jnp.zeros((256, 512)), "b": jnp.zeros((7,))}
    opt = adafactor(min_dim_factored=128)
    state = opt.init(params)
    assert set(state.states["w"].keys()) == {"row", "col"}
    assert state.states["w"]["row"].shape == (256,)
    assert state.states["w"]["col"].shape == (512,)
    assert set(state.states["b"].keys()) == {"v"}


def test_clip_by_global_norm():
    tree = {"a": jnp.full((4,), 100.0)}
    clipped, norm = clip_by_global_norm(tree, 1.0)
    assert float(norm) == pytest.approx(200.0)
    assert float(jnp.linalg.norm(clipped["a"])) == pytest.approx(1.0, rel=1e-5)


# ---------------------------------------------------------------------------
# Fastest-k masked aggregation math
# ---------------------------------------------------------------------------

def test_example_weights_layout():
    mask = jnp.array([1.0, 0.0, 1.0, 0.0])
    w = example_weights(mask, batch=8)
    np.testing.assert_array_equal(
        np.asarray(w), [1, 1, 0, 0, 1, 1, 0, 0]
    )


def test_masked_ce_equals_subset_ce():
    """Masked CE over all workers == plain CE over the kept workers."""
    rng = jax.random.PRNGKey(0)
    B, S, V, n = 8, 4, 11, 4
    logits = jax.random.normal(rng, (B, S, V))
    labels = jax.random.randint(rng, (B, S), 0, V)
    mask = jnp.array([1.0, 0.0, 1.0, 1.0])
    loss_masked, _ = masked_weighted_ce(logits, labels, None, mask)
    keep = np.repeat(np.asarray(mask) > 0, B // n)
    loss_subset, _ = masked_weighted_ce(
        logits[keep], labels[keep], None, None
    )
    assert float(loss_masked) == pytest.approx(float(loss_subset), rel=1e-6)


def test_masked_gradient_unbiasedness():
    """E over random k-subsets of the masked gradient == full gradient."""
    rng = np.random.default_rng(0)
    B, S, V, n = 8, 4, 7, 8
    logits = jnp.asarray(rng.normal(size=(B, S, V)), jnp.float32)
    labels = jnp.asarray(rng.integers(0, V, size=(B, S)))

    def grad_for(mask):
        f = lambda lg: masked_weighted_ce(lg, labels, None, mask)[0]
        return np.asarray(jax.grad(f)(logits))

    full = grad_for(jnp.ones((n,)))
    acc = np.zeros_like(full)
    trials = 400
    k = 3
    for _ in range(trials):
        idx = rng.choice(n, size=k, replace=False)
        m = np.zeros(n, np.float32)
        m[idx] = 1
        acc += grad_for(jnp.asarray(m))
    np.testing.assert_allclose(acc / trials, full, atol=2e-2)


# ---------------------------------------------------------------------------
# Compression + error feedback
# ---------------------------------------------------------------------------

def test_int8_roundtrip_small_error():
    x = jnp.asarray(np.random.default_rng(0).normal(size=(256,)), jnp.float32)
    q, scale = Int8Codec.encode(x)
    err = np.abs(np.asarray(Int8Codec.decode(q, scale) - x)).max()
    assert err <= float(scale) * 0.5 + 1e-9


def test_error_feedback_converges():
    """SGD on a quadratic with int8-compressed grads + EF still converges."""
    w = jnp.array([5.0, -3.0, 2.0, -1.0])
    resid = {"w": jnp.zeros_like(w)}
    params = {"w": w}
    for _ in range(300):
        grads = {"w": 2 * params["w"]}
        dec, resid = ef_compress_tree(grads, resid)
        params = {"w": params["w"] - 0.05 * dec["w"]}
    assert float(jnp.abs(params["w"]).max()) < 1e-2


# ---------------------------------------------------------------------------
# Checkpointing
# ---------------------------------------------------------------------------

def test_checkpoint_save_restore_roundtrip(tmp_path):
    mgr = CheckpointManager(tmp_path, keep_last=2)
    state = {"w": jnp.arange(6.0).reshape(2, 3), "n": {"m": jnp.ones((4,))}}
    mgr.save(10, state, extras={"stage": {"k": 3, "beta": 0.6}})
    mgr.save(20, state)
    mgr.save(30, state)
    # retention: only last 2 kept
    steps = sorted(p.name for p in tmp_path.iterdir() if p.name.startswith("step"))
    assert steps == ["step_000000020", "step_000000030"]
    assert mgr.latest_step() == 30

    restored = mgr.restore_latest(state)
    assert restored is not None
    step, restored_state, extras = restored
    assert step == 30
    np.testing.assert_array_equal(
        np.asarray(restored_state["w"]), np.asarray(state["w"])
    )


def test_checkpoint_async_and_extras(tmp_path):
    mgr = CheckpointManager(tmp_path)
    state = {"w": jnp.ones((8, 8))}
    mgr.save_async(5, state, extras={"stage": {"k": 2, "beta": 1.0}})
    mgr.wait()
    step, restored, extras = mgr.restore_latest(state)
    assert step == 5 and extras["stage"]["k"] == 2


def test_checkpoint_atomicity_no_partial_dirs(tmp_path):
    mgr = CheckpointManager(tmp_path)
    mgr.save(1, {"w": jnp.zeros((2,))})
    leftovers = [p for p in tmp_path.iterdir() if p.name.startswith(".tmp")]
    assert not leftovers


def test_checkpoint_truncated_arrays_names_offending_path(tmp_path):
    mgr = CheckpointManager(tmp_path, keep_last=10)
    state = {"w": jnp.ones((4,))}
    mgr.save(1, state)
    bad = tmp_path / "step_000000001" / "arrays.npz"
    bad.write_bytes(bad.read_bytes()[: 20])        # truncate mid-archive
    with pytest.raises(CheckpointError) as e:
        mgr.restore(1, state)
    assert str(bad) in str(e.value)


def test_checkpoint_corrupt_meta_names_offending_path(tmp_path):
    mgr = CheckpointManager(tmp_path, keep_last=10)
    state = {"w": jnp.ones((4,))}
    mgr.save(2, state)
    bad = tmp_path / "step_000000002" / "meta.json"
    bad.write_text('{"step": 2, "time":')           # truncated JSON
    with pytest.raises(CheckpointError) as e:
        mgr.restore(2, state)
    assert str(bad) in str(e.value)


def test_checkpoint_unknown_schema_refused(tmp_path):
    import json as _json

    mgr = CheckpointManager(tmp_path, keep_last=10)
    state = {"w": jnp.ones((4,))}
    mgr.save(3, state)
    meta_path = tmp_path / "step_000000003" / "meta.json"
    meta = _json.loads(meta_path.read_text())
    meta["schema"] = CHECKPOINT_SCHEMA + 1
    meta_path.write_text(_json.dumps(meta))
    with pytest.raises(CheckpointError) as e:
        mgr.restore(3, state)
    msg = str(e.value)
    assert str(meta_path) in msg and str(CHECKPOINT_SCHEMA + 1) in msg


def test_checkpoint_missing_dir_and_corrupt_latest(tmp_path):
    mgr = CheckpointManager(tmp_path)
    state = {"w": jnp.ones((2,))}
    with pytest.raises(CheckpointError) as e:
        mgr.restore(77, state)
    assert "step_000000077" in str(e.value)
    mgr.save(5, state)
    # a LATEST pointing at an existing entry whose name is not a step
    # directory is corrupt (a dangling pointer, by contrast, just means
    # "no checkpoint" — pruning can legitimately leave one)
    (tmp_path / "not-a-step-dir").mkdir()
    (tmp_path / "LATEST").write_text("not-a-step-dir")
    with pytest.raises(CheckpointError) as e:
        mgr.latest_step()
    assert "LATEST" in str(e.value)


def test_checkpoint_pre_schema_checkpoints_still_load(tmp_path):
    """Checkpoints written before the schema field existed load as
    version 1 — hardening must not orphan old runs."""
    import json as _json

    mgr = CheckpointManager(tmp_path)
    state = {"w": jnp.arange(4.0)}
    mgr.save(8, state, extras={"stage": {"k": 2}})
    meta_path = tmp_path / "step_000000008" / "meta.json"
    meta = _json.loads(meta_path.read_text())
    del meta["schema"]
    meta_path.write_text(_json.dumps(meta))
    restored, extras = mgr.restore(8, state)
    np.testing.assert_array_equal(np.asarray(restored["w"]),
                                  np.asarray(state["w"]))
    assert extras["stage"]["k"] == 2


@pytest.mark.parametrize("dtype", ["bfloat16", "float16", "int8", "float32"])
def test_checkpoint_restores_each_leaf_dtype(tmp_path, dtype):
    """npz has no name for bfloat16 (it reads back as raw 2-byte voids):
    the meta records each leaf's dtype and restore brings it back."""
    mgr = CheckpointManager(tmp_path)
    state = {"w": (jnp.arange(12) - 6).reshape(3, 4).astype(dtype),
             "b": jnp.ones((2,), jnp.float32)}
    mgr.save(4, state)
    restored, _ = mgr.restore(4, jax.eval_shape(lambda: state))
    for k in state:
        assert restored[k].dtype == state[k].dtype
        np.testing.assert_array_equal(np.asarray(restored[k], np.float32),
                                      np.asarray(state[k], np.float32))


def test_compile_cache_dir_is_the_environments_or_the_checkouts(monkeypatch):
    from repro.runtime.compile_cache import (
        CHECKOUT_CACHE_DIR,
        enable_compile_cache,
    )

    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
        assert enable_compile_cache() == "/elsewhere/cache"
        assert jax.config.jax_compilation_cache_dir == before  # JAX's own
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        assert enable_compile_cache() == str(CHECKOUT_CACHE_DIR)
        assert jax.config.jax_compilation_cache_dir == str(CHECKOUT_CACHE_DIR)
        assert CHECKOUT_CACHE_DIR.parent == Path(__file__).resolve().parents[1]
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


# ---------------------------------------------------------------------------
# Data pipeline
# ---------------------------------------------------------------------------

def test_staged_batcher_beta_scaling():
    stream = TokenStream(vocab_size=97, seed=0)
    b = StagedBatcher(stream, n_workers=4, global_batch=16, seq_len=8)
    full = b.batch_for_stage(1.0)
    half = b.batch_for_stage(0.5)
    assert full["inputs"].shape == (16, 8)
    assert half["inputs"].shape == (8, 8)
    assert full["labels"].shape == full["inputs"].shape
    # labels are next-token shifted views of the same stream
    assert (full["inputs"][:, 1:] == full["labels"][:, :-1]).all()


def test_token_stream_learnable_structure():
    stream = TokenStream(vocab_size=97, seed=0, noise=0.0)
    arr = stream.sequences(4, 16)
    nxt = (31 * arr[:, :-1] + 17) % 97
    assert (nxt == arr[:, 1:]).mean() == 1.0


# ---------------------------------------------------------------------------
# Telemetry / straggler demotion
# ---------------------------------------------------------------------------

def test_straggler_tracker_flags_persistent_straggler():
    n = 8
    tr = StragglerTracker(n, warmup=4)
    rng = np.random.default_rng(0)
    alive = np.ones(n, bool)
    for _ in range(50):
        z = rng.exponential(1.0, n)
        z[3] *= 10.0  # worker 3 is 10x slower on average
        tr.observe(z, alive)
    assert tr.persistent_stragglers(4.0) == [3]


def test_straggler_tracker_late_joiner_seeds_from_own_data():
    """Regression: seeding must be per-worker, not on the tracker's first
    observation globally. A worker first observed late must start from
    ITS first sample, not crawl up from the zero init (which made late
    joiners look artificially fast and immune to demotion)."""
    n = 4
    tr = StragglerTracker(n, warmup=4)
    alive = np.ones(n, bool)
    late = np.array([False, False, False, True])
    for _ in range(20):
        tr.observe(np.array([1.0, 1.0, 1.0, np.inf]), alive & ~late)
    # worker 3 joins, persistently 8x slower
    for _ in range(10):
        tr.observe(np.array([1.0, 1.0, 1.0, 8.0]), alive)
    est = tr.mean_estimate()
    assert est[3] == pytest.approx(8.0, rel=0.05), \
        "late joiner's estimate must be seeded from its own first sample"
    assert tr.persistent_stragglers(4.0) == [3]


def test_straggler_tracker_censored_never_observed_worker():
    """Under fastest-k the straggler is NEVER observed — only censored at
    z_(k). The time-on-test estimate must still grow past any threshold,
    but only be flagged once the expected-wins fairness guard is met.
    (Default warmup: with k/n = 1/4, transient estimates of unlucky
    normal workers need ~16 rounds to settle.)"""
    n = 4
    tr = StragglerTracker(n, min_expected_wins=4.0)
    alive = np.ones(n, bool)
    rng = np.random.default_rng(1)
    flagged_at = None
    for t in range(40):
        z = rng.exponential(1.0, n)
        z[0] = np.inf  # the straggler never makes the fastest k
        observed = np.zeros(n, bool)
        observed[np.argmin(z)] = True  # k = 1
        level = float(z[observed][0])
        tr.observe(np.where(observed, z, np.nan), alive,
                   observed=observed, censor_level=level)
        flags = tr.persistent_stragglers(3.0)
        if flagged_at is None and flags:
            flagged_at = t
            assert flags == [0]
    assert flagged_at is not None, "censored straggler must be caught"
    # k/n = 1/4 per round: expected wins reach 4.0 only at round 16
    assert flagged_at >= 15, "fairness guard must delay the verdict"


def test_straggler_tracker_state_roundtrip():
    n = 3
    tr = StragglerTracker(n, warmup=2)
    rng = np.random.default_rng(2)
    alive = np.ones(n, bool)
    for _ in range(10):
        tr.observe(rng.exponential(1.0, n) * np.array([1, 1, 6.0]), alive)
    tr2 = StragglerTracker(n, warmup=2)
    tr2.load_state_dict(tr.state_dict())
    np.testing.assert_array_equal(tr2.mean_estimate(), tr.mean_estimate())
    assert tr2.persistent_stragglers(3.0) == tr.persistent_stragglers(3.0)
    with pytest.raises(ValueError):
        StragglerTracker(n + 1).load_state_dict(tr.state_dict())


def test_tracker_reset_worker_forgets_history():
    n = 4
    tr = StragglerTracker(n, warmup=2)
    alive = np.ones(n, bool)
    for _ in range(10):
        tr.observe(np.array([1.0, 1.0, 1.0, 9.0]), alive)
    assert tr.persistent_stragglers(4.0) == [3]
    tr.reset_worker(3)  # recovered + rejoined: stale slowness must not demote
    assert tr.persistent_stragglers(4.0) == []
    assert np.isnan(tr.mean_estimate()[3])
