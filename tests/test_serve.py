"""repro.serve correctness: continuous batching must be invisible.

The contract that makes the slot-pool machinery trustable is exact
token equivalence: a request served by the continuous-batching engine —
joining mid-flight, sharing decode ticks with strangers, surviving
chunked prefill and masked dead lanes — must emit the identical greedy
token stream as a lone offline run of the same model. Checked across an
attention family and a recurrent family (the two cache disciplines).

Plus: slot-pool allocate/free/reuse/defrag/reset invariants, scheduler
determinism, and the hedged router's order-statistics pricing
(brute-force ``expected_kth`` match, loser cancellation freeing slots,
EWMA straggler demotion).
"""

import gc
import weakref

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.delay_models import GeneralizedDelayModel, SimplifiedDelayModel
from repro.core.order_stats import expected_kth
from repro.models import build_model
from repro.models.layers import ParamSpec, is_paged_spec
from repro.serve import (
    BlockManager,
    HedgedRouter,
    ReplicaSet,
    Scheduler,
    ServeEngine,
    SlotPool,
    generate_offline,
    run_static,
)

RNG = jax.random.PRNGKey(0)
MAX_LEN = 64


def _model(arch):
    cfg = get_config(arch).reduced()
    model = build_model(cfg)
    return model, model.init(RNG)


def _workload(vocab, n=6, seed=0):
    rng = np.random.default_rng(seed)
    reqs = []
    for i in range(n):
        p = int(rng.integers(3, 20))
        m = int(rng.integers(1, 12))
        prompt = rng.integers(0, vocab, size=p).astype(np.int32)
        reqs.append((prompt, m, i * 0.004))
    return reqs


# ---------------------------------------------------------------------------
# Token equivalence: continuous batching == offline decode
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("arch", ["smollm-135m", "xlstm-125m"])
def test_continuous_batching_matches_offline(arch):
    """Staggered arrivals, mixed lengths, chunked prefill, 3 slots for 6
    requests — every request's greedy tokens must be identical to a
    per-request offline decode (attention + recurrent cache families)."""
    model, params = _model(arch)
    reqs = _workload(model.cfg.vocab_size)
    eng = ServeEngine(
        model, params, n_slots=3, max_len=MAX_LEN,
        scheduler=Scheduler(3, prefill_chunk=8, decode_per_prefill=2),
    )
    rids = [eng.submit(p, m, arrival=a) for p, m, a in reqs]
    results = eng.run()
    for rid, (p, m, _) in zip(rids, reqs):
        ref = generate_offline(model, params, p, m, MAX_LEN)
        assert results[rid].tokens == ref, f"{arch} rid={rid} diverged"
        assert results[rid].t_done is not None


def test_static_baseline_matches_offline():
    model, params = _model("smollm-135m")
    reqs = _workload(model.cfg.vocab_size, n=5, seed=3)
    results, stats = run_static(model, params, reqs, n_slots=2, max_len=MAX_LEN)
    for rid, (p, m, _) in zip(sorted(results), reqs):
        assert results[rid].tokens == generate_offline(model, params, p, m, MAX_LEN)
    assert stats.generated_tokens == sum(m for _, m, _ in reqs)


def test_slots_reused_across_requests():
    """More requests than slots forces mid-flight reuse of freed slots."""
    model, params = _model("smollm-135m")
    reqs = _workload(model.cfg.vocab_size, n=7, seed=5)
    eng = ServeEngine(model, params, n_slots=2, max_len=MAX_LEN)
    rids = [eng.submit(p, m, arrival=a) for p, m, a in reqs]
    results = eng.run()
    assert eng.pool.n_active == 0
    for rid, (p, m, _) in zip(rids, reqs):
        assert results[rid].tokens == generate_offline(model, params, p, m, MAX_LEN)


def test_engine_event_log_is_deterministic():
    model, params = _model("smollm-135m")
    reqs = _workload(model.cfg.vocab_size, n=6, seed=1)

    def go():
        eng = ServeEngine(model, params, n_slots=3, max_len=MAX_LEN)
        for p, m, a in reqs:
            eng.submit(p, m, arrival=a)
        eng.run()
        return eng.events

    assert go() == go()


def test_prefill_bucket_capped_at_max_len():
    """Regression: the pad bucket must never exceed the slot capacity past
    the chunk start — an oversized dynamic_update_slice either crashes or
    gets its start clamped by XLA, silently overwriting valid cache rows."""
    model, params = _model("smollm-135m")
    rng = np.random.default_rng(11)
    # (a) bucket(24) = 32 > max_len = 29: would crash unclamped.
    prompt = rng.integers(0, model.cfg.vocab_size, size=24).astype(np.int32)
    eng = ServeEngine(model, params, n_slots=1, max_len=29)
    rid = eng.submit(prompt, 4)
    assert eng.run()[rid].tokens == generate_offline(model, params, prompt, 4, 29)
    # (b) chunked: last chunk start=30, bucket 16 would clamp to start 24
    # and corrupt rows 24-29 — tokens must still match offline exactly.
    prompt = rng.integers(0, model.cfg.vocab_size, size=34).astype(np.int32)
    eng = ServeEngine(
        model, params, n_slots=1, max_len=40,
        scheduler=Scheduler(1, prefill_chunk=5),
    )
    rid = eng.submit(prompt, 5)
    assert eng.run()[rid].tokens == generate_offline(model, params, prompt, 5, 40)


def test_engine_defrag_mid_flight_keeps_equivalence():
    """Defragging while requests are generating must remap the engine's
    per-slot decode state along with the pool rows."""
    model, params = _model("smollm-135m")
    reqs = _workload(model.cfg.vocab_size, n=5, seed=9)
    eng = ServeEngine(model, params, n_slots=3, max_len=MAX_LEN)
    rids = [eng.submit(p, m, arrival=a) for p, m, a in reqs]
    defragged = 0
    while eng.step() != "done":
        # Defrag whenever the pool fragments (a freed slot below a live one).
        act = eng.pool.active
        if act.any() and not act[: eng.pool.n_active].all():
            assert eng.defrag()
            defragged += 1
    assert defragged > 0, "workload never fragmented the pool; weak test"
    results = dict(eng._requests)
    for rid, (p, m, _) in zip(rids, reqs):
        ref = generate_offline(model, params, p, m, MAX_LEN)
        assert results[rid].tokens == ref, f"rid={rid} diverged after defrag"


# ---------------------------------------------------------------------------
# Slot pool invariants
# ---------------------------------------------------------------------------

def test_slot_pool_allocate_free_reuse():
    model, _ = _model("smollm-135m")
    pool = SlotPool(model, n_slots=3, max_len=8)
    slots = [pool.allocate(owner=i) for i in range(3)]
    assert slots == [0, 1, 2] and pool.n_free == 0
    assert pool.allocate() is None          # full
    pool.free(1)
    assert pool.allocate(owner=9) == 1      # lowest free slot reused
    with pytest.raises(ValueError):
        pool.free(1)
        pool.free(1)                        # double free rejected


def test_slot_pool_defrag_compacts_and_preserves():
    model, _ = _model("smollm-135m")
    pool = SlotPool(model, n_slots=4, max_len=8)
    for i in range(4):
        pool.allocate(owner=i)
    # Stamp recognizable content via per-slot writes.
    for s in range(4):
        one = jax.tree.map(
            lambda spec: np.full([1 if a == "act_batch" else d
                                  for a, d in zip(spec.axes, spec.shape)],
                                 float(s + 1), np.float32),
            pool.specs, is_leaf=lambda x: isinstance(x, ParamSpec),
        )
        pool.write_slot(s, one, position=s + 1)
    pool.free(0)
    pool.free(2)
    moves = pool.defrag()
    # Active slots 1,3 compact to 0,1 with contents and positions intact.
    assert moves == {1: 0, 3: 1}
    assert pool.active.tolist() == [True, True, False, False]
    assert pool.owner[:2] == [1, 3]
    assert pool.positions[:2].tolist() == [2, 4]
    leaf = jax.tree.leaves(pool.caches)[0]
    ax = jax.tree.leaves(
        pool.specs, is_leaf=lambda x: isinstance(x, ParamSpec)
    )[0].axes.index("act_batch")
    got = np.moveaxis(np.asarray(leaf, np.float32), ax, 0).reshape(4, -1)[:, 0]
    assert got[:2].tolist() == [2.0, 4.0]


def test_slot_pool_reset_restores_spec_init():
    """Reset must restore spec-defined fills — notably ONES for the sLSTM
    normalizer state, not a blanket zero. (The 2-layer reduced xlstm has
    no sLSTM block, so force one in — the pool never needs params.)"""
    import dataclasses

    cfg = get_config("xlstm-125m").reduced()
    cfg = dataclasses.replace(
        cfg, xlstm=dataclasses.replace(cfg.xlstm, slstm_every=2)
    )
    model = build_model(cfg)
    pool = SlotPool(model, n_slots=2, max_len=8)
    # Scribble over both slots.
    junk = jax.tree.map(
        lambda spec: np.full(spec.shape, 7.0, np.float32),
        pool.specs, is_leaf=lambda x: isinstance(x, ParamSpec),
    )
    pool.caches = jax.tree.map(lambda c, j: j.astype(np.asarray(c).dtype),
                               pool.caches, junk)
    pool.reset_slot(0)
    specs = jax.tree.leaves(pool.specs, is_leaf=lambda x: isinstance(x, ParamSpec))
    leaves = jax.tree.leaves(pool.caches)
    assert any(s.init == "ones" for s in specs), "xlstm must carry a ones-init state"
    for spec, leaf in zip(specs, leaves):
        ax = spec.axes.index("act_batch")
        arr = np.moveaxis(np.asarray(leaf, np.float32), ax, 0)
        want = 1.0 if spec.init == "ones" else 0.0
        assert np.all(arr[0] == want), f"slot 0 of {spec} not reset to {want}"
        assert np.all(arr[1] == 7.0), "reset must not touch other slots"


# ---------------------------------------------------------------------------
# Paged KV: block-table engine must be invisible too
# ---------------------------------------------------------------------------

@pytest.mark.parametrize(
    "arch", ["smollm-135m", "deepseek-v3", "xlstm-125m", "zamba2"]
)
def test_paged_engine_matches_offline(arch):
    """The byte-identity contract under paging, for all four cache
    disciplines (GQA KV, MLA latent, pure recurrent, hybrid): chunked
    prefill, slot reuse, AND arena pressure (10 blocks < the 18 a full
    pool would reserve, so admissions queue on block budget) must leave
    every request's greedy tokens identical to contiguous offline
    decode."""
    model, params = _model(arch)
    reqs = _workload(model.cfg.vocab_size, n=5)
    eng = ServeEngine(
        model, params, n_slots=3, max_len=48,
        scheduler=Scheduler(3, prefill_chunk=8, decode_per_prefill=2),
        block_size=8, arena_blocks=10,
    )
    rids = [eng.submit(p, min(m, 24), arrival=a) for p, m, a in reqs]
    results = eng.run()
    for rid, (p, m, _) in zip(rids, reqs):
        ref = generate_offline(model, params, p, min(m, 24), 48)
        assert results[rid].tokens == ref, f"{arch} rid={rid} diverged (paged)"
    if eng.pool.manager is not None:
        eng.pool.manager.check()
        assert eng.pool.manager.n_free_blocks == eng.pool.manager.num_blocks


def test_paged_engine_defrag_mid_flight():
    """Defrag under paging permutes host block tables only (device
    gather happens just for contiguous leaves — none here) and must keep
    token equivalence."""
    model, params = _model("smollm-135m")
    reqs = _workload(model.cfg.vocab_size, n=5, seed=9)
    eng = ServeEngine(model, params, n_slots=3, max_len=MAX_LEN, block_size=16)
    rids = [eng.submit(p, m, arrival=a) for p, m, a in reqs]
    defragged = 0
    while eng.step() != "done":
        act = eng.pool.active
        if act.any() and not act[: eng.pool.n_active].all():
            if eng.defrag():
                defragged += 1
            eng.pool.manager.check()
    assert defragged > 0, "workload never fragmented the pool; weak test"
    for rid, (p, m, _) in zip(rids, reqs):
        assert eng._requests[rid].tokens == generate_offline(
            model, params, p, m, MAX_LEN
        ), f"rid={rid} diverged after paged defrag"


def test_paged_pool_defrag_is_device_noop_for_attention():
    """Pure-attention pools have only paged leaves: defrag must not
    touch (or copy) the arenas at all — block tables permute host-side."""
    model, _ = _model("smollm-135m")
    pool = SlotPool(model, n_slots=4, max_len=32, block_size=16)
    assert all(is_paged_spec(s) for s in pool._spec_leaves)
    for i in range(4):
        assert pool.allocate(owner=i, n_tokens=20) is not None
        pool.ensure_rows(i, 20)   # physically place the slot's 2 blocks
    tables_before = pool.manager.tables.copy()
    leaves_before = jax.tree.leaves(pool.caches)
    pool.free(0)
    pool.free(2)
    moves = pool.defrag()
    assert moves == {1: 0, 3: 1}
    # Device arenas are the very same buffers (no gather ran).
    for a, b in zip(jax.tree.leaves(pool.caches), leaves_before):
        assert a is b
    # Block tables moved with their slots.
    assert (pool.manager.tables[0] == tables_before[1]).all()
    assert (pool.manager.tables[1] == tables_before[3]).all()
    pool.manager.check()


def test_paged_pool_commit_append_free_lifecycle():
    model, _ = _model("smollm-135m")
    pool = SlotPool(model, n_slots=2, max_len=32, block_size=8, arena_blocks=6)
    mgr = pool.manager
    s0 = pool.allocate(owner=0, n_tokens=17)     # commits 3 blocks, owns 0
    assert mgr.n_committed_blocks == 3 and mgr.n_used_blocks == 0
    pool.ensure_rows(s0, 9)                      # rows -> physical blocks
    assert mgr.n_used_blocks == 2
    pool.ensure_rows(s0, 9)                      # idempotent
    assert mgr.n_used_blocks == 2
    # Admission is bounded by COMMITTED budgets, not physical blocks.
    assert pool.can_admit(24) and not pool.can_admit(25)
    assert pool.allocate(owner=1, n_tokens=25) is None
    # Growing past the committed budget is a programming error.
    with pytest.raises(ValueError, match="budget"):
        pool.ensure_rows(s0, 25)
    pool.free(s0)
    assert mgr.n_free_blocks == 6 and mgr.n_committed_blocks == 0
    assert pool.can_admit(32)                    # full slot now fits
    assert mgr.used_high_water == 2              # live-token high-water
    mgr.check()


def test_paged_decode_tick_leaves_mid_prefill_lane_untouched():
    """A decode tick writes only for the lanes that are decoding: a lane
    between two prefill chunks reads a NULL table, so the tick's write
    for it falls into the sink and its own blocks keep exactly the rows
    its prefill wrote. The streams stay those of offline decode."""
    model, params = _model("smollm-135m")
    eng = ServeEngine(
        model, params, n_slots=2, max_len=MAX_LEN,
        scheduler=Scheduler(2, prefill_chunk=8, decode_per_prefill=1),
        block_size=16,
    )
    rng = np.random.default_rng(3)
    vocab = model.cfg.vocab_size
    reqs = [(rng.integers(0, vocab, size=5).astype(np.int32), 8),
            (rng.integers(0, vocab, size=20).astype(np.int32), 4)]
    rids = [eng.submit(p, m) for p, m in reqs]

    def prefilling_blocks():
        pool = eng.pool
        return {
            slot: [np.asarray(leaf[:, blocks]) for leaf in jax.tree.leaves(pool.caches)]
            for slot in range(pool.n_slots)
            if pool.owner[slot] is not None and not eng._decoding[slot]
            for blocks in [pool.manager.tables[slot][pool.manager.tables[slot] != 0]]
        }

    ticks = 0
    while True:
        before = prefilling_blocks()
        action = eng.step()
        if action == "done":
            break
        if action == "decode" and before:
            ticks += 1
            for slot, leaves in before.items():
                for old, new in zip(leaves, prefilling_blocks()[slot]):
                    np.testing.assert_array_equal(old, new)
    assert ticks > 0, "no tick ran beside a lane mid-prefill; weak test"
    for rid, (p, m) in zip(rids, reqs):
        assert eng._requests[rid].tokens == generate_offline(model, params, p, m, MAX_LEN)


def test_paged_engine_rejects_oversized_request():
    model, params = _model("smollm-135m")
    eng = ServeEngine(model, params, n_slots=2, max_len=48,
                      block_size=8, arena_blocks=4)
    with pytest.raises(ValueError, match="arena"):
        eng.submit(np.arange(30, dtype=np.int32), 10)   # 5 blocks > 4


# ---------------------------------------------------------------------------
# BlockManager invariants
# ---------------------------------------------------------------------------

def test_block_manager_invariants():
    mgr = BlockManager(n_slots=3, n_rows=64, block_size=16, num_blocks=8)
    assert mgr.table_width == 4
    mgr.commit(0, 33)                  # budget 3 blocks
    mgr.commit(1, 64)                  # budget 4 blocks
    mgr.check()
    assert mgr.n_committed_blocks == 7 and mgr.n_used_blocks == 0
    mgr.append(0, 17)                  # 2 physical blocks
    mgr.append(1, 64)                  # 4 physical blocks
    assert mgr.n_used_blocks == 6 and mgr.used_high_water == 6
    mgr.append(0, 30)                  # still 2 blocks: no growth
    assert mgr.n_used_blocks == 6
    mgr.append(0, 33)                  # grows to 3 (its full budget)
    assert mgr.n_used_blocks == 7
    mgr.check()
    # Commitment and capacity bounds.
    assert not mgr.can_commit(17)      # 2 more blocks > 8 - 7 committed
    assert mgr.can_commit(16)
    with pytest.raises(ValueError, match="over-committed"):
        mgr.commit(2, 33)
    with pytest.raises(ValueError, match="table width"):
        mgr.commit(2, 65)              # > slot capacity regardless of free
    with pytest.raises(ValueError, match="budget"):
        mgr.append(0, 49)              # past its own commitment
    # Free returns blocks AND budget instantly; tables go back to NULL.
    mgr.free(1)
    assert mgr.n_free_blocks == 5 and mgr.n_committed_blocks == 3
    assert (mgr.tables[1] == 0).all()
    mgr.check()
    mgr.free(0)
    assert mgr.n_free_blocks == 8
    assert mgr.used_high_water == 7    # high-water survives frees
    mgr.check()


def test_block_manager_never_hands_out_a_block_twice():
    mgr = BlockManager(n_slots=4, n_rows=32, block_size=8, num_blocks=12)
    rng = np.random.default_rng(0)
    budget = [0] * 4
    for _ in range(300):
        slot = int(rng.integers(4))
        p = rng.random()
        if budget[slot] and p < 0.3:
            mgr.free(slot)
            budget[slot] = 0
        elif budget[slot]:
            mgr.append(slot, int(rng.integers(1, budget[slot] + 1)))
        else:
            want = int(rng.integers(1, 33))
            if mgr.can_commit(want):
                mgr.commit(slot, want)
                budget[slot] = want
        mgr.check()   # asserts disjoint ownership + free-list integrity


def test_block_size_must_divide_rows():
    model, _ = _model("smollm-135m")
    with pytest.raises(ValueError, match="divide"):
        SlotPool(model, n_slots=2, max_len=32, block_size=24)


# ---------------------------------------------------------------------------
# Model lifetime: pool/engine jit caches must not pin dropped models
# ---------------------------------------------------------------------------

def test_dropped_model_pool_ops_collectable():
    """Regression: ``_pool_ops``/``_engine_steps`` used to live in a
    module-level lru_cache keyed on the model, pinning every model ever
    served (and its jit traces) for the process lifetime. The memo now
    lives on the model instance, so dropping the model frees it."""
    cfg = get_config("smollm-135m").reduced()
    model = build_model(cfg)
    pool = SlotPool(model, n_slots=2, max_len=16)
    assert any(k.startswith("_memo_") for k in model.__dict__), (
        "pool ops memo should live on the model instance"
    )
    ref = weakref.ref(model)
    del pool, model
    gc.collect()
    assert ref() is None, "dropped model is still pinned by the ops cache"


# ---------------------------------------------------------------------------
# Hedged router
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("delay_model", [
    SimplifiedDelayModel(lambda_y=2.0, x=0.05),
    GeneralizedDelayModel(lambda_x=4.0, lambda_y=2.0, x=0.02),
])
@pytest.mark.parametrize("quorum,c", [(1, 0.08), (2, 0.05)])
def test_hedge_choice_matches_bruteforce(delay_model, quorum, c):
    n_rep = 8
    router = HedgedRouter(delay_model, n_rep, quorum=quorum, cost_per_replica=c)
    plan = router.choose_hedge()
    brute = min(
        range(quorum, n_rep + 1),
        key=lambda n: expected_kth(delay_model, n, min(quorum, n), 1.0) + c * n,
    )
    assert plan.n_h == brute
    assert plan.k == min(quorum, plan.n_h)
    assert len(plan.replicas) == plan.n_h
    assert plan.expected_cost == pytest.approx(
        expected_kth(delay_model, plan.n_h, plan.k, 1.0) + c * plan.n_h
    )


def test_hedge_cancellation_frees_slots():
    dm = SimplifiedDelayModel(lambda_y=2.0, x=0.05)
    router = HedgedRouter(dm, 6, quorum=1, cost_per_replica=0.08)
    rs = ReplicaSet(dm, [1.0] * 6, seed=2)
    out = router.dispatch(rs, auto_complete=False)
    assert out.plan.n_h > 1, "this pricing must actually hedge"
    assert router.inflight.sum() == out.plan.n_h
    # A concurrent hedge must avoid the busy replicas.
    out2 = router.dispatch(rs, auto_complete=False)
    assert set(out2.plan.replicas).isdisjoint(out.plan.replicas)
    # Completion releases the winner AND every cancelled loser.
    assert len(out.completed) == out.plan.k
    assert len(out.cancelled) == out.plan.n_h - out.plan.k
    router.complete(out)
    router.complete(out2)
    assert router.inflight.sum() == 0
    assert sorted(router.available()) == list(range(6))


def test_router_demotes_persistent_straggler():
    dm = SimplifiedDelayModel(lambda_y=2.0, x=0.05)
    router = HedgedRouter(dm, 5, quorum=1, cost_per_replica=0.05)
    rs = ReplicaSet(dm, [1.0, 1.0, 1.0, 1.0, 8.0], seed=3)
    for _ in range(300):
        router.dispatch(rs)
    plan = router.choose_hedge()
    assert 4 not in plan.replicas, "EWMA-slow replica must stop being chosen"


def test_router_respects_quorum_capacity():
    dm = SimplifiedDelayModel(lambda_y=2.0, x=0.05)
    router = HedgedRouter(dm, 3, quorum=2, cost_per_replica=0.0, n_max=3)
    rs = ReplicaSet(dm, [1.0] * 3, seed=4)
    out = router.dispatch(rs, auto_complete=False)
    assert out is not None
    # Fewer free replicas than the quorum -> no feasible hedge.
    assert router.dispatch(rs, auto_complete=False) is None
    router.complete(out)
    assert router.dispatch(rs) is not None
