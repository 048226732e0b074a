"""Moonlight-16B-A3B (MLA without a query LoRA, sigmoid-routed experts, a
chip's share of them) against the plain float32 reference
(``chipbench/reference_mla_moe.py``), at ``ModelConfig.reduced`` widths
on the CPU: 3 layers (one dense, two MoE), 8 experts of which 2 are held,
top-2, float32 weights.

The reference imports nothing of the program; the tests hand it the
program's weights and compare:

* (a) chunked paged prefill then paged (absorbed) decode through
  ``ServeEngine``, in logits at every served position;
* (b) the share: for one MoE layer, the parts every share computes
  (offsets 0, 2, 4, 6), with the shared experts counted once, add up to
  the uncut reference layer;
* (c) the choice of experts uses the selection bias, their weights do not;
* (d) the direct query projection when there is no query LoRA;
* (e) the norm epsilon is the config's.
"""

import dataclasses
import functools
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import build_model, count_params_analytic
from repro.models import attention as attn
from repro.models import moe as moe_mod
from repro.serve import Scheduler, ServeEngine

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from chipbench import reference_mla_moe as ref  # noqa: E402
from chipbench.model_mla_moe import MLAMoEDims  # noqa: E402

#: float32 on the CPU, both sides: what is left is the order of the sums
#: (chunked online softmax, absorbed decode, dispatch buffers) and the
#: reference's HIGHEST matmuls, about 3e-6 on logits of size ~4;
#: one held expert zeroed moves a logit by about 1
LOGIT_ATOL = 5e-5


def _cfg(**kw):
    return get_config("moonlight-16b-a3b").reduced(n_layers=3, **kw)


def _dims(cfg) -> MLAMoEDims:
    m, e = cfg.mla, cfg.moe
    return MLAMoEDims(
        n_layers=cfg.n_layers, d_model=cfg.d_model, n_heads=cfg.n_heads,
        q_lora_rank=m.q_lora_rank, kv_lora_rank=m.kv_lora_rank,
        qk_nope=m.qk_nope_head_dim, qk_rope=m.qk_rope_head_dim, v_head=m.v_head_dim,
        d_ff=cfg.d_ff, first_k_dense=e.first_k_dense, n_experts=e.n_experts,
        held=e.held, held_offset=e.held_offset, top_k=e.top_k, d_expert=e.d_expert,
        n_shared=e.n_shared_experts, scoring=e.scoring,
        routed_scaling=e.routed_scaling, vocab=cfg.vocab_size, rope_theta=cfg.rope_theta,
        rms_eps=cfg.rms_norm_eps, tied=cfg.tie_embeddings, param_bytes=4)


def _params(model, seed=0, bias_std=0.05):
    """The program's initial weights, with a selection bias large enough
    to move choices."""
    p = model.init(jax.random.PRNGKey(seed))
    seg = p["stack"][-1]["ffn"]
    seg["router_bias"] = bias_std * jax.random.normal(
        jax.random.PRNGKey(seed + 1), seg["router_bias"].shape, jnp.float32)
    return p


def _ref_logits(d, params, seq):
    with jax.default_matmul_precision("highest"):
        return np.asarray(ref.logits(d, None, params, ref.hidden(d, None, params, jnp.asarray(seq))))


def _serve_capturing_logits(model, params, prompts, max_new):
    """Serve ``prompts`` through a paged engine (chunks of 16, blocks of
    8) and keep, per request, the logits that produced each served token."""
    eng = ServeEngine(model, params, n_slots=3, max_len=96, block_size=8,
                      scheduler=Scheduler(3, prefill_chunk=16), prefill_bucket=8)
    seen = []
    for name in ("_prefill", "_decode"):
        fn = getattr(eng, name)

        def wrapped(*a, fn=fn):
            out = fn(*a)
            seen.append(np.asarray(out[0][:, -1]))
            return out
        setattr(eng, name, wrapped)
    rids = [eng.submit(p, max_new) for p in prompts]
    per_rid = {r: [] for r in rids}
    while True:
        lanes = eng._decoding.copy()
        owners = list(eng.pool.owner)
        kind = eng.step()
        if kind == "done":
            break
        req = None
        if kind == "prefill":
            req = eng.request(eng.events[-1][2])
            if len(req.tokens) == 1 and len(per_rid[req.rid]) == 0:
                per_rid[req.rid].append(seen[-1][0])
        elif kind == "decode":
            for lane in np.nonzero(lanes)[0]:
                per_rid[owners[lane]].append(seen[-1][lane])
    return eng, {r: (eng.request(r).tokens, np.stack(per_rid[r])) for r in rids}


def test_paged_prefill_and_decode_match_the_reference_logits():
    cfg = _cfg()
    model = build_model(cfg)
    params = _params(model)
    d = _dims(cfg)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, size=n).astype(np.int32) for n in (5, 21, 40)]
    eng, served = _serve_capturing_logits(model, params, prompts, 9)
    assert eng._kv_path == "latent_gather"
    for prompt, (toks, prog) in zip(prompts, served.values()):
        assert len(toks) == 9 and prog.shape == (9, cfg.vocab_size)
        seq = np.concatenate([prompt, np.asarray(toks, np.int32)])
        want = _ref_logits(d, params, seq)[len(prompt) - 1: len(seq) - 1]
        np.testing.assert_allclose(prog, want, atol=LOGIT_ATOL, rtol=0)
        # each served token is the program's own argmax at its position
        np.testing.assert_array_equal(prog.argmax(-1), toks)


def test_paged_decode_through_the_latent_kernel_matches_the_reference_logits(monkeypatch):
    """The same served logits with paged decode run by the interpreted
    Pallas latent kernel (the TPU's path) in place of the gathered view,
    over lanes of one and of two kernel groups (blocks of 8, 8 a group)."""
    monkeypatch.setattr(attn, "paged_latent_attention", functools.partial(
        attn._paged_latent_kernel, interpret=True))
    cfg = _cfg()
    model = build_model(cfg)
    params = _params(model)
    d = _dims(cfg)
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, cfg.vocab_size, size=n).astype(np.int32) for n in (11, 70)]
    _, served = _serve_capturing_logits(model, params, prompts, 9)
    for prompt, (toks, prog) in zip(prompts, served.values()):
        seq = np.concatenate([prompt, np.asarray(toks, np.int32)])
        want = _ref_logits(d, params, seq)[len(prompt) - 1: len(seq) - 1]
        np.testing.assert_allclose(prog, want, atol=LOGIT_ATOL, rtol=0)
        np.testing.assert_array_equal(prog.argmax(-1), toks)


@pytest.mark.parametrize("rank,dtype,block,platform,path", [
    (512, jnp.bfloat16, 16, "tpu", "latent_kernel"),
    (512, jnp.float32, 16, "tpu", "latent_kernel"),
    (512, jnp.float8_e4m3fn, 16, "tpu", "latent_gather"),   # half a (32, 128) tile
    (448, jnp.bfloat16, 16, "tpu", "latent_gather"),        # rank not whole lanes
    (512, jnp.bfloat16, 8, "tpu", "latent_gather"),         # half a (16, 128) tile
    (512, jnp.bfloat16, 16, "cpu", "latent_gather"),
], ids=["bf16-tpu", "f32-tpu", "fp8-tpu", "rank448-tpu", "block8-tpu", "bf16-cpu"])
def test_latent_path_is_chosen_by_platform_and_geometry(rank, dtype, block, platform, path):
    assert attn.paged_latent_path(rank, dtype, block, platform) == path


def test_paged_latent_decode_lowers_to_the_kernel_only_for_the_tpu(monkeypatch):
    """``mla_apply``'s paged absorbed decode lowers to the Pallas kernel for
    the TPU where a block is whole tiles (rank 128 in float32, blocks of
    16), and to the gathered view for the CPU. Run with the interpreted
    kernel, its output matches the gather's on live lanes, its cache
    writes are the same, and a dead lane on a NULL table reads nothing."""
    base = _cfg()
    cfg = dataclasses.replace(base, mla=dataclasses.replace(base.mla, kv_lora_rank=128))
    p = build_model(cfg).init(jax.random.PRNGKey(8))["stack"][0]["attn"]
    m, B, T, bs = cfg.mla, 4, 24, 16
    rng = np.random.default_rng(4)
    cache = {"ckv": jnp.asarray(rng.normal(size=(B * T + 1, bs, m.kv_lora_rank)), jnp.float32),
             "k_rope": jnp.asarray(rng.normal(size=(B * T + 1, bs, m.qk_rope_head_dim)),
                                   jnp.float32)}
    idx = jnp.asarray([0, 21, 8 * 16 + 5, T * bs - 1], jnp.int32)
    table = np.asarray(rng.permutation(B * T) + 1, np.int32).reshape(B, T)
    table[0] = attn.NULL_BLOCK                  # a dead lane writes to the sink
    table = jnp.asarray(table)
    x = jnp.asarray(rng.normal(size=(B, 1, cfg.d_model)), jnp.float32)

    def apply():
        return attn.mla_apply(p, x, cfg, positions=idx[:, None], cache=cache,
                              cache_index=idx, absorb=True, block_table=table)

    y_gather, c_gather = apply()
    H = cfg.n_heads
    traced = jax.jit(functools.partial(attn.paged_latent_attention, scale=0.1)).trace(
        jnp.zeros((B, 1, H, m.kv_lora_rank)), jnp.zeros((B, 1, H, m.qk_rope_head_dim)),
        cache["ckv"], cache["k_rope"], table, idx + 1)
    for platform, kernel in ((jax.default_backend(), False), ("tpu", True)):
        text = traced.lower(lowering_platforms=(platform,)).as_text()
        assert ("tpu_custom_call" in text) == kernel, platform

    monkeypatch.setattr(attn, "paged_latent_attention", functools.partial(
        attn._paged_latent_kernel, interpret=True))
    y_kernel, c_kernel = apply()
    np.testing.assert_array_equal(np.asarray(y_kernel[0]), 0.0)
    np.testing.assert_allclose(np.asarray(y_kernel[1:]), np.asarray(y_gather[1:]),
                               atol=2e-5, rtol=2e-5)
    for name in ("ckv", "k_rope"):
        np.testing.assert_array_equal(np.asarray(c_kernel[name]), np.asarray(c_gather[name]))


def test_engine_counts_held_expert_rows():
    """Each step's held-expert rows come back with its tokens: live tokens
    x layers x (choices on held experts), against held x capacity x
    layers computed."""
    cfg = _cfg()
    model = build_model(cfg)
    params = _params(model)
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, cfg.vocab_size, size=n).astype(np.int32) for n in (7, 30)]
    eng, _ = _serve_capturing_logits(model, params, prompts, 5)
    s = eng.stats
    e, layers = cfg.moe, cfg.n_layers - cfg.moe.first_k_dense
    live = s.prefill_tokens + (s.generated_tokens - len(prompts))
    assert 0 < s.moe_rows <= live * layers * e.top_k
    # each prefill chunk's capacity is its bucket, each tick's the slots
    assert s.moe_rows_computed % (e.held * layers) == 0
    assert s.moe_rows < s.moe_rows_computed
    assert not eng._moe_pending


def test_expert_shares_add_up_to_the_uncut_layer():
    full = _cfg(moe=dataclasses.replace(_cfg().moe, n_held=None))
    E, S = full.moe.n_experts, 2
    model = build_model(full)
    ffn = jax.tree.map(lambda x: x[0], _params(model)["stack"][-1]["ffn"])
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 11, full.d_model), jnp.float32)
    shared = np.asarray(moe_mod.mlp_apply(ffn["shared"], x, full.act, glu=True))
    total = shared.copy()
    for off in range(0, E, S):
        cfg = dataclasses.replace(full, moe=dataclasses.replace(full.moe, n_held=S,
                                                                held_offset=off))
        part = dict(ffn, **{k: ffn[k][off:off + S] for k in ("w_in", "w_gate", "w_out")})
        out, _, rows = moe_mod.moe_apply(part, x, cfg)
        total += np.asarray(out) - shared
        assert int(rows.max()) <= full.moe.top_k
    with jax.default_matmul_precision("highest"):
        want = ref.moe(_dims(full), None, x.reshape(-1, full.d_model), ffn)
    np.testing.assert_allclose(total.reshape(-1, full.d_model), np.asarray(want),
                               atol=2e-5, rtol=2e-5)
    uncut, _, rows = moe_mod.moe_apply(ffn, x, full)
    np.testing.assert_allclose(np.asarray(uncut), total, atol=2e-5, rtol=2e-5)
    assert int(rows.min()) == full.moe.top_k        # every choice is held, none dropped


def test_selection_uses_the_bias_and_the_weights_do_not():
    cfg = _cfg()
    e = cfg.moe
    x = jax.random.normal(jax.random.PRNGKey(2), (64, cfg.d_model), jnp.float32)
    router = jax.random.normal(jax.random.PRNGKey(3), (cfg.d_model, e.n_experts)) / cfg.d_model ** 0.5
    bias = 0.2 * jax.random.normal(jax.random.PRNGKey(4), (e.n_experts,))
    w, experts, _ = moe_mod._route(x, {"router": router, "router_bias": bias}, e)
    scores = jax.nn.sigmoid(x @ router)
    np.testing.assert_array_equal(experts, jax.lax.top_k(scores + bias, e.top_k)[1])
    unbiased = jnp.take_along_axis(scores, experts, axis=-1)
    np.testing.assert_allclose(
        w, unbiased / unbiased.sum(-1, keepdims=True) * e.routed_scaling, rtol=1e-5)
    # the seeded bias changes at least one choice
    assert (np.sort(experts, -1) != np.sort(jax.lax.top_k(scores, e.top_k)[1], -1)).any()
    r_experts, r_w = ref.route(_dims(cfg), x, router, bias)
    np.testing.assert_array_equal(r_experts, experts)
    np.testing.assert_allclose(r_w, w, rtol=1e-5)


def test_mla_without_query_lora_projects_queries_directly():
    cfg = _cfg()
    m = cfg.mla
    specs = attn.mla_specs(cfg)
    assert m.q_lora_rank is None
    assert specs["wq"].shape == (cfg.d_model, cfg.n_heads, m.qk_nope_head_dim + m.qk_rope_head_dim)
    assert not {"wq_a", "wq_b", "q_norm"} & set(specs)
    p = build_model(cfg).init(jax.random.PRNGKey(6))["stack"][0]["attn"]
    x = jax.random.normal(jax.random.PRNGKey(7), (1, 13, cfg.d_model), jnp.float32)
    y, _ = attn.mla_apply(p, x, cfg, positions=jnp.arange(13))
    with jax.default_matmul_precision("highest"):
        want = ref._attention(_dims(cfg), None, x[0], p)
    np.testing.assert_allclose(np.asarray(y[0]), np.asarray(want), atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("eps", [1e-5, 0.5])
def test_norm_epsilon_is_the_configs(eps):
    cfg = _cfg(rms_norm_eps=eps)
    model = build_model(cfg)
    params = _params(model)
    tokens = jnp.asarray(np.arange(12, dtype=np.int32)[None] * 7 % cfg.vocab_size)
    h, _ = model.hidden(params, tokens, jnp.arange(12))
    for ref_eps, close in ((eps, True), (1e-6 if eps == 0.5 else 0.5, False)):
        with jax.default_matmul_precision("highest"):
            want = ref.hidden(dataclasses.replace(_dims(cfg), rms_eps=ref_eps), None, params,
                              tokens[0])
        assert np.allclose(np.asarray(h[0]), np.asarray(want), atol=1e-4) == close


def test_param_count_holds_the_share():
    cfg = _cfg()
    e = cfg.moe
    leaves = jax.tree.leaves(build_model(cfg).init(jax.random.PRNGKey(0)))
    total = sum(int(x.size) for x in leaves)
    assert count_params_analytic(cfg) == total
    per_expert = 3 * cfg.d_model * e.d_expert
    layers = cfg.n_layers - e.first_k_dense
    active = count_params_analytic(cfg, active_only=True)
    assert active == total - round((e.held - e.top_k * e.held / e.n_experts) * per_expert * layers)
    assert 0 < active < total
    # at the published sizes: 8 held of 64 (the old count went negative)
    full = get_config("moonlight-16b-a3b")
    assert 0 < count_params_analytic(full, active_only=True) < count_params_analytic(full)


def test_decode_spans_read_latent_gather_and_moe_spans_carry_the_rows(tmp_path):
    from test_wall_spans import _named, _profile, _program_spans

    cfg = _cfg()
    model = build_model(cfg)
    eng = ServeEngine(model, _params(model), n_slots=2, max_len=64, block_size=8,
                      scheduler=Scheduler(2, prefill_chunk=16), prefill_bucket=8)
    rng = np.random.default_rng(2)
    with _profile(tmp_path):
        for n in (9, 20):
            eng.submit(rng.integers(0, cfg.vocab_size, size=n).astype(np.int32), 4)
        eng.run()
    spans = _program_spans(tmp_path)
    decodes = _named(spans, "repro.engine.decode")
    assert decodes and all(s[3]["kv_path"] == "latent_gather" for s in decodes)
    moe = [s[3] for s in _named(spans, "repro.engine.moe")]
    assert sum(a["moe_rows"] for a in moe) == eng.stats.moe_rows > 0
    assert sum(a["moe_rows_computed"] for a in moe) == eng.stats.moe_rows_computed
