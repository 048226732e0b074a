"""Per-architecture smoke tests: reduced config, one forward/train step on
CPU, output shapes + finiteness; decode/prefill consistency for one arch
per family; gradient flow."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, list_archs
from repro.models import build_model, count_params_analytic
from repro.models.layers import init_from_specs

RNG = jax.random.PRNGKey(0)


def _batch(cfg, B=2, S=32):
    if cfg.input_kind == "tokens":
        inputs = jax.random.randint(RNG, (B, S), 0, cfg.vocab_size)
    else:
        inputs = jax.random.normal(RNG, (B, S, cfg.d_model), jnp.float32)
    labels = jax.random.randint(RNG, (B, S), 0, cfg.vocab_size)
    return {"inputs": inputs, "labels": labels}


@pytest.mark.parametrize("arch", list_archs())
def test_smoke_train_step(arch):
    cfg = get_config(arch).reduced()
    model = build_model(cfg)
    params = model.init(RNG)
    batch = _batch(cfg)
    loss, metrics = model.train_loss(params, batch)
    assert loss.shape == ()
    assert bool(jnp.isfinite(loss)), f"{arch} loss not finite"
    assert float(loss) > 0


@pytest.mark.parametrize("arch", ["smollm-135m", "xlstm-125m"])
def test_gradients_finite(arch):
    cfg = get_config(arch).reduced()
    model = build_model(cfg)
    params = model.init(RNG)
    batch = _batch(cfg)
    grads = jax.grad(lambda p: model.train_loss(p, batch)[0])(params)
    for leaf in jax.tree.leaves(grads):
        assert bool(jnp.isfinite(leaf).all())


@pytest.mark.parametrize(
    "arch",
    ["smollm-135m", "qwen3-moe-30b-a3b", "deepseek-v3-671b", "zamba2-1.2b",
     "xlstm-125m"],
)
def test_decode_matches_prefill_logits(arch):
    """Greedy decode step-by-step must agree with teacher-forced forward.

    MoE archs: capacity dropping is batch-size dependent (8 routed tokens
    vs 1), so the comparison is only meaningful drop-free — crank the
    capacity factor up for this test."""
    cfg = get_config(arch).reduced()
    if cfg.moe is not None:
        cfg = dataclasses.replace(
            cfg, moe=dataclasses.replace(cfg.moe, capacity_factor=16.0)
        )
    model = build_model(cfg)
    params = model.init(RNG)
    B, T = 1, 8
    tokens = jax.random.randint(jax.random.PRNGKey(3), (B, T), 0, cfg.vocab_size)

    # Teacher-forced logits for every prefix position.
    positions = jnp.arange(T)
    h, _ = model.hidden(params, tokens, positions)
    full_logits = model.logits(params, h)  # (B, T, V)

    # Step-by-step decode with the cache.
    caches = init_from_specs(RNG, model.cache_specs(B, T + 1))
    outs = []
    for t in range(T):
        logits, caches = model.decode_step(
            params, tokens[:, t : t + 1], caches, jnp.int32(t)
        )
        outs.append(logits[:, 0])
    dec_logits = jnp.stack(outs, axis=1)

    np.testing.assert_allclose(
        np.asarray(dec_logits, np.float32),
        np.asarray(full_logits, np.float32),
        atol=2e-2,
        rtol=2e-2,
    )


def test_param_counts_match_spec_tree():
    for arch in list_archs():
        cfg = get_config(arch)
        n = count_params_analytic(cfg)
        na = count_params_analytic(cfg, active_only=True)
        assert n > 0 and na <= n
        if cfg.moe is not None:
            assert na < n  # MoE must have inactive experts


def test_moe_capacity_drops_gracefully():
    """With capacity factor ~0, every token is dropped -> output only the
    shared path (or zeros), still finite."""
    cfg = get_config("qwen3-moe-30b-a3b").reduced()
    cfg = dataclasses.replace(
        cfg, moe=dataclasses.replace(cfg.moe, capacity_factor=0.01)
    )
    model = build_model(cfg)
    params = model.init(RNG)
    loss, _ = model.train_loss(params, _batch(cfg))
    assert bool(jnp.isfinite(loss))


def test_encoder_is_order_sensitive_but_not_causal():
    """hubert (bidirectional): flipping a LATE frame must change EARLY
    outputs (non-causal), unlike the causal decoders."""
    cfg = get_config("hubert-xlarge").reduced()
    model = build_model(cfg)
    params = model.init(RNG)
    B, S = 1, 16
    x = jax.random.normal(RNG, (B, S, cfg.d_model), jnp.float32)
    h1, _ = model.hidden(params, x, jnp.arange(S))
    x2 = x.at[:, -1].set(-x[:, -1])
    h2, _ = model.hidden(params, x2, jnp.arange(S))
    delta_early = float(jnp.abs(h1[:, 0] - h2[:, 0]).max())
    assert delta_early > 1e-6  # information flows backwards in an encoder


def test_causal_decoder_is_causal():
    cfg = get_config("smollm-135m").reduced()
    model = build_model(cfg)
    params = model.init(RNG)
    B, S = 1, 16
    toks = jax.random.randint(RNG, (B, S), 0, cfg.vocab_size)
    h1, _ = model.hidden(params, toks, jnp.arange(S))
    toks2 = toks.at[:, -1].set((toks[:, -1] + 1) % cfg.vocab_size)
    h2, _ = model.hidden(params, toks2, jnp.arange(S))
    np.testing.assert_allclose(
        np.asarray(h1[:, :-1], np.float32), np.asarray(h2[:, :-1], np.float32),
        atol=1e-5,
    )


def test_pallas_attention_path_matches_default(monkeypatch):
    """cfg.use_pallas routes through the flash kernel and must agree with
    the chunked-jnp path. The model never picks the kernel's interpreter
    itself; here the test hands it the interpreted kernel."""
    import functools

    import repro.kernels.flash_attention as fa

    monkeypatch.setattr(
        fa, "flash_attention", functools.partial(fa.flash_attention,
                                                 interpret=True)
    )
    cfg = get_config("smollm-135m").reduced(n_layers=2, max_seq_len=128)
    cfg_p = dataclasses.replace(cfg, use_pallas=True)
    m0, m1 = build_model(cfg), build_model(cfg_p)
    params = m0.init(RNG)
    toks = jax.random.randint(RNG, (2, 128), 0, cfg.vocab_size)
    h0, _ = m0.hidden(params, toks, jnp.arange(128))
    h1, _ = m1.hidden(params, toks, jnp.arange(128))
    np.testing.assert_allclose(
        np.asarray(h0, np.float32), np.asarray(h1, np.float32),
        atol=2e-3, rtol=2e-3,
    )


def test_pallas_attention_path_refuses_non_tpu_backend():
    """No quiet fallback: on a backend without the TPU compiler the
    kernel path raises instead of running the Pallas interpreter."""
    cfg = dataclasses.replace(
        get_config("smollm-135m").reduced(n_layers=1, max_seq_len=128),
        use_pallas=True,
    )
    model = build_model(cfg)
    params = model.init(RNG)
    toks = jnp.zeros((1, 128), jnp.int32)
    with pytest.raises(ValueError, match="interpret"):
        model.hidden(params, toks, jnp.arange(128))


@pytest.mark.parametrize("heads", [(16, 2, 128, "paged_kernel"), (9, 3, 64, "gather")],
                         ids=["qwen2.5-heads", "smollm-heads"])
def test_paged_gqa_decode_kernel_matches_gather(monkeypatch, heads):
    """Paged GQA decode lowered for the TPU runs the Pallas kernel over
    live blocks where a block is whole tiles of the TPU's layout (qwen2.5's
    2 kv heads of 128), and the gathered view otherwise (smollm's 3 of 64)
    and on every other backend. Then the test hands ``gqa_apply`` the
    interpreted kernel and compares its output and cache writes with the
    gather path, over lanes that sit at ragged positions across several
    kernel groups, and a dead lane on a NULL table, which the kernel path
    skips."""
    from repro.models import attention as attn

    H, Hkv, D, tpu_path = heads
    cfg = get_config("smollm-135m").reduced(
        n_layers=1, d_model=64, n_heads=H, n_kv_heads=Hkv, head_dim=D)
    params = init_from_specs(RNG, attn.gqa_specs(cfg))
    B, T, bs = 4, 40, 16
    rng = np.random.default_rng(0)
    n_blocks = B * T
    arena = lambda: jnp.asarray(  # noqa: E731
        rng.normal(size=(n_blocks + 1, bs, Hkv, D)), jnp.float32)
    cache = {"k": arena(), "v": arena()}
    idx = jnp.asarray([0, 17, 16 * 16 + 3, T * bs - 1], jnp.int32)
    table = np.asarray(rng.permutation(n_blocks) + 1, np.int32).reshape(B, T)
    table[0] = attn.NULL_BLOCK                  # a dead lane writes to the sink
    table = jnp.asarray(table)
    x = jnp.asarray(rng.normal(size=(B, 1, cfg.d_model)), jnp.float32)

    def apply():
        return attn.gqa_apply(params, x, cfg, positions=idx[:, None], cache=cache,
                              cache_index=idx, block_table=table)

    y_gather, c_gather = apply()
    traced = jax.jit(attn.paged_decode_attention).trace(
        jnp.zeros((B, 1, H, D)), cache["k"], cache["v"], table, idx + 1)
    for platform, path in ((jax.default_backend(), "gather"), ("tpu", tpu_path)):
        text = traced.lower(lowering_platforms=(platform,)).as_text()
        assert ("tpu_custom_call" in text) == (path == "paged_kernel"), platform
        assert attn.paged_decode_path(Hkv, D, jnp.float32, platform) == path

    monkeypatch.setattr(attn, "paged_decode_attention", functools.partial(
        attn._paged_decode_kernel, interpret=True))
    y_kernel, c_kernel = apply()
    # The dead lane reads nothing on the kernel path (the gather attends
    # over the sink's garbage); its output is discarded either way.
    np.testing.assert_array_equal(np.asarray(y_kernel[0]), 0.0)
    np.testing.assert_allclose(np.asarray(y_kernel[1:]), np.asarray(y_gather[1:]),
                               atol=2e-5, rtol=2e-5)
    for name in ("k", "v"):
        np.testing.assert_array_equal(np.asarray(c_kernel[name]),
                                      np.asarray(c_gather[name]))
