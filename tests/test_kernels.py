"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps, interpret mode."""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention import attention_ref, flash_attention
from repro.kernels.rmsnorm import rmsnorm, rmsnorm_ref
from repro.kernels.ssd_scan import ssd_ref, ssd_scan

RNG = np.random.default_rng(7)


def _arr(shape, dtype):
    return jnp.asarray(RNG.normal(size=shape), dtype)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

FLASH_CASES = [
    # B, Sq, Skv, H, Hkv, D, Dv, causal
    (2, 128, 128, 4, 2, 64, 64, True),
    (1, 256, 256, 8, 8, 64, 64, True),     # MHA
    (1, 200, 200, 4, 1, 64, 64, True),     # MQA, ragged seq (padding path)
    (2, 128, 128, 4, 2, 128, 128, False),  # bidirectional
    (1, 64, 64, 2, 2, 32, 32, True),       # small blocks
    (1, 384, 384, 6, 3, 64, 64, True),     # 3 q blocks
]


@pytest.mark.parametrize("case", FLASH_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_matches_ref(case, dtype):
    B, Sq, Skv, H, Hkv, D, Dv, causal = case
    q = _arr((B, Sq, H, D), dtype)
    k = _arr((B, Skv, Hkv, D), dtype)
    v = _arr((B, Skv, Hkv, Dv), dtype)
    out = flash_attention(q, k, v, causal=causal, block_q=128, block_kv=128,
                          interpret=True)
    ref = attention_ref(q, k, v, causal=causal)
    tol = 6e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), atol=tol
    )


def test_flash_attention_block_size_invariance():
    q = _arr((1, 256, 4, 64), jnp.float32)
    k = _arr((1, 256, 2, 64), jnp.float32)
    v = _arr((1, 256, 2, 64), jnp.float32)
    a = flash_attention(q, k, v, causal=True, block_q=64, block_kv=64,
                        interpret=True)
    b = flash_attention(q, k, v, causal=True, block_q=128, block_kv=256,
                        interpret=True)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


# ---------------------------------------------------------------------------
# SSD scan
# ---------------------------------------------------------------------------

SSD_CASES = [
    # B, S, H, P, G, N, chunk
    (2, 64, 4, 32, 2, 16, 16),
    (1, 100, 2, 64, 1, 32, 32),   # padding path
    (2, 256, 4, 64, 2, 64, 128),
    (1, 128, 8, 64, 8, 64, 64),   # one group per head
]


@pytest.mark.parametrize("case", SSD_CASES)
def test_ssd_scan_matches_recurrence(case):
    B, S, H, P, G, N, chunk = case
    x = _arr((B, S, H, P), jnp.float32)
    dt = jnp.asarray(RNG.uniform(0.01, 0.3, size=(B, S, H)), jnp.float32)
    A = jnp.asarray(-RNG.uniform(0.5, 2.0, size=(H,)), jnp.float32)
    Bm = _arr((B, S, G, N), jnp.float32)
    Cm = _arr((B, S, G, N), jnp.float32)
    out = ssd_scan(x, dt, A, Bm, Cm, chunk=chunk, interpret=True)
    ref = ssd_ref(x, dt, A, Bm, Cm)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=5e-4)


def test_ssd_chunk_invariance():
    B, S, H, P, G, N = 1, 192, 2, 32, 1, 16
    x = _arr((B, S, H, P), jnp.float32)
    dt = jnp.asarray(RNG.uniform(0.01, 0.3, size=(B, S, H)), jnp.float32)
    A = jnp.asarray(-RNG.uniform(0.5, 2.0, size=(H,)), jnp.float32)
    Bm = _arr((B, S, G, N), jnp.float32)
    Cm = _arr((B, S, G, N), jnp.float32)
    a = ssd_scan(x, dt, A, Bm, Cm, chunk=32, interpret=True)
    b = ssd_scan(x, dt, A, Bm, Cm, chunk=96, interpret=True)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-4)


# ---------------------------------------------------------------------------
# rmsnorm
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("shape", [(4, 64, 128), (2, 100, 576), (1, 7, 64)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rmsnorm_matches_ref(shape, dtype):
    x = _arr(shape, dtype)
    scale = _arr(shape[-1:], dtype)
    out = rmsnorm(x, scale, interpret=True)
    ref = rmsnorm_ref(x, scale)
    # bf16: the oracle rounds to bf16 BEFORE the scale multiply, the fused
    # kernel keeps f32 until the end — a few-ULP ordering difference.
    tol = 1e-1 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), atol=tol
    )


# ---------------------------------------------------------------------------
# flash decode (single-query attention over a long cache)
# ---------------------------------------------------------------------------

from repro.kernels.decode_attention import (  # noqa: E402
    decode_ref,
    flash_decode,
    paged_decode_ref,
    paged_flash_decode,
)
from repro.kernels.decode_attention.kernel import PAGES_PER_GROUP  # noqa: E402

DECODE_CASES = [
    # B, S, H, Hkv, D, block_kv
    (2, 256, 8, 2, 64, 64),
    (1, 320, 4, 4, 128, 64),    # non-power-of-two block count
    (3, 1024, 8, 1, 64, 512),   # MQA
]


@pytest.mark.parametrize("case", DECODE_CASES)
def test_flash_decode_matches_ref(case):
    B, S, H, Hkv, D, block = case
    q = _arr((B, H, D), jnp.float32)
    k = _arr((B, S, Hkv, D), jnp.float32)
    v = _arr((B, S, Hkv, D), jnp.float32)
    lengths = jnp.asarray(RNG.integers(1, S + 1, size=(B,)), jnp.int32)
    out = flash_decode(q, k, v, lengths, block_kv=block, interpret=True)
    ref = decode_ref(q, k, v, lengths)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_flash_decode_never_pads_the_cache():
    """Regression: the wrapper used to jnp.pad (= copy) the whole K/V
    cache in HBM on every decode tick when S % block_kv != 0. Caches are
    allocated block-aligned now (cache_specs rounds max_len up), so a
    non-dividing request clamps to the largest dividing block — same
    result, zero copies — and an unalignable cache is an error."""
    q = _arr((1, 4, 32), jnp.float32)
    k = _arr((1, 96, 2, 32), jnp.float32)
    v = _arr((1, 96, 2, 32), jnp.float32)
    lengths = jnp.array([57])
    out = flash_decode(q, k, v, lengths, block_kv=64, interpret=True)  # -> 48
    ref = decode_ref(q, k, v, lengths)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)
    # An aligned default-blocked long cache also clamps instead of raising.
    k2 = _arr((1, 528, 2, 32), jnp.float32)   # 528 = round_kv_len(520)
    v2 = _arr((1, 528, 2, 32), jnp.float32)
    out2 = flash_decode(q, k2, v2, lengths, interpret=True)  # 512 -> 264
    np.testing.assert_allclose(
        np.asarray(out2), np.asarray(decode_ref(q, k2, v2, lengths)), atol=2e-5
    )
    # No divisor >= 8 (prime length): the cache violated the alignment
    # contract — refuse rather than silently copy it every tick.
    k3 = _arr((1, 97, 2, 32), jnp.float32)
    with pytest.raises(ValueError, match="block-aligned"):
        flash_decode(q, k3, k3, lengths, block_kv=64, interpret=True)


def test_flash_decode_length_masking_exact():
    """Entries beyond `lengths` must have zero influence."""
    B, S, H, Hkv, D = 1, 128, 4, 2, 32
    q = _arr((B, H, D), jnp.float32)
    k = _arr((B, S, Hkv, D), jnp.float32)
    v = _arr((B, S, Hkv, D), jnp.float32)
    L = 50
    out1 = flash_decode(q, k, v, jnp.array([L]), block_kv=64, interpret=True)
    k2 = k.at[:, L:].set(99.0)   # poison the masked tail
    v2 = v.at[:, L:].set(-99.0)
    out2 = flash_decode(q, k2, v2, jnp.array([L]), block_kv=64, interpret=True)
    np.testing.assert_allclose(np.asarray(out1), np.asarray(out2), atol=1e-6)


# ---------------------------------------------------------------------------
# paged flash decode (block-table arena)
# ---------------------------------------------------------------------------

def _scatter_to_arena(k, v, lengths, block_size, seed=0):
    """Scatter contiguous (B, S, ...) caches into a shuffled block arena
    with garbage everywhere a live block is not (the NULL sink block 0
    and all unreferenced rows), returning (k_arena, v_arena, tables)."""
    rng = np.random.default_rng(seed)
    B, S = k.shape[:2]
    T = S // block_size
    ids = rng.permutation(B * T) + 1          # blocks shuffled, 0 = sink
    k_arena = rng.normal(size=(B * T + 1, block_size, *k.shape[2:]))
    v_arena = rng.normal(size=(B * T + 1, block_size, *v.shape[2:]))
    tables = np.zeros((B, T), np.int32)
    nxt = 0
    for b in range(B):
        n_live = -(-int(lengths[b]) // block_size)
        for t in range(n_live):
            bid = int(ids[nxt]); nxt += 1
            tables[b, t] = bid
            k_arena[bid] = np.asarray(k[b, t * block_size:(t + 1) * block_size])
            v_arena[bid] = np.asarray(v[b, t * block_size:(t + 1) * block_size])
    return (jnp.asarray(k_arena, k.dtype), jnp.asarray(v_arena, v.dtype),
            jnp.asarray(tables))


PAGED_CASES = [
    # S, H, Hkv, D, block_size
    (64, 8, 2, 64, 16),    # GQA, small blocks
    (128, 8, 1, 64, 32),   # MQA
    (64, 8, 8, 32, 64),    # MHA, one block per sequence
    (512, 16, 2, 128, 16),  # qwen2.5-3b's heads, block 16: T = 4 groups
    (256, 8, 1, 128, 16),  # MQA in float32 is whole tiles too: 2 groups
    (256, 9, 3, 64, 16),   # smollm-135m's heads: the (B, T) grid
]


@pytest.mark.parametrize("case", PAGED_CASES)
def test_paged_flash_decode_matches_oracles(case):
    """Kernel vs the jnp paged oracle vs the contiguous oracle across the
    boundary lengths {0, 1, bs-1, bs, bs+1, a group, a group + 1, max} in
    one ragged batch, and a dead lane (a NULL table, as the engine's free
    slots have). Only live blocks are populated in the arena — everything
    else is garbage, so any read past a block table's live prefix shows
    up; groups past the live ones must be skipped."""
    S, H, Hkv, D, bs = case
    group = min(PAGES_PER_GROUP * bs, S)
    lengths = np.array([0, 1, bs - 1, bs, min(bs + 1, S), group,
                        min(group + 1, S), S, min(bs + 1, S)], np.int32)
    B = len(lengths)
    q = _arr((B, H, D), jnp.float32)
    k = _arr((B, S, Hkv, D), jnp.float32)
    v = _arr((B, S, Hkv, D), jnp.float32)
    k_arena, v_arena, tables = _scatter_to_arena(k, v, lengths, bs)
    live = np.arange(B) != B - 1
    tables = tables.at[B - 1].set(0)            # dead lane: reads the sink
    lengths = jnp.asarray(lengths)

    ref = paged_decode_ref(q, k_arena, v_arena, tables, lengths)
    out = paged_flash_decode(q, k_arena, v_arena, tables, lengths,
                             interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)

    # The paged oracle must equal the contiguous oracle bit-for-bit on
    # live rows (this is the engine's byte-identity contract) and zero
    # the length-0 convention rows.
    contig = np.asarray(decode_ref(q, k, v, lengths))
    contig = np.where(np.asarray(lengths)[:, None, None] > 0, contig, 0.0)
    np.testing.assert_array_equal(np.asarray(ref)[live], contig[live])


def test_paged_flash_decode_ragged_gqa_sweep():
    """Random ragged lengths x GQA group sizes (G in {1, 4, 8})."""
    S, D, bs, B = 96, 32, 16, 4
    for Hkv in (8, 2, 1):
        H = 8
        lengths = np.asarray(RNG.integers(1, S + 1, size=(B,)), np.int32)
        q = _arr((B, H, D), jnp.float32)
        k = _arr((B, S, Hkv, D), jnp.float32)
        v = _arr((B, S, Hkv, D), jnp.float32)
        k_arena, v_arena, tables = _scatter_to_arena(k, v, lengths, bs,
                                                     seed=Hkv)
        out = paged_flash_decode(q, k_arena, v_arena, tables,
                                 jnp.asarray(lengths), interpret=True)
        ref = decode_ref(q, k, v, jnp.asarray(lengths))
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, err_msg=f"Hkv={Hkv}")


# ---------------------------------------------------------------------------
# paged latent decode (MLA's absorbed decode over paged latent arenas)
# ---------------------------------------------------------------------------

from repro.kernels.decode_attention import (  # noqa: E402
    paged_latent_decode,
    paged_latent_ref,
)
from repro.models.attention import latent_decode_attention  # noqa: E402

LATENT_CASES = [
    # S, H, r, rope, block_size
    (416, 16, 512, 64, 16),   # Moonlight's widths: up to 4 groups of 128 rows
    (208, 4, 128, 32, 8),     # a narrow rank, rope and block: groups of 64 rows
]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("case", LATENT_CASES, ids=["moonlight", "narrow"])
def test_paged_latent_decode_matches_oracle(case, dtype):
    """Kernel vs ``mla_apply``'s own absorbed einsums over the gathered
    views, in one ragged batch: a lane of length 0 on a NULL table, lengths
    that are not multiples of a block or a group, one lane of several
    groups and one of the whole capacity. Only live blocks are populated
    in the arenas (garbage elsewhere, the sink included), so a read past a
    table's live prefix shows up."""
    S, H, r, rope, bs = case
    group = PAGES_PER_GROUP * bs
    lengths = np.array([0, 1, bs - 1, bs + 3, group - 5, group + 7,
                        3 * group - 9, S], np.int32)
    B = len(lengths)
    scale = 1.0 / np.sqrt(192.0)
    q_lat = _arr((B, H, r), dtype)
    q_rope = _arr((B, H, rope), dtype)
    ckv = _arr((B, S, r), dtype)
    k_rope = _arr((B, S, rope), dtype)
    c_arena, r_arena, tables = _scatter_to_arena(ckv, k_rope, lengths, bs)
    assert not np.asarray(tables[0]).any()       # length 0: a NULL table
    lengths = jnp.asarray(lengths)

    ref = paged_latent_ref(q_lat, q_rope, c_arena, r_arena, tables, lengths,
                           scale=scale)
    out = paged_latent_decode(q_lat, q_rope, c_arena, r_arena, tables, lengths,
                              scale=scale, interpret=True)
    assert out.shape == (B, H, r) and out.dtype == dtype
    np.testing.assert_array_equal(np.asarray(out[0], np.float32), 0.0)
    # bfloat16: the output's own rounding and the probabilities' on the MXU
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(ref),
                               atol=tol, rtol=tol)

    # The gathered oracle is the contiguous path bit for bit on live lanes.
    contig = latent_decode_attention(q_lat[:, None], q_rope[:, None], ckv, k_rope,
                                     lengths, scale=scale)[:, 0]
    np.testing.assert_array_equal(np.asarray(ref)[1:], np.asarray(contig)[1:])
