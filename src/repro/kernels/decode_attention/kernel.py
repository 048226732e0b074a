"""Pallas TPU flash-decode: single-query attention over a long KV cache.

Decode at 32k-500k context is HBM-bound (the roofline table's verdict on
every decode cell): the step reads the whole KV cache once. This kernel
streams the cache HBM->VMEM in blocks on the LAST (sequential) grid dim,
carrying partial softmax statistics (m, l, acc) in VMEM scratch, and
masks beyond the valid length — one pass, no (S,) score materialization
in HBM.

Grid = (B, num_kv_blocks); each program owns one sequence and reads each
cache block ONCE for every head. The cache's (Hkv, D) minor dims are
viewed as one (Hkv * D) row (a free reshape), so a block is a
(block_kv, Hkv * D) slab whose last two dims the TPU tiles natively. GQA
is resolved with a block-diagonal query: head ``j``'s query occupies the
``D`` columns of its kv head ``j // G`` and is zero elsewhere, so one
(H, Hkv*D) @ (Hkv*D, block_kv) product yields every head's scores and
one (H, block_kv) @ (block_kv, Hkv*D) product every head's partial
output (the wrapper keeps each head's own kv-head columns). Decode is
bandwidth-bound, so the Hkv-fold extra MXU work is free.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["decode_attention_fwd", "paged_decode_attention_fwd"]

NEG_INF = -1e30


def _block_diag_query(q: jax.Array, Hkv: int) -> jax.Array:
    """(B, H, D) -> (B, H, Hkv * D): head j's query in the columns of its
    kv head j // G, zeros elsewhere."""
    B, H, D = q.shape
    owner = jnp.arange(H) // (H // Hkv)                          # (H,)
    sel = (owner[:, None] == jnp.arange(Hkv)[None, :]).astype(q.dtype)
    return (q[:, :, None, :] * sel[None, :, :, None]).reshape(B, H, Hkv * D)


def _own_head_columns(out: jax.Array, Hkv: int, Dv: int) -> jax.Array:
    """(B, H, Hkv * Dv) -> (B, H, Dv): each head's own kv-head block."""
    B, H, _ = out.shape
    owner = jnp.arange(H) // (H // Hkv)
    out = out.reshape(B, H, Hkv, Dv)
    return jnp.take_along_axis(out, owner[None, :, None, None], axis=2)[:, :, 0]


def _online_softmax_step(q_ref, k_ref, v_ref, m_ref, l_ref, acc_ref, *,
                         scale, kv_start, length):
    """One cache block of the flash-decode recurrence for every head."""
    q = q_ref[0].astype(jnp.float32) * scale                # (H, Hkv*D)
    k = k_ref[0].astype(jnp.float32)                        # (bkv, Hkv*D)
    v = v_ref[0].astype(jnp.float32)                        # (bkv, Hkv*Dv)
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )                                                       # (H, bkv)
    kv_ids = kv_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where(kv_ids < length, s, NEG_INF)

    m_prev = m_ref[...]                                     # (H, 1)
    m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + p.sum(axis=1, keepdims=True)
    pv = jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )                                                       # (H, Hkv*Dv)
    acc_ref[...] = acc_ref[...] * corr + pv
    m_ref[...] = m_new


def _init_stats(m_ref, l_ref, acc_ref):
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)


def _write_out(o_ref, l_ref, acc_ref):
    # length == 0 leaves l at 0 -> output exactly zeros (the paged
    # oracle mirrors this convention for empty sequences).
    l = jnp.maximum(l_ref[...], 1e-30)
    o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


def _kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
            *, scale, block_kv):
    b = pl.program_id(0)
    ikv = pl.program_id(1)

    @pl.when(ikv == 0)
    def _init():
        _init_stats(m_ref, l_ref, acc_ref)

    length = len_ref[b]
    kv_start = ikv * block_kv

    @pl.when(kv_start < length)
    def _step():
        _online_softmax_step(q_ref, k_ref, v_ref, m_ref, l_ref, acc_ref,
                             scale=scale, kv_start=kv_start, length=length)

    @pl.when(ikv == pl.num_programs(1) - 1)
    def _finish():
        _write_out(o_ref, l_ref, acc_ref)


def _scratch(H: int, width: int):
    return [
        pltpu.VMEM((H, 1), jnp.float32),       # m
        pltpu.VMEM((H, 1), jnp.float32),       # l
        pltpu.VMEM((H, width), jnp.float32),   # acc
    ]


def decode_attention_fwd(
    q: jax.Array,        # (B, H, D) — single query position per sequence
    k: jax.Array,        # (B, S, Hkv, D)
    v: jax.Array,        # (B, S, Hkv, Dv)
    lengths: jax.Array,  # (B,) valid prefix length per sequence
    *,
    block_kv: int = 512,
    interpret: bool = False,
) -> jax.Array:
    B, H, D = q.shape
    S, Hkv, Dv = k.shape[1], k.shape[2], v.shape[3]
    scale = 1.0 / math.sqrt(D)

    block_kv = min(block_kv, S)
    if S % block_kv:
        # Padding here would jnp.pad (= copy) the whole K/V cache in HBM
        # on EVERY decode tick. Caches are allocated block-aligned once
        # (``Model.cache_specs`` rounds max_len up to KV_SEQ_ALIGN), so a
        # dividing block always exists — clamp to the largest one instead
        # of copying. A cache with no usable divisor was allocated
        # without the alignment contract: that IS a caller bug.
        block_kv = next(b for b in range(block_kv, 0, -1) if S % b == 0)
        if block_kv < 8:
            raise ValueError(
                f"cache length S={S} has no usable kv block size; allocate "
                "the cache block-aligned (cache_specs rounds max_len up)"
            )
    n_kv = S // block_kv

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,  # lengths
        grid=(B, n_kv),
        in_specs=[
            pl.BlockSpec((1, H, Hkv * D), lambda b, i, lens: (b, 0, 0)),
            pl.BlockSpec((1, block_kv, Hkv * D), lambda b, i, lens: (b, i, 0)),
            pl.BlockSpec((1, block_kv, Hkv * Dv), lambda b, i, lens: (b, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, H, Hkv * Dv), lambda b, i, lens: (b, 0, 0)),
        scratch_shapes=_scratch(H, Hkv * Dv),
    )
    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale, block_kv=block_kv),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, Hkv * Dv), q.dtype),
        interpret=interpret,
    )(
        lengths.astype(jnp.int32),
        _block_diag_query(q, Hkv),
        k.reshape(B, S, Hkv * D),
        v.reshape(B, S, Hkv * Dv),
    )
    return _own_head_columns(out, Hkv, Dv)


# ---------------------------------------------------------------------------
# Paged flash-decode: the KV cache is a global block arena + per-sequence
# block tables (vLLM-style). The grid's sequential dim walks TABLE SLOTS,
# not cache rows: the block table is scalar-prefetched (SMEM before the
# body runs) so each K/V BlockSpec index_map gathers the right arena row,
# and slots past ceil(length/block) clamp to the last live block — Pallas
# skips the HBM->VMEM copy when the mapped block index repeats, and
# @pl.when skips the compute. Decode traffic and FLOPs are therefore
# proportional to LIVE tokens, not to n_slots * max_len.
# ---------------------------------------------------------------------------

def _paged_kernel(tab_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
                  m_ref, l_ref, acc_ref, *, scale, block_size):
    b = pl.program_id(0)
    t = pl.program_id(1)

    @pl.when(t == 0)
    def _init():
        _init_stats(m_ref, l_ref, acc_ref)

    length = len_ref[b]
    kv_start = t * block_size

    @pl.when(kv_start < length)
    def _step():
        _online_softmax_step(q_ref, k_ref, v_ref, m_ref, l_ref, acc_ref,
                             scale=scale, kv_start=kv_start, length=length)

    @pl.when(t == pl.num_programs(1) - 1)
    def _finish():
        _write_out(o_ref, l_ref, acc_ref)


def paged_decode_attention_fwd(
    q: jax.Array,             # (B, H, D) — single query position per sequence
    k_arena: jax.Array,       # (num_blocks + 1, block_size, Hkv, D)
    v_arena: jax.Array,       # (num_blocks + 1, block_size, Hkv, Dv)
    block_tables: jax.Array,  # (B, T) arena indices; 0 = NULL sink block
    lengths: jax.Array,       # (B,) valid prefix length per sequence
    *,
    interpret: bool = False,
) -> jax.Array:
    B, H, D = q.shape
    n_rows, block_size, Hkv, Dv = (k_arena.shape[0], k_arena.shape[1],
                                   k_arena.shape[2], v_arena.shape[3])
    T = block_tables.shape[1]
    scale = 1.0 / math.sqrt(D)

    def kv_map(b, t, tab_ref, len_ref):
        # Clamp dead table slots to the last live block: a repeated block
        # index costs no new copy, and the body skips the compute.
        n_live = jax.lax.div(len_ref[b] + block_size - 1, block_size)
        t_eff = jnp.minimum(t, jnp.maximum(n_live - 1, 0))
        return (tab_ref[b, t_eff], 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,  # block_tables, lengths
        grid=(B, T),
        in_specs=[
            pl.BlockSpec((1, H, Hkv * D), lambda b, t, tab, lens: (b, 0, 0)),
            pl.BlockSpec((1, block_size, Hkv * D), kv_map),
            pl.BlockSpec((1, block_size, Hkv * Dv), kv_map),
        ],
        out_specs=pl.BlockSpec((1, H, Hkv * Dv),
                               lambda b, t, tab, lens: (b, 0, 0)),
        scratch_shapes=_scratch(H, Hkv * Dv),
    )
    out = pl.pallas_call(
        functools.partial(_paged_kernel, scale=scale, block_size=block_size),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, Hkv * Dv), q.dtype),
        interpret=interpret,
    )(
        block_tables.astype(jnp.int32),
        lengths.astype(jnp.int32),
        _block_diag_query(q, Hkv),
        k_arena.reshape(n_rows, block_size, Hkv * D),
        v_arena.reshape(n_rows, block_size, Hkv * Dv),
    )
    return _own_head_columns(out, Hkv, Dv)
