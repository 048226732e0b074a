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

__all__ = ["decode_attention_fwd", "paged_decode_attention_fwd", "whole_tiles"]

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
# block tables (vLLM-style), and decode reads only each sequence's LIVE
# blocks: traffic and FLOPs scale with live tokens, not with
# n_slots * max_len.
#
# The arena is read in its own layout (num_blocks + 1, block_size, Hkv,
# D). Viewing it as (..., Hkv * D) is not free on the TPU: a 2-wide
# second-minor dim is tiled (2, 128), and that reshape copies the whole
# arena on every call.
#
# Where a block is whole tiles of that layout (D a multiple of 128 lanes,
# Hkv a power of two of at least one 32-bit word's rows), one kernel
# invocation walks every sequence's live blocks in groups of ``pages``
# table slots: the arena stays in HBM and each block is DMA'd into a
# double-buffered VMEM group through the scalar-prefetched block table,
# the next group's copies (the next sequence's first group included) in
# flight while this one computes. Groups past ceil(length / block_size)
# blocks cost neither a copy nor compute; the last group's slots past
# the live blocks repeat the last live block. A group's (rows, Hkv, D)
# K and V are viewed as (rows * Hkv, D), row r's kv head h at
# r * Hkv + h: every query head scores every row and keeps those of its
# own kv head (the block-diagonal query's arithmetic), with one matmul
# for the scores and one for the values.
#
# Other geometries (smollm-135m's 3 kv heads of 64) cannot be sliced out
# of the tiled arena by a DMA. For them a (B, T) grid walks one block a
# step over the arena viewed as (..., Hkv * D), with a block-diagonal
# query: the scalar-prefetched table drives each K/V BlockSpec's
# index_map, dead slots clamp to the last live block (a repeated block
# index is not copied again) and @pl.when skips their compute. The model
# does not call it (``attention.paged_decode_attention`` gathers there).
# ---------------------------------------------------------------------------

#: Table slots per group: 8 blocks of 16 rows = 128 rows a DMA group. On
#: a v5e at the chat cell's geometry 8 was faster than 4 and 16 with 8
#: live lanes of 300-3000 rows, and within 3 % of 16 with 32 live lanes
#: of 1000-2048 (PERF.md).
PAGES_PER_GROUP = 8


def whole_tiles(Hkv: int, D: int, dtype) -> bool:
    """A (block_size, Hkv, D) block is whole tiles of the arena's TPU
    layout, so a DMA can slice it out."""
    packing = 4 // jnp.dtype(dtype).itemsize
    return D % 128 == 0 and Hkv >= packing and Hkv & (Hkv - 1) == 0


def _paged_flat_kernel(tab_ref, len_ref, q_ref, k_hbm, v_hbm, o_ref,
                       k_buf, v_buf, sems, *, scale, block_size, pages):
    B = len_ref.shape[0]
    H = q_ref.shape[1]
    Hkv = k_buf.shape[3]
    rows = pages * block_size
    owner = jax.lax.broadcasted_iota(jnp.int32, (H, 1), 0) // (H // Hkv)
    col = jax.lax.broadcasted_iota(jnp.int32, (1, rows * Hkv), 1)

    def n_blocks(b):
        return jax.lax.div(len_ref[b] + block_size - 1, block_size)

    def start(b, g, slot):
        # Every slot of the group is copied, those past the live blocks
        # repeating the last live one (whose rows the mask drops), so one
        # wait a buffer covers the whole group.
        n = n_blocks(b)
        for j in range(pages):
            bid = tab_ref[b, jnp.minimum(g * pages + j, n - 1)]
            for i, (src, buf) in enumerate(((k_hbm, k_buf), (v_hbm, v_buf))):
                pltpu.make_async_copy(src.at[bid], buf.at[slot, j],
                                      sems.at[i, slot]).start()

    def wait(slot):
        for i, buf in enumerate((k_buf, v_buf)):
            pltpu.make_async_copy(buf.at[slot], buf.at[slot],
                                  sems.at[i, slot]).wait()

    def next_live(b):
        """The first sequence at or after ``b`` with a live row (B if none)."""
        return jax.lax.while_loop(
            lambda x: (x < B) & (len_ref[jnp.minimum(x, B - 1)] == 0),
            lambda x: x + 1, b)

    o_ref[...] = jnp.zeros_like(o_ref)     # sequences with no live row
    first = next_live(0)

    @pl.when(first < B)
    def _():
        start(first, 0, 0)

    def sequence(b, w):
        length = len_ref[b]
        n_groups = jax.lax.div(n_blocks(b) + pages - 1, pages)
        # The jnp path's rounding: q * scale in q's dtype, both MXU
        # operands in the arena's dtype, accumulated in float32.
        q = (q_ref[b] * jnp.asarray(scale, q_ref.dtype)).astype(k_buf.dtype)

        def group(g, carry):
            w, m_prev, l_prev, acc_prev = carry
            slot = w % 2
            more = g + 1 < n_groups
            nb = jnp.where(more, b, next_live(b + 1))

            @pl.when(nb < B)
            def _():
                start(nb, jnp.where(more, g + 1, 0), 1 - slot)

            wait(slot)
            k = k_buf[slot].reshape(rows * Hkv, -1)
            v = v_buf[slot].reshape(rows * Hkv, -1)
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )                                                  # (H, rows*Hkv)
            own = (col % Hkv == owner) & (g * rows + col // Hkv < length)
            s = jnp.where(own, s, NEG_INF)
            m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
            p = jnp.exp(s - m_new)
            corr = jnp.exp(m_prev - m_new)
            pv = jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )                                                  # (H, Dv)
            l_new = l_prev * corr + p.sum(axis=1, keepdims=True)
            return w + 1, m_new, l_new, acc_prev * corr + pv

        init = (w, jnp.full((H, 1), NEG_INF, jnp.float32),
                jnp.zeros((H, 1), jnp.float32),
                jnp.zeros((H, v_buf.shape[-1]), jnp.float32))
        w, _, l, acc = jax.lax.fori_loop(0, n_groups, group, init)

        @pl.when(n_groups > 0)
        def _():
            o_ref[b] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)

        return w

    jax.lax.fori_loop(0, B, sequence, 0)


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

    if whole_tiles(Hkv, D, k_arena.dtype) and whole_tiles(Hkv, Dv, v_arena.dtype):
        pages = min(PAGES_PER_GROUP, T)
        hbm = pl.BlockSpec(memory_space=pltpu.HBM)
        vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,  # block_tables, lengths
            grid=(1,),
            in_specs=[vmem, hbm, hbm],
            out_specs=vmem,
            scratch_shapes=[
                pltpu.VMEM((2, pages, block_size, Hkv, D), k_arena.dtype),
                pltpu.VMEM((2, pages, block_size, Hkv, Dv), v_arena.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),   # (K / V, buffer)
            ],
        )
        kernel = functools.partial(_paged_flat_kernel, scale=scale,
                                   block_size=block_size, pages=pages)
        return pl.pallas_call(
            kernel, grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((B, H, Dv), q.dtype),
            interpret=interpret,
        )(block_tables.astype(jnp.int32), lengths.astype(jnp.int32), q,
          k_arena, v_arena)

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
