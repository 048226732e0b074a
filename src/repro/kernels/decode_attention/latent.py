"""Pallas TPU paged latent decode: MLA's absorbed single-query attention
over a paged latent cache, reading only each sequence's live blocks.

Decode with the W_UK/W_UV absorption attends in latent space: every head
scores the shared latent rows (``ckv``, rank r) and rope keys (``k_rope``)
with its absorbed query, and sums the latent rows by the softmax:

    s = (q_lat . ckv^T + q_rope . k_rope^T) * scale        (H, rows)
    o_lat = softmax(s) . ckv                                (H, r)

There is one kv "head", so no block-diagonal query is needed. The two
arenas, (num_blocks + 1, block_size, r) and (num_blocks + 1, block_size,
rope), are read in their own layout through the scalar-prefetched block
table with the walk of the paged GQA kernel (``kernel.py``): one
invocation visits every sequence's live blocks in groups of
``PAGES_PER_GROUP`` table slots, DMA'd into double-buffered VMEM with
the next group's copies (the next live sequence's first group included)
in flight while this one computes. Slots of the last group past the
live blocks repeat the last live block, and the length mask drops their
rows; a sequence of length 0 reads nothing and attends to zeros.

Rounding is the jnp path's (``attention.mla_apply``'s einsums as XLA's
default precision runs them on the TPU): both MXU operands in the
arena's dtype, float32 accumulation and softmax statistics, the scale
applied to the float32 scores.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .kernel import NEG_INF, PAGES_PER_GROUP

__all__ = ["latent_whole_tiles", "paged_latent_decode", "paged_latent_decode_fwd"]


def latent_whole_tiles(kv_lora_rank: int, block_size: int, dtype) -> bool:
    """A (block_size, kv_lora_rank) latent block is whole tiles of the
    arena's TPU layout ((8 x packing, 128) for a dtype packing ``packing``
    rows in a 32-bit word), so blocks stack into one group in VMEM."""
    packing = 4 // jnp.dtype(dtype).itemsize
    return kv_lora_rank % 128 == 0 and block_size % (8 * packing) == 0


def _paged_latent_kernel(tab_ref, len_ref, ql_ref, qr_ref, c_hbm, r_hbm,
                         o_ref, c_buf, r_buf, sems, *, scale, block_size,
                         pages):
    B = len_ref.shape[0]
    H = ql_ref.shape[1]
    rows = pages * block_size
    col = jax.lax.broadcasted_iota(jnp.int32, (1, rows), 1)
    arenas = ((c_hbm, c_buf), (r_hbm, r_buf))

    def n_blocks(b):
        return jax.lax.div(len_ref[b] + block_size - 1, block_size)

    def start(b, g, slot):
        n = n_blocks(b)
        for j in range(pages):
            bid = tab_ref[b, jnp.minimum(g * pages + j, n - 1)]
            for i, (src, buf) in enumerate(arenas):
                pltpu.make_async_copy(src.at[bid], buf.at[slot, j],
                                      sems.at[i, slot]).start()

    def wait(slot):
        for i, (_, buf) in enumerate(arenas):
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
        q_lat = ql_ref[b].astype(c_buf.dtype)                  # (H, r)
        q_rope = qr_ref[b].astype(r_buf.dtype)                 # (H, rope)

        def group(g, carry):
            w, m_prev, l_prev, acc_prev = carry
            slot = w % 2
            more = g + 1 < n_groups
            nb = jnp.where(more, b, next_live(b + 1))

            @pl.when(nb < B)
            def _():
                start(nb, jnp.where(more, g + 1, 0), 1 - slot)

            wait(slot)
            ckv = c_buf[slot].reshape(rows, -1)
            k_rope = r_buf[slot].reshape(rows, -1)
            nt = (((1,), (1,)), ((), ()))
            s = (jax.lax.dot_general(q_lat, ckv, nt,
                                     preferred_element_type=jnp.float32)
                 + jax.lax.dot_general(q_rope, k_rope, nt,
                                       preferred_element_type=jnp.float32)
                 ) * scale                                     # (H, rows)
            s = jnp.where(g * rows + col < length, s, NEG_INF)
            m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
            p = jnp.exp(s - m_new)
            corr = jnp.exp(m_prev - m_new)
            pv = jax.lax.dot_general(
                p.astype(ckv.dtype), ckv, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )                                                  # (H, r)
            l_new = l_prev * corr + p.sum(axis=1, keepdims=True)
            return w + 1, m_new, l_new, acc_prev * corr + pv

        init = (w, jnp.full((H, 1), NEG_INF, jnp.float32),
                jnp.zeros((H, 1), jnp.float32),
                jnp.zeros((H, c_buf.shape[-1]), jnp.float32))
        w, _, l, acc = jax.lax.fori_loop(0, n_groups, group, init)

        @pl.when(n_groups > 0)
        def _():
            o_ref[b] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)

        return w

    jax.lax.fori_loop(0, B, sequence, 0)


def paged_latent_decode_fwd(
    q_lat: jax.Array,         # (B, H, r) absorbed query
    q_rope: jax.Array,        # (B, H, rope)
    ckv_arena: jax.Array,     # (num_blocks + 1, block_size, r)
    rope_arena: jax.Array,    # (num_blocks + 1, block_size, rope)
    block_tables: jax.Array,  # (B, T) arena indices; 0 = NULL sink block
    lengths: jax.Array,       # (B,) valid prefix length per sequence
    *,
    scale: float,
    interpret: bool = False,
) -> jax.Array:
    """-> o_lat (B, H, r) in ``q_lat``'s dtype: each head's softmax-weighted
    sum of its sequence's live latent rows (zeros for length 0)."""
    B, H, r = q_lat.shape
    # A DMA cannot slice a row narrower than 128 lanes out of the tiled
    # arena (Mosaic refuses the slice): the rope keys, and their query,
    # get zero lanes to whole tiles. That copies the rope arena, an eighth
    # of the latent bytes at Moonlight's widths, once a call.
    pad = -rope_arena.shape[2] % 128
    if pad:
        rope_arena = jnp.pad(rope_arena, ((0, 0), (0, 0), (0, pad)))
        q_rope = jnp.pad(q_rope, ((0, 0), (0, 0), (0, pad)))
    block_size, rope = ckv_arena.shape[1], rope_arena.shape[2]
    pages = min(PAGES_PER_GROUP, block_tables.shape[1])
    hbm = pl.BlockSpec(memory_space=pltpu.HBM)
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,  # block_tables, lengths
        grid=(1,),
        in_specs=[vmem, vmem, hbm, hbm],
        out_specs=vmem,
        scratch_shapes=[
            pltpu.VMEM((2, pages, block_size, r), ckv_arena.dtype),
            pltpu.VMEM((2, pages, block_size, rope), rope_arena.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),   # (ckv / k_rope, buffer)
        ],
    )
    kernel = functools.partial(_paged_latent_kernel, scale=scale,
                               block_size=block_size, pages=pages)
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, r), q_lat.dtype),
        interpret=interpret, name="paged_latent_decode",
    )(block_tables.astype(jnp.int32), lengths.astype(jnp.int32), q_lat,
      q_rope, ckv_arena, rope_arena)


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def paged_latent_decode(q_lat, q_rope, ckv_arena, rope_arena, block_tables,
                        lengths, *, scale: float, interpret: bool = False):
    """MLA's absorbed decode over paged latent arenas: walks only each
    sequence's live blocks via the scalar-prefetched block table."""
    return paged_latent_decode_fwd(
        q_lat, q_rope, ckv_arena, rope_arena, block_tables, lengths,
        scale=scale, interpret=interpret,
    )
