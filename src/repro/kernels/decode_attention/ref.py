"""Oracles: the model stack's own masked single-query attention.

The paged oracle is the exact jnp path the serving engine decodes with
(gather the block-table view, run ``decode_attention``) — so kernel
parity here transitively proves parity with the engine's hot loop.
"""

import jax.numpy as jnp

from repro.models.attention import decode_attention as _model_decode
from repro.models.attention import latent_decode_attention, paged_kv_view


def decode_ref(q, k, v, lengths):
    # model path takes (B, 1, H, D); kernel takes (B, H, D).
    out = _model_decode(q[:, None], k, v, length=lengths)
    return out[:, 0]


def paged_decode_ref(q, k_arena, v_arena, block_tables, lengths):
    """jnp paged decode: contiguous per-sequence views gathered through
    the block table, then the standard masked decode attention. Empty
    sequences (length 0) return zeros, matching the kernel convention
    (the model softmax would spread mass uniformly over garbage there,
    but length 0 never reaches decode — it exists only for tests)."""
    k = paged_kv_view(k_arena, block_tables)
    v = paged_kv_view(v_arena, block_tables)
    out = _model_decode(q[:, None], k, v, length=lengths)[:, 0]
    return jnp.where((lengths > 0)[:, None, None], out, jnp.zeros_like(out))


def paged_latent_ref(q_lat, q_rope, ckv_arena, rope_arena, block_tables,
                     lengths, *, scale):
    """jnp paged latent decode: ``mla_apply``'s absorbed einsums over the
    gathered per-sequence views, (B, H, ...) queries in and o_lat (B, H, r)
    out in float32; length-0 sequences return zeros, as above."""
    out = latent_decode_attention(
        q_lat[:, None], q_rope[:, None], paged_kv_view(ckv_arena, block_tables),
        paged_kv_view(rope_arena, block_tables), lengths, scale=scale)[:, 0]
    return jnp.where((lengths > 0)[:, None, None], out, jnp.zeros_like(out))
