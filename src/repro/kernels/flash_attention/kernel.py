"""Pallas TPU flash attention (GQA, causal) — pl.pallas_call + BlockSpec.

TPU-native design (not a CUDA port):
  * the wrapper moves heads ahead of the sequence, (B, H, S, D), so each
    tile is one head's (rows x D) slab;
  * grid = (B, H, num_q_blocks, num_kv_blocks); the LAST grid dim is
    sequential on TPU, so the online-softmax state (m, l, acc) lives in
    VMEM scratch carried across kv steps of one (b, h, iq) tile;
  * BlockSpecs stream (block_q x D) query tiles and (block_kv x D) KV
    tiles HBM->VMEM; the MXU sees (block_q x D) @ (D x block_kv) and
    (block_q x block_kv) @ (block_kv x Dv) matmuls — block sizes default
    to 128 to match the 128x128 systolic array;
  * GQA is resolved in the index_map (kv head = q head // group), so no
    KV duplication ever materializes;
  * causal tiles below the diagonal are skipped with pl.when (work
    skipped, not masked), the diagonal tile uses an iota mask.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["flash_attention_fwd"]

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *, scale,
            causal, block_q, block_kv, seq_kv):
    iq = pl.program_id(2)
    ikv = pl.program_id(3)
    n_kv = pl.num_programs(3)

    @pl.when(ikv == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_start = iq * block_q
    kv_start = ikv * block_kv

    def _step():
        q = q_ref[0, 0].astype(jnp.float32) * scale          # (bq, D)
        k = k_ref[0, 0].astype(jnp.float32)                  # (bkv, D)
        v = v_ref[0, 0].astype(jnp.float32)                  # (bkv, Dv)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                                    # (bq, bkv)
        # Bounds + causal mask on the diagonal tile.
        kv_ids = kv_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = kv_ids < seq_kv
        if causal:
            q_ids = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            mask = mask & (q_ids >= kv_ids)
        s = jnp.where(mask, s, NEG_INF)

        # Softmax statistics stay 2-D (bq, 1): sublane-major columns.
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + p.sum(axis=1, keepdims=True)
        pv = jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        acc_ref[...] = acc_ref[...] * corr + pv
        m_ref[...] = m_new

    if causal:
        # Skip tiles strictly above the causal frontier (work elided,
        # not just masked — the big win for long-context prefill).
        pl.when(kv_start <= q_start + block_q - 1)(_step)
    else:
        _step()

    @pl.when(ikv == n_kv - 1)
    def _finish():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


def flash_attention_fwd(
    q: jax.Array,   # (B, Sq, H, D)
    k: jax.Array,   # (B, Skv, Hkv, D)
    v: jax.Array,   # (B, Skv, Hkv, Dv)
    *,
    causal: bool = True,
    block_q: int = 128,
    block_kv: int = 128,
    interpret: bool = False,
) -> jax.Array:
    B, Sq, H, D = q.shape
    Skv, Hkv, Dv = k.shape[1], k.shape[2], v.shape[3]
    G = H // Hkv
    scale = 1.0 / math.sqrt(D)

    block_q = min(block_q, Sq)
    block_kv = min(block_kv, Skv)
    pad_q = (-Sq) % block_q
    pad_kv = (-Skv) % block_kv
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
    if pad_kv:
        k = jnp.pad(k, ((0, 0), (0, pad_kv), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_kv), (0, 0), (0, 0)))
    Sq_p, Skv_p = Sq + pad_q, Skv + pad_kv
    n_q, n_kv = Sq_p // block_q, Skv_p // block_kv

    # Heads ahead of the sequence: every block's last two dims are then
    # (rows, D) with D the full head dim, the layout the TPU tiles in
    # (8, 128) sublane x lane units. A (1, rows, 1, D) block over the
    # (B, S, H, D) layout would put a size-1 block on the second-minor
    # axis, which the chip's compiler refuses.
    q, k, v = (jnp.swapaxes(t, 1, 2) for t in (q, k, v))

    grid = (B, H, n_q, n_kv)
    kernel = functools.partial(
        _kernel,
        scale=scale,
        causal=causal,
        block_q=block_q,
        block_kv=block_kv,
        seq_kv=Skv,
    )
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec(
                (1, 1, block_q, D), lambda b, h, iq, ikv: (b, h, iq, 0)
            ),
            pl.BlockSpec(
                (1, 1, block_kv, D), lambda b, h, iq, ikv, G=G: (b, h // G, ikv, 0)
            ),
            pl.BlockSpec(
                (1, 1, block_kv, Dv), lambda b, h, iq, ikv, G=G: (b, h // G, ikv, 0)
            ),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, block_q, Dv), lambda b, h, iq, ikv: (b, h, iq, 0)
        ),
        out_shape=jax.ShapeDtypeStruct((B, H, Sq_p, Dv), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),    # m
            pltpu.VMEM((block_q, 1), jnp.float32),    # l
            pltpu.VMEM((block_q, Dv), jnp.float32),   # acc
        ],
        interpret=interpret,
    )(q, k, v)
    return jnp.swapaxes(out, 1, 2)[:, :Sq]
