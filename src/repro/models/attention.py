"""Attention: GQA (with rope, qk-norm, bias options) and DeepSeek MLA.

Three execution paths share one set of weights:
  * train/prefill: memory-efficient chunked attention (lax.scan over KV
    chunks with online softmax) — O(seq * chunk) activation memory, which
    is what makes the 32k-prefill cells lowerable; optionally routed to
    the Pallas flash kernel (cfg.use_pallas) on TPU.
  * decode: single-query attention against a KV cache, with optional
    sequence-parallel cache (shard the cache over 'model', merge partial
    softmax statistics with psum — flash-decode style).

KV caches are plain pytrees: {"k": (B, S, Hkv, D), "v": ...} for GQA and
{"ckv": (B, S, r_kv), "k_rope": (B, S, r_qk)} for MLA (the latent cache is
exactly MLA's memory saving).

Serving additionally supports PAGED caches (vLLM-style): each leaf's
(batch, seq) front is replaced by a global block arena
(num_blocks + 1, block_size, ...), and a per-sequence ``block_table``
(B, T) of arena indices says which rows belong to whom. Row 0 of the
arena is the reserved NULL sink: never allocated, it absorbs writes from
masked/dead lanes and backs unallocated table entries, so paged updates
need no per-slot masking. The paged prefill and verify paths gather a
contiguous per-sequence view and run the *same* attention math as the
contiguous paths — aligned geometry (``block_size`` dividing the rounded
``max_len``) makes the views shape- and bit-identical, which is the
token-equivalence contract the serve tests enforce. Paged decode does the
same off the TPU; lowered for the TPU, where a block is whole tiles of the
TPU's layout, it runs a Pallas kernel over each lane's live blocks only
(``paged_decode_attention`` for GQA, ``paged_latent_attention`` for MLA).
"""

from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import MLAConfig, ModelConfig
from .layers import ParamSpec, apply_rope, norm_apply, norm_specs

NEG_INF = -1e30

#: KV cache sequence axes are rounded up to this multiple at allocation
#: time so the flash-decode kernel never pads (= copies) the cache in HBM
#: on the hot path, and so paged block sizes divide the row count evenly.
KV_SEQ_ALIGN = 16

#: Arena row reserved as the write sink for masked/dead lanes and the
#: target of unallocated block-table entries. Never handed out by the
#: BlockManager; its contents are garbage and are never read unmasked.
NULL_BLOCK = 0


def round_kv_len(max_len: int, block: int = KV_SEQ_ALIGN) -> int:
    """Round a cache capacity up to the kernel/paging block multiple."""
    return -(-int(max_len) // block) * block


def paged_kv_view(arena: jax.Array, block_table: jax.Array) -> jax.Array:
    """Gather a contiguous per-sequence view (B, T*block_size, ...) out of
    a block arena (num_blocks+1, block_size, ...) via ``block_table``
    (B, T). Rows past each sequence's length are whatever stale/null
    blocks the table points at — callers mask by length, exactly like the
    contiguous decode paths mask their dead tail rows."""
    g = arena[block_table]  # (B, T, block_size, ...)
    return g.reshape(block_table.shape[0], -1, *arena.shape[2:])


def cache_row_update(
    cache: jax.Array,
    new: jax.Array,
    idx: jax.Array,
    *,
    block_table: Optional[jax.Array] = None,
) -> jax.Array:
    """Write ``new`` (B, S_new, ...) into ``cache`` (B, S, ...) at sequence
    offset ``idx`` — scalar (all rows share one write position: classic
    decode) or per-row ``(B,)`` (slot-pooled serving, where every sequence
    in the batch sits at its own length).

    With ``block_table`` (B, T), ``cache`` is a block arena
    (num_blocks+1, block_size, ...) and the single decode row
    (S_new == 1) is scattered to ``arena[table[b, idx//bs], idx % bs]``.
    Dead lanes carry NULL table entries, so their writes land in the sink
    block — no per-slot masking needed.

    Copy-on-write contract (prefix sharing, DESIGN.md §16): the scatter
    writes blindly through the table, so the CALLER must guarantee every
    targeted block is private (refcount 1) — the pool's
    ``ensure_writable`` forks shared blocks (table swap + device copy)
    before the write reaches here. This function stays fork-oblivious by
    design: forking on the host keeps the jitted scatter shape-stable."""
    new = new.astype(cache.dtype)
    if block_table is not None:
        bs = cache.shape[1]
        B = block_table.shape[0]
        idx = jnp.broadcast_to(jnp.asarray(idx, jnp.int32), (B,))
        bid = jnp.take_along_axis(block_table, (idx // bs)[:, None], axis=1)[:, 0]
        return cache.at[bid, idx % bs].set(new[:, 0])
    if jnp.ndim(idx) == 0:
        return jax.lax.dynamic_update_slice_in_dim(cache, new, idx, axis=1)
    return jax.vmap(
        lambda c, n, i: jax.lax.dynamic_update_slice_in_dim(c, n, i, axis=0)
    )(cache, new, idx)


def cache_rows_update(
    cache: jax.Array,
    new: jax.Array,
    start: jax.Array,
    *,
    block_table: Optional[jax.Array] = None,
    n_valid: Optional[jax.Array] = None,
) -> jax.Array:
    """Bulk prefill write: ``new`` (B, P, ...) rows land at sequence
    positions ``start + [0, P)``. Contiguous caches take one dynamic
    slice update; paged arenas scatter every row through the block table
    (positions whose table entry is still NULL — pad-bucket overhang past
    the reserved blocks — fall into the sink block).

    ``start`` may be per-row ``(B,)`` (speculative verify: every slot
    sits at its own length), in which case the contiguous path switches
    to a scatter whose out-of-bounds rows are DROPPED, never clamped —
    an XLA-clamped write start would silently overwrite valid rows.
    ``n_valid`` (B,) marks how many of the P rows are real per sequence;
    rows past it are dropped (contiguous) or routed to the NULL sink
    (paged), so one fixed-shape verify call can carry ragged per-slot
    draft lengths as data.

    Copy-on-write contract: same as ``cache_row_update`` — callers must
    fork shared blocks in ``[start, start + n_valid)`` first
    (``SlotPool.ensure_writable``). Adopted prefix blocks always sit
    BELOW the write start (prefill resumes after the adopted rows), so
    under the serving engine the only shared row a prefill chunk can
    touch is the full-match re-feed, which forks before the call."""
    new = new.astype(cache.dtype)
    B, P = new.shape[:2]
    start = jnp.asarray(start, jnp.int32)
    if block_table is None:
        if start.ndim == 0 and n_valid is None:
            return jax.lax.dynamic_update_slice_in_dim(cache, new, start, axis=1)
        pos = jnp.broadcast_to(start.reshape(-1, 1), (B, 1)) + jnp.arange(P)
        if n_valid is not None:
            # Out-of-range row index -> scatter-drop.
            pos = jnp.where(jnp.arange(P)[None, :] < n_valid[:, None],
                            pos, cache.shape[1])
        b_idx = jnp.repeat(jnp.arange(B), P)
        rows = new.reshape(B * P, *new.shape[2:])
        return cache.at[b_idx, pos.reshape(-1)].set(rows, mode="drop")
    bs = cache.shape[1]
    if start.ndim == 0:
        pos = start + jnp.arange(P)                   # (P,)
        bid = block_table[:, pos // bs]               # (B, P) gather
        off = jnp.broadcast_to(pos % bs, (B, P))
    else:
        pos = start[:, None] + jnp.arange(P)          # (B, P)
        slot = jnp.clip(pos // bs, 0, block_table.shape[1] - 1)
        bid = jnp.take_along_axis(block_table, slot, axis=1)
        off = pos % bs
    if n_valid is not None:
        # Rows past each sequence's valid count land in the NULL sink.
        bid = jnp.where(jnp.arange(P)[None, :] < n_valid[:, None],
                        bid, NULL_BLOCK)
    rows = new.reshape(B * P, *new.shape[2:])
    return cache.at[bid.reshape(-1), off.reshape(-1)].set(rows)


def decode_lengths(idx: jax.Array, batch: int) -> jax.Array:
    """Valid-prefix lengths (B,) after writing one token at ``idx``."""
    return jnp.broadcast_to(idx + 1, (batch,)).astype(jnp.int32)


# ---------------------------------------------------------------------------
# GQA specs
# ---------------------------------------------------------------------------

def gqa_specs(cfg: ModelConfig) -> Dict[str, ParamSpec]:
    d, h, hkv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    dt = cfg.dtype
    out = {
        "wq": ParamSpec((d, h, hd), ("embed", "heads", "head_dim"), "scaled", dt),
        "wk": ParamSpec((d, hkv, hd), ("embed", "kv_heads", "head_dim"), "scaled", dt),
        "wv": ParamSpec((d, hkv, hd), ("embed", "kv_heads", "head_dim"), "scaled", dt),
        "wo": ParamSpec((h, hd, d), ("heads", "head_dim", "embed"), "scaled", dt),
    }
    if cfg.qkv_bias:
        out["bq"] = ParamSpec((h, hd), ("heads", "head_dim"), "zeros", dt)
        out["bk"] = ParamSpec((hkv, hd), ("kv_heads", "head_dim"), "zeros", dt)
        out["bv"] = ParamSpec((hkv, hd), ("kv_heads", "head_dim"), "zeros", dt)
    if cfg.qk_norm:
        out["q_norm"] = norm_specs(hd, "rmsnorm", dt)
        out["k_norm"] = norm_specs(hd, "rmsnorm", dt)
    return out


def _project_qkv(params: Dict, x: jax.Array, cfg: ModelConfig, positions: jax.Array):
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, params["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, params["wv"])
    if cfg.qkv_bias:
        q = q + params["bq"]
        k = k + params["bk"]
        v = v + params["bv"]
    if cfg.qk_norm:
        q = norm_apply(params["q_norm"], q, "rmsnorm")
        k = norm_apply(params["k_norm"], k, "rmsnorm")
    if cfg.rope_theta > 0:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


# ---------------------------------------------------------------------------
# Memory-efficient chunked attention (pure jnp oracle / baseline path)
# ---------------------------------------------------------------------------

def mea_attention(
    q: jax.Array,          # (B, Sq, H, D)
    k: jax.Array,          # (B, Skv, Hkv, D)
    v: jax.Array,          # (B, Skv, Hkv, D)
    *,
    causal: bool,
    chunk: int,
    q_offset: jax.Array = 0,  # absolute position of q[0]: scalar, or (B,)
                              # per-row starts (speculative verify)
) -> jax.Array:
    """Online-softmax attention, scanned over KV chunks.

    Supports distinct K and V head dims (MLA: qk=192, v=128).
    """
    B, Sq, H, D = q.shape
    Skv, Hkv = k.shape[1], k.shape[2]
    Dv = v.shape[-1]
    G = H // Hkv
    scale = 1.0 / math.sqrt(D)
    qf = (q * scale).astype(jnp.float32).reshape(B, Sq, Hkv, G, D)

    chunk = min(chunk, Skv)
    n_chunks = math.ceil(Skv / chunk)
    pad = n_chunks * chunk - Skv
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    kc = k.astype(jnp.float32).reshape(B, n_chunks, chunk, Hkv, D)
    vc = v.astype(jnp.float32).reshape(B, n_chunks, chunk, Hkv, Dv)

    q_offset = jnp.asarray(q_offset, jnp.int32)
    q_pos = q_offset[..., None] + jnp.arange(Sq)   # (Sq,) or (B, Sq)

    def body(carry, inputs):
        m, l, acc = carry
        kj, vj, j = inputs
        # scores: (B, Sq, Hkv, G, chunk)
        s = jnp.einsum("bqhgd,bchd->bqhgc", qf, kj)
        kv_pos = j * chunk + jnp.arange(chunk)
        valid = kv_pos < Skv
        if causal:
            valid = valid & (q_pos[..., :, None] >= kv_pos)  # (…, Sq, chunk)
            if valid.ndim == 2:
                valid = valid[None]
            s = jnp.where(valid[:, :, None, None, :], s, NEG_INF)
        else:
            s = jnp.where(valid[None, None, None, None, :], s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(axis=-1)
        acc_new = acc * corr[..., None] + jnp.einsum("bqhgc,bchd->bqhgd", p, vj)
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((B, Sq, Hkv, G), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, Sq, Hkv, G), jnp.float32)
    acc0 = jnp.zeros((B, Sq, Hkv, G, Dv), jnp.float32)
    kc_t = jnp.moveaxis(kc, 1, 0)
    vc_t = jnp.moveaxis(vc, 1, 0)
    # Remat each chunk: backward recomputes the (B,Sq,H,chunk) score tile
    # instead of saving it — the chunked-attention memory win would
    # otherwise be lost to autodiff residuals (flash-attention recompute).
    (m, l, acc), _ = jax.lax.scan(
        jax.checkpoint(body), (m0, l0, acc0), (kc_t, vc_t, jnp.arange(n_chunks))
    )
    out = acc / jnp.maximum(l[..., None], 1e-30)
    return out.reshape(B, Sq, H, Dv).astype(q.dtype)


def decode_attention(
    q: jax.Array,          # (B, 1, H, D)
    k: jax.Array,          # (B, S, Hkv, D) — cache incl. current token
    v: jax.Array,
    *,
    length: Optional[jax.Array] = None,  # valid prefix length per batch elt
) -> jax.Array:
    """Single-token attention against the full cache (decode hot path)."""
    B, _, H, D = q.shape
    S, Hkv = k.shape[1], k.shape[2]
    G = H // Hkv
    scale = 1.0 / math.sqrt(D)
    qf = (q * scale).astype(jnp.float32).reshape(B, Hkv, G, D)
    s = jnp.einsum("bhgd,bshd->bhgs", qf, k.astype(jnp.float32))
    if length is not None:
        pos = jnp.arange(S)
        s = jnp.where(pos[None, None, None, :] < length[:, None, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhgs,bshd->bhgd", p, v.astype(jnp.float32))
    return out.reshape(B, 1, H, D).astype(q.dtype)


def paged_decode_path(n_kv_heads: int, head_dim: int, dtype, platform: str) -> str:
    """How paged GQA decode over arenas of ``n_kv_heads`` heads of
    ``head_dim`` in ``dtype`` reads them on a device of ``platform``: the
    Pallas kernel over live blocks ("paged_kernel") on the TPU, where a
    block is whole tiles of its layout, else the capacity-sized gathered
    view ("gather")."""
    from repro.kernels.decode_attention.kernel import whole_tiles

    fits = whole_tiles(n_kv_heads, head_dim, dtype)
    return "paged_kernel" if platform == "tpu" and fits else "gather"


def _paged_decode_gather(q, ck, cv, block_table, lengths):
    """Decode attention over the gathered (B, T * block_size) view: the
    contiguous path's exact arithmetic, so paged and contiguous tokens
    stay byte-identical."""
    return decode_attention(q, paged_kv_view(ck, block_table),
                            paged_kv_view(cv, block_table), length=lengths)


def _paged_decode_kernel(q, ck, cv, block_table, lengths, *, interpret=False):
    """The Pallas paged flash-decode: reads only each lane's live blocks,
    in the arena's own layout (``kernels/decode_attention``). A lane whose
    table starts at the NULL block holds no rows (the engine's lanes that
    are not decoding): it reads nothing and attends to zeros."""
    from repro.kernels.decode_attention import paged_flash_decode

    lengths = jnp.where(block_table[:, 0] == NULL_BLOCK, 0, lengths)
    out = paged_flash_decode(q[:, 0], ck, cv, block_table, lengths,
                             interpret=interpret)
    return out[:, None]


_PAGED_DECODE = {"paged_kernel": _paged_decode_kernel, "gather": _paged_decode_gather}


def paged_decode_attention(q, ck, cv, block_table, lengths):
    """Single-query attention of each lane over its paged KV, read as
    ``paged_decode_path`` says for the platform the program is lowered
    for: off the TPU, always the gathered view."""
    tpu = paged_decode_path(ck.shape[-2], ck.shape[-1], ck.dtype, "tpu")
    return jax.lax.platform_dependent(
        q, ck, cv, block_table, lengths,
        default=_paged_decode_gather, tpu=_PAGED_DECODE[tpu],
    )


def gqa_apply(
    params: Dict,
    x: jax.Array,
    cfg: ModelConfig,
    *,
    positions: jax.Array,
    cache: Optional[Dict] = None,
    cache_index: Optional[jax.Array] = None,
    block_table: Optional[jax.Array] = None,
) -> Tuple[jax.Array, Optional[Dict]]:
    """Full GQA block. With a cache, runs one-token decode and returns the
    updated cache; without, runs train/prefill chunked attention. With a
    ``block_table`` the cache leaves are paged arenas and decode attends
    through ``paged_decode_attention``: the live blocks on the TPU where
    the kernel fits, the gathered per-sequence view (same math, same bits
    as the contiguous path) elsewhere."""
    q, k, v = _project_qkv(params, x, cfg, positions)
    if cache is None:
        causal = cfg.causal and not cfg.is_encoder
        if cfg.use_pallas:
            # The Pallas flash kernel compiles for the TPU only; on any
            # other backend the call raises rather than quietly running
            # the kernel's interpreter.
            from repro.kernels.flash_attention import flash_attention

            out = flash_attention(q, k, v, causal=causal)
        else:
            out = mea_attention(q, k, v, causal=causal, chunk=cfg.attn_chunk)
        new_cache = None
    else:
        idx = cache_index  # int32 write position: scalar or per-row (B,)
        ck = cache_row_update(cache["k"], k, idx, block_table=block_table)
        cv = cache_row_update(cache["v"], v, idx, block_table=block_table)
        lengths = decode_lengths(idx, x.shape[0])
        if block_table is not None:
            out = paged_decode_attention(q, ck, cv, block_table, lengths)
        else:
            out = decode_attention(q, ck, cv, length=lengths)
        new_cache = {"k": ck, "v": cv}
    y = jnp.einsum("bshk,hkd->bsd", out, params["wo"])
    return y, new_cache


def gqa_prefill(
    params: Dict,
    x: jax.Array,
    cfg: ModelConfig,
    *,
    positions: jax.Array,
    cache: Dict,
    start_index: jax.Array,
    block_table: Optional[jax.Array] = None,
    n_valid: Optional[jax.Array] = None,
) -> Tuple[jax.Array, Dict]:
    """Cache-writing batched prefill: project the whole (B, S) chunk once,
    write its K/V rows at ``start_index``, and attend causally against the
    cache (rows past the chunk are masked by causality, rows before it are
    an earlier chunk's prefix — chunked-prefill continuation is free).
    Paged mode scatters the chunk's rows through the block table (bulk
    block writes) and attends against the gathered view. ``start_index``
    may be per-row (B,) with ``n_valid`` marking each row's real token
    count (speculative verify; see ``cache_rows_update``)."""
    q, k, v = _project_qkv(params, x, cfg, positions)
    ck = cache_rows_update(cache["k"], k, start_index,
                           block_table=block_table, n_valid=n_valid)
    cv = cache_rows_update(cache["v"], v, start_index,
                           block_table=block_table, n_valid=n_valid)
    if block_table is not None:
        kv_k, kv_v = paged_kv_view(ck, block_table), paged_kv_view(cv, block_table)
    else:
        kv_k, kv_v = ck, cv
    out = mea_attention(
        q, kv_k, kv_v, causal=True, chunk=cfg.attn_chunk, q_offset=start_index
    )
    y = jnp.einsum("bshk,hkd->bsd", out, params["wo"])
    return y, {"k": ck, "v": cv}


def gqa_cache_spec(
    cfg: ModelConfig,
    batch: int,
    max_len: int,
    page: Optional[Tuple[int, int]] = None,
) -> Dict[str, ParamSpec]:
    """``page=(num_blocks, block_size)`` swaps the per-slot (batch, seq)
    stripe for a global arena (num_blocks + 1, block_size, ...) — one
    extra row for the NULL sink block."""
    if page is not None:
        num_blocks, block_size = page
        shape = (num_blocks + 1, block_size, cfg.n_kv_heads, cfg.head_dim)
        axes = ("kv_blocks", "kv_block", "kv_heads", "head_dim")
    else:
        shape = (batch, max_len, cfg.n_kv_heads, cfg.head_dim)
        axes = ("act_batch", "act_kv_seq", "kv_heads", "head_dim")
    return {
        "k": ParamSpec(shape, axes, "zeros", cfg.dtype),
        "v": ParamSpec(shape, axes, "zeros", cfg.dtype),
    }


# ---------------------------------------------------------------------------
# DeepSeek MLA
# ---------------------------------------------------------------------------

def mla_specs(cfg: ModelConfig) -> Dict[str, ParamSpec]:
    m: MLAConfig = cfg.mla
    d, h = cfg.d_model, cfg.n_heads
    dt = cfg.dtype
    qk_dim = m.qk_nope_head_dim + m.qk_rope_head_dim
    if m.q_lora_rank is None:
        # no query LoRA (Moonlight, DeepSeek-V2-Lite): one direct projection
        q = {"wq": ParamSpec((d, h, qk_dim), ("embed", "heads", "head_dim"), "scaled", dt)}
    else:
        q = {
            "wq_a": ParamSpec((d, m.q_lora_rank), ("embed", "q_lora"), "scaled", dt),
            "q_norm": norm_specs(m.q_lora_rank, "rmsnorm", dt),
            "wq_b": ParamSpec(
                (m.q_lora_rank, h, qk_dim), ("q_lora", "heads", "head_dim"), "scaled", dt
            ),
        }
    return {
        **q,
        "wkv_a": ParamSpec(
            (d, m.kv_lora_rank + m.qk_rope_head_dim), ("embed", "kv_lora"), "scaled", dt
        ),
        "kv_norm": norm_specs(m.kv_lora_rank, "rmsnorm", dt),
        "wkv_b": ParamSpec(
            (m.kv_lora_rank, h, m.qk_nope_head_dim + m.v_head_dim),
            ("kv_lora", "heads", "head_dim"),
            "scaled",
            dt,
        ),
        "wo": ParamSpec((h, m.v_head_dim, d), ("heads", "head_dim", "embed"), "scaled", dt),
    }


def _mla_qkv(params: Dict, x: jax.Array, cfg: ModelConfig, positions: jax.Array):
    m: MLAConfig = cfg.mla
    # Query path.
    if m.q_lora_rank is None:
        q = jnp.einsum("bsd,dhk->bshk", x, params["wq"])
    else:
        q_lat = norm_apply(params["q_norm"], jnp.einsum("bsd,dr->bsr", x, params["wq_a"]), "rmsnorm")
        q = jnp.einsum("bsr,rhk->bshk", q_lat, params["wq_b"])
    q_nope, q_rope = jnp.split(q, [m.qk_nope_head_dim], axis=-1)
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta)
    # Latent KV path.
    ckv_full = jnp.einsum("bsd,dr->bsr", x, params["wkv_a"])
    ckv, k_rope = jnp.split(ckv_full, [m.kv_lora_rank], axis=-1)
    ckv = norm_apply(params["kv_norm"], ckv, "rmsnorm")
    k_rope = apply_rope(k_rope[:, :, None, :], positions, cfg.rope_theta)  # (B,S,1,r)
    return q_nope, q_rope, ckv, k_rope


def _mla_expand_kv(params: Dict, ckv: jax.Array, cfg: ModelConfig):
    m: MLAConfig = cfg.mla
    kv = jnp.einsum("bsr,rhk->bshk", ckv, params["wkv_b"])
    k_nope, v = jnp.split(kv, [m.qk_nope_head_dim], axis=-1)
    return k_nope, v


def latent_decode_attention(q_lat, q_rope, c_ckv, c_rope, length, *, scale):
    """MLA's absorbed single-query attention over a contiguous latent view:
    q_lat (B, 1, H, r) and q_rope (B, 1, H, rope) against c_ckv (B, S, r)
    and c_rope (B, S, rope), rows past ``length`` (B,) masked -> o_lat
    (B, 1, H, r) in float32."""
    S = c_ckv.shape[1]
    pos_mask = jnp.arange(S)[None, :] < length[:, None]
    s = (
        jnp.einsum("bshr,btr->bhst", q_lat.astype(jnp.float32), c_ckv.astype(jnp.float32))
        + jnp.einsum("bshk,btk->bhst", q_rope.astype(jnp.float32), c_rope.astype(jnp.float32))
    ) * scale
    s = jnp.where(pos_mask[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhst,btr->bshr", p, c_ckv.astype(jnp.float32))


def paged_latent_path(kv_lora_rank: int, dtype, block_size: int, platform: str) -> str:
    """How absorbed MLA decode over paged latent arenas of rank
    ``kv_lora_rank`` in ``dtype``, in blocks of ``block_size`` rows, reads
    them on a device of ``platform``: the Pallas kernel over live blocks
    ("latent_kernel") on the TPU, where a block is whole tiles of its
    layout, else the capacity-sized gathered view ("latent_gather")."""
    from repro.kernels.decode_attention.latent import latent_whole_tiles

    fits = latent_whole_tiles(kv_lora_rank, block_size, dtype)
    return "latent_kernel" if platform == "tpu" and fits else "latent_gather"


def _paged_latent_gather(q_lat, q_rope, ckv, k_rope, block_table, lengths, *, scale):
    """Absorbed decode over the gathered (B, T * block_size) latent view:
    the contiguous path's exact arithmetic."""
    return latent_decode_attention(
        q_lat, q_rope, paged_kv_view(ckv, block_table),
        paged_kv_view(k_rope, block_table), lengths, scale=scale,
    ).astype(q_lat.dtype)


def _paged_latent_kernel(q_lat, q_rope, ckv, k_rope, block_table, lengths, *,
                         scale, interpret=False):
    """The Pallas paged latent decode: reads only each lane's live blocks of
    both arenas (``kernels/decode_attention/latent.py``). A lane whose
    table starts at the NULL block holds no rows: it reads nothing and its
    o_lat is zeros."""
    from repro.kernels.decode_attention import paged_latent_decode

    lengths = jnp.where(block_table[:, 0] == NULL_BLOCK, 0, lengths)
    out = paged_latent_decode(q_lat[:, 0], q_rope[:, 0], ckv, k_rope,
                              block_table, lengths, scale=scale,
                              interpret=interpret)
    return out[:, None]


_PAGED_LATENT = {"latent_kernel": _paged_latent_kernel,
                 "latent_gather": _paged_latent_gather}


def paged_latent_attention(q_lat, q_rope, ckv, k_rope, block_table, lengths, *, scale):
    """Absorbed MLA decode of each lane over its paged latent cache, read
    as ``paged_latent_path`` says for the platform the program is lowered
    for: off the TPU, always the gathered view. -> o_lat (B, 1, H, r) in
    ``q_lat``'s dtype."""
    tpu = paged_latent_path(ckv.shape[-1], ckv.dtype, ckv.shape[1], "tpu")
    return jax.lax.platform_dependent(
        q_lat, q_rope, ckv, k_rope, block_table, lengths,
        default=lambda *a: _paged_latent_gather(*a, scale=scale),
        tpu=lambda *a: _PAGED_LATENT[tpu](*a, scale=scale),
    )


def mla_apply(
    params: Dict,
    x: jax.Array,
    cfg: ModelConfig,
    *,
    positions: jax.Array,
    cache: Optional[Dict] = None,
    cache_index: Optional[jax.Array] = None,
    absorb: bool = False,
    block_table: Optional[jax.Array] = None,
) -> Tuple[jax.Array, Optional[Dict]]:
    """MLA attention. ``absorb=True`` runs decode in latent space (the
    W_UK/W_UV absorption trick) — a §Perf optimization, baseline expands."""
    m: MLAConfig = cfg.mla
    q_nope, q_rope, ckv, k_rope = _mla_qkv(params, x, cfg, positions)
    B = x.shape[0]

    if cache is None:
        k_nope, v = _mla_expand_kv(params, ckv, cfg)
        H = cfg.n_heads
        k_rope_b = jnp.broadcast_to(k_rope, (*k_rope.shape[:2], H, m.qk_rope_head_dim))
        q_full = jnp.concatenate([q_nope, q_rope], axis=-1)
        k_full = jnp.concatenate([k_nope, k_rope_b], axis=-1)
        out = mea_attention(q_full, k_full, v, causal=True, chunk=cfg.attn_chunk)
        y = jnp.einsum("bshk,hkd->bsd", out, params["wo"])
        return y, None

    # Decode: cache holds the LATENT stream (B, S, r_kv) + rope keys.
    idx = cache_index
    new_cache = {
        "ckv": cache_row_update(cache["ckv"], ckv, idx, block_table=block_table),
        "k_rope": cache_row_update(
            cache["k_rope"], k_rope[:, :, 0, :], idx, block_table=block_table
        ),
    }
    length = decode_lengths(idx, B)

    if absorb:
        # q_nope absorbed through W_UK: scores in latent space, rank r_kv.
        wkv_b = params["wkv_b"]  # (r, H, nope+v)
        w_uk = wkv_b[:, :, : m.qk_nope_head_dim]      # (r, H, nope)
        w_uv = wkv_b[:, :, m.qk_nope_head_dim:]       # (r, H, v)
        q_lat = jnp.einsum("bshk,rhk->bshr", q_nope, w_uk)  # (B,1,H,r)
        scale = 1.0 / math.sqrt(m.qk_nope_head_dim + m.qk_rope_head_dim)
        if block_table is not None:
            o_lat = paged_latent_attention(
                q_lat, q_rope, new_cache["ckv"], new_cache["k_rope"],
                block_table, length, scale=scale)
        else:
            o_lat = latent_decode_attention(
                q_lat, q_rope, new_cache["ckv"], new_cache["k_rope"], length,
                scale=scale)
        out = jnp.einsum("bshr,rhk->bshk", o_lat.astype(x.dtype), w_uv)
    else:
        # Baseline: expand the whole latent cache to per-head K/V each step.
        if block_table is not None:
            c_ckv = paged_kv_view(new_cache["ckv"], block_table)
            c_rope = paged_kv_view(new_cache["k_rope"], block_table)
        else:
            c_ckv, c_rope = new_cache["ckv"], new_cache["k_rope"]
        S = c_ckv.shape[1]
        pos_mask = jnp.arange(S)[None, :] < length[:, None]
        k_nope, v = _mla_expand_kv(params, c_ckv, cfg)
        H = cfg.n_heads
        k_rope_b = jnp.broadcast_to(
            c_rope[:, :, None, :], (B, S, H, m.qk_rope_head_dim)
        )
        q_full = jnp.concatenate([q_nope, q_rope], axis=-1)
        k_full = jnp.concatenate([k_nope, k_rope_b], axis=-1)
        scale = 1.0 / math.sqrt(q_full.shape[-1])
        s = jnp.einsum(
            "bshk,bthk->bhst", (q_full * scale).astype(jnp.float32), k_full.astype(jnp.float32)
        )
        s = jnp.where(pos_mask[:, None, None, :], s, NEG_INF)
        p = jax.nn.softmax(s, axis=-1)
        out = jnp.einsum("bhst,bthk->bshk", p, v.astype(jnp.float32)).astype(x.dtype)

    y = jnp.einsum("bshk,hkd->bsd", out, params["wo"])
    return y, new_cache


def mla_prefill(
    params: Dict,
    x: jax.Array,
    cfg: ModelConfig,
    *,
    positions: jax.Array,
    cache: Dict,
    start_index: jax.Array,
    block_table: Optional[jax.Array] = None,
    n_valid: Optional[jax.Array] = None,
) -> Tuple[jax.Array, Dict]:
    """Cache-writing batched MLA prefill: write the latent stream for the
    whole chunk, then attend via the expanded path (see ``gqa_prefill``)."""
    m: MLAConfig = cfg.mla
    q_nope, q_rope, ckv, k_rope = _mla_qkv(params, x, cfg, positions)
    new_cache = {
        "ckv": cache_rows_update(
            cache["ckv"], ckv, start_index,
            block_table=block_table, n_valid=n_valid,
        ),
        "k_rope": cache_rows_update(
            cache["k_rope"], k_rope[:, :, 0, :], start_index,
            block_table=block_table, n_valid=n_valid,
        ),
    }
    if block_table is not None:
        c_ckv = paged_kv_view(new_cache["ckv"], block_table)
        c_rope = paged_kv_view(new_cache["k_rope"], block_table)
    else:
        c_ckv, c_rope = new_cache["ckv"], new_cache["k_rope"]
    k_nope, v = _mla_expand_kv(params, c_ckv, cfg)
    B, S, H = x.shape[0], c_ckv.shape[1], cfg.n_heads
    k_rope_b = jnp.broadcast_to(
        c_rope[:, :, None, :], (B, S, H, m.qk_rope_head_dim)
    )
    q_full = jnp.concatenate([q_nope, q_rope], axis=-1)
    k_full = jnp.concatenate([k_nope, k_rope_b], axis=-1)
    out = mea_attention(
        q_full, k_full, v, causal=True, chunk=cfg.attn_chunk, q_offset=start_index
    )
    y = jnp.einsum("bshk,hkd->bsd", out, params["wo"])
    return y, new_cache


def mla_cache_spec(
    cfg: ModelConfig,
    batch: int,
    max_len: int,
    page: Optional[Tuple[int, int]] = None,
) -> Dict[str, ParamSpec]:
    m: MLAConfig = cfg.mla
    if page is not None:
        num_blocks, block_size = page
        front, axes2 = (num_blocks + 1, block_size), ("kv_blocks", "kv_block")
    else:
        front, axes2 = (batch, max_len), ("act_batch", "act_kv_seq")
    return {
        "ckv": ParamSpec(
            (*front, m.kv_lora_rank), (*axes2, None), "zeros", cfg.dtype
        ),
        "k_rope": ParamSpec(
            (*front, m.qk_rope_head_dim), (*axes2, None), "zeros", cfg.dtype
        ),
    }
