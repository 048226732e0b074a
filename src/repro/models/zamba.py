"""Zamba2-style hybrid: Mamba2 backbone + one SHARED attention block.

Faithful structure (arXiv:2405.16712 / 2411.15242, simplified where noted):
  * n_layers Mamba2 blocks form the backbone;
  * ONE shared transformer block (attention + MLP over width 2*d_model,
    fed concat([hidden, original_embedding])) is invoked every
    ``attn_every`` Mamba blocks — weights shared across invocations;
  * each invocation gets its own LoRA adapters on the attention input
    projection and the MLP input projection (Zamba2's trick to
    de-correlate reused weights at negligible parameter cost);
  * the shared block's output is projected back to d_model and added to
    the residual stream.

Simplifications (DESIGN.md §6): rotary attention inside the shared block
(Zamba2 does the same), single shared block (1.2B variant), LoRA rank
fixed at 64 on two projections (Zamba2 adapts every linear).
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.dist.sharding import constrain_batch
from . import attention as attn
from . import mamba2
from .layers import ParamSpec, activation, norm_apply, norm_specs

__all__ = [
    "zamba_specs",
    "zamba_apply",
    "zamba_decode",
    "zamba_cache_specs",
    "n_shared_invocations",
]

LORA_RANK = 64


def n_shared_invocations(cfg: ModelConfig) -> int:
    return cfg.n_layers // cfg.attn_every


def _shared_width(cfg: ModelConfig) -> int:
    return 2 * cfg.d_model


def zamba_specs(cfg: ModelConfig) -> Dict[str, Any]:
    d, dt = cfg.d_model, cfg.dtype
    dw = _shared_width(cfg)
    n_inv = n_shared_invocations(cfg)
    h, hd = cfg.n_heads, dw // cfg.n_heads

    mamba_single = {
        "norm": norm_specs(d, cfg.norm, dt),
        "mixer": mamba2.mamba2_specs(cfg),
    }
    mamba_stack = jax.tree.map(
        lambda s: ParamSpec(
            (cfg.n_layers, *s.shape), ("layers", *s.axes), s.init, s.dtype, s.scale
        ),
        mamba_single,
        is_leaf=lambda x: isinstance(x, ParamSpec),
    )

    shared = {
        "norm": norm_specs(dw, cfg.norm, dt),
        "wqkv": ParamSpec(
            (dw, 3, h, hd), ("embed", None, "heads", "head_dim"), "scaled", dt
        ),
        "wo": ParamSpec((h, hd, dw), ("heads", "head_dim", "embed"), "scaled", dt),
        "mlp_norm": norm_specs(dw, cfg.norm, dt),
        "w_in": ParamSpec((dw, cfg.d_ff), ("embed", "ffn"), "scaled", dt),
        "w_gate": ParamSpec((dw, cfg.d_ff), ("embed", "ffn"), "scaled", dt),
        "w_out": ParamSpec((cfg.d_ff, dw), ("ffn", "embed"), "scaled", dt),
        "proj_down": ParamSpec((dw, d), ("embed", None), "scaled", dt),
        # Per-invocation LoRA adapters (stacked over invocations).
        "lora_qkv_a": ParamSpec((n_inv, dw, LORA_RANK), ("layers", "embed", None), "scaled", dt),
        "lora_qkv_b": ParamSpec((n_inv, LORA_RANK, 3 * h * hd), ("layers", None, None), "zeros", dt),
        "lora_mlp_a": ParamSpec((n_inv, dw, LORA_RANK), ("layers", "embed", None), "scaled", dt),
        "lora_mlp_b": ParamSpec((n_inv, LORA_RANK, cfg.d_ff), ("layers", None, None), "zeros", dt),
    }
    return {"mamba": mamba_stack, "shared": shared}


def _shared_block(
    params: Dict,
    h: jax.Array,
    x0: jax.Array,
    cfg: ModelConfig,
    lora: Dict,
    *,
    positions: jax.Array,
    cache: Optional[Dict] = None,
    cache_index: Optional[jax.Array] = None,
    block_table: Optional[jax.Array] = None,
):
    """One invocation of the shared attention+MLP block. ``lora`` holds
    this invocation's adapters (already sliced from the stacks)."""
    dw = _shared_width(cfg)
    H, hd = cfg.n_heads, dw // cfg.n_heads
    t = jnp.concatenate([h, x0], axis=-1)
    tn = norm_apply(params["norm"], t, cfg.norm, cfg.rms_norm_eps)

    qkv = jnp.einsum("bsd,dchk->bschk", tn, params["wqkv"])
    lora_in = jnp.einsum("bsd,dr->bsr", tn, lora["qkv_a"])
    qkv = qkv + jnp.einsum("bsr,re->bse", lora_in, lora["qkv_b"]).reshape(
        *tn.shape[:2], 3, H, hd
    )
    q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
    q = attn.apply_rope(q, positions, cfg.rope_theta)
    k = attn.apply_rope(k, positions, cfg.rope_theta)

    new_cache = None
    if cache is None:
        o = attn.mea_attention(q, k, v, causal=True, chunk=cfg.attn_chunk)
    else:
        ck = attn.cache_row_update(cache["k"], k, cache_index, block_table=block_table)
        cv = attn.cache_row_update(cache["v"], v, cache_index, block_table=block_table)
        if block_table is not None:
            kv_k = attn.paged_kv_view(ck, block_table)
            kv_v = attn.paged_kv_view(cv, block_table)
        else:
            kv_k, kv_v = ck, cv
        o = attn.decode_attention(
            q, kv_k, kv_v, length=attn.decode_lengths(cache_index, h.shape[0])
        )
        new_cache = {"k": ck, "v": cv}
    t = t + jnp.einsum("bshk,hkd->bsd", o, params["wo"])

    tn = norm_apply(params["mlp_norm"], t, cfg.norm, cfg.rms_norm_eps)
    gate = jnp.einsum("bsd,df->bsf", tn, params["w_gate"])
    up = jnp.einsum("bsd,df->bsf", tn, params["w_in"])
    up = up + jnp.einsum(
        "bsr,rf->bsf",
        jnp.einsum("bsd,dr->bsr", tn, lora["mlp_a"]),
        lora["mlp_b"],
    )
    t = t + jnp.einsum("bsf,fd->bsd", activation(cfg.act)(gate) * up, params["w_out"])
    return jnp.einsum("bsd,de->bse", t, params["proj_down"]), new_cache


def _lora_slice(shared: Dict, idx) -> Dict:
    return {
        "qkv_a": shared["lora_qkv_a"][idx],
        "qkv_b": shared["lora_qkv_b"][idx],
        "mlp_a": shared["lora_mlp_a"][idx],
        "mlp_b": shared["lora_mlp_b"][idx],
    }


def zamba_apply(
    params: Dict, x: jax.Array, cfg: ModelConfig, *, positions: jax.Array
) -> Tuple[jax.Array, jax.Array]:
    """Training/prefill forward: scan over (attn_every mamba blocks +
    one shared-block invocation) groups with per-block remat — small HLO,
    activation memory O(layers) block inputs only."""
    x0 = x
    h = x
    ae = cfg.attn_every or cfg.n_layers
    groups = n_shared_invocations(cfg) if cfg.attn_every else 0
    rem = cfg.n_layers - groups * ae

    def mamba_block(layer, h):
        hn = norm_apply(layer["norm"], h, cfg.norm, cfg.rms_norm_eps)
        return constrain_batch(h + mamba2.mamba2_apply(layer["mixer"], hn, cfg))

    def shared_block(shared, lora, h):
        delta, _ = _shared_block(
            shared, h, x0, cfg, lora, positions=positions
        )
        return constrain_batch(h + delta)

    remat = jax.checkpoint if cfg.remat != "none" else (lambda f: f)
    mamba_block_r = remat(mamba_block)
    shared_block_r = remat(shared_block)

    if groups:
        grouped = jax.tree.map(
            lambda t: t[: groups * ae].reshape(groups, ae, *t.shape[1:]),
            params["mamba"],
        )
        lora_stack = _lora_slice(params["shared"], slice(None))

        def group_fn(carry, xs):
            layers6, lora = xs
            def layer_fn(hh, lp):
                return mamba_block_r(lp, hh), None
            hh, _ = jax.lax.scan(layer_fn, carry, layers6)
            hh = shared_block_r(params["shared"], lora, hh)
            return hh, None

        h, _ = jax.lax.scan(group_fn, h, (grouped, lora_stack))

    for i in range(cfg.n_layers - rem, cfg.n_layers):
        layer = jax.tree.map(lambda t: t[i], params["mamba"])
        h = mamba_block_r(layer, h)
    return h, jnp.zeros((), jnp.float32)


def zamba_decode(
    params: Dict,
    x: jax.Array,
    cfg: ModelConfig,
    caches: Dict,
    *,
    positions: jax.Array,
    cache_index: jax.Array,
    block_tables: Optional[jax.Array] = None,
) -> Tuple[jax.Array, Dict]:
    x0 = x
    h = x
    inv = 0
    new_mamba_states = []
    new_attn_caches = []
    for i in range(cfg.n_layers):
        layer = jax.tree.map(lambda t: t[i], params["mamba"])
        state = jax.tree.map(lambda t: t[i], caches["mamba"])
        hn = norm_apply(layer["norm"], h, cfg.norm, cfg.rms_norm_eps)
        delta, new_state = mamba2.mamba2_decode(layer["mixer"], hn, cfg, state)
        h = h + delta
        new_mamba_states.append(new_state)
        if cfg.attn_every and (i + 1) % cfg.attn_every == 0:
            if inv < n_shared_invocations(cfg):
                cache = jax.tree.map(lambda t: t[inv], caches["attn"])
                delta, new_cache = _shared_block(
                    params["shared"], h, x0, cfg, _lora_slice(params["shared"], inv),
                    positions=positions, cache=cache, cache_index=cache_index,
                    block_table=block_tables,
                )
                h = h + delta
                new_attn_caches.append(new_cache)
                inv += 1
    stack = lambda trees: jax.tree.map(lambda *ts: jnp.stack(ts), *trees)
    return h, {"mamba": stack(new_mamba_states), "attn": stack(new_attn_caches)}


def zamba_cache_specs(
    cfg: ModelConfig,
    batch: int,
    max_len: int,
    page: Optional[Tuple[int, int]] = None,
) -> Dict:
    """Hybrid cache: recurrent Mamba2 states stay contiguous per-slot in
    every mode (no sequence axis to page); only the shared block's KV
    rows move into a block arena when ``page=(num_blocks, block_size)``
    is given — one arena row per (invocation, block)."""
    n_inv = n_shared_invocations(cfg)
    dw = _shared_width(cfg)
    hd = dw // cfg.n_heads
    mamba_state = jax.tree.map(
        lambda s: ParamSpec((cfg.n_layers, *s.shape), ("layers", *s.axes), s.init, s.dtype),
        mamba2.mamba2_state_spec(cfg, batch),
        is_leaf=lambda x: isinstance(x, ParamSpec),
    )
    if page is not None:
        num_blocks, block_size = page
        front = (n_inv, num_blocks + 1, block_size)
        axes = ("layers", "kv_blocks", "kv_block", "heads", "head_dim")
    else:
        front = (n_inv, batch, max_len)
        axes = ("layers", "act_batch", "act_kv_seq", "heads", "head_dim")
    attn_cache = {
        "k": ParamSpec((*front, cfg.n_heads, hd), axes, "zeros", cfg.dtype),
        "v": ParamSpec((*front, cfg.n_heads, hd), axes, "zeros", cfg.dtype),
    }
    return {"mamba": mamba_state, "attn": attn_cache}
