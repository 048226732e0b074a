"""Mixture-of-Experts FFN (top-k routing, capacity-bounded, EP-shardable).

Baseline formulation (v1, used by the dry-run): sort-based dispatch into
per-expert (E, C, d) buffers via scatter, expert compute as a single
batched einsum over the expert dimension, gather-combine. Under pjit the
expert dim shards over 'model' (expert parallelism); the scatter/gather
lower to collectives chosen by SPMD (documented in §Roofline, and the
explicit all-to-all shard_map variant is a §Perf hillclimb).

Faithfulness notes: token-choice top-k routing with softmax gates
(renormalized over the top-k), optional DeepSeek-style shared experts and
leading dense layers, capacity dropping with zero-fill (dropped tokens
pass through the residual stream only), and the standard load-balance
auxiliary loss (Switch/GShard form). DeepSeek-V3's router
(``MoEConfig.scoring="sigmoid"``): sigmoid scores in float32, the top-k
chosen on the scores plus a selection bias, the weights the chosen
unbiased scores renormalized and scaled; its sequence-wise auxiliary
loss is not built (the Switch form stands in, on the scores normalized
per token).

Expert share (``MoEConfig.n_held``/``held_offset``, expert parallelism):
the router keeps all ``n_experts`` outputs and picks its top-k over all
of them; the layer holds only its experts' weights, dispatches only the
choices that fall on them, and returns their weighted part plus the
shared experts' whole output. The absent experts' part is another
chip's; on one chip there is no exchange.
"""

from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, MoEConfig
from repro.dist.sharding import constrain_logical
from .layers import ParamSpec, activation, mlp_apply, mlp_specs

__all__ = ["moe_specs", "moe_apply", "moe_capacity"]


def moe_capacity(moe: MoEConfig, tokens: int) -> int:
    """Static per-expert capacity for a given token count. Dropless mode
    (inference) sizes the buffer for the worst case — every token on one
    expert — so routing is token-local and chunk-geometry-invariant."""
    if moe.dropless:
        return max(tokens, moe.top_k)
    cap = int(moe.capacity_factor * tokens * moe.top_k / moe.n_experts)
    return max(cap, moe.top_k)


def moe_specs(cfg: ModelConfig) -> Dict[str, ParamSpec]:
    """The router at the published width (d, n_experts); the expert
    stacks of the held experts only (held, d, d_expert). The selection
    bias (``router_bias``, float32 as DeepSeek-V3 keeps it) enters only
    the choice of experts, so it has no gradient: training leaves it as
    initialised (DeepSeek-V3 moves it by a balance rule outside the
    gradient, which this repo does not run)."""
    moe = cfg.moe
    d, dt = cfg.d_model, cfg.dtype
    de, eh = moe.d_expert, moe.held
    specs: Dict[str, ParamSpec] = {
        "router": ParamSpec((d, moe.n_experts), ("embed", None), "scaled", dt),
        "w_in": ParamSpec(
            (eh, d, de), ("expert", "embed", "expert_ffn"), "scaled", dt
        ),
        "w_gate": ParamSpec(
            (eh, d, de), ("expert", "embed", "expert_ffn"), "scaled", dt
        ),
        "w_out": ParamSpec(
            (eh, de, d), ("expert", "expert_ffn", "embed"), "scaled", dt
        ),
    }
    if moe.scoring == "sigmoid":
        specs["router_bias"] = ParamSpec((moe.n_experts,), (None,), "normal", "float32")
    if moe.n_shared_experts > 0:
        d_sh = (moe.d_shared or moe.d_expert) * moe.n_shared_experts
        specs["shared"] = mlp_specs(d, d_sh, glu=True, dtype=dt)
    return specs


def _route(
    x_flat: jax.Array, params: Dict, moe: MoEConfig
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Returns (weights (T,K), experts (T,K) int32, aux_loss scalar);
    ``experts`` index all ``n_experts``, held or not."""
    router = params["router"]
    if moe.scoring == "sigmoid":
        f32 = jnp.float32
        logits = jnp.einsum("td,de->te", x_flat.astype(f32), router.astype(f32))
        scores = jax.nn.sigmoid(logits)
        probs = scores / scores.sum(-1, keepdims=True)
    else:
        logits = jnp.einsum("td,de->te", x_flat, router).astype(jnp.float32)
        scores = probs = jax.nn.softmax(logits, axis=-1)
    if moe.scoring == "sigmoid":
        bias = jax.lax.stop_gradient(params["router_bias"]).astype(jnp.float32)
        _, experts = jax.lax.top_k(scores + bias, moe.top_k)
        weights = jnp.take_along_axis(scores, experts, axis=-1)
    else:
        weights, experts = jax.lax.top_k(scores, moe.top_k)
    weights = weights / jnp.maximum(weights.sum(-1, keepdims=True), 1e-9)
    if moe.routed_scaling != 1.0:
        weights = weights * moe.routed_scaling
    # Load-balance aux loss: E * sum_e f_e * P_e  (Switch Transformer eq. 4).
    E = moe.n_experts
    f = jnp.zeros((E,), jnp.float32).at[experts.reshape(-1)].add(1.0)
    f = f / jnp.maximum(f.sum(), 1.0)
    p = probs.mean(axis=0)
    aux = E * jnp.sum(f * p)
    return weights.astype(x_flat.dtype), experts, aux


def _dp_group_count(T: int) -> int:
    """Number of data-parallel groups for group-local dispatch (= product
    of the ambient data axes when it divides the token count, else 1)."""
    from repro.dist.sharding import _ACT_CTX  # ambient mesh context

    ctx = _ACT_CTX.get()
    if ctx is None:
        return 1
    g = 1
    for a in ctx.dp:
        g *= ctx.mesh.shape[a]
    return g if g > 1 and T % g == 0 else 1


def moe_apply(
    params: Dict, x: jax.Array, cfg: ModelConfig
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """x: (B, S, D) -> (out (B, S, D), aux_loss scalar, rows (B, S)
    int32: how many of each token's choices a held expert computed).

    Dispatch formulations (cfg.moe.dispatch — §Perf iterations):
      "data"    : dispatched tokens stay batch-sharded; scatter into the
                  model-sharded (E, C, D) buffer (v1 baseline; XLA
                  replicates + all-reduces the buffer — expensive),
      "model"   : dispatched tokens resharded over the MODEL axis before
                  the scatter, so buffer formation is a same-axis 1-D
                  exchange (all-to-all-shaped, the EP-optimal volume),
      "grouped" : per-data-group capacity buffers (refuted: XLA cannot
                  partition the 2-axis scatter; kept for the record).
    """
    if cfg.moe.dispatch == "grouped":
        if cfg.moe.held != cfg.moe.n_experts:
            raise NotImplementedError("grouped dispatch holds every expert")
        return _moe_apply_grouped(params, x, cfg)
    return _moe_apply_flat(params, x, cfg)


def _moe_apply_flat(params, x, cfg):
    moe = cfg.moe
    B, S, D = x.shape
    T = B * S
    K, E = moe.top_k, moe.held
    C = moe_capacity(moe, T)
    x_flat = x.reshape(T, D)

    weights, experts, aux = _route(x_flat, params, moe)

    flat_e = experts.reshape(-1)                       # (T*K,)
    if E != moe.n_experts:
        # held experts by local index; a choice of an absent expert goes
        # to bucket E, which no buffer holds
        flat_e = flat_e - moe.held_offset
        flat_e = jnp.where((flat_e >= 0) & (flat_e < E), flat_e, E)
    n_buckets = E + (E != moe.n_experts)
    order = jnp.argsort(flat_e, stable=True)
    sorted_e = flat_e[order]
    counts = jnp.zeros((n_buckets,), jnp.int32).at[flat_e].add(1)
    starts = jnp.cumsum(counts) - counts
    rank_sorted = jnp.arange(T * K, dtype=jnp.int32) - starts[sorted_e]
    pos = jnp.zeros((T * K,), jnp.int32).at[order].set(rank_sorted)
    keep = pos < C
    if E != moe.n_experts:
        keep = keep & (flat_e < E)

    token_idx = jnp.repeat(jnp.arange(T, dtype=jnp.int32), K)
    safe_e = jnp.where(keep, flat_e, 0)
    safe_pos = jnp.where(keep, pos, C - 1)

    disp_axis = "expert" if moe.dispatch == "model" else "act_batch"
    dispatched = jnp.where(keep[:, None], x_flat[token_idx], 0).astype(x.dtype)
    dispatched = constrain_logical(dispatched, (disp_axis, None))
    buf = jnp.zeros((E, C, D), x.dtype)
    buf = buf.at[safe_e, safe_pos].add(dispatched)
    buf = constrain_logical(buf, ("expert", None, None))

    h_in = jnp.einsum("ecd,edf->ecf", buf, params["w_in"])
    h_gate = jnp.einsum("ecd,edf->ecf", buf, params["w_gate"])
    h = activation(cfg.act)(h_gate) * h_in
    y_buf = jnp.einsum("ecf,efd->ecd", h, params["w_out"])

    y_buf = constrain_logical(y_buf, ("expert", None, None))
    gathered = y_buf[safe_e, safe_pos]                  # (T*K, D)
    gathered = constrain_logical(gathered, (disp_axis, None))
    gathered = jnp.where(keep[:, None], gathered, 0)
    w_flat = weights.reshape(-1)[:, None].astype(gathered.dtype)
    out = jnp.zeros((T, D), gathered.dtype).at[token_idx].add(gathered * w_flat)

    if moe.n_shared_experts > 0:
        out = out + mlp_apply(params["shared"], x_flat, cfg.act, glu=True)

    rows = keep.reshape(T, K).sum(-1, dtype=jnp.int32).reshape(B, S)
    return out.reshape(B, S, D), aux.astype(jnp.float32), rows


def _moe_apply_grouped(params, x, cfg):
    moe = cfg.moe
    B, S, D = x.shape
    T = B * S
    K, E = moe.top_k, moe.n_experts
    G = _dp_group_count(T)
    Tg = T // G
    C = max(moe_capacity(moe, T) // G, K)
    x_flat = x.reshape(T, D)

    weights, experts, aux = _route(x_flat, params, moe)

    # Rank each (token, choice) within its (group, expert) bucket.
    eg = experts.reshape(G, Tg * K)                     # (G, Tg*K)
    order = jnp.argsort(eg, axis=-1, stable=True)
    sorted_e = jnp.take_along_axis(eg, order, axis=-1)
    counts = jnp.zeros((G, E), jnp.int32).at[
        jnp.arange(G)[:, None], eg
    ].add(1)
    starts = jnp.cumsum(counts, axis=-1) - counts       # (G, E) exclusive
    rank_sorted = (
        jnp.arange(Tg * K, dtype=jnp.int32)[None, :]
        - jnp.take_along_axis(starts, sorted_e, axis=-1)
    )
    pos = jnp.zeros((G, Tg * K), jnp.int32).at[
        jnp.arange(G)[:, None], order
    ].set(rank_sorted)
    keep = pos < C

    g_idx = jnp.repeat(jnp.arange(G, dtype=jnp.int32)[:, None], Tg * K, axis=1)
    tok_in_g = jnp.tile(jnp.repeat(jnp.arange(Tg, dtype=jnp.int32), K), (G, 1))
    safe_e = jnp.where(keep, eg, 0)
    safe_pos = jnp.where(keep, pos, C - 1)

    xg = x_flat.reshape(G, Tg, D)
    xg = constrain_logical(xg, ("act_batch", None, None))
    dispatched = jnp.where(
        keep[..., None], jnp.take_along_axis(
            xg, tok_in_g[..., None], axis=1
        ), 0
    ).astype(x.dtype)                                    # (G, Tg*K, D)
    dispatched = constrain_logical(dispatched, ("act_batch", None, None))

    buf = jnp.zeros((G, E, C, D), x.dtype)
    buf = buf.at[g_idx, safe_e, safe_pos].add(dispatched)
    buf = constrain_logical(buf, ("act_batch", "expert", None, None))

    # Expert compute: gated FFN batched over (group, expert).
    h_in = jnp.einsum("gecd,edf->gecf", buf, params["w_in"])
    h_gate = jnp.einsum("gecd,edf->gecf", buf, params["w_gate"])
    h = activation(cfg.act)(h_gate) * h_in
    y_buf = jnp.einsum("gecf,efd->gecd", h, params["w_out"])
    y_buf = constrain_logical(y_buf, ("act_batch", "expert", None, None))

    # Combine: gather each kept choice back to its group, weight, sum.
    gathered = y_buf[g_idx, safe_e, safe_pos]            # (G, Tg*K, D)
    gathered = constrain_logical(gathered, ("act_batch", None, None))
    gathered = jnp.where(keep[..., None], gathered, 0)
    w_g = weights.reshape(G, Tg * K, 1).astype(gathered.dtype)
    out = jnp.zeros((G, Tg, D), gathered.dtype).at[
        g_idx, tok_in_g
    ].add(gathered * w_g)
    out = out.reshape(T, D)

    if moe.n_shared_experts > 0:
        out = out + mlp_apply(params["shared"], x_flat, cfg.act, glu=True)

    rows = keep.reshape(T, K).sum(-1, dtype=jnp.int32).reshape(B, S)
    return out.reshape(B, S, D), aux.astype(jnp.float32), rows
