"""Common layers, spec-first.

Every layer exposes ``*_specs(...) -> dict[name, ParamSpec]`` describing
shape/dtype/logical-axes/initializer, plus a pure ``apply`` function. The
spec tree drives three consumers:

  * ``init_from_specs``     — materialize real params (CPU smoke tests,
                              small end-to-end training),
  * ``abstract_from_specs`` — ShapeDtypeStruct stand-ins with
                              NamedSharding attached (multi-pod dry-run;
                              no allocation),
  * analytic parameter counting (roofline MODEL_FLOPS).

Logical axis names are mapped to mesh axes by ``repro.dist.sharding``.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "ParamSpec",
    "init_from_specs",
    "abstract_from_specs",
    "count_specs",
    "batch_axis_of",
    "is_paged_spec",
    "slot_read",
    "slot_write",
    "slot_reset",
    "slot_take",
    "slot_block_copy",
    "slot_mask_select",
    "rms_norm",
    "layer_norm",
    "norm_apply",
    "norm_specs",
    "rope_freqs",
    "apply_rope",
    "mlp_specs",
    "mlp_apply",
    "activation",
    "DTYPES",
]

DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}


@dataclasses.dataclass(frozen=True)
class ParamSpec:
    shape: Tuple[int, ...]
    axes: Tuple[Optional[str], ...]   # logical axis per dim (None = replicated)
    init: str = "normal"              # normal | zeros | ones | scaled(fan_in)
    dtype: str = "bfloat16"
    scale: float = 1.0                # stddev multiplier for normal inits

    def __post_init__(self):
        if len(self.shape) != len(self.axes):
            raise ValueError(f"shape/axes rank mismatch: {self.shape} vs {self.axes}")

    @property
    def size(self) -> int:
        return int(np.prod(self.shape)) if self.shape else 1


def _fan_in(shape: Tuple[int, ...]) -> int:
    # Convention: the LAST axis is the output axis; everything else is input.
    return max(int(np.prod(shape[:-1])), 1) if len(shape) > 1 else max(shape[0], 1)


def init_from_specs(rng: jax.Array, specs, dtype_override: Optional[str] = None):
    """Materialize a param pytree from a ParamSpec pytree."""
    leaves, treedef = jax.tree.flatten(
        specs, is_leaf=lambda x: isinstance(x, ParamSpec)
    )
    keys = jax.random.split(rng, len(leaves))
    out = []
    for key, spec in zip(keys, leaves):
        dt = DTYPES[dtype_override or spec.dtype]
        if spec.init == "zeros":
            out.append(jnp.zeros(spec.shape, dt))
        elif spec.init == "ones":
            out.append(jnp.ones(spec.shape, dt))
        elif spec.init == "normal":
            out.append(
                (jax.random.normal(key, spec.shape, jnp.float32) * 0.02 * spec.scale).astype(dt)
            )
        elif spec.init == "scaled":
            std = spec.scale / math.sqrt(_fan_in(spec.shape))
            out.append(
                (jax.random.normal(key, spec.shape, jnp.float32) * std).astype(dt)
            )
        else:
            raise ValueError(f"unknown init {spec.init}")
    return jax.tree.unflatten(treedef, out)


def abstract_from_specs(specs, sharding_for: Callable[[ParamSpec], object]):
    """ShapeDtypeStruct pytree with shardings — zero allocation."""
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(
            s.shape, DTYPES[s.dtype], sharding=sharding_for(s)
        ),
        specs,
        is_leaf=lambda x: isinstance(x, ParamSpec),
    )


def count_specs(specs) -> int:
    leaves = jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, ParamSpec))
    return sum(s.size for s in leaves)


# ---------------------------------------------------------------------------
# Slot-indexed cache helpers (repro.serve)
#
# Serving caches are pytrees whose leaves each carry an "act_batch" axis —
# NOT always the leading one (stacked-layer segments and the zamba shared
# block put "layers" first). The spec tree is the source of truth for
# where the slot axis lives and what a freshly reset slot contains
# (``init`` is "zeros" for KV rows but "ones" for e.g. the sLSTM
# normalizer), so every helper here walks (values, specs) together.
#
# Paged leaves (block-table KV arenas, axes carrying "kv_blocks" /
# "kv_block" instead of "act_batch"/"act_kv_seq") have NO per-slot rows:
# slot membership lives in the host-side block table, not the array
# layout. Every helper treats them as global state — read passes the
# arena through, write replaces it, reset/take are no-ops (freed blocks
# are recycled by the BlockManager; defrag never moves paged rows), and
# mask-select keeps the new arena (dead-lane writes land in the reserved
# null block by construction, so there is nothing to mask).
# ---------------------------------------------------------------------------

def _is_spec(x) -> bool:
    return isinstance(x, ParamSpec)


def is_paged_spec(spec: ParamSpec) -> bool:
    """True for block-arena cache leaves (slot axis replaced by a
    (kv_blocks, kv_block) pair addressed through a block table)."""
    return "kv_blocks" in spec.axes


def batch_axis_of(spec: ParamSpec) -> int:
    """Index of the slot ("act_batch") axis of a cache leaf."""
    return spec.axes.index("act_batch")


def slot_read(caches, specs, slot) -> "jax.Array":
    """Extract one slot as a batch-1 cache pytree (for chunked prefill
    continuation: read the slot, extend it, write it back). Paged arenas
    pass through whole — the slot's rows are found via its block table."""
    def read(c, s):
        if is_paged_spec(s):
            return c
        ax = batch_axis_of(s)
        return jax.lax.dynamic_slice_in_dim(c, slot, 1, axis=ax)
    return jax.tree.map(read, caches, specs, is_leaf=_is_spec)


def slot_write(caches, specs, slot, slot_caches):
    """Write a batch-1 cache pytree into slot ``slot`` of a pooled cache.
    Paged arena leaves were mutated in place (functionally) by the
    prefill that produced ``slot_caches`` — adopt them wholesale."""
    def write(c, s, v):
        if is_paged_spec(s):
            return v.astype(c.dtype)
        ax = batch_axis_of(s)
        return jax.lax.dynamic_update_slice_in_dim(c, v.astype(c.dtype), slot, axis=ax)
    return jax.tree.map(write, caches, specs, slot_caches, is_leaf=_is_spec)


def slot_reset(caches, specs, slot):
    """Restore one slot to its spec-defined initial value (zeros/ones).
    Paged leaves are untouched: freeing a slot returns its blocks to the
    manager, and stale rows are overwritten on reallocation (the same
    lazy-reuse discipline as contiguous slots)."""
    def reset(c, s):
        if is_paged_spec(s):
            return c
        ax = batch_axis_of(s)
        shape = list(c.shape)
        shape[ax] = 1
        fill = jnp.ones if s.init == "ones" else jnp.zeros
        return jax.lax.dynamic_update_slice_in_dim(
            c, fill(shape, c.dtype), slot, axis=ax
        )
    return jax.tree.map(reset, caches, specs, is_leaf=_is_spec)


def slot_take(caches, specs, perm):
    """Permute slots (defrag: compact live slots to the low indices).
    Paged leaves are a no-op: block tables are host arrays that permute
    for free, so defrag never gathers arena rows."""
    def take(c, s):
        if is_paged_spec(s):
            return c
        return jnp.take(c, perm, axis=batch_axis_of(s))
    return jax.tree.map(take, caches, specs, is_leaf=_is_spec)


def slot_block_copy(caches, specs, src, dst):
    """Copy arena block ``src`` into block ``dst`` on every paged leaf —
    the device half of a copy-on-write fork. The BlockManager swaps the
    writer's table entry to ``dst`` on the host; after this copy the
    subsequent ``cache_row_update``/``cache_rows_update`` scatter lands
    in the private clone, never in the shared original. Contiguous
    leaves pass through untouched (they are never shared)."""
    def cp(c, s):
        if not is_paged_spec(s):
            return c
        ax = s.axes.index("kv_blocks")
        m = jnp.moveaxis(c, ax, 0)
        m = m.at[dst].set(m[src])
        return jnp.moveaxis(m, 0, ax)
    return jax.tree.map(cp, caches, specs, is_leaf=_is_spec)


def slot_mask_select(mask, new_caches, old_caches, specs):
    """Per-slot select: where ``mask`` (n_slots,) is True take the new
    leaf rows, else keep the old — the serving analogue of the fastest-k
    ``worker_mask`` (occupancy enters as data, shapes never change).
    Paged arenas always take the new value: masked lanes' writes were
    routed to the null sink block, so live rows are already correct."""
    def sel(n, o, s):
        if is_paged_spec(s):
            return n
        ax = batch_axis_of(s)
        shape = [1] * n.ndim
        shape[ax] = n.shape[ax]
        return jnp.where(mask.reshape(shape), n, o.astype(n.dtype))
    return jax.tree.map(sel, new_caches, old_caches, specs, is_leaf=_is_spec)


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------

def norm_specs(d: int, kind: str, dtype: str) -> Dict[str, ParamSpec]:
    out = {"scale": ParamSpec((d,), ("embed",), init="ones", dtype=dtype)}
    if kind == "layernorm":
        out["bias"] = ParamSpec((d,), ("embed",), init="zeros", dtype=dtype)
    return out


def rms_norm(x: jax.Array, scale: jax.Array, eps: float = 1e-6) -> jax.Array:
    dt = x.dtype
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps)).astype(dt) * scale


def layer_norm(
    x: jax.Array, scale: jax.Array, bias: Optional[jax.Array], eps: float = 1e-5
) -> jax.Array:
    dt = x.dtype
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    y = ((xf - mean) * jax.lax.rsqrt(var + eps)).astype(dt) * scale
    if bias is not None:
        y = y + bias
    return y


def norm_apply(params: Dict, x: jax.Array, kind: str, eps: float = 1e-6) -> jax.Array:
    """``eps`` is RMSNorm's (``ModelConfig.rms_norm_eps``); LayerNorm
    keeps its own 1e-5."""
    if kind == "rmsnorm":
        return rms_norm(x, params["scale"], eps)
    return layer_norm(x, params["scale"], params.get("bias"))


# ---------------------------------------------------------------------------
# Rotary position embeddings
# ---------------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    """Inverse frequencies (head_dim // 2,) in float32."""
    return 1.0 / (
        theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim)
    )


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (..., seq, heads, head_dim); positions: broadcastable to (..., seq)."""
    head_dim = x.shape[-1]
    freqs = rope_freqs(head_dim, theta)  # (hd/2,)
    angles = positions[..., :, None, None].astype(jnp.float32) * freqs  # (...,s,1,hd/2)
    sin, cos = jnp.sin(angles), jnp.cos(angles)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# MLP / gated FFN
# ---------------------------------------------------------------------------

def activation(name: str) -> Callable[[jax.Array], jax.Array]:
    if name == "silu":
        return jax.nn.silu
    if name == "gelu":
        return lambda x: jax.nn.gelu(x, approximate=True)
    raise ValueError(f"unknown activation {name}")


def mlp_specs(d: int, d_ff: int, glu: bool, dtype: str) -> Dict[str, ParamSpec]:
    out = {
        "w_in": ParamSpec((d, d_ff), ("embed", "ffn"), init="scaled", dtype=dtype),
        "w_out": ParamSpec((d_ff, d), ("ffn", "embed"), init="scaled", dtype=dtype),
    }
    if glu:
        out["w_gate"] = ParamSpec(
            (d, d_ff), ("embed", "ffn"), init="scaled", dtype=dtype
        )
    return out


def mlp_apply(params: Dict, x: jax.Array, act: str, glu: bool) -> jax.Array:
    h = jnp.einsum("...d,df->...f", x, params["w_in"])
    if glu:
        g = jnp.einsum("...d,df->...f", x, params["w_gate"])
        h = activation(act)(g) * h
    else:
        h = activation(act)(h)
    return jnp.einsum("...f,fd->...d", h, params["w_out"])
