"""ShapeDtypeStruct input stand-ins for every (arch x shape) cell.

No allocation: the dry-run lowers against these. Shardings are attached
here so ``jit(...).lower(**specs)`` sees the production layout.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.configs.shapes import SHAPES, ShapeSpec
from repro.dist.sharding import ShardingRules, batch_pspec, make_sharding_fn
from repro.models.layers import DTYPES, ParamSpec, abstract_from_specs
from repro.models.model import Model

__all__ = ["train_input_specs", "prefill_input_specs", "decode_input_specs",
           "n_workers_for"]


def n_workers_for(mesh: Mesh) -> int:
    n = 1
    for a in ("pod", "data"):
        if a in mesh.shape:
            n *= mesh.shape[a]
    return n


def _sds(shape, dtype, mesh, pspec):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=NamedSharding(mesh, pspec))


def train_input_specs(
    cfg: ModelConfig, shape: ShapeSpec, mesh: Mesh, *, beta: float = 1.0,
    rules: ShardingRules = None,
) -> Dict[str, Any]:
    """Batch stand-ins for train_step. beta scales the per-worker batch
    (the paper's computation-load knob; changes the compiled shape)."""
    n = n_workers_for(mesh)
    B = shape.global_batch
    per_worker = max(int(round(B * beta)) // n, 1)
    Bb = per_worker * n
    S = shape.seq_len
    dp = None
    if rules is not None:
        ab = rules.get("act_batch")
        if ab is not None:
            dp = (ab,) if isinstance(ab, str) else tuple(ab)
    if cfg.input_kind == "tokens":
        inputs = _sds((Bb, S), jnp.int32, mesh, batch_pspec(mesh, Bb, 1, dp_axes=dp))
    else:
        inputs = _sds((Bb, S, cfg.d_model), DTYPES[cfg.dtype], mesh,
                      batch_pspec(mesh, Bb, 2, dp_axes=dp))
    return {
        "inputs": inputs,
        "labels": _sds((Bb, S), jnp.int32, mesh, batch_pspec(mesh, Bb, 1, dp_axes=dp)),
        "worker_mask": _sds((n,), jnp.float32, mesh, P()),
        "lr": jax.ShapeDtypeStruct((), jnp.float32),
    }


def prefill_input_specs(cfg: ModelConfig, shape: ShapeSpec, mesh: Mesh):
    B, S = shape.global_batch, shape.seq_len
    if cfg.input_kind == "tokens":
        inputs = _sds((B, S), jnp.int32, mesh, batch_pspec(mesh, B, 1))
    else:
        inputs = _sds((B, S, cfg.d_model), DTYPES[cfg.dtype], mesh,
                      batch_pspec(mesh, B, 2))
    return {"inputs": inputs}


def decode_input_specs(
    cfg: ModelConfig, shape: ShapeSpec, mesh: Mesh, rules: ShardingRules
):
    """One-token decode against a cache of length shape.seq_len."""
    model = Model(cfg)
    B, S = shape.global_batch, shape.seq_len
    token = _sds((B, 1), jnp.int32, mesh, batch_pspec(mesh, B, 1))
    caches = abstract_from_specs(
        model.cache_specs(B, S), make_sharding_fn(mesh, rules)
    )
    return {
        "token": token,
        "caches": caches,
        "cache_index": jax.ShapeDtypeStruct((), jnp.int32),
    }
