"""Generic segmented decoder/encoder stack.

A model is a sequence of SEGMENTS, each a homogeneous run of blocks whose
params are stacked along a leading 'layers' axis and executed with
``jax.lax.scan`` (small HLO, fast compile — essential for the 61-layer
cells) under a configurable remat policy. Heterogeneous archs (deepseek's
dense->moe split, xlstm's mlstm/slstm interleave) are expressed as
multiple segments; zamba2's shared-block wiring lives in ``zamba.py``.

Block kinds:
  dense      : pre-norm GQA attn + pre-norm (G)MLP     (llama/qwen/smollm/chameleon)
  parallel   : single norm, attn + MLP in parallel      (command-r)
  encoder    : bidirectional attn + MLP, conv-pos input (hubert)
  moe        : GQA attn + MoE FFN                       (qwen3-moe)
  mla_dense  : MLA attn + dense MLP                     (deepseek first-3)
  mla_moe    : MLA attn + MoE FFN                       (deepseek)
  mlstm/slstm: xLSTM blocks
  mamba      : Mamba2 block
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.dist.sharding import constrain_batch
from . import attention as attn
from . import mamba2, moe, xlstm
from .layers import ParamSpec, mlp_apply, mlp_specs, norm_apply, norm_specs

__all__ = ["segment_plan", "stack_specs", "block_specs", "block_apply", "run_segments"]


@dataclasses.dataclass(frozen=True)
class Segment:
    kind: str
    count: int


def segment_plan(cfg: ModelConfig) -> List[Segment]:
    if cfg.family in ("dense", "vlm"):
        kind = "parallel" if cfg.parallel_block else "dense"
        return [Segment(kind, cfg.n_layers)]
    if cfg.family in ("encoder", "audio"):
        return [Segment("encoder", cfg.n_layers)]
    if cfg.family == "moe":
        if cfg.mla is not None:
            k = cfg.moe.first_k_dense
            segs = []
            if k:
                segs.append(Segment("mla_dense", k))
            segs.append(Segment("mla_moe", cfg.n_layers - k))
            return segs
        k = cfg.moe.first_k_dense
        segs = []
        if k:
            segs.append(Segment("dense", k))
        segs.append(Segment("moe", cfg.n_layers - k))
        return segs
    if cfg.family == "xlstm":
        xc = cfg.xlstm
        segs: List[Segment] = []
        run = 0
        for i in range(cfg.n_layers):
            if (i + 1) % xc.slstm_every == 0:
                if run:
                    segs.append(Segment("mlstm", run))
                    run = 0
                segs.append(Segment("slstm", 1))
            else:
                run += 1
        if run:
            segs.append(Segment("mlstm", run))
        return segs
    if cfg.family in ("ssm", "hybrid"):
        raise ValueError("ssm/hybrid stacks are built in zamba.py / model.py")
    raise ValueError(f"no segment plan for family {cfg.family}")


# ---------------------------------------------------------------------------
# Per-block specs
# ---------------------------------------------------------------------------

def block_specs(cfg: ModelConfig, kind: str) -> Dict[str, Any]:
    d, dt = cfg.d_model, cfg.dtype
    if kind in ("dense", "parallel", "encoder", "moe"):
        out = {
            "attn_norm": norm_specs(d, cfg.norm, dt),
            "attn": attn.gqa_specs(cfg),
        }
        if kind != "parallel":
            out["mlp_norm"] = norm_specs(d, cfg.norm, dt)
        if kind == "moe":
            out["ffn"] = moe.moe_specs(cfg)
        else:
            out["ffn"] = mlp_specs(d, cfg.d_ff, cfg.glu, dt)
        return out
    if kind in ("mla_dense", "mla_moe"):
        out = {
            "attn_norm": norm_specs(d, cfg.norm, dt),
            "attn": attn.mla_specs(cfg),
            "mlp_norm": norm_specs(d, cfg.norm, dt),
        }
        if kind == "mla_moe":
            out["ffn"] = moe.moe_specs(cfg)
        else:
            out["ffn"] = mlp_specs(d, cfg.d_ff, cfg.glu, dt)
        return out
    if kind == "mamba":
        return {"norm": norm_specs(d, cfg.norm, dt), "mixer": mamba2.mamba2_specs(cfg)}
    if kind == "mlstm":
        return {"norm": norm_specs(d, cfg.norm, dt), "mixer": xlstm.mlstm_specs(cfg)}
    if kind == "slstm":
        return {"norm": norm_specs(d, cfg.norm, dt), "mixer": xlstm.slstm_specs(cfg)}
    raise ValueError(f"unknown block kind {kind}")


def stack_specs(cfg: ModelConfig, seg: Segment):
    """Stack one block's specs along a leading 'layers' axis."""
    single = block_specs(cfg, seg.kind)
    if seg.count == 1:
        return single

    def stack(s: ParamSpec) -> ParamSpec:
        return ParamSpec(
            (seg.count, *s.shape), ("layers", *s.axes), s.init, s.dtype, s.scale
        )

    return jax.tree.map(stack, single, is_leaf=lambda x: isinstance(x, ParamSpec))


# ---------------------------------------------------------------------------
# Per-block apply (train/prefill; decode lives in model.py)
# ---------------------------------------------------------------------------

def block_apply(
    params: Dict,
    x: jax.Array,
    cfg: ModelConfig,
    kind: str,
    *,
    positions: jax.Array,
) -> Tuple[jax.Array, jax.Array]:
    """Returns (x_out, aux_loss)."""
    aux = jnp.zeros((), jnp.float32)
    if kind in ("dense", "encoder", "moe", "mla_dense", "mla_moe"):
        h = norm_apply(params["attn_norm"], x, cfg.norm, cfg.rms_norm_eps)
        if kind.startswith("mla"):
            a, _ = attn.mla_apply(params["attn"], h, cfg, positions=positions)
        else:
            a, _ = attn.gqa_apply(params["attn"], h, cfg, positions=positions)
        x = x + a
        h = norm_apply(params["mlp_norm"], x, cfg.norm, cfg.rms_norm_eps)
        if kind in ("moe", "mla_moe"):
            f, aux, _ = moe.moe_apply(params["ffn"], h, cfg)
        else:
            f = mlp_apply(params["ffn"], h, cfg.act, cfg.glu)
        return x + f, aux
    if kind == "parallel":
        h = norm_apply(params["attn_norm"], x, cfg.norm, cfg.rms_norm_eps)
        a, _ = attn.gqa_apply(params["attn"], h, cfg, positions=positions)
        f = mlp_apply(params["ffn"], h, cfg.act, cfg.glu)
        return x + a + f, aux
    if kind == "mamba":
        h = norm_apply(params["norm"], x, cfg.norm, cfg.rms_norm_eps)
        return x + mamba2.mamba2_apply(params["mixer"], h, cfg), aux
    if kind == "mlstm":
        h = norm_apply(params["norm"], x, cfg.norm, cfg.rms_norm_eps)
        return x + xlstm.mlstm_apply(params["mixer"], h, cfg), aux
    if kind == "slstm":
        h = norm_apply(params["norm"], x, cfg.norm, cfg.rms_norm_eps)
        y, _ = xlstm.slstm_apply(params["mixer"], h, cfg)
        return x + y, aux
    raise ValueError(f"unknown block kind {kind}")


def _remat_wrap(fn, cfg: ModelConfig):
    if cfg.remat == "none":
        return fn
    if cfg.remat == "full":
        return jax.checkpoint(fn)
    if cfg.remat == "selective":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable
        )
    raise ValueError(f"unknown remat {cfg.remat}")


def run_segments(
    seg_params: List[Dict],
    segs: List[Segment],
    x: jax.Array,
    cfg: ModelConfig,
    *,
    positions: jax.Array,
) -> Tuple[jax.Array, jax.Array]:
    """Forward through all segments; scan within multi-block segments."""
    total_aux = jnp.zeros((), jnp.float32)
    for params, seg in zip(seg_params, segs):
        body = _remat_wrap(
            lambda p, h: block_apply(p, h, cfg, seg.kind, positions=positions), cfg
        )
        if seg.count == 1 or not cfg.scan_layers:
            if seg.count == 1:
                x, aux = body(params, x)
                x = constrain_batch(x)
                total_aux = total_aux + aux
            else:
                for i in range(seg.count):
                    layer = jax.tree.map(lambda t: t[i], params)
                    x, aux = body(layer, x)
                    x = constrain_batch(x)
                    total_aux = total_aux + aux
        else:
            def scan_fn(h, layer):
                h2, aux = body(layer, h)
                return constrain_batch(h2), aux
            x, auxes = jax.lax.scan(scan_fn, x, params)
            total_aux = total_aux + auxes.sum()
    return x, total_aux
