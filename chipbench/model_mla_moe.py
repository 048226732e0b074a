"""A DeepSeek-V3-family configuration file (latent attention, sparse
experts) -> the program's ``ModelConfig`` and the sizes the reference and
the counters use.

The file holds the published config's keys (Hugging Face names) as they
are run, with the chip's share of the experts in ``n_routed_experts``
(listed in ``reduced``) and the published count under ``published``.
``program_config`` starts from the program's registry entry, sets the run
settings the file states (``program``), and refuses to run when any size
or setting differs from the file, as ``model.program_config`` does for
the dense decoders.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional

from .common import BenchError


@dataclasses.dataclass(frozen=True)
class MLAMoEDims:
    """Multi-head latent attention (with or without a query LoRA), a
    leading run of dense SwiGLU layers, then layers of routed experts (the
    held share) beside shared experts; RMSNorm, RoPE, an untied or tied
    head."""

    n_layers: int
    d_model: int
    n_heads: int
    q_lora_rank: Optional[int]
    kv_lora_rank: int
    qk_nope: int
    qk_rope: int
    v_head: int
    d_ff: int                     # the dense layers
    first_k_dense: int
    n_experts: int                # published: the router's width
    held: int                     # held here
    held_offset: int
    top_k: int
    d_expert: int
    n_shared: int                 # shared experts, one SwiGLU of n_shared x d_expert
    scoring: str
    routed_scaling: float
    vocab: int
    rope_theta: float
    rms_eps: float
    tied: bool
    param_bytes: int = 2          # bytes per stored parameter (bf16)

    @property
    def qk_head(self) -> int:
        return self.qk_nope + self.qk_rope

    @property
    def n_moe_layers(self) -> int:
        return self.n_layers - self.first_k_dense


#: keys of the published config whose values the program has no switch
#: for: it runs them as stated here and refuses any other
FIXED = {"hidden_act": "silu", "attention_bias": False, "moe_layer_freq": 1,
         "n_group": 1, "topk_group": 1, "topk_method": "noaux_tc",
         "norm_topk_prob": True, "num_nextn_predict_layers": 0}


def dims_of(config: Dict[str, Any]) -> MLAMoEDims:
    prog = config["program"]
    share = config["expert_share"]
    return MLAMoEDims(
        n_layers=int(config["num_hidden_layers"]),
        d_model=int(config["hidden_size"]),
        n_heads=int(config["num_attention_heads"]),
        q_lora_rank=(None if config["q_lora_rank"] is None else int(config["q_lora_rank"])),
        kv_lora_rank=int(config["kv_lora_rank"]),
        qk_nope=int(config["qk_nope_head_dim"]),
        qk_rope=int(config["qk_rope_head_dim"]),
        v_head=int(config["v_head_dim"]),
        d_ff=int(config["intermediate_size"]),
        first_k_dense=int(config["first_k_dense_replace"]),
        n_experts=int(config["published"]["n_routed_experts"]),
        held=int(config["n_routed_experts"]),
        held_offset=int(share["offset"]),
        top_k=int(config["num_experts_per_tok"]),
        d_expert=int(config["moe_intermediate_size"]),
        n_shared=int(config["n_shared_experts"]),
        scoring=str(config["scoring_func"]),
        routed_scaling=float(config["routed_scaling_factor"]),
        vocab=int(config["vocab_size"]),
        rope_theta=float(config["rope_theta"]),
        rms_eps=float(config["rms_norm_eps"]),
        tied=bool(config["tie_word_embeddings"]),
        param_bytes={"bfloat16": 2, "float32": 4}[prog["dtype"]],
    )


def program_config(config: Dict[str, Any]):
    """The program's ``ModelConfig`` for this file, checked key by key."""
    from repro.configs import get_config

    prog = config["program"]
    cfg = dataclasses.replace(get_config(config["registry"]),
                              dtype=prog["dtype"], remat=prog["remat"])
    d = dims_of(config)
    moe, mla = cfg.moe, cfg.mla
    if moe is None or mla is None:
        raise BenchError(f"{config['registry']}: the program's entry has no "
                         f"{'experts' if moe is None else 'latent attention'}")
    have = {
        "n_layers": cfg.n_layers, "d_model": cfg.d_model, "n_heads": cfg.n_heads,
        "q_lora_rank": mla.q_lora_rank, "kv_lora_rank": mla.kv_lora_rank,
        "qk_nope": mla.qk_nope_head_dim, "qk_rope": mla.qk_rope_head_dim,
        "v_head": mla.v_head_dim, "d_ff": cfg.d_ff, "first_k_dense": moe.first_k_dense,
        "n_experts": moe.n_experts, "held": moe.held, "held_offset": moe.held_offset,
        "top_k": moe.top_k, "d_expert": moe.d_expert,
        "n_shared": moe.n_shared_experts if moe.d_shared in (0, moe.d_expert) else None,
        "scoring": moe.scoring, "routed_scaling": moe.routed_scaling, "vocab": cfg.vocab_size,
        "rope_theta": cfg.rope_theta, "rms_eps": cfg.rms_norm_eps,
        "tied": cfg.tie_embeddings,
    }
    diff = {k: (v, getattr(d, k)) for k, v in have.items() if v != getattr(d, k)}
    for key, want in FIXED.items():
        if config[key] != want:
            diff[key] = (want, config[key])
    if not moe.dropless or cfg.mtp:
        diff["router"] = (moe.dropless, cfg.mtp)
    if cfg.norm != "rmsnorm" or cfg.act != "silu" or not cfg.glu or not cfg.mla_absorb:
        diff["block"] = (cfg.norm, cfg.act, cfg.glu, cfg.mla_absorb)
    if d.held_offset + d.held > d.n_experts or config["expert_share"]["held"] != d.held:
        diff["expert_share"] = (config["expert_share"], d.held)
    if diff:
        raise BenchError(f"{config['registry']}: program differs from the "
                         f"configuration file (program, file): {diff}")
    return cfg
