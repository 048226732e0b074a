"""A tiny latent-attention, sparse-expert serving cell for CPU tests: the
Moonlight registry entry at ``ModelConfig.reduced`` widths (3 layers, one
dense; 8 experts, 2 held, top-2), with scanned layers as the chip cell
runs them, in float32: at this size bfloat16's routing flips against the
float32 reference decide the served gap (0.19-1.44 over every finished
request on seeds 7-9, against the fp8 control's 1.78-2.54), where float32
reads 0."""

from __future__ import annotations

import json

from chipbench.common import Cell
from chipbench.tests.tiny import BENCH

TINY_MLA_MOE = {
    "name": "tiny-mla-moe", "registry": "moonlight-16b-a3b",
    "num_hidden_layers": 3, "hidden_size": 128, "intermediate_size": 256,
    "num_attention_heads": 4, "q_lora_rank": None, "kv_lora_rank": 32,
    "qk_nope_head_dim": 32, "qk_rope_head_dim": 16, "v_head_dim": 32,
    "first_k_dense_replace": 1, "n_routed_experts": 2, "num_experts_per_tok": 2,
    "moe_intermediate_size": 64, "n_shared_experts": 2, "scoring_func": "sigmoid",
    "norm_topk_prob": True, "routed_scaling_factor": 2.446, "vocab_size": 512,
    "rope_theta": 50000, "rms_norm_eps": 1e-5, "tie_word_embeddings": False,
    "hidden_act": "silu", "attention_bias": False, "moe_layer_freq": 1, "n_group": 1,
    "topk_group": 1, "topk_method": "noaux_tc", "num_nextn_predict_layers": 0,
    "published": {"n_routed_experts": 8},
    "expert_share": {"held": 2, "offset": 0, "of": 8, "ranks": 4},
    "program": {"dtype": "float32", "remat": "full"},
}


def tiny_program_config(config):
    """The program's config for ``TINY_MLA_MOE``."""
    from repro.configs import get_config

    return get_config(config["registry"]).reduced(
        n_layers=config["num_hidden_layers"], dtype=config["program"]["dtype"],
        remat="full", scan_layers=True)


def serve_cell(**overrides) -> Cell:
    tr = json.loads((BENCH / "traffic" / "chat_moonlight.json").read_text())
    tr.update(slots=4, max_len=128, arena_blocks=24, prefill_chunk=32, prefill_bucket=16,
              drain_s=30, trace_seconds=1.0, arrivals={"process": "poisson", "rate_per_s": 4.0},
              prompt_len={"dist": "lognormal", "median": 24, "sigma": 0.8, "min": 4, "max": 80},
              output_len={"dist": "lognormal", "median": 8, "sigma": 0.8, "min": 2, "max": 40},
              check={"min_tokens": 20})
    tr.update(overrides)
    return Cell("tiny.serve_mla_moe", 1, dict(TINY_MLA_MOE), tr, {"served_gap_mean": 0.05}, {})
