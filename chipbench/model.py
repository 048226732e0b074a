"""A configuration file -> the program's ``ModelConfig`` and the sizes
the reference and the counters use.

The file holds the published config's keys (Hugging Face names) as they
are run. ``program_config`` starts from the program's registry entry,
sets the run settings the file states (``program``), and refuses to run
when any size differs from the file: the file is the configuration as
run, not a description of it.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict

from .common import BenchError


@dataclasses.dataclass(frozen=True)
class Dims:
    """A dense GQA decoder (RMSNorm, RoPE, optional QKV bias, SwiGLU,
    tied or untied head), as the published configs describe it."""

    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    rope_theta: float
    rms_eps: float
    qkv_bias: bool
    tied: bool
    param_bytes: int = 2          # bytes per stored parameter (bf16)

    @property
    def group(self) -> int:
        return self.n_heads // self.n_kv_heads


def dims_of(config: Dict[str, Any]) -> Dims:
    prog = config["program"]
    return Dims(
        n_layers=int(config["num_hidden_layers"]),
        d_model=int(config["hidden_size"]),
        n_heads=int(config["num_attention_heads"]),
        n_kv_heads=int(config["num_key_value_heads"]),
        head_dim=int(config["head_dim"]),
        d_ff=int(config["intermediate_size"]),
        vocab=int(config["vocab_size"]),
        rope_theta=float(config["rope_theta"]),
        rms_eps=float(config["rms_norm_eps"]),
        qkv_bias=bool(config["attention_bias"]),
        tied=bool(config["tie_word_embeddings"]),
        param_bytes={"bfloat16": 2, "float32": 4}[prog["dtype"]],
    )


#: the program's fixed RMSNorm epsilon (``repro.models.layers.rms_norm``)
PROGRAM_RMS_EPS = 1e-6


def program_config(config: Dict[str, Any]):
    """The program's ``ModelConfig`` for this file, checked key by key."""
    from repro.configs import get_config

    prog = config["program"]
    cfg = dataclasses.replace(get_config(config["registry"]),
                              dtype=prog["dtype"], remat=prog["remat"])
    d = dims_of(config)
    want = {
        "n_layers": d.n_layers, "d_model": d.d_model, "n_heads": d.n_heads,
        "n_kv_heads": d.n_kv_heads, "head_dim": d.head_dim, "d_ff": d.d_ff,
        "vocab_size": d.vocab, "rope_theta": d.rope_theta,
        "qkv_bias": d.qkv_bias, "tie_embeddings": d.tied,
    }
    diff = {k: (getattr(cfg, k), v) for k, v in want.items() if getattr(cfg, k) != v}
    if d.rms_eps != PROGRAM_RMS_EPS:
        diff["rms_norm_eps"] = (PROGRAM_RMS_EPS, d.rms_eps)
    if cfg.family != "dense" or cfg.norm != "rmsnorm" or cfg.act != "silu" or not cfg.glu:
        diff["block"] = (cfg.family, cfg.norm, cfg.act, cfg.glu)
    if diff:
        raise BenchError(f"{config['registry']}: program differs from the "
                         f"configuration file (program, file): {diff}")
    return cfg
