"""Config system: one frozen dataclass tree per architecture.

Every assigned architecture is expressed as a ``ModelConfig``; reduced
smoke variants are derived with ``ModelConfig.reduced()``. Shape presets
(train_4k / prefill_32k / decode_32k / long_500k) live in ``shapes.py``.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

__all__ = [
    "MoEConfig",
    "SSMConfig",
    "MLAConfig",
    "XLSTMConfig",
    "ModelConfig",
]


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    n_experts: int
    top_k: int
    d_expert: int              # per-expert FFN hidden size
    n_shared_experts: int = 0  # DeepSeek-style always-on shared expert(s)
    first_k_dense: int = 0     # leading layers that stay dense
    capacity_factor: float = 1.25
    router_aux_weight: float = 0.001  # load-balance loss weight
    d_shared: int = 0          # hidden size of the shared expert (0 = d_expert)
    dispatch: str = "data"     # dispatched-token sharding: data | model | grouped
    # Inference mode: capacity = the full token count, so no token is ever
    # dropped. Capacity-dropped routing makes logits depend on how many
    # tokens share one forward call — a training throughput concession that
    # breaks chunked-prefill/prefix-sharing byte-identity (a 27-token
    # prompt prefilled as 8+8+8+3 drops different tokens than one 27-token
    # call). Dropless routing is token-local and therefore chunk-invariant.
    dropless: bool = False
    # Router: the top-k experts' weights are renormalised over the k, times
    # ``routed_scaling``. "sigmoid" is DeepSeek-V3's ``noaux_tc`` with one
    # group: the choice ranks the scores plus an (E,) selection bias
    # (``router_bias``), the weights are the chosen experts' unbiased scores.
    scoring: str = "softmax"   # softmax | sigmoid
    routed_scaling: float = 1.0
    # This chip's share of the experts (expert parallelism): ``n_experts``
    # stays the published count, the router's width; the layer holds
    # experts held_offset .. held_offset + n_held - 1 and computes only
    # their part of the result. None holds them all.
    n_held: Optional[int] = None
    held_offset: int = 0

    def __post_init__(self) -> None:
        if self.scoring not in ("softmax", "sigmoid"):
            raise ValueError(f"unknown router scoring {self.scoring}")
        if not 0 <= self.held_offset < self.held_offset + self.held <= self.n_experts:
            raise ValueError(
                f"held experts {self.held_offset}..{self.held_offset + self.held - 1} "
                f"are not among the {self.n_experts} experts")

    @property
    def held(self) -> int:
        """Experts held here (all of them unless ``n_held`` says)."""
        return self.n_experts if self.n_held is None else self.n_held


@dataclasses.dataclass(frozen=True)
class SSMConfig:
    """Mamba2 (SSD) block parameters."""

    d_state: int = 64
    d_conv: int = 4
    expand: int = 2
    head_dim: int = 64         # SSD head dim (nheads = expand*d_model/head_dim)
    n_groups: int = 1
    chunk: int = 128           # SSD chunk length


@dataclasses.dataclass(frozen=True)
class MLAConfig:
    """DeepSeek multi-head latent attention."""

    q_lora_rank: Optional[int] = 1536   # None: a direct query projection
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128


@dataclasses.dataclass(frozen=True)
class XLSTMConfig:
    """xLSTM block mix: mLSTM (matrix memory) + sLSTM (scalar memory)."""

    slstm_every: int = 8       # 1 sLSTM per this many blocks (paper's [7:1])
    mlstm_proj_factor: float = 2.0
    slstm_proj_factor: float = 1.3333
    conv1d_kernel: int = 4


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                # dense | moe | ssm | hybrid | encoder | xlstm | vlm | audio
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    vocab_size: int

    causal: bool = True
    rope_theta: float = 10000.0
    qkv_bias: bool = False
    qk_norm: bool = False
    logit_softcap: float = 0.0
    logit_scale: float = 1.0
    norm: str = "rmsnorm"      # rmsnorm | layernorm
    rms_norm_eps: float = 1e-6  # epsilon of the blocks' and final RMSNorms
    act: str = "silu"          # silu | gelu
    glu: bool = True           # gated FFN (SwiGLU/GeGLU); False = plain MLP
    tie_embeddings: bool = False
    parallel_block: bool = False  # attention and FFN in parallel (command-r)

    moe: Optional[MoEConfig] = None
    ssm: Optional[SSMConfig] = None
    mla: Optional[MLAConfig] = None
    xlstm: Optional[XLSTMConfig] = None

    attn_every: int = 0        # hybrid (zamba2): shared attn block period; 0 = off
    mtp: bool = False          # DeepSeek multi-token-prediction aux head
    mla_absorb: bool = False   # decode MLA in latent space (perf variant)

    input_kind: str = "tokens"  # tokens | frames (precomputed modality embeddings)
    max_seq_len: int = 8192

    # runtime knobs (overridable per experiment)
    dtype: str = "bfloat16"
    remat: str = "full"        # none | full | selective
    scan_layers: bool = True
    attn_chunk: int = 1024     # memory-efficient attention KV chunk
    use_pallas: bool = False   # route hot paths through Pallas kernels

    def __post_init__(self) -> None:
        if self.family not in (
            "dense", "moe", "ssm", "hybrid", "encoder", "xlstm", "vlm", "audio"
        ):
            raise ValueError(f"unknown family {self.family}")
        if self.n_heads % max(self.n_kv_heads, 1) != 0:
            raise ValueError("n_heads must be divisible by n_kv_heads")

    # -- derived -------------------------------------------------------------
    @property
    def is_encoder(self) -> bool:
        return self.family in ("encoder", "audio") or not self.causal

    @property
    def sub_quadratic(self) -> bool:
        """Can this arch run the long_500k shape? (SSM/hybrid/linear recurrent)"""
        return self.family in ("ssm", "hybrid", "xlstm")

    def reduced(self, **overrides) -> "ModelConfig":
        """A tiny same-family variant for CPU smoke tests."""
        small = dict(
            n_layers=min(self.n_layers, 2 if self.attn_every == 0 else 4),
            d_model=128,
            n_heads=4,
            n_kv_heads=min(self.n_kv_heads, 2),
            head_dim=32,
            d_ff=256 if self.d_ff else 0,
            vocab_size=512,
            max_seq_len=256,
            dtype="float32",
            remat="none",
            scan_layers=False,
            attn_chunk=64,
        )
        if self.moe is not None:
            small["moe"] = dataclasses.replace(
                self.moe,
                n_experts=8,
                top_k=2,
                d_expert=64,
                first_k_dense=min(self.moe.first_k_dense, 1),
                d_shared=64 if self.moe.n_shared_experts else 0,
                # a share stays a share: 2 of the 8 experts held
                n_held=None if self.moe.n_held is None else 2,
                held_offset=0,
            )
        if self.ssm is not None:
            small["ssm"] = dataclasses.replace(
                self.ssm, d_state=16, head_dim=32, chunk=32
            )
        if self.mla is not None:
            small["mla"] = MLAConfig(
                q_lora_rank=None if self.mla.q_lora_rank is None else 64,
                kv_lora_rank=32,
                qk_nope_head_dim=32,
                qk_rope_head_dim=16,
                v_head_dim=32,
            )
        if self.attn_every:
            small["attn_every"] = 2
        small.update(overrides)
        return dataclasses.replace(self, **small)

    # -- accounting ------------------------------------------------------
    def param_count(self) -> int:
        """Analytic parameter count (exact for our implementation)."""
        from repro.models.model import count_params_analytic  # lazy, avoids cycle

        return count_params_analytic(self)

    def active_param_count(self) -> int:
        from repro.models.model import count_params_analytic

        return count_params_analytic(self, active_only=True)
