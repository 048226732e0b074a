"""Operations and bytes that the served steps of a latent-attention,
sparse-expert model require, from shapes (``flops`` does the dense
decoders).

Counted for the work the result needs, so that no implementation can
read over its roofline, whatever it computes on the way:

* a prefill chunk counts its real tokens, not its pad bucket; a decode
  tick its live lanes, not the pool's slots;
* the routed experts count the rows routed to held experts (``routed``,
  a layer: the engine's counted ``moe_rows`` where the caller has them,
  else the share's expected load, tokens x top_k x held / n_experts),
  not the capacity rows a dropless buffer computes; the expert weights
  read are those of the held experts that some token is expected to
  reach;
* attention counts each (query, key) pair once over the 192-wide scores
  and the 128-wide values, the least of its two forms: neither the
  expansion of latent rows to per-head K and V (the expanded form) nor
  the absorption of W_UK and W_UV into the query and output (the latent
  form) is counted;
* bytes count the weights once a call (the embedding's rows that the
  tokens read, not its table, when the head is untied), the live latent
  rows read (r_kv + rope a layer) and the rows written.

A matmul of (m x k) by (k x n) is 2mkn operations.
"""

from __future__ import annotations

from typing import Iterable, Optional, Tuple

from .model_mla_moe import MLAMoEDims


def attn_params(d: MLAMoEDims) -> int:
    """Weights of one layer's attention, the latent norm's included."""
    q = (d.d_model * d.n_heads * d.qk_head if d.q_lora_rank is None else
         d.d_model * d.q_lora_rank + d.q_lora_rank + d.q_lora_rank * d.n_heads * d.qk_head)
    kv = (d.d_model * (d.kv_lora_rank + d.qk_rope) + d.kv_lora_rank
          + d.kv_lora_rank * d.n_heads * (d.qk_nope + d.v_head))
    return q + kv + d.n_heads * d.v_head * d.d_model


def expert_params(d: MLAMoEDims) -> int:
    return 3 * d.d_model * d.d_expert


def shared_params(d: MLAMoEDims) -> int:
    return 3 * d.d_model * d.n_shared * d.d_expert


def param_count(d: MLAMoEDims) -> int:
    """Every stored parameter (the program's count)."""
    norms = 2 * d.d_model
    dense = attn_params(d) + norms + 3 * d.d_model * d.d_ff
    moe = (attn_params(d) + norms + d.d_model * d.n_experts + d.n_experts
           + d.held * expert_params(d) + shared_params(d))
    head = 0 if d.tied else d.d_model * d.vocab
    return (d.first_k_dense * dense + d.n_moe_layers * moe + d.vocab * d.d_model
            + d.d_model + head)


def latent_bytes_per_token(d: MLAMoEDims) -> int:
    """Bytes one cached token holds over all layers (the latent and the
    rope key)."""
    return d.n_layers * (d.kv_lora_rank + d.qk_rope) * d.param_bytes


def routed_rows(d: MLAMoEDims, tokens: int) -> float:
    """Rows one MoE layer's held experts take for ``tokens`` tokens, as
    the share's expected load."""
    return tokens * d.top_k * d.held / d.n_experts


def weight_bytes(d: MLAMoEDims, tokens: int) -> float:
    """Weights one call of ``tokens`` tokens reads: all but the experts
    and the untied embedding, the expected held experts reached, and the
    embedding rows gathered."""
    reached = d.held * (1.0 - (1.0 - d.top_k / d.n_experts) ** tokens)
    embed = tokens * d.d_model if not d.tied else 0
    rest = param_count(d) - d.n_moe_layers * d.held * expert_params(d) - (
        0 if d.tied else d.vocab * d.d_model)
    return d.param_bytes * (rest + embed + d.n_moe_layers * reached * expert_params(d))


def token_matmul_params(d: MLAMoEDims) -> int:
    """Attention weights each token multiplies through in one layer (the
    query, the latent and the output projections; not ``wkv_b``)."""
    q = (d.d_model * d.n_heads * d.qk_head if d.q_lora_rank is None else
         d.d_model * d.q_lora_rank + d.q_lora_rank * d.n_heads * d.qk_head)
    return q + d.d_model * (d.kv_lora_rank + d.qk_rope) + d.n_heads * d.v_head * d.d_model


def forward_flops(d: MLAMoEDims, tokens: int, pairs: int, head_rows: int,
                  routed: Optional[float] = None) -> float:
    """Forward operations for ``tokens`` through every layer, ``pairs``
    attended (query, key) pairs a layer, the head on ``head_rows`` rows,
    ``routed`` rows a MoE layer taken by held experts (None: the share's
    expected load)."""
    routed = routed_rows(d, tokens) if routed is None else routed
    attn = 2 * tokens * token_matmul_params(d) * d.n_layers
    attn += 2 * pairs * d.n_heads * (d.qk_head + d.v_head) * d.n_layers
    dense = 2 * tokens * 3 * d.d_model * d.d_ff * d.first_k_dense
    moe = d.n_moe_layers * (2 * tokens * (d.d_model * d.n_experts + shared_params(d))
                            + 2 * routed * expert_params(d))
    head = 2 * head_rows * d.d_model * d.vocab
    return attn + dense + moe + head


def _attn_pairs(start: int, n: int) -> int:
    return n * start + n * (n + 1) // 2


def prefill_cost(d: MLAMoEDims, start: int, n_tok: int,
                 routed: Optional[float] = None) -> Tuple[float, float]:
    """(operations, bytes) of one prefill chunk of ``n_tok`` real tokens
    written at ``start``; logits for the last row only."""
    flops = forward_flops(d, n_tok, _attn_pairs(start, n_tok), 1, routed)
    lat = latent_bytes_per_token(d)
    return flops, weight_bytes(d, n_tok) + lat * (start + n_tok) + lat * n_tok


def decode_cost(d: MLAMoEDims, contexts: Iterable[int],
                routed: Optional[float] = None) -> Tuple[float, float]:
    """(operations, bytes) of one decode tick whose live lanes attend
    over ``contexts`` rows each (the new token's row included)."""
    contexts = list(contexts)
    lanes = len(contexts)
    flops = forward_flops(d, lanes, sum(contexts), lanes, routed)
    lat = latent_bytes_per_token(d)
    return flops, weight_bytes(d, lanes) + lat * sum(contexts) + lat * lanes
