"""Operations and bytes that each program kind requires, from shapes.

Counted for the work the result needs, not for what the program happens
to compute: a training step counts only the rows whose gradient is
applied, and no recomputation (remat); a prefill chunk counts its real
tokens, not its pad bucket; a decode tick counts its live lanes and the
KV rows they hold, not the arena's capacity. A matmul of (m x k) by
(k x n) is 2mkn operations; causal attention counts the (query, key)
pairs at or below the diagonal, twice (scores and values) for each.
"""

from __future__ import annotations

from typing import Iterable, Tuple

from .model import Dims


def layer_matmul_params(d: Dims) -> int:
    """Weights one token multiplies through in one block."""
    attn = d.d_model * d.head_dim * (2 * d.n_heads + 2 * d.n_kv_heads)
    return attn + 3 * d.d_model * d.d_ff


def param_count(d: Dims) -> int:
    """Every stored parameter (the program's count, norms and biases in)."""
    per_layer = layer_matmul_params(d) + 2 * d.d_model
    if d.qkv_bias:
        per_layer += d.head_dim * (d.n_heads + 2 * d.n_kv_heads)
    head = 0 if d.tied else d.d_model * d.vocab
    return d.n_layers * per_layer + d.vocab * d.d_model + d.d_model + head


def param_bytes(d: Dims) -> int:
    return param_count(d) * d.param_bytes


def kv_bytes_per_token(d: Dims) -> int:
    """Bytes one cached token holds over all layers (K and V)."""
    return d.n_layers * 2 * d.n_kv_heads * d.head_dim * d.param_bytes


def _attn_pairs(start: int, n: int) -> int:
    """(query, key) pairs of ``n`` causal queries at positions
    start .. start+n-1 (each sees every key up to itself)."""
    return n * start + n * (n + 1) // 2


def forward_flops(d: Dims, tokens: int, pairs: int, head_rows: int) -> int:
    """Forward operations for ``tokens`` through every block, ``pairs``
    attended (query, key) pairs per layer, and the logits head on
    ``head_rows`` rows."""
    dense = 2 * tokens * d.n_layers * layer_matmul_params(d)
    attn = 4 * d.n_heads * d.head_dim * pairs * d.n_layers
    head = 2 * head_rows * d.d_model * d.vocab
    return dense + attn + head


def train_step_flops(d: Dims, applied_rows: int, seq_len: int) -> int:
    """Forward and backward (three forward passes' worth) over the rows
    whose gradient the step applies."""
    tokens = applied_rows * seq_len
    pairs = applied_rows * _attn_pairs(0, seq_len)
    return 3 * forward_flops(d, tokens, pairs, tokens)


def prefill_cost(d: Dims, start: int, n_tok: int) -> Tuple[int, int]:
    """(operations, bytes) of one prefill chunk of ``n_tok`` real tokens
    written at ``start``: the weights once, the KV rows attended read,
    the chunk's rows written; logits for the last row only."""
    flops = forward_flops(d, n_tok, _attn_pairs(start, n_tok), 1)
    kv = kv_bytes_per_token(d)
    nbytes = param_bytes(d) + kv * (start + n_tok) + kv * n_tok
    return flops, nbytes


def decode_cost(d: Dims, contexts: Iterable[int]) -> Tuple[int, int]:
    """(operations, bytes) of one decode tick whose live lanes attend
    over ``contexts`` rows each (the new token's row included)."""
    contexts = list(contexts)
    lanes = len(contexts)
    flops = forward_flops(d, lanes, sum(contexts), lanes)
    kv = kv_bytes_per_token(d)
    nbytes = param_bytes(d) + kv * sum(contexts) + kv * lanes
    return flops, nbytes
