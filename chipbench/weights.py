"""Weights from a seed, in the parameter layout the program takes.

Two rules, each one jitted call on the device:

* ``program_rule``: a copy of the program's own initializer
  (``repro.models.layers.init_from_specs``): one key per leaf, split from
  the seed in the tree's flatten order; ``normal`` leaves at 0.02,
  ``scaled`` leaves at 1/sqrt(product of all but the last axis, the
  stacked layer axis included), biases 0, norm scales 1. The training
  loop makes its weights this way inside ``train()``; the reference
  makes the same weights from the same seed with this copy.
* ``serving_rule``: the benchmark's weights for served cells. Each matrix
  has unit gain over its own fan-in (per layer), QKV biases and norm
  scales are drawn too, so that every part of the block moves the
  logits and greedy decoding does not collapse onto one token.
"""

from __future__ import annotations

import functools
import math
from typing import Dict

import numpy as np

from .model import Dims


class Leaf:
    """Shape and initializer of one parameter (a pytree leaf)."""

    def __init__(self, shape, init: str, fan_in: int = 1):
        self.shape = tuple(int(s) for s in shape)
        self.init = init
        self.fan_in = int(fan_in)     # contracted size of one layer's matrix


def layout(d: Dims) -> Dict:
    """The program's parameter tree for a dense decoder (one stacked
    segment of ``n_layers`` blocks)."""
    L, D, H, K, E, F = (d.n_layers, d.d_model, d.n_heads, d.n_kv_heads,
                        d.head_dim, d.d_ff)
    attn = {
        "wq": Leaf((L, D, H, E), "scaled", D),
        "wk": Leaf((L, D, K, E), "scaled", D),
        "wv": Leaf((L, D, K, E), "scaled", D),
        "wo": Leaf((L, H, E, D), "scaled", H * E),
    }
    if d.qkv_bias:
        attn["bq"] = Leaf((L, H, E), "bias")
        attn["bk"] = Leaf((L, K, E), "bias")
        attn["bv"] = Leaf((L, K, E), "bias")
    block = {
        "attn": attn,
        "attn_norm": {"scale": Leaf((L, D), "ones")},
        "ffn": {"w_in": Leaf((L, D, F), "scaled", D),
                "w_gate": Leaf((L, D, F), "scaled", D),
                "w_out": Leaf((L, F, D), "scaled", F)},
        "mlp_norm": {"scale": Leaf((L, D), "ones")},
    }
    tree = {"embed": Leaf((d.vocab, D), "normal"),
            "final_norm": {"scale": Leaf((D,), "ones")},
            "stack": [block]}
    if not d.tied:
        tree["head"] = Leaf((D, d.vocab), "scaled", D)
    return tree


def _is_leaf(x) -> bool:
    return isinstance(x, Leaf)


def _program_leaf(key, leaf: Leaf, dtype):
    import jax
    import jax.numpy as jnp

    if leaf.init == "bias":
        return jnp.zeros(leaf.shape, dtype)
    if leaf.init == "ones":
        return jnp.ones(leaf.shape, dtype)
    if leaf.init == "normal":
        return (jax.random.normal(key, leaf.shape, jnp.float32) * 0.02 * 1.0).astype(dtype)
    fan = max(int(np.prod(leaf.shape[:-1])), 1)
    std = 1.0 / math.sqrt(fan)
    return (jax.random.normal(key, leaf.shape, jnp.float32) * std).astype(dtype)


def _serving_leaf(key, leaf: Leaf, dtype):
    import jax
    import jax.numpy as jnp

    x = jax.random.normal(key, leaf.shape, jnp.float32)
    if leaf.init == "bias":
        return (0.5 * x).astype(dtype)
    if leaf.init == "ones":
        return (1.0 + 0.1 * x).astype(dtype)
    if leaf.init == "normal":
        return (0.02 * x).astype(dtype)
    return (x / math.sqrt(leaf.fan_in)).astype(dtype)


def _init(rule, d: Dims, dtype_name: str, key):
    import jax
    import jax.numpy as jnp

    dtype = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[dtype_name]
    leaves, treedef = jax.tree.flatten(layout(d), is_leaf=_is_leaf)
    keys = jax.random.split(key, len(leaves))
    fn = _program_leaf if rule == "program" else _serving_leaf
    return jax.tree.unflatten(treedef, [fn(k, l, dtype) for k, l in zip(keys, leaves)])


def program_seed(seed: int) -> int:
    """The seed handed to the program and to ``PRNGKey`` (31 bits)."""
    return int(seed) % (2 ** 31)


def make_weights(rule: str, d: Dims, dtype_name: str, seed: int, out_shardings=None):
    """All weights in one jitted call on the device."""
    import jax

    fn = jax.jit(functools.partial(_init, rule, d, dtype_name),
                 out_shardings=out_shardings)
    return fn(jax.random.PRNGKey(program_seed(seed)))
