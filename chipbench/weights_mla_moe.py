"""Serving weights from a seed for a latent-attention, sparse-expert
configuration, in the parameter layout the program takes, in one jitted
call on the device.

The rule is ``weights.serving_rule``'s: each matrix has unit gain over its
own fan-in (per layer, per expert), norm scales are drawn about 1. The
selection bias, which no published config gives, is drawn N(0, 0.05^2)
per expert and kept in float32 (the configuration file's ``assumed``):
about the spread of the sigmoid scores' top-6 margins, so that it moves
some choices and not most.
"""

from __future__ import annotations

import functools
from typing import Dict

from .model_mla_moe import MLAMoEDims
from .weights import Leaf, _serving_leaf, program_seed

BIAS_STD = 0.05


def _stacked(tree, n: int):
    """One layer's leaves as a segment of ``n`` layers holds them (the
    program stacks a segment of one layer not at all)."""
    if n == 1:
        return tree
    import jax

    return jax.tree.map(lambda l: Leaf((n, *l.shape), l.init, l.fan_in), tree,
                        is_leaf=lambda x: isinstance(x, Leaf))


def layout(d: MLAMoEDims) -> Dict:
    """The program's parameter tree: a segment of dense layers, then one
    of MoE layers."""
    D, H = d.d_model, d.n_heads
    attn = {
        "wkv_a": Leaf((D, d.kv_lora_rank + d.qk_rope), "scaled", D),
        "kv_norm": {"scale": Leaf((d.kv_lora_rank,), "ones")},
        "wkv_b": Leaf((d.kv_lora_rank, H, d.qk_nope + d.v_head), "scaled", d.kv_lora_rank),
        "wo": Leaf((H, d.v_head, D), "scaled", H * d.v_head),
    }
    if d.q_lora_rank is None:
        attn["wq"] = Leaf((D, H, d.qk_head), "scaled", D)
    else:
        attn.update(wq_a=Leaf((D, d.q_lora_rank), "scaled", D),
                    q_norm={"scale": Leaf((d.q_lora_rank,), "ones")},
                    wq_b=Leaf((d.q_lora_rank, H, d.qk_head), "scaled", d.q_lora_rank))

    def swiglu(f):
        return {"w_in": Leaf((D, f), "scaled", D), "w_gate": Leaf((D, f), "scaled", D),
                "w_out": Leaf((f, D), "scaled", f)}

    def block(ffn):
        return {"attn": attn, "attn_norm": {"scale": Leaf((D,), "ones")},
                "ffn": ffn, "mlp_norm": {"scale": Leaf((D,), "ones")}}

    E, de = d.held, d.d_expert
    experts = {
        "router": Leaf((D, d.n_experts), "scaled", D),
        "router_bias": Leaf((d.n_experts,), "selection_bias"),
        "w_in": Leaf((E, D, de), "scaled", D),
        "w_gate": Leaf((E, D, de), "scaled", D),
        "w_out": Leaf((E, de, D), "scaled", de),
        "shared": swiglu(d.n_shared * de),
    }
    stack = [_stacked(block(swiglu(d.d_ff)), d.first_k_dense),
             _stacked(block(experts), d.n_moe_layers)]
    tree = {"embed": Leaf((d.vocab, D), "normal"),
            "final_norm": {"scale": Leaf((D,), "ones")},
            "stack": [s for s, n in zip(stack, (d.first_k_dense, d.n_moe_layers)) if n]}
    if not d.tied:
        tree["head"] = Leaf((D, d.vocab), "scaled", D)
    return tree


def _leaf(key, leaf: Leaf, dtype):
    import jax
    import jax.numpy as jnp

    if leaf.init == "selection_bias":
        return BIAS_STD * jax.random.normal(key, leaf.shape, jnp.float32)
    return _serving_leaf(key, leaf, dtype)


def _init(d: MLAMoEDims, dtype_name: str, key):
    import jax
    import jax.numpy as jnp

    dtype = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[dtype_name]
    leaves, treedef = jax.tree.flatten(layout(d), is_leaf=lambda x: isinstance(x, Leaf))
    keys = jax.random.split(key, len(leaves))
    return jax.tree.unflatten(treedef, [_leaf(k, l, dtype) for k, l in zip(keys, leaves)])


def make_weights(d: MLAMoEDims, dtype_name: str, seed: int):
    """All weights in one jitted call on the device."""
    import jax

    fn = jax.jit(functools.partial(_init, d, dtype_name))
    return fn(jax.random.PRNGKey(program_seed(seed)))
