"""GPipe-style pipeline parallelism over a mesh axis.

``stage_params`` splits a layer-stacked parameter tree into per-stage
chunks; ``pipeline_forward`` runs the classic GPipe schedule: microbatch
``m`` enters stage 0 at tick ``m``, activations rotate stage-to-stage
with ``ppermute`` each tick, and the last stage emits microbatch ``m``
at tick ``m + n_stages - 1``. Total ticks: ``n_micro + n_stages - 1``
(the usual bubble); each device only ever holds its own stage's weights.

Expressed with ``shard_map`` so the per-stage compute is explicitly
local and the only communication is the neighbor exchange.
"""

from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

__all__ = ["stage_params", "pipeline_forward"]


def stage_params(params, n_stages: int):
    """Split layer-stacked params (L, ...) into (n_stages, L/n_stages, ...).

    Works leaf-wise on pytrees; every leaf's leading dim must be the
    layer dim and divisible by ``n_stages``.
    """

    def split(w):
        L = w.shape[0]
        if L % n_stages != 0:
            raise ValueError(f"{L} layers not divisible by {n_stages} stages")
        return w.reshape(n_stages, L // n_stages, *w.shape[1:])

    return jax.tree.map(split, params)


def pipeline_forward(
    layer_fn: Callable,
    staged_params,
    x: jax.Array,
    mesh,
    axis: str = "pipe",
) -> jax.Array:
    """Run ``layer_fn`` over all layers of ``staged_params`` in a GPipe
    schedule on the ``axis`` dim of ``mesh``.

    layer_fn: ``(layer_params, h) -> h`` for a single layer.
    staged_params: output of :func:`stage_params`; leading dim must equal
        the mesh axis size.
    x: (n_micro, microbatch, ...) microbatched inputs.

    Returns (n_micro, microbatch, ...) outputs, numerically identical to
    applying all layers sequentially to each microbatch.
    """
    if axis not in mesh.shape:
        axis = tuple(mesh.shape)[0]
    n_stages = mesh.shape[axis]
    leading = {w.shape[0] for w in jax.tree.leaves(staged_params)}
    if leading != {n_stages}:
        raise ValueError(
            f"staged_params leading dim(s) {sorted(leading)} != pipeline axis "
            f"{axis!r} size {n_stages}; re-split with stage_params(params, "
            f"{n_stages}) or pass the intended mesh axis"
        )
    n_micro = x.shape[0]
    ticks = n_micro + n_stages - 1
    perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

    def stage_apply(params, h):
        def body(carry, layer):
            return layer_fn(layer, carry), None

        out, _ = jax.lax.scan(body, h, params)
        return out

    def per_stage(params, xs):
        # params: (1, layers_per_stage, ...) local shard; xs replicated.
        params = jax.tree.map(lambda w: w[0], params)
        stage = jax.lax.axis_index(axis)

        def tick(t, carry):
            state, outs = carry
            # Stage 0 ingests microbatch t (clipped: the tail ticks feed
            # garbage that can never reach a valid output slot); other
            # stages consume the neighbor's activation from tick t-1.
            inp = jnp.where(
                stage == 0, xs[jnp.clip(t, 0, n_micro - 1)], state
            )
            h = stage_apply(params, inp)
            # The last stage finished microbatch t - (n_stages - 1).
            m = t - (n_stages - 1)
            outs = jnp.where(
                (stage == n_stages - 1) & (m >= 0),
                outs.at[jnp.clip(m, 0, n_micro - 1)].set(h),
                outs,
            )
            state = jax.lax.ppermute(h, axis, perm)
            return state, outs

        state0 = jnp.zeros_like(xs[0])
        outs0 = jnp.zeros_like(xs)
        _, outs = jax.lax.fori_loop(0, ticks, tick, (state0, outs0))
        # Outputs live on the last stage (zeros elsewhere): psum
        # replicates them so the caller sees one full array.
        return jax.lax.psum(outs, axis)

    fn = jax.shard_map(
        per_stage,
        mesh=mesh,
        in_specs=(P(axis), P()),
        out_specs=P(),
        check_vma=False,
    )
    return fn(staged_params, x)
