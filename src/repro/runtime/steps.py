"""The jitted step functions: train_step / prefill_step / decode_step.

These are the units the dry-run lowers and the production loop executes.
``make_train_step`` builds a pure function

    (params, opt_state, batch) -> (params, opt_state, metrics)

where batch = {inputs, labels, [mask], worker_mask, lr}. The fastest-k
worker mask enters as DATA (recompile-free across stages with the same
shapes); per-stage beta changes the batch shape and hits the compile
cache keyed by shape — by design (DESIGN.md §2.3).
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.dist.collectives import contributors, masked_weighted_ce
from repro.dist.sharding import activation_sharding
from repro.models.model import Model
from repro.optim.optimizers import (
    Optimizer,
    apply_updates,
    clip_by_global_norm,
    global_norm,
)

__all__ = [
    "make_train_step",
    "make_prefill_step",
    "make_slot_prefill_step",
    "make_decode_step",
    "make_slot_decode_step",
    "make_slot_verify_step",
    "make_slot_replay_step",
    "make_init_fn",
]


def make_train_step(
    model: Model,
    optimizer: Optimizer,
    *,
    clip_norm: Optional[float] = 1.0,
    accum_steps: int = 1,
    accum_dtype=jnp.float32,
    param_shardings=None,
    gather_shardings=None,
) -> Callable:
    """param_shardings: optional pytree of NamedShardings matching params;
    gradients are constrained to them (scatter-formed grads — embedding
    rows in particular — otherwise come out replicated under SPMD).

    gather_shardings: ZeRO-1 mode — params are all-gathered ONCE per step
    to this (non-FSDP) layout and reused across every remat pass and
    accumulation microbatch; gradients reduce-scatter back to the sharded
    layout at the boundary. Kills the per-layer-per-microbatch FSDP weight
    re-gather traffic (§Perf)."""
    cfg = model.cfg

    def _gather(params):
        if gather_shardings is None:
            return params
        return jax.tree.map(
            lambda p, sh: jax.lax.with_sharding_constraint(p, sh),
            params, gather_shardings,
        )

    def _pin(grads):
        if param_shardings is None:
            return grads
        return jax.tree.map(
            lambda g, sh: jax.lax.with_sharding_constraint(g, sh),
            grads, param_shardings,
        )

    def loss_fn(params, batch):
        inputs, labels = batch["inputs"], batch["labels"]
        positions = jnp.arange(labels.shape[1])
        h, aux = model.hidden(params, inputs, positions)
        logits = model.logits(params, h)
        ce, denom = masked_weighted_ce(
            logits, labels, batch.get("mask"), batch.get("worker_mask")
        )
        loss = ce
        if cfg.moe is not None:
            loss = loss + cfg.moe.router_aux_weight * aux
        if cfg.mtp:
            mask = batch.get("mask")
            if mask is None:
                mask = jnp.ones(labels.shape, jnp.float32)
            mtp = model._mtp_loss(params, h, inputs, labels, mask, positions)
            loss = loss + 0.3 * mtp
        return loss, {"ce": ce, "aux": aux, "denom": denom}

    def _grads_direct(params, batch):
        (loss, metrics), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            _gather(params), batch
        )
        return loss, metrics, _pin(grads)

    def _grads_accum(params, batch):
        """Microbatched gradient accumulation (scan over A slices).

        The batch is worker-major; each worker's b_w examples are split
        evenly across the A microbatches so the fastest-k example
        weighting stays exact. Per-microbatch gradients are combined
        weighted by their masked token counts (metrics['denom']), which
        reproduces the single-big-batch gradient bit-for-bit in exact
        arithmetic."""
        A = accum_steps
        n = batch["worker_mask"].shape[0]
        B = batch["inputs"].shape[0]
        bw = B // n
        assert bw % A == 0, f"per-worker batch {bw} not divisible by accum {A}"

        def resh(x):
            x = x.reshape(n, A, bw // A, *x.shape[1:])
            x = jnp.moveaxis(x, 1, 0)
            return x.reshape(A, n * (bw // A), *x.shape[3:])

        mb = {k: resh(batch[k]) for k in ("inputs", "labels") if k in batch}
        if batch.get("mask") is not None:
            mb["mask"] = resh(batch["mask"])

        params_g = _gather(params)  # ZeRO-1: one gather, reused by all microbatches

        def body(carry, xs):
            gsum, lsum, dsum, auxsum = carry
            micro = dict(xs)
            micro["worker_mask"] = batch["worker_mask"]
            micro["lr"] = batch["lr"]
            (loss, metrics), grads = jax.value_and_grad(loss_fn, has_aux=True)(
                params_g, micro
            )
            grads = _pin(grads)
            w = metrics["denom"]
            gsum = jax.tree.map(
                lambda a, g: (a + w * g.astype(jnp.float32)).astype(accum_dtype),
                gsum, grads,
            )
            return (gsum, lsum + w * loss, dsum + w, auxsum + metrics["aux"]), None

        gsum0 = jax.tree.map(lambda p: jnp.zeros(p.shape, accum_dtype), params)
        (gsum, lsum, dsum, auxsum), _ = jax.lax.scan(
            body, (gsum0, 0.0, jnp.float32(0.0), jnp.float32(0.0)), mb
        )
        dsum = jnp.maximum(dsum, 1.0)
        grads = jax.tree.map(lambda g: g.astype(jnp.float32) / dsum, gsum)
        loss = lsum / dsum
        metrics = {"ce": loss, "aux": auxsum / accum_steps, "denom": dsum}
        return loss, metrics, grads

    def train_step(params, opt_state, batch):
        if accum_steps > 1:
            loss, metrics, grads = _grads_accum(params, batch)
        else:
            loss, metrics, grads = _grads_direct(params, batch)
        if clip_norm is not None:
            grads, gnorm = clip_by_global_norm(grads, clip_norm)
        else:
            gnorm = global_norm(grads)
        updates, opt_state = optimizer.update(grads, opt_state, params, batch["lr"])
        params = apply_updates(params, updates)
        metrics = dict(metrics)
        metrics.update(
            loss=loss,
            grad_norm=gnorm,
            contributors=(
                contributors(batch["worker_mask"])
                if batch.get("worker_mask") is not None
                else jnp.asarray(0.0)
            ),
        )
        return params, opt_state, metrics

    return train_step


def make_prefill_step(model: Model) -> Callable:
    def prefill_step(params, inputs):
        return model.prefill(params, inputs)

    return prefill_step


def make_slot_prefill_step(model: Model, moe_rows: bool = False) -> Callable:
    """Cache-writing batched prefill for the serving engine.

    (params, inputs (B,P) right-padded, caches, length (B,), start_index,
    [block_tables]) -> (last-valid logits (B,1,V), caches). Like the
    fastest-k ``worker_mask``, the ragged-length information enters as
    DATA — one compile per (B, P-bucket) shape, re-used across every
    admission. ``block_tables`` (B, T) routes the chunk's cache rows
    through paged arenas (None = contiguous slot stripes). ``moe_rows``
    adds a third result: the held experts' rows the chunk's real tokens
    took, summed (``Model.prefill_with_cache``)."""

    def slot_prefill_step(params, inputs, caches, length, start_index,
                          block_tables=None):
        out = model.prefill_with_cache(
            params, inputs, caches, length=length, start_index=start_index,
            block_tables=block_tables, moe_rows=moe_rows,
        )
        return (*out[:2], out[2].sum()) if moe_rows else out

    return slot_prefill_step


def make_decode_step(model: Model) -> Callable:
    def decode_step(params, token, caches, cache_index):
        return model.decode_step(params, token, caches, cache_index)

    return decode_step


def make_slot_decode_step(model: Model, moe_rows: bool = False) -> Callable:
    """One decode tick over the whole slot pool.

    ``cache_index`` is the per-slot position vector (n_slots,) — every
    slot sits at its own length; free slots ride along as masked lanes
    (their writes land in dead rows and are overwritten at allocation),
    so occupancy never changes the compiled shape. ``moe_rows`` adds a
    third result, each lane's held-expert rows (``Model.decode_step``)."""

    def slot_decode_step(params, tokens, caches, cache_index, block_tables=None):
        return model.decode_step(
            params, tokens, caches, cache_index, block_tables=block_tables,
            moe_rows=moe_rows,
        )

    return slot_decode_step


def make_slot_verify_step(model: Model) -> Callable:
    """Speculative verify over the whole slot pool: one fused multi-token
    call scores every lane's draft window at its own position.

    (params, tokens (B, S), caches, n_input (B,), positions (B,),
    [block_tables]) -> (greedy tokens (B, S) int32, caches). Per-lane
    draft lengths ride along as DATA (``n_input``; 0 = free lane, 1 =
    plain decode, 1 + gamma = speculating) — one compile per window
    width S covers every round. The caches come back committed per the
    family-specific contract of ``Model.verify_with_cache``: the caller
    applies the exact-argmax acceptance rule to the returned greedy
    tokens and rewinds its per-slot positions to the accepted prefix."""

    def slot_verify_step(params, tokens, caches, n_input, positions,
                         block_tables=None):
        logits, caches = model.verify_with_cache(
            params, tokens, caches, n_input, positions,
            block_tables=block_tables,
        )
        return jnp.argmax(logits, axis=-1).astype(jnp.int32), caches

    return slot_verify_step


def make_slot_replay_step(model: Model) -> Callable:
    """Draft-side resync after a verify round: commit exactly ``n_input``
    already-known tokens per lane into the caches (no acceptance chain —
    the tokens ARE the committed stream). Same shapes as
    ``make_slot_verify_step``; returns only the caches."""

    def slot_replay_step(params, tokens, caches, n_input, positions,
                         block_tables=None):
        _, caches = model.verify_with_cache(
            params, tokens, caches, n_input, positions,
            block_tables=block_tables, greedy_commit=False,
        )
        return caches

    return slot_replay_step


def make_init_fn(model: Model, optimizer: Optimizer) -> Callable:
    """(rng) -> (params, opt_state); jit-able so init can be sharded."""

    def init(rng):
        params = model.init(rng)
        return params, optimizer.init(params)

    return init
