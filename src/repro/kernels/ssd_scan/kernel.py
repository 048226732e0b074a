"""Pallas TPU kernel for the Mamba2 SSD chunked scan.

Grid = (batch, heads, n_chunks); the chunk dim is LAST (sequential on
TPU), so the inter-chunk SSM state (N x P) is carried in VMEM scratch —
the recurrence never touches HBM. Per chunk the kernel does three
MXU matmuls ((Q,N)@(N,P), (Q,N)@(N,Q), (Q,Q)@(Q,P)) plus a cumulative-
decay mask, which is exactly the SSD "dual" form mapped onto the
128x128 systolic array (Q = chunk = 128 by default).

Inputs are pre-activated: dt already softplus'd (+bias), A = -exp(a_log).
The wrapper lays every input out as (batch, heads, chunk, Q, features)
and computes the per-chunk cumulative decay (an elementwise prefix),
so the kernel body is matmuls, exps and one broadcast subtraction. The
D-skip and gating stay in the surrounding jnp block (cheap,
bandwidth-bound there anyway).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["ssd_scan_fwd"]


def _kernel(x_ref, dt_ref, acol_ref, arow_ref, bt_ref, c_ref, y_ref,
            state_ref):
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    x = x_ref[0, 0, 0].astype(jnp.float32)           # (Q, P)
    dt = dt_ref[0, 0, 0]                             # (Q, 1)
    a_col = acol_ref[0, 0, 0]                        # (Q, 1) cumulative dt*A
    a_row = arow_ref[0, 0, 0]                        # (1, Q) the same, as a row
    Bt = bt_ref[0, 0, 0].astype(jnp.float32)         # (N, Q)
    Cm = c_ref[0, 0, 0].astype(jnp.float32)          # (Q, N)
    Q = x.shape[0]
    a_total = a_row[:, Q - 1:]                       # (1, 1)

    state = state_ref[...]                           # (N, P)

    # Inter-chunk: y_i = exp(a_cum_i) * C_i @ state_in.
    y_inter = jnp.exp(a_col) * jax.lax.dot_general(
        Cm, state, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )                                                # (Q, P)

    # Intra-chunk: scores = (C B^T) o L, y += scores @ (dt * x).
    seg = a_col - a_row                              # (Q, Q)
    iq = jax.lax.broadcasted_iota(jnp.int32, seg.shape, 0)
    jq = jax.lax.broadcasted_iota(jnp.int32, seg.shape, 1)
    L = jnp.where(iq >= jq, jnp.exp(seg), 0.0)
    scores = jax.lax.dot_general(
        Cm, Bt, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    ) * L                                            # (Q, Q)
    y = y_inter + jax.lax.dot_general(
        scores, x * dt, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    # State update: S <- exp(a_total) S + B^T @ (exp(a_total - a_cum) dt x).
    w = jnp.exp(a_total - a_col) * dt                # (Q, 1)
    state_ref[...] = jnp.exp(a_total) * state + jax.lax.dot_general(
        Bt, x * w, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    y_ref[0, 0, 0] = y.astype(y_ref.dtype)


def ssd_scan_fwd(
    x: jax.Array,    # (B, S, H, P)
    dt: jax.Array,   # (B, S, H)  — softplus'd
    A: jax.Array,    # (H,)       — negative
    Bm: jax.Array,   # (B, S, G, N)
    Cm: jax.Array,   # (B, S, G, N)
    *,
    chunk: int = 128,
    interpret: bool = False,
) -> jax.Array:
    B, S, H, P = x.shape
    G, N = Bm.shape[2], Bm.shape[3]
    hg = H // G
    Q = min(chunk, S)
    pad = (-S) % Q
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        # Pad dt with ZEROS: decay exp(0*A)=1, update dt*...=0 — inert.
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        Bm = jnp.pad(Bm, ((0, 0), (0, pad), (0, 0), (0, 0)))
        Cm = jnp.pad(Cm, ((0, 0), (0, pad), (0, 0), (0, 0)))
    Sp = S + pad
    nc = Sp // Q

    def chunked(t):
        """(B, S, H, F) -> (B, H, nc, Q, F): heads ahead of the sequence and
        one chunk per block, so every block's last two dims are the full
        (Q, F) of its array — the tiling rule holds for any Q and F."""
        return jnp.moveaxis(t, 2, 1).reshape(B, t.shape[2], nc, Q, t.shape[3])

    # The per-chunk cumulative decay is a cheap elementwise prefix in XLA;
    # the kernel gets it both as a column and as a row so that the
    # segment-sum matrix is one broadcast subtraction.
    dt_c = chunked(dt.astype(jnp.float32)[..., None])              # (B,H,nc,Q,1)
    a_col = jnp.cumsum(dt_c * A.astype(jnp.float32)[None, :, None, None, None],
                       axis=3)
    a_row = jnp.swapaxes(a_col, 3, 4)                              # (B,H,nc,1,Q)
    Bt = jnp.swapaxes(chunked(Bm), 3, 4)                           # (B,G,nc,N,Q)

    def per_head(shape):
        return pl.BlockSpec(shape, lambda b, h, c: (b, h, c, 0, 0))

    def per_group(shape):
        return pl.BlockSpec(shape, lambda b, h, c, hg=hg: (b, h // hg, c, 0, 0))

    out = pl.pallas_call(
        _kernel,
        grid=(B, H, nc),
        in_specs=[
            per_head((1, 1, 1, Q, P)),
            per_head((1, 1, 1, Q, 1)),
            per_head((1, 1, 1, Q, 1)),
            per_head((1, 1, 1, 1, Q)),
            per_group((1, 1, 1, N, Q)),
            per_group((1, 1, 1, Q, N)),
        ],
        out_specs=per_head((1, 1, 1, Q, P)),
        out_shape=jax.ShapeDtypeStruct((B, H, nc, Q, P), x.dtype),
        scratch_shapes=[pltpu.VMEM((N, P), jnp.float32)],
        interpret=interpret,
    )(chunked(x), dt_c, a_col, a_row, Bt, chunked(Cm))
    out = jnp.moveaxis(out.reshape(B, H, Sp, P), 1, 2)
    return out[:, :S]
