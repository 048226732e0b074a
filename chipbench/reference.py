"""Plain reference of a dense GQA decoder, in float32.

Written from the published description of the Llama/Qwen2 block:
pre-norm RMSNorm; attention with rotary embeddings (rotate-half form,
base ``rope_theta``), optional biases on the Q, K and V projections, and
grouped K/V heads (query head h reads K/V head h // group); SwiGLU
feed-forward, silu(x W_gate) * (x W_up) W_down; a final RMSNorm and a
head tied to the embedding (or its own). Every matmul runs at
``Precision.HIGHEST``. It imports nothing of the program; it reads the
weights in the program's parameter layout (``weights.layout``), as
stored (bfloat16), and widens each layer to float32 as it runs.

Departures, each so that it computes what the configuration states:
stored parameters are rounded to the configured dtype after each
optimizer update (the configuration keeps bfloat16 weights), and the
RMSNorm epsilon is the configuration file's.

``quant="fp8"`` is the control: the program's arithmetic one precision
down. Wherever the program holds a bfloat16 value (every matmul operand
and output, each norm's output, the residual stream, the logits), the
control holds it scaled per tensor to the e4m3 range and rounded to
float8_e4m3fn; every matmul's output gradient is scaled per tensor and
rounded to float8_e5m2 before the backward matmuls take it. Softmax and
the loss stay in float32, as in the program.
"""

from __future__ import annotations

import functools
import math
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from .model import Dims

HIGHEST = jax.lax.Precision.HIGHEST
E4M3 = (jnp.float8_e4m3fn, 448.0)
E5M2 = (jnp.float8_e5m2, 57344.0)


def _round(x, fmt):
    """``x`` scaled per tensor to the format's range and rounded to it."""
    dtype, top = fmt
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, amax / top, 1.0)
    return (x / scale).astype(dtype).astype(jnp.float32) * scale


def _q(x, quant):
    """Forward operand in e4m3; the gradient passes straight through."""
    if quant is None:
        return x
    return x + jax.lax.stop_gradient(_round(x, E4M3) - x)


@jax.custom_vjp
def _grad_e5m2(y):
    return y


def _grad_e5m2_fwd(y):
    return y, None


def _grad_e5m2_bwd(_, ct):
    return (_round(ct, E5M2),)


_grad_e5m2.defvjp(_grad_e5m2_fwd, _grad_e5m2_bwd)


def _mm(spec, a, b, quant):
    y = jnp.einsum(spec, _q(a, quant), _q(b, quant), precision=HIGHEST)
    return y if quant is None else _q(_grad_e5m2(y), quant)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _rope(x, pos, theta):
    e = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, e, 2, dtype=jnp.float32) / e)
    ang = pos.astype(jnp.float32)[:, None] * inv           # (S, E/2)
    sin, cos = jnp.sin(ang)[:, None, :], jnp.cos(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _block(d: Dims, quant, x, p):
    """One decoder block; ``p`` holds one layer's (stored) weights."""
    p = jax.tree.map(lambda w: w.astype(jnp.float32), p)
    a = p["attn"]
    B, S, _ = x.shape
    pos = jnp.arange(S)
    h = _q(_rms(x, p["attn_norm"]["scale"], d.rms_eps), quant)
    q = _mm("bsd,dhe->bshe", h, a["wq"], quant)
    k = _mm("bsd,dhe->bshe", h, a["wk"], quant)
    v = _mm("bsd,dhe->bshe", h, a["wv"], quant)
    if d.qkv_bias:
        q, k, v = _q(q + a["bq"], quant), _q(k + a["bk"], quant), _q(v + a["bv"], quant)
    q, k = _q(_rope(q, pos, d.rope_theta), quant), _q(_rope(k, pos, d.rope_theta), quant)
    q = q.reshape(B, S, d.n_kv_heads, d.group, d.head_dim)
    s = _mm("bqkge,bske->bkgqs", q, k, quant) / math.sqrt(d.head_dim)
    causal = pos[:, None] >= pos[None, :]
    s = jnp.where(causal, s, -jnp.inf)
    w = jax.nn.softmax(s, axis=-1)
    o = _mm("bkgqs,bske->bqkge", w, v, quant).reshape(B, S, d.n_heads, d.head_dim)
    x = _q(x + _mm("bshe,hed->bsd", o, a["wo"], quant), quant)
    h = _q(_rms(x, p["mlp_norm"]["scale"], d.rms_eps), quant)
    f = p["ffn"]
    g = _q(jax.nn.silu(_mm("bsd,df->bsf", h, f["w_gate"], quant)), quant)
    u = _mm("bsd,df->bsf", h, f["w_in"], quant)
    return _q(x + _mm("bsf,fd->bsd", g * u, f["w_out"], quant), quant)


def hidden(d: Dims, quant, params, tokens):
    """Final-norm hidden states (B, S, D) for token ids (B, S)."""
    x = _q(params["embed"][tokens].astype(jnp.float32), quant)
    body = jax.checkpoint(functools.partial(_block, d, quant))
    x, _ = jax.lax.scan(lambda c, p: (body(c, p), None), x, params["stack"][0])
    return _q(_rms(x, params["final_norm"]["scale"].astype(jnp.float32), d.rms_eps), quant)


def logits(d: Dims, quant, params, h):
    if d.tied:
        return _mm("...d,vd->...v", h, params["embed"].astype(jnp.float32), quant)
    return _mm("...d,dv->...v", h, params["head"].astype(jnp.float32), quant)


# ---------------------------------------------------------------------------
# training: three steps of masked fastest-k SGD with clipped AdamW
# ---------------------------------------------------------------------------

def _nll_sum(d: Dims, quant, params, inputs, labels):
    z = logits(d, quant, params, hidden(d, quant, params, inputs))
    lse = jax.nn.logsumexp(z, axis=-1)
    gold = jnp.take_along_axis(z, labels[..., None], axis=-1)[..., 0]
    return jnp.sum(lse - gold)


def leaf_norms(tree) -> List[float]:
    return [float(x) for x in jax.tree.leaves(jax.tree.map(
        lambda a: jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32)))), tree))]


def applied_rows(mask: np.ndarray, rows_per_worker: int) -> np.ndarray:
    """Row indices of the workers whose gradient the step applies (the
    batch is worker-major)."""
    return np.concatenate([np.arange(w * rows_per_worker, (w + 1) * rows_per_worker)
                           for w in np.nonzero(mask)[0]])


def train_steps(d: Dims, hp: Dict, params, batches: Sequence[Dict], masks,
                rows_per_worker: int, quant: Optional[str] = None,
                fault: Optional[str] = None) -> Dict:
    """Run ``len(batches)`` steps from ``params`` and return what is
    compared: each step's loss, the per-leaf norm of the first step's
    clipped gradient, and the per-leaf norms of the parameters' change
    after the first step and after the last.

    The gradient of the applied rows is accumulated one worker's rows at
    a time. ``fault`` plants a known fault in place of the program
    (``"half_batch"``: the mean over half of the applied rows only, or
    over the first half of the positions where one row is applied;
    ``"frozen"``: the update is never applied)."""
    grad_fn = jax.jit(jax.value_and_grad(functools.partial(_nll_sum, d, quant)))
    add = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b), donate_argnums=0)
    # differentiate a float32 copy: the gradient of a bfloat16 leaf would
    # come back rounded to bfloat16
    widen = jax.jit(lambda t: jax.tree.map(lambda p: p.astype(jnp.float32), t))

    @jax.jit
    def adam(params, m, v, g, t, denom):
        g = jax.tree.map(lambda x: x / denom, g)
        gn = jnp.sqrt(sum(jnp.sum(jnp.square(x)) for x in jax.tree.leaves(g)))
        scale = jnp.minimum(1.0, hp["clip_norm"] / jnp.maximum(gn, 1e-9))
        g = jax.tree.map(lambda x: x * scale, g)
        b1, b2 = hp["b1"], hp["b2"]
        m = jax.tree.map(lambda a, x: b1 * a + (1 - b1) * x, m, g)
        v = jax.tree.map(lambda a, x: b2 * a + (1 - b2) * x * x, v, g)
        c1, c2 = 1 - b1 ** t, 1 - b2 ** t

        def upd(p, a, b):
            pf = p.astype(jnp.float32)
            u = -hp["lr"] * ((a / c1) / (jnp.sqrt(b / c2) + hp["eps"])
                             + hp["weight_decay"] * pf)
            return (pf + u).astype(p.dtype)

        return jax.tree.map(upd, params, m, v), m, v, g

    start = params
    m = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
    v = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
    losses, first_grad, change1 = [], None, None
    for t, (batch, mask) in enumerate(zip(batches, masks), start=1):
        rows = applied_rows(np.asarray(mask), rows_per_worker)
        positions = batch["inputs"].shape[1]
        if fault == "half_batch":
            if len(rows) > 1:
                rows = rows[: len(rows) // 2]
            else:
                positions //= 2
        total, g = 0.0, None
        wide = widen(params)
        for blk in rows.reshape(-1, min(rows_per_worker, len(rows))):
            nll, gb = grad_fn(wide, batch["inputs"][blk, :positions],
                              batch["labels"][blk, :positions])
            total += float(nll)
            g = gb if g is None else add(g, gb)
        denom = float(len(rows) * positions)
        losses.append(total / denom)
        del wide
        new, m, v, gc = adam(params, m, v, g, float(t), denom)
        if first_grad is None:
            first_grad = leaf_norms(gc)
        del g, gc
        if fault != "frozen":
            params = new
        if change1 is None:
            change1 = leaf_norms(_change(params, start))
    return {"loss": losses, "grad_norm": first_grad, "change1_norm": change1,
            "change_norm": leaf_norms(_change(params, start))}


@jax.jit
def _change(a, b):
    return jax.tree.map(lambda x, y: x.astype(jnp.float32) - y.astype(jnp.float32), a, b)


# ---------------------------------------------------------------------------
# serving: how far below the reference's best each served token lies
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnums=(0,))
def _gaps(d: Dims, params, seq, read, toks):
    """For each read position and each row of ``toks`` (K, R): the
    reference's best logit minus the logit of that token. ``seq`` (1, L)
    is prompt plus served tokens; position ``read[i]`` predicts
    ``toks[:, i]``."""
    z = logits(d, None, params, hidden(d, None, params, seq)[0][read])
    best = jnp.max(z, axis=-1)
    return best[None] - jnp.take_along_axis(z, toks.T, axis=-1).T


@functools.partial(jax.jit, static_argnums=(0, 1))
def _top(d: Dims, quant, params, seq, read):
    """The token that ``quant`` precision ranks first at each read position."""
    return jnp.argmax(logits(d, quant, params, hidden(d, quant, params, seq)[0][read]), axis=-1)


#: sequences and read positions are padded to multiples of this, so that
#: a run compiles the reference for at most four sequence lengths
BUCKET = 1024


def _bucket(n: int) -> int:
    return -(-n // BUCKET) * BUCKET


def served_gaps(d: Dims, params, prompt: np.ndarray, tokens: Sequence[int],
                control: bool = False):
    """Gaps, in float32 reference logits, of the tokens served after
    ``prompt``: one for each served token, read at the position that
    produced it. ``control=True`` also reads, at the same positions, the
    gap of the token that fp8 (the control) ranks first there, and
    returns ``(gaps, control_gaps)``: the control needs no decode of its
    own."""
    seq = np.concatenate([np.asarray(prompt, np.int32), np.asarray(tokens, np.int32)])
    read = np.arange(len(prompt) - 1, len(seq) - 1)
    n = len(read)
    L, R = _bucket(len(seq)), _bucket(n)
    seq_p = np.zeros((1, L), np.int32)
    seq_p[0, :len(seq)] = seq
    read_p = np.zeros(R, np.int32)
    read_p[:n] = read
    toks_p = np.zeros((1, R), np.int32)
    toks_p[0, :n] = seq[read + 1]
    if control:
        toks_p = np.concatenate([toks_p, np.asarray(_top(d, "fp8", params, seq_p, read_p),
                                                    np.int32)[None]])
    gaps = np.asarray(_gaps(d, params, seq_p, read_p, toks_p))[:, :n]
    return (gaps[0], gaps[1]) if control else gaps[0]
