"""Plain reference of a DeepSeek-V3-family decoder, in float32.

Written from the published DeepSeek-V3 modeling code, as Moonlight-16B-A3B
runs it:

* pre-norm RMSNorm (the configuration's epsilon) around each block;
* multi-head latent attention, not absorbed: the query by one direct
  projection ``wq`` (D, H, nope + rope) when there is no query LoRA (else
  ``wq_a``, an RMSNorm, ``wq_b``); the latent ``wkv_a`` (D, r_kv + rope)
  split into c (RMSNorm at 1e-6, as DeepSeek's ``kv_a_layernorm``) and one
  rope key shared by the heads; K and V expanded per head from c by
  ``wkv_b``; scores over all 192 dims scaled by 1/sqrt(192), causal
  softmax, ``wo``;
* the leading dense layers: a SwiGLU, silu(x W_gate) * (x W_up) W_down;
* the MoE layers: sigmoid scores of the router in float32; the top-k
  chosen on the scores plus the selection bias; the weights the chosen
  experts' unbiased scores, renormalised over the k and multiplied by the
  routed scaling factor; the held experts' (the chip's share's) weighted
  SwiGLU outputs, plus the shared experts' SwiGLU taken whole;
* a final RMSNorm and an untied head (or the embedding, tied).

Every matmul runs at ``Precision.HIGHEST``. It imports nothing of the
program: it reads the weights in the program's parameter layout, as
stored (bfloat16), and widens each layer to float32 as it runs.
Departures: RoPE uses the program's half-split form, which equals the
published interleaved one up to a fixed permutation of the 64 rope
columns of ``wq`` and ``wkv_a``; each expert is computed for every token
and weighted by zero where it was not chosen (the same sum); the routed
part is the held share's, and the absent experts' part is left out, as
the program leaves it to the chips that hold them.

``quant="fp8"`` is the control (``reference``'s): every value the program
holds in bfloat16 held in float8_e4m3fn, scaled per tensor. Scores,
softmax and the router stay in float32, as in the program.
"""

from __future__ import annotations

import functools
import math
from typing import Dict, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from .model_mla_moe import MLAMoEDims
from .reference import _mm, _q, _rms, _rope

#: DeepSeek's latent norms keep their default epsilon
LATENT_EPS = 1e-6
#: queries attend in blocks of this many, so that one block's scores fit
Q_BLOCK = 512


def _attention(d: MLAMoEDims, quant, x, a):
    """Non-absorbed MLA of one layer over a whole sequence x (S, D)."""
    S = x.shape[0]
    pos = jnp.arange(S)
    if d.q_lora_rank is None:
        q = _mm("sd,dhe->she", x, a["wq"], quant)
    else:
        ql = _q(_rms(_mm("sd,dr->sr", x, a["wq_a"], quant), a["q_norm"]["scale"], LATENT_EPS),
                quant)
        q = _mm("sr,rhe->she", ql, a["wq_b"], quant)
    q_nope, q_rope = q[..., :d.qk_nope], _q(_rope(q[..., d.qk_nope:], pos, d.rope_theta), quant)
    kv = _mm("sd,dr->sr", x, a["wkv_a"], quant)
    c = _q(_rms(kv[:, :d.kv_lora_rank], a["kv_norm"]["scale"], LATENT_EPS), quant)
    k_rope = _q(_rope(kv[:, None, d.kv_lora_rank:], pos, d.rope_theta), quant)[:, 0]
    kvh = _mm("sr,rhe->she", c, a["wkv_b"], quant)
    k_nope, v = kvh[..., :d.qk_nope], kvh[..., d.qk_nope:]
    nb = -(-S // Q_BLOCK)
    pad = nb * Q_BLOCK - S
    qn = jnp.pad(q_nope, ((0, pad), (0, 0), (0, 0))).reshape(nb, Q_BLOCK, d.n_heads, d.qk_nope)
    qr = jnp.pad(q_rope, ((0, pad), (0, 0), (0, 0))).reshape(nb, Q_BLOCK, d.n_heads, d.qk_rope)
    scale = 1.0 / math.sqrt(d.qk_head)

    def block(args):
        i, qn_b, qr_b = args
        s = (_mm("qhe,khe->hqk", qn_b, k_nope, quant)
             + _mm("qhe,ke->hqk", qr_b, k_rope, quant)) * scale
        qpos = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        s = jnp.where(qpos[:, None] >= pos[None, :], s, -jnp.inf)
        return _mm("hqk,khe->qhe", jax.nn.softmax(s, axis=-1), v, quant)

    o = jax.lax.map(block, (jnp.arange(nb), qn, qr)).reshape(nb * Q_BLOCK, d.n_heads, d.v_head)
    return _mm("she,hed->sd", o[:S], a["wo"], quant)


def _swiglu(x, w_gate, w_in, w_out, quant):
    g = _q(jax.nn.silu(_mm("sd,df->sf", x, w_gate, quant)), quant)
    return _mm("sf,fd->sd", g * _mm("sd,df->sf", x, w_in, quant), w_out, quant)


def route(d: MLAMoEDims, x, router, bias):
    """(experts (S, K) over all ``n_experts``, weights (S, K)) for x (S, D)."""
    scores = jax.nn.sigmoid(jnp.einsum("sd,de->se", x, router, precision=jax.lax.Precision.HIGHEST))
    _, experts = jax.lax.top_k(scores + bias, d.top_k)
    w = jnp.take_along_axis(scores, experts, axis=-1)
    w = w / (w.sum(-1, keepdims=True) + 1e-20)
    return experts, w * d.routed_scaling


def moe(d: MLAMoEDims, quant, x, f):
    """The held experts' weighted outputs plus the shared experts' for
    x (S, D); ``f`` holds one layer's (float32) expert weights."""
    experts, w = route(d, x, f["router"], f["router_bias"])
    held = d.held_offset + jnp.arange(d.held)
    coef = jnp.einsum("sk,ske->se", w, (experts[..., None] == held).astype(jnp.float32))

    def one(acc, e):
        y = _swiglu(x, f["w_gate"][e], f["w_in"][e], f["w_out"][e], quant)
        return acc + coef[:, e, None] * y, None

    out, _ = jax.lax.scan(one, jnp.zeros_like(x), jnp.arange(d.held))
    sh = f["shared"]
    return out + _swiglu(x, sh["w_gate"], sh["w_in"], sh["w_out"], quant)


def _block(d: MLAMoEDims, quant, is_moe: bool, x, p):
    """One decoder layer over x (S, D); ``p`` holds its (stored) weights."""
    p = jax.tree.map(lambda w: w.astype(jnp.float32), p)
    h = _q(_rms(x, p["attn_norm"]["scale"], d.rms_eps), quant)
    x = _q(x + _attention(d, quant, h, p["attn"]), quant)
    h = _q(_rms(x, p["mlp_norm"]["scale"], d.rms_eps), quant)
    f = p["ffn"]
    y = moe(d, quant, h, f) if is_moe else _swiglu(h, f["w_gate"], f["w_in"], f["w_out"], quant)
    return _q(x + y, quant)


def hidden(d: MLAMoEDims, quant, params, tokens):
    """Final-norm hidden states (S, D) for token ids (S,)."""
    x = _q(params["embed"][tokens].astype(jnp.float32), quant)
    segments = [(False, d.first_k_dense), (True, d.n_moe_layers)]
    for seg, (is_moe, count) in zip(params["stack"], [s for s in segments if s[1]]):
        body = functools.partial(_block, d, quant, is_moe)
        if count == 1:        # the program stacks a segment of one layer not at all
            x = body(x, seg)
        else:
            x, _ = jax.lax.scan(lambda c, p: (body(c, p), None), x, seg)
    return _q(_rms(x, params["final_norm"]["scale"].astype(jnp.float32), d.rms_eps), quant)


def logits(d: MLAMoEDims, quant, params, h):
    if d.tied:
        return _mm("...d,vd->...v", h, params["embed"].astype(jnp.float32), quant)
    return _mm("...d,dv->...v", h, params["head"].astype(jnp.float32), quant)


# ---------------------------------------------------------------------------
# serving: how far below the reference's best each served token lies
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnums=(0,))
def _gaps(d: MLAMoEDims, params, seq, read, toks):
    """For each read position and each row of ``toks`` (K, R): the
    reference's best logit minus the logit of that token. ``seq`` (L,) is
    prompt plus served tokens; position ``read[i]`` predicts ``toks[:, i]``."""
    z = logits(d, None, params, hidden(d, None, params, seq)[read])
    best = jnp.max(z, axis=-1)
    return best[None] - jnp.take_along_axis(z, toks.T, axis=-1).T


def _top(d: MLAMoEDims, quant, params, seq, read):
    """The token that ``quant`` precision ranks first at each read position."""
    return jnp.argmax(logits(d, quant, params, hidden(d, quant, params, seq)[read]), axis=-1)


#: the TPU compiler's default scoped VMEM (16 MiB) is 1.5 MiB short for one
#: fusion of the fp8 control's attention over 8,192 keys (the rows of a
#: score block with their per-tensor scaling), whatever the query block;
#: the control alone is compiled with more
TPU_CONTROL_OPTIONS = {"xla_tpu_scoped_vmem_limit_kib": "32768"}


@functools.cache
def _top_jit():
    opts = TPU_CONTROL_OPTIONS if jax.default_backend() == "tpu" else None
    return jax.jit(_top, static_argnums=(0, 1), compiler_options=opts)


#: sequences and read positions are padded to multiples of this, so that
#: a run compiles the reference for at most four sequence lengths
BUCKET = 2048


def _bucket(n: int) -> int:
    return -(-n // BUCKET) * BUCKET


def served_gaps(d: MLAMoEDims, params, prompt: np.ndarray, tokens: Sequence[int],
                control: bool = False) -> Dict[str, np.ndarray]:
    """Gaps, in float32 reference logits, at each position that produced
    one of the tokens served after ``prompt``, all from one reference
    pass: ``"served"``, of the token served there; ``"altered"``, of the
    next token id in its place (a planted fault, read with the context
    unchanged); with ``control=True`` also ``"control"``, of the token
    fp8 ranks first there."""
    seq = np.concatenate([np.asarray(prompt, np.int32), np.asarray(tokens, np.int32)])
    read = np.arange(len(prompt) - 1, len(seq) - 1)
    n = len(read)
    seq_p = np.zeros(_bucket(len(seq)), np.int32)
    seq_p[:len(seq)] = seq
    read_p = np.zeros(_bucket(n), np.int32)
    read_p[:n] = read
    toks_p = np.zeros((2, len(read_p)), np.int32)
    toks_p[0, :n] = seq[read + 1]
    toks_p[1, :n] = (seq[read + 1] + 1) % d.vocab
    if control:
        toks_p = np.concatenate([toks_p, np.asarray(_top_jit()(d, "fp8", params, seq_p, read_p),
                                                    np.int32)[None]])
    gaps = np.asarray(_gaps(d, params, seq_p, read_p, toks_p))[:, :n]
    return dict(zip(("served", "altered", "control"), gaps))
