"""Top-level model: embeddings, stacks, losses, prefill/decode entry points.

``build_model(cfg)`` returns a ``Model`` whose methods are pure functions
of (params, inputs) — ready for jax.jit/pjit with shardings attached by
the launch layer.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.dist.collectives import masked_weighted_ce
from repro.dist.sharding import constrain_batch
from . import attention as attn
from . import mamba2, moe, xlstm, zamba
from .layers import (
    ParamSpec,
    abstract_from_specs,
    count_specs,
    init_from_specs,
    mlp_apply,
    norm_apply,
    norm_specs,
    slot_mask_select,
)
from .transformer import Segment, block_apply, run_segments, segment_plan, stack_specs

__all__ = ["Model", "build_model", "count_params_analytic"]


# ---------------------------------------------------------------------------
# Per-kind decode-step functions (single token, cache threading)
# ---------------------------------------------------------------------------

#: block kinds whose attention is ``attention.gqa_apply``.
_GQA_KINDS = ("dense", "parallel", "moe")


def _block_decode(
    params: Dict,
    x: jax.Array,
    cfg: ModelConfig,
    kind: str,
    *,
    positions: jax.Array,
    cache: Dict,
    cache_index: jax.Array,
    block_tables: Optional[jax.Array] = None,
) -> Tuple[jax.Array, Dict, Optional[jax.Array]]:
    """-> (x, new_cache, rows): ``rows`` (B, S) counts each token's
    choices that a held expert computed (MoE kinds; None otherwise)."""
    if kind in _GQA_KINDS:
        h = norm_apply(params["attn_norm"], x, cfg.norm, cfg.rms_norm_eps)
        a, new_cache = attn.gqa_apply(
            params["attn"], h, cfg, positions=positions,
            cache=cache, cache_index=cache_index, block_table=block_tables,
        )
        if kind == "parallel":
            f = mlp_apply(params["ffn"], h, cfg.act, cfg.glu)
            return x + a + f, new_cache, None
        x = x + a
        return _ffn(params, x, cfg, kind, new_cache)
    if kind in ("mla_dense", "mla_moe"):
        h = norm_apply(params["attn_norm"], x, cfg.norm, cfg.rms_norm_eps)
        a, new_cache = attn.mla_apply(
            params["attn"], h, cfg, positions=positions,
            cache=cache, cache_index=cache_index, absorb=cfg.mla_absorb,
            block_table=block_tables,
        )
        return _ffn(params, x + a, cfg, kind, new_cache)
    if kind == "mlstm":
        h = norm_apply(params["norm"], x, cfg.norm, cfg.rms_norm_eps)
        y, new_state = xlstm.mlstm_decode(params["mixer"], h, cfg, cache)
        return x + y, new_state, None
    if kind == "slstm":
        h = norm_apply(params["norm"], x, cfg.norm, cfg.rms_norm_eps)
        y, new_state = xlstm.slstm_apply(params["mixer"], h, cfg, state=cache)
        return x + y, new_state, None
    raise ValueError(f"no decode for block kind {kind}")


def _ffn(params: Dict, x: jax.Array, cfg: ModelConfig, kind: str, new_cache):
    """The block's pre-norm feed-forward and residual, after attention:
    -> (x, new_cache, rows) as ``_block_decode`` returns them."""
    h = norm_apply(params["mlp_norm"], x, cfg.norm, cfg.rms_norm_eps)
    if kind in ("moe", "mla_moe"):
        f, _, rows = moe.moe_apply(params["ffn"], h, cfg)
        return x + f, new_cache, rows
    return x + mlp_apply(params["ffn"], h, cfg.act, cfg.glu), new_cache, None


#: block kinds with a fused multi-token cache-writing prefill. Recurrent
#: kinds (mlstm/slstm/mamba) prefill through the masked decode scan instead.
_FUSED_PREFILL_KINDS = _GQA_KINDS + ("mla_dense", "mla_moe")


def _block_prefill(
    params: Dict,
    x: jax.Array,
    cfg: ModelConfig,
    kind: str,
    *,
    positions: jax.Array,
    cache: Dict,
    start_index: jax.Array,
    block_tables: Optional[jax.Array] = None,
    n_valid: Optional[jax.Array] = None,
) -> Tuple[jax.Array, Dict, Optional[jax.Array]]:
    """Multi-token block forward that also writes the block's cache rows
    (the serving prefill; mirrors ``_block_decode`` with S > 1)."""
    if kind in _GQA_KINDS:
        h = norm_apply(params["attn_norm"], x, cfg.norm, cfg.rms_norm_eps)
        a, new_cache = attn.gqa_prefill(
            params["attn"], h, cfg, positions=positions,
            cache=cache, start_index=start_index, block_table=block_tables,
            n_valid=n_valid,
        )
        if kind == "parallel":
            f = mlp_apply(params["ffn"], h, cfg.act, cfg.glu)
            return x + a + f, new_cache, None
        return _ffn(params, x + a, cfg, kind, new_cache)
    if kind in ("mla_dense", "mla_moe"):
        h = norm_apply(params["attn_norm"], x, cfg.norm, cfg.rms_norm_eps)
        a, new_cache = attn.mla_prefill(
            params["attn"], h, cfg, positions=positions,
            cache=cache, start_index=start_index, block_table=block_tables,
            n_valid=n_valid,
        )
        return _ffn(params, x + a, cfg, kind, new_cache)
    raise ValueError(f"no fused prefill for block kind {kind}")


def _block_cache_specs(
    cfg: ModelConfig, kind: str, batch: int, max_len: int, page=None
) -> Optional[Dict]:
    if kind in _GQA_KINDS:
        return attn.gqa_cache_spec(cfg, batch, max_len, page)
    if kind in ("mla_dense", "mla_moe"):
        return attn.mla_cache_spec(cfg, batch, max_len, page)
    if kind == "mlstm":
        return xlstm.mlstm_state_spec(cfg, batch)
    if kind == "slstm":
        return xlstm.slstm_state_spec(cfg, batch)
    if kind == "encoder":
        return None
    raise ValueError(f"no cache spec for {kind}")


# ---------------------------------------------------------------------------
# Model
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Model:
    cfg: ModelConfig

    # -- specs ---------------------------------------------------------------
    @functools.cached_property
    def segments(self) -> List[Segment]:
        if self.cfg.family in ("ssm", "hybrid"):
            return []  # zamba path
        return segment_plan(self.cfg)

    def param_specs(self) -> Dict[str, Any]:
        cfg = self.cfg
        dt = cfg.dtype
        specs: Dict[str, Any] = {}
        if cfg.input_kind == "tokens":
            specs["embed"] = ParamSpec(
                (cfg.vocab_size, cfg.d_model), ("vocab", "embed"), "normal", dt
            )
        else:  # frames (audio stub): projection + depthwise positional conv
            specs["frame_proj"] = ParamSpec(
                (cfg.d_model, cfg.d_model), ("embed", "embed_out"), "scaled", dt
            )
            specs["pos_conv_w"] = ParamSpec((16, cfg.d_model), (None, "embed"), "scaled", dt)
            specs["pos_conv_b"] = ParamSpec((cfg.d_model,), ("embed",), "zeros", dt)
            specs["embed"] = ParamSpec(  # output head for masked prediction
                (cfg.vocab_size, cfg.d_model), ("vocab", "embed"), "normal", dt
            )
        if cfg.family in ("ssm", "hybrid"):
            specs["stack"] = zamba.zamba_specs(cfg)
        else:
            specs["stack"] = [stack_specs(cfg, seg) for seg in self.segments]
        specs["final_norm"] = norm_specs(cfg.d_model, cfg.norm, dt)
        if not cfg.tie_embeddings:
            specs["head"] = ParamSpec(
                (cfg.d_model, cfg.vocab_size), ("embed", "vocab"), "scaled", dt
            )
        if cfg.mtp:
            specs["mtp"] = {
                "proj": ParamSpec(
                    (2 * cfg.d_model, cfg.d_model), ("embed", "embed_out"), "scaled", dt
                ),
                "block": stack_specs(cfg, Segment(self._mtp_kind(), 1)),
                "norm": norm_specs(cfg.d_model, cfg.norm, dt),
            }
        return specs

    def _mtp_kind(self) -> str:
        return "mla_dense" if self.cfg.mla is not None else "dense"

    def init(self, rng: jax.Array, dtype_override: Optional[str] = None):
        return init_from_specs(rng, self.param_specs(), dtype_override)

    def abstract_params(self, sharding_for):
        return abstract_from_specs(self.param_specs(), sharding_for)

    # -- forward -------------------------------------------------------------
    def embed_inputs(self, params: Dict, inputs: jax.Array) -> jax.Array:
        cfg = self.cfg
        if cfg.input_kind == "tokens":
            return params["embed"][inputs]
        x = jnp.einsum("bsd,de->bse", inputs.astype(params["frame_proj"].dtype),
                       params["frame_proj"])
        # Depthwise positional conv (HuBERT-style stub).
        W = params["pos_conv_w"].shape[0]
        x_pad = jnp.pad(x, ((0, 0), (W - 1, 0), (0, 0)))
        pos = sum(
            x_pad[:, i : i + x.shape[1], :] * params["pos_conv_w"][i] for i in range(W)
        ) + params["pos_conv_b"]
        return x + pos

    def hidden(
        self, params: Dict, inputs: jax.Array, positions: jax.Array
    ) -> Tuple[jax.Array, jax.Array]:
        cfg = self.cfg
        x = constrain_batch(self.embed_inputs(params, inputs))
        if cfg.family in ("ssm", "hybrid"):
            h, aux = zamba.zamba_apply(params["stack"], x, cfg, positions=positions)
        else:
            h, aux = run_segments(
                params["stack"], self.segments, x, cfg, positions=positions
            )
        return norm_apply(params["final_norm"], h, cfg.norm, cfg.rms_norm_eps), aux

    def logits(self, params: Dict, h: jax.Array) -> jax.Array:
        cfg = self.cfg
        if cfg.tie_embeddings or cfg.input_kind != "tokens":
            out = jnp.einsum("bsd,vd->bsv", h, params["embed"])
        else:
            out = jnp.einsum("bsd,dv->bsv", h, params["head"])
        if cfg.logit_scale != 1.0:
            out = out * cfg.logit_scale
        if cfg.logit_softcap > 0:
            out = cfg.logit_softcap * jnp.tanh(out / cfg.logit_softcap)
        return out

    # -- training ------------------------------------------------------------
    def train_loss(
        self, params: Dict, batch: Dict[str, jax.Array]
    ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
        """batch: inputs (B,S) int32 or (B,S,D) frames, labels (B,S) int32,
        optional mask (B,S)."""
        cfg = self.cfg
        inputs, labels = batch["inputs"], batch["labels"]
        S = labels.shape[1]
        positions = jnp.arange(S)
        h, aux = self.hidden(params, inputs, positions)
        logits = self.logits(params, h)
        mask = batch.get("mask")
        if mask is None:
            mask = jnp.ones(labels.shape, jnp.float32)
        ce = _masked_ce(logits, labels, mask)
        loss = ce + cfg.moe.router_aux_weight * aux if cfg.moe else ce
        metrics = {"ce": ce, "aux": aux}
        if cfg.mtp:
            mtp_loss = self._mtp_loss(params, h, inputs, labels, mask, positions)
            loss = loss + 0.3 * mtp_loss
            metrics["mtp"] = mtp_loss
        metrics["loss"] = loss
        return loss, metrics

    def _mtp_loss(self, params, h, inputs, labels, mask, positions):
        """DeepSeek-V3 multi-token prediction: one extra block predicts t+2."""
        cfg = self.cfg
        emb_next = params["embed"][jnp.roll(inputs, -1, axis=1)]
        x = jnp.einsum(
            "bsd,de->bse",
            jnp.concatenate([h, emb_next], axis=-1),
            params["mtp"]["proj"],
        )
        x, _ = block_apply(
            params["mtp"]["block"], x, cfg, self._mtp_kind(), positions=positions
        )
        x = norm_apply(params["mtp"]["norm"], x, cfg.norm, cfg.rms_norm_eps)
        logits2 = self.logits(params, x)
        labels2 = jnp.roll(labels, -1, axis=1)
        mask2 = mask * (jnp.arange(labels.shape[1]) < labels.shape[1] - 1)
        return _masked_ce(logits2, labels2, mask2)

    # -- serving ---------------------------------------------------------------
    def cache_specs(
        self,
        batch: int,
        max_len: int,
        *,
        block_size: Optional[int] = None,
        num_blocks: int = 0,
    ):
        """Cache spec tree for ``batch`` sequences of up to ``max_len``
        tokens. The sequence axis is rounded up to ``attn.KV_SEQ_ALIGN``
        once, here, at allocation time — so the flash-decode kernel never
        pads (copies) the cache in HBM per tick, and paged block sizes
        tile the rows evenly.

        ``block_size`` switches leaves that carry a sequence axis to the
        paged arena layout ((num_blocks + 1, block_size, ...) addressed
        through block tables); leaves without one — recurrent conv/SSM/
        xLSTM states — keep their contiguous per-slot layout in either
        mode, behind the same pool API."""
        cfg = self.cfg
        max_len = attn.round_kv_len(max_len)
        page = None
        if block_size is not None:
            page = (num_blocks, block_size)
        if cfg.family in ("ssm", "hybrid"):
            return zamba.zamba_cache_specs(cfg, batch, max_len, page)
        out = []
        for seg in self.segments:
            single = _block_cache_specs(cfg, seg.kind, batch, max_len, page)
            if seg.count > 1:
                single = jax.tree.map(
                    lambda s: ParamSpec(
                        (seg.count, *s.shape), ("layers", *s.axes), s.init, s.dtype
                    ),
                    single,
                    is_leaf=lambda x: isinstance(x, ParamSpec),
                )
            out.append(single)
        return out

    def blank_caches(
        self,
        batch: int,
        max_len: int,
        *,
        block_size: Optional[int] = None,
        num_blocks: int = 0,
    ):
        """Freshly initialized caches (cache specs are deterministic
        zeros/ones fills, so no meaningful randomness is consumed)."""
        return init_from_specs(
            jax.random.PRNGKey(0),
            self.cache_specs(
                batch, max_len, block_size=block_size, num_blocks=num_blocks
            ),
        )

    @functools.cached_property
    def fused_prefill(self) -> bool:
        """True when every block has a multi-token cache-writing prefill
        (pure-attention stacks); recurrent/hybrid stacks fall back to the
        masked decode scan in ``prefill_with_cache``."""
        if self.cfg.family in ("ssm", "hybrid"):
            return False
        return all(seg.kind in _FUSED_PREFILL_KINDS for seg in self.segments)

    @functools.cached_property
    def gqa_decode(self) -> bool:
        """True when some block decodes through ``attention.gqa_apply``
        (whose paged decode is ``attention.paged_decode_attention``)."""
        return any(seg.kind in _GQA_KINDS for seg in self.segments)

    def prefill(self, params: Dict, inputs: jax.Array) -> jax.Array:
        """Prefill forward -> logits for the last position (no cache
        writing — the dry-run lowers this as the prefill compute; serving
        uses ``prefill_with_cache``)."""
        S = inputs.shape[1]
        positions = jnp.arange(S)
        h, _ = self.hidden(params, inputs, positions)
        return self.logits(params, h[:, -1:, :])

    def prefill_with_cache(
        self,
        params: Dict,
        inputs: jax.Array,                     # (B, P) int32, right-padded
        caches,
        length: Optional[jax.Array] = None,    # (B,) valid tokens per row
        start_index: jax.Array = 0,            # scalar: first write position
        block_tables: Optional[jax.Array] = None,  # (B, T) paged arenas
        moe_rows: bool = False,
    ):
        """Batched cache-writing prefill -> (last-valid logits (B,1,V), caches).

        ``inputs`` may be right-padded to a bucket size; ``length`` marks
        each row's true token count. Attention stacks run the fused path
        (one projection for the whole chunk; pad rows are causally inert
        and their stale cache rows are masked by decode's length mask).
        Recurrent/hybrid stacks scan the decode step with per-row update
        masking so pad tokens never touch the state. ``start_index > 0``
        continues a partially prefilled cache (chunked prefill). With
        ``block_tables`` the sequence-axis cache leaves are paged arenas
        and the chunk's rows are written as bulk block scatters.

        ``moe_rows=True`` (an MoE stack) adds a third result, (B,): the
        choices of each row's real tokens that held experts computed,
        summed over the MoE layers."""
        cfg = self.cfg
        B, P = inputs.shape
        start_index = jnp.asarray(start_index, jnp.int32)
        if length is None:
            length = jnp.full((B,), P, jnp.int32)

        if self.fused_prefill:
            positions = start_index + jnp.arange(P)
            h, new_caches, rows = self._fused_prefill_stack(
                params, inputs, caches, positions=positions,
                start_index=start_index, block_tables=block_tables,
            )
            last = jnp.clip(length - 1, 0, P - 1)
            h_last = jnp.take_along_axis(h, last[:, None, None], axis=1)
            if moe_rows:
                live = jnp.arange(P)[None, :] < length[:, None]
                return (self.logits(params, h_last), new_caches,
                        jnp.where(live, rows, 0).sum(-1))
            return self.logits(params, h_last), new_caches
        if moe_rows:
            raise ValueError("moe_rows needs a fused (attention) prefill")

        # Recurrent/hybrid fallback: scan the decode step over the chunk,
        # masking cache updates (and the returned logits) past each row's
        # true length. Exactly equivalent to feeding the unpadded prompt.
        # (Paged KV leaves skip the mask: pad-token writes land at rows
        # past the row's length, which every read masks out — identical
        # to the contiguous path's masked tail.)
        specs = self.cache_specs(  # axes metadata only; sizes unused
            B, 2, block_size=1 if block_tables is not None else None
        )

        def body(carry, xs):
            caches_c, last_logits = carry
            tok, t = xs
            logits, new_caches = self.decode_step(
                params, tok[:, None], caches_c, start_index + t,
                block_tables=block_tables,
            )
            valid = t < length
            caches_c = slot_mask_select(valid, new_caches, caches_c, specs)
            last_logits = jnp.where(valid[:, None, None], logits, last_logits)
            return (caches_c, last_logits), None

        last0 = jnp.zeros((B, 1, cfg.vocab_size), params["embed"].dtype)
        (caches, last_logits), _ = jax.lax.scan(
            body, (caches, last0), (jnp.moveaxis(inputs, 1, 0), jnp.arange(P))
        )
        return last_logits, caches

    def _fused_prefill_stack(
        self,
        params: Dict,
        inputs: jax.Array,
        caches,
        *,
        positions: jax.Array,
        start_index: jax.Array,
        block_tables: Optional[jax.Array] = None,
        n_valid: Optional[jax.Array] = None,
    ):
        """Shared cache-writing stack walk of the fused (pure-attention)
        path -> (final-norm hidden states (B, S, D), caches). The single
        source of truth for ``prefill_with_cache`` AND
        ``verify_with_cache`` — the byte-identity contract depends on
        those two never diverging in how they traverse the stack."""
        cfg = self.cfg
        x = self.embed_inputs(params, inputs)
        new_caches, rows = [], []
        h = x
        for seg_params, seg_cache, seg in zip(
            params["stack"], caches, self.segments
        ):
            if seg.count == 1:
                h, nc, r = _block_prefill(
                    seg_params, h, cfg, seg.kind, positions=positions,
                    cache=seg_cache, start_index=start_index,
                    block_tables=block_tables, n_valid=n_valid,
                )
            else:
                def scan_fn(carry, xs):
                    layer, cache = xs
                    h2, nc, r = _block_prefill(
                        layer, carry, cfg, seg.kind, positions=positions,
                        cache=cache, start_index=start_index,
                        block_tables=block_tables, n_valid=n_valid,
                    )
                    return h2, (nc, r)
                h, (nc, r) = jax.lax.scan(scan_fn, h, (seg_params, seg_cache))
                r = None if r is None else r.sum(0)
            new_caches.append(nc)
            rows.append(r)
        h = norm_apply(params["final_norm"], h, cfg.norm, cfg.rms_norm_eps)
        return h, new_caches, _layer_sum(rows)

    def verify_with_cache(
        self,
        params: Dict,
        inputs: jax.Array,                     # (B, S) int32 draft windows
        caches,
        n_input: jax.Array,                    # (B,) valid inputs per row
        start_indices: jax.Array,              # (B,) first write position
        block_tables: Optional[jax.Array] = None,
        greedy_commit: bool = True,
    ):
        """Batched multi-token verify for speculative decoding ->
        (all-position logits (B, S, V), caches).

        Row ``b`` scores ``inputs[b, :n_input[b]]`` — the pending token
        followed by the draft proposals — starting at its own cache
        position ``start_indices[b]``; rows past ``n_input`` are inert
        pad (their logits are garbage the caller must ignore). Every slot
        sits at its own length, so the per-row start/count enter as DATA
        and one compile per S covers every round (the ``worker_mask``
        discipline).

        Cache commitment is family-specific but the CONTRACT is shared —
        on return the caches are valid for a committed prefix of any
        length ``a+1 <= n_input[b]`` the caller derives from the logits
        by the exact-argmax acceptance rule:

          * attention stacks (fused path): K/V rows are written for all
            ``n_input`` inputs; rows past the accepted prefix are stale
            but DEAD (every read masks by the caller-tracked position),
            so rollback is a host-side position rewind — block-table or
            contiguous alike.
          * recurrent/hybrid stacks (scan path): state cannot rewind, so
            the scan replays the acceptance rule ON DEVICE — step t
            commits its state update only while the greedy chain is
            unbroken (argmax(logits_{t-1}) == inputs[t]), which is
            bit-identical to the host's decision because both argmax the
            same logits. ``greedy_commit=False`` disables the chain and
            commits all ``n_input`` tokens (draft-side replay sync).
        """
        cfg = self.cfg
        B, S = inputs.shape
        start = jnp.asarray(start_indices, jnp.int32)
        n_input = jnp.asarray(n_input, jnp.int32)

        if self.fused_prefill:
            positions = start[:, None] + jnp.arange(S)   # (B, S) rope positions
            h, new_caches, _ = self._fused_prefill_stack(
                params, inputs, caches, positions=positions,
                start_index=start, block_tables=block_tables, n_valid=n_input,
            )
            return self.logits(params, h), new_caches

        # Recurrent/hybrid: scan the decode step, gating state commits by
        # the on-device greedy acceptance chain (see docstring).
        specs = self.cache_specs(  # axes metadata only; sizes unused
            B, 2, block_size=1 if block_tables is not None else None
        )
        nxt = jnp.concatenate(
            [inputs[:, 1:], jnp.zeros((B, 1), inputs.dtype)], axis=1
        )

        def body(carry, xs):
            caches_c, acc = carry
            tok, nxt_tok, t = xs
            logits, new_caches = self.decode_step(
                params, tok[:, None], caches_c, start + t,
                block_tables=block_tables,
            )
            commit = acc & (t < n_input)
            caches_c = slot_mask_select(commit, new_caches, caches_c, specs)
            if greedy_commit:
                g = jnp.argmax(logits[:, -1, :], axis=-1).astype(inputs.dtype)
                acc = acc & ((g == nxt_tok) | (t + 1 >= n_input))
            return (caches_c, acc), logits[:, 0, :]

        (caches, _), ys = jax.lax.scan(
            body,
            (caches, jnp.ones((B,), bool)),
            (jnp.moveaxis(inputs, 1, 0), jnp.moveaxis(nxt, 1, 0),
             jnp.arange(S)),
        )
        return jnp.moveaxis(ys, 0, 1), caches

    def decode_step(
        self,
        params: Dict,
        token: jax.Array,          # (B, 1) int32
        caches,
        cache_index: jax.Array,    # int32 current length: scalar or (B,)
        block_tables: Optional[jax.Array] = None,  # (B, T): paged KV arenas
        moe_rows: bool = False,
    ):
        """-> (logits (B, 1, V), caches); ``moe_rows=True`` (an MoE stack)
        adds (B,): each lane's choices that held experts computed, summed
        over the MoE layers."""
        cfg = self.cfg
        x = params["embed"][token]
        idx = jnp.asarray(cache_index, jnp.int32)
        if idx.ndim == 0:
            positions = jnp.full((1,), idx, jnp.int32)
        else:
            positions = idx[:, None]  # (B, 1): per-slot rope positions
        cache_index = idx
        if cfg.family in ("ssm", "hybrid"):
            h, new_caches = zamba.zamba_decode(
                params["stack"], x, cfg, caches,
                positions=positions, cache_index=cache_index,
                block_tables=block_tables,
            )
            rows = [None]
        else:
            new_caches, rows = [], []
            h = x
            for seg_params, seg_cache, seg in zip(params["stack"], caches, self.segments):
                if seg.count == 1:
                    h, nc, r = _block_decode(
                        seg_params, h, cfg, seg.kind,
                        positions=positions, cache=seg_cache, cache_index=cache_index,
                        block_tables=block_tables,
                    )
                else:
                    def scan_fn(carry, xs):
                        layer, cache = xs
                        h2, nc, r = _block_decode(
                            layer, carry, cfg, seg.kind,
                            positions=positions, cache=cache, cache_index=cache_index,
                            block_tables=block_tables,
                        )
                        return h2, (nc, r)
                    h, (nc, r) = jax.lax.scan(scan_fn, h, (seg_params, seg_cache))
                    r = None if r is None else r.sum(0)
                new_caches.append(nc)
                rows.append(r)
        h = norm_apply(params["final_norm"], h, cfg.norm, cfg.rms_norm_eps)
        if moe_rows:
            return self.logits(params, h), new_caches, _layer_sum(rows).sum(-1)
        return self.logits(params, h), new_caches


def build_model(cfg: ModelConfig) -> Model:
    return Model(cfg)


def _layer_sum(rows: List[Optional[jax.Array]]) -> Optional[jax.Array]:
    """Sum of the segments' per-token expert rows (None: no MoE layer)."""
    rows = [r for r in rows if r is not None]
    return sum(rows[1:], rows[0]) if rows else None


def count_params_analytic(cfg: ModelConfig, active_only: bool = False) -> int:
    """Parameter count from the spec tree (exact): the experts held (the
    tree holds only a share's). active_only: count each MoE layer's held
    experts at the number a token reaches on average, top_k x held /
    n_experts (top_k when all are held), plus the shared experts."""
    model = build_model(cfg)
    total = count_specs(model.param_specs())
    if active_only and cfg.moe is not None:
        moe = cfg.moe
        per_expert = 3 * cfg.d_model * moe.d_expert
        n_moe_layers = cfg.n_layers - moe.first_k_dense
        active = moe.top_k * moe.held / moe.n_experts
        total -= round((moe.held - active) * per_expert * n_moe_layers)
    return total


def _masked_ce(logits: jax.Array, labels: jax.Array, mask: jax.Array) -> jax.Array:
    return masked_weighted_ce(logits, labels, mask)[0]
