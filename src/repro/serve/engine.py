"""Continuous-batching inference engine over a fixed slot pool.

The pool's ``n_slots`` lanes are one fixed-shape jitted decode call; slot
occupancy enters as DATA (a mask + per-slot position vector), exactly
like the fastest-k ``worker_mask`` in ``repro.runtime.steps`` — so
requests join and leave mid-flight with zero recompiles. Admission runs
the batched cache-writing prefill (``model.prefill_with_cache``) into a
batch-1 cache that is then installed into the freed slot with one
spec-driven slice write; prompts are padded to power-of-two buckets so a
handful of compiles cover every length.

Decode is greedy (argmax) by design: tests assert the continuous-batched
token stream is identical to a per-request offline decode, which is the
correctness contract that makes the scheduler/pool machinery trustable.

``run_static`` is the baseline the benchmarks compare against: same
kernels, same pool, but admissions barrier until the whole previous
batch drains (classic static batching — finished lanes ride dead until
the longest request completes).

Paged mode (``block_size=...``): sequence-axis cache leaves live in a
global block arena addressed through per-slot block tables, admission
switches from "a free slot" to "enough free blocks for the request's
whole token budget" (admit-by-budget: requests queue under arena
pressure and re-enter as finishing requests return blocks), and KV
memory tracks live tokens instead of ``n_slots * max_len`` stripes.
Greedy tokens stay byte-identical to the contiguous engine and to
offline decode — paging is a layout change, not a math change.

Speculative mode (``draft_model=...``): decode actions become
draft-then-verify rounds (DESIGN.md §12, ``serve.speculative``) with
the same byte-identity contract — speculation only moves throughput.

Public API contract: the engine is SPEC-DRIVEN — it talks to caches
only through ``SlotPool`` and the jitted steps built from
``model.cache_specs``/``prefill_with_cache``/``decode_step``/
``verify_with_cache``, so any registered arch family serves unchanged
(attention KV, MLA latent, recurrent, hybrid). Model-specific behavior
lives entirely behind those Model methods; the one family-visible
distinction (fused vs scan verify commit) is documented on
``Model.verify_with_cache`` and tested per family.
"""

from __future__ import annotations

import dataclasses
import hashlib
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.attention import NULL_BLOCK, paged_decode_path, paged_latent_path
from repro.models.layers import ParamSpec, is_paged_spec, slot_mask_select
from repro.models.moe import moe_capacity
from repro.obs import NULL_OBS, Observability, span
from repro.runtime.steps import (
    make_slot_decode_step,
    make_slot_prefill_step,
    make_slot_verify_step,
)

from .kv_pool import ArenaExhausted, SlotPool, SlotSnapshot, model_scoped_cache
from .scheduler import CostModel, EventClock, Request, Scheduler, next_bucket
from .speculative import DraftRunner, SpecController

__all__ = [
    "ServeEngine", "EngineStats", "MigrationTicket", "TicketIntegrityError",
    "ticket_checksum", "generate_offline", "run_static",
]


class TicketIntegrityError(ValueError):
    """A :class:`MigrationTicket` failed its end-to-end integrity check
    at import: the payload was mutated between ``export_request`` (which
    seals the checksum) and ``import_request`` (which verifies it).
    Resuming from a corrupt ticket would silently diverge the greedy
    stream — the importer must reject it and the owner requeue from the
    last trusted prefix instead."""


@dataclasses.dataclass(frozen=True)
class MigrationTicket:
    """Everything needed to resume a mid-decode request on ANOTHER engine
    of the same model + pool geometry: the immutable submission, the
    tokens emitted so far, the next token to feed (``pending``), and the
    slot's cache state as a :class:`SlotSnapshot`. Restoring re-admits
    the request with its prefix already in cache — no re-prefill — and
    the greedy continuation is byte-identical to never having moved
    (pinned per arch family in tests)."""

    prompt: np.ndarray
    max_new_tokens: int
    arrival: float
    deadline: Optional[float]
    tokens: Tuple[int, ...]       # emitted so far (stream prefix)
    pending: int                  # next token to feed (last emitted)
    snapshot: SlotSnapshot
    #: end-to-end integrity seal over every resume-relevant field,
    #: computed at export (``ticket_checksum``) and verified at import.
    #: ``None`` = unsealed (hand-built test tickets): import skips the
    #: check, matching pre-checksum tickets.
    checksum: Optional[str] = None


def ticket_checksum(ticket: "MigrationTicket") -> str:
    """SHA-256 over the ticket's resume-relevant content: prompt bytes,
    budget, emitted tokens, pending token, and every snapshot cache leaf
    (shape + dtype + raw bytes). Deliberately EXCLUDES ``deadline`` —
    the owner legitimately rewrites it in flight (absolute deadlines are
    clock-local, so migration carries remaining budget instead), and a
    re-seal hook on the transfer path would be exactly the kind of
    mutable-in-transit field an integrity seal must not cover."""
    h = hashlib.sha256()
    prompt = np.ascontiguousarray(np.asarray(ticket.prompt, np.int32))
    h.update(prompt.tobytes())
    h.update(np.int64(ticket.max_new_tokens).tobytes())
    h.update(np.asarray(ticket.tokens, np.int64).tobytes())
    h.update(np.int64(ticket.pending).tobytes())
    snap = ticket.snapshot
    h.update(np.int64(snap.position).tobytes())
    h.update(np.int64(snap.n_blocks).tobytes())
    for leaf in jax.tree_util.tree_leaves(snap.data):
        a = np.ascontiguousarray(np.asarray(leaf))
        h.update(str((a.shape, a.dtype.str)).encode())
        h.update(a.tobytes())
    return h.hexdigest()


@dataclasses.dataclass
class EngineStats:
    generated_tokens: int = 0
    decode_ticks: int = 0
    prefill_calls: int = 0
    prefill_tokens: int = 0
    spec_rounds: int = 0          # speculation rounds (draft + verify)
    draft_ticks: int = 0          # sequential draft decode ticks
    spec_accepted: int = 0        # draft tokens the target accepted
    cancelled_requests: int = 0   # deadline expiries + explicit cancels
    preempted_requests: int = 0   # evict-and-requeue events (prefix sharing)
    prefix_hits: int = 0          # admissions that adopted a trie chain
    prefix_rows_shared: int = 0   # cache rows skipped via adoption
    migrated_out: int = 0         # requests exported as MigrationTickets
    migrated_in: int = 0          # tickets restored into this engine
    moe_rows: int = 0             # held experts' rows that real tokens took
    moe_rows_computed: int = 0    # held experts x capacity x MoE layers
    virtual_seconds: float = 0.0

    @property
    def tokens_per_vsec(self) -> float:
        return self.generated_tokens / max(self.virtual_seconds, 1e-12)


@model_scoped_cache
def _engine_steps(model, n_slots: int, max_len: int,
                  block_size: Optional[int], arena_blocks: int):
    """Jitted prefill/decode shared across every engine of the same
    geometry on the same model (per-instance jax.jit closures would
    re-trace each time a new engine is built — benchmarks build
    several). Cached on the model instance, not a module global, so a
    dropped model releases its traces."""
    specs = model.cache_specs(
        n_slots, max_len, block_size=block_size, num_blocks=arena_blocks
    )
    # MoE stacks (all fused) count their held experts' rows on the device
    moe_rows = model.cfg.moe is not None and model.fused_prefill
    prefill = make_slot_prefill_step(model, moe_rows)
    decode = make_slot_decode_step(model, moe_rows)

    def decode_tick(params, tokens, caches, positions, mask, tables=None):
        logits, new_caches, *rows = decode(params, tokens, caches, positions, tables)
        # Lanes not decoding (free / mid-prefill) must not mutate
        # state: recurrent leaves would otherwise absorb garbage.
        # (Paged leaves skip the select — dead-lane writes went to the
        # NULL sink block via their zeroed block tables.)
        new_caches = slot_mask_select(mask, new_caches, caches, specs)
        if rows:
            # the held experts' rows of the lanes that decode
            return logits, new_caches, jnp.where(mask, rows[0], 0).sum()
        return logits, new_caches

    # Speculative verify (only traced when an engine actually has a
    # draft model — jax.jit is lazy). Needs no extra masking: dead-lane
    # writes are dropped/sunk by ``n_input`` and recurrent commits are
    # gated on-device (Model.verify_with_cache).
    verify = jax.jit(make_slot_verify_step(model))

    return jax.jit(prefill), jax.jit(decode_tick), verify


class ServeEngine:
    def __init__(
        self,
        model,
        params,
        *,
        n_slots: int,
        max_len: int,
        scheduler: Optional[Scheduler] = None,
        prefill_bucket: int = 16,
        block_size: Optional[int] = None,
        arena_blocks: Optional[int] = None,
        prefix_sharing: bool = False,
        draft_model=None,
        draft_params=None,
        gamma_max: int = 4,
        spec_controller: Optional[SpecController] = None,
        obs: Optional[Observability] = None,
        obs_name: Optional[str] = None,
    ):
        """``block_size`` turns on paged KV (see module docstring);
        ``arena_blocks`` caps the arena below full capacity to serve
        under an explicit memory budget (admit-by-budget queuing).

        ``prefix_sharing`` (paged only, DESIGN.md §16) switches the
        arena to copy-on-write sharing with preempt-and-requeue:
        admissions adopt trie-matched prompt blocks instead of
        recomputing them, shared blocks fork before any write, and
        arena pressure evicts the cheapest lane (recompute-vs-hold
        priced by the cost model) rather than queuing. Greedy streams
        stay byte-identical to offline decode — including preempted
        requests, which replay from the longest resident prefix.

        ``draft_model``/``draft_params`` turn on speculative decoding
        (DESIGN.md §12): decode actions become draft-then-verify rounds
        whose draft length is adapted by ``spec_controller`` (default:
        ``SpecController(gamma_max)``). Greedy output stays byte-identical
        to the non-speculative engine and to offline decode — acceptance
        is exact argmax match, so speculation is purely a throughput
        bet.

        ``obs``: observability bundle (``repro.obs``) — defaults to the
        disabled ``NULL_OBS`` singleton, in which case every hook below
        is a no-op costing one attribute check. ``obs_name`` labels this
        engine's trace lane (replicas pass ``"replica <id>"``)."""
        if model.cfg.is_encoder:
            raise ValueError("serving needs a causal decoder architecture")
        if prefix_sharing and draft_model is not None:
            raise ValueError(
                "prefix_sharing and speculative decoding are mutually "
                "exclusive: the draft twin pool does not track the target's "
                "copy-on-write forks, so lockstep would silently break"
            )
        if (prefix_sharing and model.cfg.moe is not None
                and not model.cfg.moe.dropless):
            raise ValueError(
                "prefix_sharing requires dropless MoE routing "
                "(cfg.moe.dropless=True): adopting a prefix changes how "
                "many tokens share the suffix prefill call, and "
                "capacity-dropped routing makes logits depend on that "
                "count — byte-identity to offline decode would silently "
                "break"
            )
        self.model = model
        self.params = params
        self.prefix_sharing = bool(prefix_sharing)
        self.pool = SlotPool(
            model, n_slots, max_len,
            block_size=block_size, arena_blocks=arena_blocks,
            prefix_sharing=prefix_sharing,
        )
        #: chaos-search teeth only (tools/chaos_search.py --leak-blocks):
        #: when set, a CANCELLED slot's last block is dropped instead of
        #: freed — a seeded refcount bug the block-conservation oracle
        #: must catch and ddmin must shrink to the one cancel atom.
        self._chaos_leak_blocks = False
        self.sched = scheduler or Scheduler(n_slots)
        self.prefill_bucket = prefill_bucket
        self.stats = EngineStats()
        self.events: List[Tuple[str, float, int]] = []  # (action, vtime, rid)
        # -- observability ----------------------------------------------------
        self.obs = obs or NULL_OBS
        self._tr = self.obs.tracer
        self.pid = self._tr.register_process(obs_name or "engine")
        self._span_ids: Dict[int, int] = {}   # rid -> open lifecycle span
        if self.sched.obs is NULL_OBS:
            self.sched.bind_obs(self.obs)
        m = self.obs.metrics
        self._m_tokens = m.counter("engine.generated_tokens")
        self._m_prefill_tokens = m.counter("engine.prefill_tokens")
        self._m_decode_ticks = m.counter("engine.decode_ticks")
        self._g_slots = m.gauge("engine.slots_active")
        self._g_blocks = m.gauge("engine.arena_blocks_used")
        self._requests: Dict[int, Request] = {}
        self._next_rid = 0
        # Per-slot decode state (host side).
        self._pending = np.zeros(n_slots, np.int32)   # next token to feed
        self._decoding = np.zeros(n_slots, bool)      # prefill done, generating
        # Fresh batch-1 caches for a slot's first prefill chunk. Paged
        # mode keeps only the contiguous (recurrent-state) leaves — the
        # arena leaves are stand-ins (num_blocks=0 = just the NULL row)
        # swapped for the pool's real arenas at call time.
        self._blank1 = model.blank_caches(
            1, max_len, block_size=block_size, num_blocks=0
        )
        self._prefill, self._decode, self._verify = _engine_steps(
            model, n_slots, max_len, block_size,
            0 if self.pool.manager is None else self.pool.manager.num_blocks,
        )
        #: The decode tick's KV read, the ``kv_path`` of its span: what
        #: ``paged_decode_path`` picks on the pool's device for a paged
        #: GQA model, and ``paged_latent_path`` for paged absorbed MLA
        #: ("latent_gather" for MLA that expands the cache), "gather" for
        #: other paged models, "contiguous" unpaged.
        cfg = model.cfg
        if not self.pool.paged:
            self._kv_path = "contiguous"
        else:
            leaf = jax.tree.leaves(self.pool.caches)[0]
            platform = next(iter(leaf.devices())).platform
            if model.gqa_decode:
                self._kv_path = paged_decode_path(
                    cfg.n_kv_heads, cfg.head_dim, cfg.dtype, platform)
            elif cfg.mla is not None and cfg.mla_absorb:
                self._kv_path = paged_latent_path(
                    cfg.mla.kv_lora_rank, cfg.dtype, block_size, platform)
            elif cfg.mla is not None:
                self._kv_path = "latent_gather"
            else:
                self._kv_path = "gather"
        #: MoE: each step's held-expert rows (a device scalar) wait here
        #: until the next read of tokens carries them back.
        self._moe_pending: List[jax.Array] = []
        self._moe_computed = 0
        # -- speculation (optional) ------------------------------------------
        self.draft: Optional[DraftRunner] = None
        self.spec: Optional[SpecController] = None
        if draft_model is not None:
            if draft_params is None:
                raise ValueError("draft_model needs draft_params")
            if draft_model.cfg.vocab_size != model.cfg.vocab_size:
                raise ValueError(
                    "draft and target models must share a vocabulary "
                    f"({draft_model.cfg.vocab_size} != {model.cfg.vocab_size})"
                )
            self.draft = DraftRunner(draft_model, draft_params, n_slots, max_len)
            self.spec = spec_controller or SpecController(gamma_max)
            self.spec.draft_fused = draft_model.fused_prefill
            if self.spec.obs is NULL_OBS:
                self.spec.obs = self.obs

    @property
    def speculative(self) -> bool:
        return self.draft is not None

    # -- submission ----------------------------------------------------------
    def submit(
        self, prompt, max_new_tokens: int, arrival: float = 0.0,
        deadline: Optional[float] = None,
    ) -> int:
        """``deadline``: absolute virtual-time deadline; None defers to
        the scheduler's ``deadline_ticks`` default (stamped at
        admission). The wall span ``repro.engine.submit`` marks the
        request's wall arrival."""
        with span("repro.engine.submit", rid=self._next_rid):
            prompt = np.asarray(prompt, np.int32).reshape(-1)
            if prompt.size + max_new_tokens > self.pool.max_len:
                raise ValueError(
                    f"prompt({prompt.size}) + max_new_tokens({max_new_tokens}) "
                    f"exceeds max_len({self.pool.max_len})"
                )
            if self.pool.paged:
                mgr = self.pool.manager
                need = mgr.blocks_for(prompt.size + max_new_tokens)
                if need > mgr.num_blocks:
                    # Reject outright: a request bigger than the whole arena
                    # could never be admitted, even with the pool idle.
                    raise ValueError(
                        f"request needs {need} blocks but the arena has only "
                        f"{mgr.num_blocks} — raise arena_blocks or block_size"
                    )
            rid = self._next_rid
            self._next_rid += 1
            req = Request(
                rid, prompt, int(max_new_tokens), float(arrival),
                deadline=deadline,
            )
            self._requests[rid] = req
            self.sched.submit(req)
            if self._tr.enabled:
                # The span opens at this engine's LOCAL clock, not at the
                # logical arrival: a hedge copy can be handed to a replica
                # whose clock is behind the arrival stamp, and span ends
                # must never precede their begins.
                self._span_ids[rid] = self._tr.begin_span(
                    "request", self.pid, self.sched.clock.now,
                    args={"rid": rid, "arrival": float(arrival),
                          "prompt_len": int(prompt.size),
                          "max_new_tokens": int(max_new_tokens)},
                )
            return rid

    def _end_request_span(self, req: Request, outcome: str, ts: float) -> None:
        """Close a request's lifecycle span exactly once, whatever path
        retired it (done / cancelled / deadline / migrated) — leaked
        spans under chaos are a test failure (tests/test_obs.py)."""
        sid = self._span_ids.pop(req.rid, None)
        if sid:
            self._tr.end_span(
                sid, ts,
                args={"outcome": outcome, "n_tokens": len(req.tokens)},
            )

    # -- cancellation / deadlines --------------------------------------------
    def cancel(self, rid: int, reason: str = "cancelled") -> bool:
        """Tear down an unfinished request NOW, wherever it is in its
        lifecycle, actually freeing what it holds: a waiting request
        leaves the queue; a mid-prefill or decoding request frees its
        slot — and, in paged mode, returns its arena blocks, which is
        what lets a queued request admit (hedged-loser cancellation is
        only affordable because of this). Returns False if the request
        is unknown, already finished, or already cancelled. The partial
        token stream is kept on the request."""
        req = self._requests.get(rid)
        if req is None or req.t_done is not None or req.cancelled:
            return False
        self.sched.drop(req)
        if rid in self.pool.owner:              # holds a slot (prefill/decode)
            slot = self._slot_of(rid)
            self._decoding[slot] = False
            if self._chaos_leak_blocks and self.pool.paged:
                # Seeded bug (chaos teeth): drop the slot's last block on
                # the cancel path without freeing it. Only cancel-bearing
                # schedules trip the conservation oracle, so ddmin can
                # shrink the repro to exactly that one atom.
                mgr = self.pool.manager
                owned = mgr._owned[slot]
                if owned:
                    bid = owned.pop()
                    mgr.tables[slot, len(owned)] = NULL_BLOCK
                    mgr.refcount[bid] -= 1
            self._free_slot(slot)
        req.t_cancelled = self.sched.clock.now
        req.cancel_reason = reason
        self.stats.cancelled_requests += 1
        self.events.append(("cancel", self.sched.clock.now, rid))
        now = self.sched.clock.now
        self._end_request_span(req, reason, now)
        if self.obs.enabled:
            self.obs.metrics.counter(f"engine.cancel.{reason}").inc()
            self._tr.instant("cancel", self.pid, now,
                             args={"rid": rid, "reason": reason})
        return True

    def _expire_deadlines(self) -> List[int]:
        """Cancel every unfinished request past its deadline (reason
        ``"deadline"``); returns their rids so a frontend can requeue
        them elsewhere and record the expiry as censored telemetry."""
        now = self.sched.clock.now
        expired = [
            rid for rid, req in self._requests.items()
            if req.t_done is None and not req.cancelled
            and req.deadline is not None and req.deadline <= now
        ]
        for rid in expired:
            self.cancel(rid, reason="deadline")
        return expired

    # -- migration -----------------------------------------------------------
    def export_request(self, rid: int) -> MigrationTicket:
        """Snapshot a decoding request into a :class:`MigrationTicket`
        and release everything it holds here (reason ``"migrated"``).

        Only DECODING requests carry cache state worth handing off;
        waiting / mid-prefill requests migrate by plain resubmission.
        Speculative engines refuse: the draft pool's twin state is not
        part of the snapshot, and a desynced draft would poison
        lockstep. The position invariant checked here is the engine's
        decode bookkeeping contract: after ``m`` emitted tokens the slot
        has ``prompt_len + m - 1`` rows written and ``pending`` = token
        ``m``, so the importing engine's next decode tick emits token
        ``m + 1`` of the identical greedy stream."""
        if self.speculative:
            raise ValueError("cannot export from a speculative engine "
                             "(draft twin state is not snapshotted)")
        req = self._requests.get(rid)
        if req is None or req.t_done is not None or req.cancelled:
            raise ValueError(f"request {rid} is not live")
        slot = self._slot_of(rid)
        if not self._decoding[slot]:
            raise ValueError(f"request {rid} is not decoding "
                             "(migrate queued requests by resubmission)")
        expect = req.prompt_len + len(req.tokens) - 1
        assert int(self.pool.positions[slot]) == expect, (
            f"slot {slot} position {self.pool.positions[slot]} != {expect}"
        )
        ticket = MigrationTicket(
            prompt=req.prompt,
            max_new_tokens=req.max_new_tokens,
            arrival=req.arrival,
            deadline=req.deadline,
            tokens=tuple(req.tokens),
            pending=int(self._pending[slot]),
            snapshot=self.pool.snapshot_slot(slot),
        )
        # Seal AFTER the ticket is complete: the checksum covers every
        # resume-relevant field (not the clock-local deadline, which the
        # owner rewrites in flight — see ticket_checksum).
        ticket = dataclasses.replace(ticket, checksum=ticket_checksum(ticket))
        self._decoding[slot] = False
        self._free_slot(slot)
        req.t_cancelled = self.sched.clock.now
        req.cancel_reason = "migrated"
        self.stats.migrated_out += 1
        self.events.append(("migrate_out", self.sched.clock.now, rid))
        now = self.sched.clock.now
        self._end_request_span(req, "migrated", now)
        if self.obs.enabled:
            self.obs.metrics.counter("engine.migrated_out").inc()
            self._tr.instant("migrate_out", self.pid, now,
                             args={"rid": rid, "n_tokens": len(req.tokens)})
        return ticket

    def import_request(self, ticket: MigrationTicket) -> Optional[int]:
        """Re-admit a migrated request with its cache prefix restored —
        no re-prefill. Returns the new local rid, or None when the pool
        cannot admit it right now (no free slot / not enough blocks):
        the caller keeps the ticket and retries after capacity frees, or
        falls back to resubmitting prompt + emitted tokens."""
        if self.speculative:
            raise ValueError("cannot import into a speculative engine "
                             "(draft twin state is not snapshotted)")
        if ticket.checksum is not None:
            # Verify BEFORE touching the pool: a corrupt ticket must be
            # rejected without allocating anything (reject-and-requeue is
            # the owner's job; resuming from garbage would silently
            # diverge the greedy stream).
            expect = ticket_checksum(ticket)
            if expect != ticket.checksum:
                raise TicketIntegrityError(
                    f"migration ticket failed integrity check: sealed "
                    f"{ticket.checksum[:12]}…, recomputed {expect[:12]}…"
                )
        budget = int(ticket.prompt.size) + int(ticket.max_new_tokens)
        if budget > self.pool.max_len:
            raise ValueError("ticket exceeds this engine's max_len")
        rid = self._next_rid
        slot = self.pool.restore_slot(ticket.snapshot, owner=rid, n_tokens=budget)
        if slot is None:
            return None
        self._next_rid += 1
        req = Request(
            rid, ticket.prompt, int(ticket.max_new_tokens),
            float(ticket.arrival), deadline=ticket.deadline,
        )
        req.tokens = list(ticket.tokens)
        req.prefilled = req.prompt_len
        req.t_admit = self.sched.clock.now
        req.t_first_token = self.sched.clock.now
        self._requests[rid] = req
        self._pending[slot] = np.int32(ticket.pending)
        self._decoding[slot] = True
        self.stats.migrated_in += 1
        self.events.append(("migrate_in", self.sched.clock.now, rid))
        now = self.sched.clock.now
        if self._tr.enabled:
            self._span_ids[rid] = self._tr.begin_span(
                "request", self.pid, now,
                args={"rid": rid, "arrival": float(ticket.arrival),
                      "prompt_len": int(ticket.prompt.size),
                      "max_new_tokens": int(ticket.max_new_tokens),
                      "migrated_in": True,
                      "tokens_so_far": len(ticket.tokens)},
            )
        if self.obs.enabled:
            self.obs.metrics.counter("engine.migrated_in").inc()
            self._tr.instant("migrate_in", self.pid, now,
                             args={"rid": rid,
                                   "n_tokens": len(ticket.tokens)})
        return rid

    # -- introspection (frontend/replica layers) -----------------------------
    def request(self, rid: int) -> Request:
        return self._requests[rid]

    def live_rids(self) -> List[int]:
        """Requests neither finished nor cancelled (queued, mid-prefill,
        or decoding)."""
        return [
            rid for rid, r in self._requests.items()
            if r.t_done is None and not r.cancelled
        ]

    def decoding_rids(self) -> List[int]:
        """Requests mid-decode — the ones that carry migratable cache
        state (``export_request``)."""
        return [
            self.pool.owner[int(s)] for s in np.nonzero(self._decoding)[0]
        ]

    @property
    def has_work(self) -> bool:
        """True while a ``step()`` would do something other than idle
        forever (active slots, queued arrivals, or mid-prefill work)."""
        return bool(
            self.pool.n_active > 0 or self.sched.waiting or self.sched.running
        )

    # -- actions -------------------------------------------------------------
    def _slot_of(self, rid: int) -> int:
        return self.pool.owner.index(rid)

    @staticmethod
    def _budget(req: Request) -> int:
        """Cache rows a request can touch over its whole lifetime —
        reserved in full at admission so decode never stalls on blocks."""
        return req.prompt_len + req.max_new_tokens

    def _can_admit(self, req: Request) -> bool:
        if not self.prefix_sharing:
            return self.pool.can_admit(self._budget(req))
        # Sharing mode: no whole-budget commitment — admit when the
        # PREFILL (minus whatever the trie already holds) fits the live
        # free list, leaving at least one block of headroom. Decode-time
        # growth is covered by preempt-and-requeue, not by reservation.
        pool = self.pool
        if pool.n_free == 0 or not pool.manager.can_commit(self._budget(req)):
            return False
        mgr = pool.manager
        matched = (0 if pool._any_contiguous
                   else len(pool.prefix.match(req.prefill_target())))
        need = mgr.blocks_for(req.prefill_len) - matched
        return mgr.n_free_blocks >= max(need, 1)

    def _fresh_slot_caches(self):
        """Batch-1 caches for a first prefill chunk: blank contiguous
        leaves, the pool's live arenas for paged leaves (pure pytree
        re-composition — no device work)."""
        if not self.pool.paged:
            return self._blank1
        return jax.tree.map(
            lambda s, pooled, blank: pooled if is_paged_spec(s) else blank,
            self.pool.specs, self.pool.caches, self._blank1,
            is_leaf=lambda x: isinstance(x, ParamSpec),
        )

    def _do_prefill(self, req: Request) -> None:
        sched, pool = self.sched, self.pool
        t0 = sched.clock.now
        target = req.prefill_target()   # prompt, + emitted[:-1] on replay
        first = req.rid not in pool.owner
        if first:
            # First chunk. (Detected by slot ownership, not prefilled==0:
            # a trie adoption below pre-advances ``prefilled``.)
            sched.on_admit(req)
            slot = pool.allocate(owner=req.rid, n_tokens=self._budget(req))
            assert slot is not None, "scheduler admitted without slot/blocks"
            if self.prefix_sharing:
                matched = pool.adopt_prefix(slot, target)
                if matched:
                    if matched == len(target):
                        # Full-block full match: re-feed the last token so
                        # its write forks the shared tail block — the
                        # emitted continuation needs logits at that row.
                        matched -= 1
                        pool.positions[slot] = matched
                    req.prefilled = matched
                    self.stats.prefix_hits += 1
                    self.stats.prefix_rows_shared += matched
        else:
            slot = self._slot_of(req.rid)

        start, n_tok = sched.chunk_for(req)
        # Cap the pad bucket at the slot capacity past `start`: an oversized
        # chunk would crash (update wider than the cache) or, worse, let
        # XLA clamp the write start and silently overwrite valid rows.
        # submit() guarantees n_tok <= max_len - start.
        bucket = min(next_bucket(n_tok, self.prefill_bucket), pool.max_len - start)
        chunk = np.zeros((1, bucket), np.int32)
        chunk[0, :n_tok] = target[start : start + n_tok]
        # Lazily grow the slot's block table to cover the chunk's real
        # rows (bucket overhang past them falls into the NULL sink), and
        # fork any shared block the scatter would touch (only the full-
        # match re-feed row can be shared: adopted blocks sit below the
        # write start). Either can hit arena pressure under sharing.
        self._ensure_preempting(
            slot, lambda: pool.ensure_rows(slot, start + n_tok)
        )
        if self.prefix_sharing:
            self._ensure_preempting(
                slot, lambda: pool.ensure_writable(slot, start, start + n_tok)
            )
        # Capture the slot view AFTER the ensures: a copy-on-write fork
        # rewrites pool.caches, and an earlier capture would hand the
        # prefill a stale arena missing the forked block's rows.
        slot_caches = (self._fresh_slot_caches() if first
                       else pool.read_slot(slot))
        with span("repro.engine.dispatch"):
            logits, slot_caches, *rows = self._prefill(
                self.params,
                jnp.asarray(chunk),
                slot_caches,
                jnp.asarray([n_tok], jnp.int32),
                jnp.int32(start),
                pool.tables_device(slot),
            )
        self._moe_step(rows, bucket)
        pool.write_slot(slot, slot_caches, position=start + n_tok)
        if self.speculative:
            # The draft cache must hold the same prefix (same bucketed
            # chunk, so the draft reuses the target's compile shapes).
            self.draft.prefill_chunk(
                slot, jnp.asarray(chunk), n_tok, start, owner=req.rid
            )
            sched.on_draft_prefill(n_tok)
        done = start + n_tok >= req.prefill_len
        sched.on_prefill_chunk(req, n_tok, done)
        self.stats.prefill_calls += 1
        self.stats.prefill_tokens += n_tok
        if done:
            if self.prefix_sharing:
                pool.register_prefix(slot, req.prompt)
            if req.tokens:
                # Replay of a preempted request: every emitted token is
                # already in the stream — re-enter decode exactly where
                # the eviction hit, feeding the last emitted token. No
                # emit here, so the stream stays byte-identical.
                self._pending[slot] = np.int32(req.tokens[-1])
                self._decoding[slot] = True
            else:
                with span("repro.engine.sync"):
                    tok = int(self._read(jnp.argmax(logits[0, -1])))
                self._emit(req, tok)
                if self._finished(req):     # max_new_tokens == 1
                    self._free_slot(slot)
                else:
                    self._pending[slot] = tok
                    self._decoding[slot] = True
        self.events.append(("prefill", self.sched.clock.now, req.rid))
        if self.obs.enabled:
            self._m_prefill_tokens.inc(n_tok)
            self._tr.complete(
                "prefill", self.pid, t0, sched.clock.now,
                args={"rid": req.rid, "start": start,
                      "n_tokens": n_tok, "done": done},
            )

    def _moe_step(self, rows: List[jax.Array], tokens: int) -> None:
        """Hold a step's held-expert rows (``rows``: [] or its one device
        scalar) for the next read; count what its ``tokens``-token call
        computed: held experts x capacity x MoE layers."""
        if rows:
            moe = self.model.cfg.moe
            self._moe_pending.append(rows[0])
            self._moe_computed += (moe.held * moe_capacity(moe, tokens)
                                   * (self.model.cfg.n_layers - moe.first_k_dense))

    def _read(self, tokens: jax.Array) -> np.ndarray:
        """Bring a step's tokens to the host, and in the same transfer the
        held-expert rows of the steps since the last read; these land in
        ``stats`` and in a ``repro.engine.moe`` span (``moe_rows=``,
        ``moe_rows_computed=``)."""
        if not self._moe_pending:
            return np.asarray(tokens)
        tokens, rows = jax.device_get((tokens, self._moe_pending))
        moe_rows, computed = int(sum(rows)), self._moe_computed
        self._moe_pending, self._moe_computed = [], 0
        self.stats.moe_rows += moe_rows
        self.stats.moe_rows_computed += computed
        with span("repro.engine.moe", moe_rows=moe_rows, moe_rows_computed=computed):
            pass
        return np.asarray(tokens)

    def _free_slot(self, slot: int) -> None:
        self.pool.free(slot)
        if self.speculative:
            self.draft.pool.free(slot)

    # -- preemption (prefix sharing, DESIGN.md §16) --------------------------
    def _recompute_cost(self, req: Request, slot: int) -> float:
        """Price of evicting ``slot`` now: prefill over the replay
        sequence MINUS whatever prefix would still be trie-resident
        after the victim's own references drop (it re-adopts that part
        for free on requeue)."""
        replay = req.prompt_len + max(len(req.tokens) - 1, 0)
        resident = self.pool.match_resident(
            req.prefill_target(), exclude_slot=slot
        )
        return self.sched.clock.cost.recompute(replay - resident)

    def _preempt_slot(self, slot: int) -> None:
        """Evict ``slot``'s request and requeue it: blocks freed NOW,
        emitted tokens kept, next admission replays from the longest
        still-resident prefix (byte-identical continuation — pinned in
        tests/test_prefix.py)."""
        req = self._requests[self.pool.owner[slot]]
        self._decoding[slot] = False
        self._pending[slot] = 0
        self._free_slot(slot)
        self.sched.requeue(req)
        self.stats.preempted_requests += 1
        now = self.sched.clock.now
        self.events.append(("preempt", now, req.rid))
        if self.obs.enabled:
            self.obs.metrics.counter("engine.preempted").inc()
            self._tr.instant("preempt", self.pid, now,
                             args={"rid": req.rid,
                                   "n_tokens": len(req.tokens)})

    def _preempt_for(self, needy_slot: int) -> None:
        """FORCED eviction: ``needy_slot``'s in-flight write hit an empty
        free list and must proceed (its action is half-priced already).
        Evict the cheapest-to-recompute OTHER lane, preferring decoding
        lanes (a mid-prefill lane has no emitted stream to protect).
        Livelock-free: every forced preemption follows the preemptor
        completing a write + emit, so global progress is monotone."""
        best, best_rc = None, None
        for s in np.nonzero(self.pool.active)[0]:
            s = int(s)
            if s == needy_slot:
                continue
            rc = self._recompute_cost(
                self._requests[self.pool.owner[s]], s
            )
            # Decoding lanes first: preempting the mid-prefill lane the
            # scheduler is committed to would wedge its chunk loop.
            rank = (0 if self._decoding[s] else 1, rc)
            if best_rc is None or rank < best_rc:
                best, best_rc = s, rank
        if best is None:
            raise RuntimeError(
                f"arena exhausted with no preemptable lane (slot "
                f"{needy_slot} alone holds the arena) — raise arena_blocks"
            )
        self._preempt_slot(best)

    def _ensure_preempting(self, slot: int, fn) -> None:
        """Run a block-allocating pool op, evicting lanes until it fits
        (sharing mode; pass-through elsewhere — legacy commitment makes
        exhaustion impossible)."""
        while True:
            try:
                return fn()
            except ArenaExhausted:
                self._preempt_for(slot)

    def _maybe_preempt_for_admission(self) -> None:
        """PRICED eviction at admission: when the queue head is blocked
        on blocks (not on slots), evict the lane whose recompute is
        cheapest — but only if recompute undercuts holding it to
        completion (the paper's wait-vs-recompute trade, priced by the
        event-clock cost model), and only from requests strictly YOUNGER
        than the head. The age guard makes admission eviction a strict
        priority order, so two queued requests can never evict each
        other in a ping-pong (the oldest live request is never evicted
        for admission — it only ever finishes). At most one eviction per
        step keeps the policy incremental and replayable."""
        sched = self.sched
        if sched.running:
            return                      # finish the in-flight prefill first
        req = sched._eligible()
        if req is None or self.pool.n_free == 0 or self._can_admit(req):
            return
        cost = sched.clock.cost
        head_key = (req.arrival, req.rid)
        best, best_rc = None, None
        for s in np.nonzero(self.pool.active)[0]:
            s = int(s)
            victim = self._requests[self.pool.owner[s]]
            if (victim.arrival, victim.rid) <= head_key:
                continue                # never evict an older request
            rc = self._recompute_cost(victim, s)
            hold = cost.hold(victim.max_new_tokens - len(victim.tokens))
            if rc < hold and (best_rc is None or rc < best_rc):
                best, best_rc = s, rc
        if best is not None:
            self._preempt_slot(best)

    def _do_decode(self) -> None:
        pool = self.pool
        t0 = self.sched.clock.now
        # Each decoding lane writes one row at its position: grow its
        # block table (and fork any shared block under sharing) BEFORE
        # snapshotting the lane mask — under arena pressure these ensures
        # may preempt OTHER decoding lanes, which must then drop out of
        # this tick. Legacy mode never fails here (whole-budget commit).
        for slot in np.nonzero(self._decoding)[0]:
            slot = int(slot)
            if not self._decoding[slot]:
                continue                # preempted by an earlier ensure
            pos = int(pool.positions[slot])
            self._ensure_preempting(
                slot, lambda s=slot, p=pos: pool.ensure_rows(s, p + 1)
            )
            if self.prefix_sharing and self._decoding[slot]:
                self._ensure_preempting(
                    slot,
                    lambda s=slot, p=pos: pool.ensure_writable(s, p, p + 1),
                )
        mask = self._decoding.copy()
        with span("repro.engine.dispatch"):
            tokens = jnp.asarray(self._pending[:, None])
            positions = jnp.asarray(np.clip(pool.positions, 0, pool.max_len - 1))
            logits, pool.caches, *rows = self._decode(
                self.params, tokens, pool.caches, positions, jnp.asarray(mask),
                pool.tables_device(lanes=mask),
            )
        self._moe_step(rows, pool.n_slots)
        self.sched.on_decode_tick()
        self.stats.decode_ticks += 1
        with span("repro.engine.sync"):
            next_tok = np.asarray(self._read(jnp.argmax(logits[:, -1, :], axis=-1)),
                                  np.int32)
        for slot in np.nonzero(mask)[0]:
            slot = int(slot)
            pool.positions[slot] += 1
            req = self._requests[pool.owner[slot]]
            self._emit(req, int(next_tok[slot]))
            if self._finished(req):
                self._decoding[slot] = False
                self._free_slot(slot)
            else:
                self._pending[slot] = next_tok[slot]
        self.events.append(("decode", self.sched.clock.now, -1))
        if self.obs.enabled:
            self._m_decode_ticks.inc()
            self._tr.complete(
                "decode", self.pid, t0, self.sched.clock.now,
                args={"lanes": int(mask.sum())},
            )

    def _do_spec_round(self) -> None:
        """One draft-then-verify round over the whole pool (replaces a
        decode tick when a draft model is attached).

        Per-lane draft budgets enter the fixed-shape verify call as DATA
        (``n_input``: 0 = free/mid-prefill lane, 1 = plain decode — a
        lane one token from its budget — 1 + gamma_b = speculating), so
        one compile per window width covers every occupancy pattern.
        Rollback is the position rewind described in DESIGN.md §12.2:
        the verify call itself committed only what the acceptance rule
        allows, block tables keep their (within-budget) blocks, and the
        draft resyncs by replaying the committed tokens from its
        snapshot."""
        pool, sched, draft = self.pool, self.sched, self.draft
        t0 = sched.clock.now
        n_slots = pool.n_slots
        decoding = self._decoding.copy()
        slots = np.nonzero(decoding)[0]
        plan = self.spec.choose_gamma(sched.clock.cost)
        gamma = plan.gamma
        if gamma == 0 or slots.size == 0:
            # Plain decode tick — but the draft cache must still consume
            # the tokens the target consumes, or it falls behind the
            # committed stream and later rounds would draft from a stale
            # prefix. One masked draft tick (proposal discarded) keeps
            # the lockstep; lanes that finished were freed in both pools.
            old_pending = self._pending.copy()
            self._do_decode()
            live = decoding & self._decoding
            if live.any():
                draft.decode_tick(old_pending, live)
                sched.on_draft_decode()
                self.stats.draft_ticks += 1
            return
        # Per-lane draft budget: never draft past a request's remaining
        # token budget (the last emitted token needs no successor), which
        # also keeps every verify write inside the committed block budget.
        remaining = np.zeros(n_slots, np.int64)
        for slot in slots:
            req = self._requests[pool.owner[slot]]
            remaining[slot] = req.max_new_tokens - len(req.tokens)
        gamma_b = np.minimum(gamma, np.maximum(remaining - 1, 0))
        S = gamma + 1
        inputs = np.zeros((n_slots, S), np.int32)
        inputs[:, 0] = self._pending
        n_input = np.zeros(n_slots, np.int32)
        n_input[slots] = 1 + gamma_b[slots]

        # -- draft phase: gamma masked sequential ticks ----------------------
        draft.snapshot()
        tokens = self._pending.copy()
        draft_ticks = 0
        for j in range(gamma):
            mask_j = decoding & (gamma_b > j)
            if not mask_j.any():
                break
            proposed = draft.decode_tick(tokens, mask_j)
            tokens = np.where(mask_j, proposed, tokens)
            inputs[mask_j, j + 1] = proposed[mask_j]
            draft_ticks += 1

        # -- verify phase: one fused target call over the pool ---------------
        starts = pool.positions.copy()
        for slot in slots:
            pool.ensure_rows(int(slot), int(starts[slot]) + int(n_input[slot]))
        with span("repro.engine.dispatch"):
            positions = jnp.asarray(np.clip(starts, 0, pool.max_len - 1))
            greedy, pool.caches = self._verify(
                self.params, jnp.asarray(inputs), pool.caches,
                jnp.asarray(n_input), positions, pool.tables_device(),
            )
        with span("repro.engine.sync"):
            greedy = np.asarray(greedy, np.int32)

        # -- acceptance: exact argmax chain, then emit + rewind --------------
        n_commit = np.zeros(n_slots, np.int32)
        emitted_live: List[int] = []   # per-lane commits, still-decoding lanes
        emitted_all: List[int] = []
        for slot in slots:
            slot = int(slot)
            ni = int(n_input[slot])
            a = 0
            while a < ni - 1 and greedy[slot, a] == inputs[slot, a + 1]:
                a += 1
            self.spec.observe(a, ni - 1)
            self.stats.spec_accepted += a
            req = self._requests[pool.owner[slot]]
            for i in range(a + 1):
                self._emit(req, int(greedy[slot, i]))
            pool.positions[slot] = int(starts[slot]) + a + 1
            n_commit[slot] = a + 1
            emitted_all.append(a + 1)
            if self._finished(req):
                self._decoding[slot] = False
                self._free_slot(slot)
                n_commit[slot] = 0      # freed draft lane: leave it alone
            else:
                self._pending[slot] = greedy[slot, a]
                emitted_live.append(a + 1)

        # -- draft resync: rollback to the committed stream ------------------
        extra_ticks, replayed = draft.resync(inputs, n_commit)
        draft_ticks += extra_ticks
        # Debt credit = the WEAKEST live lane's progress: a low-acceptance
        # lane must still see decode_per_prefill rounds' worth of tokens
        # between prefill chunks (finished lanes need no guarantee; an
        # all-finished round credits its full commit).
        emitted = min(emitted_live) if emitted_live else max(emitted_all)
        sched.on_spec_round(draft_ticks, S, emitted, replay=replayed)
        self.stats.spec_rounds += 1
        self.stats.draft_ticks += draft_ticks
        self.events.append(("spec", sched.clock.now, -1))
        if self.obs.enabled:
            self._tr.complete(
                "spec_round", self.pid, t0, sched.clock.now,
                args={"gamma": int(gamma), "lanes": int(slots.size),
                      "committed": int(sum(emitted_all))},
            )

    def _emit(self, req: Request, tok: int) -> None:
        if not req.tokens:
            req.t_first_token = self.sched.clock.now
        req.tokens.append(tok)
        self.stats.generated_tokens += 1
        self._m_tokens.inc()

    def _finished(self, req: Request) -> bool:
        if len(req.tokens) >= req.max_new_tokens:
            if req.t_done is None:
                req.t_done = self.sched.clock.now
                self._end_request_span(req, "done", req.t_done)
            return True
        return False

    def defrag(self) -> Dict[int, int]:
        """Compact the pool's live slots and remap the engine's per-slot
        decode state to match — safe mid-flight (bare ``pool.defrag()``
        would silently desync ``_pending``/``_decoding``). With a draft
        attached, the draft pool compacts with the identical permutation
        (its occupancy mirrors the target's by construction), keeping
        the two pools in slot-index lockstep across the move."""
        moves = self.pool.defrag()
        if self.speculative:
            draft_moves = self.draft.pool.defrag()
            assert draft_moves == moves, (
                f"draft pool desync under defrag: {draft_moves} != {moves}"
            )
        if moves:
            inv = {new: old for old, new in moves.items()}
            pending, decoding = self._pending, self._decoding
            self._pending = np.zeros_like(pending)
            self._decoding = np.zeros_like(decoding)
            for s in np.nonzero(self.pool.active)[0]:
                src = inv.get(int(s), int(s))
                self._pending[s] = pending[src]
                self._decoding[s] = decoding[src]
        return moves

    # -- driver --------------------------------------------------------------
    def step(self) -> str:
        """Run one scheduler action; returns its kind. Deadlines are
        policed here, before the action is chosen — an expired request's
        slot (and blocks) are free by the time admission is priced.

        Each step and its phases are wall spans (``repro.engine.*``,
        docs/observability.md) that record while a ``jax.profiler`` trace
        is running."""
        with span("repro.engine.step"):
            with span("repro.engine.schedule"):
                self._expire_deadlines()
                if self.prefix_sharing:
                    self._maybe_preempt_for_admission()
                kind, req = self.sched.next_action(
                    self.pool.n_active, self.pool.n_free, self._can_admit
                )
            if kind == "prefill":
                with span("repro.engine.prefill", rid=req.rid):
                    self._do_prefill(req)
            elif kind == "decode":
                if self.speculative:
                    with span("repro.engine.spec"):
                        self._do_spec_round()
                else:
                    # live_rows: the KV rows the tick attends over.
                    live = self._decoding
                    with span("repro.engine.decode", kv_path=self._kv_path,
                              live_rows=int(self.pool.positions[live].sum() + live.sum())):
                        self._do_decode()
            elif kind == "idle":
                t0 = self.sched.clock.now
                self.sched.on_idle()
                self.events.append(("idle", self.sched.clock.now, -1))
                if self._tr.enabled:
                    self._tr.complete("idle", self.pid, t0, self.sched.clock.now)
            if self.obs.enabled and kind != "done":
                self._g_slots.set(self.pool.n_active)
                values = {"slots": int(self.pool.n_active)}
                if self.pool.paged:
                    used = self.pool.manager.n_used_blocks
                    self._g_blocks.set(used)
                    values["blocks"] = int(used)
                self._tr.counter(
                    "occupancy", self.pid, self.sched.clock.now, values
                )
            return kind

    def run(self) -> Dict[int, Request]:
        """Drive until every submitted request completes."""
        while self.step() != "done":
            pass
        self.stats.virtual_seconds = self.sched.clock.now
        return dict(self._requests)


# ---------------------------------------------------------------------------
# References: per-request offline decode + static batching baseline
# ---------------------------------------------------------------------------

@model_scoped_cache
def _offline_decode(model):
    return jax.jit(model.decode_step)


def generate_offline(
    model, params, prompt, max_new_tokens: int, max_len: int
) -> List[int]:
    """Single-request greedy generation with batch-1 caches — the token
    stream the continuous-batching engine must reproduce exactly."""
    prompt = np.asarray(prompt, np.int32).reshape(1, -1)
    P = prompt.shape[1]
    caches = model.blank_caches(1, max_len)
    logits, caches = model.prefill_with_cache(
        params, jnp.asarray(prompt), caches,
        length=jnp.asarray([P], jnp.int32), start_index=jnp.int32(0),
    )
    tok = int(jnp.argmax(logits[0, -1]))
    out = [tok]
    decode = _offline_decode(model)
    for t in range(P, P + max_new_tokens - 1):
        logits, caches = decode(
            params, jnp.asarray([[tok]], jnp.int32), caches, jnp.int32(t)
        )
        tok = int(jnp.argmax(logits[0, -1]))
        out.append(tok)
    return out


class _StaticScheduler(Scheduler):
    """Static batching: admissions barrier until the pool fully drains."""

    def __init__(self, n_slots: int, *, clock: Optional[EventClock] = None):
        super().__init__(n_slots, clock=clock)
        self._barrier_open = True

    def next_action(self, n_active: int, n_free: int, can_admit=None):
        if n_active == 0:
            self._barrier_open = True
        if self.running:
            return "prefill", self.running[0]
        req = self._eligible()
        if (req is not None and n_free > 0 and self._barrier_open
                and (can_admit is None or can_admit(req))):
            return "prefill", req
        if n_active > 0:
            self._barrier_open = False
            return "decode", None
        if self._next_arrival() is not None:
            return "idle", None
        return "done", None


def run_static(
    model,
    params,
    requests: List[Tuple[np.ndarray, int, float]],   # (prompt, max_new, arrival)
    *,
    n_slots: int,
    max_len: int,
    cost: Optional[CostModel] = None,
    prefill_bucket: int = 16,
) -> Tuple[Dict[int, Request], EngineStats]:
    """Same kernels/pool, static-batch admission (the baseline)."""
    sched = _StaticScheduler(n_slots, clock=EventClock(cost))
    eng = ServeEngine(
        model, params, n_slots=n_slots, max_len=max_len,
        scheduler=sched, prefill_bucket=prefill_bucket,
    )
    for prompt, m, arr in requests:
        eng.submit(prompt, m, arrival=arr)
    return eng.run(), eng.stats
