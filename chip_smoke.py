#!/usr/bin/env python3
"""Chip smoke: the paper's main path, once, on a TPU at smollm-135m's
published widths (30 layers, d_model 576, 9 heads / 3 KV heads, vocab
49152, tied embeddings, bfloat16, full remat). Weights and data come from
a seed; nothing is downloaded.

    python chip_smoke.py               # one chip: train, then serve
    python chip_smoke.py --four-chips  # sharded train on four chips,
                                       # against one device of the four

Phases, all in this one process (it alone holds the chips):

  train  ``runtime.train_loop.train`` as ``examples/train_lm.py`` builds
         it: adaptive (k, beta) over n = 8 simulated workers, beta grid
         (0.25, 0.5, 0.75, 1.0), seq 1024, global batch 16, tokens from
         a seeded ``TokenStream`` over the first 4096 ids. A checkpoint
         at step 20 is resumed in a second run, whose history and final
         params must equal the uninterrupted run's.
  serve  ``ServeEngine`` with paged KV (block 16) and its ``Scheduler``:
         8 slots, max_len 1024, 12 seeded requests. Every request must
         finish. Its streams are compared with ``generate_offline``: the
         bf16 pass reports the share of agreeing tokens (bf16 matmuls can
         flip near-ties of random-init logits); a float32 pass under
         ``jax.default_matmul_precision("highest")`` must agree exactly.
  four   (``--four-chips`` only) the train loop on a (data=4) mesh, params
         and optimizer state sharded by ``DEFAULT_RULES`` and each batch
         split over ``data``, against the same seed on one device: in
         bfloat16 the loss gap is reported, and in float32 under
         ``jax.default_matmul_precision("highest")`` it must stay within
         ``FOUR_CHIP_RTOL`` at every step.

Around each phase the script waits for the device and prints wall
seconds split into compile (lowering plus compiling, or reading the
persistent compilation cache) and the rest. These are smoke timings, not
benchmark numbers. The last line of stdout is one JSON object naming the
device. With no TPU, or outside a checkout, it exits non-zero and prints
no result.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import sys
import tempfile
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
SRC = ROOT / "src"

SEED = 0
# train
N_WORKERS, SEQ, GLOBAL_BATCH = 8, 1024, 16
BETAS = (0.25, 0.5, 0.75, 1.0)
TRAIN_STEPS, RESUME_AT, FOUR_CHIP_STEPS = 30, 20, 12
LR = 1e-3
# The token stream draws from the first 4096 ids of the 49152-id vocab,
# as text puts most of its mass on a few thousand tokens. Over the whole
# vocab its next-token rule is a random permutation of 49152 ids that no
# 30-step run can learn (the loss then drifts up under Adam's per-element
# steps); over 4096 ids the loss falls within the run.
STREAM_VOCAB = 4096
# Relative loss tolerance, at every step, of the four-chip float32 run
# against one device: only the order of the sharded reductions differs.
# The bfloat16 runs are compared too but not gated: Adam's first updates
# are nearly sign(g), so a last-bit bf16 gradient difference can flip a
# whole lr-sized step, and bf16 params keep it.
FOUR_CHIP_RTOL = 1e-3
# serve: a bf16 pass, then a shorter float32 pass that must be exact
SLOTS, MAX_LEN, BLOCK, PREFILL_CHUNK, PREFILL_BUCKET = 8, 1024, 16, 256, 64
N_REQUESTS, PROMPT_LENS, NEW_TOKENS = 12, range(64, 513, 64), (32, 64)
EXACT_REQUESTS, EXACT_PROMPT_LENS, EXACT_NEW_TOKENS = 4, (64, 128, 192), (16, 32)


class SmokeFailure(RuntimeError):
    """A phase produced a wrong or missing result."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


class CompileClock:
    """Seconds JAX spends lowering and compiling (or reading compiled
    programs back from the persistent cache), from its monitoring
    events."""

    EVENTS = (
        "/jax/core/compile/jaxpr_to_mlir_module_duration",
        "/jax/core/compile/backend_compile_duration",
    )

    def __init__(self):
        from jax import monitoring

        self.seconds = 0.0
        self.compiles = 0
        self.cache_hits = 0
        monitoring.register_event_duration_secs_listener(self._on_duration)
        monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, duration, **_):
        if event in self.EVENTS:
            self.seconds += duration
            if event == self.EVENTS[1]:
                self.compiles += 1

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


@contextlib.contextmanager
def phase(name: str, clock: CompileClock):
    """Print a phase's wall seconds, split into compile and the rest. The
    body must end with the device idle (``block_until_ready``)."""
    c0, n0, h0 = clock.seconds, clock.compiles, clock.cache_hits
    t0 = time.perf_counter()
    yield
    wall = time.perf_counter() - t0
    comp = clock.seconds - c0
    print(f"[smoke timing, not a benchmark] {name}: wall {wall:.3f} s = "
          f"compile {comp:.3f} s ({clock.compiles - n0} programs, "
          f"{clock.cache_hits - h0} from cache) + steady {wall - comp:.3f} s",
          flush=True)


# ---------------------------------------------------------------------------
# train
# ---------------------------------------------------------------------------

def run_train(cfg, *, steps: int, checkpoint_dir=None, mesh=None):
    """One ``train`` call built as ``examples/train_lm.py`` builds it. The
    diagnostic's ``rel_tol=1.0`` counts any non-rising loss as a plateau,
    so the stage advances every ``min_iters`` steps whatever the loss
    does: the smoke drives the stage switches and the per-beta batch
    shapes, not the diagnostic."""
    import jax

    from repro.core import DiagnosticConfig, SimplifiedDelayModel, StrategyConfig
    from repro.data import StagedBatcher, TokenStream
    from repro.models import build_model
    from repro.optim.optimizers import get_optimizer
    from repro.runtime.train_loop import TrainLoopConfig, train

    n = N_WORKERS
    strategy = StrategyConfig(
        "adaptive_kbeta", n=n, s=GLOBAL_BATCH // n, k_max=n // 2,
        beta_grid=BETAS,
        diagnostic=DiagnosticConfig(kind="loss", rel_tol=1.0, min_iters=3,
                                    consecutive=1),
    )
    batcher = StagedBatcher(TokenStream(min(STREAM_VOCAB, cfg.vocab_size),
                                        seed=SEED),
                            n_workers=n, global_batch=GLOBAL_BATCH,
                            seq_len=SEQ)
    loop_cfg = TrainLoopConfig(
        total_steps=steps, lr=LR, seed=SEED, log_every=10,
        checkpoint_dir=None if checkpoint_dir is None else str(checkpoint_dir),
        checkpoint_every=RESUME_AT,
    )
    out = train(build_model(cfg), get_optimizer("adamw", weight_decay=0.01),
                strategy, SimplifiedDelayModel(lambda_y=1.0, x=0.05), batcher,
                loop_cfg, mesh=mesh)
    jax.block_until_ready((out["params"], out["opt_state"]))
    return out


def train_phase(cfg, clock: CompileClock) -> None:
    import jax
    import jax.numpy as jnp

    with tempfile.TemporaryDirectory(dir=ROOT, prefix=".smoke_ckpt_") as d:
        with phase(f"train {TRAIN_STEPS} steps, checkpoint at {RESUME_AT}",
                   clock):
            full = run_train(cfg, steps=TRAIN_STEPS, checkpoint_dir=d)
        with phase(f"train resumed from step {RESUME_AT}", clock):
            resumed = run_train(cfg, steps=TRAIN_STEPS, checkpoint_dir=d)
    hist = full["history"]
    losses = np.array([h["loss"] for h in hist])
    switches = [h["switched_to"] for h in hist if "switched_to" in h]
    print(f"train: loss {losses[0]:.4f} -> {losses[-1]:.4f}; stage switches "
          f"{switches}; compiled batch shapes {full['compiled_shapes']}",
          flush=True)
    check(len(hist) == TRAIN_STEPS, f"train ran {len(hist)} steps")
    check(bool(np.isfinite(losses).all()), f"non-finite loss: {losses}")
    check(losses[-5:].mean() < losses[:5].mean(),
          f"loss did not fall: {losses}")
    check(len(switches) >= 1, "no (k, beta) stage switch happened")
    check(len(full["compiled_shapes"]) >= 2,
          f"one beta batch shape only: {full['compiled_shapes']}")
    check(resumed["history"] == hist[RESUME_AT:],
          "resumed history differs from the uninterrupted run")
    same = jax.tree.map(lambda a, b: bool(jnp.array_equal(a, b)),
                        full["params"], resumed["params"])
    check(all(jax.tree.leaves(same)),
          "resumed params differ from the uninterrupted run")
    print(f"train: resume from step {RESUME_AT} replayed "
          f"{len(resumed['history'])} steps field for field, params equal",
          flush=True)


# ---------------------------------------------------------------------------
# serve
# ---------------------------------------------------------------------------

def make_requests(vocab: int, n: int, prompt_lens, new_tokens, seed: int):
    rng = np.random.default_rng(seed)
    return [
        (rng.integers(0, vocab, size=int(rng.choice(prompt_lens))).astype(
            np.int32),
         int(rng.integers(new_tokens[0], new_tokens[1] + 1)))
        for _ in range(n)
    ]


def serve_and_reference(model, params, requests, clock: CompileClock,
                        label: str):
    """(engine streams, offline streams) for ``requests``."""
    import jax

    from repro.serve import Scheduler, ServeEngine, generate_offline

    with phase(f"{label}: engine, {len(requests)} requests", clock):
        engine = ServeEngine(
            model, params, n_slots=SLOTS, max_len=MAX_LEN,
            scheduler=Scheduler(SLOTS, prefill_chunk=PREFILL_CHUNK),
            block_size=BLOCK, prefill_bucket=PREFILL_BUCKET,
        )
        rids = [engine.submit(p, m, arrival=i * 1e-3)
                for i, (p, m) in enumerate(requests)]
        results = engine.run()
        jax.block_until_ready(engine.pool.caches)
    s = engine.stats
    print(f"{label}: {s.generated_tokens} tokens, {s.prefill_calls} prefill "
          f"calls, {s.decode_ticks} decode ticks, arena high-water "
          f"{engine.pool.manager.used_high_water}/"
          f"{engine.pool.manager.num_blocks} blocks", flush=True)
    with phase(f"{label}: generate_offline reference", clock):
        offline = [generate_offline(model, params, p, m, MAX_LEN)
                   for p, m in requests]
    return [list(results[r].tokens) for r in rids], offline


def serve_phase(cfg, clock: CompileClock) -> None:
    import jax
    import jax.numpy as jnp

    from repro.models import build_model

    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(SEED))
    requests = make_requests(cfg.vocab_size, N_REQUESTS, PROMPT_LENS,
                             NEW_TOKENS, SEED)
    got, want = serve_and_reference(model, params, requests, clock,
                                    f"serve {cfg.dtype}")
    for (_, m), toks in zip(requests, got):
        check(len(toks) == m, f"request finished with {len(toks)}/{m} tokens")
    agree = sum(int(a == b) for g, w in zip(got, want) for a, b in zip(g, w))
    total = sum(m for _, m in requests)
    print(f"serve {cfg.dtype}: all {len(requests)} requests complete; "
          f"{agree}/{total} tokens ({agree / total:.4f}) agree with "
          f"generate_offline", flush=True)
    logits = jax.jit(model.prefill)(params, jnp.asarray(requests[0][0])[None])
    check(logits.shape == (1, 1, cfg.vocab_size),
          f"prefill logits shape {logits.shape}")
    check(bool(jnp.isfinite(logits).all()), "non-finite prefill logits")

    # Exact token identity is a float32 property: the same weights, widened.
    cfg32 = dataclasses.replace(cfg, dtype="float32")
    model32 = build_model(cfg32)
    params32 = jax.tree.map(lambda p: p.astype(jnp.float32), params)
    requests32 = make_requests(cfg.vocab_size, EXACT_REQUESTS,
                               EXACT_PROMPT_LENS, EXACT_NEW_TOKENS, SEED + 1)
    with jax.default_matmul_precision("highest"):
        got32, want32 = serve_and_reference(
            model32, params32, requests32, clock, "serve float32 highest")
    for i, ((_, m), g, w) in enumerate(zip(requests32, got32, want32)):
        check(len(g) == m, f"float32 request {i}: {len(g)}/{m} tokens")
        check(g == w, f"float32 request {i}: engine {g} != offline {w}")
    print(f"serve float32 highest: {len(requests32)} streams identical to "
          f"generate_offline token for token", flush=True)


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------

def param_byte_shares(params):
    """{device: share of all parameter bytes held on it}."""
    import jax

    held: dict = {}
    total = 0
    for leaf in jax.tree.leaves(params):
        total += leaf.nbytes
        for shard in leaf.addressable_shards:
            held[shard.device] = held.get(shard.device, 0) + shard.data.nbytes
    return {d: b / total for d, b in held.items()}


def sharded_vs_one_device(cfg, clock: CompileClock, mesh, label: str):
    """(sharded run, one-device run, relative loss gap per step) of the
    same seed."""
    with phase(f"four chips {label}: sharded train, {FOUR_CHIP_STEPS} steps",
               clock):
        sharded = run_train(cfg, steps=FOUR_CHIP_STEPS, mesh=mesh)
    with phase(f"four chips {label}: same seed on one device", clock):
        single = run_train(cfg, steps=FOUR_CHIP_STEPS)
    l4 = np.array([h["loss"] for h in sharded["history"]])
    l1 = np.array([h["loss"] for h in single["history"]])
    rel = np.abs(l4 - l1) / np.abs(l1)
    print(f"four chips {label}: sharded losses {l4.tolist()}", flush=True)
    print(f"four chips {label}: one-device losses {l1.tolist()}", flush=True)
    print(f"four chips {label}: relative loss gap per step {rel.tolist()} "
          f"(max {rel.max()})", flush=True)
    check(bool(np.isfinite(l4).all()), f"non-finite sharded loss: {l4}")
    check([h["beta"] for h in sharded["history"]]
          == [h["beta"] for h in single["history"]],
          f"{label} sharded run walked another stage path")
    check(len(sharded["compiled_shapes"]) >= 2,
          f"one beta batch shape only: {sharded['compiled_shapes']}")
    return sharded, single, rel


def four_chip_phase(cfg, clock: CompileClock, devices) -> None:
    import jax

    from repro.launch.mesh import make_mesh

    mesh = make_mesh((4,), ("data",), devices=devices[:4])
    sharded, _, _ = sharded_vs_one_device(cfg, clock, mesh, cfg.dtype)
    shares = param_byte_shares(sharded["params"])
    print("four chips: parameter bytes per device: "
          + ", ".join(f"{d.id}: {s:.4f}" for d, s in sorted(
              shares.items(), key=lambda kv: kv[0].id)), flush=True)
    check(len(shares) == 4, f"params live on {len(shares)} devices, not 4")
    check(max(shares.values()) < 0.5,
          f"one device holds {max(shares.values()):.2%} of the params")
    l4 = [h["loss"] for h in sharded["history"]]
    check(np.mean(l4[-3:]) < np.mean(l4[:3]), f"sharded loss did not fall: {l4}")
    del sharded

    # The gate: the same weights and batches in float32, so that the only
    # difference left is the order of the sharded reductions.
    cfg32 = dataclasses.replace(cfg, dtype="float32")
    with jax.default_matmul_precision("highest"):
        _, _, rel = sharded_vs_one_device(cfg32, clock, mesh,
                                          "float32 highest")
    check(rel.max() <= FOUR_CHIP_RTOL,
          f"float32 sharded losses off by {rel.max()} relative "
          f"(tolerance {FOUR_CHIP_RTOL})")


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded train phase on four chips "
                         "and the one-device run it is compared with")
    args = ap.parse_args(argv)
    if not (SRC / "repro").is_dir():
        print(f"chip_smoke: no repro package at {SRC}: run this script from "
              "a checkout of the repository", file=sys.stderr)
        return 2
    sys.path.insert(0, str(SRC))

    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, but JAX found platform "
              f"{dev.platform!r} ({dev.device_kind})", file=sys.stderr)
        return 2
    need = 4 if args.four_chips else 1
    if len(devices) < need:
        print(f"chip_smoke: needs {need} chips, JAX found {len(devices)}",
              file=sys.stderr)
        return 2

    from repro.configs import get_config
    from repro.runtime.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    clock = CompileClock()
    cfg = get_config("smollm-135m")   # published widths, bf16, full remat
    print(f"device: {dev.platform} {dev.device_kind} x{len(devices)}; "
          f"jax {jax.__version__}; compile cache {cache_dir}; model "
          f"{cfg.name} {cfg.n_layers}L d{cfg.d_model} vocab {cfg.vocab_size} "
          f"{cfg.dtype} remat={cfg.remat}", flush=True)

    if args.four_chips:
        four_chip_phase(cfg, clock, devices)
    else:
        train_phase(cfg, clock)
        serve_phase(cfg, clock)
    stats = dev.memory_stats() or {}
    if "peak_bytes_in_use" in stats:
        print(f"device 0 peak bytes in use: {stats['peak_bytes_in_use']}",
              flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
