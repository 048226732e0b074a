"""Training cells: the program's ``train()`` pinned at one (k, beta) stage.

One call of ``repro.runtime.train_loop.train`` is the object under test:
it builds the weights, the optimizer state and the compiled step, and
runs every step, the set-up steps and the window's alike. The harness
gives it a batcher of its own (``Feed``), whose ``batch_for_stage`` the
loop calls once at the start of each step, after the previous step's
loss has come back to the host. So the calls mark the step boundaries:

* calls 0-3 are set-up: step 0 compiles (or loads from the cache), and
  the state of steps 0-3 is what the reference checks;
* call 4 opens the window; the call that finds ``--seconds`` elapsed
  ends it by raising ``WindowClosed``, which leaves ``train()``.

The loop offers no hook for its state, so the feed reads the loop's
local variables (``params``, ``opt_state``, ``mask``, ``history``) from
its caller's frame, read-only, at set-up calls. The stage is pinned by
the strategy's ``k0``/``beta0`` and a diagnostic whose ``min_iters`` no
run reaches, so the controller, telemetry and diagnostic still run
every step.
"""

from __future__ import annotations

import gc
import sys
import time
from typing import Dict, List, Optional

import numpy as np

from ..common import memory_peak_bytes
from ..gen import token_stream
from ..model import dims_of, program_config
from ..tracing import Window
from ..weights import make_weights, program_seed
from . import Check, RunResult, gap, worst_leaf_gap

SETUP_STEPS = 4          # steps 0-3 run before the window
CHECKED_STEPS = 3        # the reference follows the first three


class WindowClosed(Exception):
    """Raised by the feed to end ``train()`` when the window is over."""


def fastest_k_masks(traffic: Dict, seed: int, steps: int) -> List[np.ndarray]:
    """The workers each step waits for, drawn as the paper's simplified
    delay model draws them (x + Exp(scale = beta / lambda)) from the
    loop's seed: the k smallest of n response times."""
    n, k, beta = traffic["n_workers"], traffic["k"], traffic["beta"]
    dl = traffic["delay"]
    rng = np.random.default_rng(program_seed(seed))
    out = []
    for _ in range(steps):
        z = dl["x"] + rng.exponential(scale=beta / dl["lambda_y"], size=n)
        m = np.zeros(n, np.float32)
        m[np.argpartition(z, k - 1)[:k]] = 1.0
        out.append(m)
    return out


class Feed:
    """The duck-typed batcher ``train()`` draws from."""

    def __init__(self, setup, window, seconds: float, trace: Window,
                 trace_seconds: float, b1: float, clock):
        self.setup, self.window = setup, window
        self.seconds, self.trace, self.trace_seconds = seconds, trace, trace_seconds
        self.b1, self.clock = b1, clock
        self.times: List[float] = []
        self.k_eff: List[int] = []
        self.masks: List[np.ndarray] = []
        self.captured: Dict = {}
        self.compiles_at_open = None
        self.traced_steps = 0
        self.gc_pauses: List[tuple] = []     # (generation, seconds) in the window
        self._gc_t = 0.0

    def _on_gc(self, phase: str, info: Dict) -> None:
        if phase == "start":
            self._gc_t = time.perf_counter()
        else:
            self.gc_pauses.append((info["generation"], time.perf_counter() - self._gc_t))

    def close(self) -> None:
        if self._on_gc in gc.callbacks:
            gc.callbacks.remove(self._on_gc)

    def _capture(self, i: int, loop: Dict) -> None:
        import jax
        import jax.numpy as jnp

        if i == 0:
            self.captured["params0"] = jax.device_get(loop["params"])
        elif i == 1:
            self.captured["params1"] = jax.device_get(loop["params"])
            m = loop["opt_state"].m
            norms = jax.jit(lambda t: [jnp.sqrt(jnp.sum(jnp.square(x))) for x in jax.tree.leaves(t)])(m)
            self.captured["grad_norm"] = [float(x) / (1 - self.b1) for x in norms]
        elif i == CHECKED_STEPS:
            self.captured["params3"] = jax.device_get(loop["params"])
            self.captured["loss"] = [h["loss"] for h in loop["history"][:CHECKED_STEPS]]

    def batch_for_stage(self, beta, n_workers=None):
        from jax.profiler import TraceAnnotation

        t = time.perf_counter()
        loop = sys._getframe(1).f_locals
        i = len(self.times)
        self.times.append(t)
        self.k_eff.append(int(np.asarray(loop["mask"]).sum()))
        if i < CHECKED_STEPS:
            self.masks.append(np.asarray(loop["mask"]).copy())
        if i <= CHECKED_STEPS:
            self._capture(i, loop)
        if i == SETUP_STEPS:
            self.compiles_at_open = self.clock.compiles + self.clock.cache_hits
            gc.callbacks.append(self._on_gc)
            self.trace.start()
        elif i > SETUP_STEPS:
            if self.trace.active:
                self.traced_steps += 1
                if t - self.times[SETUP_STEPS] >= self.trace_seconds:
                    self.trace.stop()
            if t - self.times[SETUP_STEPS] >= self.seconds:
                self.close()
                raise WindowClosed
        with TraceAnnotation("bench.batch"):
            if i < SETUP_STEPS:
                return self.setup[i]
            return self.window[(i - SETUP_STEPS) % len(self.window)]


def reference_readings(cell, seed: int, devices, quant: Optional[str] = None,
                       fault: Optional[str] = None) -> Dict:
    """The reference's loss, first clipped gradient and change over the
    checked steps, from the seed alone: the program's initial weights (a
    copy of its rule), the same batches, the fastest-k masks redrawn.
    ``quant``/``fault``: the control, or a planted fault (see
    ``reference.train_steps``)."""
    from .. import reference

    tr = cell.traffic
    d = dims_of(cell.config)
    params0 = make_weights("program", d, cell.config["program"]["dtype"], seed)
    rows_w = token_stream.rows_per_step(tr) // tr["n_workers"]
    return reference.train_steps(d, tr["optimizer"], params0,
                                 token_stream.batches(tr, seed, CHECKED_STEPS),
                                 fastest_k_masks(tr, seed, CHECKED_STEPS), rows_w,
                                 quant=quant, fault=fault)


def compare(prog: Dict, ref: Dict) -> Dict[str, float]:
    """The numbers read against the reference: the relative gap of the
    first step's loss; the worst leaf's gap of the first clipped
    gradient's norm; and the worst leaf's gap of the norm of the
    parameters' change after the first step (``change1_gap``) and after
    the checked steps (``change_gap``). Leaves whose reference gradient
    is under a thousandth of the median leaf's move by round-off alone
    and are left out of the changes. Which of them decide ``correct`` is
    the limits file's to say (see PERF.md for why each is or is not)."""
    med = float(np.median(ref["grad_norm"]))
    counted = [i for i, g in enumerate(ref["grad_norm"]) if g >= 1e-3 * med]
    return {
        "first_loss_gap": gap(prog["loss"][0], ref["loss"][0], abs(ref["loss"][0])),
        "grad_gap": worst_leaf_gap(prog["grad_norm"], ref["grad_norm"])[0],
        "change1_gap": worst_leaf_gap(prog["change1_norm"], ref["change1_norm"], counted)[0],
        "change_gap": worst_leaf_gap(prog["change_norm"], ref["change_norm"], counted)[0],
    }


def run(cell, seed: int, seconds: float, trace_dir, devices, clock, t0: float) -> RunResult:
    import jax

    from repro.core import DiagnosticConfig, SimplifiedDelayModel, StrategyConfig
    from repro.models import build_model
    from repro.optim.optimizers import get_optimizer
    from repro.runtime.train_loop import TrainLoopConfig, train

    tr = cell.traffic
    cfg = program_config(cell.config)
    d = dims_of(cell.config)
    opt = tr["optimizer"]
    optimizer = get_optimizer("adamw", b1=opt["b1"], b2=opt["b2"], eps=opt["eps"],
                              weight_decay=opt["weight_decay"])
    strategy = StrategyConfig(
        "adaptive_kbeta", n=tr["n_workers"], s=tr["rows_per_worker"],
        k0=tr["k"], beta0=tr["beta"], k_max=tr["k_max"], beta_grid=tuple(tr["beta_grid"]),
        diagnostic=DiagnosticConfig(kind="loss", min_iters=10 ** 9),
    )
    delay = SimplifiedDelayModel(lambda_y=tr["delay"]["lambda_y"], x=tr["delay"]["x"])
    batches = token_stream.batches(tr, seed, SETUP_STEPS + tr["window_batches"])
    window = Window(trace_dir)
    feed = Feed(batches[:SETUP_STEPS], batches[SETUP_STEPS:], seconds, window,
                tr["trace_seconds"], opt["b1"], clock)
    loop_cfg = TrainLoopConfig(total_steps=10 ** 9, lr=opt["lr"], seed=program_seed(seed),
                               log_every=0)
    try:
        train(build_model(cfg), optimizer, strategy, delay, feed, loop_cfg)
        raise RuntimeError("train() returned before the window closed")
    except WindowClosed:
        pass
    finally:
        feed.close()
    window.stop()
    compiles = clock.compiles + clock.cache_hits - feed.compiles_at_open
    peak = memory_peak_bytes(devices)

    t = feed.times
    w0, w1 = SETUP_STEPS, len(t) - 1          # steps w0 .. w1-1 ran in the window
    rows_w = token_stream.rows_per_step(tr) // tr["n_workers"]
    applied = sum(feed.k_eff[i] * rows_w * tr["seq_len"] for i in range(w0, w1))
    e2e = {"train_tokens_per_s": applied / (t[w1] - t[w0]),
           "setup_s": t[w0] - t0}
    traced = list(range(w0, w0 + feed.traced_steps))
    record = {"kind": "train", "dims": d, "seq_len": tr["seq_len"],
              "unattributed": "train loop (unattributed)",
              "applied_rows": [feed.k_eff[i] * rows_w for i in traced]}
    steps = np.diff(t[w0:w1 + 1])
    notes = [f"window: {w1 - w0} steps in {t[w1] - t[w0]:.3f} s (step s min "
             f"{steps.min():.4f}, median {np.median(steps):.4f}, max {steps.max():.4f}), "
             f"k_eff {sorted(set(feed.k_eff[w0:w1]))}, {compiles} compiles inside"]
    slow = [(i, round(float(s), 4)) for i, s in enumerate(steps) if s > 2 * np.median(steps)]
    gc_s = [s for _, s in feed.gc_pauses]
    notes.append(f"window: steps over twice the median (index, s) {slow}; gc {len(gc_s)} "
                 f"collections ({sum(g == 2 for g, _ in feed.gc_pauses)} full), "
                 f"{sum(gc_s):.4f} s in all, longest {max(gc_s, default=0.0):.4f} s")

    # -- the reference, once the window has closed and the loop is gone --
    prog = feed.captured
    start = jax.tree.leaves(prog.pop("params0"))
    for key, at in (("change1_norm", "params1"), ("change_norm", "params3")):
        prog[key] = [
            float(np.sqrt(np.sum(np.square(b.astype(np.float32) - a.astype(np.float32)))))
            for a, b in zip(start, jax.tree.leaves(prog.pop(at)))]
    masks_prog = feed.masks
    del feed, batches
    gc.collect()
    ref = reference_readings(cell, seed, devices)
    gaps = compare(prog, ref)
    gaps["mask_diff"] = float(sum(int((a != b).sum()) for a, b in
                                  zip(masks_prog, fastest_k_masks(tr, seed, CHECKED_STEPS))))
    gaps["window_compiles"] = float(compiles)
    limits = dict(cell.limits, mask_diff=0.0, window_compiles=0.0)
    checks = [Check(k, v, limits[k]) for k, v in gaps.items() if k in limits]
    loose = ", ".join(f"{k} {v!r}" for k, v in gaps.items() if k not in limits)
    notes.append(f"losses program {prog['loss']} reference {ref['loss']}"
                 + (f"; not compared: {loose}" if loose else ""))
    record["readings"] = {"program": prog, "reference": ref}
    return RunResult(e2e=e2e, attempted=w1 - w0, failed=0, checks=checks, record=record,
                     memory_peak_bytes=peak, notes=notes)
