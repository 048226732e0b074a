"""Training cells on a ``data`` mesh: the program's ``train()`` pinned at
one (k, beta) stage, as ``train`` runs it, with the weights and the Adam
state sharded over the cell's chips (FSDP, ``repro``'s default rules).

The window, its clock and its metrics are ``train``'s (``Feed``, the
applied tokens of the steps in the window). What differs:

* ``train()`` is handed a mesh of ``traffic["mesh_data"]`` chips on one
  ``data`` axis; each chip holds whole workers' rows of every batch;
* the reference runs on the same chips, each weight placed as the
  program placed it (the shardings the feed reads at the first step) and
  the applied rows split over ``data``. It follows the first step only:
  the loss, the clipped gradient and the AdamW update, which at step 1
  needs no moment of an earlier step. Three steps would keep two float32
  moments, a float32 gradient and a float32 copy of the weights at once,
  12 bytes a parameter more than the first step holds: 37 GB for
  qwen2.5-3b over the four chips' 64 GB, beside the rest.

The record says ``kind: "train"``, so the training cells' readers read it.
"""

from __future__ import annotations

import gc
import time
from typing import Dict, Optional

import numpy as np

from ..common import memory_peak_bytes
from ..gen import token_stream
from ..model import dims_of, program_config
from ..tracing import Window
from ..weights import make_weights, program_seed
from . import Check, RunResult, gap, worst_leaf_gap
from .train import CHECKED_STEPS, SETUP_STEPS, Feed, WindowClosed, fastest_k_masks


class ShardedFeed(Feed):
    """``train.Feed`` that also keeps the program's weight shardings."""

    def _capture(self, i: int, loop: Dict) -> None:
        if i == 0:
            import jax

            self.captured["shardings"] = jax.tree.map(lambda p: p.sharding, loop["params"])
        super()._capture(i, loop)


def make_mesh(cell, devices):
    from repro.launch.mesh import make_mesh as program_mesh

    n = int(cell.traffic["mesh_data"])
    return program_mesh((n,), ("data",), devices=devices[:n])


def first_step_fn(d, hp: Dict, rows, quant: Optional[str] = None):
    """The reference's first step, jitted: (weights as stored, inputs,
    labels) -> (the loss, the per-leaf norms of the clipped gradient and
    of the parameters' change). The gradient is of a float32 copy of the
    weights; the update is AdamW at t = 1 from zero moments, as
    ``reference.train_steps`` makes it.
    ``rows`` is the inputs' sharding, which the hidden states and the
    logits keep: left to itself the compiler gathers the float32 logits
    of every row on each chip."""
    import jax
    import jax.numpy as jnp

    from .. import reference

    b1, b2 = hp["b1"], hp["b2"]

    def change(p, g):
        pf = p.astype(jnp.float32)
        m, v = (1 - b1) * g, (1 - b2) * g * g
        u = -hp["lr"] * ((m / (1 - b1)) / (jnp.sqrt(v / (1 - b2)) + hp["eps"])
                         + hp["weight_decay"] * pf)
        new = (pf + u).astype(p.dtype)
        return new.astype(jnp.float32) - pf

    def norm(x):
        return jnp.sqrt(jnp.sum(jnp.square(x)))

    def nll_sum(wide, inputs, labels):
        # reference._nll_sum with the rows' sharding held
        h = jax.lax.with_sharding_constraint(reference.hidden(d, quant, wide, inputs), rows)
        z = jax.lax.with_sharding_constraint(reference.logits(d, quant, wide, h), rows)
        gold = jnp.take_along_axis(z, labels[..., None], axis=-1)[..., 0]
        return jnp.sum(jax.nn.logsumexp(z, axis=-1) - gold)

    def step(params, inputs, labels):
        wide = jax.tree.map(lambda p: p.astype(jnp.float32), params)
        nll, g = jax.value_and_grad(nll_sum)(wide, inputs, labels)
        g = jax.tree.map(lambda x: x / inputs.size, g)
        gn = jnp.sqrt(sum(jnp.sum(jnp.square(x)) for x in jax.tree.leaves(g)))
        g = jax.tree.map(lambda x: x * jnp.minimum(1.0, hp["clip_norm"] / jnp.maximum(gn, 1e-9)), g)
        return (nll / inputs.size, [norm(x) for x in jax.tree.leaves(g)],
                [norm(change(p, x)) for p, x in zip(jax.tree.leaves(params), jax.tree.leaves(g))])

    return jax.jit(step)


def reference_readings(cell, seed: int, mesh, shardings, quant: Optional[str] = None,
                       fault: Optional[str] = None) -> Dict:
    """The reference's first step from the seed alone, on ``mesh``: the
    program's initial weights (a copy of its rule) placed by
    ``shardings``, the first batch, the first fastest-k mask redrawn.
    -> the step's loss and the per-leaf norms of its clipped gradient and
    of the parameters' change. ``quant`` and ``fault="half_batch"`` as
    ``train``'s reference takes them."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from .. import reference

    tr = cell.traffic
    d = dims_of(cell.config)
    params = make_weights("program", d, cell.config["program"]["dtype"], seed,
                          out_shardings=shardings)
    batch = token_stream.batches(tr, seed, 1)[0]
    rows_w = token_stream.rows_per_step(tr) // tr["n_workers"]
    rows = reference.applied_rows(fastest_k_masks(tr, seed, 1)[0], rows_w)
    positions = tr["seq_len"]
    if fault == "half_batch":
        if len(rows) > 1:
            rows = rows[: len(rows) // 2]
        else:
            positions //= 2
    place = NamedSharding(mesh, P("data") if len(rows) % mesh.shape["data"] == 0 else P())
    inputs = jax.device_put(batch["inputs"][rows, :positions], place)
    labels = jax.device_put(batch["labels"][rows, :positions], place)
    step = first_step_fn(d, tr["optimizer"], place, quant)
    loss, grad_norm, change1 = jax.device_get(step(params, inputs, labels))
    return {"loss": [float(loss)], "grad_norm": [float(x) for x in grad_norm],
            "change1_norm": [float(x) for x in change1]}


def compare(prog: Dict, ref: Dict) -> Dict[str, float]:
    """``train.compare``'s numbers of the first step: the loss's relative
    gap, the worst leaf's gap of the clipped gradient's norm, and of the
    norm of the parameters' change (leaves whose reference gradient is
    under a thousandth of the median leaf's left out)."""
    med = float(np.median(ref["grad_norm"]))
    counted = [i for i, g in enumerate(ref["grad_norm"]) if g >= 1e-3 * med]
    return {
        "first_loss_gap": gap(prog["loss"][0], ref["loss"][0], abs(ref["loss"][0])),
        "grad_gap": worst_leaf_gap(prog["grad_norm"], ref["grad_norm"])[0],
        "change1_gap": worst_leaf_gap(prog["change1_norm"], ref["change1_norm"], counted)[0],
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
    mesh = make_mesh(cell, devices)
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
    feed = ShardedFeed(batches[:SETUP_STEPS], batches[SETUP_STEPS:], seconds, window,
                       tr["trace_seconds"], opt["b1"], clock)
    loop_cfg = TrainLoopConfig(total_steps=10 ** 9, lr=opt["lr"], seed=program_seed(seed),
                               log_every=0)
    try:
        train(build_model(cfg), optimizer, strategy, delay, feed, loop_cfg, mesh=mesh)
        raise RuntimeError("train() returned before the window closed")
    except WindowClosed:
        pass
    finally:
        feed.close()
    window.stop()
    compiles = clock.compiles + clock.cache_hits - feed.compiles_at_open
    peak = memory_peak_bytes(devices[:mesh.size])

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
             f"{steps.min():.4f}, median {np.median(steps):.4f}, max {steps.max():.4f}) "
             f"on a data={mesh.size} mesh, k_eff {sorted(set(feed.k_eff[w0:w1]))}, "
             f"{compiles} compiles inside"]

    # -- the reference, once the window has closed and the loop is gone --
    prog = feed.captured
    shardings = prog.pop("shardings")
    start = jax.tree.leaves(prog.pop("params0"))
    prog.pop(f"params{CHECKED_STEPS}")
    prog["change1_norm"] = [
        float(np.sqrt(np.sum(np.square(b.astype(np.float32) - a.astype(np.float32)))))
        for a, b in zip(start, jax.tree.leaves(prog.pop("params1")))]
    mask_prog = feed.masks[0]
    del feed, batches, start
    gc.collect()
    ref = reference_readings(cell, seed, mesh, shardings)
    gaps = compare(prog, ref)
    gaps["mask_diff"] = float((mask_prog != fastest_k_masks(tr, seed, 1)[0]).sum())
    gaps["window_compiles"] = float(compiles)
    limits = dict(cell.limits, mask_diff=0.0, window_compiles=0.0)
    checks = [Check(k, v, limits[k]) for k, v in gaps.items() if k in limits]
    notes.append(f"first loss program {prog['loss'][0]!r} reference {ref['loss'][0]!r}")
    record["readings"] = {"program": prog, "reference": ref, "shardings": shardings}
    return RunResult(e2e=e2e, attempted=w1 - w0, failed=0, checks=checks, record=record,
                     memory_peak_bytes=peak, notes=notes)
