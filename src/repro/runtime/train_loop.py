"""Production training loop: the paper's controller driving a JAX model.

Wires together:
  * ``Controller`` (adaptive-(k,beta) stages, stationarity diagnostics,
    online delay-model estimation from CENSORED telemetry),
  * per-stage compiled train steps (compile cache keyed by batch shape),
  * masked fastest-k aggregation (the worker mask is DATA — no recompile
    across straggler subsets; per-stage beta batch shape is the only
    recompile axis),
  * async checkpointing + exact resume (full control state, telemetry,
    and RNG streams round-trip, so a resumed run replays the exact
    history the uninterrupted run would have produced),
  * fault handling: worker failure -> permanent mask + controller n-=1;
    persistent straggler demotion via censoring-aware telemetry; worker
    REJOIN -> controller n+=1 (``Controller.add_worker``).

Censoring discipline (DESIGN.md §2.5): a fastest-k step only ever
observes the k response times it waited for. The controller receives
exactly those k order statistics plus the count of censored workers, and
fits the delay model with the censored MLE — feeding it the full
uncensored sample (including times of workers the step never waited for)
is physically impossible on real hardware and was the bug this loop
used to have.

On real hardware the response times come from per-host step telemetry;
in this container they are sampled from the paper's delay models — the
control path is identical (DESIGN.md §2).
"""

from __future__ import annotations

import contextlib
import dataclasses
from typing import Any, Callable, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.controller import Controller, Stage, StrategyConfig
from repro.core.order_stats import DelayModel
from repro.data.pipeline import StagedBatcher
from repro.dist.collectives import check_worker_major
from repro.dist.sharding import (
    DEFAULT_RULES,
    abstract_state,
    activation_sharding,
    batch_pspec,
)
from repro.models.model import Model
from repro.obs import NULL_OBS, Observability, span
from repro.optim.optimizers import Optimizer
from repro.runtime.checkpoint import CheckpointManager
# FaultEvent moved to repro.runtime.faults (PR 7) so the serving plane can
# consume the same chaos schema; re-exported here for compatibility.
from repro.runtime.faults import FaultEvent, schedule_by_step
from repro.runtime.steps import make_train_step
from repro.runtime.telemetry import StragglerTracker

__all__ = ["FaultEvent", "TrainLoopConfig", "train"]


@dataclasses.dataclass
class TrainLoopConfig:
    total_steps: int = 200
    lr: float = 3e-4
    checkpoint_every: int = 50
    checkpoint_dir: Optional[str] = None
    log_every: int = 10
    seed: int = 0
    estimate_model: bool = True      # fit delay model from (censored) telemetry
    oracle_to_controller: bool = True  # False: controller sees ONLY telemetry
    fail_worker_at: Optional[int] = None   # legacy single-failure injection
    fail_worker_id: int = 0
    demote_after_ewma: Optional[float] = None  # straggler demotion threshold
    events: Sequence[FaultEvent] = ()          # chaos schedule


def _event_schedule(cfg: TrainLoopConfig) -> Dict[int, List[FaultEvent]]:
    events = list(cfg.events)
    if cfg.fail_worker_at is not None:
        events.append(FaultEvent(cfg.fail_worker_at, "fail", cfg.fail_worker_id))
    return schedule_by_step(events)


def _init_state(model: Model, optimizer: Optimizer, seed: int, mesh):
    """Fresh (params, opt_state). With a mesh, each leaf is created
    already sharded by ``DEFAULT_RULES`` (FSDP over ``data``, tensor
    parallel over ``model``), so no device ever holds the whole model."""
    key = jax.random.PRNGKey(seed)
    if mesh is None:
        params = model.init(key)
        return params, optimizer.init(params)
    params_abs, opt_abs = abstract_state(model, mesh, DEFAULT_RULES, optimizer)

    def shardings(tree):
        return jax.tree.map(lambda s: s.sharding, tree)

    params = jax.jit(model.init, out_shardings=shardings(params_abs))(key)
    opt_state = jax.jit(optimizer.init, out_shardings=shardings(opt_abs))(params)
    return params, opt_state


def _place_batch(batch: Dict[str, np.ndarray], mesh) -> Dict[str, jax.Array]:
    """Host batch -> device. With a mesh the worker-major rows shard over
    the data-parallel axes (each device holds whole workers' examples);
    the worker mask and the learning rate are replicated."""
    if mesh is None:
        return {k: jnp.asarray(v) for k, v in batch.items()}
    rows = batch["inputs"].shape[0]
    out = {}
    for k, v in batch.items():
        spec = (batch_pspec(mesh, rows, v.ndim - 1)
                if k in ("inputs", "labels") else P())
        out[k] = jax.device_put(v, NamedSharding(mesh, spec))
    return out


def train(
    model: Model,
    optimizer: Optimizer,
    strategy: StrategyConfig,
    delay_model: DelayModel,
    batcher: StagedBatcher,
    loop_cfg: TrainLoopConfig,
    mesh=None,
    obs: Optional[Observability] = None,
) -> Dict[str, Any]:
    """Run the adaptive-(k,beta) training loop. Returns history dict.

    ``obs``: observability bundle (``repro.obs``). When enabled, every
    step lands as a ``train_step`` complete event on the loop's
    ``sim_time`` lane, chaos/demotion transitions as ``fault`` instants,
    the per-step wait/compute split as histograms, and every stage
    switch as a ``train.stage`` decision-log entry carrying the censored
    telemetry it was priced from.

    Whatever ``obs`` is, each step's phases are wall spans
    (``repro.train.*``, docs/observability.md) that record while a
    ``jax.profiler`` trace is running."""
    obs = obs or NULL_OBS
    tr_obs = obs.tracer
    pid = tr_obs.register_process("train")
    rng = np.random.default_rng(loop_cfg.seed)
    ctrl = Controller(
        strategy,
        model=delay_model if loop_cfg.oracle_to_controller else None,
        estimate_model=loop_cfg.estimate_model,
    )
    n0 = strategy.n  # fleet size at loop start; worker ids are 0..n0-1
    tracker = StragglerTracker(
        n0, metrics=obs.metrics if obs.enabled else None
    )
    schedule = _event_schedule(loop_cfg)
    h_step = obs.metrics.histogram("train.step_time")
    # Wait = how long the FASTEST observed worker idled for the k-th
    # (the straggler tax fastest-k is buying down); compute = the mean
    # observed response time (what the workers were actually doing).
    h_wait = obs.metrics.histogram("train.wait")
    h_compute = obs.metrics.histogram("train.compute")
    g_workers = obs.metrics.gauge("train.n_workers")

    params, opt_state = _init_state(model, optimizer, loop_cfg.seed, mesh)
    step_fn_cache: Dict[tuple, Callable] = {}
    base_step = make_train_step(
        model, optimizer,
        param_shardings=(None if mesh is None
                         else jax.tree.map(lambda p: p.sharding, params)),
    )

    def compiled_step(shape):
        if shape not in step_fn_cache:
            step_fn_cache[shape] = jax.jit(base_step, donate_argnums=(0, 1))
        return step_fn_cache[shape]

    ckpt = (
        CheckpointManager(loop_cfg.checkpoint_dir)
        if loop_cfg.checkpoint_dir
        else None
    )
    alive = np.ones(n0, bool)
    slow_factor = np.ones(n0)
    sim_time = 0.0
    start_step = 0
    if ckpt is not None:
        restored = ckpt.restore_latest(
            {"params": params, "opt": opt_state},
            device_put_fn=(None if mesh is None
                           else lambda v, like: jax.device_put(v, like.sharding)),
        )
        if restored is not None:
            start_step, state, extras = restored
            params, opt_state = state["params"], state["opt"]
            if extras.get("controller"):
                # Full control-state resume: controller (stage walk +
                # diagnostic + telemetry), straggler tracker, fleet
                # membership, the event clock, and both RNG streams.
                ctrl.load_state_dict(extras["controller"])
                tracker.load_state_dict(extras["tracker"])
                alive = np.asarray(extras["alive"], bool)
                slow_factor = np.asarray(extras["slow_factor"], np.float64)
                sim_time = float(extras["sim_time"])
                rng.bit_generator.state = extras["rng_state"]
                batcher.stream.rng.bit_generator.state = extras["stream_rng_state"]
            elif extras.get("stage"):
                # Older checkpoints carried only the stage pair.
                ctrl.stage = Stage(**extras["stage"])

    history: List[Dict[str, float]] = []

    ctx = activation_sharding(mesh) if mesh is not None else contextlib.nullcontext()
    with ctx:
        for step in range(start_step, loop_cfg.total_steps):
            with span("repro.train.step", step=step):
                with span("repro.train.plan"):
                    # ---- chaos events ---------------------------------------
                    for ev in schedule.get(step, ()):
                        applied = False
                        if ev.kind == "fail" and alive[ev.worker]:
                            alive[ev.worker] = False
                            ctrl.remove_worker()
                            applied = True
                        elif ev.kind == "rejoin" and not alive[ev.worker]:
                            alive[ev.worker] = True
                            slow_factor[ev.worker] = ev.factor
                            tracker.reset_worker(ev.worker)
                            ctrl.add_worker()
                            applied = True
                        elif ev.kind == "slow":
                            slow_factor[ev.worker] = ev.factor
                            applied = True
                        if applied and obs.enabled:
                            obs.metrics.counter(f"train.fault.{ev.kind}").inc()
                            tr_obs.instant(
                                "fault", pid, sim_time,
                                args={"kind": ev.kind, "worker": ev.worker,
                                      "step": step},
                            )

                    # ---- pending demotions from telemetry -------------------
                    if loop_cfg.demote_after_ewma is not None:
                        for w in tracker.persistent_stragglers(loop_cfg.demote_after_ewma):
                            if alive[w] and alive.sum() > 1:
                                alive[w] = False
                                ctrl.remove_worker()
                                if obs.enabled:
                                    obs.metrics.counter("train.demotions").inc()
                                    tr_obs.instant(
                                        "demote", pid, sim_time,
                                        args={"worker": int(w), "step": step},
                                    )

                    # ---- the n-contract: controller and fleet must agree ----
                    n_active = int(alive.sum())
                    if n_active != ctrl.cfg.n:
                        raise RuntimeError(
                            f"fleet/controller divergence: {n_active} alive workers "
                            f"but controller prices n={ctrl.cfg.n}"
                        )
                    active_ids = np.nonzero(alive)[0]
                    stage = ctrl.stage

                    # ---- response times + fastest-k mask --------------------
                    # Sample the FULL original fleet every step so the RNG
                    # stream consumption is independent of membership (exact
                    # resume and run-to-run comparability), then restrict to
                    # active workers.
                    z_full = delay_model.sample(rng, n0, stage.beta) * slow_factor
                    z_act = z_full[active_ids]
                    k_eff = min(stage.k, n_active)
                    order = np.argpartition(z_act, k_eff - 1)[:k_eff]
                    t_step = float(z_act[order].max())
                    t0_step = sim_time
                    sim_time += t_step
                    mask = np.zeros(n_active, np.float32)
                    mask[order] = 1.0

                    # ---- censored telemetry ---------------------------------
                    # Only the k waited-for times are observable on real
                    # hardware; everyone else is censored at the step time z_(k).
                    selected = np.zeros(n0, bool)
                    selected[active_ids[order]] = True
                    tracker.observe(z_full, alive, observed=selected, censor_level=t_step)

                # ---- batch sized for the CURRENT fleet ----------------------
                # Called from this frame, not a helper: a batcher may read the
                # loop's state from its caller's frame (tests/test_wall_spans.py).
                with span("repro.train.batch"):
                    np_batch = batcher.batch_for_stage(stage.beta, n_workers=n_active)
                    check_worker_major(np_batch["inputs"].shape[0], n_active)
                with span("repro.train.put"):
                    batch = _place_batch(
                        {
                            "inputs": np_batch["inputs"],
                            "labels": np_batch["labels"],
                            "worker_mask": mask,
                            "lr": np.float32(loop_cfg.lr),
                        },
                        mesh,
                    )
                with span("repro.train.dispatch"):
                    fn = compiled_step(np_batch["inputs"].shape)
                    params, opt_state, metrics = fn(params, opt_state, batch)

                # The one place the loop blocks on the step program.
                with span("repro.train.wait"):
                    loss = float(metrics["loss"])

                with span("repro.train.control"):
                    ctrl.observe(
                        loss=loss,
                        response_times=np.sort(z_act[order]),
                        n_unobserved=n_active - k_eff,
                    )
                    switched = ctrl.maybe_advance()

                    if obs.enabled:
                        observed = np.sort(z_act[order])
                        h_step.observe(t_step)
                        h_wait.observe(t_step - float(observed[0]))
                        h_compute.observe(float(observed.mean()))
                        g_workers.set(n_active)
                        tr_obs.complete(
                            "train_step", pid, t0_step, sim_time,
                            args={"step": step, "k": stage.k,
                                  "beta": float(stage.beta),
                                  "n_workers": n_active,
                                  "loss": round(loss, 6)},
                        )
                        if switched is not None:
                            tr_obs.instant(
                                "stage_switch", pid, sim_time,
                                args={"step": step, "k": switched.k,
                                      "beta": float(switched.beta)},
                            )
                            fitted = ctrl.current_model()
                            obs.decisions.record(
                                "train.stage",
                                {"k": switched.k, "beta": float(switched.beta)},
                                {"stage_idx": ctrl.stage_idx,
                                 "n": ctrl.cfg.n,
                                 "rt_samples": len(ctrl._rt_samples),
                                 "rt_censored": int(sum(ctrl._rt_censored)),
                                 "lambda_y": (
                                     round(float(fitted.lambda_y), 6)
                                     if fitted is not None else None
                                 )},
                                step=step, vtime=sim_time,
                            )

                    with span("repro.train.read"):
                        contributors = float(metrics["contributors"])
                    with span("repro.train.read"):
                        grad_norm = float(metrics["grad_norm"])
                    history.append(
                        {
                            "step": step,
                            "loss": loss,
                            "k": stage.k,
                            "beta": stage.beta,
                            "n_workers": n_active,
                            "sim_time": sim_time,
                            "contributors": contributors,
                            "grad_norm": grad_norm,
                        }
                    )
                    if switched is not None:
                        history[-1]["switched_to"] = (switched.k, switched.beta)

                    if ckpt is not None and (step + 1) % loop_cfg.checkpoint_every == 0:
                        ckpt.save_async(
                            step + 1,
                            {"params": params, "opt": opt_state},
                            extras={
                                "stage": dataclasses.asdict(ctrl.stage),  # legacy key
                                "controller": ctrl.state_dict(),
                                "tracker": tracker.state_dict(),
                                "alive": [int(a) for a in alive],
                                "slow_factor": [float(f) for f in slow_factor],
                                "sim_time": sim_time,
                                "rng_state": rng.bit_generator.state,
                                "stream_rng_state": batcher.stream.rng.bit_generator.state,
                            },
                        )

                    if loop_cfg.log_every and step % loop_cfg.log_every == 0:
                        # The structured record is the source of truth; the
                        # legacy print stays as its stdout view unless the log
                        # is already echoing its own rendering.
                        obs.log.emit(
                            "train_step", t=sim_time, step=step,
                            loss=round(loss, 4), k=stage.k,
                            beta=float(stage.beta), workers=n_active,
                        )
                        if not obs.log.echo:
                            print(
                                f"step {step:5d} loss {loss:8.4f} k={stage.k:2d} "
                                f"beta={stage.beta:4.2f} t={sim_time:9.2f} "
                                f"workers={n_active}",
                                flush=True,
                            )

    if ckpt is not None:
        ckpt.wait()
    return {
        "history": history,
        "params": params,
        "opt_state": opt_state,
        "controller": ctrl,
        "tracker": tracker,
        "alive": alive,
        "compiled_shapes": list(step_fn_cache.keys()),
        "sim_time": sim_time,
    }
