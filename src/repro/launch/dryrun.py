import os

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", "")
)

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this produces, WITHOUT allocating real arrays:
  * compiled.memory_analysis()  — proves the per-device footprint,
  * compiled.cost_analysis()    — HLO FLOPs / bytes for §Roofline,
  * collective byte counts      — parsed from the compiled HLO text,
and writes one JSON artifact per cell under artifacts/dryrun/.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch smollm-135m \
      --shape train_4k [--multi-pod] [--variant baseline]
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod]
"""

import argparse
import json
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp

from repro.analysis.hlo_cost import analyze_hlo
from repro.configs import SHAPES, cell_status, get_config, list_archs
from repro.configs.base import ModelConfig
from repro.dist.sharding import (
    DEFAULT_RULES,
    FSDP_POD_RULES,
    PURE_DP_RULES,
    SP_DECODE_RULES,
    ShardingRules,
    abstract_state,
    activation_sharding,
    make_sharding_fn,
)
from repro.launch.mesh import make_production_mesh
from repro.launch.specs import (
    decode_input_specs,
    prefill_input_specs,
    train_input_specs,
)
from repro.models.model import Model
from repro.optim.optimizers import get_optimizer
from repro.runtime.steps import make_decode_step, make_prefill_step, make_train_step

ARTIFACTS = Path(__file__).resolve().parents[3] / "artifacts" / "dryrun"


def rules_for(cfg: ModelConfig, variant: str, kind: str) -> ShardingRules:
    if variant == "pure_dp":
        return PURE_DP_RULES
    rules = DEFAULT_RULES
    if cfg.name.startswith("deepseek"):
        rules = rules.replace(embed=("pod", "data"))  # pod-wide ZeRO for 671B
    if kind == "decode" and variant != "no_sp_decode":
        # Sequence-parallel KV caches: the only way 32k x 128 caches fit
        # when kv_heads < the model-axis width (distributed flash-decode).
        rules = rules.replace(act_kv_seq="model")
    return rules


def dp_axes_for(variant: str):
    return ("pod", "data", "model") if variant == "pure_dp" else None


def accum_for(cfg: ModelConfig, kind: str, variant: str = "baseline") -> int:
    """Gradient-accumulation microbatches for train cells (memory)."""
    if kind != "train":
        return 1
    if variant in ("zero1_state_noseq", "accum8"):
        return 8
    if cfg.param_count() > 100e9:
        return 8
    if cfg.d_model >= 8192:
        return 4
    return 1


def seq_axis_for(cfg: ModelConfig, kind: str, variant: str):
    # Megatron-style sequence-parallel activations for the wide archs.
    if variant in ("no_seq_shard", "zero1_state_noseq"):
        return None
    if kind == "train" and cfg.d_model >= 4096:
        return "model"
    return None


def optimizer_for(cfg: ModelConfig):
    # Adafactor for the giant configs (fits 16 GB/chip), AdamW elsewhere.
    if cfg.param_count() > 20e9:
        return get_optimizer("adafactor")
    return get_optimizer("adamw")


def apply_variant(cfg: ModelConfig, variant: str) -> ModelConfig:
    import dataclasses

    if variant == "baseline":
        return cfg
    if variant == "mla_absorb":
        return dataclasses.replace(cfg, mla_absorb=True)
    if variant == "mla_materialize":
        return dataclasses.replace(cfg, mla_absorb=False)
    if variant == "no_remat":
        return dataclasses.replace(cfg, remat="none")
    if variant == "selective_remat":
        return dataclasses.replace(cfg, remat="selective")
    if variant in ("moe_ep", "moe_grouped"):
        return dataclasses.replace(
            cfg, moe=dataclasses.replace(
                cfg.moe, dispatch="model" if variant == "moe_ep" else "grouped"
            )
        )
    if variant in ("sp_decode", "no_sp_decode", "seq_shard", "no_seq_shard",
                   "zero1", "zero1_state", "zero1_state_noseq", "pure_dp",
                   "accum8"):
        return cfg
    raise ValueError(f"unknown variant {variant}")


def dryrun_cell(
    arch: str,
    shape_name: str,
    *,
    multi_pod: bool = False,
    variant: str = "baseline",
    save: bool = True,
) -> dict:
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    skip = cell_status(cfg, shape_name)
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    cell_id = f"{cfg.name}__{shape_name}__{mesh_name}__{variant}"
    if skip is not None:
        result = {"cell": cell_id, "status": "SKIP", "reason": skip}
        if save:
            _save(result)
        return result

    cfg = apply_variant(cfg, variant)
    mesh = make_production_mesh(multi_pod=multi_pod)
    rules = rules_for(cfg, variant, shape.kind)
    model = Model(cfg)
    t0 = time.time()

    seq_axis = seq_axis_for(cfg, shape.kind, variant)
    accum = accum_for(cfg, shape.kind, variant)
    with jax.set_mesh(mesh), activation_sharding(
        mesh, seq_axis=seq_axis, dp_axes=dp_axes_for(variant), rules=rules
    ):
        if shape.kind == "train":
            optimizer = optimizer_for(cfg)
            if variant.startswith("zero1_state"):
                # TRUE ZeRO-1: the param STATE lives TP-only (replicated
                # over data — affordable for <100B at 256 chips); only the
                # optimizer state + gradient flow stay FSDP-sharded. No
                # per-layer weight gathers exist at all.
                g_rules = rules.replace(embed=None)
                params, _ = abstract_state(model, mesh, g_rules)
                _, opt_state = abstract_state(model, mesh, rules, optimizer)
            else:
                params, opt_state = abstract_state(model, mesh, rules, optimizer)
            accum_dtype = (
                jnp.bfloat16 if cfg.param_count() > 100e9 else jnp.float32
            )
            gather_shardings = None
            if variant.startswith("zero1_state"):
                # pin grads to the FSDP layout -> reduce-scatter at the
                # boundary; optimizer update runs on shards.
                fsdp_shardings = jax.tree.map(
                    lambda sp: make_sharding_fn(mesh, rules)(sp),
                    model.param_specs(),
                    is_leaf=lambda x: hasattr(x, "axes"),
                )
                step = make_train_step(
                    model, optimizer, accum_steps=accum,
                    accum_dtype=accum_dtype,
                    param_shardings=fsdp_shardings,
                )
                lowered = jax.jit(step, donate_argnums=(0, 1)).lower(
                    params, opt_state,
                    train_input_specs(cfg, shape, mesh, rules=rules),
                )
                t_lower = time.time() - t0
                compiled = lowered.compile()
                t_compile = time.time() - t0 - t_lower
                return _finish(cfg, shape, mesh, rules, variant, cell_id,
                               mesh_name, compiled, t_lower, t_compile,
                               accum, seq_axis, save)
            if variant == "zero1":
                # ZeRO-1: gather weights once per step (to the TP-only
                # layout), reduce-scatter grads back to the FSDP layout.
                g_rules = rules.replace(embed=None)
                gather_shardings = jax.tree.map(
                    lambda sp: make_sharding_fn(mesh, g_rules)(sp),
                    model.param_specs(),
                    is_leaf=lambda x: hasattr(x, "axes"),
                )
            step = make_train_step(
                model, optimizer, accum_steps=accum, accum_dtype=accum_dtype,
                param_shardings=jax.tree.map(lambda p: p.sharding, params),
                gather_shardings=gather_shardings,
            )
            lowered = jax.jit(step, donate_argnums=(0, 1)).lower(
                params, opt_state, train_input_specs(cfg, shape, mesh, rules=rules)
            )
        elif shape.kind == "prefill":
            params, _ = abstract_state(model, mesh, rules)
            step = make_prefill_step(model)
            lowered = jax.jit(step).lower(
                params, **prefill_input_specs(cfg, shape, mesh)
            )
        else:  # decode
            params, _ = abstract_state(model, mesh, rules)
            step = make_decode_step(model)
            ins = decode_input_specs(cfg, shape, mesh, rules)
            lowered = jax.jit(step, donate_argnums=(2,)).lower(
                params, ins["token"], ins["caches"], ins["cache_index"]
            )
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    return _finish(cfg, shape, mesh, rules, variant, cell_id, mesh_name,
                   compiled, t_lower, t_compile, accum, seq_axis, save)


def _finish(cfg, shape, mesh, rules, variant, cell_id, mesh_name, compiled,
            t_lower, t_compile, accum, seq_axis, save):
    shape_name = shape.name
    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    hlo = compiled.as_text()
    loop_cost = analyze_hlo(hlo)  # loop-aware (XLA counts while bodies once)

    n_devices = mesh.size
    result = {
        "cell": cell_id,
        "status": "OK",
        "arch": cfg.name,
        "shape": shape_name,
        "kind": shape.kind,
        "mesh": mesh_name,
        "variant": variant,
        "n_devices": n_devices,
        "lower_s": round(t_lower, 1),
        "compile_s": round(t_compile, 1),
        "params_total": cfg.param_count(),
        "params_active": cfg.active_param_count(),
        "seq_len": shape.seq_len,
        "global_batch": shape.global_batch,
        "accum_steps": accum,
        "seq_axis": seq_axis,
        "memory": {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "peak_bytes": (
                getattr(mem, "temp_size_in_bytes", 0)
                + getattr(mem, "argument_size_in_bytes", 0)
            ),
        },
        "cost": {
            # XLA's own numbers (while bodies counted ONCE — kept for
            # reference) and the loop-aware re-analysis used by §Roofline.
            "xla_flops": cost.get("flops") if cost else None,
            "xla_bytes_accessed": cost.get("bytes accessed") if cost else None,
            "flops": loop_cost.flops,
            "hbm_bytes": loop_cost.hbm_bytes,
            "unknown_trip_counts": loop_cost.unknown_trip_counts,
        },
        "collectives": loop_cost.as_dict()["collective_bytes"],
        "collective_counts": loop_cost.as_dict()["collective_counts"],
        "collective_top_sources": [
            [src, b] for src, b in loop_cost.top_collective_sources(10)
        ],
    }
    if save:
        _save(result)
    return result


def _save(result: dict):
    ARTIFACTS.mkdir(parents=True, exist_ok=True)
    path = ARTIFACTS / f"{result['cell']}.json"
    path.write_text(json.dumps(result, indent=2))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", type=str, default=None)
    ap.add_argument("--shape", type=str, default=None, choices=list(SHAPES))
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--variant", type=str, default="baseline")
    ap.add_argument("--skip-existing", action="store_true")
    args = ap.parse_args()

    cells = []
    archs = list_archs() if (args.all or args.arch is None) else [args.arch]
    shapes = list(SHAPES) if (args.all or args.shape is None) else [args.shape]
    for a in archs:
        for s in shapes:
            cells.append((a, s))

    failures = 0
    for arch, shape_name in cells:
        mesh_name = "pod2x16x16" if args.multi_pod else "pod16x16"
        cfg_name = get_config(arch).name
        cell_id = f"{cfg_name}__{shape_name}__{mesh_name}__{args.variant}"
        if args.skip_existing and (ARTIFACTS / f"{cell_id}.json").exists():
            prev = json.loads((ARTIFACTS / f"{cell_id}.json").read_text())
            print(f"[cached] {cell_id}: {prev['status']}", flush=True)
            continue
        try:
            r = dryrun_cell(
                arch, shape_name, multi_pod=args.multi_pod, variant=args.variant
            )
            if r["status"] == "OK":
                mem_gb = r["memory"]["peak_bytes"] / 2**30
                print(
                    f"[ok] {cell_id}: {mem_gb:.2f} GiB/device, "
                    f"flops={r['cost']['flops']:.3e}, "
                    f"hbm={r['cost']['hbm_bytes']:.3e}, "
                    f"coll={sum(r['collectives'].values())/2**30:.3f} GiB "
                    f"(lower {r['lower_s']}s compile {r['compile_s']}s)",
                    flush=True,
                )
            else:
                print(f"[skip] {cell_id}: {r['reason']}", flush=True)
        except Exception as e:  # noqa: BLE001 — report and continue the sweep
            failures += 1
            print(f"[FAIL] {cell_id}: {e}", flush=True)
            traceback.print_exc()
    if failures:
        raise SystemExit(f"{failures} cells failed")


if __name__ == "__main__":
    main()
