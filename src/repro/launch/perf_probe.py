import os

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", "")
)

"""Perf probe: compile one cell and attribute collective/HBM traffic to
source jax ops — the dry-run profiler used by the §Perf iteration loop.

  PYTHONPATH=src python -m repro.launch.perf_probe --arch chameleon-34b \
      --shape train_4k [--variant baseline] [--multi-pod]
"""

import argparse

from repro.launch import dryrun as dr


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", required=True)
    ap.add_argument("--variant", default="baseline")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--top", type=int, default=14)
    args = ap.parse_args()

    import jax

    from repro.analysis.hlo_cost import analyze_hlo
    from repro.configs import SHAPES, get_config
    from repro.dist.sharding import abstract_state, activation_sharding
    from repro.launch.mesh import make_production_mesh
    from repro.launch.specs import (
        decode_input_specs,
        prefill_input_specs,
        train_input_specs,
    )
    from repro.models.model import Model
    from repro.runtime.steps import (
        make_decode_step,
        make_prefill_step,
        make_train_step,
    )

    cfg = dr.apply_variant(get_config(args.arch), args.variant)
    shape = SHAPES[args.shape]
    mesh = make_production_mesh(multi_pod=args.multi_pod)
    rules = dr.rules_for(cfg, args.variant, shape.kind)
    model = Model(cfg)
    seq_axis = dr.seq_axis_for(cfg, shape.kind, args.variant)
    accum = dr.accum_for(cfg, shape.kind)

    import jax.numpy as jnp

    with jax.set_mesh(mesh), activation_sharding(mesh, seq_axis=seq_axis,
                                                 rules=rules):
        if shape.kind == "train":
            opt = dr.optimizer_for(cfg)
            params, opt_state = abstract_state(model, mesh, rules, opt)
            step = make_train_step(
                model, opt, accum_steps=accum,
                accum_dtype=jnp.bfloat16 if cfg.param_count() > 100e9 else jnp.float32,
                param_shardings=jax.tree.map(lambda p: p.sharding, params),
            )
            compiled = jax.jit(step, donate_argnums=(0, 1)).lower(
                params, opt_state, train_input_specs(cfg, shape, mesh)
            ).compile()
        elif shape.kind == "prefill":
            params, _ = abstract_state(model, mesh, rules)
            compiled = jax.jit(make_prefill_step(model)).lower(
                params, **prefill_input_specs(cfg, shape, mesh)
            ).compile()
        else:
            params, _ = abstract_state(model, mesh, rules)
            ins = decode_input_specs(cfg, shape, mesh, rules)
            compiled = jax.jit(make_decode_step(model), donate_argnums=(2,)).lower(
                params, ins["token"], ins["caches"], ins["cache_index"]
            ).compile()

    cost = analyze_hlo(compiled.as_text())
    mem = compiled.memory_analysis()
    print(f"\ncell: {cfg.name} x {args.shape} ({'pod2' if args.multi_pod else 'pod1'}) "
          f"variant={args.variant}")
    print(f"peak GiB/dev: {(mem.temp_size_in_bytes + mem.argument_size_in_bytes)/2**30:.2f}")
    print(f"flops/dev: {cost.flops:.3e}  hbm/dev: {cost.hbm_bytes:.3e}  "
          f"coll/dev: {cost.total_collective_bytes():.3e}")
    print(f"\ntop collective sources (GiB/device/step):")
    for src, b in cost.top_collective_sources(args.top):
        print(f"  {b/2**30:9.2f}  {src[:140]}")


if __name__ == "__main__":
    main()
