"""The four-chip training kind ``train_fsdp4`` at a tiny size, on eight
host devices forced in a child process (the flag must be set before JAX
starts, and this directory's tests share one process): the program runs
on a ``data=4`` mesh and passes, its weights sharded over the four
devices; the reference on the mesh, placed as the program placed its
weights, reads the same first step as ``train``'s one-device reference;
the fp8 control and the planted fault fail a number each."""

import json
import os
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


def _child() -> None:
    import time

    import jax

    from chipbench.common import CompileClock
    from chipbench.harness import train, train_fsdp4
    from chipbench.tests import tiny

    train_fsdp4.program_config = tiny.tiny_program_config
    cell = tiny.train_cell("train_fsdp4")
    cell.chips = cell.traffic["mesh_data"]
    devices = jax.devices()[:cell.chips]
    seed = 2 ** 31 + 5
    res = train_fsdp4.run(cell, seed, 0.0, None, devices, CompileClock(), time.perf_counter())
    raw = res.record["readings"]
    mesh = train_fsdp4.make_mesh(cell, devices)
    faults = {k: train_fsdp4.compare(train_fsdp4.reference_readings(
        cell, seed, mesh, raw["shardings"], **kw), raw["reference"])
        for k, kw in (("control", {"quant": "fp8"}), ("half_batch", {"fault": "half_batch"}))}
    one = train.reference_readings(cell, seed, jax.devices()[:1])
    print(json.dumps({
        "checks": {c.name: [c.value, c.limit] for c in res.checks},
        "correct": res.correct,
        "embed_devices": len(raw["shardings"]["embed"].device_set),
        "mesh": dict(mesh.shape),
        "sharded": raw["reference"],
        "one_device": {k: one[k] for k in ("grad_norm", "change1_norm")} | {"loss": one["loss"][:1]},
        "faults": faults,
        "limits": cell.limits,
    }))


def test_fsdp4_kind_on_eight_host_devices():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               PYTHONPATH=os.pathsep.join([str(ROOT), str(ROOT / "src")]))
    out = subprocess.run([sys.executable, __file__], env=env, capture_output=True,
                         text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-4000:]
    r = json.loads(out.stdout.strip().splitlines()[-1])
    assert r["correct"], r["checks"]
    assert r["mesh"] == {"data": 4} and r["embed_devices"] == 4
    # the first step on the mesh is the one-device reference's first step
    for key in ("loss", "grad_norm", "change1_norm"):
        a, b = r["sharded"][key], r["one_device"][key]
        assert len(a) == len(b)
        for x, y in zip(a, b):
            assert abs(x - y) <= 1e-4 * max(abs(y), 1e-6), (key, x, y)
    for kind, gaps in r["faults"].items():
        assert any(v > r["limits"][k] for k, v in gaps.items()), (kind, gaps)


if __name__ == "__main__":
    _child()
