"""Shared test setup.

Must run before ANY jax import: jax locks the device count on first
backend initialization, and the mesh/sharding tests (make_mesh,
constrain_batch under a real mesh, the sharded train step and loop) need
multiple devices on CPU-only CI. The subprocess-based test_pipeline_parallel
sets its own XLA_FLAGS in the child process and is unaffected.
"""

import os

_FLAG = "--xla_force_host_platform_device_count=8"

if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "") + " " + _FLAG
    ).strip()
