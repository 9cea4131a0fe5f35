"""Test configuration: force an 8-device virtual CPU mesh so multi-device
sharding paths run on any host, mirroring the reference's
"mpiexec -n N on localhost" testing model (reference tests/README:5-7).

The chip runs (chip_smoke.py, benchmark/run.py) drive a real TPU; tests
always run on the virtual CPU mesh for device-count-invariant
assertions.  jax may already be imported by a pytest plugin, so the
platform is set via jax.config (backends initialize lazily); the
environment variables are for the child processes some tests start.
"""
import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)
# the reference is double-precision throughout; tests assert in f64
jax.config.update("jax_enable_x64", True)
