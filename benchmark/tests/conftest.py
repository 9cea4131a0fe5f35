"""The benchmark's own tests run on the CPU, at sizes a test run holds,
with four virtual devices for the four-chip cell."""
import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
HERE = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))
sys.path.insert(1, str(HERE.parent))

import jax  # noqa: E402

jax.config.update("jax_num_cpu_devices", 4)
