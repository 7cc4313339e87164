"""The benchmark's own tests run on the CPU:

    python -m pytest chipbench/tests

Four virtual CPU devices, so that the four-chip runner rehearses too.
"""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 4)
