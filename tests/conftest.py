"""Test env: force an 8-device virtual CPU mesh before JAX initialises.

Mirrors the reference's "multi-node without cluster" strategy (SURVEY.md §4):
envtest/simulators there, virtual CPU devices here.

The tests need the CPU backend whether or not the caller exported
JAX_PLATFORMS=cpu (the tier-1 command does): only it has the eight virtual
devices, and on a machine with a chip JAX would otherwise open the TPU. The
jax.config pin below overrides the environment either way; it must come
after XLA_FLAGS is set and before the first device use.
"""

import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()
os.environ.setdefault("JAX_ENABLE_X64", "0")

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
