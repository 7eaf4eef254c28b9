"""Test env: the CPU platform with 8 virtual devices.

This is the distributed-without-a-cluster strategy (SURVEY.md §4): mesh +
collective code paths run on a simulated 8-device host, so CI needs no TPU.
``JAX_PLATFORMS=cpu`` and the device count in ``XLA_FLAGS`` are set before
jax is imported, and every child process a test starts inherits them.

The persistent compile cache is off for the tests (and for the children,
through the environment): a run must not depend on what an earlier run
left in the checkout's ``.jax_cache/``, and reloading XLA:CPU executables
logs a screenful per hit.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

jax.config.update("jax_default_matmul_precision", "highest")

assert len(jax.devices()) == 8, (
    f"tests require the 8-device virtual CPU mesh, got {jax.devices()}"
)
