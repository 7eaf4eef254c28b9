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

# The files that take longest here, longest first (seconds of a worker's
# time under ``-n 6 --dist loadfile``, each a few hundred). Under xdist they
# are handed out before every other file, so that the run ends on short
# files; xdist's own order (most tests first) left several of them to the
# end, where one worker ran on alone for minutes. Order only: a file that
# is not here keeps xdist's place, and one here that is gone is skipped.
LONGEST_FIRST = (
    "tests/test_chip_compile.py",
    "tests/test_device_step.py",
    "tests/test_multihost.py",
    "tests/test_integration.py",
    "tests/test_scopes.py",
    "tests/benchmark/test_run_cpu_qwen3_next.py",
    "tests/test_routed_diffusion.py",
    "tests/test_zero.py",
    "tests/test_bench.py",
    "tests/test_attention.py",
    "tests/test_tensor_parallel.py",
    "tests/test_linear_attention.py",
)


def pytest_configure(config):
    # the scheduler hands files out in the order the workers collected
    # them (``pytest_collection_modifyitems`` below), not by their counts
    if getattr(config.option, "dist", "no") in ("loadfile", "loadscope"):
        config.option.loadscopereorder = False


def pytest_collection_modifyitems(session, config, items):
    if not os.environ.get("PYTEST_XDIST_WORKER"):
        return  # one process: the collection's own order
    files = {}
    for item in items:
        files.setdefault(item.nodeid.split("::", 1)[0], []).append(item)
    rank = {name: i for i, name in enumerate(LONGEST_FIRST)}

    def place(f):
        if f in rank:
            return rank[f], 0
        # the rest as xdist orders them: most tests first, ties as collected
        return len(rank), -len(files[f])

    items[:] = [item for f in sorted(files, key=place) for item in files[f]]
