"""Where JAX's persistent compilation cache lives.

Every run on a fresh machine starts with no compiled code, and the first
compile of one step is most of a short run. The cache directory is part
of nothing the program decides: where ``JAX_COMPILATION_CACHE_DIR`` is
set JAX reads it itself and this module sets no other; where it is not,
the cache goes to ONE fixed directory in the checkout (``.jax_cache/``,
ignored by git) — the path is part of how a later process finds the
entries, so it never carries a temporary name, a process id or a time.

Called by ``flags.run`` (the entry of ``mnist_dist.py`` and of
``python -m distributed_tensorflow_tpu.serving``), by ``bench.py`` and by
``chip_smoke.py``, before anything compiles.
"""

from __future__ import annotations

import os

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DEFAULT_DIR = os.path.join(_CHECKOUT, ".jax_cache")


def compile_cache_dir() -> str:
    """The directory in use: the environment's, else the checkout's."""
    return os.environ.get(ENV_VAR) or DEFAULT_DIR


def enable_compile_cache() -> str:
    """Point JAX at ``compile_cache_dir()`` and return it. With the
    environment variable set this touches no JAX setting at all."""
    path = compile_cache_dir()
    if not os.environ.get(ENV_VAR):
        import jax

        jax.config.update("jax_compilation_cache_dir", path)
    return path
