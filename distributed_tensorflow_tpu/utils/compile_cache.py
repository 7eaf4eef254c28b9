"""Where JAX's persistent compilation cache lives.

Every run on a fresh machine starts with no compiled code, and the first
compile of one step is most of a short run. The cache directory is part
of nothing the program decides: where ``JAX_COMPILATION_CACHE_DIR`` is
set JAX reads it itself and this module sets no other; where it is not,
the cache goes to ONE fixed directory in the checkout (``.jax_cache/``,
ignored by git) — the path is part of how a later process finds the
entries, so it never carries a temporary name, a process id or a time.

One thing more goes into what an entry is found by: the scope catalog
(``utils/telemetry.SCOPES``). JAX leaves an operation's metadata out of the
key, so an executable compiled before the program named its blocks with
``jax.named_scope`` would be loaded for the same program with names: the
profiler's trace then shows the old, nameless paths and every operation
reads "unscoped" (seen in PR 25: equal keys with and without a scope; the
chip tool's machine came with the executables of the commit before). With
the catalog hashed into the key (JAX's ``cache_key.custom_hook``), programs
compiled under another catalog are not found; programs of checkouts that
share it still are, wherever they lie and whichever lines moved. JAX's own
switch (``jax_compilation_cache_include_metadata_in_key``) was tried first
and given up: it keys every entry by source path and line, so each checkout
and each edit fills the cache again, and a cache with a size limit (192 MiB
on the chip tool's machine, 30-80 MB a cell) then evicts what the other
checkout needs. A change that redraws a scope's boundary without touching
the arithmetic must rename the scope, or the old names are loaded.

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


def key_salt() -> str:
    """What this program adds to every cache key: its scope catalog."""
    from distributed_tensorflow_tpu.utils.telemetry import SCOPES

    return "scopes=" + ",".join(SCOPES)


def enable_compile_cache() -> str:
    """Point JAX at ``compile_cache_dir()`` and return it, with the scope
    catalog in the key. With the environment variable set this touches no
    JAX setting at all."""
    from jax._src import cache_key

    cache_key.custom_hook = key_salt
    path = compile_cache_dir()
    if not os.environ.get(ENV_VAR):
        import jax

        jax.config.update("jax_compilation_cache_dir", path)
    return path
