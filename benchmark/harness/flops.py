"""Where the counts went: each architecture brings its own, in its family's
module under ``benchmark/reference/`` (``manifest.FAMILY_NAMES``), and the
harness reaches them through ``cell.family()``.

This file stays for one caller outside the benchmark's directories, which a
``benchmark`` PR may not edit: ``tests/test_efficiency.py`` holds the
program's own budget (``utils/efficiency.flops_budget``) to
``train_flops_per_token(sizes, seq_len)`` for a decoder of the first
configuration's family. It goes with that import (PERF.md, Open questions).
"""

from __future__ import annotations

from benchmark.harness import manifest


def train_flops_per_token(sizes: dict, seq_len: int) -> float:
    first = manifest.load_manifest()["workloads"][0]["name"]
    return manifest.load_cell(first).family().train_flops_per_token({**sizes, "seq_len": seq_len})
