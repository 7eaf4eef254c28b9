"""dttlint — the repo's invariant linter (static analysis, stdlib ``ast``).

The reference framework leaned on TF's graph-time placement checks to
catch topology mistakes before a step ran; the JAX port has no such
graph pass, so the load-bearing invariants this tree has learned the
hard way (replicated-leaf divergence from a mis-axed collective, loop
variants forgetting the scalar contract, flags without parse-time
validators, span names drifting from the ARCHITECTURE catalog) were
enforced by memory and runtime tests alone. dttlint turns each of those
hand-fixed bug classes into a named, machine-checked rule — the same
move XLA makes with its static shape/layout verification, and the
in-tree invariant linters large trainers (Megatron-LM) carry.

Rules (each one names the PR whose bug class it fossilizes — see
docs/ARCHITECTURE.md "Static analysis"):

  DTT001 collective-axis   collectives must name their axis via
                           ``mesh.DATA_AXIS``/``MODEL_AXIS`` or a
                           forwarded parameter — never a string literal
  DTT002 ledger-coverage   a parallel/ module with collectives must
                           export a ``*_comm_rows`` pricing builder
  DTT003 scalar-contract   every ``_train_*`` loop of training/loop.py
                           (the device-resident driver, the three
                           host-fed loops) emits the standard scalar
                           families and polls ``maybe_resize``
  DTT004 fault-registry    fired point names exist in
                           ``INJECTION_POINTS``; no registered point is
                           orphaned
  DTT005 span-catalog     ``trace_span``/instant names match the
                           ARCHITECTURE span-catalog table, both ways
  DTT006 flag-validator    every ``DEFINE_*`` flag is covered by a
                           registered parse-time validator (or an
                           explicit baseline entry)
  DTT007 trace-purity      no host impurities (``time.time``,
                           ``np.random``, ``print``, host branching on
                           traced args) inside jit/shard_map/scan bodies
  DTT008 donation-safety   a donated argument is not read after the
                           donating call in the same scope
  DTT009 traced-coverage   every parallel/ collective call site is
                           reachable from a dttcheck-traced step
                           function (the jaxpr layer's closure rule)
  DTT010 inventory-coverage  every threading.Thread/Timer construction
                           site is discoverable by the dttsan thread
                           inventory (the concurrency layer's closure
                           rule)
  DTT011 perf-coverage     every public bench phase is dttperf-
                           resolvable — fact-covered (PHASE_FACTS, so
                           DTP002 enforces its facts non-null) or
                           exempted with a stated reason (the
                           performance layer's closure rule)

Run it: ``python -m tools.dttlint [--json] [--baseline PATH] [--fix]``.
Exit 0 = no non-baselined findings and no stale suppressions; nonzero
otherwise (the tier-1 contract). The checked-in baseline
(``tools/dttlint/baseline.json``) suppresses known findings by STABLE
key (never line numbers) and carries a ``reason`` per entry; an entry
whose finding no longer exists FAILS the run loudly, so the baseline
can only shrink.
"""

from __future__ import annotations

import ast
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from tools._analysis_common import (  # noqa: E402 — the shared runner
    REPO_ROOT,
    AnalysisResult,
    Finding,
    apply_baseline,
    load_baseline as _load_baseline,
)

# the historical names, kept for every existing caller (tests, bench):
# dttlint's result type IS the shared analysis result
LintResult = AnalysisResult

DEFAULT_BASELINE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "baseline.json")

# the walk set: the package, the tools, and the top-level entry points
# (bench.py per the bench contract; __graft_entry__/mnist_dist are repo
# code too and have grown collectives of their own)
LINT_TARGETS = ("distributed_tensorflow_tpu", "tools",
                "bench.py", "__graft_entry__.py", "mnist_dist.py")
SPAN_CATALOG_DOC = os.path.join("docs", "ARCHITECTURE.md")


class RepoIndex:
    """Everything the rules read, parsed once: {relpath: ast.Module}
    for the walk set, raw sources (for --fix), and the ARCHITECTURE
    doc text (DTT005's other half)."""

    def __init__(self, root: str = REPO_ROOT, targets=LINT_TARGETS):
        self.root = root
        self.trees: dict[str, ast.Module] = {}
        self.sources: dict[str, str] = {}
        self.errors: list[Finding] = []
        for target in targets:
            full = os.path.join(root, target)
            if os.path.isfile(full):
                self._load(target)
            elif os.path.isdir(full):
                for dirpath, dirnames, filenames in os.walk(full):
                    dirnames[:] = [d for d in dirnames
                                   if d != "__pycache__"]
                    for name in sorted(filenames):
                        if name.endswith(".py"):
                            rel = os.path.relpath(
                                os.path.join(dirpath, name), root)
                            self._load(rel)
        doc = os.path.join(root, SPAN_CATALOG_DOC)
        self.doc_text = (open(doc, encoding="utf-8").read()
                         if os.path.exists(doc) else "")

    def _load(self, rel: str) -> None:
        rel = rel.replace(os.sep, "/")
        src = open(os.path.join(self.root, rel), encoding="utf-8").read()
        self.sources[rel] = src
        try:
            self.trees[rel] = ast.parse(src, filename=rel)
        except SyntaxError as e:  # a file that won't parse is a finding
            self.errors.append(Finding(
                "DTT000", f"DTT000:{rel}", rel, e.lineno or 0,
                f"syntax error: {e.msg}"))


def load_baseline(path: str | None = None) -> list[dict]:
    return _load_baseline(path, DEFAULT_BASELINE)


def run_lint(root: str = REPO_ROOT, baseline_path: str | None = None,
             rules=None, targets=LINT_TARGETS) -> LintResult:
    """The one entry point (CLI, tier-1 test, bench lint_phase).
    Baseline matching and stale-suppression detection ride the shared
    ``tools/_analysis_common`` machinery (dttcheck's too)."""
    from tools.dttlint.rules import ALL_RULES

    index = RepoIndex(root, targets)
    active = list(rules) if rules else list(ALL_RULES)
    found: list[Finding] = list(index.errors)
    for rule in active:
        found.extend(rule(index))
    return apply_baseline(
        found, load_baseline(baseline_path),
        rules=tuple(getattr(r, "rule_id", r.__name__) for r in active))
