"""The eight dttlint rules (see package docstring + docs/ARCHITECTURE.md
"Static analysis" for each rule's rationale and the PR it fossilizes).

Every rule is a callable ``rule(index: RepoIndex) -> list[Finding]``
with a ``rule_id`` attribute; ``ALL_RULES`` is the registry the runner
executes. Finding keys are STABLE (symbol-based, never line numbers) so
the baseline survives unrelated edits.
"""

from __future__ import annotations

import ast
import re

from tools.dttlint import Finding

# ------------------------------------------------------------- helpers

#: collective primitives whose axis argument names a mesh axis (the
#: PR-1/PR-5 replicated-leaf divergence class all rode on these)
COLLECTIVES = {
    "psum": 1, "pmean": 1, "psum_scatter": 1, "all_gather": 1,
    "ppermute": 1, "all_to_all": 1, "axis_index": 0, "axis_size": 0,
}
DEFINE_NAMES = ("DEFINE_string", "DEFINE_integer", "DEFINE_float",
                "DEFINE_boolean", "DEFINE_bool")
AXIS_CONSTANT_HINT = ("name the axis via mesh.DATA_AXIS/MODEL_AXIS (or "
                      "forward an axis_name= parameter) — a string "
                      "literal dodges the one place the axis convention "
                      "lives and is how the PR-1/PR-5 replicated-leaf "
                      "divergence entered")


def _dotted(node) -> str | None:
    """``jax.lax.psum`` -> "jax.lax.psum"; non-name chains -> None."""
    parts = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def _callee(call: ast.Call) -> str:
    """Last path segment of the callee ("psum", "trace_span", ...) —
    works through non-name bases too (``get_tracer().record_instant``)."""
    if isinstance(call.func, ast.Attribute):
        return call.func.attr
    if isinstance(call.func, ast.Name):
        return call.func.id
    return ""


def _is_collective(call: ast.Call) -> bool:
    chain = _dotted(call.func) or ""
    name = chain.rsplit(".", 1)[-1]
    if name not in COLLECTIVES:
        return False
    # require a lax-ish chain (or a bare name, the import-from form) so
    # an unrelated method named e.g. .all_gather can't trip it
    head = chain.rsplit(".", 1)[0] if "." in chain else ""
    return head in ("", "lax", "jax.lax")


class _Counter:
    """Occurrence counter so two identical violations in one scope get
    distinct, deterministic keys (:2 suffix on the repeat)."""

    def __init__(self):
        self.seen: dict[str, int] = {}

    def key(self, base: str) -> str:
        n = self.seen.get(base, 0) + 1
        self.seen[base] = n
        return base if n == 1 else f"{base}:{n}"


def _walk_scoped(tree):
    """Yield (node, qualname) with the enclosing function qualname."""
    def visit(node, qual):
        for child in ast.iter_child_nodes(node):
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                yield child, qual
                yield from visit(child, f"{qual}.{child.name}"
                                 if qual else child.name)
            else:
                yield child, qual
                yield from visit(child, qual)

    yield from visit(tree, "")


# -------------------------------------------------- DTT001 collective-axis


def _import_aliases(tree, original: str) -> set:
    """Local names an imported symbol is bound to (``PartitionSpec as
    _PS`` -> {"PartitionSpec", "_PS"}), import statements at any depth."""
    names = {original}
    for node in ast.walk(tree):
        if isinstance(node, ast.ImportFrom):
            for alias in node.names:
                if alias.name == original and alias.asname:
                    names.add(alias.asname)
    return names


def rule_collective_axis(index) -> list:
    """DTT001: collectives (and PartitionSpec/Mesh axis tuples) must
    name their axis via the mesh constants or a forwarded parameter,
    never a string literal."""
    out = []
    for rel, tree in index.trees.items():
        counter = _Counter()
        ps_names = _import_aliases(tree, "PartitionSpec") | {"P"}
        mesh_names = _import_aliases(tree, "Mesh")
        for node, qual in _walk_scoped(tree):
            if not isinstance(node, ast.Call):
                continue
            name = _callee(node)
            literals = []
            if _is_collective(node):
                pos = COLLECTIVES[name]
                if len(node.args) > pos:
                    literals.append(node.args[pos])
                literals += [kw.value for kw in node.keywords
                             if kw.arg in ("axis_name", "axis")]
            elif name in ps_names:
                for a in node.args:
                    literals += (list(a.elts) if isinstance(a, ast.Tuple)
                                 else [a])
            elif name in mesh_names:
                axes = [kw.value for kw in node.keywords
                        if kw.arg == "axis_names"]
                if len(node.args) > 1:
                    axes.append(node.args[1])
                for a in axes:
                    literals += (list(a.elts) if isinstance(a, ast.Tuple)
                                 else [a])
            for lit in literals:
                if isinstance(lit, ast.Constant) and \
                        isinstance(lit.value, str):
                    base = f"{rel}::{qual or '<module>'}::{name}:" \
                           f"{lit.value}"
                    out.append(Finding(
                        "DTT001", counter.key(base), rel, lit.lineno,
                        f"string-literal axis {lit.value!r} in "
                        f"{name}(); {AXIS_CONSTANT_HINT}",
                        fix={"lineno": lit.lineno,
                             "col": lit.col_offset,
                             "end_col": lit.end_col_offset,
                             "literal": lit.value}))
    return out


rule_collective_axis.rule_id = "DTT001"


# -------------------------------------------------- DTT002 ledger-coverage


def rule_ledger_coverage(index) -> list:
    """DTT002: a parallel/ module containing collective primitives must
    export a ``*_comm_rows`` pricing builder, so a new comm path cannot
    dodge ``utils/resources.comm_ledger`` (the r13 wire accounting)."""
    out = []
    for rel, tree in index.trees.items():
        if "/parallel/" not in f"/{rel}" or rel.endswith("__init__.py"):
            continue
        has_collective = any(
            isinstance(n, ast.Call) and _is_collective(n)
            for n, _ in _walk_scoped(tree))
        if not has_collective:
            continue
        has_builder = any(
            isinstance(n, ast.FunctionDef) and
            n.name.endswith("_comm_rows")
            for n in tree.body)
        if not has_builder:
            out.append(Finding(
                "DTT002", f"{rel}", rel, 1,
                f"{rel} uses collective primitives but exports no "
                f"*_comm_rows builder — comm_ledger cannot price its "
                f"wire bytes (add one next to the collectives, the r13 "
                f"convention)"))
    return out


rule_ledger_coverage.rule_id = "DTT002"


# -------------------------------------------------- DTT003 scalar-contract


#: what each required call statically guarantees (the runtime twin is
#: tests/test_resources.py::test_scalar_contract_every_loop_variant)
_LOOP_CONTRACT = {
    "_display_scalars": "the display-cadence scalar families "
                        "(throughput, step breakdown, mfu/goodput, "
                        "hbm, compiles, comm)",
    "_log_recovery": "the recovery/resize scalar family (resize_s via "
                     "elastic.book_resize)",
    "maybe_resize": "the elastic boundary poll "
                    "(ElasticSupervisor.maybe_resize)",
}


def rule_scalar_contract(index) -> list:
    """DTT003: every ``_train_*`` loop must statically wire the full
    scalar contract and poll the elastic supervisor — the bug class PR 8
    had to add a runtime contract test for. In training/loop.py that is
    the device-resident driver (``_train_device``, every ``--device_data``
    layout) and the three host-fed loops (``_train_once``,
    ``_train_pipeline``, ``_train_zero``); the rule goes, with its tests,
    once those three are one loop too."""
    out = []
    for rel, tree in index.trees.items():
        for node in tree.body:
            if not (isinstance(node, ast.FunctionDef) and
                    node.name.startswith("_train_")):
                continue
            called = set()
            for sub in ast.walk(node):
                if isinstance(sub, ast.Call):
                    called.add(_callee(sub))
            for req, what in _LOOP_CONTRACT.items():
                if req not in called:
                    out.append(Finding(
                        "DTT003", f"{rel}::{node.name}::{req}", rel,
                        node.lineno,
                        f"loop variant {node.name} never calls {req} — "
                        f"it would ship without {what}"))
    return out


rule_scalar_contract.rule_id = "DTT003"


# -------------------------------------------------- DTT004 fault-registry


def rule_fault_registry(index) -> list:
    """DTT004: every literal point name at a ``fault_point(...)`` site
    exists in ``INJECTION_POINTS``, and no registered point is orphaned
    (a point nobody fires is an untested recovery claim)."""
    registry: dict[str, tuple] = {}  # name -> (rel, lineno)
    sites: dict[str, list] = {}
    for rel, tree in index.trees.items():
        for node, _ in _walk_scoped(tree):
            if isinstance(node, ast.Assign):
                targets = [t.id for t in node.targets
                           if isinstance(t, ast.Name)]
                if "INJECTION_POINTS" in targets and \
                        isinstance(node.value, ast.Dict):
                    for k in node.value.keys:
                        if isinstance(k, ast.Constant) and \
                                isinstance(k.value, str):
                            registry[k.value] = (rel, k.lineno)
            if isinstance(node, ast.Call) and \
                    _callee(node) == "fault_point" and node.args:
                first = node.args[0]
                if isinstance(first, ast.Constant) and \
                        isinstance(first.value, str):
                    sites.setdefault(first.value, []).append(
                        (rel, first.lineno))
    if not registry:
        return []  # nothing to check against (fixture slices)
    out = []
    for name, where in sorted(sites.items()):
        if name not in registry:
            for rel, line in where:
                out.append(Finding(
                    "DTT004", f"{rel}::fire::{name}", rel, line,
                    f"fault_point({name!r}) names an UNREGISTERED "
                    f"injection point — add it to "
                    f"faults.INJECTION_POINTS (parse-time validation "
                    f"rejects any spec naming it, so the site is "
                    f"unreachable by --fault_spec)"))
    for name, (rel, line) in sorted(registry.items()):
        if name not in sites:
            out.append(Finding(
                "DTT004", f"registry::{name}", rel, line,
                f"injection point {name!r} is registered but never "
                f"fired by any fault_point site — an orphaned recovery "
                f"claim (drop it or wire the site)"))
    return out


rule_fault_registry.rule_id = "DTT004"


# -------------------------------------------------- DTT005 span-catalog


def _doc_span_names(doc_text: str) -> tuple[set, set]:
    """Parse the ARCHITECTURE span-catalog table: -> (exact names,
    parameterized prefixes like "fault:")."""
    exact, prefixes = set(), set()
    in_table = False
    for line in doc_text.splitlines():
        stripped = line.strip()
        if re.match(r"^\|\s*span\s*\|\s*where\s*\|$", stripped):
            in_table = True
            continue
        if in_table:
            if not stripped.startswith("|"):
                break
            first_cell = stripped.split("|")[1]
            for tok in re.findall(r"`([^`]+)`", first_cell):
                for name in (t.strip() for t in tok.split("/")):
                    if "<" in name:
                        prefixes.add(name.split("<", 1)[0])
                    elif name:
                        exact.add(name)
    return exact, prefixes


def _resolve_span_name(first, func_def, tree=None) -> tuple[list, list]:
    """First arg of a span call -> (exact names, prefix candidates).
    Name args resolve through assignments in the enclosing function
    (the span_name/zspan conditional-constant pattern); attribute args
    (``layout.span``) through the keyword arguments of that name anywhere
    in the module (``_DeviceLayout(span=...)``: the caller that chose the
    mode names the span, the one driver emits it)."""
    if isinstance(first, ast.Constant) and isinstance(first.value, str):
        return [first.value], []
    if isinstance(first, ast.JoinedStr):
        head = first.values[0] if first.values else None
        if isinstance(head, ast.Constant) and \
                isinstance(head.value, str) and head.value.endswith(":"):
            return [], [head.value]
        return [], []
    if isinstance(first, ast.Name) and func_def is not None:
        names = []
        for sub in ast.walk(func_def):
            if isinstance(sub, ast.Assign) and any(
                    isinstance(t, ast.Name) and t.id == first.id
                    for t in sub.targets):
                names += _value_constants(sub.value)
        return names, []
    if isinstance(first, ast.Attribute) and tree is not None:
        names = []
        for sub in ast.walk(tree):
            if isinstance(sub, ast.keyword) and sub.arg == first.attr:
                names += _value_constants(sub.value)
        return names, []
    return [], []


def _value_constants(expr) -> list:
    """String constants an expression can EVALUATE to — IfExp takes its
    branches only (the test's comparison constants, e.g. the "zb" in
    ``"pp_step_zb" if sched == "zb" else "pp_step"``, are not values)."""
    if isinstance(expr, ast.Constant) and isinstance(expr.value, str):
        return [expr.value]
    if isinstance(expr, ast.IfExp):
        return _value_constants(expr.body) + _value_constants(expr.orelse)
    if isinstance(expr, ast.BoolOp):
        out = []
        for v in expr.values:
            out += _value_constants(v)
        return out
    return []


# the span-emitting entry points DTT005 audits: the live context
# manager, the instant marker, the request plane's retroactively-
# timed completed span (utils/telemetry.record_span), and the instant a
# program records as it is lowered (utils/profiling.lowering_instant)
_SPAN_CALLEES = ("trace_span", "record_instant", "record_span",
                 "lowering_instant")


def _has_span_sites(index) -> bool:
    return any(
        isinstance(n, ast.Call) and
        _callee(n) in _SPAN_CALLEES and n.args
        for tree in index.trees.values() for n, _ in _walk_scoped(tree))


def rule_span_catalog(index) -> list:
    """DTT005: every ``trace_span``/``record_instant``/``record_span``
    name literal appears in the ARCHITECTURE span-catalog table, and
    every table row has a live call site — docs drift flags in BOTH
    directions.
    A walk set WITH span sites but WITHOUT a parseable catalog table
    is itself a finding: the rule must never self-disable silently
    (a reworded table header would otherwise green every invariant
    this rule exists to enforce)."""
    exact_doc, prefix_doc = _doc_span_names(index.doc_text or "")
    if not exact_doc and not prefix_doc:
        if _has_span_sites(index):
            return [Finding(
                "DTT005", "docs::span-table", "docs/ARCHITECTURE.md", 0,
                "the walk set emits spans but no span-catalog table "
                "parses from docs/ARCHITECTURE.md (header must be "
                "'| span | where |') — the rule would silently "
                "self-disable")]
        return []
    out = []
    seen_exact: set = set()
    seen_prefix: set = set()
    for rel, tree in index.trees.items():
        # map spans to their enclosing function for Name resolution
        enclosing: dict[int, ast.FunctionDef] = {}
        for node, _ in _walk_scoped(tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                for sub in ast.walk(node):
                    if isinstance(sub, ast.Call):
                        enclosing.setdefault(id(sub), node)
        for node, qual in _walk_scoped(tree):
            if not (isinstance(node, ast.Call) and
                    _callee(node) in _SPAN_CALLEES
                    and node.args):
                continue
            names, prefixes = _resolve_span_name(
                node.args[0], enclosing.get(id(node)), tree)
            for name in names:
                seen_exact.add(name)
                if name in exact_doc:
                    continue
                if any(name.startswith(p) for p in prefix_doc):
                    seen_prefix.update(
                        p for p in prefix_doc if name.startswith(p))
                    continue
                out.append(Finding(
                    "DTT005", f"{rel}::span::{name}", rel, node.lineno,
                    f"span name {name!r} is not in the ARCHITECTURE "
                    f"span-catalog table (docs/ARCHITECTURE.md) — add "
                    f"the row or rename the span"))
            for p in prefixes:
                seen_prefix.add(p)
                if p not in prefix_doc:
                    out.append(Finding(
                        "DTT005", f"{rel}::span::{p}<...>", rel,
                        node.lineno,
                        f"parameterized span family {p!r}<...> is not "
                        f"in the span-catalog table"))
    for name in sorted(exact_doc - seen_exact):
        out.append(Finding(
            "DTT005", f"docs::span::{name}", "docs/ARCHITECTURE.md", 0,
            f"catalog table documents span {name!r} but no "
            f"trace_span/record_instant site emits it — stale docs row"))
    for p in sorted(prefix_doc - seen_prefix):
        out.append(Finding(
            "DTT005", f"docs::span::{p}<...>", "docs/ARCHITECTURE.md", 0,
            f"catalog table documents span family {p!r}<...> but no "
            f"site emits it — stale docs row"))
    return out


rule_span_catalog.rule_id = "DTT005"


# -------------------------------------------------- DTT006 flag-validator


def rule_flag_validator(index) -> list:
    """DTT006: every ``DEFINE_*`` flag in flags.py is read by a
    registered parse-time validator (``FLAGS._register_validator``) —
    or carries an explicit baseline entry saying why no invariant
    exists (free-form strings/paths). 108 flags with 15 validators was
    how config mistakes kept surfacing mid-trace instead of at the
    command line."""
    out = []
    for rel, tree in index.trees.items():
        if not rel.endswith("flags.py"):
            continue
        defined: dict[str, int] = {}
        registered: set = set()
        validators: dict[str, ast.FunctionDef] = {}
        for node, _ in _walk_scoped(tree):
            if isinstance(node, ast.FunctionDef):
                validators.setdefault(node.name, node)
            if not isinstance(node, ast.Call):
                continue
            name = _callee(node)
            if name in DEFINE_NAMES and node.args and \
                    isinstance(node.args[0], ast.Constant):
                defined.setdefault(node.args[0].value, node.lineno)
            if name == "_register_validator" and node.args and \
                    isinstance(node.args[0], ast.Name):
                registered.add(node.args[0].id)
        # reader HELPERS: a local function whose body does
        # ``values.get(<param>)`` covers the string constant its call
        # sites pass at that parameter position (the _require pattern)
        helper_arg: dict[str, int] = {}
        for fn in validators.values():
            param_names = [a.arg for a in fn.args.args]
            for sub in ast.walk(fn):
                if isinstance(sub, ast.Call) and \
                        _callee(sub) == "get" and sub.args and \
                        isinstance(sub.args[0], ast.Name) and \
                        sub.args[0].id in param_names:
                    helper_arg[fn.name] = param_names.index(
                        sub.args[0].id)
        covered: set = set()
        for fn_name in registered:
            fn = validators.get(fn_name)
            if fn is None:
                continue
            for sub in ast.walk(fn):
                if not isinstance(sub, ast.Call):
                    if isinstance(sub, ast.Subscript) and \
                            isinstance(sub.slice, ast.Constant):
                        covered.add(sub.slice.value)
                    continue
                name = _callee(sub)
                if name == "get" and sub.args and \
                        isinstance(sub.args[0], ast.Constant):
                    covered.add(sub.args[0].value)
                pos = helper_arg.get(name)
                if pos is not None and pos < len(sub.args) and \
                        isinstance(sub.args[pos], ast.Constant):
                    covered.add(sub.args[pos].value)
        for flag, line in sorted(defined.items()):
            if flag not in covered:
                out.append(Finding(
                    "DTT006", f"flags::{flag}", rel, line,
                    f"--{flag} has no registered parse-time validator "
                    f"(no _register_validator'd function reads it) — "
                    f"add a check or an explicit baseline entry naming "
                    f"why none applies"))
    return out


rule_flag_validator.rule_id = "DTT006"


# -------------------------------------------------- DTT007 trace-purity


_STATIC_ATTRS = {"shape", "ndim", "dtype", "size", "sharding"}
_SAFE_TEST_CALLS = {"isinstance", "hasattr", "getattr", "len",
                    "callable"}


def _banned_impurity(call: ast.Call) -> str | None:
    chain = _dotted(call.func) or ""
    if chain == "print":
        return "print() (host I/O inside a traced body runs at TRACE "\
               "time only — once per compile, never per step)"
    if chain in ("time.time", "time.perf_counter", "time.monotonic",
                 "time.sleep"):
        return f"{chain}() (host clocks freeze at trace time; measure "\
               f"around the dispatch, not inside the program)"
    parts = chain.split(".")
    if len(parts) >= 2 and parts[0] in ("np", "numpy") and \
            parts[1] == "random":
        return f"{chain}() (host RNG is drawn ONCE at trace time and "\
               f"baked into the executable; use jax.random with a "\
               f"threaded key)"
    return None


def _test_references_param(test, params: set) -> str | None:
    """A Name load of a traced parameter inside an if/while test —
    host branching on a traced value (TracerBoolConversionError at
    best, silent trace-time specialization at worst). ``is``/``is
    not`` comparisons, isinstance/len/etc. calls, and static
    attributes (.shape/.ndim/.dtype) are structure, not values."""
    if isinstance(test, ast.BoolOp):
        for v in test.values:
            hit = _test_references_param(v, params)
            if hit:
                return hit
        return None
    if isinstance(test, ast.UnaryOp) and isinstance(test.op, ast.Not):
        return _test_references_param(test.operand, params)
    if isinstance(test, ast.Compare) and all(
            isinstance(op, (ast.Is, ast.IsNot)) for op in test.ops):
        return None
    if isinstance(test, ast.Call) and \
            (_callee(test) in _SAFE_TEST_CALLS):
        return None

    hits: list[str] = []

    def collect(node, under_static: bool):
        if isinstance(node, ast.Attribute):
            under_static = under_static or node.attr in _STATIC_ATTRS
        if isinstance(node, ast.Name) and not under_static and \
                node.id in params:
            hits.append(node.id)
        for child in ast.iter_child_nodes(node):
            collect(child, under_static)

    collect(test, False)
    return hits[0] if hits else None


def _static_argnames(call: ast.Call | None, fn) -> set:
    """Names jit treats as STATIC (static_argnames, or static_argnums
    mapped onto the resolved function's positional params) — excluded
    from the host-branching check: branching on them is config
    dispatch, not a traced-value read."""
    if call is None:
        return set()
    static: set = set()
    positional = [a.arg for a in fn.args.args] \
        if isinstance(fn, (ast.FunctionDef, ast.Lambda)) else []
    for kw in call.keywords:
        if kw.arg == "static_argnames":
            static |= {c.value for c in ast.walk(kw.value)
                       if isinstance(c, ast.Constant) and
                       isinstance(c.value, str)}
        elif kw.arg == "static_argnums":
            for c in ast.walk(kw.value):
                if isinstance(c, ast.Constant) and \
                        isinstance(c.value, int) and \
                        c.value < len(positional):
                    static.add(positional[c.value])
    return static


def _traced_entries(tree):
    """Yield (fn_node, via, static_names) for every function body
    handed to jax.jit / shard_map / lax.scan — lambdas directly, Names
    resolved through same-scope defs."""

    def defs_in(body):
        return {n.name: n for n in body
                if isinstance(n, ast.FunctionDef)}

    def visit(node, env):
        scope_env = env
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.Module)):
            scope_env = dict(env)
            scope_env.update(defs_in(node.body))
        if isinstance(node, ast.FunctionDef):
            for dec in node.decorator_list:
                # @jax.jit / @jit / @jax.jit(...) / @partial(jax.jit, ...)
                chain = _dotted(dec) or ""
                if isinstance(dec, ast.Call):
                    chain = _dotted(dec.func) or ""
                    if chain in ("partial", "functools.partial"):
                        if any((_dotted(a) or "").split(".")[-1] ==
                               "jit" for a in dec.args):
                            yield node, "jit", _static_argnames(dec,
                                                                node)
                        continue
                    if chain in ("jax.jit", "jit"):
                        yield node, "jit", _static_argnames(dec, node)
                        continue
                if chain in ("jax.jit", "jit", "shard_map",
                             "jax.shard_map"):
                    yield node, chain.rsplit(".", 1)[-1], set()
        if isinstance(node, ast.Call):
            chain = _dotted(node.func) or ""
            name = chain.rsplit(".", 1)[-1]
            is_entry = (
                name == "jit" and chain in ("jit", "jax.jit")
            ) or (
                name == "shard_map"
            ) or (
                name == "scan" and chain in ("lax.scan", "jax.lax.scan")
            )
            if is_entry and node.args:
                first = node.args[0]
                fn = None
                if isinstance(first, ast.Lambda):
                    fn = first
                elif isinstance(first, ast.Name) and \
                        first.id in scope_env:
                    fn = scope_env[first.id]
                if fn is not None:
                    yield fn, name, (_static_argnames(node, fn)
                                     if name == "jit" else set())
        for child in ast.iter_child_nodes(node):
            yield from visit(child, scope_env)

    yield from visit(tree, {})


def rule_trace_purity(index) -> list:
    """DTT007: no host impurities inside traced step bodies."""
    out = []
    for rel, tree in index.trees.items():
        seen: set = set()
        for fn, via, static in _traced_entries(tree):
            if id(fn) in seen:
                continue
            seen.add(id(fn))
            fn_name = getattr(fn, "name", "<lambda>")
            params = {a.arg for a in fn.args.args +
                      fn.args.kwonlyargs +
                      ([fn.args.vararg] if fn.args.vararg else []) +
                      ([fn.args.kwarg] if fn.args.kwarg else [])}
            params -= static
            body = fn.body if isinstance(fn.body, list) else [fn.body]
            for stmt in body:
                for sub in ast.walk(stmt):
                    if isinstance(sub, ast.Call):
                        why = _banned_impurity(sub)
                        if why:
                            out.append(Finding(
                                "DTT007",
                                f"{rel}::{fn_name}::"
                                f"{(_dotted(sub.func) or 'call')}",
                                rel, sub.lineno,
                                f"traced body {fn_name} (via {via}) "
                                f"calls {why}"))
                    if isinstance(sub, (ast.If, ast.While)):
                        hit = _test_references_param(sub.test, params)
                        if hit:
                            out.append(Finding(
                                "DTT007",
                                f"{rel}::{fn_name}::branch:{hit}",
                                rel, sub.lineno,
                                f"traced body {fn_name} (via {via}) "
                                f"branches on traced argument "
                                f"{hit!r} with host control flow — "
                                f"use lax.cond/jnp.where"))
    return out


rule_trace_purity.rule_id = "DTT007"


# -------------------------------------------------- DTT008 donation-safety


def _donated_positions(call: ast.Call) -> set:
    """jax.jit(..., donate_argnums=...) -> the statically-known donated
    positions (handles the ``(0,) if donate else ()`` conditional)."""
    for kw in call.keywords:
        if kw.arg != "donate_argnums":
            continue
        positions = set()
        for sub in ast.walk(kw.value):
            if isinstance(sub, ast.Constant) and \
                    isinstance(sub.value, int):
                positions.add(sub.value)
        return positions
    return set()


def rule_donation_safety(index) -> list:
    """DTT008: a buffer donated to a jitted call is DEAD after it —
    reading the donor variable afterwards returns deleted-buffer
    errors on device (or silently stale data through a host copy).
    Checked where both the donating ``jax.jit(...,
    donate_argnums=...)`` binding and the call are visible in one
    scope (the bench/tool/script pattern; builder-returned steps are
    covered by the runtime's own donation checks)."""
    out = []
    for rel, tree in index.trees.items():
        scopes = [tree] + [n for n in ast.walk(tree)
                           if isinstance(n, ast.FunctionDef)]
        for scope in scopes:
            # donating callables bound in THIS scope's direct body
            donators: dict[str, set] = {}
            for stmt in scope.body:
                if isinstance(stmt, ast.Assign) and \
                        len(stmt.targets) == 1 and \
                        isinstance(stmt.targets[0], ast.Name) and \
                        isinstance(stmt.value, ast.Call) and \
                        _callee(stmt.value) == "jit":
                    pos = _donated_positions(stmt.value)
                    if pos:
                        donators[stmt.targets[0].id] = pos
            if not donators:
                continue
            # donating calls + subsequent loads/stores, shallow walk
            # (nested defs close over different lifetimes — skip them)
            def shallow(node):
                for child in ast.iter_child_nodes(node):
                    if isinstance(child, (ast.FunctionDef,
                                          ast.AsyncFunctionDef,
                                          ast.Lambda)):
                        continue
                    yield child
                    yield from shallow(child)

            events = []  # (line, kind, varname)
            in_call: set = set()  # Name nodes inside a donating call
            for stmt in scope.body:
                for sub in shallow(stmt):
                    if isinstance(sub, ast.Call) and \
                            isinstance(sub.func, ast.Name) and \
                            sub.func.id in donators:
                        # the call's own argument reads are the
                        # donation, not a read-after-donate (a wrapped
                        # call puts them on LATER lines than the call)
                        for arg in sub.args + [kw.value
                                               for kw in sub.keywords]:
                            in_call.update(id(n) for n in ast.walk(arg)
                                           if isinstance(n, ast.Name))
                        for p in donators[sub.func.id]:
                            if p < len(sub.args) and isinstance(
                                    sub.args[p], ast.Name):
                                events.append((sub.lineno, "donate",
                                               sub.args[p].id))
                    elif isinstance(sub, ast.Name) and \
                            id(sub) not in in_call:
                        kind = ("store" if isinstance(
                            sub.ctx, ast.Store) else "load")
                        events.append((sub.lineno, kind, sub.id))
            events.sort()
            donated_at: dict[str, int] = {}
            for line, kind, var in events:
                if kind == "donate":
                    donated_at[var] = line
                elif kind == "store" and var in donated_at:
                    del donated_at[var]
                elif kind == "load" and var in donated_at and \
                        line > donated_at[var]:
                    scope_name = getattr(scope, "name", "<module>")
                    out.append(Finding(
                        "DTT008",
                        f"{rel}::{scope_name}::{var}",
                        rel, line,
                        f"{var!r} was donated to a jitted call at "
                        f"line {donated_at[var]} and read again here "
                        f"— the donated buffer is dead (rebind the "
                        f"result or pass donate=False)"))
                    del donated_at[var]  # one report per donation
    return out


rule_donation_safety.rule_id = "DTT008"


# ---------------------------------------------- DTT009 traced-coverage


#: the data-MOVING collectives DTT009 tracks (axis_index/axis_size are
#: reads, not wire traffic — DTT001 still covers their axis argument)
_DATA_COLLECTIVES = {"psum", "pmean", "psum_scatter", "all_gather",
                     "ppermute", "all_to_all"}
_DTTCHECK_PREFIX = "tools/dttcheck"


def _identifiers(node) -> set:
    """Every Name id and Attribute attr under ``node`` — the
    conservative reference set (a function passed as a VALUE, e.g.
    ``jax.tree.map(_gather_leaf, ...)``, counts as referenced)."""
    out = set()
    for sub in ast.walk(node):
        if isinstance(sub, ast.Name):
            out.add(sub.id)
        elif isinstance(sub, ast.Attribute):
            out.add(sub.attr)
    return out


def rule_traced_coverage(index) -> list:
    """DTT009: every ``parallel/`` collective call site must be
    reachable from a dttcheck-traced step function — the AST and jaxpr
    layers stay CLOSED UNDER EXTENSION: a new collective path that no
    scenario traces is a comm path whose ledger bytes, deadlock
    freedom, and donation story nobody has machine-proven (the r18
    twin of DTT002's ledger-coverage rule). Reachability is
    name-based and conservative: roots are every identifier
    ``tools/dttcheck/`` mentions; edges are every identifier a
    top-level ``parallel/`` function's body mentions (calls AND
    values — builders pass helpers through ``jax.tree.map`` etc.)."""
    roots: set = set()
    has_dttcheck = False
    for rel, tree in index.trees.items():
        if rel.startswith(_DTTCHECK_PREFIX):
            has_dttcheck = True
            roots |= _identifiers(tree)
    # keyed by (rel, name): reachability is name-based, but a function
    # whose NAME collides with one in another parallel/ module must
    # still contribute its own collective sites (a name-keyed dict
    # would silently drop the second module's — a false negative)
    funcs: dict = {}  # (rel, name) -> node
    for rel, tree in index.trees.items():
        if "/parallel/" not in f"/{rel}" or rel.endswith("__init__.py"):
            continue
        for node in tree.body:
            if isinstance(node, ast.FunctionDef):
                funcs[(rel, node.name)] = node
    names = {name for _, name in funcs}
    first_site: dict = {}   # (rel, name) -> first data-collective line
    edges: dict = {}        # name -> union of referenced func names
    for (rel, name), node in funcs.items():
        for sub in ast.walk(node):
            if isinstance(sub, ast.Call) and _is_collective(sub) and \
                    _callee(sub) in _DATA_COLLECTIVES:
                first_site.setdefault((rel, name), sub.lineno)
        edges[name] = edges.get(name, set()) | (
            _identifiers(node) & names)
    if not first_site:
        return []  # no collective sites in scope (fixture slices)
    if not has_dttcheck:
        return [Finding(
            "DTT009", "tools::dttcheck-missing", _DTTCHECK_PREFIX, 0,
            "parallel/ contains collective call sites but no "
            "tools/dttcheck/ sources are in the walk set — the "
            "traced-coverage rule would silently self-disable")]
    reachable = names & roots
    stack = list(reachable)
    while stack:
        for callee in edges[stack.pop()]:
            if callee not in reachable:
                reachable.add(callee)
                stack.append(callee)
    out = []
    for rel, name in sorted(first_site):
        if name in reachable:
            continue
        out.append(Finding(
            "DTT009", f"{rel}::{name}", rel, first_site[(rel, name)],
            f"collective call site in {name}() is not reachable from "
            f"any dttcheck-traced step function (tools/dttcheck "
            f"references no path to it) — its wire bytes, deadlock "
            f"freedom, and donation story are machine-unproven; add a "
            f"scenario (or wire it into an existing traced builder)"))
    return out


rule_traced_coverage.rule_id = "DTT009"


# ------------------------------------------- DTT010 inventory-coverage


_DTTSAN_PREFIX = "tools/dttsan"


def rule_inventory_coverage(index) -> list:
    """DTT010: every ``threading.Thread``/``Timer`` construction site
    must be dttsan-inventory-REACHABLE — discoverable by the thread
    inventory with a statically-resolvable target (the r20 twin of
    DTT009's traced-coverage rule: the AST and concurrency layers stay
    closed under extension). A Thread whose target the inventory cannot
    name is a concurrent root no pass can prove race-free, and one the
    SAN001 registry can never pin. Self-disable guarded: Thread sites
    with no tools/dttsan/ sources in the walk set are themselves a
    finding."""
    raw_sites = []  # (rel, qual, line, callee)
    has_dttsan = any(rel.startswith(_DTTSAN_PREFIX)
                     for rel in index.trees)
    for rel, tree in index.trees.items():
        for node, qual in _walk_scoped(tree):
            if not isinstance(node, ast.Call):
                continue
            chain = _dotted(node.func) or ""
            name = chain.rsplit(".", 1)[-1]
            head = chain.rsplit(".", 1)[0] if "." in chain else ""
            if name in ("Thread", "Timer") and head in ("", "threading"):
                raw_sites.append((rel, qual, node.lineno, name))
    if not raw_sites:
        return []
    if not has_dttsan:
        return [Finding(
            "DTT010", "tools::dttsan-missing", _DTTSAN_PREFIX, 0,
            "the walk set contains threading.Thread/Timer construction "
            "sites but no tools/dttsan/ sources — the inventory-"
            "coverage rule would silently self-disable")]
    from tools.dttsan.inventory import discover_roots

    roots, _bad = discover_roots(index)
    covered = {(r.path, r.line) for r in roots}
    out = []
    counters: dict = {}
    for rel, qual, line, name in sorted(raw_sites):
        if (rel, line) in covered:
            continue
        c = counters[rel] = counters.get(rel, _Counter())
        out.append(Finding(
            "DTT010", c.key(f"{rel}::{qual or '<module>'}:{name}"),
            rel, line,
            f"threading.{name} constructed here is NOT discoverable by "
            f"the dttsan thread inventory (its target does not resolve "
            f"to a named function/method) — an unnameable root escapes "
            f"the registry and every concurrency pass; name the target "
            f"(a def or self-method)"))
    return out


rule_inventory_coverage.rule_id = "DTT010"


# ----------------------------------------------- DTT011 perf-coverage


_DTTPERF_PREFIX = "tools/dttperf"


def _perf_coverage_tables(index) -> tuple:
    """The string keys of every ``PHASE_FACTS`` / ``PHASE_EXEMPT``
    top-level dict literal under ``tools/dttperf/`` — extracted from
    the AST (not imported: the linter must see exactly what the walk
    set SAYS, the same discipline as every other rule). Returns
    (facts_keys, exempt_with_reason, exempt_bare)."""
    facts: set = set()
    exempt: set = set()
    bare: set = set()
    for rel, tree in index.trees.items():
        if not rel.startswith(_DTTPERF_PREFIX):
            continue
        for node in tree.body:
            if not isinstance(node, (ast.Assign, ast.AnnAssign)):
                continue
            targets = node.targets if isinstance(node, ast.Assign) \
                else [node.target]
            names = {t.id for t in targets if isinstance(t, ast.Name)}
            if not names & {"PHASE_FACTS", "PHASE_EXEMPT"} or \
                    not isinstance(node.value, ast.Dict):
                continue
            for k, v in zip(node.value.keys, node.value.values):
                if not (isinstance(k, ast.Constant)
                        and isinstance(k.value, str)):
                    continue
                if "PHASE_FACTS" in names:
                    facts.add(k.value)
                elif isinstance(v, ast.Constant) and \
                        isinstance(v.value, str) and v.value.strip():
                    exempt.add(k.value)
                else:
                    bare.add(k.value)
    return facts, exempt, bare


def rule_perf_coverage(index) -> list:
    """DTT011: every public bench phase must be dttperf-RESOLVABLE —
    either fact-covered (a ``PHASE_FACTS`` row: DTP002 then enforces
    its facts non-null in every record) or explicitly exempted with a
    stated reason (a ``PHASE_EXEMPT`` row) — the AST and performance
    layers stay closed under extension (the r23 twin of DTT009/DTT010):
    a new phase in neither table is a measurement the performance
    contract silently cannot see — its facts could go null, its rates
    unbanded, and no pass would notice. Self-disable guarded: bench
    phases with no tools/dttperf/ sources in the walk set are
    themselves a finding. A PHASE_EXEMPT entry whose reason is not a
    non-empty string literal counts as uncovered (an unexplained
    exemption is an unexplained hole in the contract)."""
    phases = []  # (rel, name, line)
    for rel, tree in index.trees.items():
        if not rel.endswith("bench.py"):
            continue
        for node in tree.body:
            if isinstance(node, ast.FunctionDef) and \
                    node.name.endswith("_phase") and \
                    not node.name.startswith("_"):
                phases.append((rel, node.name, node.lineno))
    if not phases:
        return []  # no bench phases in scope (fixture slices)
    has_dttperf = any(rel.startswith(_DTTPERF_PREFIX)
                      for rel in index.trees)
    if not has_dttperf:
        return [Finding(
            "DTT011", "tools::dttperf-missing", _DTTPERF_PREFIX, 0,
            "the walk set contains bench phases but no tools/dttperf/ "
            "sources — the perf-coverage rule would silently "
            "self-disable")]
    facts, exempt, bare = _perf_coverage_tables(index)
    out = []
    for rel, name, line in sorted(phases):
        if name in facts or name in exempt:
            continue
        why = ("is PHASE_EXEMPT but its reason is not a non-empty "
               "string literal — an unexplained exemption is an "
               "unexplained hole in the contract"
               if name in bare else
               "is in neither PHASE_FACTS nor PHASE_EXEMPT in "
               "tools/dttperf/ — a phase the performance contract "
               "cannot see: its facts could go null and its rates "
               "drift with no pass noticing")
        out.append(Finding(
            "DTT011", f"{rel}::{name}", rel, line,
            f"bench phase {name}() {why}; add a PHASE_FACTS row (and "
            f"let DTP002 enforce it) or a PHASE_EXEMPT entry with the "
            f"reason"))
    return out


rule_perf_coverage.rule_id = "DTT011"


ALL_RULES = (
    rule_collective_axis,
    rule_ledger_coverage,
    rule_scalar_contract,
    rule_fault_registry,
    rule_span_catalog,
    rule_flag_validator,
    rule_trace_purity,
    rule_donation_safety,
    rule_traced_coverage,
    rule_inventory_coverage,
    rule_perf_coverage,
)
