"""Layer 1 — AST source lint: every ROADMAP standing invariant as a
named, waivable rule.

The grep that used to back ``tests/test_invariants.py`` could only see
the literal string ``perf_counter``; these rules resolve imports through
the AST, so ``import time as _t; _t.time()`` and
``from time import perf_counter as _pc`` are caught too, and waivers
(``findings.load_waivers``) replace "the one allowed file" hard-coding
with an explained baseline.

Rules (ids are stable — waivers and tests key on them):

``timing-confinement`` (error)
    ``time.perf_counter`` / ``time.time`` / ``time.monotonic`` /
    ``timeit`` anywhere outside ``src/repro/perf/measure.py``.  All
    timing goes through ``repro.perf.measure`` (interleaved repeats,
    medians); wall-clock *timestamps* that genuinely need epoch time are
    waived with a reason, not exempted silently.

``compat-shim-bypass`` (error)
    direct ``jax.sharding.Mesh(...)`` / ``jax.make_mesh`` construction,
    ``shard_map`` access (``jax.shard_map`` or
    ``jax.experimental.shard_map``), or ``.cost_analysis()`` method
    calls outside ``core/compat.py`` + ``launch/mesh.py``, so a JAX
    upgrade that moves one of these APIs is fixed in those two modules.

``results-writer-bypass`` (error)
    raw ``json.dump`` / ``json.dumps`` in ``benchmarks/`` outside
    ``benchmarks/common.py``.  Every ``benchmarks/results/`` artifact
    must be a ``repro.perf.report.Report`` written via
    ``benchmarks.common.save_result`` so the schema gate sees it.

``donation-hygiene`` (warning)
    a buffer passed positionally through a ``jax.jit(...,
    donate_argnums=...)`` function and then *read again* later in the
    same scope without being rebound — a donated buffer is invalidated
    by the call.  (Heuristic: tracks module/function-local names only;
    the trace layer checks the compiled side — see ``missed-donation``
    in ``repro.analysis.trace``.)

``interpret-mode-leak`` (error)
    ``pl.pallas_call(..., interpret=True)`` — the literal constant,
    alias-resolved through any import spelling, directly or through
    ``functools.partial`` — anywhere outside ``tests/`` and the kernel
    ``*/ref.py`` oracles.  Interpret mode on a hot path is a silent
    ~100x: production call sites must thread a resolved flag
    (``kernels.common.interpret_default``) so TPU runs compile.

Run it: ``python -m repro.analysis`` (or ``scripts/ci.sh --lint``).
This module is stdlib-only; importing it never imports jax.
"""
from __future__ import annotations

import ast
import dataclasses
import pathlib
from typing import Dict, List, Optional, Sequence, Set, Tuple

from repro.analysis.findings import Finding

#: directories scanned by default, relative to the repo root
SCAN_DIRS = ("src", "benchmarks", "examples", "scripts")

_TIMING_ALLOWED = ("src/repro/perf/measure.py",)
_COMPAT_ALLOWED = ("src/repro/core/compat.py", "src/repro/launch/mesh.py")
_RESULTS_ALLOWED = ("benchmarks/common.py",)

_TIME_BAD_ATTRS = {"perf_counter", "perf_counter_ns", "time", "monotonic"}
_SHARD_MAP_DOTTED = {"jax.shard_map", "jax.experimental.shard_map",
                     "jax.experimental.shard_map.shard_map"}


@dataclasses.dataclass(frozen=True)
class Rule:
    rule: str
    severity: str
    description: str


SOURCE_RULES: Dict[str, Rule] = {r.rule: r for r in (
    Rule("timing-confinement", "error",
         "time.perf_counter/time.time/timeit outside perf/measure.py"),
    Rule("compat-shim-bypass", "error",
         "Mesh/shard_map/cost_analysis outside core/compat.py + "
         "launch/mesh.py"),
    Rule("results-writer-bypass", "error",
         "raw json.dump in benchmarks/ instead of common.save_result"),
    Rule("donation-hygiene", "warning",
         "donated buffer read again after the donating call"),
    Rule("interpret-mode-leak", "error",
         "literal pallas_call(interpret=True) outside tests/ and "
         "*/ref.py"),
    Rule("parse-error", "error", "file does not parse"),
)}


def _dotted(node: ast.AST, mod_aliases: Dict[str, str]) -> Optional[str]:
    """Resolve an attribute chain to a dotted module path, following
    import aliases at the root; None when the root is not a tracked
    module alias."""
    parts: List[str] = []
    cur = node
    while isinstance(cur, ast.Attribute):
        parts.append(cur.attr)
        cur = cur.value
    if not isinstance(cur, ast.Name):
        return None
    root = mod_aliases.get(cur.id)
    if root is None:
        return None
    parts.append(root)
    return ".".join(reversed(parts))


def _collect_imports(tree: ast.AST) -> Tuple[Dict[str, str], Dict[str, str],
                                             List[Tuple[ast.AST, str, str]]]:
    """One pass over every import in the file.

    Returns (module aliases {local: root module}, constructor/function
    aliases {local: dotted origin}, and import-site findings material
    [(node, rule-key, message)]).
    """
    mod_aliases: Dict[str, str] = {}
    name_aliases: Dict[str, str] = {}
    import_hits: List[Tuple[ast.AST, str, str]] = []
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for a in node.names:
                root = a.name.split(".")[0]
                if root in ("time", "jax", "json", "functools"):
                    if a.asname:
                        # `import jax.experimental.pallas as pl` binds the
                        # FULL dotted path to the alias, so pl.pallas_call
                        # resolves to jax.experimental.pallas.pallas_call
                        mod_aliases[a.asname] = a.name
                    else:
                        mod_aliases[root] = root
                if root == "timeit":
                    import_hits.append((node, "timing", f"import {a.name}"))
        elif isinstance(node, ast.ImportFrom):
            mod = node.module or ""
            if mod in ("jax.experimental", "jax.experimental.pallas",
                       "functools"):
                for a in node.names:
                    if mod == "jax.experimental" and a.name == "pallas":
                        mod_aliases[a.asname or a.name] = \
                            "jax.experimental.pallas"
                    elif mod == "jax.experimental.pallas" \
                            and a.name == "pallas_call":
                        name_aliases[a.asname or a.name] = \
                            "jax.experimental.pallas.pallas_call"
                    elif mod == "functools" and a.name == "partial":
                        name_aliases[a.asname or a.name] = \
                            "functools.partial"
            if mod == "time":
                for a in node.names:
                    if a.name in _TIME_BAD_ATTRS:
                        asname = f" as {a.asname}" if a.asname else ""
                        import_hits.append((
                            node, "timing",
                            f"from time import {a.name}{asname}"))
                        name_aliases[a.asname or a.name] = f"time.{a.name}"
            elif mod == "timeit":
                import_hits.append((node, "timing", "from timeit import ..."))
            elif mod == "jax.experimental.shard_map":
                import_hits.append((node, "shard_map",
                                    "from jax.experimental.shard_map "
                                    "import ..."))
            elif mod == "jax.sharding":
                for a in node.names:
                    if a.name == "Mesh":
                        name_aliases[a.asname or a.name] = "jax.sharding.Mesh"
            elif mod == "json":
                for a in node.names:
                    if a.name in ("dump", "dumps"):
                        name_aliases[a.asname or a.name] = f"json.{a.name}"
    return mod_aliases, name_aliases, import_hits


def _outermost_attributes(tree: ast.AST) -> List[ast.Attribute]:
    """Attribute nodes that are not the ``.value`` of a longer chain —
    so ``jax.experimental.shard_map`` reports once, not per link."""
    inner: Set[int] = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Attribute) and isinstance(node.value,
                                                          ast.Attribute):
            inner.add(id(node.value))
    return [n for n in ast.walk(tree)
            if isinstance(n, ast.Attribute) and id(n) not in inner]


def _stored_names(stmt: ast.stmt) -> Set[str]:
    return {n.id for n in ast.walk(stmt)
            if isinstance(n, ast.Name) and isinstance(n.ctx, ast.Store)}


def _donation_findings(tree: ast.AST, rel: str,
                       mod_aliases: Dict[str, str]) -> List[Finding]:
    """Per-scope heuristic: name = jax.jit(..., donate_argnums=...);
    name(<args>) donating a plain-Name buffer; any later Load of that
    buffer in the same scope before a rebind is a use-after-donation."""
    findings: List[Finding] = []

    def _donated_positions(call: ast.Call) -> Optional[Tuple[int, ...]]:
        if _dotted(call.func, mod_aliases) != "jax.jit":
            return None
        for kw in call.keywords:
            if kw.arg != "donate_argnums":
                continue
            v = kw.value
            if isinstance(v, ast.Constant) and isinstance(v.value, int):
                return (v.value,)
            if isinstance(v, ast.Tuple) and all(
                    isinstance(e, ast.Constant) and isinstance(e.value, int)
                    for e in v.elts):
                return tuple(e.value for e in v.elts)
            return None
        return None

    def _scan_scope(body: List[ast.stmt]) -> None:
        jitted: Dict[str, Tuple[int, ...]] = {}
        # donated-name -> (call line) still awaiting rebind
        live: Dict[str, int] = {}
        for stmt in body:
            # reads first: `y = g(x)` after donating x is a use
            for name, call_line in list(live.items()):
                loads = [n for n in ast.walk(stmt)
                         if isinstance(n, ast.Name) and n.id == name
                         and isinstance(n.ctx, ast.Load)]
                if loads:
                    findings.append(Finding(
                        "donation-hygiene", "warning", rel, loads[0].lineno,
                        f"`{name}` was donated to a jax.jit("
                        f"donate_argnums=...) call on line {call_line} and "
                        "is read again here — donated buffers are "
                        "invalidated by the call; rebind the result "
                        f"(`{name} = fn({name}, ...)`) or stop donating"))
                    del live[name]
            stored = _stored_names(stmt)
            for name in stored:
                live.pop(name, None)
            for node in ast.walk(stmt):
                if not isinstance(node, ast.Call):
                    continue
                pos = _donated_positions(node)
                if pos is not None:
                    # pattern: fn_name = jax.jit(..., donate_argnums=...)
                    if (isinstance(stmt, ast.Assign)
                            and len(stmt.targets) == 1
                            and isinstance(stmt.targets[0], ast.Name)):
                        jitted[stmt.targets[0].id] = pos
                    continue
                if (isinstance(node.func, ast.Name)
                        and node.func.id in jitted):
                    for i in jitted[node.func.id]:
                        if i < len(node.args) and isinstance(node.args[i],
                                                             ast.Name):
                            arg = node.args[i].id
                            if arg not in stored:   # not rebound by result
                                live[arg] = node.lineno

    for node in ast.walk(tree):
        if isinstance(node, (ast.Module, ast.FunctionDef,
                             ast.AsyncFunctionDef)):
            _scan_scope(list(node.body))
    return findings


def lint_source(src: str, rel: str) -> List[Finding]:
    """Run every source rule over one file's text (``rel`` is the
    repo-relative posix path — rules scope on it)."""
    rel = rel.replace("\\", "/")
    try:
        tree = ast.parse(src)
    except SyntaxError as e:
        return [Finding("parse-error", "error", rel, e.lineno or 0,
                        f"file does not parse: {e.msg}")]
    findings: List[Finding] = []
    mod_aliases, name_aliases, import_hits = _collect_imports(tree)

    timing_ok = rel in _TIMING_ALLOWED
    compat_ok = rel in _COMPAT_ALLOWED
    in_benchmarks = rel.startswith("benchmarks/")
    results_ok = (not in_benchmarks) or rel in _RESULTS_ALLOWED
    # interpret-mode exemptions: tests may force the interpreter, and the
    # kernel ref.py oracles are allowed to be slow and dense
    interp_ok = (rel.startswith("tests/") or rel == "ref.py"
                 or rel.endswith("/ref.py"))

    def _is_pallas_call(expr: ast.AST) -> bool:
        if isinstance(expr, ast.Name):
            return (name_aliases.get(expr.id)
                    == "jax.experimental.pallas.pallas_call")
        return _dotted(expr, mod_aliases) \
            == "jax.experimental.pallas.pallas_call"

    for node, kind, what in import_hits:
        if kind == "timing" and not timing_ok:
            findings.append(Finding(
                "timing-confinement", "error", rel, node.lineno,
                f"{what} — timing must go through repro.perf.measure "
                "(aliased imports bypass nothing)"))
        elif kind == "shard_map" and not compat_ok:
            findings.append(Finding(
                "compat-shim-bypass", "error", rel, node.lineno,
                f"{what} — use repro.core.compat.shard_map"))

    for node in _outermost_attributes(tree):
        d = _dotted(node, mod_aliases)
        if d is None:
            continue
        if (not timing_ok and d.startswith("time.")
                and d.split(".", 1)[1] in _TIME_BAD_ATTRS):
            findings.append(Finding(
                "timing-confinement", "error", rel, node.lineno,
                f"{d} outside src/repro/perf/measure.py — route timing "
                "through repro.perf.measure (measure()/now()); wall-clock "
                "timestamps need an explicit waiver with a reason"))
        elif not compat_ok and d in _SHARD_MAP_DOTTED:
            findings.append(Finding(
                "compat-shim-bypass", "error", rel, node.lineno,
                f"{d} — use repro.core.compat.shard_map"))

    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        func = node.func
        d = _dotted(func, mod_aliases)
        origin = (name_aliases.get(func.id)
                  if isinstance(func, ast.Name) else None)
        if not compat_ok:
            if d == "jax.make_mesh" or d == "jax.sharding.Mesh" \
                    or origin == "jax.sharding.Mesh":
                findings.append(Finding(
                    "compat-shim-bypass", "error", rel, node.lineno,
                    f"direct mesh construction ({d or origin}) — build "
                    "meshes via repro.launch.mesh.make_mesh"))
            elif isinstance(func, ast.Attribute) \
                    and func.attr == "cost_analysis":
                findings.append(Finding(
                    "compat-shim-bypass", "error", rel, node.lineno,
                    ".cost_analysis() may return None or a per-module "
                    "list — use repro.core.compat.cost_dict"))
        if not results_ok and (d in ("json.dump", "json.dumps")
                               or origin in ("json.dump", "json.dumps")):
            findings.append(Finding(
                "results-writer-bypass", "error", rel, node.lineno,
                f"raw {d or origin}() in benchmarks/ — every "
                "benchmarks/results/ artifact must be a Report written "
                "via benchmarks.common.save_result"))
        if not interp_ok:
            # literal interpret=True at a pallas_call site — directly or
            # curried through functools.partial(pl.pallas_call, ...)
            is_partial = ((d == "functools.partial"
                           or origin == "functools.partial")
                          and node.args and _is_pallas_call(node.args[0]))
            if (_is_pallas_call(func) or is_partial) and any(
                    kw.arg == "interpret"
                    and isinstance(kw.value, ast.Constant)
                    and kw.value.value is True for kw in node.keywords):
                findings.append(Finding(
                    "interpret-mode-leak", "error", rel, node.lineno,
                    "pallas_call(interpret=True) outside tests// ref.py "
                    "— interpret mode on a production path is a silent "
                    "~100x; thread a resolved flag through "
                    "kernels.common.interpret_default instead"))
        # `from time import perf_counter as _pc; _pc()` — the import is
        # already flagged; flag the call too so waivers can't hide a use
        # behind an import-only waiver line
        if not timing_ok and origin and origin.startswith("time."):
            findings.append(Finding(
                "timing-confinement", "error", rel, node.lineno,
                f"call of {origin} (imported under the name "
                f"`{func.id}`) — route timing through repro.perf.measure"))

    findings.extend(_donation_findings(tree, rel, mod_aliases))
    findings.sort(key=lambda f: (f.path, f.line, f.rule))
    return findings


def lint_file(path: pathlib.Path, root: pathlib.Path) -> List[Finding]:
    rel = path.resolve().relative_to(root.resolve()).as_posix()
    return lint_source(path.read_text(encoding="utf-8"), rel)


def iter_tree(root: pathlib.Path,
              subdirs: Sequence[str] = SCAN_DIRS) -> List[pathlib.Path]:
    files: List[pathlib.Path] = []
    for sub in subdirs:
        base = root / sub
        if not base.is_dir():
            continue
        files.extend(p for p in sorted(base.rglob("*.py"))
                     if "__pycache__" not in p.parts)
    return files


def lint_tree(root: pathlib.Path,
              subdirs: Sequence[str] = SCAN_DIRS) -> List[Finding]:
    """Every source rule over the standing scan set (src/ benchmarks/
    examples/ scripts/) under ``root``."""
    findings: List[Finding] = []
    for path in iter_tree(root, subdirs):
        findings.extend(lint_file(path, root))
    return findings
