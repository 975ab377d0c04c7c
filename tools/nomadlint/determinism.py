"""Determinism pass: decision paths must be a pure function of their
seeds.

Scope rationale: DET001/DET003 cover the modules whose outputs feed the
seed-replay contract (simcluster event digests, fuzz differential families)
— scheduler, server, raft, state, simcluster, device solve, structs,
network, events, faults. Observability modules (telemetry/trace/bundle)
are excluded from DET001/DET003: a reservoir sample or span id draw
cannot change a placement. DET002 (wall clock) additionally covers the
observability modules so every ``time.time()`` in the tree carries an
explicit wall-clock-is-correct reason or gets converted.
"""

from __future__ import annotations

import ast
from typing import List, Optional, Set

from tools.nomadlint.project import ModuleInfo, Project, qualname_of
from tools.nomadlint.registry import Finding

DECISION_SCOPE = (
    "nomad_tpu/scheduler",
    "nomad_tpu/server",
    "nomad_tpu/raft",
    "nomad_tpu/state",
    "nomad_tpu/simcluster",
    "nomad_tpu/tpu",
    "nomad_tpu/ops",
    "nomad_tpu/structs.py",
    "nomad_tpu/network.py",
    "nomad_tpu/events.py",
    "nomad_tpu/faults.py",
)

TIME_SCOPE = DECISION_SCOPE + (
    "nomad_tpu/telemetry.py",
    "nomad_tpu/trace.py",
    "nomad_tpu/bundle.py",
    "nomad_tpu/backoff.py",
)

# Importing these names from `random` is fine: an instantiated
# random.Random IS the seeded-stream pattern.
_SEEDED_OK = {"Random", "SystemRandom"}


def _random_aliases(mod: ModuleInfo) -> (Set[str], Set[str]):
    """(names bound to the random MODULE, names bound to its global
    functions via from-imports)."""
    mod_names: Set[str] = set()
    func_names: Set[str] = set()
    for node in ast.walk(mod.tree):
        if isinstance(node, ast.Import):
            for alias in node.names:
                if alias.name == "random":
                    mod_names.add(alias.asname or "random")
        elif isinstance(node, ast.ImportFrom) and node.module == "random":
            for alias in node.names:
                if alias.name not in _SEEDED_OK:
                    func_names.add(alias.asname or alias.name)
    return mod_names, func_names


def _time_aliases(mod: ModuleInfo) -> (Set[str], Set[str]):
    mod_names: Set[str] = set()
    func_names: Set[str] = set()
    for node in ast.walk(mod.tree):
        if isinstance(node, ast.Import):
            for alias in node.names:
                if alias.name == "time":
                    mod_names.add(alias.asname or "time")
        elif isinstance(node, ast.ImportFrom) and node.module == "time":
            for alias in node.names:
                if alias.name == "time":
                    func_names.add(alias.asname or "time")
    return mod_names, func_names


def _set_typed_names(fn: ast.AST) -> Set[str]:
    """Names locally provable to be sets inside one function: assigned a
    set literal/comprehension/set()/frozenset() call."""
    names: Set[str] = set()
    for node in ast.walk(fn):
        if isinstance(node, ast.Assign) and _is_set_expr(node.value):
            for tgt in node.targets:
                if isinstance(tgt, ast.Name):
                    names.add(tgt.id)
        elif isinstance(node, ast.AnnAssign) and isinstance(node.target, ast.Name):
            if _is_set_annotation(node.annotation):
                names.add(node.target.id)
    return names


def _is_set_expr(expr: ast.AST) -> bool:
    if isinstance(expr, (ast.Set, ast.SetComp)):
        return True
    if isinstance(expr, ast.Call) and isinstance(expr.func, ast.Name):
        return expr.func.id in ("set", "frozenset")
    return False


def _is_set_annotation(ann: ast.AST) -> bool:
    base = ann.value if isinstance(ann, ast.Subscript) else ann
    if isinstance(base, ast.Name):
        return base.id in ("Set", "set", "FrozenSet", "frozenset")
    if isinstance(base, ast.Attribute):
        return base.attr in ("Set", "FrozenSet")
    return False


def _self_set_attrs(cls: ast.ClassDef) -> Set[str]:
    """Attributes assigned ``self.X = set()/{...}`` anywhere in the
    class, or annotated as sets."""
    attrs: Set[str] = set()
    for node in ast.walk(cls):
        if isinstance(node, ast.Assign) and _is_set_expr(node.value):
            for tgt in node.targets:
                if (isinstance(tgt, ast.Attribute)
                        and isinstance(tgt.value, ast.Name)
                        and tgt.value.id == "self"):
                    attrs.add(tgt.attr)
        elif (isinstance(node, ast.AnnAssign)
                and isinstance(node.target, ast.Attribute)
                and isinstance(node.target.value, ast.Name)
                and node.target.value.id == "self"
                and _is_set_annotation(node.annotation)):
            attrs.add(node.target.attr)
    return attrs


def run(project: Project) -> List[Finding]:
    findings: List[Finding] = []
    for mod in project.scoped(TIME_SCOPE):
        in_decision = project.in_scope(mod.relpath, DECISION_SCOPE)
        raw: List[Finding] = []
        rand_mods, rand_funcs = _random_aliases(mod)
        time_mods, time_funcs = _time_aliases(mod)

        for node in ast.walk(mod.tree):
            # DET002 applies everywhere in TIME_SCOPE.
            if isinstance(node, ast.Call):
                f = node.func
                if (isinstance(f, ast.Attribute) and f.attr == "time"
                        and isinstance(f.value, ast.Name)
                        and f.value.id in time_mods):
                    raw.append(Finding(
                        "DET002", mod.relpath, node.lineno,
                        qualname_of(node),
                        "time.time() — use time.monotonic() for "
                        "intervals/deadlines; wall clock only for "
                        "user-facing timestamps with an allow() reason",
                        snippet=mod.snippet(node.lineno),
                    ))
                elif (isinstance(f, ast.Name) and f.id in time_funcs):
                    raw.append(Finding(
                        "DET002", mod.relpath, node.lineno,
                        qualname_of(node),
                        "time() imported from time module — same rule "
                        "as time.time()",
                        snippet=mod.snippet(node.lineno),
                    ))
            if not in_decision:
                continue
            # DET001: draws from the process-global random module.
            if isinstance(node, ast.Attribute):
                if (isinstance(node.value, ast.Name)
                        and node.value.id in rand_mods
                        and node.attr not in _SEEDED_OK):
                    raw.append(Finding(
                        "DET001", mod.relpath, node.lineno,
                        qualname_of(node),
                        f"global random.{node.attr} in a decision path — "
                        "use a name-salted seeded stream "
                        "(random.Random(seed ^ crc32(name)))",
                        snippet=mod.snippet(node.lineno),
                    ))
            elif isinstance(node, ast.Name) and node.id in rand_funcs:
                if isinstance(getattr(node, "ctx", None), ast.Load):
                    raw.append(Finding(
                        "DET001", mod.relpath, node.lineno,
                        qualname_of(node),
                        f"{node.id}() from the global random module in a "
                        "decision path — use a seeded Random instance",
                        snippet=mod.snippet(node.lineno),
                    ))
            # DET003: iteration over provable sets.
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                raw.extend(_set_iteration(mod, node))
        # _set_iteration runs per FunctionDef, and ast.walk hands us nested
        # functions both standalone and within their parent — dedupe.
        seen = set()
        deduped = []
        for f in raw:
            k = (f.rule_id, f.line, f.message)
            if k not in seen:
                seen.add(k)
                deduped.append(f)
        findings.extend(project.filter_allowed(mod, deduped))
    return findings


def _set_iteration(mod: ModuleInfo, fn: ast.AST) -> List[Finding]:
    out: List[Finding] = []
    local_sets = _set_typed_names(fn)
    cls = _enclosing_class_attrs(mod, fn)

    def is_set_target(it: ast.AST) -> Optional[str]:
        if _is_set_expr(it):
            return "a set expression"
        if isinstance(it, ast.Name) and it.id in local_sets:
            return f"local set {it.id!r}"
        if (isinstance(it, ast.Attribute)
                and isinstance(it.value, ast.Name)
                and it.value.id == "self" and it.attr in cls):
            return f"set attribute self.{it.attr}"
        return None

    for node in ast.walk(fn):
        iters: List[ast.AST] = []
        if isinstance(node, ast.For):
            iters.append(node.iter)
        elif isinstance(node, (ast.ListComp, ast.SetComp, ast.DictComp,
                               ast.GeneratorExp)):
            iters.extend(g.iter for g in node.generators)
        for it in iters:
            what = is_set_target(it)
            if what is not None:
                out.append(Finding(
                    "DET003", mod.relpath, it.lineno, qualname_of(node),
                    f"iteration over {what}: set order is hash order, "
                    "which varies across processes — iterate sorted(...) "
                    "or keep a list/dict",
                    snippet=mod.snippet(it.lineno),
                ))
    return out


def _enclosing_class_attrs(mod: ModuleInfo, fn: ast.AST) -> Set[str]:
    # A method's stamped qualname is its ENCLOSING scope — i.e. the
    # class's full dotted name — so the class is the ClassDef whose own
    # qualname + name equals it. The attr set is memoized on the ClassDef
    # node itself (dies with the AST; a process-global cache keyed by
    # node id could alias a recycled id across Projects).
    qual = qualname_of(fn, mod.modname)
    for node in ast.walk(mod.tree):
        if (isinstance(node, ast.ClassDef)
                and f"{qualname_of(node, mod.modname)}.{node.name}" == qual):
            attrs = getattr(node, "_nl_set_attrs", None)
            if attrs is None:
                attrs = node._nl_set_attrs = _self_set_attrs(node)
            return attrs
    return set()
