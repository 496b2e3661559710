"""Registry-drift rules: metrics vs FAMILIES, config reads vs declared knobs.

metric-registry-drift — `_private/runtime_metrics.py` is the single
declaration point for every built-in metric family (docs and the exposure
test read FAMILIES).  Families declared but never registered, registered
but never recorded, recorded with tag keys that don't match the
declaration, or constructed ad hoc outside the registry are all drift that
ends as a dashboard querying a series that does not exist.

config-knob-drift — every ``global_config().<knob>`` read must resolve to
a declared field of RayTpuConfig: a typo'd knob read silently returns
AttributeError at runtime (or worse, getattr-with-default semantics hide it
forever), and an undeclared knob has no RAY_TPU_<name> override, no blob
distribution, and no documented default.
"""

from __future__ import annotations

import ast
import os
from typing import Dict, List, Optional, Set, Tuple

from ray_tpu._private.analysis.engine import (
    Engine, FileContext, Finding, Rule, Severity)

_REGISTRY_REL = "ray_tpu/_private/runtime_metrics.py"
_CONFIG_REL = "ray_tpu/_private/config.py"
_METRIC_CTORS = ("Counter", "Gauge", "Histogram", "Sketch")


def _call_names(path: str) -> Set[str]:
    """Every callee name (Name or terminal Attribute) in one file — the
    cheap liveness signal for registry recording helpers."""
    out: Set[str] = set()
    try:
        with open(path, "r", encoding="utf-8") as f:
            tree = ast.parse(f.read())
    except (OSError, SyntaxError):
        return out
    for n in ast.walk(tree):
        if isinstance(n, ast.Call):
            if isinstance(n.func, ast.Name):
                out.add(n.func.id)
            elif isinstance(n.func, ast.Attribute):
                out.add(n.func.attr)
    return out


def _const_str_tuple(node: ast.AST) -> Optional[Tuple[str, ...]]:
    if isinstance(node, (ast.Tuple, ast.List)):
        out = []
        for e in node.elts:
            if isinstance(e, ast.Constant) and isinstance(e.value, str):
                out.append(e.value)
            else:
                return None
        return tuple(out)
    return None


class MetricRegistryDrift(Rule):
    id = "metric-registry-drift"
    severity = Severity.MEDIUM
    summary = ("metric family declarations, FAMILIES registration, "
               "recordings and tag keys out of sync")
    hint = ("declare every family once in _private/runtime_metrics.py, "
            "list it in FAMILIES, and record with exactly the declared "
            "tag keys")
    doc = """\
_private/runtime_metrics.py declares every built-in family ONCE; FAMILIES
is what the docs and the exposure test enumerate.  Four drift shapes are
flagged:

  1. declared-not-registered (medium): a module-level Counter/Gauge/
     Histogram/Sketch assignment missing from FAMILIES — it exists but the
     exposure surface doesn't know it.
  2. tag-key mismatch (medium): a `_bound(FAMILY, k=...)` or
     `FAMILY.with_tags({...})` recording whose keys differ from the
     declaration's tag_keys — the recorded series never joins the declared
     one.
  3. out-of-registry family (medium): a ray_tpu_* family constructed
     outside runtime_metrics.py — invisible to FAMILIES, docs and tests.
  4. declared-but-never-recorded (low, warn): a FAMILIES entry no code
     records — either dead weight to prune or a missing instrumentation
     point to wire (each carries a written justification if kept).
"""

    def __init__(self):
        self._declared: Dict[str, Tuple[str, Tuple[str, ...], int]] = {}
        self._families: Set[str] = set()
        self._families_line = 0
        self._registry_seen = False
        # var -> [(rel, line, keys or None-for-dynamic)]
        self._recordings: Dict[str, List[Tuple[str, int,
                                               Optional[Tuple]]]] = {}
        self._uses: Set[str] = set()
        self._outside: List[Tuple[str, int, str]] = []
        # helper-liveness: a family only counts as recorded if the registry
        # helper that records it is actually CALLED from runtime code
        self._alias: Dict[str, str] = {}        # module alias -> var
        self._func_refs: List[Tuple[str, str]] = []   # (func, referenced id)
        self._introspect: List[Tuple[str, str]] = []  # (func, var) VAR._x
        self._called: Set[str] = set()          # every callee name, repo-wide
        self._external_uses: Set[str] = set()   # VAR referenced outside

    # -- collection ----------------------------------------------------------
    def _metric_ctor(self, call: ast.Call) -> Optional[str]:
        f = call.func
        name = f.id if isinstance(f, ast.Name) else (
            f.attr if isinstance(f, ast.Attribute) else None)
        return name if name in _METRIC_CTORS else None

    def visit_Assign(self, node: ast.Assign, ctx: FileContext) -> None:
        if ctx.rel != _REGISTRY_REL or ctx.func_stack or ctx.class_stack:
            return
        self._registry_seen = True
        if not isinstance(node.value, (ast.Call, ast.Tuple)):
            return
        if isinstance(node.value, ast.Tuple) and len(node.targets) == 1 \
                and isinstance(node.targets[0], ast.Name) \
                and node.targets[0].id == "FAMILIES":
            self._families_line = node.lineno
            for e in node.value.elts:
                if isinstance(e, ast.Name):
                    self._families.add(e.id)
            return
        if isinstance(node.value, ast.Call):
            # module-level recording alias: _x = VAR.with_tags(...)
            vf = node.value.func
            if isinstance(vf, ast.Attribute) and vf.attr == "with_tags" \
                    and isinstance(vf.value, ast.Name) \
                    and vf.value.id.isupper() \
                    and len(node.targets) == 1 \
                    and isinstance(node.targets[0], ast.Name):
                self._alias[node.targets[0].id] = vf.value.id
            ctor = self._metric_ctor(node.value)
            if ctor and len(node.targets) == 1 \
                    and isinstance(node.targets[0], ast.Name) \
                    and node.value.args \
                    and isinstance(node.value.args[0], ast.Constant):
                family = node.value.args[0].value
                tag_keys: Tuple[str, ...] = ()
                for kw in node.value.keywords:
                    if kw.arg == "tag_keys":
                        keys = _const_str_tuple(kw.value)
                        if keys is None:
                            return  # dynamic tag_keys: skip checks
                        tag_keys = keys
                self._declared[node.targets[0].id] = (
                    family, tag_keys, node.lineno)

    def visit_Call(self, node: ast.Call, ctx: FileContext) -> None:
        # callee-name liveness (who calls which recording helper)
        f0 = node.func
        if isinstance(f0, ast.Name):
            self._called.add(f0.id)
        elif isinstance(f0, ast.Attribute):
            self._called.add(f0.attr)
        # out-of-registry construction of a ray_tpu_* family
        if ctx.rel not in (_REGISTRY_REL, "ray_tpu/util/metrics.py"):
            ctor = self._metric_ctor(node)
            if ctor and node.args and isinstance(node.args[0], ast.Constant) \
                    and isinstance(node.args[0].value, str) \
                    and node.args[0].value.startswith("ray_tpu_"):
                if not ctx.allowed(self.id, node.lineno):
                    self._outside.append(
                        (ctx.rel, node.lineno, node.args[0].value))
        # recordings: _bound(VAR, k=...) and VAR.with_tags(...)
        f = node.func
        if isinstance(f, ast.Name) and f.id == "_bound" and node.args \
                and isinstance(node.args[0], ast.Name):
            var = node.args[0].id
            if any(kw.arg is None for kw in node.keywords):
                keys: Optional[Tuple] = None  # **tags: dynamic
            else:
                keys = tuple(sorted(kw.arg for kw in node.keywords))
            self._recordings.setdefault(var, []).append(
                (ctx.rel, node.lineno, keys))
            self._uses.add(var)
        elif isinstance(f, ast.Attribute) and f.attr == "with_tags":
            base = f.value
            var = None
            if isinstance(base, ast.Name):
                var = base.id
            elif isinstance(base, ast.Attribute) and base.attr.isupper():
                var = base.attr  # runtime_metrics.VAR.with_tags(...)
            if var and var.isupper():
                if not node.args:
                    keys = ()
                elif isinstance(node.args[0], ast.Dict) and all(
                        isinstance(k, ast.Constant)
                        for k in node.args[0].keys):
                    keys = tuple(sorted(k.value for k in node.args[0].keys))
                else:
                    keys = None
                self._recordings.setdefault(var, []).append(
                    (ctx.rel, node.lineno, keys))
                self._uses.add(var)

    def visit_Name(self, node: ast.Name, ctx: FileContext) -> None:
        # any other Load reference to a declared metric var (snapshot
        # folds, helper binds, direct imports elsewhere) counts as
        # "recorded/used" for the never-recorded warning — but the FAMILIES
        # listing and the declaration target themselves do not
        if not isinstance(node.ctx, ast.Load):
            return
        if ctx.rel == _REGISTRY_REL:
            if ctx.func_stack:
                fname = getattr(ctx.func_stack[0], "name", "<lambda>")
                self._func_refs.append((fname, node.id))
                if node.id.isupper():
                    self._uses.add(node.id)
        elif node.id.isupper():
            self._uses.add(node.id)
            self._external_uses.add(node.id)

    def visit_Attribute(self, node: ast.Attribute, ctx: FileContext) -> None:
        if node.attr.isupper() and isinstance(node.value, ast.Name) \
                and node.value.id == "runtime_metrics":
            self._uses.add(node.attr)
            self._external_uses.add(node.attr)
        # VAR._snapshot / VAR._points inside a registry helper is
        # introspection (a read), not a recording
        if ctx.rel == _REGISTRY_REL and ctx.func_stack \
                and node.attr.startswith("_") \
                and isinstance(node.value, ast.Name) \
                and node.value.id.isupper():
            fname = getattr(ctx.func_stack[0], "name", "<lambda>")
            self._introspect.append((fname, node.value.id))

    # -- verdicts ------------------------------------------------------------
    def finalize(self, engine: Engine) -> List[Finding]:
        out: List[Finding] = []
        if not self._registry_seen:
            # partial run (--diff) that didn't include the registry: parse
            # it directly so recordings can still be checked.  Declarations
            # come from MODULE-LEVEL statements only — ast.walk would hand
            # function-local assignments to visit_Assign with empty stacks,
            # misclassifying them as declarations.
            path = os.path.join(engine.root, _REGISTRY_REL)
            if os.path.exists(path):
                with open(path, "r", encoding="utf-8") as fobj:
                    source = fobj.read()
                tree = ast.parse(source)
                ctx = FileContext(engine.root, path, source, tree)
                for n in tree.body:
                    if isinstance(n, ast.Assign):
                        self.visit_Assign(n, ctx)
                for n in ast.walk(tree):
                    # references inside helper bodies count as uses
                    if isinstance(n, (ast.FunctionDef,
                                      ast.AsyncFunctionDef)):
                        for m in ast.walk(n):
                            if isinstance(m, ast.Name) and m.id.isupper():
                                self._uses.add(m.id)
                    if isinstance(n, ast.Call):
                        self.visit_Call(n, ctx)
        # helper-liveness: which declared vars have a registry recorder
        # function that runtime code actually calls.  Callers in
        # benchmarks/ count (they are runtime consumers outside the linted
        # tree); callers only in tests/ do not — a family recorded solely
        # by its own test is still dead on every real code path.  Needs
        # the WHOLE tree walked: a --diff run has no caller visibility,
        # so the never-recorded verdict is skipped there.
        check_liveness = not engine.partial
        called = set(self._called)
        bench_dir = os.path.join(engine.root, "benchmarks")
        if os.path.isdir(bench_dir):
            for fn in os.listdir(bench_dir):
                if fn.endswith(".py"):
                    called.update(_call_names(os.path.join(bench_dir, fn)))
        from collections import Counter

        name_refs = Counter(self._func_refs)
        intro = Counter(self._introspect)
        live_recorded: Set[str] = set()
        for (func, ident), n in name_refs.items():
            var = ident if ident.isupper() else self._alias.get(ident)
            if var is None or var not in self._declared:
                continue
            eff = n - (intro.get((func, ident), 0) if ident.isupper() else 0)
            if eff > 0 and func in called:
                live_recorded.add(var)

        for var, (family, tag_keys, line) in sorted(self._declared.items()):
            if var not in self._families:
                out.append(Finding(
                    rule=self.id, severity=Severity.MEDIUM,
                    path=_REGISTRY_REL, line=line,
                    message=f"{var} ({family}) declared but not listed in "
                            f"FAMILIES", hint=self.hint))
            elif check_liveness and var not in live_recorded \
                    and var not in self._external_uses:
                out.append(Finding(
                    rule=self.id, severity=Severity.LOW,
                    path=_REGISTRY_REL, line=line,
                    message=f"{var} ({family}) is in FAMILIES but no live "
                            f"code path records it "
                            f"(declared-but-never-recorded)",
                    hint="prune it or wire the missing instrumentation "
                         "point; keep only with a written justification"))
            declared_keys = tuple(sorted(tag_keys))
            for rel, rline, keys in self._recordings.get(var, ()):
                if keys is None:
                    continue  # dynamic tags: the runtime cache handles it
                if tuple(sorted(keys)) != declared_keys:
                    out.append(Finding(
                        rule=self.id, severity=Severity.MEDIUM,
                        path=rel, line=rline,
                        message=f"recording {var} ({family}) with tag keys "
                                f"{tuple(keys)} but it declares "
                                f"{tuple(declared_keys)}",
                        hint=self.hint))
        for rel, line, family in self._outside:
            out.append(Finding(
                rule=self.id, severity=Severity.MEDIUM, path=rel, line=line,
                message=f"family {family} constructed outside the registry "
                        f"(_private/runtime_metrics.py)",
                hint="declare it once in runtime_metrics.py and record "
                     "through a bound recorder"))
        return out


class ConfigKnobDrift(Rule):
    id = "config-knob-drift"
    severity = Severity.MEDIUM
    summary = ("global_config().<knob> read without a declared default in "
               "_private/config.py")
    hint = ("add the field (with its default and a comment) to "
            "RayTpuConfig in _private/config.py — that is what gives it a "
            "RAY_TPU_<name> override and blob distribution")
    doc = """\
RayTpuConfig in _private/config.py is the single flag table: a field there
gets a documented default, a RAY_TPU_<name> env override, and head-node
blob distribution.  A config read that does NOT resolve to a declared
field is either a typo (AttributeError at runtime, usually on a cold error
path where no test walks) or an undeclared knob that can't be overridden
or distributed.

The rule tracks `global_config().<attr>` chains plus reads through local
aliases (`cfg = global_config(); ... cfg.<attr>`), scoped per function so
unrelated variables named cfg elsewhere never alias the flag table.
"""

    def __init__(self):
        self._fields: Set[str] = set()
        self._config_seen = False
        self._reads: List[Tuple[str, int, str]] = []
        self._scopes: List[Set[str]] = [set()]

    _METHODS = {"to_blob", "from_blob"}

    def begin_file(self, ctx: FileContext) -> None:
        # the module-level alias scope is per FILE: a module-level
        # `cfg = global_config()` in one file must not alias every later
        # file's unrelated `cfg` locals
        self._scopes = [set()]

    # -- config.py field collection ------------------------------------------
    def visit_AnnAssign(self, node: ast.AnnAssign, ctx: FileContext) -> None:
        if ctx.rel != _CONFIG_REL:
            return
        if ctx.class_stack and ctx.class_stack[-1].name == "RayTpuConfig" \
                and isinstance(node.target, ast.Name):
            self._config_seen = True
            self._fields.add(node.target.id)

    # -- alias scope tracking ------------------------------------------------
    def visit_FunctionDef(self, node, ctx: FileContext) -> None:
        self._scopes.append(set())

    def leave_FunctionDef(self, node, ctx: FileContext) -> None:
        self._scopes.pop()

    visit_AsyncFunctionDef = visit_FunctionDef
    leave_AsyncFunctionDef = leave_FunctionDef

    @staticmethod
    def _is_global_config_call(node: ast.AST) -> bool:
        if not isinstance(node, ast.Call):
            return False
        f = node.func
        return (isinstance(f, ast.Name) and f.id == "global_config") or (
            isinstance(f, ast.Attribute) and f.attr == "global_config")

    def visit_Assign(self, node: ast.Assign, ctx: FileContext) -> None:
        names = [t.id for t in node.targets if isinstance(t, ast.Name)]
        if not names:
            return
        if self._is_global_config_call(node.value):
            self._scopes[-1].update(names)
        else:
            # rebinding a former alias kills it for the rest of the scope
            # (lexically approximate, but aliases are write-once in practice)
            self._scopes[-1].difference_update(names)

    def visit_Attribute(self, node: ast.Attribute, ctx: FileContext) -> None:
        if ctx.rel == _CONFIG_REL:
            return
        attr = node.attr
        if attr.startswith("__") or attr in self._METHODS:
            return
        direct = self._is_global_config_call(node.value)
        aliased = isinstance(node.value, ast.Name) and any(
            node.value.id in s for s in self._scopes)
        if (direct or aliased) and not ctx.allowed(self.id, node.lineno):
            self._reads.append((ctx.rel, node.lineno, attr))

    # -- verdicts ------------------------------------------------------------
    def finalize(self, engine: Engine) -> List[Finding]:
        if not self._config_seen:
            path = os.path.join(engine.root, _CONFIG_REL)
            if os.path.exists(path):
                with open(path, "r", encoding="utf-8") as fobj:
                    tree = ast.parse(fobj.read())
                for n in ast.walk(tree):
                    if isinstance(n, ast.ClassDef) \
                            and n.name == "RayTpuConfig":
                        for m in n.body:
                            if isinstance(m, ast.AnnAssign) \
                                    and isinstance(m.target, ast.Name):
                                self._fields.add(m.target.id)
        out: List[Finding] = []
        for rel, line, attr in self._reads:
            if attr not in self._fields:
                out.append(Finding(
                    rule=self.id, severity=self.severity, path=rel,
                    line=line,
                    message=f"config read .{attr} has no declared default "
                            f"in RayTpuConfig", hint=self.hint))
        return out
