"""graftlint engine: one AST walk per file, rules subscribe to node events.

Design (reference direction: clang-tidy's check registry over one AST pass;
Ray's C++ core wires clang-tidy + TSan for exactly this bug class — PARITY.md):

- Each rule is a plugin object with ``visit_<NodeType>`` /
  ``leave_<NodeType>`` handlers; the engine walks each file's AST exactly
  ONCE and dispatches every node to the rules subscribed to its type, so
  adding rules never adds passes (the full-repo budget is <15 s,
  benchmarks/lint_overhead_bench.py).
- The walk maintains the shared lexical context rules need (class stack,
  function stack, enclosing-With chain, per-line suppression pragmas) in a
  ``FileContext`` so each rule stays a few dozen lines of matching logic.
- Repo-level rules (registry drift) collect per-file facts during the walk
  and emit findings from ``finalize()`` after every file was seen.

Findings carry rule id / severity / file:line / message / fix hint.  A
finding is suppressed in-source by a pragma on its line (or the line above)::

    # graftlint: allow(rule-id) — reason the invariant holds here

The reason text is REQUIRED: a bare allow() is itself a finding.  For
swallowed-exception the repo's established ``# noqa: BLE001 — reason`` idiom
counts as the same thing (reasoned suppression); a bare ``noqa`` does not.
"""

from __future__ import annotations

import ast
import os
import re
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Sequence, Tuple


class Severity:
    HIGH = "high"        # a bug class a prior PR actually shipped and fixed
    MEDIUM = "medium"    # drift that will become a bug (registry/config)
    LOW = "low"          # advisory (declared-but-never-recorded, ...)

    ORDER = {HIGH: 0, MEDIUM: 1, LOW: 2}


@dataclass(frozen=True)
class Finding:
    rule: str            # rule id, e.g. "blocking-under-lock"
    severity: str        # Severity.*
    path: str            # repo-relative posix path
    line: int
    message: str
    hint: str = ""

    @property
    def key(self) -> str:
        """Baseline identity.  Deliberately line-numbered: grandfathered
        findings must be re-justified (or fixed) when the code around them
        moves — a baseline that silently tracks drifting code rots."""
        return f"{self.rule}:{self.path}:{self.line}"

    def render(self) -> str:
        s = f"{self.path}:{self.line}: [{self.rule}/{self.severity}] {self.message}"
        if self.hint:
            s += f"\n    hint: {self.hint}"
        return s


# pragma: "# graftlint: allow(rule-a, rule-b) — reason" (reason required)
_ALLOW_RE = re.compile(
    r"#\s*graftlint:\s*allow\(([a-z0-9_,\s-]+)\)\s*(?:—|--|:)?\s*(.*)$")
# tool markers are instructions to tools, not written reasons
_TOOL_MARKER_RE = re.compile(
    r"^(pragma[:\s]|type:\s*ignore|noqa\b|graftlint:|todo\b|fixme\b|xxx\b)",
    re.IGNORECASE)


class FileContext:
    """Everything rules can see while their file is being walked."""

    def __init__(self, root: str, path: str, source: str, tree: ast.Module):
        self.root = root
        self.path = path
        self.rel = os.path.relpath(path, root).replace(os.sep, "/")
        self.source = source
        self.lines = source.splitlines()
        self.tree = tree
        # lexical stacks, maintained by the engine during the walk
        self.class_stack: List[ast.ClassDef] = []
        self.func_stack: List[ast.AST] = []
        # (lock_name, with_node) chain of lock-guarded With statements the
        # walk is currently inside (cleared across nested def/lambda: their
        # bodies do not run under the enclosing lock)
        self.lock_stack: List[Tuple[str, ast.With]] = []
        self.findings: List[Finding] = []
        self._allow: Dict[int, set] = {}
        self._scan_pragmas()

    def _scan_pragmas(self) -> None:
        for i, line in enumerate(self.lines, start=1):
            m = _ALLOW_RE.search(line)
            if m:
                rules = {r.strip() for r in m.group(1).split(",") if r.strip()}
                if m.group(2).strip():
                    self._allow[i] = rules
                else:
                    self._allow.setdefault(i, set()).add("__bare_allow__")

    # -- suppression queries ------------------------------------------------
    def allowed(self, rule_id: str, line: int) -> bool:
        """Pragma on the line itself, or anywhere in the contiguous comment
        block directly above it (multi-line justifications are the norm)."""
        if rule_id in self._allow.get(line, ()):
            return True
        ln = line - 1
        while 1 <= ln <= len(self.lines):
            stripped = self.lines[ln - 1].strip()
            if not stripped.startswith("#"):
                break
            if rule_id in self._allow.get(ln, ()):
                return True
            ln -= 1
        return False

    def bare_allow_lines(self) -> Iterable[int]:
        for ln, rules in self._allow.items():
            if "__bare_allow__" in rules and len(rules) == 1:
                yield ln

    def reasoned_comment(self, line: int) -> bool:
        """The line carries a comment with an actual WRITTEN reason — the
        repo's justification idiom (``# noqa: BLE001 — reason`` or
        ``continue  # peer gone; next tick retries``).  Bare tool markers
        (``# noqa``, ``# pragma: no cover``, ``# type: ignore``, ``# TODO``)
        are instructions to tools, not reasons, and do not qualify; nor
        does anything shorter than three words — a reason is prose."""
        if not (1 <= line <= len(self.lines)):
            return False
        s = self.lines[line - 1]
        if "#" not in s:
            return False
        comment = s.split("#", 1)[1].strip()
        # strip ONE leading noqa marker (with optional codes + dash), then
        # judge what remains; any other leading tool marker disqualifies
        comment = re.sub(r"^noqa(:\s*[A-Z0-9, ]+)?\s*", "", comment)
        comment = comment.lstrip("—-: ").strip()
        if not comment or _TOOL_MARKER_RE.match(comment):
            return False
        return len(re.findall(r"[A-Za-z][\w'-]*", comment)) >= 3

    def class_name(self) -> str:
        return ".".join(c.name for c in self.class_stack) or "<module>"

    # -- emission -----------------------------------------------------------
    def emit(self, rule: "Rule", node_or_line, message: str,
             hint: str = "") -> None:
        line = getattr(node_or_line, "lineno", node_or_line)
        if self.allowed(rule.id, line):
            return
        self.findings.append(Finding(
            rule=rule.id, severity=rule.severity, path=self.rel,
            line=int(line), message=message, hint=hint or rule.hint))


class Rule:
    """Plugin base.  Subclasses define ``visit_<NodeType>`` handlers (and
    optionally ``leave_<NodeType>``, ``begin_file``, ``end_file``,
    ``finalize``) plus id/severity/doc metadata for ``--explain``."""

    id: str = ""
    severity: str = Severity.MEDIUM
    summary: str = ""
    doc: str = ""          # long-form --explain text
    hint: str = ""

    def begin_file(self, ctx: FileContext) -> None:
        pass

    def end_file(self, ctx: FileContext) -> None:
        pass

    def finalize(self, engine: "Engine") -> List[Finding]:
        return []


# helper-name heuristic: a With item guards a lock if its terminal
# name mentions one of these (the repo's naming is consistent: _lock,
# _*_lock, _cv, _dispatch_cv, _REGISTRY_LOCK, ...)
_LOCKISH = ("lock", "_cv", "mutex", "cond")


def lockish_name(expr: ast.AST) -> Optional[str]:
    """The lock's short name when a ``with`` item lexically looks like a
    lock acquisition (Name/Attribute whose terminal identifier mentions
    lock/cv/mutex/cond), else None."""
    node = expr
    if isinstance(node, ast.Call) and not node.args and not node.keywords:
        # e.g. "with self._lock_for(key):" stays un-matched; a bare
        # zero-arg call is not a lock acquisition we can name statically
        return None
    if isinstance(node, ast.Attribute):
        name = node.attr
    elif isinstance(node, ast.Name):
        name = node.id
    else:
        return None
    low = name.lower()
    if any(tok in low for tok in _LOCKISH):
        return name
    return None


class Engine:
    """Walks each file once; dispatches node events to subscribed rules."""

    def __init__(self, root: str, rules: Sequence[Rule],
                 partial: bool = False):
        self.root = root
        self.rules = list(rules)
        # partial = not the whole ray_tpu tree (--diff / explicit paths):
        # rules needing whole-repo knowledge (recording liveness) skip
        # their cross-file verdicts instead of emitting false drift
        self.partial = partial
        self.files_seen: List[str] = []
        self.parse_errors: List[Finding] = []
        # retained per-file contexts so finalize()-time findings (repo
        # rules) can still honor in-source allow() pragmas
        self._contexts: Dict[str, FileContext] = {}
        # dispatch tables: node-type name -> [(rule, visit_fn, leave_fn)]
        self._dispatch: Dict[str, List[tuple]] = {}
        for rule in self.rules:
            for attr in dir(rule):
                if attr.startswith("visit_"):
                    t = attr[len("visit_"):]
                    self._dispatch.setdefault(t, []).append(
                        (rule, getattr(rule, attr),
                         getattr(rule, "leave_" + t, None)))
                elif attr.startswith("leave_"):
                    t = attr[len("leave_"):]
                    if not hasattr(rule, "visit_" + t):
                        self._dispatch.setdefault(t, []).append(
                            (rule, None, getattr(rule, attr)))

    # -- file walk ----------------------------------------------------------
    def run_file(self, path: str) -> List[Finding]:
        with open(path, "r", encoding="utf-8") as f:
            source = f.read()
        try:
            tree = ast.parse(source, filename=path)
        except SyntaxError as e:
            rel = os.path.relpath(path, self.root).replace(os.sep, "/")
            # the file WAS seen — callers gate on files_seen, and an
            # unparseable file must surface its finding, not read as
            # "nothing to lint"
            self.files_seen.append(rel)
            f = Finding(rule="parse-error", severity=Severity.HIGH, path=rel,
                        line=e.lineno or 0, message=f"syntax error: {e.msg}")
            self.parse_errors.append(f)
            return [f]
        ctx = FileContext(self.root, path, source, tree)
        self.files_seen.append(ctx.rel)
        for rule in self.rules:
            rule.begin_file(ctx)
        self._walk(tree, ctx)
        for rule in self.rules:
            rule.end_file(ctx)
        # retain the ctx for finalize-time pragma checks, but drop the AST
        # and raw source first — Engine.allowed() reads only lines+pragmas,
        # and holding 199 parsed trees for the run's lifetime is dead weight
        ctx.tree = None
        ctx.source = ""
        self._contexts[ctx.rel] = ctx
        # a bare allow() pragma (no reason) is itself a finding: the whole
        # point of the pragma is the written justification
        for ln in ctx.bare_allow_lines():
            ctx.findings.append(Finding(
                rule="bare-allow", severity=Severity.MEDIUM, path=ctx.rel,
                line=ln, message="graftlint allow() pragma without a reason",
                hint="write the justification after an em-dash: "
                     "# graftlint: allow(rule) — why this is safe"))
        return ctx.findings

    def _walk(self, node: ast.AST, ctx: FileContext) -> None:
        tname = type(node).__name__
        subs = self._dispatch.get(tname, ())
        for rule, visit, _ in subs:
            if visit is not None:
                visit(node, ctx)

        is_class = isinstance(node, ast.ClassDef)
        is_func = isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                                    ast.Lambda))
        saved_locks: Optional[List] = None
        if is_class:
            ctx.class_stack.append(node)
        if is_func:
            ctx.func_stack.append(node)
            # a nested def/lambda body does NOT run under the enclosing
            # lock — it runs whenever it is later called
            saved_locks = ctx.lock_stack
            ctx.lock_stack = []

        pushed = 0
        if isinstance(node, ast.With):
            for item in node.items:
                name = lockish_name(item.context_expr)
                if name is not None:
                    ctx.lock_stack.append((name, node))
                    pushed += 1

        for child in ast.iter_child_nodes(node):
            self._walk(child, ctx)

        for _ in range(pushed):
            ctx.lock_stack.pop()
        if is_func:
            ctx.func_stack.pop()
            ctx.lock_stack = saved_locks
        if is_class:
            ctx.class_stack.pop()

        for rule, _, leave in subs:
            if leave is not None:
                leave(node, ctx)

    def allowed(self, rule_id: str, rel: str, line: int) -> bool:
        """Finalize-time pragma check: repo-level rules route their
        Findings through this so in-source allow() pragmas keep working
        for findings emitted after the per-file walk."""
        ctx = self._contexts.get(rel)
        return ctx.allowed(rule_id, line) if ctx is not None else False

    # -- entry points --------------------------------------------------------
    def run(self, paths: Iterable[str]) -> List[Finding]:
        findings: List[Finding] = []
        # dedup: a file passed directly AND via its directory must be
        # walked (and its findings reported) exactly once
        for path in sorted(dict.fromkeys(self._expand(paths))):
            findings.extend(self.run_file(path))
        for rule in self.rules:
            findings.extend(f for f in rule.finalize(self)
                            if not self.allowed(f.rule, f.path, f.line))
        findings.sort(key=lambda f: (Severity.ORDER.get(f.severity, 9),
                                     f.path, f.line, f.rule))
        return findings

    def _expand(self, paths: Iterable[str]) -> Iterable[str]:
        for p in paths:
            if os.path.isdir(p):
                for dirpath, dirnames, filenames in os.walk(p):
                    dirnames[:] = [d for d in dirnames
                                   if d != "__pycache__"
                                   and not d.startswith(".")]
                    for fn in filenames:
                        if fn.endswith(".py"):
                            yield os.path.join(dirpath, fn)
            elif p.endswith(".py"):
                yield p


def all_rules() -> List[Rule]:
    """The shipped rule set, one instance each (fresh state per engine)."""
    from ray_tpu._private.analysis.rules_concurrency import (
        BlockingUnderLock, LockOrderCycle, ThreadHygiene)
    from ray_tpu._private.analysis.rules_hygiene import SwallowedException
    from ray_tpu._private.analysis.rules_registry import (
        ConfigKnobDrift, MetricRegistryDrift)

    return [BlockingUnderLock(), LockOrderCycle(), SwallowedException(),
            MetricRegistryDrift(), ConfigKnobDrift(), ThreadHygiene()]


def run_analysis(root: str, paths: Optional[Sequence[str]] = None,
                 rules: Optional[Sequence[Rule]] = None,
                 partial: bool = False) -> Tuple[List[Finding], "Engine"]:
    """THE entry-point recipe (lint CLI and the gate both route
    here so they can never drift apart): ``root`` anchors repo-relative
    paths; ``paths`` defaults to ``<root>/ray_tpu``.  Returns (findings,
    engine) — the engine carries ``files_seen`` for reporting."""
    eng = Engine(root, rules if rules is not None else all_rules(),
                 partial=partial)
    findings = eng.run(paths or [os.path.join(root, "ray_tpu")])
    return findings, eng
