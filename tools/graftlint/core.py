"""graftlint core: one parse per file, pluggable checkers, suppressions.

The framework's load-bearing invariants (one textual-output funnel,
shard_map only via ``parallel/compat.py``, "auto" sentinels resolved
before compiled-program cache keys, no host syncs in hot loops,
heartbeats closed on all paths, ...) started life as ad-hoc AST walks in
``tests/test_lint.py``. graftlint turns them into a real subsystem:

* :class:`Repo` walks the tree once and parses each file once; every
  checker shares the same :class:`Module` objects (AST + source +
  suppression map).
* :class:`Checker` subclasses declare one rule each (``rule`` id +
  ``description``) and yield :class:`Finding`\\ s from ``check(repo)``.
* ``# graftlint: disable=<rule>[,<rule>...]`` on the flagged line
  suppresses that line; ``# graftlint: disable-file=<rule>`` anywhere in
  a file suppresses the whole file. Suppressed findings are retained
  (visible under ``--show-suppressed``) but don't fail the run.
* A checker whose anchor pattern vanished (the code it guards was
  renamed away) raises :class:`CheckerRotError`, which the runner turns
  into a failing finding — a lint that silently matches nothing is
  itself a defect (every migrated test_lint.py guard kept its anti-rot
  assertion this way).

``python -m tools.graftlint`` runs everything (exit 1 on unsuppressed
findings); ``tests/test_lint.py`` bridges the same pass into tier-1 as
one parameterized test per rule.
"""

from __future__ import annotations

import ast
import io
import json
import os
import re
import sys
import tokenize
from dataclasses import dataclass, field
from typing import Dict, Iterable, Iterator, List, Optional, Sequence, Tuple

__all__ = [
    "Finding", "Module", "Repo", "Checker", "CheckerRotError",
    "register", "REGISTRY", "run", "render_human", "render_json",
    "call_name", "functions_containing", "loop_body_nodes", "first_lineno",
]

_SUPPRESS_RE = re.compile(
    r"graftlint:\s*(disable|disable-file)\s*=\s*([A-Za-z0-9_\-, ]+)")

#: package dir every rule ultimately protects (relative to repo root)
PACKAGE = "mmlspark_tpu"

#: default scan set: the package, its tests/tools, and the root-level
#: entrypoints (the shard_map funnel historically guarded all of these)
DEFAULT_SCAN = ("mmlspark_tpu", "tests", "tools",
                "__graft_entry__.py", "bench.py", "chip_smoke.py")


@dataclass
class Finding:
    """One rule violation at one source location."""

    rule: str
    path: str           # repo-relative, '/'-separated
    line: int
    message: str
    suppressed: bool = False

    def location(self) -> str:
        return f"{self.path}:{self.line}"

    def to_dict(self) -> Dict[str, object]:
        return {"rule": self.rule, "path": self.path, "line": self.line,
                "message": self.message, "suppressed": self.suppressed}


class CheckerRotError(Exception):
    """The pattern a checker anchors on no longer exists — the guard
    would silently pass forever. Raised by checkers, converted by the
    runner into a finding against the checker itself."""


class Module:
    """One parsed source file shared by every checker."""

    def __init__(self, root: str, path: str):
        self.path = path
        self.rel = os.path.relpath(path, root).replace(os.sep, "/")
        with open(path, encoding="utf-8") as f:
            self.source = f.read()
        self.tree = ast.parse(self.source, filename=path)
        #: line -> set of rule ids disabled on that line
        self.line_suppressions: Dict[int, set] = {}
        #: rule ids disabled for the whole file
        self.file_suppressions: set = set()
        self._scan_suppressions()
        self._owner: Optional[Dict[ast.AST, Optional[str]]] = None

    def _scan_suppressions(self) -> None:
        try:
            tokens = tokenize.generate_tokens(
                io.StringIO(self.source).readline)
            for tok in tokens:
                if tok.type != tokenize.COMMENT:
                    continue
                m = _SUPPRESS_RE.search(tok.string)
                if not m:
                    continue
                rules = {r.strip() for r in m.group(2).split(",") if r.strip()}
                if m.group(1) == "disable-file":
                    self.file_suppressions |= rules
                else:
                    self.line_suppressions.setdefault(
                        tok.start[0], set()).update(rules)
        except tokenize.TokenError:
            pass  # a file that parses but won't tokenize keeps no overrides

    def suppressed(self, rule: str, line: int) -> bool:
        return (rule in self.file_suppressions
                or rule in self.line_suppressions.get(line, ()))

    def owner_map(self) -> Dict[ast.AST, Optional[str]]:
        """node -> innermost enclosing function name (cached)."""
        if self._owner is None:
            self._owner = functions_containing(self.tree)
        return self._owner


class Repo:
    """The scanned tree: every ``.py`` under the scan roots, parsed once."""

    def __init__(self, root: str, scan: Sequence[str] = DEFAULT_SCAN):
        self.root = os.path.abspath(root)
        self.scan = tuple(scan)
        self._modules: Optional[List[Module]] = None
        self._by_rel: Dict[str, Module] = {}
        self.parse_errors: List[Finding] = []

    def modules(self) -> List[Module]:
        if self._modules is None:
            self._modules = []
            for rel in self.scan:
                top = os.path.join(self.root, rel)
                if os.path.isfile(top) and top.endswith(".py"):
                    self._load(top)
                elif os.path.isdir(top):
                    for dirpath, dirnames, filenames in os.walk(top):
                        dirnames[:] = sorted(
                            d for d in dirnames
                            if d != "__pycache__" and not d.startswith("."))
                        for fn in sorted(filenames):
                            if fn.endswith(".py"):
                                self._load(os.path.join(dirpath, fn))
        return self._modules

    def _load(self, path: str) -> None:
        rel = os.path.relpath(path, self.root).replace(os.sep, "/")
        try:
            mod = Module(self.root, path)
        except (SyntaxError, UnicodeDecodeError, OSError) as e:
            self.parse_errors.append(Finding(
                "parse-error", rel, getattr(e, "lineno", 0) or 0, str(e)))
            return
        assert self._modules is not None
        self._modules.append(mod)
        self._by_rel[mod.rel] = mod

    def module(self, rel: str) -> Optional[Module]:
        self.modules()
        return self._by_rel.get(rel.replace(os.sep, "/"))

    def under(self, *prefixes: str) -> List[Module]:
        """Modules whose repo-relative path starts with any prefix
        (a directory prefix matches only whole path components)."""
        out = []
        for mod in self.modules():
            for p in prefixes:
                p = p.replace(os.sep, "/")
                if mod.rel == p or mod.rel.startswith(p.rstrip("/") + "/"):
                    out.append(mod)
                    break
        return out

    def package(self) -> List[Module]:
        return self.under(PACKAGE)


class Checker:
    """One rule. Subclasses set ``rule`` + ``description`` and implement
    ``check(repo)`` yielding findings (suppression is applied by the
    runner, not the checker)."""

    rule: str = ""
    description: str = ""

    def check(self, repo: Repo) -> Iterator[Finding]:
        raise NotImplementedError

    def finding(self, module_or_rel, line: int, message: str) -> Finding:
        rel = module_or_rel.rel if isinstance(module_or_rel, Module) \
            else str(module_or_rel)
        return Finding(self.rule, rel, line, message)


#: rule id -> checker instance (populated by the checks package import)
REGISTRY: Dict[str, Checker] = {}


def register(checker: Checker) -> Checker:
    """Add one checker instance to the registry (import-time)."""
    if not checker.rule:
        raise ValueError("checker has no rule id")
    if checker.rule in REGISTRY:
        raise ValueError(f"duplicate rule id {checker.rule!r}")
    REGISTRY[checker.rule] = checker
    return checker


def load_checkers() -> Dict[str, Checker]:
    """Import the bundled checker modules (idempotent) and return the
    registry. Third-party checkers can call :func:`register` directly."""
    from . import checks  # noqa: F401 — import populates REGISTRY
    return REGISTRY


def run(repo: Repo, rules: Optional[Sequence[str]] = None
        ) -> Tuple[List[Finding], List[Finding]]:
    """Run checkers over ``repo``; returns (active, suppressed) findings,
    both sorted. Unknown rule ids raise ValueError. Files that failed to
    parse surface as active ``parse-error`` findings on every run."""
    load_checkers()
    if rules is None:
        selected = list(REGISTRY.values())
    else:
        unknown = [r for r in rules if r not in REGISTRY]
        if unknown:
            raise ValueError(
                f"unknown rule(s) {unknown}; known: {sorted(REGISTRY)}")
        # a repeated --rule must not run (and report) a checker twice
        selected = [REGISTRY[r] for r in dict.fromkeys(rules)]
    repo.modules()
    active: List[Finding] = list(repo.parse_errors)
    suppressed: List[Finding] = []
    for checker in selected:
        # drain the generator finding-by-finding: checkers yield real
        # violations first and raise their rot check last — a rot error
        # must ADD a finding, not mask the violations already yielded
        found: List[Finding] = []
        try:
            for f in checker.check(repo):
                found.append(f)
        except CheckerRotError as e:
            found.append(Finding(checker.rule, "<graftlint>", 0,
                                 f"lint-rot: {e}"))
        for f in found:
            mod = repo.module(f.path)
            if mod is not None and mod.suppressed(f.rule, f.line):
                f.suppressed = True
                suppressed.append(f)
            else:
                active.append(f)
    key = lambda f: (f.path, f.line, f.rule)  # noqa: E731
    return sorted(active, key=key), sorted(suppressed, key=key)


def _ran(rules: Optional[Sequence[str]]) -> List[str]:
    """The rule ids a run actually executed (None = the full registry).
    Renderers report these, not the whole catalogue — a ``--rule``-scoped
    CI log must not read as a clean full pass."""
    return sorted(REGISTRY) if rules is None else sorted(set(rules))


def render_human(active: List[Finding], suppressed: List[Finding],
                 show_suppressed: bool = False,
                 rules: Optional[Sequence[str]] = None) -> str:
    lines = [f"{f.location()}: {f.rule}: {f.message}" for f in active]
    if show_suppressed:
        lines += [f"{f.location()}: {f.rule}: [suppressed] {f.message}"
                  for f in suppressed]
    n = len(active)
    ran = _ran(rules)
    scope = (f"{len(ran)} rules" if len(ran) == len(REGISTRY)
             else f"{len(ran)} of {len(REGISTRY)} rules")
    lines.append(f"graftlint: {n} finding{'s' if n != 1 else ''} "
                 f"({len(suppressed)} suppressed, {scope})")
    return "\n".join(lines)


def render_json(active: List[Finding], suppressed: List[Finding],
                rules: Optional[Sequence[str]] = None) -> str:
    return json.dumps({
        "findings": [f.to_dict() for f in active],
        "suppressed": [f.to_dict() for f in suppressed],
        "rules": {r: REGISTRY[r].description for r in _ran(rules)},
    }, indent=2, sort_keys=True)


# ---------------------------------------------------------------------------
# Shared AST helpers (the walking test_lint.py used to copy-paste per guard)
# ---------------------------------------------------------------------------


def call_name(call: ast.Call) -> Tuple[Optional[str], Optional[str]]:
    """(qualifier, name) of a call: ``np.asarray(x)`` -> ("np",
    "asarray"), ``float(x)`` -> (None, "float"), anything unnamed ->
    (None, None). The qualifier is the dotted prefix when every link is
    a plain Name/Attribute chain (``jax.tree_util.tree_map`` ->
    "jax.tree_util")."""
    fn = call.func
    if isinstance(fn, ast.Name):
        return None, fn.id
    if isinstance(fn, ast.Attribute):
        parts: List[str] = []
        node = fn.value
        while isinstance(node, ast.Attribute):
            parts.append(node.attr)
            node = node.value
        if isinstance(node, ast.Name):
            parts.append(node.id)
            return ".".join(reversed(parts)), fn.attr
        return None, fn.attr
    return None, None


def functions_containing(tree: ast.AST) -> Dict[ast.AST, Optional[str]]:
    """Map every AST node to its innermost enclosing function name."""
    owner: Dict[ast.AST, Optional[str]] = {tree: None}

    def walk(node: ast.AST, fn_name: Optional[str]) -> None:
        for child in ast.iter_child_nodes(node):
            name = fn_name
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                name = child.name
            owner[child] = name
            walk(child, name)

    walk(tree, None)
    return owner


def loop_body_nodes(loop: ast.AST) -> Iterator[ast.AST]:
    """Nodes inside a For/While body, excluding nested function/lambda
    bodies — helpers *defined* outside the loop and merely called inside
    it are the sanctioned pattern for deliberate host syncs."""
    stack = list(getattr(loop, "body", [])) + list(getattr(loop, "orelse", []))
    while stack:
        n = stack.pop()
        if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)):
            continue
        yield n
        stack.extend(ast.iter_child_nodes(n))


def first_lineno(fn_node: ast.AST, match) -> Optional[int]:
    """Smallest lineno inside ``fn_node`` for which ``match(node)``."""
    best: Optional[int] = None
    for node in ast.walk(fn_node):
        if match(node):
            ln = getattr(node, "lineno", None)
            if ln is not None and (best is None or ln < best):
                best = ln
    return best
