"""Resolve-before-cache-key rule.

A compiled-program cache key built from an unresolved "auto" sentinel —
or from config that an ``os.environ`` read / ``resolve_*()`` call is
about to change — aliases programs across backends: two processes (or
two phases of one process) hit the same key for different programs. The
PR 4 incident class.

Two parts, one rule (``resolve-before-cache-key``):

1. **The anchored pins**: ``Booster.predict_plan`` is THE predictor-key
   site, and what decides its key (the predict dtype lane, the tuned
   bucket ladder) must be resolved before the key tuple is assembled.
2. **The general analysis**: in ANY package function, an ``os.environ``
   read or a ``resolve_*()`` call *lexically after* the function's first
   cache-key construction (an assignment to a ``*cache_key*`` name, a
   subscript/``get``/``setdefault`` on a ``*_CACHE`` global, or a
   ``_cached_program(...)`` call) is flagged: whatever that read
   resolves was not part of the key just built. Deliberate
   reads-that-don't-feed-keys carry an inline suppression with a
   justification.
"""

from __future__ import annotations

import ast
import re
from typing import Iterator, Optional

from ..core import (Checker, CheckerRotError, Finding, Module, Repo,
                    call_name, first_lineno, register)

_CACHE_NAME_RE = re.compile(r".*_CACHE$")
_BOOSTER = "mmlspark_tpu/models/gbdt/booster.py"


def _is_cache_key_construction(node: ast.AST) -> bool:
    if isinstance(node, ast.Assign):
        if any(isinstance(t, ast.Name) and "cache_key" in t.id
               for t in node.targets):
            return True
    if isinstance(node, ast.Subscript) and \
            isinstance(node.value, ast.Name) and \
            _CACHE_NAME_RE.match(node.value.id):
        return True
    if isinstance(node, ast.Call):
        qual, name = call_name(node)
        if name == "_cached_program":
            return True
        if name in ("get", "setdefault", "pop") and qual and \
                _CACHE_NAME_RE.match(qual.split(".")[-1]):
            return True
    return False


def _is_env_read(node: ast.AST) -> bool:
    return (isinstance(node, ast.Attribute) and node.attr == "environ"
            and isinstance(node.value, ast.Name)
            and node.value.id == "os")


def _is_resolver_call(node: ast.AST) -> Optional[str]:
    if isinstance(node, ast.Call):
        _qual, name = call_name(node)
        if name and name.startswith("resolve_"):
            return name
    return None


class ResolveBeforeCacheKey(Checker):
    rule = "resolve-before-cache-key"
    description = "os.environ reads and resolve_*() calls must precede " \
                  "any compiled-program cache-key construction in the " \
                  "same function"

    def check(self, repo: Repo) -> Iterator[Finding]:
        yield from self._anchored_pin(repo)
        for mod in repo.package():
            for fn in ast.walk(mod.tree):
                if not isinstance(fn, (ast.FunctionDef,
                                       ast.AsyncFunctionDef)):
                    continue
                yield from self._scan_fn(mod, fn)

    def _scan_fn(self, mod: Module, fn: ast.AST) -> Iterator[Finding]:
        cache_ln = first_lineno(fn, _is_cache_key_construction)
        if cache_ln is None:
            return
        # nested defs establish their own ordering scope: a closure that
        # reads env lazily AFTER the outer key was built is exactly the
        # aliasing hazard, so nested bodies are NOT excluded here
        for node in ast.walk(fn):
            ln = getattr(node, "lineno", None)
            if ln is None or ln <= cache_ln:
                continue
            if _is_env_read(node):
                yield self.finding(
                    mod, ln,
                    f"os.environ read at line {ln} after cache-key "
                    f"construction at line {cache_ln} in {fn.name}() — "
                    "resolve before the key is built (or the key aliases "
                    "across configs)")
            else:
                resolver = _is_resolver_call(node)
                if resolver:
                    yield self.finding(
                        mod, ln,
                        f"{resolver}() at line {ln} after cache-key "
                        f"construction at line {cache_ln} in {fn.name}()"
                        " — resolve before the key is built")

    def _anchored_pin(self, repo: Repo) -> Iterator[Finding]:
        booster = repo.module(_BOOSTER)
        if booster is None:
            raise CheckerRotError("models/gbdt/booster.py moved")
        # predict_plan is THE predictor-key site (booster hot path + bundle
        # builder both call it), and the dtype lane must be resolved
        # through the quantize funnel before the key tuple is assembled.
        # Note the key here is a plain ``key = (...)`` assignment —
        # _is_cache_key_construction only matches ``*cache_key*`` names /
        # _CACHE subscripts, so the pin carries its own predicate.
        pp = next((n for n in ast.walk(booster.tree)
                   if isinstance(n, ast.FunctionDef)
                   and n.name == "predict_plan"), None)
        if pp is None:
            raise CheckerRotError("predict_plan vanished from booster.py")

        def is_dtype_resolver(n: ast.AST) -> bool:
            return (isinstance(n, ast.Call)
                    and call_name(n)[1] == "resolve_predict_dtype")

        def is_key_assign(n: ast.AST) -> bool:
            return (isinstance(n, ast.Assign)
                    and any(isinstance(t, ast.Name) and t.id == "key"
                            for t in n.targets))

        pp_key_ln = first_lineno(pp, is_key_assign)
        pp_resolver_ln = first_lineno(pp, is_dtype_resolver)
        if pp_key_ln is None:
            raise CheckerRotError(
                "predict_plan no longer assembles a key tuple — "
                "anchored pin matches nothing")
        if pp_resolver_ln is None:
            yield self.finding(
                booster, pp.lineno,
                "predict_plan no longer resolves the predict dtype "
                "(resolve_predict_dtype call missing) — an env-dependent "
                "lane outside the key aliases quantized and f32 programs")
        elif pp_resolver_ln >= pp_key_ln:
            yield self.finding(
                booster, pp_resolver_ln,
                f"resolve_predict_dtype (line {pp_resolver_ln}) must run "
                f"before predict_plan's key assembly (line {pp_key_ln})")

        # the tuned bucket ladder (PR 19) decides predict_plan's n_pad,
        # which joins the key: resolved after the key it would alias tuned
        # and pow2 programs under one entry
        def is_ladder_resolver(n: ast.AST) -> bool:
            return (isinstance(n, ast.Call)
                    and call_name(n)[1] == "resolve_bucket_ladder")

        pl_ln = first_lineno(pp, is_ladder_resolver)
        if pl_ln is None:
            yield self.finding(
                booster, pp.lineno,
                "predict_plan no longer resolves the tuned bucket ladder "
                "(tuning.resolve_bucket_ladder call missing) — n_pad "
                "joins the key, so an unresolved ladder aliases tuned "
                "and pow2 programs")
        elif pl_ln >= pp_key_ln:
            yield self.finding(
                booster, pl_ln,
                f"tuning.resolve_bucket_ladder (line {pl_ln}) must run "
                f"before predict_plan's key assembly (line {pp_key_ln})")


register(ResolveBeforeCacheKey())
