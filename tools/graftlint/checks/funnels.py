"""Funnel rules: "only module X may call Y".

Five load-bearing single-owner contracts, one declarative table. Each
entry names the API being funneled, the one place allowed to touch it,
and a matcher over shared ASTs — what used to be five copy-pasted AST
walks in ``tests/test_lint.py``:

* ``raw-output-funnel`` — ``observability/logging.py`` is the ONE
  textual-output path (JSON records + flight mirror + rate limit +
  trace ids); a bare ``print(`` / ``sys.stderr.write`` bypasses all of
  it.
* ``stdlib-getlogger`` — stdlib ``logging.getLogger`` creates a
  parallel unstructured stream the kill switch and collectors never see.
* ``response-funnel`` — every HTTP response under ``io/`` goes through
  ``serving.write_http_response`` (Content-Length + per-status counters
  + future response policy in one place).
* ``shard-map-funnel`` — ``parallel/compat.py`` is the one place the
  jax shard_map API skew is resolved; a bare ``jax.shard_map`` (or a
  direct experimental import) anywhere else reintroduces the version
  skew that cost 240 tier-1 tests.
* ``trace-header-literal`` — the W3C wire contract lives in
  ``observability/tracing.py`` (TRACEPARENT_HEADER / REQUEST_ID_HEADER);
  a string literal at any other call site can drift per hop and break
  cross-process stitching.
* ``deadline-header-literal`` — the ``X-Deadline-Ms`` wire contract
  lives in ``robustness/policy.py`` (DEADLINE_HEADER); a re-spelled
  literal at another hop silently breaks deadline propagation the same
  way a drifted trace header breaks stitching.
* ``retry-sleep-funnel`` — a bare ``time.sleep`` inside a loop under
  ``io/`` is an unjittered, deadline-blind retry (or a poll that should
  ride an Event); the sanctioned delays are ``robustness/policy.py``'s
  ``backoff`` / ``RetryPolicy.sleep_before``.
* ``tuning-store-funnel`` — the auto-tuner's decision store is read and
  written only by ``mmlspark_tpu/tuning/``; an ad-hoc ``load_store`` /
  ``save_store`` call (or a re-spelled ``tuning.json``) bypasses the
  format-version and fingerprint checks that make a stale store degrade
  loudly to static rules.
* ``placement-funnel`` — ``parallel/placement.py`` is THE device-placement
  layer (ROADMAP item 6): only it may call ``jax.device_put`` or construct
  ``NamedSharding``/``PartitionSpec``/``SingleDeviceSharding``
  (``parallel/compat.py`` allowlisted). An ad-hoc placement call site
  re-opens the per-model-family placement divergence the funnel closed,
  and its decision is invisible to the flight recorder.
* ``bundle-io-funnel`` — ``mmlspark_tpu/bundles/`` is the one door for
  ``jax.export`` (serializing/deserializing compiled executables): an
  ad-hoc deserialize site bypasses the bundle manifest's fingerprint,
  checksum and key-recomputation checks — exactly the wrong-numerics
  risk the bundle subsystem exists to make impossible.
"""

from __future__ import annotations

import ast
from dataclasses import dataclass
from typing import Callable, Iterator, List, Optional, Tuple

from ..core import (Checker, CheckerRotError, Finding, Module, Repo,
                    call_name, loop_body_nodes, register)

#: (line, detail) pairs a matcher reports for one module
Matches = Iterator[Tuple[int, str]]


def _match_raw_output(mod: Module) -> Matches:
    for node in ast.walk(mod.tree):
        if isinstance(node, ast.Call) and \
                isinstance(node.func, ast.Name) and node.func.id == "print":
            yield node.lineno, "print("
        elif (isinstance(node, ast.Attribute) and node.attr == "write"
              and isinstance(node.value, ast.Attribute)
              and node.value.attr in ("stderr", "stdout")
              and isinstance(node.value.value, ast.Name)
              and node.value.value.id == "sys"):
            yield node.lineno, f"sys.{node.value.attr}.write"


def _match_getlogger(mod: Module) -> Matches:
    for node in ast.walk(mod.tree):
        if isinstance(node, ast.Attribute) and node.attr == "getLogger":
            yield node.lineno, "logging.getLogger"


def _match_send_response(mod: Module) -> Matches:
    owner = mod.owner_map()
    for node in ast.walk(mod.tree):
        if (isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and node.func.attr == "send_response"):
            yield node.lineno, f"send_response in {owner.get(node)}()"


def _match_shard_map(mod: Module) -> Matches:
    for node in ast.walk(mod.tree):
        if (isinstance(node, ast.Attribute) and node.attr == "shard_map"
                and isinstance(node.value, ast.Name)
                and node.value.id == "jax"):
            yield node.lineno, "jax.shard_map"
        elif (isinstance(node, ast.ImportFrom) and node.module
              and node.module.startswith("jax.experimental.shard_map")):
            yield node.lineno, f"from {node.module} import"


_TRACE_HEADERS = frozenset({"traceparent", "x-request-id"})


def _match_trace_headers(mod: Module) -> Matches:
    for node in ast.walk(mod.tree):
        if isinstance(node, ast.Constant) and isinstance(node.value, str) \
                and node.value.strip().lower() in _TRACE_HEADERS:
            yield node.lineno, repr(node.value)


def _match_deadline_header(mod: Module) -> Matches:
    for node in ast.walk(mod.tree):
        if isinstance(node, ast.Constant) and isinstance(node.value, str) \
                and node.value.strip().lower() == "x-deadline-ms":
            yield node.lineno, repr(node.value)


_PLACEMENT_NAMES = frozenset(
    {"NamedSharding", "PartitionSpec", "SingleDeviceSharding"})


def _match_placement(mod: Module) -> Matches:
    """Raw jax placement surface: importing the sharding constructors
    (from jax.sharding OR re-exported through jax), importing the
    jax.sharding module wholesale (any constructor is then one attribute
    away), touching constructors via an attribute path ending in
    ``.sharding.<Name>``, or calling ``device_put`` as ``jax.device_put``/
    a bare import. Importing ``Mesh`` by name stays legal — mesh topology
    is :mod:`parallel.mesh`'s business, placement is not."""
    for node in ast.walk(mod.tree):
        if isinstance(node, ast.ImportFrom):
            if node.module in ("jax.sharding", "jax"):
                for alias in node.names:
                    if alias.name in _PLACEMENT_NAMES or (
                            node.module == "jax"
                            and alias.name in ("device_put", "sharding")):
                        yield (node.lineno,
                               f"from {node.module} import {alias.name}")
        elif isinstance(node, ast.Import):
            for alias in node.names:
                if alias.name == "jax.sharding":
                    yield node.lineno, "import jax.sharding"
        elif isinstance(node, ast.Attribute):
            if (node.attr == "device_put"
                    and isinstance(node.value, ast.Name)
                    and node.value.id == "jax"):
                yield node.lineno, "jax.device_put"
            elif (node.attr in _PLACEMENT_NAMES
                  and isinstance(node.value, ast.Attribute)
                  and node.value.attr == "sharding"):
                yield node.lineno, f"<module>.sharding.{node.attr}"


def _match_jax_export(mod: Module) -> Matches:
    """The jax.export surface: importing the module (``import jax.export``
    / ``from jax import export`` / ``from jax.export import ...``) or
    touching it as ``jax.export.<...>``. Any of these is one call away
    from deserializing an executable outside the bundle checks."""
    for node in ast.walk(mod.tree):
        if isinstance(node, ast.ImportFrom):
            if node.module == "jax":
                for alias in node.names:
                    if alias.name == "export":
                        yield node.lineno, "from jax import export"
            elif node.module and (node.module == "jax.export"
                                  or node.module.startswith("jax.export.")):
                yield node.lineno, f"from {node.module} import"
        elif isinstance(node, ast.Import):
            for alias in node.names:
                if alias.name == "jax.export":
                    yield node.lineno, "import jax.export"
        elif (isinstance(node, ast.Attribute) and node.attr == "export"
              and isinstance(node.value, ast.Name)
              and node.value.id == "jax"):
            yield node.lineno, "jax.export"


def _match_tuning_store(mod: Module) -> Matches:
    """The tuning store surface: calling its (de)serializers by name or
    re-spelling the store filename. Either is one step from reading
    decisions without the format-version + fingerprint checks that make
    a stale or foreign store degrade loudly instead of mis-tuning."""
    for node in ast.walk(mod.tree):
        if isinstance(node, ast.Call):
            _qual, name = call_name(node)
            if name in ("load_store", "save_store"):
                yield node.lineno, f"{name}("
        elif isinstance(node, ast.Constant) \
                and isinstance(node.value, str) \
                and node.value.strip().lower() == "tuning.json":
            yield node.lineno, repr(node.value)


def _match_loop_sleep(mod: Module) -> Matches:
    owner = mod.owner_map()
    for node in ast.walk(mod.tree):
        if not isinstance(node, (ast.For, ast.While)):
            continue
        for inner in loop_body_nodes(node):
            if isinstance(inner, ast.Call):
                qual, name = call_name(inner)
                if name == "sleep" and qual == "time":
                    yield inner.lineno, \
                        f"time.sleep in a loop in {owner.get(inner)}()"


@dataclass(frozen=True)
class FunnelRule:
    rule: str
    description: str
    #: repo-relative scan roots (dirs or files)
    scope: Tuple[str, ...]
    #: repo-relative paths where the API is legitimately used (the owner)
    allow: Tuple[str, ...]
    match: Callable[[Module], Matches]
    remedy: str
    #: (path, function) pairs that must exist in the scan, else the rule
    #: has rotted (the funnel owner was renamed away)
    anchors: Tuple[Tuple[str, Optional[str]], ...] = ()
    #: (path, function): matches inside this function of this file are
    #: the funnel itself, not violations
    allow_in_function: Tuple[Tuple[str, str], ...] = ()


FUNNEL_RULES: Tuple[FunnelRule, ...] = (
    FunnelRule(
        rule="raw-output-funnel",
        description="textual output only via observability.logging "
                    "(get_logger / console)",
        scope=("mmlspark_tpu",),
        allow=("mmlspark_tpu/observability/logging.py",),
        match=_match_raw_output,
        remedy="route through observability.logging.get_logger or "
               "console()",
        anchors=(("mmlspark_tpu/observability/logging.py", "console"),),
    ),
    FunnelRule(
        rule="stdlib-getlogger",
        description="no stdlib logging.getLogger outside the logging "
                    "funnel",
        scope=("mmlspark_tpu",),
        allow=("mmlspark_tpu/observability/logging.py",),
        match=_match_getlogger,
        remedy="use observability.logging.get_logger",
        anchors=(("mmlspark_tpu/observability/logging.py", "get_logger"),),
    ),
    FunnelRule(
        rule="response-funnel",
        description="io/ handlers emit responses only through "
                    "serving.write_http_response",
        scope=("mmlspark_tpu/io",),
        allow=(),
        match=_match_send_response,
        remedy="route through serving.write_http_response (the "
               "status-counter funnel)",
        anchors=(("mmlspark_tpu/io/serving.py", "write_http_response"),),
        allow_in_function=(("mmlspark_tpu/io/serving.py",
                            "write_http_response"),),
    ),
    FunnelRule(
        rule="shard-map-funnel",
        description="shard_map only via parallel/compat.py (the "
                    "version-skew funnel)",
        scope=("mmlspark_tpu", "tests", "tools", "__graft_entry__.py",
               "bench.py", "chip_smoke.py"),
        allow=("mmlspark_tpu/parallel/compat.py",),
        match=_match_shard_map,
        remedy="import shard_map from mmlspark_tpu.parallel.compat",
        anchors=(("mmlspark_tpu/parallel/compat.py", None),),
    ),
    FunnelRule(
        rule="trace-header-literal",
        description="trace header names only from observability.tracing "
                    "constants",
        scope=("mmlspark_tpu",),
        allow=("mmlspark_tpu/observability/tracing.py",),
        match=_match_trace_headers,
        remedy="use tracing.TRACEPARENT_HEADER / tracing.REQUEST_ID_HEADER",
        anchors=(("mmlspark_tpu/observability/tracing.py", None),),
    ),
    FunnelRule(
        rule="deadline-header-literal",
        description="the X-Deadline-Ms header name only from "
                    "robustness.policy.DEADLINE_HEADER",
        scope=("mmlspark_tpu",),
        allow=("mmlspark_tpu/robustness/policy.py",),
        match=_match_deadline_header,
        remedy="use robustness.policy.DEADLINE_HEADER (a re-spelled "
               "literal silently breaks deadline propagation at that hop)",
        anchors=(("mmlspark_tpu/robustness/policy.py", None),),
    ),
    FunnelRule(
        rule="placement-funnel",
        description="device placement (device_put / NamedSharding / "
                    "PartitionSpec / SingleDeviceSharding) only via "
                    "parallel/placement.py",
        scope=("mmlspark_tpu",),
        allow=("mmlspark_tpu/parallel/placement.py",
               "mmlspark_tpu/parallel/compat.py"),
        match=_match_placement,
        remedy="route through parallel.placement (pspec / sharding / "
               "shard_rows / device_put / put_on_device) so the decision "
               "is funneled and flight-logged",
        anchors=(("mmlspark_tpu/parallel/placement.py", "pspec"),),
    ),
    FunnelRule(
        rule="bundle-io-funnel",
        description="jax.export (executable serialization / "
                    "deserialization) only via mmlspark_tpu/bundles",
        scope=("mmlspark_tpu",),
        allow=("mmlspark_tpu/bundles/bundle.py",
               "mmlspark_tpu/bundles/__init__.py",
               "mmlspark_tpu/bundles/__main__.py"),
        match=_match_jax_export,
        remedy="route executable (de)serialization through "
               "mmlspark_tpu.bundles (build_bundle / prewarm) — an "
               "ad-hoc deserialize bypasses the manifest's fingerprint, "
               "checksum, and key-recomputation checks",
        anchors=(("mmlspark_tpu/bundles/bundle.py", "build_bundle"),),
    ),
    FunnelRule(
        rule="tuning-store-funnel",
        description="the tuning store (load_store / save_store / the "
                    "tuning.json filename) only via mmlspark_tpu/tuning",
        scope=("mmlspark_tpu",),
        allow=("mmlspark_tpu/tuning/store.py",
               "mmlspark_tpu/tuning/__init__.py"),
        match=_match_tuning_store,
        remedy="route through mmlspark_tpu.tuning (resolve_* / "
               "snapshot_payload / provenance) — an ad-hoc store reader "
               "bypasses the format-version and fingerprint checks that "
               "make a stale store degrade to static rules instead of "
               "mis-tuning the process",
        anchors=(("mmlspark_tpu/tuning/store.py", "save_store"),),
    ),
    FunnelRule(
        rule="retry-sleep-funnel",
        description="no bare time.sleep inside io/ loop bodies (retry "
                    "delays go through robustness.policy)",
        scope=("mmlspark_tpu/io",),
        allow=(),
        match=_match_loop_sleep,
        remedy="route retry delays through robustness.policy.backoff / "
               "RetryPolicy.sleep_before, and waits through an Event",
        anchors=(("mmlspark_tpu/robustness/policy.py", "backoff"),),
    ),
)


class FunnelChecker(Checker):
    """One table entry = one rule instance."""

    def __init__(self, spec: FunnelRule):
        self.spec = spec
        self.rule = spec.rule
        self.description = spec.description

    def _check_anchors(self, repo: Repo) -> None:
        for path, fn_name in self.spec.anchors:
            mod = repo.module(path)
            if mod is None:
                raise CheckerRotError(f"anchor file {path} is gone")
            if fn_name is not None and not any(
                    isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))
                    and n.name == fn_name for n in ast.walk(mod.tree)):
                raise CheckerRotError(
                    f"anchor function {fn_name}() vanished from {path}")

    def check(self, repo: Repo) -> Iterator[Finding]:
        self._check_anchors(repo)
        allowed_fns = dict(self.spec.allow_in_function)
        for mod in repo.under(*self.spec.scope):
            if mod.rel in self.spec.allow:
                continue
            for line, detail in self.spec.match(mod):
                if mod.rel in allowed_fns:
                    # the funnel function itself is the sanctioned site
                    node_fn = self._function_at(mod, line)
                    if node_fn == allowed_fns[mod.rel]:
                        continue
                yield self.finding(mod, line,
                                   f"{detail} — {self.spec.remedy}")

    @staticmethod
    def _function_at(mod: Module, line: int) -> Optional[str]:
        """Innermost function whose body spans ``line``."""
        best: Optional[Tuple[int, str]] = None
        for node in ast.walk(mod.tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                end = getattr(node, "end_lineno", None)
                if end is not None and node.lineno <= line <= end:
                    if best is None or node.lineno > best[0]:
                        best = (node.lineno, node.name)
        return best[1] if best else None


for _spec in FUNNEL_RULES:
    register(FunnelChecker(_spec))
