"""Tier-1 bridge for graftlint (thin runner — the rules moved out).

The 12 ad-hoc AST guards that used to live here are now declarative
checkers in ``tools/graftlint/`` (one rule each; see
``docs/static_analysis.md`` for the old-guard -> rule mapping). This
shim runs the full pass as one parameterized test per rule, so a
violation fails tier-1 with the exact rule id and file:line — identical
coverage, one engine, one parse per file.

The guards that stay here: the *runtime* complement of
``obs-import-cycle`` (a fresh interpreter importing the telemetry layer
standalone, proving the static rule's conclusion — no jax, no framework —
against the real import system), and the layering of the histogram choice:
tree growth and the kernel module do not ask the tuning plane anything.
"""

import ast
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from tools.graftlint import core  # noqa: E402

core.load_checkers()


@pytest.fixture(scope="module")
def repo():
    """One parsed tree shared by every per-rule test."""
    return core.Repo(ROOT)


@pytest.mark.parametrize("rule", sorted(core.REGISTRY))
def test_rule_clean(repo, rule):
    active, _suppressed = core.run(repo, rules=[rule])
    assert not active, "\n".join(
        f"{f.location()}: {f.rule}: {f.message}" for f in active)


def test_observability_imports_standalone():
    """A fresh interpreter can import the telemetry layer on its own —
    the runtime proof of the obs-import-cycle rule (and it keeps the
    import cheap: no jax, no framework)."""
    proc = subprocess.run(
        [sys.executable, "-c",
         "import sys\n"
         "import mmlspark_tpu.observability as o\n"
         "assert 'jax' not in sys.modules, 'observability imported jax'\n"
         "o.counter('lint_total').inc()\n"
         "print(o.get_registry().render_prometheus())"],
        capture_output=True, text=True, timeout=120, cwd=ROOT)
    assert proc.returncode == 0, proc.stderr
    assert "lint_total 1" in proc.stdout


@pytest.mark.parametrize("module", [
    "mmlspark_tpu/models/gbdt/growth.py", "mmlspark_tpu/ops/histogram.py"])
def test_histogram_layers_do_not_import_tuning(module):
    """One histogram design, one engine choice (``resolve_engine``: the
    environment and the backend): neither layer imports ``mmlspark_tpu
    .tuning``, at module level or inside a function."""
    with open(os.path.join(ROOT, module)) as f:
        tree = ast.parse(f.read())
    package = module.split("/")[:-1]                  # for relative imports
    imported = []
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            imported += [a.name for a in node.names]
        elif isinstance(node, ast.ImportFrom):
            base = package[:len(package) - node.level + 1] if node.level \
                else []
            base = ".".join(base + ([node.module] if node.module else []))
            imported += [base] + [f"{base}.{a.name}" for a in node.names]
    assert not [m for m in imported
                if m == "mmlspark_tpu.tuning"
                or m.startswith("mmlspark_tpu.tuning.")], imported


if __name__ == "__main__":
    sys.exit(pytest.main([__file__, "-q"]))
