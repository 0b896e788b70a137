"""Persistent compile cache (utils/compile_cache) tests.

The warm-start proof runs in subprocesses — the whole point is COLD
processes skipping XLA recompilation — and asserts on deterministic
signals, not wall time: jax's own cache-hit monitoring events (surfaced
as ``persistent_compile_cache_hits_total`` by the utils/compile_cache
funnel) and the ``persistent_cache`` field on the flight recorder's
compile/program_build events.
"""

import json
import os
import subprocess
import sys

import pytest

from mmlspark_tpu.utils import compile_cache

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# one tiny fit + one predict, then dump (hit counter, compile events) as
# the last stdout line. The predict path AOT-compiles through
# _ObservedProgram, so a real `compile` flight event (with wall time and
# the persistent_cache field) is always present.
_CHILD = r"""
import json, os
import numpy as np
from mmlspark_tpu.models.gbdt.booster import train_booster
from mmlspark_tpu.models.gbdt.growth import GrowConfig
from mmlspark_tpu.observability import flight, metrics

rng = np.random.default_rng(0)
X = rng.normal(size=(512, 4)).astype(np.float32)
y = (X[:, 0] + 0.5 * X[:, 1] > 0).astype(np.float32)
b = train_booster(X, y, objective="binary", num_iterations=2,
                  cfg=GrowConfig(num_leaves=7), max_bin=15,
                  bin_sample_count=512, seed=0)
pred = b.predict(X[:64])
snap = metrics.get_registry().snapshot()
fam = snap.get("persistent_compile_cache_hits_total") or {}
hits = sum(s.get("value", 0) for s in fam.get("series", []))
evs = [e for e in flight.events()
       if e.get("kind") in ("compile", "program_build")]
print(json.dumps({
    "hits": hits,
    "compiles_total": sum(
        s.get("value", 0) for s in (snap.get("gbdt_compiles_total")
                                    or {}).get("series", [])),
    "n_events": len(evs),
    "persistent_fields": sorted({e.get("persistent_cache", "<absent>")
                                 for e in evs}),
    "pred0": float(np.asarray(pred).ravel()[0]),
}))
"""


def _run_child(cache_dir: str) -> dict:
    env = dict(os.environ)
    env.update({"JAX_PLATFORMS": "cpu",
                "JAX_COMPILATION_CACHE_DIR": cache_dir})
    proc = subprocess.run([sys.executable, "-c", _CHILD], env=env,
                          capture_output=True, text=True, timeout=420,
                          cwd=_REPO)
    assert proc.returncode == 0, proc.stderr[-4000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_warm_cache_dir_skips_recompilation(tmp_path):
    """Cold process #2 on a warm cache dir must FETCH, not compile: jax
    reports persistent-cache hits (counted by the funnel's monitoring
    listener), and every compile/program_build flight event carries the
    active cache dir so a flight dump shows which cache served it."""
    d = str(tmp_path / "xla_cache")
    first = _run_child(d)
    assert os.path.isdir(d) and os.listdir(d), \
        "first run left no persistent cache entries"
    assert first["n_events"] > 0
    assert first["persistent_fields"] == [d], first
    assert first["compiles_total"] >= 1          # the predict AOT compile

    second = _run_child(d)
    assert second["hits"] > 0, (
        "second process compiled from scratch despite a warm "
        f"JAX_COMPILATION_CACHE_DIR: {second}")
    assert second["persistent_fields"] == [d], second
    assert second["pred0"] == first["pred0"]     # cached programs: same math


@pytest.fixture
def fresh_funnel(monkeypatch):
    """A not-yet-initialized funnel whose jax.config writes are recorded
    instead of applied (the suite's own cache dir must survive the test)."""
    import jax
    monkeypatch.setattr(compile_cache, "_INITIALIZED", False)
    monkeypatch.setattr(compile_cache, "_DIR", None)
    monkeypatch.setattr(compile_cache, "_SOURCE", None)
    updates = {}
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: updates.__setitem__(k, v))
    # a second listener would double-count hits for the rest of the suite
    monkeypatch.setattr(jax.monitoring, "register_event_listener",
                        lambda fn: None)
    return updates


def _dir_updates(updates: dict) -> list:
    return [v for k, v in updates.items() if k.endswith("cache_dir")]


def test_env_var_wins_and_nothing_is_set_in_code(fresh_funnel):
    # conftest placed the suite's cache through the variable before jax
    # was imported, so jax already uses it; the funnel reports that value
    # and sets no dir, not even when a caller brings a fallback
    want = os.environ["JAX_COMPILATION_CACHE_DIR"]
    assert compile_cache.ensure("/some/bundle/xla_cache") == want
    assert compile_cache.cache_dir() == want
    assert compile_cache.cache_source() == "env:JAX_COMPILATION_CACHE_DIR"
    assert _dir_updates(fresh_funnel) == []
    assert sorted(fresh_funnel) == [
        "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes"]


def test_unset_uses_the_fixed_in_checkout_path(monkeypatch, fresh_funnel):
    # the path is part of what lets two processes share entries: one fixed
    # string per checkout, no temp name, pid or timestamp in it
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    assert compile_cache.ensure() == os.path.join(_REPO, ".jax_cache")
    assert compile_cache.cache_source() == "default"
    assert _dir_updates(fresh_funnel) == [os.path.join(_REPO, ".jax_cache")]
    # first call wins: a later fallback cannot move the cache mid-process
    assert compile_cache.ensure("/elsewhere") == \
        os.path.join(_REPO, ".jax_cache")


def test_installed_package_sets_no_directory(monkeypatch, fresh_funnel):
    # site-packages is no checkout (no pyproject.toml beside the package)
    # and often read-only: without the variable there is no persistent
    # cache, and the funnel says so instead of claiming a default
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    monkeypatch.setattr(compile_cache, "DEFAULT_DIR", None)
    assert compile_cache.ensure() is None
    assert compile_cache.cache_source() == "unset"
    assert _dir_updates(fresh_funnel) == []


def test_misses_are_counted_beside_hits():
    from mmlspark_tpu.observability import metrics

    def total(name):
        fam = metrics.get_registry().snapshot().get(name) or {}
        return sum(s["value"] for s in fam.get("series", []))

    before = {n: total(n) for n in compile_cache._EVENT_COUNTERS.values()}
    for event in compile_cache._EVENT_COUNTERS:
        compile_cache._on_event(event)
    compile_cache._on_event("/jax/compilation_cache/tasks_using_cache")
    assert {n: total(n) - before[n] for n in before} == {
        "persistent_compile_cache_hits_total": 1,
        "persistent_compile_cache_misses_total": 1}


def test_bundle_fallback_applies_only_without_the_env_var(monkeypatch,
                                                          fresh_funnel):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    assert compile_cache.ensure("/bundle/xla_cache") == "/bundle/xla_cache"
    assert compile_cache.cache_source() == "fallback"


if __name__ == "__main__":
    sys.exit(pytest.main([__file__, "-q"]))
