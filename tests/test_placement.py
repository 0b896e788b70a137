"""The placement funnel + topology-independent sharded GBDT training.

Two contracts land here:

* **placement decisions are funneled and observable** — every estimator's
  replicate-vs-batch-shard choice routes through
  ``parallel/placement.plan_for`` and lands in the flight ring as a
  ``placement`` event (deduped per distinct decision), and the resolver
  helpers (``resolve_hist_blocks``, the ``MMLSPARK_TPU_MESH_DEVICES`` mesh
  cap) behave per their docs.

* **cross-device-count tree identity** — with the canonical blocked
  reduction pinned (``GrowConfig.hist_blocks=8``), training the same data
  on 1, 2 and 8 virtual devices produces BIT-IDENTICAL boosters
  (``model_string()`` equality), for all three histogram engines, across
  depthwise/leafwise growth, categorical splits and int8 quantized
  gradients. Each run is a subprocess because the device count is fixed
  at jax init (``xla_force_host_platform_device_count``).
"""

import os
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

ENGINES = ["onehot", "scatter", "pallas"]

_IDENT_DRIVER = """
import sys
import numpy as np
from mmlspark_tpu.models.gbdt.booster import train_booster
from mmlspark_tpu.models.gbdt.growth import GrowConfig

out = sys.argv[1]
rng = np.random.default_rng(7)
n = 960
X = rng.normal(size=(n, 6)).astype(np.float32)
X[:, 3] = rng.integers(0, 8, size=n)
y = (X[:, 0] * X[:, 1] + 0.4 * X[:, 2] > 0).astype(np.float32)
parts = []
for tag, policy, quant, cats in [
        ("depthwise", "depthwise", False, ()),
        ("leafwise", "leafwise", False, ()),
        ("categorical", "depthwise", False, (3,)),
        ("quantized", "depthwise", True, ())]:
    cfg = GrowConfig(num_leaves=7, min_data_in_leaf=5, growth_policy=policy,
                     quantized_grad=quant, hist_blocks=8)
    b = train_booster(X, y, objective="binary", num_iterations=2, cfg=cfg,
                      max_bin=63, bin_sample_count=n, seed=0,
                      categorical_features=cats)
    parts.append(tag + chr(10) + b.model_string())
open(out, "w").write((chr(10) + "====" + chr(10)).join(parts))
"""


def _run_ident(tmp_path, engine: str, devices: int) -> dict:
    """One subprocess fit at a pinned engine/device-count; returns
    {config_tag: model_string}."""
    env = dict(os.environ)
    env.update({
        "PYTHONPATH": ROOT,
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": f"--xla_force_host_platform_device_count={devices}",
        "MMLSPARK_TPU_HIST_ENGINE": engine,
    })
    if engine == "pallas":
        env["MMLSPARK_TPU_PALLAS_INTERPRET"] = "1"
    else:
        env.pop("MMLSPARK_TPU_PALLAS_INTERPRET", None)
    out = tmp_path / f"model.{engine}.{devices}.txt"
    r = subprocess.run([sys.executable, "-c", _IDENT_DRIVER, str(out)],
                       env=env, capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, (engine, devices, r.stderr[-3000:])
    chunks = out.read_text().split("\n====\n")
    return {c.split("\n", 1)[0]: c.split("\n", 1)[1] for c in chunks}


class TestCrossDeviceTreeIdentity:
    """The data_parallel contract, stronger than LightGBM's own: not just
    the same split decisions, the same bytes. The canonical blocked
    reduction (hist_blocks=8) pins the f32 fold order, the quantization
    scales and the stochastic-rounding bits to GLOBAL row geometry, so the
    mesh size stops being an input to the model."""

    # tier-1 runs the backend-default engine (scatter on CPU); the full
    # engine matrix rides the `slow` tier + the ci_check dryrun_multichip
    # lane, keeping the tier-1 wall budget honest (9 subprocess fits would
    # cost ~6 min on the 2-CPU runner)
    @pytest.mark.parametrize(
        "engine",
        [e if e == "scatter" else pytest.param(e, marks=pytest.mark.slow)
         for e in ENGINES])
    def test_1_2_8_devices_bit_identical(self, tmp_path, engine):
        runs = {k: _run_ident(tmp_path, engine, k) for k in (1, 2, 8)}
        for tag in runs[1]:
            for k in (2, 8):
                assert runs[k][tag] == runs[1][tag], (
                    f"{engine}/{tag}: {k}-device trees differ from "
                    "1-device trees")
        # and the fits actually trained something nontrivial
        assert all(len(s) > 200 for s in runs[1].values())


class TestHistBlocksResolution:
    def test_auto_default_is_plain(self, mesh8, monkeypatch):
        from mmlspark_tpu.parallel import placement
        monkeypatch.delenv("MMLSPARK_TPU_HIST_BLOCKS", raising=False)
        assert placement.resolve_hist_blocks("auto", mesh8, 960) == 0

    def test_env_knob_engages_and_degrades(self, mesh8, monkeypatch):
        from mmlspark_tpu.observability import flight
        from mmlspark_tpu.parallel import placement
        monkeypatch.setenv("MMLSPARK_TPU_HIST_BLOCKS", "8")
        assert placement.resolve_hist_blocks("auto", mesh8, 960) == 8
        # indivisible padding: the env-knob path degrades with a flight
        # event instead of failing the fit
        before = len([e for e in flight.events()
                      if e.get("site") == "gbdt.hist_blocks"])
        assert placement.resolve_hist_blocks("auto", mesh8, 8 * 123 + 4) == 0
        after = [e for e in flight.events()
                 if e.get("site") == "gbdt.hist_blocks"]
        assert len(after) == before + 1
        assert after[-1]["decision"] == "fallback_plain"

    def test_explicit_invalid_raises(self, mesh8):
        from mmlspark_tpu.parallel import placement
        with pytest.raises(ValueError, match="multiple"):
            # 6 blocks cannot tile an 8-shard data axis
            placement.resolve_hist_blocks(6, mesh8, 960)
        with pytest.raises(ValueError, match="row count"):
            placement.resolve_hist_blocks(8, mesh8, 8 * 100 + 4)
        with pytest.raises(ValueError, match="voting"):
            placement.resolve_hist_blocks(8, mesh8, 960, voting=True)

    def test_blocked_quantized_totals_widen_before_the_fold(self):
        """Per-BLOCK quantized sums accumulate int32 (bounded by q_max *
        rows_per_block) but must widen to f32 before the cross-block fold
        — folding raw int32 across all blocks would wrap once q_max *
        total_rows crosses 2^31 (~17M rows at q_max=127)."""
        import jax.numpy as jnp

        from mmlspark_tpu.models.gbdt.growth import _stat_totals
        base = (jnp.ones((3, 64), jnp.int8) * 3)
        qs = jnp.asarray([0.5, 0.5, 0.5], jnp.float32)
        tot = _stat_totals(base, qs, None, 8, 8)
        assert tot.dtype == jnp.float32
        np.testing.assert_allclose(np.asarray(tot), [96.0, 96.0, 96.0])

    def test_resolved_value_keys_the_config(self):
        """hist_blocks rides GrowConfig, so it reaches every
        compiled-program cache key for free — but it must be CONCRETE by
        growth time."""
        import jax.numpy as jnp

        from mmlspark_tpu.models.gbdt.growth import (
            GrowConfig, _hist_block_geometry)
        assert _hist_block_geometry(
            GrowConfig(hist_blocks="auto"), None, 960) == (0, 960)
        assert _hist_block_geometry(
            GrowConfig(hist_blocks=8), None, 960) == (8, 120)
        with pytest.raises(ValueError, match="tile"):
            _hist_block_geometry(GrowConfig(hist_blocks=7), None, 960)
        del jnp


class TestPlacementEvents:
    @pytest.fixture(autouse=True)
    def _fresh_decisions(self):
        from mmlspark_tpu.parallel import placement
        placement.reset_decision_log()
        yield
        placement.reset_decision_log()

    @staticmethod
    def _placement_events():
        from mmlspark_tpu.observability import flight
        return [e for e in flight.events() if e.get("kind") == "placement"]

    def test_gbdt_fit_and_predict_decisions(self):
        from mmlspark_tpu.core.dataset import Dataset
        from mmlspark_tpu.models.gbdt.api import LightGBMClassifier

        rng = np.random.default_rng(3)
        X = rng.normal(size=(480, 4)).astype(np.float32)
        y = (X[:, 0] > 0).astype(np.float64)
        ds = Dataset({"features": X, "label": y})
        n0 = len(self._placement_events())
        model = LightGBMClassifier(numIterations=2, numLeaves=4,
                                   minDataInLeaf=5).fit(ds)
        model.transform(ds)
        ev = self._placement_events()[n0:]
        by_site = {e["site"]: e for e in ev}
        assert by_site["gbdt.ingest"]["decision"] == "shard_rows"
        assert by_site["gbdt.fit"]["decision"] == "shard_rows"
        assert by_site["gbdt.fit"]["backend"] == "cpu"
        assert by_site["gbdt.predict"]["decision"] == "replicate"
        # dedup: an identical second fit emits no new decision events
        n1 = len(self._placement_events())
        LightGBMClassifier(numIterations=2, numLeaves=4,
                           minDataInLeaf=5).fit(ds)
        dup = [e for e in self._placement_events()[n1:]
               if e["site"] in by_site]
        assert dup == []

    def test_plan_for_unit(self, mesh8):
        from mmlspark_tpu.parallel import placement
        p = placement.plan_for("unit.test", mesh=mesh8, rows=64)
        assert p.decision == "shard_rows" and p.nshards == 8
        assert p.backend == "cpu" and p.donate_buffers is False
        # rows are recorded but do NOT flip the decision: shard sites pad
        # short batches to the shard multiple and shard them anyway, so
        # the logged decision must match what shard_rows actually does
        p2 = placement.plan_for("unit.test2", mesh=mesh8, rows=3)
        assert p2.decision == "shard_rows"
        assert placement.shard_rows(np.arange(3.0), mesh8)[0].shape[0] == 8
        ev = self._placement_events()
        assert any(e["site"] == "unit.test" for e in ev)
        assert any(e["site"] == "unit.test2" and e["rows"] == 3
                   for e in ev)

    def test_plan_shardings(self, mesh8):
        from mmlspark_tpu.parallel import placement
        p = placement.plan_for("unit.shardings", mesh=mesh8, rows=64)
        sh = p.batch(ndim=2)
        assert sh.spec == placement.pspec("data", None)
        assert p.replicated().spec == placement.pspec()


class TestMeshDeviceCap:
    def test_mesh_devices_knob_caps_default(self, monkeypatch):
        from mmlspark_tpu.parallel.mesh import make_mesh
        monkeypatch.setenv("MMLSPARK_TPU_MESH_DEVICES", "2")
        assert make_mesh().shape["data"] == 2
        # explicit shape/devices are honored as given
        assert make_mesh({"data": 8}).shape["data"] == 8
        monkeypatch.delenv("MMLSPARK_TPU_MESH_DEVICES")
        assert make_mesh().shape["data"] == 8
