"""Round-4 LightGBM param-surface additions (reference:
lightgbm/LightGBMParams.scala): improvementTolerance,
isProvideTrainingMetric, pos/negBaggingFraction, maxDeltaStep,
maxBinByFeature, slotNames.
"""

import numpy as np
import pytest

from mmlspark_tpu.core.dataset import Dataset
from mmlspark_tpu.models.gbdt.api import LightGBMClassifier
from mmlspark_tpu.models.gbdt.booster import (Booster, LightGBMDataset,
                                              train_booster)
from mmlspark_tpu.models.gbdt.growth import GrowConfig
from mmlspark_tpu.ops.binning import QuantileBinner


def _binary(n=3000, F=8, seed=0):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, F)).astype(np.float32)
    y = (X[:, 0] + 0.5 * X[:, 1] + 0.3 * rng.normal(size=n) > 0
         ).astype(np.float32)
    return X, y


def _ds(X, y, **cols):
    return Dataset({"features": X, "label": y, **cols})


class TestImprovementTolerance:
    def test_large_tolerance_stops_earlier(self):
        X, y = _binary()
        vi = (np.arange(len(y)) % 4 == 0)
        kw = dict(numIterations=60, numLeaves=15, maxBin=63,
                  earlyStoppingRound=3, validationIndicatorCol="isVal")
        strict = LightGBMClassifier(**kw).fit(_ds(X, y, isVal=vi))
        loose = LightGBMClassifier(improvementTolerance=10.0, **kw).fit(
            _ds(X, y, isVal=vi))
        # nothing improves logloss by 10 after iteration 0 (which beats the
        # +inf init), so stopping fires at the first opportunity — 4
        # evaluated iterations, model truncated to the best (iteration 0)
        assert len(loose.booster.eval_history["binary_logloss"]) == 4
        assert loose.booster.num_iterations == 1
        assert (len(strict.booster.eval_history["binary_logloss"])
                > len(loose.booster.eval_history["binary_logloss"]))

    def test_fused_matches_host_with_tolerance(self, monkeypatch):
        X, y = _binary()
        vi = (np.arange(len(y)) % 4 == 0)
        clf = LightGBMClassifier(numIterations=40, numLeaves=15, maxBin=63,
                                 earlyStoppingRound=4,
                                 improvementTolerance=1e-3,
                                 validationIndicatorCol="isVal")
        monkeypatch.delenv("MMLSPARK_TPU_DISABLE_FUSED_VALID",
                           raising=False)
        fused = clf.fit(_ds(X, y, isVal=vi))
        monkeypatch.setenv("MMLSPARK_TPU_DISABLE_FUSED_VALID", "1")
        host = clf.fit(_ds(X, y, isVal=vi))
        assert fused.booster.num_iterations == host.booster.num_iterations
        assert fused.booster.best_iteration == host.booster.best_iteration

    def test_negative_rejected(self):
        X, y = _binary(300)
        with pytest.raises(ValueError, match="improvementTolerance"):
            train_booster(X, y, objective="binary", num_iterations=2,
                          early_stopping_tolerance=-1.0)


class TestProvideTrainingMetric:
    def test_history_records_training_metric(self):
        X, y = _binary()
        m = LightGBMClassifier(numIterations=12, numLeaves=15, maxBin=63,
                               isProvideTrainingMetric=True).fit(_ds(X, y))
        hist = m.booster.eval_history["training_binary_logloss"]
        assert len(hist) == 12
        assert hist[-1] < hist[0]          # the margin is being fit
        assert all(np.isfinite(hist))

    def test_works_alongside_validation(self):
        X, y = _binary()
        vi = (np.arange(len(y)) % 4 == 0)
        m = LightGBMClassifier(numIterations=10, numLeaves=15, maxBin=63,
                               isProvideTrainingMetric=True,
                               validationIndicatorCol="isVal").fit(
            _ds(X, y, isVal=vi))
        h = m.booster.eval_history
        assert len(h["training_binary_logloss"]) == 10
        assert len(h["binary_logloss"]) == 10

    def test_rejected_for_rf_and_dart(self):
        X, y = _binary(400)
        for bt, kw in (("rf", dict(baggingFraction=0.6, baggingFreq=1)),
                       ("dart", {})):
            with pytest.raises(ValueError, match="isProvideTrainingMetric"):
                LightGBMClassifier(numIterations=2, boostingType=bt,
                                   isProvideTrainingMetric=True,
                                   **kw).fit(_ds(X, y))


class TestStratifiedBagging:
    def test_fits_and_differs_from_plain(self):
        X, y = _binary(4000)
        base = dict(numIterations=10, numLeaves=15, maxBin=63,
                    baggingFreq=1, baggingSeed=7)
        plain = LightGBMClassifier(baggingFraction=0.5, **base).fit(
            _ds(X, y))
        strat = LightGBMClassifier(posBaggingFraction=0.9,
                                   negBaggingFraction=0.2, **base).fit(
            _ds(X, y))
        acc = ((strat.booster.predict(X) > 0.5) == y).mean()
        assert acc > 0.8
        assert not np.allclose(plain.booster.predict(X[:100]),
                               strat.booster.predict(X[:100]))

    def test_rf_accepts_stratified_bagging(self):
        X, y = _binary(2000)
        m = LightGBMClassifier(numIterations=6, numLeaves=15, maxBin=63,
                               boostingType="rf", baggingFreq=1,
                               posBaggingFraction=0.8,
                               negBaggingFraction=0.4).fit(_ds(X, y))
        assert ((m.booster.predict(X) > 0.5) == y).mean() > 0.8

    def test_both_fraction_styles_rejected(self):
        X, y = _binary(400)
        with pytest.raises(ValueError, match="not both"):
            train_booster(X, y, objective="binary", num_iterations=2,
                          bagging_fraction=0.5, bagging_freq=1,
                          pos_bagging_fraction=0.9,
                          neg_bagging_fraction=0.3)

    def test_validation_errors(self):
        X, y = _binary(400)
        with pytest.raises(ValueError, match="baggingFreq"):
            train_booster(X, y, objective="binary", num_iterations=2,
                          pos_bagging_fraction=0.5)
        with pytest.raises(ValueError, match="binary"):
            train_booster(X, (y + (X[:, 2] > 1)).astype(np.float32),
                          objective="multiclass", num_class=3,
                          num_iterations=2, bagging_freq=1,
                          neg_bagging_fraction=0.5)
        with pytest.raises(ValueError, match="goss"):
            train_booster(X, y, objective="binary", num_iterations=2,
                          boosting_type="goss", bagging_freq=1,
                          pos_bagging_fraction=0.5)


class TestMaxDeltaStep:
    def test_leaf_values_clamped(self):
        X, y = _binary(2000)
        # tiny leaves + no regularization produce extreme raw outputs
        cfg = GrowConfig(num_leaves=31, min_data_in_leaf=1,
                         min_sum_hessian_in_leaf=0.0, learning_rate=0.1)
        free = train_booster(X, y, objective="binary", num_iterations=3,
                             cfg=cfg, max_bin=63)
        clamped = train_booster(X, y, objective="binary", num_iterations=3,
                                cfg=cfg._replace(max_delta_step=0.5),
                                max_bin=63)
        assert np.abs(np.asarray(free.trees.leaf_value)).max() > 0.05 + 1e-6
        assert np.abs(np.asarray(clamped.trees.leaf_value)).max() \
            <= 0.5 * 0.1 + 1e-6          # max_delta_step * learning_rate


class TestMaxBinByFeature:
    def test_per_feature_bin_caps(self):
        X, y = _binary(3000, F=4)
        caps = [4, 255, 8, 255]
        b = QuantileBinner(63, 3000, 0, max_bin_by_feature=caps).fit(X)
        finite = np.isfinite(b.upper_bounds).sum(axis=1)
        assert finite[0] <= 3 and finite[2] <= 7
        assert finite[1] > 30 and finite[3] > 30
        binned = b.transform(X)
        assert binned[:, 0].max() <= 3 and binned[:, 2].max() <= 7

    def test_through_estimator_and_roundtrip(self, tmp_path):
        X, y = _binary(2000, F=4)
        m = LightGBMClassifier(numIterations=5, numLeaves=15, maxBin=63,
                               maxBinByFeature=[4, 63, 8, 63]).fit(
            _ds(X, y))
        acc = ((m.booster.predict(X) > 0.5) == y).mean()
        assert acc > 0.8
        p = str(tmp_path / "m")
        m.booster.save(p)
        loaded = Booster.load(p)
        np.testing.assert_array_equal(loaded.predict(X[:64]),
                                      m.booster.predict(X[:64]))
        assert loaded.binner_state["max_bin_by_feature"] == [4, 63, 8, 63]

    def test_bad_values_rejected(self):
        X, y = _binary(300, F=4)
        with pytest.raises(ValueError, match="at least 2"):
            LightGBMDataset.construct(X, y, max_bin=63,
                                      max_bin_by_feature=[1, 63, 63, 63])
        with pytest.raises(ValueError, match="entries"):
            QuantileBinner(63, 300, 0,
                           max_bin_by_feature=[4]).fit(X)


class TestSlotNames:
    def test_names_flow_into_native_model(self):
        X, y = _binary(2000, F=3)
        names = ["age", "income", "score"]
        m = LightGBMClassifier(numIterations=5, numLeaves=7, maxBin=31,
                               slotNames=names).fit(_ds(X, y))
        s = m.get_native_model()
        assert "feature_names=age income score" in s
        # importances section uses the names too
        assert any(ln.startswith(("age=", "income=", "score="))
                   for ln in s.splitlines())
        b2 = Booster.from_lightgbm_string(s)
        np.testing.assert_allclose(b2.predict_raw(X[:64]),
                                   m.booster.predict_raw(X[:64]),
                                   rtol=1e-6, atol=1e-7)

    def test_wrong_length_rejected(self):
        X, y = _binary(300, F=3)
        with pytest.raises(ValueError, match="slotNames"):
            LightGBMClassifier(numIterations=2,
                               slotNames=["a", "b"]).fit(_ds(X, y))

    def test_whitespace_names_rejected(self):
        X, y = _binary(300, F=3)
        with pytest.raises(ValueError, match="whitespace"):
            LightGBMClassifier(numIterations=2,
                               slotNames=["a", "my feature", "c"]).fit(
                _ds(X, y))


class TestNonCachedPathsHonorPerFeatureBins:
    def test_direct_array_path(self, tmp_path):
        # train_booster's internal construct (the ranker / checkpointDir /
        # numBatches route) must thread max_bin_by_feature like the cached
        # sweep path does
        X, y = _binary(1500, F=4)
        b = train_booster(X, y, objective="binary", num_iterations=3,
                          max_bin=63, max_bin_by_feature=[4, 63, 63, 63],
                          cfg=GrowConfig(num_leaves=7))
        assert b.binner_state["max_bin_by_feature"] == [4, 63, 63, 63]
        finite = np.isfinite(
            np.asarray(b.binner_state["upper_bounds"])[0]).sum()
        assert finite <= 3


class TestMetricOverride:
    """LightGBM `metric` param (reference: LightGBMParams metric)."""

    def test_binary_error_device_path(self, monkeypatch):
        X, y = _binary()
        vi = (np.arange(len(y)) % 4 == 0)
        kw = dict(numIterations=20, numLeaves=15, maxBin=63,
                  earlyStoppingRound=4, metric="binary_error",
                  validationIndicatorCol="isVal")
        m = LightGBMClassifier(**kw).fit(_ds(X, y, isVal=vi))
        hist = m.booster.eval_history["binary_error"]
        assert 0 <= min(hist) and max(hist) <= 1
        assert min(hist) < 0.2            # the signal is learnable
        # fused-vs-host equivalence holds under the override too
        monkeypatch.setenv("MMLSPARK_TPU_DISABLE_FUSED_VALID", "1")
        host = LightGBMClassifier(**kw).fit(_ds(X, y, isVal=vi))
        assert host.booster.num_iterations == m.booster.num_iterations
        np.testing.assert_allclose(host.booster.eval_history["binary_error"],
                                   hist, rtol=1e-6)

    def test_auc_device_early_stopping(self):
        X, y = _binary()
        vi = (np.arange(len(y)) % 4 == 0)
        m = LightGBMClassifier(numIterations=15, numLeaves=15, maxBin=63,
                               earlyStoppingRound=5, metric="auc",
                               validationIndicatorCol="isVal").fit(
            _ds(X, y, isVal=vi))
        hist = m.booster.eval_history["auc"]
        assert len(hist) >= 1 and max(hist) > 0.9
        assert all(0.0 <= v <= 1.0 for v in hist)
        # one dispatch: the metric no longer forces the host loop
        from mmlspark_tpu.observability import spans
        fits = [e for e in spans.get_trace_events()
                if e["ph"] == "X" and e["name"] == "gbdt_fit"]
        assert fits[-1]["args"]["path"] == "fused_valid"
        assert fits[-1]["args"]["metric"] == "auc"

    def test_auc_matches_sklearn(self):
        from sklearn.metrics import roc_auc_score

        from _gbdt_reference import auc_float64
        from mmlspark_tpu.models.gbdt.objectives import auc_device

        rng = np.random.default_rng(0)
        s = np.round(rng.normal(size=500), 1)     # rounding forces ties
        y = (s + rng.normal(scale=1.0, size=500) > 0).astype(float)
        w = rng.random(500) + 0.1
        ref = roc_auc_score(y, s, sample_weight=w)
        # the tests' float64 reference is sklearn's; the device's is it to f32
        assert abs(auc_float64(s, y, w) - ref) < 1e-10
        ours = float(auc_device(np.float32(s), np.float32(y), np.float32(w)))
        assert abs(ours - ref) < 1e-6

    def test_mae_regression(self):
        from mmlspark_tpu.models.gbdt.api import LightGBMRegressor

        rng = np.random.default_rng(1)
        X = rng.normal(size=(2000, 5)).astype(np.float32)
        y = (2 * X[:, 0] + rng.normal(scale=0.1, size=2000)).astype(
            np.float64)
        vi = (np.arange(2000) % 4 == 0)
        m = LightGBMRegressor(numIterations=15, numLeaves=15, maxBin=63,
                              metric="mae",
                              validationIndicatorCol="isVal").fit(
            _ds(X, y, isVal=vi))
        hist = m.booster.eval_history["mae"]
        assert hist[-1] < hist[0]

    def test_invalid_combos_rejected(self):
        X, y = _binary(300)
        with pytest.raises(ValueError, match="not supported"):
            train_booster(X, y, objective="binary", num_iterations=2,
                          eval_metric_name="ndcg")
        with pytest.raises(ValueError, match="not supported"):
            train_booster(X, y, objective="regression", num_iterations=2,
                          eval_metric_name="auc")
        with pytest.raises(ValueError, match="dart"):
            train_booster(X, y, objective="binary", num_iterations=2,
                          boosting_type="dart",
                          eval_metric_name="binary_error")

    def test_l2_is_mse_and_l1_alias(self):
        from mmlspark_tpu.models.gbdt.api import LightGBMRegressor

        rng = np.random.default_rng(2)
        X = rng.normal(size=(1500, 4)).astype(np.float32)
        y = (X[:, 0] + rng.normal(scale=0.1, size=1500)).astype(np.float64)
        vi = (np.arange(1500) % 4 == 0)
        l2 = LightGBMRegressor(numIterations=8, maxBin=63, metric="l2",
                               validationIndicatorCol="isVal").fit(
            _ds(X, y, isVal=vi))
        rmse = LightGBMRegressor(numIterations=8, maxBin=63,
                                 validationIndicatorCol="isVal").fit(
            _ds(X, y, isVal=vi))
        h2 = l2.booster.eval_history["l2"]
        hr = rmse.booster.eval_history["rmse"]
        # LightGBM l2 is MSE: the square of the rmse curve
        np.testing.assert_allclose(h2, np.square(hr), rtol=1e-5)
        l1 = LightGBMRegressor(numIterations=4, maxBin=63, metric="l1",
                               validationIndicatorCol="isVal").fit(
            _ds(X, y, isVal=vi))
        assert "l1" in l1.booster.eval_history

    def test_ranker_validates_metric(self):
        from mmlspark_tpu.models.gbdt.api import LightGBMRanker

        rng = np.random.default_rng(0)
        n = 400
        X = rng.normal(size=(n, 4)).astype(np.float32)
        y = rng.integers(0, 3, n).astype(np.float64)
        g = np.repeat(np.arange(n // 8), 8).astype(np.int64)
        ds = Dataset({"features": X, "label": y, "group": g})
        with pytest.raises(ValueError, match="not supported"):
            LightGBMRanker(numIterations=2, groupCol="group",
                           metric="auc").fit(ds)
        m = LightGBMRanker(numIterations=3, groupCol="group",
                           metric="ndcg").fit(ds)
        assert m.booster.num_trees == 3
