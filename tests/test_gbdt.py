"""GBDT tests: accuracy-regression baselines + API behavior.

Modeled on the reference's LightGBM suite
(lightgbm/split1/VerifyLightGBMClassifier.scala — 29+ scenarios incl. weights,
unbalance, early stopping, saved native models, CV interop) and its checked-in
metric baselines with tolerances
(core/test/benchmarks/Benchmarks.scala, benchmarks_VerifyLightGBMClassifier.csv).
"""

import numpy as np
import pytest
from sklearn.datasets import load_breast_cancer, load_diabetes, load_iris
from sklearn.metrics import accuracy_score, mean_squared_error, roc_auc_score
from sklearn.model_selection import train_test_split

from mmlspark_tpu.core.dataset import Dataset
from mmlspark_tpu.models.gbdt.api import (LightGBMClassificationModel,
                                          LightGBMClassifier,
                                          LightGBMRegressionModel,
                                          LightGBMRegressor)
from mmlspark_tpu.models.gbdt.booster import Booster, train_booster
from mmlspark_tpu.models.gbdt.growth import GrowConfig

# Checked-in metric baselines with tolerances (Benchmarks.scala parity):
# reference AUC on its breast-cancer benchmark is 0.9925 (tol 0.1);
# we gate tighter since this exact dataset differs.
BASELINE_BINARY_AUC = 0.98
BASELINE_MULTI_ACC = 0.90
BASELINE_REG_RMSE = 70.0


def _binary_data():
    X, y = load_breast_cancer(return_X_y=True)
    return train_test_split(X, y, test_size=0.3, random_state=0)


def _many_rows_data(with_cats: bool, n: int = 9000, F: int = 10):
    """9000 x 10 binary task; with ``with_cats`` features 8 and 9 are
    category ids in [0, 6) that the label depends on."""
    rng = np.random.default_rng(7)
    X = rng.normal(size=(n, F)).astype(np.float32)
    margin = X[:, 0] * X[:, 1] - X[:, 2] + 0.2 * rng.normal(size=n)
    if with_cats:
        X[:, 8:] = rng.integers(0, 6, size=(n, 2))
        margin = margin + np.isin(X[:, 8], (1, 4)) - (X[:, 9] == 2)
    return X, (margin > 0).astype(np.float32)


def _routed_rows(b: Booster, X, cats) -> np.ndarray:
    """[T, M] rows reaching every node of every tree, by a plain walk over
    the finished trees' raw thresholds and category bitsets."""
    trees = b.trees
    feat, left, right = (np.asarray(a) for a in (trees.feat, trees.left,
                                                 trees.right))
    is_leaf, bits = np.asarray(trees.is_leaf), np.asarray(trees.cat_bitset)
    thr = np.asarray(b.thr_raw)
    out = np.zeros(feat.shape, np.float32)
    for t in range(feat.shape[0]):
        node = np.zeros(len(X), np.int64)
        rows = np.arange(len(X))                 # rows not at a leaf yet
        for _ in range(feat.shape[1]):
            out[t] += np.bincount(node[rows], minlength=feat.shape[1])
            rows = rows[~is_leaf[t, node[rows]]]
            if not len(rows):
                break
            nd = node[rows]
            f = feat[t, nd]
            x = X[rows, f]
            go_left = x <= thr[t, nd]
            cid = x.astype(np.int64)
            member = (bits[t, nd, cid >> 5] >> (cid & 31).astype(np.uint32)
                      ) & 1
            go_left = np.where(np.isin(f, cats), member.astype(bool), go_left)
            node[rows] = np.where(go_left, left[t, nd], right[t, nd])
    return out


def _logloss(y, p):
    p = np.clip(np.asarray(p, np.float64), 1e-12, 1 - 1e-12)
    return float(-np.mean(y * np.log(p) + (1 - y) * np.log(1 - p)))


def _to_ds(X, y, **extra):
    cols = {"features": np.asarray(X, np.float32), "label": np.asarray(y, np.float64)}
    cols.update(extra)
    return Dataset(cols)


@pytest.fixture(scope="module")
def binary_fitted():
    Xtr, Xte, ytr, yte = _binary_data()
    clf = LightGBMClassifier(numIterations=20, numLeaves=15, minDataInLeaf=5,
                             maxBin=63)
    model = clf.fit(_to_ds(Xtr, ytr))
    return model, Xte, yte


class TestClassifier:
    def test_auc_baseline(self, binary_fitted):
        model, Xte, yte = binary_fitted
        out = model.transform(_to_ds(Xte, yte))
        probs = np.asarray(out["probability"])
        assert roc_auc_score(yte, probs[:, 1]) > BASELINE_BINARY_AUC

    def test_output_columns(self, binary_fitted):
        model, Xte, yte = binary_fitted
        out = model.transform(_to_ds(Xte, yte))
        assert set(["rawPrediction", "probability", "prediction"]) <= set(out.columns)
        probs = np.asarray(out["probability"])
        assert probs.shape == (len(yte), 2)
        assert np.allclose(probs.sum(axis=1), 1.0, atol=1e-5)
        raw = np.asarray(out["rawPrediction"])
        assert np.all((raw[:, 1] > 0) == (probs[:, 1] > 0.5))

    def test_accuracy(self, binary_fitted):
        model, Xte, yte = binary_fitted
        out = model.transform(_to_ds(Xte, yte))
        assert accuracy_score(yte, out["prediction"]) > 0.93

    def test_multiclass(self):
        X, y = load_iris(return_X_y=True)
        Xtr, Xte, ytr, yte = train_test_split(X, y, test_size=0.3, random_state=0)
        model = LightGBMClassifier(numIterations=30, numLeaves=7, minDataInLeaf=3,
                                   maxBin=63).fit(_to_ds(Xtr, ytr))
        out = model.transform(_to_ds(Xte, yte))
        assert accuracy_score(yte, out["prediction"]) > BASELINE_MULTI_ACC
        assert np.asarray(out["probability"]).shape == (len(yte), 3)

    def test_early_stopping_with_validation_indicator(self):
        Xtr, Xte, ytr, yte = _binary_data()
        X = np.concatenate([Xtr, Xte])
        y = np.concatenate([ytr, yte])
        vi = np.concatenate([np.zeros(len(ytr)), np.ones(len(yte))]).astype(bool)
        clf = LightGBMClassifier(numIterations=120, numLeaves=15, minDataInLeaf=5,
                                 maxBin=63, earlyStoppingRound=5,
                                 validationIndicatorCol="isVal")
        model = clf.fit(_to_ds(X, y, isVal=vi))
        assert model.booster.num_iterations < 120
        assert model.booster.best_iteration >= 0
        assert len(model.booster.eval_history["binary_logloss"]) > 0

    def test_fused_early_stopping_matches_host_loop(self, monkeypatch):
        # the device while_loop path (validation + stopping bookkeeping on
        # device, ONE dispatch) must reproduce the host loop exactly: same
        # best_iter, same metric history, same final model
        Xtr, Xte, ytr, yte = _binary_data()
        X = np.concatenate([Xtr, Xte])
        y = np.concatenate([ytr, yte])
        vi = np.concatenate([np.zeros(len(ytr)),
                             np.ones(len(yte))]).astype(bool)
        clf = LightGBMClassifier(numIterations=60, numLeaves=15,
                                 minDataInLeaf=5, maxBin=63,
                                 earlyStoppingRound=5,
                                 validationIndicatorCol="isVal")
        monkeypatch.delenv("MMLSPARK_TPU_DISABLE_FUSED_VALID",
                           raising=False)
        fused = clf.fit(_to_ds(X, y, isVal=vi))
        monkeypatch.setenv("MMLSPARK_TPU_DISABLE_FUSED_VALID", "1")
        host = clf.fit(_to_ds(X, y, isVal=vi))
        assert fused.booster.best_iteration == host.booster.best_iteration
        assert fused.booster.num_iterations == host.booster.num_iterations
        np.testing.assert_allclose(
            fused.booster.eval_history["binary_logloss"],
            host.booster.eval_history["binary_logloss"], rtol=1e-6)
        np.testing.assert_allclose(fused.booster.predict(Xte),
                                   host.booster.predict(Xte), rtol=1e-6)

    @pytest.mark.parametrize("variant", ["goss", "rf", "multiclass"])
    def test_fused_es_matches_host_loop_variants(self, monkeypatch, variant):
        # fuse_es engages by default for EVERY validated configuration;
        # equivalence was previously pinned only for binary gbdt (+dart).
        # Pin the other families the fused path silently covers.
        if variant == "multiclass":
            X, y = load_iris(return_X_y=True)
            vi = (np.arange(len(y)) % 3 == 0)
            kw = dict(numIterations=40, numLeaves=7, minDataInLeaf=3,
                      maxBin=63, earlyStoppingRound=4,
                      validationIndicatorCol="isVal")
            metric = "multi_logloss"
        else:
            Xtr, Xte, ytr, yte = _binary_data()
            X = np.concatenate([Xtr, Xte])
            y = np.concatenate([ytr, yte])
            vi = np.concatenate([np.zeros(len(ytr)),
                                 np.ones(len(yte))]).astype(bool)
            kw = dict(numIterations=40, numLeaves=15, minDataInLeaf=5,
                      maxBin=63, earlyStoppingRound=4,
                      validationIndicatorCol="isVal", boostingType=variant)
            if variant == "rf":
                kw.update(baggingFraction=0.632, baggingFreq=1)
            metric = "binary_logloss"
        clf = LightGBMClassifier(**kw)
        data = _to_ds(X, y, isVal=vi)
        monkeypatch.delenv("MMLSPARK_TPU_DISABLE_FUSED_VALID",
                           raising=False)
        fused = clf.fit(data)
        monkeypatch.setenv("MMLSPARK_TPU_DISABLE_FUSED_VALID", "1")
        host = clf.fit(data)
        assert fused.booster.best_iteration == host.booster.best_iteration
        assert fused.booster.num_iterations == host.booster.num_iterations
        np.testing.assert_allclose(fused.booster.eval_history[metric],
                                   host.booster.eval_history[metric],
                                   rtol=1e-6)
        np.testing.assert_allclose(fused.booster.predict(X[vi]),
                                   host.booster.predict(X[vi]), rtol=1e-6)

    def test_fused_dart_matches_host_loop(self, monkeypatch):
        # the fused dart dispatch precomputes the drop schedule from the
        # same numpy stream the host loop draws — models must be identical,
        # with and without a validation set
        Xtr, Xte, ytr, yte = _binary_data()
        X = np.concatenate([Xtr, Xte])
        y = np.concatenate([ytr, yte])
        vi = np.concatenate([np.zeros(len(ytr)),
                             np.ones(len(yte))]).astype(bool)
        for with_valid in (False, True):
            kw = dict(numIterations=25, numLeaves=15, boostingType="dart",
                      dropRate=0.3, maxBin=63, labelCol="label")
            if with_valid:
                kw.update(validationIndicatorCol="isVal",
                          earlyStoppingRound=6)
            data = (_to_ds(X, y, isVal=vi) if with_valid
                    else _to_ds(Xtr, ytr))
            monkeypatch.delenv("MMLSPARK_TPU_DISABLE_FUSED_DART",
                               raising=False)
            fused = LightGBMClassifier(**kw).fit(data)
            monkeypatch.setenv("MMLSPARK_TPU_DISABLE_FUSED_DART", "1")
            host = LightGBMClassifier(**kw).fit(data)
            monkeypatch.delenv("MMLSPARK_TPU_DISABLE_FUSED_DART")
            assert fused.booster.num_trees == host.booster.num_trees
            assert (fused.booster.best_iteration
                    == host.booster.best_iteration)
            np.testing.assert_allclose(fused.booster.predict(Xte),
                                       host.booster.predict(Xte),
                                       rtol=1e-6)
            if with_valid:
                np.testing.assert_allclose(
                    fused.booster.eval_history["binary_logloss"],
                    host.booster.eval_history["binary_logloss"], rtol=1e-6)

    def test_is_unbalance(self):
        rng = np.random.default_rng(0)
        n = 2000
        X = rng.normal(size=(n, 5)).astype(np.float32)
        y = (X[:, 0] + rng.normal(scale=2.0, size=n) > 2.2).astype(float)  # rare
        model = LightGBMClassifier(numIterations=20, numLeaves=7, isUnbalance=True,
                                   maxBin=63).fit(_to_ds(X, y))
        out = model.transform(_to_ds(X, y))
        # unbalance weighting must push predicted positive rate up toward recall
        recall = ((np.asarray(out["prediction"]) == 1) & (y == 1)).sum() / max(y.sum(), 1)
        assert recall > 0.5

    def test_sample_weights(self):
        # upweighting one class should move predictions toward it
        rng = np.random.default_rng(1)
        X = rng.normal(size=(500, 3)).astype(np.float32)
        y = (X[:, 0] > 0).astype(float)
        w = np.where(y == 1, 10.0, 1.0)
        m_w = LightGBMClassifier(numIterations=10, numLeaves=7, maxBin=63,
                                 weightCol="w").fit(_to_ds(X, y, w=w))
        m_u = LightGBMClassifier(numIterations=10, numLeaves=7, maxBin=63).fit(
            _to_ds(X, y))
        p_w = np.asarray(m_w.transform(_to_ds(X, y))["probability"])[:, 1].mean()
        p_u = np.asarray(m_u.transform(_to_ds(X, y))["probability"])[:, 1].mean()
        assert p_w > p_u

    def test_feature_importances(self, binary_fitted):
        model, _, _ = binary_fitted
        imp_split = model.get_feature_importances("split")
        imp_gain = model.get_feature_importances("gain")
        assert len(imp_split) == 30
        assert sum(imp_split) > 0 and sum(imp_gain) > 0

    def test_native_model_roundtrip(self, binary_fitted, tmp_path):
        model, Xte, yte = binary_fitted
        p = str(tmp_path / "model.txt")
        model.save_native_model(p)
        loaded = LightGBMClassificationModel.load_native_model(p)
        a = np.asarray(model.transform(_to_ds(Xte, yte))["probability"])
        b = np.asarray(loaded.transform(_to_ds(Xte, yte))["probability"])
        assert np.allclose(a, b, atol=1e-6)

    def test_stage_persistence(self, binary_fitted, tmp_path):
        model, Xte, yte = binary_fitted
        p = str(tmp_path / "stage")
        model.save(p)
        loaded = LightGBMClassificationModel.load(p)
        a = np.asarray(model.transform(_to_ds(Xte, yte))["probability"])
        b = np.asarray(loaded.transform(_to_ds(Xte, yte))["probability"])
        assert np.allclose(a, b, atol=1e-6)

    def test_thresholds(self, binary_fitted):
        model, Xte, yte = binary_fitted
        model2 = model.copy({"thresholds": [0.01, 0.99]})
        out2 = model2.transform(_to_ds(Xte, yte))
        # heavy threshold on class 1 shifts predictions toward class 0
        assert np.asarray(out2["prediction"]).mean() <= \
            np.asarray(model.transform(_to_ds(Xte, yte))["prediction"]).mean()


class TestRegressor:
    def test_rmse_baseline(self):
        X, y = load_diabetes(return_X_y=True)
        Xtr, Xte, ytr, yte = train_test_split(X, y, test_size=0.3, random_state=0)
        model = LightGBMRegressor(numIterations=60, numLeaves=15, minDataInLeaf=10,
                                  maxBin=63).fit(_to_ds(Xtr, ytr))
        out = model.transform(_to_ds(Xte, yte))
        rmse = mean_squared_error(yte, out["prediction"]) ** 0.5
        assert rmse < BASELINE_REG_RMSE

    @pytest.mark.parametrize("objective", ["regression_l1", "huber", "fair", "mape"])
    def test_robust_objectives(self, objective):
        X, y = load_diabetes(return_X_y=True)
        model = LightGBMRegressor(objective=objective, numIterations=30,
                                  numLeaves=15, maxBin=63).fit(_to_ds(X, y))
        pred = np.asarray(model.transform(_to_ds(X, y))["prediction"])
        assert mean_squared_error(y, pred) ** 0.5 < 120.0

    def test_quantile(self):
        X, y = load_diabetes(return_X_y=True)
        for alpha, lo, hi in [(0.1, 0.7, 1.0), (0.9, 0.0, 0.3)]:
            model = LightGBMRegressor(objective="quantile", alpha=alpha,
                                      numIterations=50, numLeaves=15,
                                      maxBin=63).fit(_to_ds(X, y))
            pred = np.asarray(model.transform(_to_ds(X, y))["prediction"])
            frac_above = (y > pred).mean()
            assert lo <= frac_above <= hi

    def test_poisson_tweedie_positive(self):
        X, y = load_diabetes(return_X_y=True)
        for obj in ["poisson", "tweedie"]:
            model = LightGBMRegressor(objective=obj, numIterations=25,
                                      numLeaves=15, maxBin=63).fit(_to_ds(X, y))
            pred = np.asarray(model.transform(_to_ds(X, y))["prediction"])
            assert np.all(pred > 0)

    def test_num_batches_warm_start(self):
        X, y = load_diabetes(return_X_y=True)
        model = LightGBMRegressor(numIterations=30, numLeaves=7, maxBin=63,
                                  numBatches=3).fit(_to_ds(X, y))
        assert model.booster.num_iterations == 90  # 3 batches x 30 iters

    def test_model_string_warm_start(self):
        X, y = load_diabetes(return_X_y=True)
        m1 = LightGBMRegressor(numIterations=20, numLeaves=7, maxBin=63).fit(
            _to_ds(X, y))
        m2 = LightGBMRegressor(numIterations=20, numLeaves=7, maxBin=63,
                               modelString=m1.get_native_model()).fit(_to_ds(X, y))
        assert m2.booster.num_iterations == 40
        r1 = mean_squared_error(y, np.asarray(m1.transform(_to_ds(X, y))["prediction"]))
        r2 = mean_squared_error(y, np.asarray(m2.transform(_to_ds(X, y))["prediction"]))
        assert r2 < r1  # continued training improves train fit


class TestBoosterInternals:
    def test_bagging_feature_fraction(self):
        X, y = load_diabetes(return_X_y=True)
        b = train_booster(X, y, objective="regression", num_iterations=30,
                          cfg=GrowConfig(num_leaves=7), max_bin=63,
                          feature_fraction=0.6, bagging_fraction=0.7, bagging_freq=1)
        rmse = mean_squared_error(y, b.predict(X)) ** 0.5
        assert rmse < 100

    def test_predict_leaf_shape(self):
        X, y = load_diabetes(return_X_y=True)
        b = train_booster(X[:100], y[:100], objective="regression",
                          num_iterations=5, cfg=GrowConfig(num_leaves=7), max_bin=31)
        leaves = b.predict_leaf(X[:10])
        assert leaves.shape == (10, 5)
        is_leaf = np.asarray(b.trees.is_leaf)
        for t in range(5):
            assert np.all(is_leaf[t][leaves[:, t].astype(int)])

    def test_max_depth_respected(self):
        X, y = load_diabetes(return_X_y=True)
        b = train_booster(X, y, objective="regression", num_iterations=3,
                          cfg=GrowConfig(num_leaves=31, max_depth=2), max_bin=63)
        # depth-2 tree has at most 4 leaves => at most 7 nodes
        assert np.all(np.asarray(b.trees.node_count) <= 7)

    def test_deterministic(self):
        X, y = load_diabetes(return_X_y=True)
        b1 = train_booster(X, y, objective="regression", num_iterations=5,
                           cfg=GrowConfig(num_leaves=7), max_bin=31, seed=1)
        b2 = train_booster(X, y, objective="regression", num_iterations=5,
                           cfg=GrowConfig(num_leaves=7), max_bin=31, seed=1)
        assert np.allclose(b1.predict(X), b2.predict(X))

    def test_distributed_equivalence_8_vs_1_shard(self):
        # The strongest multi-chip correctness signal available without
        # hardware: data_parallel GBDT must produce the SAME model on an
        # 8-way data mesh as on a single shard — the histogram psum is a
        # plain sum, so shard topology must not leak into split decisions.
        # Ragged row count (569 % 8 != 0) exercises the padded-shard path.
        import jax
        from mmlspark_tpu.parallel import mesh as meshlib

        X, y = load_breast_cancer(return_X_y=True)
        cfg = GrowConfig(num_leaves=15)
        common = dict(objective="binary", num_iterations=10, cfg=cfg,
                      max_bin=63, seed=0)
        b8 = train_booster(X, y, **common)  # default mesh: 8 virtual devices
        with meshlib.default_mesh(
                meshlib.make_mesh({"data": 1}, devices=jax.devices()[:1])):
            b1 = train_booster(X, y, **common)
        # identical structure: same split features and bins in every tree
        assert np.array_equal(np.asarray(b8.trees.feat),
                              np.asarray(b1.trees.feat))
        assert np.array_equal(np.asarray(b8.trees.thr_bin),
                              np.asarray(b1.trees.thr_bin))
        np.testing.assert_allclose(b8.predict(X), b1.predict(X),
                                   rtol=0, atol=1e-5)

    def test_distributed_equivalence_voting_quality(self):
        # voting_parallel's ballot is shard-topology-dependent BY DESIGN
        # (each shard votes its local top-k, like LightGBM's approximate
        # voting learner) — so only quality equivalence is asserted.
        import jax
        from mmlspark_tpu.parallel import mesh as meshlib

        X, y = load_breast_cancer(return_X_y=True)
        common = dict(objective="binary", num_iterations=10,
                      cfg=GrowConfig(num_leaves=15, voting=True, top_k=5),
                      max_bin=63, seed=0)
        b8 = train_booster(X, y, **common)
        with meshlib.default_mesh(
                meshlib.make_mesh({"data": 1}, devices=jax.devices()[:1])):
            b1 = train_booster(X, y, **common)
        a8 = roc_auc_score(y, b8.predict(X))
        a1 = roc_auc_score(y, b1.predict(X))
        assert min(a8, a1) > 0.99 and abs(a8 - a1) < 5e-3, (a8, a1)

    def test_leaf_batch_matches_sequential(self):
        # Splits of distinct leaves are independent, so batched best-first
        # takes exactly the sequential splits whenever the num_leaves budget
        # is not the binding constraint — predictions must match bitwise-ish.
        X, y = load_diabetes(return_X_y=True)
        common = dict(objective="regression", num_iterations=5, max_bin=63,
                      seed=3)
        b1 = train_booster(X, y, cfg=GrowConfig(
            num_leaves=63, min_data_in_leaf=40, leaf_batch=1), **common)
        b8 = train_booster(X, y, cfg=GrowConfig(
            num_leaves=63, min_data_in_leaf=40, leaf_batch=8), **common)
        assert np.allclose(b1.predict(X), b8.predict(X), atol=1e-5)

    @pytest.mark.parametrize("cats", [(), (8, 9)],
                             ids=["numeric", "categorical"])
    @pytest.mark.parametrize("quantized", [False, True], ids=["f32", "int8"])
    @pytest.mark.parametrize("policy", ["leafwise", "depthwise"])
    def test_single_device_many_rows(self, policy, quantized, cats):
        # The one-chip benchmark cells' shape: a single device, no collective
        # axis, thousands of rows. Every node's count must be the rows the
        # finished tree routes there (the benchmark's count_gap, at toy
        # size); with f32 statistics the model equals the 8-device fit's to
        # the leaf_batch test's tolerance (what criteo255q.train beside
        # criteo255q.train4 relies on). With int8 each shard quantizes by
        # itself, so across meshes only the training loss is comparable.
        import jax
        from mmlspark_tpu.parallel import mesh as meshlib

        X, y = _many_rows_data(bool(cats))
        fit = dict(objective="binary", num_iterations=5, max_bin=63, seed=0,
                   categorical_features=cats)
        cfg = GrowConfig(num_leaves=15, growth_policy=policy,
                         quantized_grad=quantized, quant_warmup_iters=0)
        one = meshlib.make_mesh({"data": 1}, devices=jax.devices()[:1])
        with meshlib.default_mesh(one):
            b1 = train_booster(X, y, cfg=cfg, **fit)
        routed = _routed_rows(b1, X, cats)
        cnt = np.asarray(b1.trees.node_cnt)
        assert (cnt > 0).sum() >= 3 * b1.num_trees     # real trees grew
        if quantized:
            # the count channel is quantized with the gradients: a node's
            # count is exact to the benchmark's count_gap limit, not the bit
            assert np.max(np.abs(cnt - routed) / np.maximum(routed, 1)
                          ) < 1e-4
            with meshlib.default_mesh(one):
                bf = train_booster(X, y, cfg=cfg._replace(
                    quantized_grad=False), **fit)
            l8, lf = _logloss(y, b1.predict(X)), _logloss(y, bf.predict(X))
            assert abs(l8 - lf) < 0.02 * lf, (l8, lf)
        else:
            np.testing.assert_array_equal(cnt, routed)
            b8 = train_booster(X, y, cfg=cfg, **fit)   # 8 virtual devices
            assert np.allclose(b1.predict(X), b8.predict(X), atol=1e-5)

    def test_leaf_batch_budget_quality(self):
        # With a binding leaf budget the batched order may differ from
        # sequential near exhaustion — quality must stay equivalent.
        X, y = load_breast_cancer(return_X_y=True)
        aucs = []
        for lb in (1, 8):
            b = train_booster(X, y, objective="binary", num_iterations=15,
                              cfg=GrowConfig(num_leaves=15, leaf_batch=lb),
                              max_bin=63, seed=0)
            aucs.append(roc_auc_score(y, b.predict(X)))
        assert min(aucs) > 0.99
        assert abs(aucs[0] - aucs[1]) < 5e-3

    def test_leaf_batch_voting_quality(self):
        # Under voting_parallel the top-2k ballot spans the whole batch's
        # children (documented batch-wide approximation, like depthwise's
        # frontier-wide vote) — quality must stay on par with the exact
        # per-split ballot of leaf_batch=1.
        X, y = load_breast_cancer(return_X_y=True)
        aucs = []
        for lb in (1, 8):
            b = train_booster(X, y, objective="binary", num_iterations=10,
                              cfg=GrowConfig(num_leaves=15, leaf_batch=lb,
                                             voting=True, top_k=5),
                              max_bin=63, seed=0)
            aucs.append(roc_auc_score(y, b.predict(X)))
        assert min(aucs) > 0.99
        assert abs(aucs[0] - aucs[1]) < 5e-3

    def test_min_data_in_leaf(self):
        X, y = load_diabetes(return_X_y=True)
        b = train_booster(X, y, objective="regression", num_iterations=3,
                          cfg=GrowConfig(num_leaves=31, min_data_in_leaf=50),
                          max_bin=63)
        cnt = np.asarray(b.trees.node_cnt)
        leaf = np.asarray(b.trees.is_leaf) & (cnt > 0)
        assert cnt[leaf].min() >= 50


class TestBinning:
    def test_quantile_binner(self):
        from mmlspark_tpu.ops.binning import QuantileBinner

        rng = np.random.default_rng(0)
        X = rng.normal(size=(1000, 3)).astype(np.float32)
        b = QuantileBinner(max_bin=16).fit(X)
        Xb = b.transform(X)
        assert Xb.min() >= 0 and Xb.max() <= 15
        # roughly uniform occupancy for continuous data
        counts = np.bincount(Xb[:, 0], minlength=16)
        assert counts.min() > 20

    def test_nan_goes_to_bin0(self):
        from mmlspark_tpu.ops.binning import QuantileBinner

        X = np.array([[1.0], [2.0], [np.nan], [3.0]], dtype=np.float32)
        b = QuantileBinner(max_bin=4).fit(X)
        assert b.transform(X)[2, 0] == 0

    def test_few_distinct_values(self):
        from mmlspark_tpu.ops.binning import QuantileBinner

        X = np.array([[0.0], [1.0], [0.0], [1.0], [2.0]], dtype=np.float32)
        b = QuantileBinner(max_bin=255).fit(X)
        Xb = b.transform(X)
        # each distinct value gets its own bin
        assert len(np.unique(Xb)) == 3


def _ranking_data(seed=0, n_groups=60):
    rng = np.random.default_rng(seed)
    groups, ys, feats = [], [], []
    for g in range(n_groups):
        sz = int(rng.integers(3, 12))
        rel = rng.integers(0, 4, sz)
        x = rng.normal(size=(sz, 5)).astype(np.float32)
        x[:, 0] += rel  # feature 0 carries the relevance signal
        groups += [g] * sz
        ys += rel.tolist()
        feats.append(x)
    return np.concatenate(feats), np.asarray(ys, np.float64), np.asarray(groups)


class TestRanker:
    """reference: lightgbm/LightGBMRanker.scala + group handling :80-98"""

    def test_lambdarank_learns_ranking(self):
        from mmlspark_tpu.models.gbdt.api import LightGBMRanker

        X, y, g = _ranking_data()
        ds = _to_ds(X, y, query=g)
        model = LightGBMRanker(groupCol="query", numIterations=20,
                               numLeaves=7, minDataInLeaf=2).fit(ds)
        score = model.transform(ds)["prediction"]
        # within-group concordance: higher label should score higher
        concordant = total = 0
        for gid in np.unique(g):
            m = g == gid
            s, yy = score[m], y[m]
            for i in range(len(s)):
                for j in range(len(s)):
                    if yy[i] > yy[j]:
                        total += 1
                        concordant += s[i] > s[j]
        assert concordant / total > 0.75

    def test_ranker_early_stopping_ndcg(self):
        from mmlspark_tpu.models.gbdt.api import LightGBMRanker

        X, y, g = _ranking_data()
        vmask = (g % 5 == 0).astype(np.float64)
        ds = _to_ds(X, y, query=g, isVal=vmask)
        model = LightGBMRanker(groupCol="query", numIterations=50,
                               numLeaves=7, minDataInLeaf=2,
                               validationIndicatorCol="isVal",
                               earlyStoppingRound=5).fit(ds)
        hist = model.booster.eval_history["ndcg"]
        assert len(hist) >= 1
        # ndcg must improve over training (higher_is_better path)
        assert max(hist) >= hist[0]

    def test_ranker_native_model_roundtrip(self, tmp_path):
        from mmlspark_tpu.models.gbdt.api import (LightGBMRanker,
                                                  LightGBMRankerModel)

        X, y, g = _ranking_data()
        ds = _to_ds(X, y, query=g)
        model = LightGBMRanker(groupCol="query", numIterations=5,
                               numLeaves=7, minDataInLeaf=2).fit(ds)
        p = str(tmp_path / "ranker.txt")
        model.save_native_model(p)
        loaded = LightGBMRankerModel.load_native_model(p)
        np.testing.assert_allclose(loaded.booster.predict_raw(X),
                                   model.booster.predict_raw(X), rtol=1e-6)


class TestShapAndLeaf:
    """reference: LightGBMBooster.scala:250-269 predict contribs / leaf"""

    def test_shap_sums_to_raw_prediction(self):
        Xtr, Xte, ytr, yte = _binary_data()
        model = LightGBMClassifier(numIterations=10).fit(_to_ds(Xtr, ytr))
        contrib = model.booster.predict_contrib(Xte.astype(np.float32))
        raw = model.booster.predict_raw(Xte.astype(np.float32))[:, 0]
        assert contrib.shape == (len(Xte), Xte.shape[1] + 1)
        np.testing.assert_allclose(contrib.sum(axis=1), raw, atol=1e-3)

    def test_shap_and_leaf_columns(self):
        Xtr, Xte, ytr, yte = _binary_data()
        model = LightGBMClassifier(numIterations=5).fit(_to_ds(Xtr, ytr))
        model.set(featuresShapCol="shap", leafPredictionCol="leaves")
        out = model.transform(_to_ds(Xte, yte))
        assert out["shap"].shape == (len(Xte), Xte.shape[1] + 1)
        assert out["leaves"].shape == (len(Xte), model.booster.num_trees)

    def test_multiclass_shap_shape(self):
        X, y = load_iris(return_X_y=True)
        model = LightGBMClassifier(numIterations=4).fit(_to_ds(X, y))
        contrib = model.booster.predict_contrib(X.astype(np.float32))
        assert contrib.shape == (len(X), (X.shape[1] + 1) * 3)


class TestParallelModes:
    """reference: lightgbm/LightGBMParams.scala:13-27 parallelism + topK"""

    def test_voting_parallel_matches_quality(self):
        Xtr, Xte, ytr, yte = _binary_data()
        model = LightGBMClassifier(numIterations=15,
                                   parallelism="voting_parallel",
                                   topK=5).fit(_to_ds(Xtr, ytr))
        p = model.transform(_to_ds(Xte, yte))["probability"][:, 1]
        assert roc_auc_score(yte, p) > BASELINE_BINARY_AUC

    def test_goss(self):
        Xtr, Xte, ytr, yte = _binary_data()
        model = LightGBMClassifier(numIterations=15,
                                   boostingType="goss").fit(_to_ds(Xtr, ytr))
        p = model.transform(_to_ds(Xte, yte))["probability"][:, 1]
        assert roc_auc_score(yte, p) > 0.95

    def test_depthwise_growth_matches_quality(self):
        """growthPolicy=depthwise (one batched histogram pass per level)
        must match best-first quality; save/load keeps predicting."""
        Xtr, Xte, ytr, yte = _binary_data()
        model = LightGBMClassifier(numIterations=15,
                                   growthPolicy="depthwise").fit(
            _to_ds(Xtr, ytr))
        p = model.transform(_to_ds(Xte, yte))["probability"][:, 1]
        assert roc_auc_score(yte, p) > BASELINE_BINARY_AUC
        # leaf budget respected (count only allocated node slots)
        nodes = int(model.booster.trees.node_count[0])
        assert model.booster.trees.is_leaf[0][:nodes].sum() <= 31

    def test_depthwise_voting_matches_quality(self):
        """Per-level voting_parallel (two small collectives per level
        instead of the full [F, W*3, B] psum) stays within quality noise
        of full data_parallel depthwise growth."""
        Xtr, Xte, ytr, yte = _binary_data()
        accs = {}
        for par in ("data_parallel", "voting_parallel"):
            m = LightGBMClassifier(numIterations=15, numLeaves=15,
                                   minDataInLeaf=5,
                                   growthPolicy="depthwise",
                                   parallelism=par, topK=5).fit(
                _to_ds(Xtr, ytr))
            out = m.transform(_to_ds(Xte, yte))
            accs[par] = (out.array("prediction") == yte).mean()
        assert accs["voting_parallel"] >= accs["data_parallel"] - 0.05, accs


class TestBoostingTypes:
    """rf + dart boosting (reference: lightgbm/TrainParams.scala:9-10)."""

    def test_rf(self):
        Xtr, Xte, ytr, yte = _binary_data()
        model = LightGBMClassifier(numIterations=25, boostingType="rf",
                                   baggingFraction=0.632, baggingFreq=1,
                                   featureFraction=0.8).fit(_to_ds(Xtr, ytr))
        p = model.transform(_to_ds(Xte, yte))["probability"][:, 1]
        assert roc_auc_score(yte, p) > 0.93
        # forest probabilities are calibrated-ish around the averaged margin,
        # not saturated like a boosted margin
        assert np.isfinite(p).all()

    def test_rf_requires_bagging(self):
        Xtr, _, ytr, _ = _binary_data()
        with pytest.raises(ValueError, match="requires bagging"):
            LightGBMClassifier(numIterations=2, boostingType="rf").fit(
                _to_ds(Xtr, ytr))

    def test_rf_regressor(self):
        rng = np.random.default_rng(7)
        X = rng.normal(size=(400, 6)).astype(np.float32)
        y = (X[:, 0] * 2 - X[:, 1] + 0.1 * rng.normal(size=400)).astype(
            np.float64)
        from mmlspark_tpu.models.gbdt.api import LightGBMRegressor
        ds = Dataset({"features": X, "label": y})
        model = LightGBMRegressor(numIterations=30, boostingType="rf",
                                  baggingFraction=0.7, baggingFreq=1,
                                  minDataInLeaf=5).fit(ds)
        pred = model.transform(ds)["prediction"]
        resid = np.asarray(pred) - y
        # averaged forest must track the signal (weaker than boosting but real)
        assert np.corrcoef(pred, y)[0, 1] > 0.9
        assert np.abs(resid).mean() < np.abs(y - y.mean()).mean()

    def test_dart(self):
        Xtr, Xte, ytr, yte = _binary_data()
        model = LightGBMClassifier(numIterations=25, boostingType="dart",
                                   dropRate=0.2, skipDrop=0.3).fit(
            _to_ds(Xtr, ytr))
        p = model.transform(_to_ds(Xte, yte))["probability"][:, 1]
        assert roc_auc_score(yte, p) > BASELINE_BINARY_AUC

    def test_dart_early_stopping_history(self):
        Xtr, Xte, ytr, yte = _binary_data()
        n = len(ytr) + len(yte)
        X = np.concatenate([Xtr, Xte])
        y = np.concatenate([ytr, yte])
        vmask = np.zeros(n); vmask[len(ytr):] = 1
        ds = Dataset({"features": X.astype(np.float32),
                      "label": y.astype(np.float64), "isVal": vmask})
        model = LightGBMClassifier(numIterations=20, boostingType="dart",
                                   validationIndicatorCol="isVal",
                                   earlyStoppingRound=5).fit(ds)
        hist = model.booster.eval_history
        assert len(next(iter(hist.values()))) > 0

    def test_dart_rejects_warm_start_and_checkpoint(self, tmp_path):
        Xtr, _, ytr, _ = _binary_data()
        base = LightGBMClassifier(numIterations=2).fit(_to_ds(Xtr, ytr))
        with pytest.raises(ValueError, match="warm start"):
            LightGBMClassifier(numIterations=2, boostingType="dart",
                               modelString=base.get_native_model()).fit(
                _to_ds(Xtr, ytr))
        with pytest.raises(ValueError, match="checkpointDir"):
            LightGBMClassifier(numIterations=2, boostingType="dart",
                               checkpointDir=str(tmp_path / "ck")).fit(
                _to_ds(Xtr, ytr))

    def test_unknown_boosting_type_rejected(self):
        Xtr, _, ytr, _ = _binary_data()
        with pytest.raises(ValueError, match="not supported"):
            LightGBMClassifier(numIterations=2, boostingType="plain").fit(
                _to_ds(Xtr, ytr))


class TestLightGBMDataset:
    """Bin-once/train-many dataset (LightGBMDataset.scala:70-159 parity)."""

    def test_dataset_training_matches_array_training(self):
        from mmlspark_tpu.models.gbdt.booster import LightGBMDataset
        Xtr, _, ytr, _ = _binary_data()
        kw = dict(objective="binary", num_iterations=5,
                  cfg=GrowConfig(num_leaves=7), max_bin=31)
        b_arr = train_booster(Xtr, ytr, **kw)
        ds = LightGBMDataset.construct(Xtr, ytr, max_bin=31)
        b_ds = train_booster(dataset=ds, **kw)
        np.testing.assert_allclose(b_arr.predict(Xtr), b_ds.predict(Xtr),
                                   rtol=1e-6)
        # train-many: a second, longer run against the same dataset
        b2 = train_booster(dataset=ds, objective="binary", num_iterations=8,
                           cfg=GrowConfig(num_leaves=7))
        assert b2.num_trees == 8

    @pytest.mark.parametrize("dtype", ["uint8", "int16"])
    def test_narrow_bin_storage_trains_identically(self, dtype):
        # uint8/int16 bin storage (the Criteo-scale HBM lever) must produce
        # the SAME model as int32: bin ids are < max_bin so storage width
        # is semantics-free
        from mmlspark_tpu.models.gbdt.booster import LightGBMDataset
        Xtr, _, ytr, _ = _binary_data()
        kw = dict(objective="binary", num_iterations=5,
                  cfg=GrowConfig(num_leaves=7))
        ds32 = LightGBMDataset.construct(Xtr, ytr, max_bin=255)
        dsn = LightGBMDataset.construct(Xtr, ytr, max_bin=255,
                                        bin_dtype=dtype)
        assert str(dsn.Xbt_d.dtype) == dtype
        p32 = train_booster(dataset=ds32, **kw).predict(Xtr)
        pn = train_booster(dataset=dsn, **kw).predict(Xtr)
        np.testing.assert_array_equal(p32, pn)

    def test_narrow_bin_storage_validation(self):
        from mmlspark_tpu.models.gbdt.booster import LightGBMDataset
        Xtr, _, ytr, _ = _binary_data()
        with pytest.raises(ValueError, match="bin_dtype"):
            LightGBMDataset.construct(Xtr, ytr, bin_dtype="float32")
        with pytest.raises(ValueError, match="max_bin"):
            LightGBMDataset.construct(Xtr, ytr, max_bin=300,
                                      bin_dtype="uint8")

    def test_dataset_weighted_and_goss(self):
        from mmlspark_tpu.models.gbdt.booster import LightGBMDataset
        Xtr, _, ytr, _ = _binary_data()
        w = np.where(ytr > 0, 2.0, 1.0).astype(np.float32)
        kw = dict(objective="binary", num_iterations=4,
                  cfg=GrowConfig(num_leaves=7), max_bin=31,
                  boosting_type="goss")
        b_arr = train_booster(Xtr, ytr, w, **kw)
        ds = LightGBMDataset.construct(Xtr, ytr, w, max_bin=31)
        b_ds = train_booster(dataset=ds, **kw)
        np.testing.assert_allclose(b_arr.predict(Xtr), b_ds.predict(Xtr),
                                   rtol=1e-6)

    def test_dataset_rejects_checkpoint_and_blind_warm_start(self, tmp_path):
        from mmlspark_tpu.models.gbdt.booster import LightGBMDataset
        Xtr, _, ytr, _ = _binary_data()
        ds = LightGBMDataset.construct(Xtr, ytr, max_bin=31)
        with pytest.raises(ValueError, match="checkpointDir"):
            train_booster(dataset=ds, objective="binary", num_iterations=2,
                          checkpoint_dir=str(tmp_path / "ck"))
        warm = train_booster(Xtr, ytr, objective="binary", num_iterations=2,
                             cfg=GrowConfig(num_leaves=7), max_bin=31)
        with pytest.raises(ValueError, match="pass X alongside"):
            train_booster(dataset=ds, objective="binary", num_iterations=2,
                          init_booster=warm)
        with pytest.raises(ValueError, match="either X and y"):
            train_booster(objective="binary", num_iterations=2)

    def test_pack_unpack_roundtrip(self):
        from mmlspark_tpu.models.gbdt.booster import (pack_trees,
                                                      unpack_trees)
        from mmlspark_tpu.models.gbdt.growth import Tree, bitset_words
        rng = np.random.default_rng(0)
        M, BW, lead = 9, bitset_words(63), (3, 2)
        def arr(shape, dt):
            if dt == np.bool_:
                return rng.integers(0, 2, shape).astype(bool)
            if dt in (np.int32, np.uint32):
                return rng.integers(0, 100, shape).astype(dt)
            return rng.normal(size=shape).astype(np.float32)
        import jax.numpy as jnp
        fields = {}
        from mmlspark_tpu.models.gbdt.booster import _TREE_FIELD_DTYPES
        for name in Tree._fields:
            shape = lead + ((M, BW) if name == "cat_bitset"
                            else () if name == "node_count" else (M,))
            fields[name] = arr(shape, _TREE_FIELD_DTYPES[name])
        t = Tree(**{k: jnp.asarray(v) for k, v in fields.items()})
        flat = np.asarray(pack_trees(t))
        out = unpack_trees(flat, lead, M, BW)
        for name in Tree._fields:
            got = getattr(out, name)
            assert got.dtype == np.dtype(_TREE_FIELD_DTYPES[name]), name
            np.testing.assert_array_equal(got, fields[name], err_msg=name)


class TestInitScorePadding:
    """init_score must honor zero weights: the device path feeds padded
    sharded labels (padding rows carry weight 0). regression_l1/quantile
    previously used unweighted median/quantile (code-review finding)."""

    @pytest.mark.parametrize("objective", ["regression_l1", "quantile"])
    def test_base_score_ignores_padding(self, objective):
        rng = np.random.default_rng(3)
        # n chosen so n % 8 != 0: the 8-device test mesh zero-pads labels
        n = 1001
        X = rng.normal(size=(n, 4)).astype(np.float32)
        y = (rng.normal(size=n) + 50.0).astype(np.float32)  # far from 0
        b = train_booster(X, y, objective=objective, num_iterations=1,
                          cfg=GrowConfig(num_leaves=4), max_bin=15)
        # an unweighted median over zero-padded labels would sit far below
        # the data median; the weighted quantile must stay inside the data
        assert 48.0 < float(b.base_score[0]) < 52.0

    def test_weighted_quantile_matches_numpy(self):
        from mmlspark_tpu.models.gbdt.objectives import weighted_quantile
        import jax.numpy as jnp
        rng = np.random.default_rng(0)
        y = rng.normal(size=501).astype(np.float32)
        w = np.ones(501, np.float32)
        got = float(weighted_quantile(jnp.asarray(y), jnp.asarray(w), 0.5))
        assert abs(got - float(np.median(y))) < 1e-5
        # zero-weight entries must not move the quantile
        y2 = np.concatenate([y, np.full(100, -1e6, np.float32)])
        w2 = np.concatenate([w, np.zeros(100, np.float32)])
        got2 = float(weighted_quantile(jnp.asarray(y2), jnp.asarray(w2), 0.5))
        assert abs(got2 - got) < 1e-5


class TestBinnedDatasetCache:
    """Sweep fast path: estimator fits on identical data + binning params
    reuse one pre-binned device dataset (content-fingerprint keyed)."""

    def test_sweep_reuses_ingest_and_matches_uncached(self, monkeypatch):
        from mmlspark_tpu.models.gbdt import api as gbdt_api
        from mmlspark_tpu.models.gbdt.booster import LightGBMDataset
        gbdt_api.clear_binned_dataset_cache()  # isolate
        constructs = []
        orig = LightGBMDataset.construct.__func__

        def counting(cls, *a, **k):
            constructs.append(1)
            return orig(cls, *a, **k)

        monkeypatch.setattr(LightGBMDataset, "construct",
                            classmethod(counting))
        Xtr, _, ytr, _ = _binary_data()
        ds = _to_ds(Xtr, ytr)
        preds = {}
        for lr in (0.1, 0.3):
            m = LightGBMClassifier(numIterations=4, numLeaves=7,
                                   learningRate=lr, maxBin=31).fit(ds)
            preds[lr] = np.asarray(m.transform(ds)["probability"])
        assert len(constructs) == 1     # second fit reused the ingest
        # the cached path must match training straight from arrays, and the
        # learner param must actually vary across cached fits
        direct = train_booster(Xtr, ytr, objective="binary",
                               num_iterations=4,
                               cfg=GrowConfig(num_leaves=7,
                                              learning_rate=0.3),
                               max_bin=31)
        np.testing.assert_allclose(preds[0.3][:, 1], direct.predict(Xtr),
                                   rtol=1e-6)
        assert np.abs(preds[0.1] - preds[0.3]).max() > 1e-4
        n_after_direct = len(constructs)   # direct array path constructs too
        # changed data invalidates the fingerprint
        ds2 = _to_ds(Xtr + 1.0, ytr)
        LightGBMClassifier(numIterations=4, numLeaves=7, maxBin=31).fit(ds2)
        assert len(constructs) == n_after_direct + 1
        # changed binning params invalidate too
        LightGBMClassifier(numIterations=4, numLeaves=7, maxBin=63).fit(ds)
        assert len(constructs) == n_after_direct + 2
        gbdt_api.clear_binned_dataset_cache()
        assert len(gbdt_api._BINNED_CACHE) == 0


def test_ranker_label_gain():
    """labelGain (reference LightGBMRanker labelGain): custom NDCG gains
    train and evaluate; grades beyond the table fail fast (LightGBM
    parity), and the tuple-ized kwargs stay program-cache hashable."""
    rng = np.random.default_rng(0)
    n = 400
    X = rng.normal(size=(n, 4)).astype(np.float32)
    rel = np.clip((X[:, 0] * 2 + rng.normal(size=n)).astype(int), 0, 2)
    g = np.repeat(np.arange(n // 8), 8).astype(np.int64)
    ds = _to_ds(X, rel.astype(np.float64), group=g)
    from mmlspark_tpu.models.gbdt.api import LightGBMRanker
    m = LightGBMRanker(numIterations=5, numLeaves=7, maxBin=31,
                       groupCol="group",
                       labelGain=[0.0, 1.0, 10.0]).fit(ds)
    assert np.isfinite(m.booster.predict_raw(X)).all()
    with pytest.raises(ValueError, match="relevance grade"):
        LightGBMRanker(numIterations=2, groupCol="group",
                       labelGain=[0.0]).fit(ds)


def test_lambdarank_without_group_size_raises_clearly():
    """A direct train_booster('lambdarank') without group_size must fail
    with the actionable error, not a ZeroDivisionError from the metric
    probe (scoring-only loaded rankers still predict fine)."""
    rng = np.random.default_rng(0)
    X = rng.normal(size=(64, 3)).astype(np.float32)
    y = rng.integers(0, 3, 64).astype(np.float32)
    with pytest.raises(ValueError, match="group_size"):
        train_booster(X, y, objective="lambdarank", num_iterations=2)
