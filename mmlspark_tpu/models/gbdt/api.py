"""LightGBM-style estimators/models: the public GBDT API surface.

Parity with the reference's LightGBM stages (reference:
lightgbm/LightGBMClassifier.scala:24-195, LightGBMRegressor.scala,
lightgbm/LightGBMParams.scala — param names are kept verbatim so code written
against the reference's PySpark wrappers ports by renaming imports only).
Execution is the TPU-native booster: rows sharded over the mesh ``data`` axis,
histogram psum over ICI instead of the socket ring; cluster-topology params of
the reference (numTasks/parallelism/timeout) are accepted for compatibility
but the mesh defines the actual topology.
"""

from __future__ import annotations

import os
from typing import Optional

import numpy as np

from ...core.dataset import Dataset, _is_sparse
from ...core.params import (HasFeaturesCol, HasGroupCol, HasInitScoreCol,
                            HasLabelCol, HasPredictionCol, HasProbabilityCol,
                            HasRawPredictionCol, HasValidationIndicatorCol,
                            HasWeightCol, Param, Params, TypeConverters)
from ...core.pipeline import Estimator, Model
from ...observability import hbm as _hbm
from ...observability import metrics as _metrics
from ...observability import spans as _spans
from ...observability import watchdog as _watchdog
from .booster import Booster, LightGBMDataset, _densify, train_booster
from .growth import GrowConfig

# Bounded cache of pre-binned device datasets keyed by a CONTENT fingerprint
# of the training arrays + every binning-relevant param. Hyperparameter
# sweeps (automl/TuneHyperparameters) fit many candidates on the same data;
# with the key being a real content hash (strided-page sha256 + full crc32,
# utils/checkpoint.data_fingerprint), candidates that only change
# learner params reuse one ingest (binner fit + transfer + device binning)
# instead of re-paying it per fit. Two entries bound device memory: each
# dataset pins an [F, n] int32 matrix in HBM.
from collections import OrderedDict

_BINNED_CACHE: "OrderedDict" = OrderedDict()
_BINNED_CACHE_MAX = 2


def _dataset_nbytes(ds) -> float:
    """Device bytes one cached binned dataset pins (the ``binned_cache``
    HBM-ledger claim): the [F, n_pad] bin matrix + label/weight/mask."""
    return float(sum(getattr(a, "nbytes", 0) or 0
                     for a in (ds.Xbt_d, ds.y_d, ds.w_d, ds.vmask_d)
                     if a is not None))


def clear_binned_dataset_cache() -> None:
    """Release the cached pre-binned device datasets (frees their HBM) —
    call after a sweep when the process moves on to other device work."""
    _BINNED_CACHE.clear()
    _hbm.set_claim("binned_cache", 0)


def _cache_enabled() -> bool:
    import os
    return os.environ.get("MMLSPARK_TPU_BINNED_CACHE", "1") != "0"


def _cached_binned_dataset(X, y, w, *, max_bin, bin_sample_count, seed,
                           categorical_features,
                           bin_dtype="int32",
                           max_bin_by_feature=None) -> LightGBMDataset:
    if not _cache_enabled():
        # skip fingerprinting entirely: hashing a 1M-row matrix per fit is
        # pure waste when the result will never be cached
        return LightGBMDataset.construct(
            _densify(X), y, w, max_bin=max_bin,
            bin_sample_count=bin_sample_count, seed=seed,
            categorical_features=categorical_features, bin_dtype=bin_dtype,
            max_bin_by_feature=max_bin_by_feature)
    from ...parallel import mesh as meshlib
    from ...utils.checkpoint import data_fingerprint

    # sparse input: fingerprint the CSR buffers directly — densifying is
    # deferred to a cache MISS so repeated sweep fits never allocate the
    # dense copy just to compute the key
    if _is_sparse(X):
        fp = data_fingerprint(X.data, X.indices, X.indptr,
                              np.asarray(X.shape), y, w)
    else:
        fp = data_fingerprint(X, y, w)
    # the active mesh is part of identity: a dataset constructed on one mesh
    # must not serve a fit running under a different default mesh
    # bin_dtype is part of identity: a uint8 fit after an int32 fit on
    # identical data must not silently reuse the wide dataset
    key = (fp, max_bin, bin_sample_count, seed,
           tuple(int(i) for i in categorical_features),
           str(bin_dtype),
           None if max_bin_by_feature is None
           else tuple(int(b) for b in max_bin_by_feature),
           meshlib.get_default_mesh())
    ds = _BINNED_CACHE.get(key)
    if ds is None:
        ds = LightGBMDataset.construct(
            _densify(X), y, w, max_bin=max_bin,
            bin_sample_count=bin_sample_count, seed=seed,
            categorical_features=categorical_features, bin_dtype=bin_dtype,
            max_bin_by_feature=max_bin_by_feature)
        _BINNED_CACHE[key] = ds
        _hbm.claim("binned_cache", _dataset_nbytes(ds))
        while len(_BINNED_CACHE) > _BINNED_CACHE_MAX:
            _k, old = _BINNED_CACHE.popitem(last=False)
            _hbm.release("binned_cache", _dataset_nbytes(old))
    else:
        _BINNED_CACHE.move_to_end(key)
    return ds


class _LightGBMParams(HasLabelCol, HasFeaturesCol, HasWeightCol, HasInitScoreCol,
                      HasValidationIndicatorCol, HasPredictionCol):
    """Shared LightGBM params (reference: lightgbm/LightGBMParams.scala)."""

    boostingType = Param("boostingType", "gbdt, rf, dart or goss", "gbdt",
                         TypeConverters.to_string)
    numIterations = Param("numIterations", "Number of boosting iterations", 100,
                          TypeConverters.to_int)
    learningRate = Param("learningRate", "Shrinkage rate", 0.1, TypeConverters.to_float)
    numLeaves = Param("numLeaves", "Max leaves per tree", 31, TypeConverters.to_int)
    maxDepth = Param("maxDepth", "Max tree depth (<=0: unlimited)", -1,
                     TypeConverters.to_int)
    maxBin = Param("maxBin", "Max feature bins", 255, TypeConverters.to_int)
    binSampleCount = Param("binSampleCount", "Rows sampled to pick bin boundaries",
                           200000, TypeConverters.to_int)
    baggingFraction = Param("baggingFraction", "Row subsample fraction", 1.0,
                            TypeConverters.to_float)
    baggingFreq = Param("baggingFreq", "Resample every k iterations (0=off)", 0,
                        TypeConverters.to_int)
    baggingSeed = Param("baggingSeed", "Bagging seed", 3, TypeConverters.to_int)
    featureFraction = Param("featureFraction", "Feature subsample per tree", 1.0,
                            TypeConverters.to_float)
    lambdaL1 = Param("lambdaL1", "L1 regularization", 0.0, TypeConverters.to_float)
    lambdaL2 = Param("lambdaL2", "L2 regularization", 0.0, TypeConverters.to_float)
    minDataInLeaf = Param("minDataInLeaf", "Minimum rows per leaf", 20,
                          TypeConverters.to_int)
    minSumHessianInLeaf = Param("minSumHessianInLeaf", "Minimum hessian sum per leaf",
                                1e-3, TypeConverters.to_float)
    minGainToSplit = Param("minGainToSplit", "Minimum gain to make a split", 0.0,
                           TypeConverters.to_float)
    earlyStoppingRound = Param("earlyStoppingRound",
                               "Stop if validation metric stalls this many rounds (0=off)",
                               0, TypeConverters.to_int)
    metricEvalPeriod = Param("metricEvalPeriod", "Evaluate metrics every k iterations",
                             1, TypeConverters.to_int)
    numBatches = Param("numBatches",
                       "Split data into sequential batches, warm-starting each "
                       "(reference: LightGBMBase.scala:28-50)", 0, TypeConverters.to_int)
    modelString = Param("modelString", "Warm-start model string", None,
                        TypeConverters.to_string)
    checkpointDir = Param("checkpointDir",
                          "Step-level checkpoint directory: training saves "
                          "every checkpointInterval iterations and resumes "
                          "from the newest checkpoint (preemption-safe; "
                          "extends the reference's model-level warm start)",
                          None, TypeConverters.to_string)
    checkpointInterval = Param("checkpointInterval",
                               "Iterations between checkpoints", 10,
                               TypeConverters.to_int)
    verbosity = Param("verbosity", "Log verbosity", -1, TypeConverters.to_int)
    growthPolicy = Param("growthPolicy",
                         "leafwise (LightGBM-parity best-first, batched: top "
                         "leafBatch pending leaves split per histogram pass) "
                         "or depthwise (TPU-throughput mode: one batched "
                         "histogram pass per level, num_leaves budget "
                         "enforced best-gain-first)", "leafwise",
                         TypeConverters.to_string)
    leafBatch = Param("leafBatch",
                      "Leafwise growth: pending leaves split per fused "
                      "histogram pass. Leaves' row sets are disjoint, so "
                      "batching only reorders splits near num_leaves "
                      "exhaustion; 1 = strict sequential best-first "
                      "(LightGBM's exact order)", 8, TypeConverters.to_int)
    # cluster-compat params: topology comes from the device mesh on TPU
    parallelism = Param("parallelism", "data_parallel or voting_parallel "
                        "(mesh collectives implement both)", "data_parallel",
                        TypeConverters.to_string)
    topK = Param("topK", "Features each shard votes for under voting_parallel "
                 "(reference: LightGBMConstants.scala:24 DefaultTopK)", 20,
                 TypeConverters.to_int)
    topRate = Param("topRate", "GOSS: top-gradient retain fraction", 0.2,
                    TypeConverters.to_float)
    otherRate = Param("otherRate", "GOSS: random retain fraction of the rest", 0.1,
                      TypeConverters.to_float)
    dropRate = Param("dropRate", "DART: per-tree dropout probability", 0.1,
                     TypeConverters.to_float)
    maxDrop = Param("maxDrop", "DART: max trees dropped per iteration", 50,
                    TypeConverters.to_int)
    skipDrop = Param("skipDrop", "DART: probability of skipping dropout for "
                     "an iteration", 0.5, TypeConverters.to_float)
    dropSeed = Param("dropSeed", "DART: dropout random seed", 4,
                     TypeConverters.to_int)
    defaultListenPort = Param("defaultListenPort", "Ignored on TPU (no socket ring)",
                              12400, TypeConverters.to_int)
    timeout = Param("timeout", "Ignored on TPU (no rendezvous)", 1200.0,
                    TypeConverters.to_float)
    useBarrierExecutionMode = Param("useBarrierExecutionMode",
                                    "Ignored: SPMD gang scheduling is inherent",
                                    False, TypeConverters.to_bool)
    boostFromAverage = Param("boostFromAverage", "Init score from label mean", True,
                             TypeConverters.to_bool)
    leafPredictionCol = Param(
        "leafPredictionCol", "If set, output per-tree leaf indices here "
        "(reference: LightGBMModelMethods predLeaf)", None, TypeConverters.to_string)
    featuresShapCol = Param(
        "featuresShapCol", "If set, output per-feature contributions here "
        "(reference: LightGBMBooster.scala:250-269). Computed per "
        "shapMethod: exact TreeSHAP by default", None,
        TypeConverters.to_string)
    shapMethod = Param(
        "shapMethod", "featuresShapCol algorithm: 'treeshap' (exact Shapley "
        "values, LightGBM native-TreeSHAP parity, host) or 'saabas' (fast "
        "on-device path attribution — sums to the prediction but deviates "
        "from Shapley on correlated features)", "treeshap",
        TypeConverters.to_string)
    categoricalSlotIndexes = Param(
        "categoricalSlotIndexes", "Feature-vector slots to treat as "
        "categorical (values are category ids; splits are LightGBM "
        "sorted-subset bitsets — reference: LightGBMParams "
        "categoricalSlotIndexes, core/schema/Categoricals.scala)", None)
    useQuantizedGrad = Param(
        "useQuantizedGrad", "Quantized-gradient histograms (LightGBM "
        "use_quantized_grad): int8 grad/hess with stochastic rounding ride "
        "the 2x-rate int8 MXU path", False, TypeConverters.to_bool)
    quantRenewLeaf = Param(
        "quantRenewLeaf", "With useQuantizedGrad: renew leaf outputs from "
        "the original f32 grad/hess after each quantized tree (LightGBM "
        "quant_train_renew_leaf) so leaf values carry no int8 error",
        True, TypeConverters.to_bool)
    quantWarmupIters = Param(
        "quantWarmupIters", "With useQuantizedGrad: run the first k "
        "boosting iterations at full precision before switching to int8 "
        "histograms — stabilizes early split selection on targets whose "
        "root-level gains are near zero (pure interactions)", 2,
        TypeConverters.to_int)
    binDtype = Param(
        "binDtype", "Storage dtype of the device-resident binned matrix: "
        "int32 (default), int16 or uint8. Bin ids are < maxBin, so narrow "
        "storage is lossless (training is bit-identical) and shrinks the "
        "HBM-resident dataset 2x/4x — the lever that fits Criteo-scale "
        "data on a pod (docs/performance.md)", "int32",
        TypeConverters.to_string)
    categoricalSlotNames = Param(
        "categoricalSlotNames", "Categorical slots by feature name; requires "
        "a featuresCol with slot names (use categoricalSlotIndexes for "
        "plain arrays)", None)
    improvementTolerance = Param(
        "improvementTolerance", "Early stopping: an iteration counts as "
        "improved only when it beats the best validation metric by more "
        "than this (reference: LightGBMParams improvementTolerance)", 0.0,
        TypeConverters.to_float)
    isProvideTrainingMetric = Param(
        "isProvideTrainingMetric", "Record the training-set metric every "
        "iteration into evalHistory['training_<metric>'] (reference: "
        "TrainParams isProvideTrainingMetric). gbdt/goss only; forces the "
        "per-iteration host loop instead of the fused dispatch", False,
        TypeConverters.to_bool)
    posBaggingFraction = Param(
        "posBaggingFraction", "Stratified bagging: keep probability for "
        "positive rows (binary only; set with negBaggingFraction and "
        "baggingFreq > 0)", 1.0, TypeConverters.to_float)
    negBaggingFraction = Param(
        "negBaggingFraction", "Stratified bagging: keep probability for "
        "negative rows (binary only)", 1.0, TypeConverters.to_float)
    maxDeltaStep = Param(
        "maxDeltaStep", "Clamp each leaf's raw output to +-this before "
        "shrinkage (0 = off; stabilizes poisson / highly imbalanced "
        "binary)", 0.0, TypeConverters.to_float)
    maxBinByFeature = Param(
        "maxBinByFeature", "Per-feature max bin counts (list as long as "
        "the feature vector; each capped by maxBin)", None)
    metric = Param(
        "metric", "Validation/early-stopping metric override (reference: "
        "LightGBMParams metric). Per objective family: binary -> "
        "binary_logloss | binary_error | auc; multiclass -> multi_logloss "
        "| multi_error; regression family -> rmse/l2 | mae/l1; ranker -> "
        "ndcg. auc computes the exact weighted rank statistic on host",
        None, TypeConverters.to_string)
    slotNames = Param(
        "slotNames", "Feature names for the feature-vector slots — flow "
        "into the native model string's feature_names and importances "
        "(reference: LightGBMParams slotNames)", None)
    driverListenPort = Param(
        "driverListenPort", "Ignored on TPU (no driver rendezvous socket)",
        0, TypeConverters.to_int)
    numTasks = Param(
        "numTasks", "Ignored on TPU: shard count comes from the device "
        "mesh (reference capped Spark task count)", 0,
        TypeConverters.to_int)
    repartitionByGroupingColumn = Param(
        "repartitionByGroupingColumn", "Ignored on TPU: the ranker pads "
        "and shards whole groups itself, so group alignment never depends "
        "on input partitioning", True, TypeConverters.to_bool)

    def _grow_config(self) -> GrowConfig:
        return GrowConfig(
            num_leaves=self.get_or_default("numLeaves"),
            max_depth=self.get_or_default("maxDepth"),
            num_bins=self.get_or_default("maxBin"),
            learning_rate=self.get_or_default("learningRate"),
            lambda_l1=self.get_or_default("lambdaL1"),
            lambda_l2=self.get_or_default("lambdaL2"),
            min_data_in_leaf=self.get_or_default("minDataInLeaf"),
            min_sum_hessian_in_leaf=self.get_or_default("minSumHessianInLeaf"),
            min_gain_to_split=self.get_or_default("minGainToSplit"),
            voting=self.get_or_default("parallelism") == "voting_parallel",
            top_k=self.get_or_default("topK"),
            growth_policy=self.get_or_default("growthPolicy"),
            leaf_batch=self.get_or_default("leafBatch"),
            quantized_grad=self.get_or_default("useQuantizedGrad"),
            quant_renew_leaf=self.get_or_default("quantRenewLeaf"),
            quant_warmup_iters=self.get_or_default("quantWarmupIters"),
            max_delta_step=self.get_or_default("maxDeltaStep"),
        )

    def _extract_arrays(self, dataset: Dataset):
        fcol = self.get_or_default("featuresCol")
        raw = dataset[fcol]
        # sparse CSR features pass through untouched (train_booster densifies
        # per row block — LGBM_DatasetCreateFromCSR parity)
        X = raw if _is_sparse(raw) else dataset.array(fcol, np.float32)
        y = dataset.array(self.get_or_default("labelCol"), np.float32)
        wcol = self.get_or_default("weightCol")
        w = dataset.array(wcol, np.float32) if wcol else None
        return X, y, w

    def _categorical_indexes(self):
        if self.get_or_default("categoricalSlotNames"):
            raise ValueError(
                "categoricalSlotNames requires named feature slots; this "
                "columnar Dataset API carries plain arrays — use "
                "categoricalSlotIndexes")
        idx = self.get_or_default("categoricalSlotIndexes")
        return tuple(int(i) for i in idx) if idx else ()

    def _split_validation(self, dataset: Dataset):
        """validationIndicatorCol semantics (reference: LightGBMBase.scala:214-219)."""
        vcol = self.get_or_default("validationIndicatorCol")
        if not vcol or vcol not in dataset:
            return dataset, None
        mask = dataset.array(vcol).astype(bool)
        return dataset.filter(~mask), dataset.filter(mask)

    def _round_callback(self):
        """Per-boost-round telemetry callback, or None.

        Opt-in via MMLSPARK_TPU_TELEMETRY_ROUNDS=1: a non-None
        iteration_callback forces train_booster onto its host loop (one
        device dispatch per round), so round-level spans must never be the
        silent default — the fused single-dispatch paths are the product.
        """
        if not (_metrics.enabled()
                and os.environ.get("MMLSPARK_TPU_TELEMETRY_ROUNDS") == "1"):
            return None
        cls = type(self).__name__
        import time as _time
        last = [_time.perf_counter()]

        def cb(it: int, round_metrics: dict) -> None:
            vals = {k: float(v) for k, v in round_metrics.items()}
            _spans.instant("boost_round", model=cls, iteration=it, **vals)
            _metrics.safe_counter("gbdt_boost_rounds_total", model=cls).inc()
            # live training-health sentinels: per-round loss (NaN /
            # divergence) and round wall time (throughput collapse)
            now = _time.perf_counter()
            _watchdog.report_training_metric(cls, it, seconds=now - last[0])
            last[0] = now
            for k, v in vals.items():
                _metrics.safe_gauge("gbdt_round_metric",
                                    model=cls, metric=k).set(v)
                _watchdog.report_training_metric(cls, it, loss=v,
                                                 metric_name=k)
        return cb

    def _publish_booster_telemetry(self, booster: Booster) -> None:
        """Registry view of a finished fit: round count, best iteration,
        final value of each tracked loss/metric series, and a fresh HBM
        sample (the binned-dataset cache retains device memory across fits
        — exactly the growth device_memory_bytes should make visible)."""
        if not _metrics.enabled():
            return
        cls = type(self).__name__
        _metrics.safe_counter("gbdt_fits_total", model=cls).inc()
        _metrics.safe_gauge("gbdt_trained_iterations",
                            model=cls).set(booster.num_iterations)
        if booster.best_iteration is not None and booster.best_iteration >= 0:
            _metrics.safe_gauge("gbdt_best_iteration",
                                model=cls).set(booster.best_iteration)
        for mname, series in (booster.eval_history or {}).items():
            if series:
                _metrics.safe_gauge("gbdt_train_metric", model=cls,
                                    metric=str(mname)).set(float(series[-1]))
        # post-fit health audit: NaN / divergence anywhere in the metric
        # history flips training_health{model} — this is the path that
        # covers the fused single-dispatch fits, which have no rounds
        _watchdog.scan_eval_history(cls, booster.eval_history)
        from ...observability.device import device_memory_gauges
        device_memory_gauges()

    def _fit_booster(self, dataset: Dataset, objective: str, num_class: int,
                     objective_kwargs: Optional[dict] = None) -> Booster:
        cls = type(self).__name__
        # fresh sentinel windows for this estimator's health stream (the
        # booster-level "gbdt" stream resets inside train_booster)
        _watchdog.reset_training_health(cls)
        with _spans.span(f"{self.uid}.train_booster",
                         metric_label=f"{cls}.train_booster",
                         objective=objective, num_class=num_class):
            booster = self._fit_booster_impl(dataset, objective, num_class,
                                             objective_kwargs)
        self._publish_booster_telemetry(booster)
        return booster

    def _fit_booster_impl(self, dataset: Dataset, objective: str,
                          num_class: int,
                          objective_kwargs: Optional[dict] = None) -> Booster:
        train_ds, valid_ds = self._split_validation(dataset)
        X, y, w = self._extract_arrays(train_ds)
        valid_set = None
        if valid_ds is not None and len(valid_ds) > 0:
            valid_set = self._extract_arrays(valid_ds)

        init_booster = None
        ms = self.get_or_default("modelString")
        if ms:
            init_booster = Booster.from_string(ms)

        num_batches = self.get_or_default("numBatches")
        common = dict(
            checkpoint_dir=self.get_or_default("checkpointDir"),
            checkpoint_period=self.get_or_default("checkpointInterval"),
            objective=objective, num_class=num_class,
            cfg=self._grow_config(),
            max_bin=self.get_or_default("maxBin"),
            bin_sample_count=self.get_or_default("binSampleCount"),
            feature_fraction=self.get_or_default("featureFraction"),
            bagging_fraction=self.get_or_default("baggingFraction"),
            bagging_freq=self.get_or_default("baggingFreq"),
            seed=self.get_or_default("baggingSeed"),
            early_stopping_rounds=self.get_or_default("earlyStoppingRound"),
            metric_eval_period=self.get_or_default("metricEvalPeriod"),
            boost_from_average=self.get_or_default("boostFromAverage"),
            objective_kwargs=objective_kwargs or {},
            boosting_type=self.get_or_default("boostingType"),
            top_rate=self.get_or_default("topRate"),
            other_rate=self.get_or_default("otherRate"),
            drop_rate=self.get_or_default("dropRate"),
            max_drop=self.get_or_default("maxDrop"),
            skip_drop=self.get_or_default("skipDrop"),
            drop_seed=self.get_or_default("dropSeed"),
            categorical_features=self._categorical_indexes(),
            bin_dtype=self.get_or_default("binDtype"),
            pos_bagging_fraction=self.get_or_default("posBaggingFraction"),
            neg_bagging_fraction=self.get_or_default("negBaggingFraction"),
            early_stopping_tolerance=self.get_or_default(
                "improvementTolerance"),
            provide_training_metric=self.get_or_default(
                "isProvideTrainingMetric"),
            max_bin_by_feature=self.get_or_default("maxBinByFeature"),
            eval_metric_name=self.get_or_default("metric"),
            # None unless MMLSPARK_TPU_TELEMETRY_ROUNDS=1: a live callback
            # forces the host loop, so fused dispatch stays the default
            iteration_callback=self._round_callback(),
        )
        num_iterations = self.get_or_default("numIterations")
        if (num_batches and num_batches > 1
                and common["boosting_type"] in ("rf", "dart")):
            # fail before batch 0 trains: batch 1 would reject the warm start
            raise ValueError(
                f"numBatches > 1 is not supported with boostingType="
                f"{common['boosting_type']!r} (its trees carry normalization "
                "state that a warm-start prefix lacks)")
        if num_batches and num_batches > 1:
            # sequential warm-started batches (reference: LightGBMBase.scala:28-50)
            n = len(y)
            bounds = np.linspace(0, n, num_batches + 1).astype(int)
            booster = init_booster
            base_ckpt = common.get("checkpoint_dir")
            for i in range(num_batches):
                sl = slice(bounds[i], bounds[i + 1])
                if base_ckpt:
                    # one subdir per batch: batch i must never resume from
                    # batch i-1's mid-train checkpoint
                    common["checkpoint_dir"] = os.path.join(
                        base_ckpt, f"batch_{i:04d}")
                booster = train_booster(
                    X[sl], y[sl], None if w is None else w[sl],
                    num_iterations=num_iterations, valid_set=valid_set,
                    init_booster=booster, **common)
            return self._apply_slot_names(booster)
        if common["checkpoint_dir"] is None:
            # sweep fast path: reuse the binned device dataset across fits
            # on identical data + binning params (content-fingerprint keyed)
            dset = _cached_binned_dataset(
                X, y, w,
                max_bin=common["max_bin"],
                bin_sample_count=common["bin_sample_count"],
                seed=common["seed"],
                categorical_features=common["categorical_features"],
                bin_dtype=common["bin_dtype"],
                max_bin_by_feature=common["max_bin_by_feature"])
            return self._apply_slot_names(train_booster(
                X=X if init_booster is not None else None,
                dataset=dset, num_iterations=num_iterations,
                valid_set=valid_set, init_booster=init_booster, **common))
        return self._apply_slot_names(train_booster(
            X, y, w, num_iterations=num_iterations,
            valid_set=valid_set, init_booster=init_booster, **common))

    def _apply_slot_names(self, booster: Booster) -> Booster:
        """Record slotNames as the model's feature names (they flow into
        the native model string; reference: LightGBMParams slotNames)."""
        names = self.get_or_default("slotNames")
        if names:
            F = booster.binner_state.get("num_features")
            if F is not None and len(names) != F:
                raise ValueError(
                    f"slotNames has {len(names)} entries for {F} features")
            names = [str(x) for x in names]
            bad = [x for x in names if not x or any(c.isspace() for c in x)]
            if bad:
                # the native text format is whitespace-delimited
                raise ValueError(
                    f"slotNames must be non-empty and whitespace-free for "
                    f"native-model interop; got {bad[:3]}")
            booster.binner_state["feature_names"] = names
        return booster


class _LightGBMModelBase(Model, _LightGBMParams):
    """Shared trained-model behavior (importances, native model export)."""

    def __init__(self, booster: Optional[Booster] = None, **kwargs):
        super().__init__(**kwargs)
        self.booster = booster

    def _add_introspection_cols(self, dataset: Dataset, X) -> Dataset:
        leaf_col = self.get_or_default("leafPredictionCol")
        if leaf_col:
            dataset = dataset.with_column(
                leaf_col, self.booster.predict_leaf(X).astype(np.float64))
        shap_col = self.get_or_default("featuresShapCol")
        if shap_col:
            dataset = dataset.with_column(
                shap_col, self.booster.predict_contrib(
                    X, method=self.get_or_default("shapMethod")
                ).astype(np.float64))
        return dataset

    def get_feature_importances(self, importance_type: str = "split"):
        return self.booster.feature_importances(importance_type).tolist()

    def get_native_model(self) -> str:
        """The model as a stock-LightGBM text string (loads in any LightGBM
        tooling; reference: LightGBMModelMethods getNativeModel)."""
        return self.booster.to_lightgbm_string()

    def save_native_model(self, path: str) -> None:
        """reference: LightGBMClassifier.scala:172-194 saveNativeModel —
        writes the LightGBM ``tree`` v3 text format for tool interop."""
        with open(path, "w") as f:
            f.write(self.booster.to_lightgbm_string())

    def _save_extra(self, path: str) -> None:
        import os
        self.booster.save(os.path.join(path, "booster"))

    def _load_extra(self, path: str) -> None:
        import os
        self.booster = Booster.load(os.path.join(path, "booster"))


class LightGBMClassifier(Estimator, _LightGBMParams, HasRawPredictionCol,
                         HasProbabilityCol):
    """Distributed GBDT classifier (reference: lightgbm/LightGBMClassifier.scala:24-66).

    HBM note: ``fit`` caches the binned device dataset (two fits, LRU) so
    sweeps skip re-ingest; the cache pins up to two [F, n] int32 matrices in
    device memory after training ends. Call
    :func:`clear_binned_dataset_cache` to release them, or set
    ``MMLSPARK_TPU_BINNED_CACHE=0`` to disable the cache entirely.
    """

    objective = Param("objective", "binary or multiclass (auto from label arity)",
                      None, TypeConverters.to_string)
    isUnbalance = Param("isUnbalance", "Upweight the minority class (binary)", False,
                        TypeConverters.to_bool)
    thresholds = Param("thresholds", "Per-class prediction thresholds", None,
                       TypeConverters.to_list_float)

    def fit(self, dataset: Dataset) -> "LightGBMClassificationModel":
        y = dataset.array(self.get_or_default("labelCol"))
        classes = np.unique(y[~np.isnan(y.astype(np.float64))])
        if classes.size and (classes.min() < 0 or
                             not np.allclose(classes, classes.astype(int))):
            raise ValueError(
                "labels must be non-negative integers 0..k-1 (use ValueIndexer "
                f"or TrainClassifier to index them); got values {classes[:5]}")
        # num_class from the max label so non-contiguous labels (e.g. {0, 2})
        # are handled as multiclass rather than silently treated as binary
        num_class = max(int(classes.max()) + 1 if classes.size else 2, 2)
        obj = self.get_or_default("objective")
        if obj is None:
            obj = "binary" if num_class <= 2 else "multiclass"
        if obj == "binary" and num_class > 2:
            raise ValueError(
                f"binary objective needs labels in {{0,1}}, got {num_class} classes")
        kwargs = {}
        if obj == "binary" and self.get_or_default("isUnbalance"):
            pos = float((y > 0).sum())
            neg = float(len(y) - pos)
            kwargs["pos_weight"] = neg / max(pos, 1.0)
        booster = self._fit_booster(
            dataset, obj, num_class if obj == "multiclass" else 1, kwargs)
        model = LightGBMClassificationModel(booster, numClasses=num_class)
        self._copy_params_to(model)
        return model


class LightGBMClassificationModel(_LightGBMModelBase, HasRawPredictionCol,
                                  HasProbabilityCol):
    numClasses = Param("numClasses", "Number of classes", 2, TypeConverters.to_int)
    thresholds = Param("thresholds", "Per-class prediction thresholds", None,
                       TypeConverters.to_list_float)

    def get_actual_num_classes(self) -> int:
        """reference: LightGBMClassificationModel actualNumClasses —
        the class count the trained booster actually models."""
        return max(self.booster.num_class, 2)

    def transform(self, dataset: Dataset) -> Dataset:
        X = _features_dense(dataset, self.get_or_default("featuresCol"))
        raw = self.booster.predict_raw(X)  # [n, K]
        K = self.get_or_default("numClasses")
        if self.booster.num_class == 1:  # binary: margin for [neg, pos]
            margins = np.concatenate([-raw, raw], axis=1)
            p1 = 1.0 / (1.0 + np.exp(-raw[:, 0]))
            probs = np.stack([1 - p1, p1], axis=1)
        else:
            margins = raw
            e = np.exp(raw - raw.max(axis=1, keepdims=True))
            probs = e / e.sum(axis=1, keepdims=True)
        th = self.get_or_default("thresholds")
        scaled = probs / np.asarray(th)[None, :] if th else probs
        pred = scaled.argmax(axis=1).astype(np.float64)
        out = dataset.with_columns({
            self.get_or_default("rawPredictionCol"): margins,
            self.get_or_default("probabilityCol"): probs,
            self.get_or_default("predictionCol"): pred,
        })
        return self._add_introspection_cols(out, X)

    @staticmethod
    def load_native_model(path: str) -> "LightGBMClassificationModel":
        with open(path) as f:
            booster = Booster.from_string(f.read())
        k = 2 if booster.num_class == 1 else booster.num_class
        return LightGBMClassificationModel(booster, numClasses=k)


class LightGBMRegressor(Estimator, _LightGBMParams):
    """Distributed GBDT regressor (reference: lightgbm/LightGBMRegressor.scala;
    objectives per TrainParams.scala:86-104).

    HBM note: ``fit`` caches binned device datasets — see
    :class:`LightGBMClassifier` for the retention/release story.
    """

    objective = Param("objective", "regression|regression_l1|huber|fair|poisson|"
                      "quantile|mape|tweedie", "regression", TypeConverters.to_string)
    alpha = Param("alpha", "Huber/quantile alpha", 0.9, TypeConverters.to_float)
    tweedieVariancePower = Param("tweedieVariancePower",
                                 "Tweedie variance power in [1, 2)", 1.5,
                                 TypeConverters.to_float)

    def fit(self, dataset: Dataset) -> "LightGBMRegressionModel":
        obj = self.get_or_default("objective")
        kwargs = {}
        if obj in ("huber", "quantile"):
            kwargs["alpha"] = self.get_or_default("alpha")
        if obj == "tweedie":
            kwargs["tweedie_variance_power"] = self.get_or_default("tweedieVariancePower")
        booster = self._fit_booster(dataset, obj, 1, kwargs)
        model = LightGBMRegressionModel(booster)
        self._copy_params_to(model)
        return model


class LightGBMRegressionModel(_LightGBMModelBase):
    def transform(self, dataset: Dataset) -> Dataset:
        X = _features_dense(dataset, self.get_or_default("featuresCol"))
        pred = self.booster.predict(X).astype(np.float64)
        out = dataset.with_column(self.get_or_default("predictionCol"), pred)
        return self._add_introspection_cols(out, X)

    @staticmethod
    def load_native_model(path: str) -> "LightGBMRegressionModel":
        with open(path) as f:
            return LightGBMRegressionModel(Booster.from_string(f.read()))


def _features_dense(dataset: Dataset, col: str) -> np.ndarray:
    """Features column as dense float32 (scoring path accepts the same
    sparse CSR input fit does)."""
    from .booster import _densify
    raw = dataset[col]
    if _is_sparse(raw):
        return _densify(raw)
    return dataset.array(col, np.float32)


def _pad_groups(X: np.ndarray, y: np.ndarray, w: Optional[np.ndarray],
                group: np.ndarray, S: int, n_shard_multiple: int):
    """Sort rows by group and pad every query group to a static width S.

    The TPU replacement for the reference's group-aware repartition
    (lightgbm/LightGBMRanker.scala:80-98 keeps each query's rows inside one
    partition): each group becomes a fixed [S] block, groups are padded to a
    multiple of the shard count, so shard boundaries never cut a group and
    every shard sees an identical static shape.

    Returns (Xp, yp, wp, valid, n_groups) with Xp of shape [G_pad*S, F].
    """
    group = np.asarray(group)
    order = np.argsort(group, kind="stable")
    X, y = X[order], y[order]
    w = None if w is None else w[order]
    _, starts, counts = np.unique(group[order], return_index=True,
                                  return_counts=True)
    G = len(starts)
    G_pad = -(-G // n_shard_multiple) * n_shard_multiple
    F = X.shape[1]
    Xp = np.zeros((G_pad * S, F), dtype=np.float32)
    yp = np.zeros(G_pad * S, dtype=np.float32)
    wp = np.zeros(G_pad * S, dtype=np.float32)
    valid = np.zeros(G_pad * S, dtype=np.float32)
    for g in range(G):
        c = min(int(counts[g]), S)  # truncate oversize groups
        sl = slice(starts[g], starts[g] + c)
        dst = slice(g * S, g * S + c)
        Xp[dst], yp[dst] = X[sl], y[sl]
        wp[dst] = 1.0 if w is None else w[sl]
        valid[dst] = 1.0
    return Xp, yp, wp, valid, G


class LightGBMRanker(Estimator, _LightGBMParams, HasGroupCol):
    """Distributed LambdaRank (reference: lightgbm/LightGBMRanker.scala).

    HBM note: ``fit`` caches binned device datasets — see
    :class:`LightGBMClassifier` for the retention/release story.

    Groups are padded to ``maxGroupSize`` static blocks so the pairwise
    lambda computation is one dense MXU batch; each shard holds whole groups
    (the reference's group-aware repartition, LightGBMRanker.scala:80-98).
    """

    objective = Param("objective", "ranking objective", "lambdarank",
                      TypeConverters.to_string)
    labelGain = Param("labelGain", "NDCG gain per relevance grade: grade "
                      "g scores labelGain[g] (reference: LightGBMRanker "
                      "labelGain; default 2^g - 1)", None,
                      TypeConverters.to_list_float)
    maxPosition = Param("maxPosition", "NDCG truncation position "
                        "(reference: TrainParams maxPosition)", 20,
                        TypeConverters.to_int)
    evalAt = Param("evalAt", "Positions for NDCG evaluation", [1, 3, 5, 10],
                   TypeConverters.to_list_int)
    maxGroupSize = Param("maxGroupSize",
                         "Static padded width per query group (rows beyond "
                         "this are truncated)", 128, TypeConverters.to_int)
    sigma = Param("sigma", "LambdaRank sigmoid steepness", 1.0,
                  TypeConverters.to_float)

    def fit(self, dataset: Dataset) -> "LightGBMRankerModel":
        from ...parallel import mesh as meshlib

        train_ds, valid_ds = self._split_validation(dataset)
        gcol = self.get_or_default("groupCol")
        if not gcol:
            raise ValueError("LightGBMRanker requires groupCol")
        nshards = meshlib.num_shards(meshlib.get_default_mesh())

        X, y, w = self._extract_arrays(train_ds)
        from .booster import _densify
        X = _densify(X)            # ranker pads groups before train_booster
        group = np.asarray(train_ds[gcol])
        sizes = np.unique(group, return_counts=True)[1]
        S = int(min(self.get_or_default("maxGroupSize"),
                    1 << int(np.ceil(np.log2(max(sizes.max(), 2))))))
        Xp, yp, wp, valid, _ = _pad_groups(X, y, w, group, S, nshards)

        valid_set = None
        if valid_ds is not None and len(valid_ds) > 0:
            Xv, yv, _ = self._extract_arrays(valid_ds)
            Xv = _densify(Xv)
            gv = np.asarray(valid_ds[gcol])
            Xvp, yvp, _, validv, _ = _pad_groups(Xv, yv, None, gv, S, nshards)
            # per-row metric weight 1/group_size -> weighted mean == mean NDCG
            # over groups (see objectives._ndcg_metric)
            gsz = validv.reshape(-1, S).sum(axis=1)
            wv = (validv.reshape(-1, S)
                  / np.maximum(gsz, 1.0)[:, None]).reshape(-1)
            valid_set = (Xvp, yvp, wv.astype(np.float32))

        eval_at = self.get_or_default("evalAt") or []
        kwargs = dict(group_size=S,
                      max_position=self.get_or_default("maxPosition"),
                      sigma=self.get_or_default("sigma"),
                      eval_at=int(max(eval_at)) if eval_at else 0)
        lg = self.get_or_default("labelGain")
        if lg is not None:
            # LightGBM fails fast when a label grade exceeds the gain
            # table; silent clamping would train against wrong gains
            max_grade = int(np.nanmax(yp)) if len(yp) else 0
            if max_grade >= len(lg):
                raise ValueError(
                    f"labelGain has {len(lg)} entries but the data "
                    f"contains relevance grade {max_grade}")
            # tuple: objective_kwargs flow into hashed program-cache keys
            kwargs["label_gain"] = tuple(float(g) for g in lg)
        booster = train_booster(
            Xp, yp, wp,
            objective="lambdarank", num_class=1,
            cfg=self._grow_config(),
            max_bin=self.get_or_default("maxBin"),
            bin_sample_count=self.get_or_default("binSampleCount"),
            feature_fraction=self.get_or_default("featureFraction"),
            bagging_fraction=self.get_or_default("baggingFraction"),
            bagging_freq=self.get_or_default("baggingFreq"),
            seed=self.get_or_default("baggingSeed"),
            num_iterations=self.get_or_default("numIterations"),
            valid_set=valid_set,
            early_stopping_rounds=self.get_or_default("earlyStoppingRound"),
            early_stopping_tolerance=self.get_or_default(
                "improvementTolerance"),
            provide_training_metric=self.get_or_default(
                "isProvideTrainingMetric"),
            max_bin_by_feature=self.get_or_default("maxBinByFeature"),
            eval_metric_name=self.get_or_default("metric"),
            metric_eval_period=self.get_or_default("metricEvalPeriod"),
            boost_from_average=False,
            objective_kwargs=kwargs,
            row_valid=valid,
            boosting_type=self.get_or_default("boostingType"),
        )
        booster = self._apply_slot_names(booster)
        model = LightGBMRankerModel(booster)
        self._copy_params_to(model)
        return model


class LightGBMRankerModel(_LightGBMModelBase):
    def transform(self, dataset: Dataset) -> Dataset:
        X = _features_dense(dataset, self.get_or_default("featuresCol"))
        score = self.booster.predict_raw(X)[:, 0].astype(np.float64)
        out = dataset.with_column(self.get_or_default("predictionCol"), score)
        return self._add_introspection_cols(out, X)

    @staticmethod
    def load_native_model(path: str) -> "LightGBMRankerModel":
        with open(path) as f:
            return LightGBMRankerModel(Booster.from_string(f.read()))
