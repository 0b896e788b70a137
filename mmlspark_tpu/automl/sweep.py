"""Trial-parallel GBDT hyperparameter sweeps: one device dispatch, N models.

The reference parallelizes TuneHyperparameters trials across a Spark thread
pool (reference: automl/TuneHyperparameters.scala:100-160 — awaitable futures
over a fixed pool). The TPU-first equivalent (SURVEY §2b "vmapped/multi-slice
sweeps") runs the trials INSIDE one compiled program: the binned dataset is
replicated, the trial axis is sharded over the mesh's ``data`` axis, and each
device vmaps its slice of trial configs through the shared boosting loop.
Continuous hyperparameters (learning rate, regularization, split thresholds)
become traced scalars, so the sweep compiles ONCE for any number of trials —
the sequential path recompiles per distinct GrowConfig.

Only a restricted estimator envelope is vmappable (plain gbdt boosting, full
rows/features each iteration, K=1 objectives, no early stopping / warm start /
checkpoints); :func:`swept_fit` returns None outside it and the caller falls
back to sequential fits.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from ..parallel.placement import pspec as P

from ..core.dataset import Dataset
from ..parallel.compat import shard_map

# estimator param -> GrowConfig field. All are used only inside jnp ops in
# growth.py (verified: no Python-level branching), so they can be traced.
SWEEPABLE: Dict[str, str] = {
    "learningRate": "learning_rate",
    "lambdaL1": "lambda_l1",
    "lambdaL2": "lambda_l2",
    "minGainToSplit": "min_gain_to_split",
    "minSumHessianInLeaf": "min_sum_hessian_in_leaf",
    "minDataInLeaf": "min_data_in_leaf",
}


def _eligible(est, param_maps: List[Dict[str, Any]]) -> bool:
    """True when ``est`` + the swept params fit the vmapped envelope."""
    from ..models.gbdt.api import LightGBMClassifier, LightGBMRegressor

    if not isinstance(est, (LightGBMClassifier, LightGBMRegressor)):
        return False
    if not param_maps or not all(set(m) <= set(SWEEPABLE)
                                 for m in param_maps):
        return False
    g = est.get_or_default
    if g("boostingType") != "gbdt":
        return False
    if (g("baggingFraction") < 1.0 or g("posBaggingFraction") < 1.0
            or g("negBaggingFraction") < 1.0 or g("featureFraction") < 1.0):
        return False
    if g("useQuantizedGrad"):
        return False
    if g("earlyStoppingRound") > 0 or g("isProvideTrainingMetric"):
        return False
    if g("modelString") or g("checkpointDir") or g("initScoreCol"):
        return False
    if g("validationIndicatorCol") or (g("numBatches") or 0) > 1:
        return False
    return True


def _objective_of(est, y: np.ndarray):
    """(objective, num_class, kwargs, model_factory) mirroring est.fit."""
    from ..models.gbdt.api import (LightGBMClassificationModel,
                                   LightGBMClassifier,
                                   LightGBMRegressionModel)

    if isinstance(est, LightGBMClassifier):
        classes = np.unique(y[~np.isnan(y.astype(np.float64))])
        num_class = max(int(classes.max()) + 1 if classes.size else 2, 2)
        obj = est.get_or_default("objective") or (
            "binary" if num_class <= 2 else "multiclass")
        if obj != "binary" or num_class > 2:
            return None          # K>1: outside the vmapped envelope
        kwargs = {}
        if est.get_or_default("isUnbalance"):
            pos = float((y > 0).sum())
            kwargs["pos_weight"] = (len(y) - pos) / max(pos, 1.0)
        return obj, num_class, kwargs, (
            lambda b: LightGBMClassificationModel(b, numClasses=num_class))
    obj = est.get_or_default("objective")
    kwargs = {}
    if obj in ("huber", "quantile"):
        kwargs["alpha"] = est.get_or_default("alpha")
    if obj == "tweedie":
        kwargs["tweedie_variance_power"] = est.get_or_default(
            "tweedieVariancePower")
    return obj, 1, kwargs, LightGBMRegressionModel


def swept_fit(est, param_maps: List[Dict[str, Any]],
              train: Dataset) -> Optional[List[Any]]:
    """Fit one model per param map in a single trial-sharded dispatch.

    Returns fitted models (the same classes ``est.fit`` produces, params
    copied from ``est.copy(param_map)``), or None when the estimator/params
    fall outside the vmappable envelope. Trials train on REPLICATED rows
    with per-trial traced hyperparameters — numerically this matches a
    sequential fit on a single-device mesh exactly (same reduction order);
    a sequential fit on a sharded mesh differs only by psum float ordering.
    """
    from ..models.gbdt.api import _cached_binned_dataset
    from ..models.gbdt.booster import _finalize_trees
    from ..models.gbdt.growth import (GrowConfig, grow_tree,
                                      grow_tree_depthwise)
    from ..models.gbdt.objectives import get_objective
    from ..parallel import mesh as meshlib

    if not _eligible(est, param_maps):
        return None
    X, y, w = est._extract_arrays(train)
    base_cfg: GrowConfig = est._grow_config()
    objinfo = _objective_of(est, y)
    if objinfo is None:
        return None
    objective, _num_class, obj_kwargs, model_factory = objinfo
    obj = get_objective(objective, 1, **obj_kwargs)
    if obj.num_scores != 1:
        return None
    max_bin = est.get_or_default("maxBin")
    num_iterations = est.get_or_default("numIterations")
    ds = _cached_binned_dataset(
        X, y, w, max_bin=max_bin,
        bin_sample_count=est.get_or_default("binSampleCount"),
        seed=est.get_or_default("baggingSeed"),
        categorical_features=est._categorical_indexes(),
        bin_dtype=est.get_or_default("binDtype"),
        max_bin_by_feature=est.get_or_default("maxBinByFeature"))
    binner = ds.binner
    cfg = base_cfg._replace(num_bins=ds.max_bin)
    is_cat_np = binner.is_cat_mask()
    is_cat_j = jnp.asarray(is_cat_np) if is_cat_np.any() else None

    # replicated copies of the (possibly sharded) binned dataset
    Xbt = np.asarray(ds.Xbt_d)
    yl = np.asarray(ds.y_d)
    wl = np.asarray(ds.w_d)
    vmask = np.asarray(ds.vmask_d)
    F, n_pad = Xbt.shape

    if est.get_or_default("boostFromAverage"):
        base = float(obj.init_score(jnp.asarray(yl),
                                    jnp.asarray(wl * vmask)))
    else:
        base = 0.0

    mesh = meshlib.get_default_mesh()
    axis = mesh.axis_names[0]
    D = mesh.shape[axis]
    # placement decision: the sweep replicates the DATASET and shards the
    # TRIAL axis — the inverse of the training-path row sharding
    from ..parallel import placement
    placement.plan_for("automl.sweep", mesh=mesh, replicate=True,
                       what="trial_axis_sharded")
    T = len(param_maps)
    T_pad = -(-T // D) * D

    # stacked per-trial values; unswept trials keep the estimator's value
    fields = sorted({k for m in param_maps for k in m})
    defaults = {k: float(est.get_or_default(k)) for k in fields}
    hp = {k: np.asarray(
        [float(param_maps[min(t, T - 1)].get(k, defaults[k]))
         for t in range(T_pad)], np.float32) for k in fields}

    grow = (grow_tree_depthwise if cfg.growth_policy == "depthwise"
            else grow_tree)

    def local(Xbt_l, yl_l, wl_l, vm_l, *hp_vals):
        def one(*hp1):
            cfg_t = cfg._replace(
                **{SWEEPABLE[k]: hp1[i] for i, k in enumerate(fields)})
            fmask = jnp.ones(F, dtype=bool)
            scores0 = jnp.full((n_pad,), jnp.float32(base))

            def it_body(sc, _it):
                g, h = obj.grad_hess(sc, yl_l, wl_l)
                tree, row_node = grow(Xbt_l, g, h, vm_l, fmask, cfg_t,
                                      axis_name=None, is_cat=is_cat_j,
                                      qkey=None)
                return sc + tree.leaf_value[row_node], tree

            _, trees = lax.scan(
                it_body, scores0,
                jnp.arange(num_iterations, dtype=jnp.int32))
            return trees                      # pytree: [iters, ...]

        return jax.vmap(one)(*hp_vals)        # pytree: [T_pad/D, iters, ...]

    fit_all = jax.jit(shard_map(
        local, mesh=mesh,
        in_specs=(P(), P(), P(), P()) + (P(axis),) * len(fields),
        out_specs=P(axis), check_vma=False))
    trees_dev = fit_all(Xbt, yl, wl, vmask, *(hp[k] for k in fields))
    trees_np = jax.tree_util.tree_map(np.asarray, trees_dev)

    depth_cap = cfg.max_depth if cfg.max_depth > 0 else max(
        1, cfg.num_leaves - 1)
    depth_cap = min(depth_cap, 2 * cfg.num_leaves)
    base_arr = np.asarray([base], np.float32)

    models = []
    for t in range(T):
        trees_list = [
            jax.tree_util.tree_map(lambda a, _t=t, _i=i: a[_t, _i],
                                   trees_np)
            for i in range(num_iterations)]
        booster = _finalize_trees(
            trees_list, binner, ds.max_bin, 1, base_arr, objective,
            depth_cap, obj_kwargs, -1, {}, None)
        trial = est.copy({k: v for k, v in param_maps[t].items()
                          if est.has_param(k)})
        model = model_factory(trial._apply_slot_names(booster))
        trial._copy_params_to(model)
        models.append(model)
    return models
