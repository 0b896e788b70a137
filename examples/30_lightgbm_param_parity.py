"""Example 30: the LightGBM param-surface tail, end to end.

Every training param of the reference's LightGBMParams.scala maps here by
name (docs/lightgbm.md "Param surface completeness"). This example drives
the long tail added in round 4 on one model: eval-metric override with
AUC-based early stopping, stratified bagging, per-feature bin caps,
leaf-output clamping, per-iteration training metric, named feature slots
flowing into the exported native model.
"""

import numpy as np

from mmlspark_tpu.core.dataset import Dataset
from mmlspark_tpu.models.gbdt.api import LightGBMClassifier


def main():
    rng = np.random.default_rng(0)
    n = 6000
    # an imbalanced binary problem with one low-cardinality feature
    age = rng.integers(18, 26, n).astype(np.float32)        # 8 values
    income = rng.lognormal(0, 1, n).astype(np.float32)
    score = rng.normal(size=n).astype(np.float32)
    y = ((income * 0.8 + score > 2.2)
         | (rng.random(n) < 0.02)).astype(np.float64)       # ~20% positive
    X = np.stack([age, income, score], axis=1)
    vi = np.arange(n) % 5 == 0
    ds = Dataset({"features": X, "label": y, "isVal": vi})

    clf = LightGBMClassifier(
        numIterations=60, numLeaves=15, maxBin=63,
        # eval on AUC (exact weighted rank statistic), stop when it stalls
        metric="auc", earlyStoppingRound=5, improvementTolerance=1e-4,
        validationIndicatorCol="isVal",
        # imbalanced data: keep most positives, subsample negatives
        posBaggingFraction=0.9, negBaggingFraction=0.4, baggingFreq=1,
        # 8 distinct ages don't need 63 bins
        maxBinByFeature=[8, 63, 63],
        # clamp extreme leaf outputs (LightGBM's imbalanced-binary advice)
        maxDeltaStep=1.0,
        # watch the train metric per iteration too
        isProvideTrainingMetric=True,
        slotNames=["age", "income", "score"],
    )
    model = clf.fit(ds)

    hist = model.booster.eval_history
    print(f"stopped after {len(hist['auc'])} evaluated iterations, "
          f"best AUC {max(hist['auc']):.4f} "
          f"(model truncated to {model.booster.num_iterations} trees)")
    print(f"train AUC path: {hist['training_auc'][0]:.3f} "
          f"-> {hist['training_auc'][-1]:.3f}")
    assert max(hist["auc"]) > 0.9

    native = model.get_native_model()
    assert "feature_names=age income score" in native
    print("native model uses slot names; importances:",
          [ln for ln in native.splitlines()
           if ln.startswith(("age=", "income=", "score="))])


if __name__ == "__main__":
    main()
