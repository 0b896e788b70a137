"""Seeded synthetic tables shared by ``chip_smoke.py`` and ``bench.py``, so
the smoke's accuracy floor and the benchmark's rates are about one signal."""

from __future__ import annotations

import numpy as np


def higgs_like(n: int, seed: int = 0):
    """``[n, 28]`` float32 normal features and a binary label with pairwise
    and quadratic structure (the dense Higgs-shaped table LightGBM is
    usually benched on). A 31-leaf booster that learns it scores ~0.78-0.83
    on its own training rows after 10 iterations; a transport or
    subnormal-flush bug gives ~0.5."""
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, 28)).astype(np.float32)
    logits = (X[:, 0] * X[:, 1] + 0.5 * X[:, 2] ** 2 - X[:, 3]
              + 0.3 * X[:, 4] * X[:, 5])
    y = (logits + rng.normal(scale=0.5, size=n) > 0).astype(np.float32)
    return X, y
