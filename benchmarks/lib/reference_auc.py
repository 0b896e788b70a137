"""Plain reference for the validated fit's metric: the held-out rows are made
again from the seed, every row is routed through the fit's trees by raw
thresholds and category bitsets (``reference._route``, the reference's own
routing: it takes nothing of the program but the trees under test), the
margin is summed in float64 on the host, and the AUC after each tree is
taken exactly: integer pair counts, a tied pair counting half.

The control computes the same from margins carried in bfloat16, the nearest
precision below the float32 the configuration's margin is kept in: 8
significant bits merge most leaf sums into ties, and the reading has to come
out as not correct.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from . import datagen, reference


def auc_exact(margin: np.ndarray, positive: np.ndarray) -> float:
    """AUC of ``margin`` (any float dtype; equal values tie) against boolean
    ``positive``: twice the pair count in Python integers over
    ``2 x positives x negatives``; 0.5 where a class is absent."""
    order = np.argsort(margin, kind="stable")
    s, p = np.asarray(margin)[order], np.asarray(positive, bool)[order]
    starts = np.flatnonzero(np.concatenate([[True], s[1:] != s[:-1]]))
    gpos = np.add.reduceat(p.astype(np.int64), starts)
    gneg = np.add.reduceat((~p).astype(np.int64), starts)
    below = np.concatenate([[0], np.cumsum(gneg)[:-1]])
    tp, tn = int(gpos.sum()), int(gneg.sum())
    if tp == 0 or tn == 0:
        return 0.5
    twice = sum(int(a) * (2 * int(b) + int(c))
                for a, b, c in zip(gpos, below, gneg) if a)
    return twice / (2 * tp * tn)


def held_out_leaves(key, plan: dict, data: dict, trees: dict,
                    bounds: np.ndarray):
    """``(leaf [T, nv] int32 node slot of every held-out row under every
    tree, y [nv] float32)`` for the chunks ``plan`` names (``first_chunk``,
    ``chunks``, ``chunk_rows``)."""
    _, _, cat_cols = datagen.feature_layout(data)
    chunk_rows = int(plan["chunk_rows"])
    dev_trees = {k: jnp.asarray(trees[k]) for k in (
        "feat", "thr_raw", "left", "right", "is_leaf", "cat_bitset")}
    T = trees["feat"].shape[0]

    def one(k, c, tr, bd):
        X, y = datagen.gen_chunk(k, c, chunk_rows, data)
        Xt = X.T
        bins = reference._bins_of(Xt, bd)
        F = Xt.shape[0]
        is_cat = jnp.zeros(F, bool).at[jnp.asarray(
            cat_cols, jnp.int32)].set(True) if cat_cols else jnp.zeros(
                F, bool)
        return jnp.stack([reference._route(
            Xt, bins, {n: v[t] for n, v in tr.items()}, is_cat)
            for t in range(T)]), y

    step = jax.jit(one)
    leaves, labels = [], []
    for c in range(int(plan["first_chunk"]),
                   int(plan["first_chunk"]) + int(plan["chunks"])):
        leaf, y = step(key, jnp.int32(c), dev_trees, jnp.asarray(bounds))
        leaves.append(np.asarray(leaf))
        labels.append(np.asarray(y))
    return np.concatenate(leaves, axis=1), np.concatenate(labels)


def replay(key, plan: dict, data: dict, trees: dict, base_score: float,
           bounds: np.ndarray, control: bool = False) -> dict:
    """``{"auc": [after tree 1, after tree 2, ...], "margin": final float64
    margin [nv], "label_mean"}``; with ``control`` also ``"control_auc"``,
    the same from a margin carried in bfloat16."""
    leaf, y = held_out_leaves(key, plan, data, trees, bounds)
    positive = y > 0.5
    margin = np.full(leaf.shape[1], np.float64(np.float32(base_score)))
    low = margin.astype(jnp.bfloat16) if control else None
    out = {"auc": [], "label_mean": float(positive.mean())}
    if control:
        out["control_auc"] = []
    for t in range(leaf.shape[0]):
        add = np.asarray(trees["leaf_value"][t], np.float64)[leaf[t]]
        margin = margin + add
        out["auc"].append(auc_exact(margin, positive))
        if control:
            low = (low.astype(np.float32) + add.astype(np.float32)).astype(
                jnp.bfloat16)
            out["control_auc"].append(auc_exact(low.astype(np.float32),
                                                positive))
    out["margin"] = margin
    return out
