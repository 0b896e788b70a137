"""Plain reference for histogram GBDT training: a replay of grown trees over
the regenerated table, in float32 at ``highest`` precision on the device and
float64 on the host. It imports nothing of the program and takes none of its
tables: the rows come from the seed again, the bin bounds from its own
quantile binner, and the trees it is handed are the answer under test, fed
back as served tokens are fed to a language model's reference.

For every tree of one fit, in order, with the scores the earlier trees left:

* route every row by the tree's own splits (raw thresholds; category bitsets)
  and count the rows of every node                          -> ``count_gap``
* sum gradient and hessian per leaf, exactly, and form the leaf value the
  configuration's objective gives                           -> ``leaf_gap``
* build every node's exact histogram and search it for the best split as the
  configuration defines the search; the split the program chose lies below
  that best by a share of it                                -> ``gain_gap``

The control computes the same search from statistics quantized to fewer
levels and reads the gap of the split that the lower precision puts first.

Departures from stock LightGBM, which the configuration's own semantics make:
categories are searched as sorted prefixes in one direction, with
``cat_smooth`` in the sort key only, and ids past ``max_bin - 2`` share the
last bin.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from . import datagen

_HIGHEST = jax.lax.Precision.HIGHEST


def quantile_bounds(sample: np.ndarray, max_bin: int, cat_cols) -> np.ndarray:
    """``[F, max_bin - 1]`` upper bounds: interior quantiles of the sample's
    non-missing values (midpoints where a field has fewer distinct values
    than bins, +inf padding), and ``c + 0.5`` for category ids. A value's bin
    is the count of bounds strictly below it; NaN falls in bin 0."""
    sample = np.asarray(sample, np.float32)
    F, B = sample.shape[1], int(max_bin)
    bounds = np.empty((F, B - 1), np.float32)
    qs = np.linspace(0.0, 1.0, B + 1)[1:-1]
    for f in range(F):
        if f in cat_cols:
            bounds[f] = np.arange(B - 1, dtype=np.float32) + 0.5
            continue
        col = sample[:, f]
        col = col[~np.isnan(col)]
        if col.size == 0:
            bounds[f] = 0.0
            continue
        uniq = np.unique(col)
        if uniq.size <= B - 1:
            mids = ((uniq[:-1] + uniq[1:]) / 2.0 if uniq.size > 1
                    else uniq[:1])
            bounds[f] = np.concatenate(
                [mids, np.full(B - 1 - mids.size, np.inf)]).astype(np.float32)
        else:
            bounds[f] = np.maximum.accumulate(
                np.quantile(col, qs).astype(np.float32))
    return bounds


# ---------------------------------------------------------------------------
# device pass: one chunk of rows through every tree of the fit
# ---------------------------------------------------------------------------


def _bins_of(Xt, bounds):
    """[F, n] bin ids: count of bounds strictly below each value."""
    def one(_, xb):
        x, b = xb
        return _, jnp.sum(b[:, None] < x[None, :], axis=0, dtype=jnp.int32)
    return jax.lax.scan(one, None, (Xt, bounds))[1]


def _route(Xt, bins, tree, is_cat_feat):
    """Leaf node id of every row: walk the node slots in order (a child's
    slot is above its parent's), moving the rows that sit on each split."""
    M = tree["feat"].shape[0]

    def body(j, node):
        f = tree["feat"][j]
        x = jax.lax.dynamic_index_in_dim(Xt, f, keepdims=False)
        b = jax.lax.dynamic_index_in_dim(bins, f, keepdims=False)
        go_left = ~(x > tree["thr_raw"][j])            # NaN goes left
        word = jnp.zeros_like(b, dtype=jnp.uint32)
        for k in range(tree["cat_bitset"].shape[1]):
            word = jnp.where((b >> 5) == k, tree["cat_bitset"][j, k], word)
        member = ((word >> (b.astype(jnp.uint32) & 31)) & 1) == 1
        go_left = jnp.where(is_cat_feat[f], member, go_left)
        here = (node == j) & ~tree["is_leaf"][j]
        return jnp.where(here, jnp.where(go_left, tree["left"][j],
                                         tree["right"][j]), node)

    return jax.lax.fori_loop(0, M, body,
                             jnp.zeros(Xt.shape[1], jnp.int32))


def _quantize(x, q_max, u):
    """Symmetric stochastic quantization to ``q_max`` levels a side, scaled
    by this chunk's largest magnitude; returns the dequantized values."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / q_max
    return jnp.clip(jnp.floor(x / scale + u), -q_max, q_max) * scale


def _chunk_pass(key, chunk_index, trees, bounds, base, leaf_nodes, *,
                chunk_rows, data, num_bins, cat_cols, control_qmax):
    """Per-leaf histograms ``[T, F, S*L, B]`` of one chunk (S statistics:
    gradient, hessian, count, and in a control run their quantized twins),
    the chunk's label sum, and each tree's largest gradient and hessian
    magnitudes ``[T, 2]``."""
    X, y = datagen.gen_chunk(key, chunk_index, chunk_rows, data)
    Xt = X.T
    bins = _bins_of(Xt, bounds)
    F = Xt.shape[0]
    is_cat_feat = jnp.zeros(F, bool).at[jnp.asarray(cat_cols, jnp.int32)].set(
        True) if cat_cols else jnp.zeros(F, bool)
    T = trees["feat"].shape[0]
    score = jnp.full((chunk_rows,), base, jnp.float32)
    iota = jnp.arange(num_bins, dtype=jnp.int32)
    hists, amax = [], []
    for t in range(T):
        tree = {k: v[t] for k, v in trees.items()}
        p = jax.nn.sigmoid(score)
        stats = [p - y, p * (1.0 - p), jnp.ones_like(p)]
        amax.append(jnp.stack([jnp.max(jnp.abs(stats[0])),
                               jnp.max(jnp.abs(stats[1]))]))
        if control_qmax:
            ku = jax.random.fold_in(jax.random.fold_in(key, 977 + t),
                                    chunk_index)
            u = jax.random.uniform(ku, (2, chunk_rows))
            stats += [_quantize(stats[0], control_qmax, u[0]),
                      _quantize(stats[1], control_qmax, u[1])]
        node = _route(Xt, bins, tree, is_cat_feat)
        onleaf = node[:, None] == leaf_nodes[t][None, :]           # [n, L]
        At = jnp.concatenate([jnp.where(onleaf, s[:, None], 0.0)
                              for s in stats], axis=1).T           # [S*L, n]

        def one_feature(b, At=At):
            onehot = (b[:, None] == iota[None, :]).astype(jnp.float32)
            return jnp.matmul(At, onehot, precision=_HIGHEST)      # [S*L, B]

        hists.append(jax.lax.map(one_feature, bins))               # [F,SL,B]
        score = score + jnp.sum(
            jnp.where(onleaf, tree["leaf_value"][leaf_nodes[t]][None, :], 0.0),
            axis=1)
    hist = jnp.stack(hists)                                        # [T,F,SL,B]
    return hist, jnp.sum(y), jnp.stack(amax)


# ---------------------------------------------------------------------------
# host analysis, float64
# ---------------------------------------------------------------------------


def _soft(g, params):
    """Gradient sum after the L1 threshold."""
    return np.sign(g) * np.maximum(np.abs(g) - params.get("lambda_l1", 0.0),
                                   0.0)


def _leaf_objective(g, h, params):
    sg = _soft(g, params)
    return sg * sg / (h + params.get("lambda_l2", 0.0) + 1e-300)


def _candidate_gains(order_h, vals_h, params, cat_mask):
    """Gains ``[F, B]`` of every candidate split of a node, computed from
    ``vals_h`` ([F, 3, B]: gradient, hessian, count per bin) with the
    categorical fields' bins ranked by ``order_h``: candidate ``[f, b]``
    sends bins ``0..b`` (numeric) or the ``b + 1`` first-ranked categories
    left. Infeasible candidates read ``-inf``."""
    B = vals_h.shape[-1]
    og, oh, oc = order_h[:, 0], order_h[:, 1], order_h[:, 2]
    ratio = np.where(oc > 0, og / (oh + params["cat_smooth"]), np.inf)
    order = np.argsort(ratio, axis=-1, kind="stable")
    order = np.where(cat_mask[:, None], order, np.arange(B)[None, :])
    g, h, c = (np.take_along_axis(vals_h[:, i], order, axis=-1)
               for i in range(3))
    gl, hl, cl = np.cumsum(g, -1), np.cumsum(h, -1), np.cumsum(c, -1)
    tg, th, tc = gl[:, -1:], hl[:, -1:], cl[:, -1:]
    gr, hr, cr = tg - gl, th - hl, tc - cl
    gain = (_leaf_objective(gl, hl, params) + _leaf_objective(gr, hr, params)
            - _leaf_objective(tg, th, params))
    ok = ((cl >= params["min_data_in_leaf"]) & (cr >= params["min_data_in_leaf"])
          & (hl >= params["min_sum_hessian_in_leaf"])
          & (hr >= params["min_sum_hessian_in_leaf"]))
    ok &= ~cat_mask[:, None] | (np.arange(B)[None, :]
                                < params["max_cat_threshold"])
    ok[:, B - 1] = False
    return np.where(ok, gain, -np.inf)


def _split_gain(vals_h, member, feat, params):
    """Gain of sending the bins in ``member`` ([B] bool) of ``feat`` left."""
    g, h, _ = vals_h[feat]
    gl, hl = g[member].sum(), h[member].sum()
    tg, th = g.sum(), h.sum()
    return (_leaf_objective(gl, hl, params)
            + _leaf_objective(tg - gl, th - hl, params)
            - _leaf_objective(tg, th, params))


def _members(tree, j, is_cat, B):
    """[B] bool: the bins that node ``j``'s split sends left."""
    if not is_cat:
        return np.arange(B) <= tree["thr_bin"][j]
    bits = tree["cat_bitset"][j]
    idx = np.arange(B)
    return ((bits[idx >> 5] >> (idx & 31).astype(np.uint32)) & 1).astype(bool)


def leaf_layout(trees: dict, num_leaves: int):
    """(leaf_nodes [T, L] slot of each leaf, -1 padded; descendants
    [T, M, L] bool: leaf l lies under node m)."""
    T, M = trees["feat"].shape
    leaf_nodes = np.full((T, num_leaves), -1, np.int32)
    under = np.zeros((T, M, num_leaves), bool)
    for t in range(T):
        used = int(trees["node_count"][t])
        leaves = [j for j in range(used) if trees["is_leaf"][t, j]]
        leaf_nodes[t, :len(leaves)] = leaves
        for l, j in enumerate(leaves):
            under[t, j, l] = True
        for j in range(used - 1, -1, -1):          # children before parents
            if not trees["is_leaf"][t, j]:
                under[t, j] = (under[t, trees["left"][t, j]]
                               | under[t, trees["right"][t, j]])
    return leaf_nodes, under


def replay(key, rows: int, data: dict, params: dict, trees: dict,
           base_score: float, bounds: np.ndarray, control_qmax: int = 0,
           quant_levels: int = 0):
    """Replay one fit's ``trees`` (numpy arrays with a leading tree axis:
    feat, thr_bin, thr_raw, left, right, is_leaf, leaf_value, node_cnt,
    node_count, cat_bitset) over the table of ``rows`` rows that ``key``
    makes. Returns the readings ``count_gap``, ``leaf_gap``, ``gain_gap``
    and ``gain_loss`` (and the control's with ``control_qmax``), with the
    nodes they were read at; with ``quant_levels``, the levels a side that
    the configuration's quantized statistics have, also ``leaf_noise``."""
    chunk_rows, chunks = datagen.chunk_plan(rows, data)
    _, _, cat_cols = datagen.feature_layout(data)
    L, B = int(params["num_leaves"]), int(params["max_bin"])
    T, M = trees["feat"].shape
    F = bounds.shape[0]
    S = 5 if control_qmax else 3
    leaf_nodes, under = leaf_layout(trees, L)

    dev_trees = {k: jnp.asarray(trees[k]) for k in (
        "feat", "thr_raw", "left", "right", "is_leaf", "leaf_value",
        "cat_bitset")}
    step = jax.jit(lambda k, c, tr, bd, base, ln: _chunk_pass(
        k, c, tr, bd, base, ln, chunk_rows=chunk_rows, data=data,
        num_bins=B, cat_cols=cat_cols, control_qmax=control_qmax))
    bounds_d, leaf_nodes_d = jnp.asarray(bounds), jnp.asarray(leaf_nodes)
    hist = np.zeros((T, F, S * L, B), np.float64)
    label_sum, pending, amax = 0.0, None, np.zeros((T, 2))
    for c in range(chunks + 1):                    # one chunk in flight
        nxt = (step(key, jnp.int32(c), dev_trees, bounds_d,
                    jnp.float32(base_score), leaf_nodes_d)
               if c < chunks else None)
        if pending is not None:
            hist += np.asarray(pending[0], np.float64)
            label_sum += float(pending[1])
            amax = np.maximum(amax, np.asarray(pending[2], np.float64))
        pending = nxt

    hist = hist.reshape(T, F, S, L, B).transpose(0, 3, 1, 2, 4)  # [T,L,F,S,B]
    out = _read(hist, amax, under, trees, params, cat_cols,
                bool(control_qmax), quant_levels)
    out["label_mean"] = label_sum / rows
    return out


def _read(hist, amax, under, trees, params, cat_cols, control, quant_levels):
    """The readings, on the host in float64, from the per-leaf histograms
    ``hist`` [T, L, F, S, B], each tree's largest gradient and hessian
    ``amax`` [T, 2] and the leaves ``under`` [T, M, L] every node."""
    T, _, F, _, B = hist.shape
    cat_mask = np.zeros(F, bool)
    cat_mask[list(cat_cols)] = True
    lr = params["learning_rate"]
    out = {"count_gap": 0.0, "leaf_gap": 0.0, "gain_gap": 0.0}
    if control:
        out["control_gain_gap"] = 0.0
    leaf_ref, leaf_prog, leaf_at, splits, counts = [], [], [], [], []
    noise, control_noise = [], []
    for t in range(T):
        tree = {k: v[t] for k, v in trees.items()}
        used = int(tree["node_count"])
        node_h = np.einsum("ml,lfsb->mfsb", under[t].astype(np.float64),
                           hist[t])                          # [M, F, S, B]
        for j in range(used):
            cnt = node_h[j, 0, 2].sum()
            counts.append({"tree": t, "node": j, "rows": float(cnt),
                           "program": float(tree["node_cnt"][j])})
            if tree["is_leaf"][j]:
                g, h = node_h[j, 0, 0].sum(), node_h[j, 0, 1].sum()
                leaf_ref.append(
                    -lr * _soft(g, params) / (h + params.get("lambda_l2", 0.0)
                                              + 1e-300) if cnt > 0 else 0.0)
                leaf_prog.append(float(tree["leaf_value"][j]))
                if quant_levels and cnt > 0:
                    # leaf sums against the exact ones, in units of what
                    # rounding cnt rows to the stated step can add up to
                    for c, prog in ((0, tree["node_grad"][j]),
                                    (1, tree["node_hess"][j])):
                        if c == 1 and t == 0:
                            continue    # every row's first hessian is the
                            # same value and quantizes without error
                        step = amax[t, c] / quant_levels
                        exact_sum = node_h[j, 0, c].sum()
                        noise.append((float(prog) - exact_sum) ** 2
                                     / (cnt * step * step))
                        if control:
                            control_noise.append(
                                (node_h[j, 0, 3 + c].sum() - exact_sum) ** 2
                                / (cnt * step * step))
                leaf_at.append({"tree": t, "node": j, "rows": float(cnt),
                                "grad": float(g), "hess": float(h),
                                "program_hess": float(tree["node_hess"][j]),
                                "program_grad": float(tree["node_grad"][j])})
                continue
            exact = node_h[j, :, :3]
            gains = _candidate_gains(exact, exact, params, cat_mask)
            best = gains.max()
            f = int(tree["feat"][j])
            taken = _split_gain(exact, _members(tree, j, cat_mask[f], B), f,
                                params)
            row = {"tree": t, "node": j, "feat": f, "best": float(best),
                   "taken": float(taken), "rows": float(cnt)}
            if control:
                quant = np.stack([node_h[j, :, 3], node_h[j, :, 4],
                                  node_h[j, :, 2]], axis=1)
                picked = np.argmax(_candidate_gains(quant, quant, params,
                                                    cat_mask))
                row["control"] = float(_candidate_gains(
                    quant, exact, params, cat_mask).reshape(-1)[picked])
            splits.append(row)
    # a split's shortfall is measured against its node's best gain or the
    # fit's median best gain, whichever is larger: a pure node's best gain is
    # rounding noise, and every split of it is as good as any other
    bests = np.asarray([r["best"] for r in splits])
    floor = float(np.median(bests[np.isfinite(bests)])) if splits else 1.0
    out["nodes"] = len(splits)
    total = float(np.sum(bests[np.isfinite(bests)])) or 1.0
    for key, name in (("taken", "gain_loss"), ("control", "control_gain_loss")):
        if splits and key in splits[0]:
            # the share of the gain on offer that the fit's splits left
            out[name] = sum(max(r["best"] - r[key], 0.0) for r in splits
                            if np.isfinite(r["best"])
                            and np.isfinite(r[key])) / total
    for r in splits:
        scale = max(r["best"], floor) if np.isfinite(r["best"]) else floor
        for key, name in (("taken", "gain_gap"),
                          ("control", "control_gain_gap")):
            if key not in r:
                continue
            gap = ((r["best"] - r[key]) / scale
                   if np.isfinite(r[key]) and np.isfinite(r["best"]) else 1.0)
            if gap > out[name]:
                out[name], out[name + "_at"] = float(gap), r
    if noise:
        out["leaf_noise"] = float(np.sqrt(np.mean(noise)))
    if control_noise:
        out["control_leaf_noise"] = float(np.sqrt(np.mean(control_noise)))
    # counts and leaf values likewise: against the node's own or the median
    # node's, whichever is larger
    floor = float(np.median([c["rows"] for c in counts])) if counts else 1.0
    for c in counts:
        gap = abs(c["program"] - c["rows"]) / max(c["rows"], floor)
        if gap > out["count_gap"]:
            out["count_gap"], out["count_gap_at"] = float(gap), c
    leaf_ref, leaf_prog = np.asarray(leaf_ref), np.asarray(leaf_prog)
    if leaf_ref.size:
        floor = np.median(np.abs(leaf_ref))
        gaps = np.abs(leaf_prog - leaf_ref) / np.maximum(np.abs(leaf_ref),
                                                         floor)
        worst = int(np.argmax(gaps))
        out["leaf_gap"] = float(gaps[worst])
        out["leaf_gap_at"] = dict(leaf_at[worst], value=float(leaf_ref[worst]),
                                  program=float(leaf_prog[worst]),
                                  median=float(floor))
    return out
