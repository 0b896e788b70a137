"""Window driver ``gbdt_train_f``: ``gbdt_train`` for a configuration whose
statistics are floats (``stats_dtype`` ``bf16``: gradient and hessian rounded
to bf16 where they enter the histogram, sums in f32).

The set-up, the window, the release and ``gbdt_train``'s comparison are
``gbdt_train``'s. Two things are added.

* ``facts["float_sum_sites"]``: what building the warm-up fit's program added
  to the program's ``gbdt_float_sums_total{site, form}``, by site
  (``layer_metrics/float_sum_sites.py`` reads it).
* One more number of the comparison, ``leaf_sum_gap``, because none of
  ``gbdt_train``'s tells this configuration from its control. The replay of
  ``reference.py`` sums the *unrounded* statistics, so against it bf16's own
  rounding (up to 2^-9 of every value, systematic where rows share a value,
  as all do in a first tree) covers what the sums lose, and int8 at 127
  levels with stochastic rounding, the control, reads no worse than bf16
  there. What the configuration states is narrower and checkable: every
  node's sums are the sums of the bf16-rounded statistics of its rows, to
  f32's relative error at the node's own size. So this driver routes the rows
  a second time (the reference's own generator, binning and routing), rounds
  each row's gradient and hessian to bf16 as the configuration says, and sums
  them per leaf on the host in float64 (a bf16 value has 8 significant bits
  and a leaf under 2^27 rows: a float64 sum of them is exact or good to
  1e-15). ``leaf_sum_gap`` is the worst node's
  ``max(|G - G_exact|, |H - H_exact|) / H_exact``, the program's recorded
  ``node_grad`` and ``node_hess`` against those sums: the error of the raw
  leaf value ``G / H`` in its own units. Sums that are wide enough read a few
  1e-6 (f32 over 8340 row blocks); the control's twin (the same rows
  quantized to 127 levels) reads its quantization noise, ``1 / sqrt(rows)`` of
  a level, largest in the smallest leaf; sums that subtract at the root's size
  read about 1.
"""

from __future__ import annotations

import numpy as np

# The sites of the program's float path that sum at the node's own magnitude,
# as the program names them. A program without them subtracts at the root's
# magnitude and cannot run this configuration (its guarantee fails by a factor
# of 1e5 at 68 M rows), so the cell ends here, at once, on such a program.
from mmlspark_tpu.models.gbdt.growth import FLOAT_SUM_SITES

from . import datagen, gbdt_train, reference
from .gbdt_train import CONTROL_QMAX, _counter_total


def _float_sum_counts() -> dict:
    return {site: _counter_total("gbdt_float_sums_total", site=site)
            for site in FLOAT_SUM_SITES}


def _chunk_leaves(key, chunk_index, trees, bounds, base, leaf_nodes, *,
                  chunk_rows, data, cat_cols, control_qmax):
    """One chunk of rows through every tree of the fit: per tree each row's
    leaf (its index in ``leaf_nodes``) and its gradient and hessian rounded
    to bf16; in a control run also their twins quantized as
    ``reference._chunk_pass`` quantizes them."""
    import jax
    import jax.numpy as jnp

    X, y = datagen.gen_chunk(key, chunk_index, chunk_rows, data)
    Xt = X.T
    bins = reference._bins_of(Xt, bounds)
    F = Xt.shape[0]
    is_cat_feat = jnp.zeros(F, bool).at[jnp.asarray(cat_cols, jnp.int32)].set(
        True) if cat_cols else jnp.zeros(F, bool)
    score = jnp.full((chunk_rows,), base, jnp.float32)
    leaves, rounded, twins = [], [], []
    for t in range(trees["feat"].shape[0]):
        tree = {k: v[t] for k, v in trees.items()}
        p = jax.nn.sigmoid(score)
        g, h = p - y, p * (1.0 - p)
        node = reference._route(Xt, bins, tree, is_cat_feat)
        onleaf = node[:, None] == leaf_nodes[t][None, :]           # [n, L]
        leaves.append(jnp.argmax(onleaf, axis=1).astype(jnp.int8))
        rounded.append(jnp.stack([g, h]).astype(jnp.bfloat16))
        if control_qmax:
            ku = jax.random.fold_in(jax.random.fold_in(key, 977 + t),
                                    chunk_index)
            u = jax.random.uniform(ku, (2, chunk_rows))
            twins.append(jnp.stack([
                reference._quantize(g, control_qmax, u[0]),
                reference._quantize(h, control_qmax, u[1])]))
        score = score + jnp.sum(
            jnp.where(onleaf, tree["leaf_value"][leaf_nodes[t]][None, :], 0.0),
            axis=1)
    return (jnp.stack(leaves), jnp.stack(rounded),
            jnp.stack(twins) if control_qmax else jnp.zeros((0,)))


def leaf_sums(key, rows: int, data: dict, params: dict, trees: dict,
              base_score: float, bounds: np.ndarray, control_qmax: int = 0):
    """``[T, L, 2]`` float64 sums of the bf16-rounded gradient and hessian of
    the rows on every leaf of ``trees`` (and the control's twins, or None),
    over the table of ``rows`` rows that ``key`` makes."""
    import jax
    import jax.numpy as jnp

    chunk_rows, chunks = datagen.chunk_plan(rows, data)
    _, _, cat_cols = datagen.feature_layout(data)
    L = int(params["num_leaves"])
    T = trees["feat"].shape[0]
    leaf_nodes, _ = reference.leaf_layout(trees, L)
    dev_trees = {k: jnp.asarray(trees[k]) for k in (
        "feat", "thr_raw", "left", "right", "is_leaf", "leaf_value",
        "cat_bitset")}
    step = jax.jit(lambda k, c, tr, bd, base, ln: _chunk_leaves(
        k, c, tr, bd, base, ln, chunk_rows=chunk_rows, data=data,
        cat_cols=cat_cols, control_qmax=control_qmax))
    fixed = (dev_trees, jnp.asarray(bounds), jnp.float32(base_score),
             jnp.asarray(leaf_nodes))
    exact = np.zeros((T, L, 2), np.float64)
    twin = np.zeros((T, L, 2), np.float64) if control_qmax else None
    pending = None
    for c in range(chunks + 1):                    # one chunk in flight
        nxt = step(key, jnp.int32(c), *fixed) if c < chunks else None
        if pending is not None:
            leaf, vals, quant = (np.asarray(a) for a in pending)
            for t in range(T):
                for s in range(2):
                    exact[t, :, s] += np.bincount(
                        leaf[t], vals[t, s].astype(np.float64), minlength=L)
                    if control_qmax:
                        twin[t, :, s] += np.bincount(
                            leaf[t], quant[t, s].astype(np.float64),
                            minlength=L)
        pending = nxt
    return exact, twin


def _node_sums(leaf, trees: dict, num_leaves: int):
    """``[T, M, 2]`` sums of every node from its leaves' ``[T, L, 2]``."""
    _, under = reference.leaf_layout(trees, num_leaves)
    return np.einsum("tml,tls->tms", under.astype(np.float64), leaf)


def read_leaf_sum_gap(exact, given, trees: dict):
    """(worst ``max(|G - G_exact|, |H - H_exact|) / H_exact`` over the nodes
    that hold rows, where it was read). ``exact``, ``given``: ``[T, M, 2]``
    node sums, the second standing in the program's place (the control's
    twins), or None for the program's own recorded ``node_grad`` and
    ``node_hess``."""
    if given is None:
        given = np.stack([trees["node_grad"], trees["node_hess"]], axis=2)
    worst, at = 0.0, None
    for t in range(trees["feat"].shape[0]):
        for j in range(int(trees["node_count"][t])):
            gx, hx = exact[t, j]
            if hx <= 0.0:
                continue
            gap = float(np.max(np.abs(given[t, j] - exact[t, j])) / hx)
            if gap > worst:
                worst, at = gap, {
                    "tree": t, "node": j, "grad": float(gx),
                    "hess": float(hx), "given_grad": float(given[t, j, 0]),
                    "given_hess": float(given[t, j, 1]),
                    "rows": float(trees["node_cnt"][t, j])}
    return worst, at


def largest_nodes(exact, trees: dict, keep: int = 4):
    """For ``tools/readings_dp.py``: the ``keep`` largest nodes of each tree
    (the root first) with the program's recorded sums less the exact
    ``[T, M, 2]`` ones, which is where sums at the root's magnitude lose the
    most."""
    rows = []
    for t in range(trees["feat"].shape[0]):
        used = int(trees["node_count"][t])
        for j in np.argsort(-exact[t, :used, 1], kind="stable")[:keep]:
            rows.append({
                "tree": t, "node": int(j), "hess": float(exact[t, j, 1]),
                "rows": float(trees["node_cnt"][t, j]),
                "grad_error": float(trees["node_grad"][t, j]
                                    - exact[t, j, 0]),
                "hess_error": float(trees["node_hess"][t, j]
                                    - exact[t, j, 1])})
    return rows


class Driver(gbdt_train.Driver):

    def set_up(self) -> dict:
        before = _float_sum_counts()
        facts = super().set_up()
        # the first fit builds the program, and the counter counts builds
        self.float_sum_sites = {site: v - before[site] for site, v
                                in _float_sum_counts().items()}
        return facts

    def window(self, seconds: float) -> dict:
        facts = super().window(seconds)
        facts["float_sum_sites"] = self.float_sum_sites
        return facts

    def compare(self, control: bool = False) -> dict:
        """``gbdt_train``'s comparison and ``leaf_sum_gap``; under
        ``control`` the twins' reading stands in the program's place, as the
        other readings' controls do."""
        out = super().compare(control)
        picked = self.boosters[self.ctx["seed"] % len(self.boosters)]
        trees = self._tree_arrays(picked)
        _, _, cats = datagen.feature_layout(self.data)
        bounds = reference.quantile_bounds(self.sample,
                                           self.params["max_bin"], cats)
        qmax = CONTROL_QMAX[self.config["stats_dtype"]] if control else 0
        exact, twin = leaf_sums(self.key, self.rows, self.data, self.params,
                                trees, float(picked.base_score[0]), bounds,
                                control_qmax=qmax)
        L = int(self.params["num_leaves"])
        exact = _node_sums(exact, trees, L)
        out["leaf_sum_gap"], at = read_leaf_sum_gap(exact, None, trees)
        if control:
            out["program_leaf_sum_gap"] = out["leaf_sum_gap"]
            out["leaf_sum_gap"], at = read_leaf_sum_gap(
                exact, _node_sums(twin, trees, L), trees)
        self.reference_facts["leaf_sum_gap_at"] = at
        self.reference_facts["largest_nodes_at"] = largest_nodes(exact, trees)
        return out
