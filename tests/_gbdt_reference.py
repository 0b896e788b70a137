"""Plain references that the validated-fit tests hold the program to: the
scorer, the metric and the early-stopping harness as they stood before PR 35
(per-row gathers; host NumPy in float64; the round staged twice); and the
``[W, n]`` row routing as it stood before PR 38. Not collected: no ``test_``
prefix."""

import jax.numpy as jnp
import numpy as np
from jax import lax

from mmlspark_tpu.models.gbdt.growth import (_bitset_words_of_rows,
                                             bit_test)


def walk_by_gathers(tree, binned, depth_cap, is_cat=None):
    """``[n, F]`` binned rows -> ``[n]`` leaf values: ``depth_cap`` steps,
    each a per-row gather of the node's fields, the row's value and the
    bitset's word (``bit_test``)."""
    node = jnp.zeros(binned.shape[0], dtype=jnp.int32)

    def body(_, node):
        f = tree.feat[node]
        x = jnp.take_along_axis(binned, f[:, None], axis=1)[:, 0]
        go_left = x <= tree.thr_bin[node]
        if is_cat is not None:
            go_left = jnp.where(is_cat[f],
                                bit_test(tree.cat_bitset[node], x), go_left)
        nxt = jnp.where(go_left, tree.left[node], tree.right[node])
        return jnp.where(tree.is_leaf[node], node, nxt)

    return tree.leaf_value[lax.fori_loop(0, depth_cap, body, node)]


def auc_float64(scores, y, w=None) -> float:
    """Exact weighted AUC, ties counted half, in float64 on the host; 0.5
    where a class is absent."""
    scores = np.asarray(scores, np.float64)
    pos = np.asarray(y, np.float64) > 0.5
    w = np.ones_like(scores) if w is None else np.asarray(w, np.float64)
    order = np.argsort(scores, kind="mergesort")
    s, p, ww = scores[order], pos[order], w[order]
    wpos, wneg = np.where(p, ww, 0.0), np.where(p, 0.0, ww)
    starts = np.flatnonzero(np.concatenate([[True], np.diff(s) != 0]))
    gpos = np.add.reduceat(wpos, starts)
    gneg = np.add.reduceat(wneg, starts)
    below = np.concatenate([[0.0], np.cumsum(gneg)[:-1]])
    tp, tn = wpos.sum(), wneg.sum()
    if tp <= 0 or tn <= 0:
        return 0.5
    return float(np.sum(gpos * (below + 0.5 * gneg)) / (tp * tn))


def es_scan_two_stage(one_iter, state0, num_iterations, early_stopping_rounds,
                      higher_is_better, tol=0.0):
    """``_fused_es_scan(track_metric=True)`` as it stood: iteration 0 inline
    (its packed length sized the buffer), the rest in a ``while_loop``."""
    def track(best, best_it, rni, m, it):
        improved = (m > best + jnp.float32(tol) if higher_is_better
                    else m < best - jnp.float32(tol))
        return (jnp.where(improved, m, best), jnp.where(improved, it, best_it),
                jnp.where(improved, 0, rni + 1))

    it0 = jnp.int32(0)
    state, packed0, m0 = one_iter(it0, state0)
    buf = jnp.zeros((num_iterations, packed0.shape[0]),
                    packed0.dtype).at[0].set(packed0)
    mbuf = jnp.full((num_iterations,), jnp.nan, jnp.float32).at[0].set(m0)
    best, best_it, rni = track(
        jnp.float32(-jnp.inf if higher_is_better else jnp.inf),
        jnp.int32(-1), jnp.int32(0), m0, it0)

    def cond(carry):
        keep = carry[0] < num_iterations
        if early_stopping_rounds > 0:
            keep &= carry[4] < early_stopping_rounds
        return keep

    def body(carry):
        it, state, best, best_it, rni, buf, mbuf = carry
        state, packed, m = one_iter(it, state)
        buf = lax.dynamic_update_index_in_dim(buf, packed, it, 0)
        mbuf = mbuf.at[it].set(m)
        best, best_it, rni = track(best, best_it, rni, m, it)
        return it + 1, state, best, best_it, rni, buf, mbuf

    it, _, _, best_it, _, buf, mbuf = lax.while_loop(
        cond, body, (jnp.int32(1), state, best, best_it, rni, buf, mbuf))
    return buf, mbuf, it, best_it


def route_rows_wn(binned_t, row_node, slots, do, feats, bins_, bits_k, lid,
                  is_cat, sibling_derived=False):
    """The routing as it stood from PR 27 to PR 37: ``[W, n]`` elementwise
    work and four reductions over ``W`` (``in_any``, ``go_left_row``,
    ``lid_row``, and the leafwise caller's ``child_pos``), the feature rows
    fetched by one gather."""
    pos_oh = row_node[None, :] == slots[:, None]
    move = pos_oh & do[:, None]
    rows = binned_t[feats].astype(jnp.int32)         # [W, n]
    goleft_k = rows <= bins_[:, None]
    if is_cat is not None:
        word = _bitset_words_of_rows(bits_k, rows)
        member = ((word >> (rows.astype(jnp.uint32) & 31)) & 1).astype(bool)
        cat_k = jnp.any((feats[:, None] == jnp.arange(is_cat.shape[0]))
                        & is_cat[None, :], axis=1)
        goleft_k = jnp.where(cat_k[:, None], member, goleft_k)
    in_any = jnp.any(move, axis=0)
    go_left_row = jnp.any(move & goleft_k, axis=0)
    lid_row = jnp.sum(jnp.where(move, lid[:, None], 0), axis=0)
    new_row_node = jnp.where(
        in_any, jnp.where(go_left_row, lid_row, lid_row + 1), row_node)
    arange_w = jnp.arange(slots.shape[0], dtype=jnp.int32)
    if sibling_derived:
        cpos = jnp.where(goleft_k, arange_w[:, None], -1)
    else:
        cpos = jnp.where(goleft_k, 2 * arange_w[:, None],
                         2 * arange_w[:, None] + 1)
    child_pos = jnp.where(
        in_any, jnp.sum(jnp.where(move, cpos, 0), axis=0), -1
    ).astype(jnp.int32)
    return new_row_node, child_pos
