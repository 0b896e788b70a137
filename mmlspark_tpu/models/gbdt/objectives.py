"""GBDT objectives: gradients/hessians + score transforms.

Parity targets: the objective set the reference exposes through LightGBM params
(reference: lightgbm/TrainParams.scala:86-104 — regression incl. quantile /
tweedie / huber / fair / poisson / mape, binary with ``isUnbalance``,
multiclass, lambdarank is handled by the ranker module).
All are elementwise jax functions fused by XLA into the boosting step.
"""

from __future__ import annotations

from typing import Callable, NamedTuple, Tuple

import jax
import jax.numpy as jnp
from jax import lax


class Objective(NamedTuple):
    name: str
    # (scores [n] or [n,K], label [n], weight [n]) -> (grad, hess) same shape
    grad_hess: Callable
    # raw score -> prediction-space transform (sigmoid/softmax/exp/identity)
    transform: Callable
    num_scores: int = 1  # per-class score columns (1 unless multiclass)
    init_score: Callable = None  # (label, weight) -> scalar base score


def _binary(label_pos_weight: float = 1.0):
    def grad_hess(score, y, w):
        p = jax.nn.sigmoid(score)
        # isUnbalance / scale_pos_weight: positives get extra weight
        wy = w * jnp.where(y > 0, label_pos_weight, 1.0)
        return (p - y) * wy, p * (1 - p) * wy

    def init_score(y, w):
        p = jnp.clip(jnp.sum(y * w) / jnp.sum(w), 1e-15, 1 - 1e-15)
        return jnp.log(p / (1 - p))

    return Objective("binary", grad_hess, jax.nn.sigmoid, 1, init_score)


def _regression_l2():
    def grad_hess(score, y, w):
        return (score - y) * w, w

    return Objective("regression", grad_hess, lambda s: s, 1,
                     lambda y, w: jnp.sum(y * w) / jnp.sum(w))


def weighted_quantile(y, w, q):
    """Weighted q-quantile: smallest y with cumulative weight >= q * total.

    Every init_score must honor zero weights: training feeds the padded,
    sharded label array whose padding rows carry weight 0 (and row_valid /
    sample weights flow through the same path). This is also LightGBM's own
    BoostFromAverage semantics for l1/quantile — a weighted percentile
    (PercentileFun), not an unweighted one.
    """
    order = jnp.argsort(y)
    ys, ws = y[order], w[order]
    cw = jnp.cumsum(ws)
    target = q * cw[-1]
    return ys[jnp.searchsorted(cw, target)]


def _regression_l1():
    def grad_hess(score, y, w):
        return jnp.sign(score - y) * w, w  # constant-hessian approximation

    return Objective("regression_l1", grad_hess, lambda s: s, 1,
                     lambda y, w: weighted_quantile(y, w, 0.5))


def _huber(alpha: float = 0.9):
    def grad_hess(score, y, w):
        d = score - y
        g = jnp.where(jnp.abs(d) <= alpha, d, alpha * jnp.sign(d))
        return g * w, w

    return Objective("huber", grad_hess, lambda s: s, 1,
                     lambda y, w: jnp.sum(y * w) / jnp.sum(w))


def _fair(c: float = 1.0):
    def grad_hess(score, y, w):
        d = score - y
        g = c * d / (jnp.abs(d) + c)
        h = c * c / (jnp.abs(d) + c) ** 2
        return g * w, h * w

    return Objective("fair", grad_hess, lambda s: s, 1,
                     lambda y, w: jnp.sum(y * w) / jnp.sum(w))


def _quantile(alpha: float = 0.5):
    def grad_hess(score, y, w):
        d = score - y
        g = jnp.where(d >= 0, 1.0 - alpha, -alpha)
        return g * w, w

    return Objective("quantile", grad_hess, lambda s: s, 1,
                     lambda y, w: weighted_quantile(y, w, alpha))


def _poisson():
    def grad_hess(score, y, w):
        e = jnp.exp(score)
        return (e - y) * w, e * w

    def init_score(y, w):
        return jnp.log(jnp.maximum(jnp.sum(y * w) / jnp.sum(w), 1e-15))

    return Objective("poisson", grad_hess, jnp.exp, 1, init_score)


def _tweedie(rho: float = 1.5):
    def grad_hess(score, y, w):
        e1 = jnp.exp((1 - rho) * score)
        e2 = jnp.exp((2 - rho) * score)
        g = -y * e1 + e2
        h = -y * (1 - rho) * e1 + (2 - rho) * e2
        return g * w, jnp.maximum(h, 1e-15) * w

    def init_score(y, w):
        return jnp.log(jnp.maximum(jnp.sum(y * w) / jnp.sum(w), 1e-15))

    return Objective("tweedie", grad_hess, jnp.exp, 1, init_score)


def _mape():
    def grad_hess(score, y, w):
        scale = 1.0 / jnp.maximum(jnp.abs(y), 1.0)
        return jnp.sign(score - y) * scale * w, scale * w

    return Objective("mape", grad_hess, lambda s: s, 1,
                     lambda y, w: jnp.sum(y * w) / jnp.sum(w))


def _multiclass(num_class: int):
    def grad_hess(scores, y, w):  # scores [n, K], y [n] int
        p = jax.nn.softmax(scores, axis=-1)
        onehot = jax.nn.one_hot(y.astype(jnp.int32), num_class, dtype=p.dtype)
        g = (p - onehot) * w[:, None]
        # LightGBM's multiclass hessian carries a factor of 2 (softmax upper bound)
        h = 2.0 * p * (1 - p) * w[:, None]
        return g, h

    return Objective("multiclass", grad_hess,
                     lambda s: jax.nn.softmax(s, axis=-1), num_class,
                     lambda y, w: jnp.float32(0.0))


def _label_gains(yy, label_gain):
    """Per-item NDCG gains: LightGBM's default 2^label - 1, or the explicit
    ``label_gain`` table (reference LightGBMRanker labelGain: gain of grade
    g is label_gain[g])."""
    if label_gain is None:
        return jnp.exp2(yy) - 1.0
    table = jnp.asarray(label_gain, jnp.float32)
    idx = jnp.clip(yy.astype(jnp.int32), 0, table.shape[0] - 1)
    return table[idx]


def _lambdarank(group_size: int, max_position: int = 20, sigma: float = 1.0,
                label_gain=None):
    """LambdaRank pairwise gradients over fixed-size padded query groups.

    TPU-native formulation of the reference's lambdarank objective
    (reference: lightgbm/LightGBMRanker.scala, TrainParams.scala `maxPosition`):
    the C++ lib walks variable-length query boundaries; here every group is
    padded to a static ``group_size`` S, so the all-pairs lambda computation is
    a dense [G, S, S] batch that maps straight onto the MXU — no ragged loops.
    Row weight doubles as the validity mask (0 = in-group padding).
    """
    S = int(group_size)

    def _ranks_and_discounts(score, mask):
        # rank of each item within its group by descending score (invalid last)
        sm = jnp.where(mask, score, -jnp.inf)
        order = jnp.argsort(-sm, axis=1)
        ranks = jnp.argsort(order, axis=1)
        disc = 1.0 / jnp.log2(ranks.astype(jnp.float32) + 2.0)
        return ranks, disc * mask

    def _max_dcg(gains, mask):
        # ideal DCG: gains sorted descending, truncated at max_position
        g_sorted = -jnp.sort(-jnp.where(mask, gains, 0.0), axis=1)
        pos = jnp.arange(S)
        d = jnp.where(pos < max_position, 1.0 / jnp.log2(pos + 2.0), 0.0)
        return jnp.maximum((g_sorted * d[None, :]).sum(axis=1), 1e-12)

    def grad_hess(score, y, w):
        s = score.reshape(-1, S)
        yy = y.reshape(-1, S)
        mask = (w.reshape(-1, S) > 0)
        gains = _label_gains(yy, label_gain) * mask
        _, disc = _ranks_and_discounts(s, mask)
        maxdcg = _max_dcg(gains, mask)

        sdiff = s[:, :, None] - s[:, None, :]
        pair = (mask[:, :, None] & mask[:, None, :]
                & (yy[:, :, None] > yy[:, None, :]))
        delta = (jnp.abs(gains[:, :, None] - gains[:, None, :])
                 * jnp.abs(disc[:, :, None] - disc[:, None, :])
                 / maxdcg[:, None, None])
        sig = jax.nn.sigmoid(-sigma * sdiff)
        lam = jnp.where(pair, -sigma * sig * delta, 0.0)
        hpair = jnp.where(pair, sigma * sigma * sig * (1.0 - sig) * delta, 0.0)
        grad = lam.sum(axis=2) - lam.sum(axis=1)
        hess = hpair.sum(axis=2) + hpair.sum(axis=1)
        return grad.reshape(-1), jnp.maximum(hess, 1e-9).reshape(-1)

    def init_score(y, w):
        return jnp.float32(0.0)

    return Objective("lambdarank", grad_hess, lambda sc: sc, 1, init_score)


def _ndcg_metric(scores, y, w, S: int, max_position: int,
                 label_gain=None):
    """Per-row NDCG@max_position of each row's group (weighted mean by caller:
    pass w = 1/group_size on valid rows to get the mean over groups)."""
    s = scores.reshape(-1, S)
    yy = y.reshape(-1, S)
    mask = (w.reshape(-1, S) > 0)
    gains = _label_gains(yy, label_gain) * mask
    sm = jnp.where(mask, s, -jnp.inf)
    order = jnp.argsort(-sm, axis=1)
    ranks = jnp.argsort(order, axis=1)
    disc = jnp.where(ranks < max_position,
                     1.0 / jnp.log2(ranks.astype(jnp.float32) + 2.0), 0.0)
    dcg = (gains * disc * mask).sum(axis=1)
    g_sorted = -jnp.sort(-jnp.where(mask, gains, 0.0), axis=1)
    pos = jnp.arange(S)
    ideal_d = jnp.where(pos < max_position, 1.0 / jnp.log2(pos + 2.0), 0.0)
    idcg = jnp.maximum((g_sorted * ideal_d[None, :]).sum(axis=1), 1e-12)
    ndcg = dcg / idcg  # [G]
    return jnp.broadcast_to(ndcg[:, None], (ndcg.shape[0], S)).reshape(-1)


def get_objective(name: str, num_class: int = 1, alpha: float = 0.9,
                  tweedie_variance_power: float = 1.5,
                  pos_weight: float = 1.0, group_size: int = 0,
                  max_position: int = 20, sigma: float = 1.0,
                  label_gain=None, **_metric_only) -> Objective:
    name = (name or "").lower()
    if name in ("binary", "logistic"):
        return _binary(pos_weight)
    if name in ("multiclass", "softmax"):
        return _multiclass(num_class)
    if name in ("regression", "regression_l2", "l2", "mse", "mean_squared_error", ""):
        return _regression_l2()
    if name in ("regression_l1", "l1", "mae"):
        return _regression_l1()
    if name == "huber":
        return _huber(alpha)
    if name == "fair":
        return _fair()
    if name == "quantile":
        return _quantile(alpha)
    if name == "poisson":
        return _poisson()
    if name == "tweedie":
        return _tweedie(tweedie_variance_power)
    if name == "mape":
        return _mape()
    if name == "lambdarank":
        if group_size <= 0:
            # scoring-only objective: a ranker model loaded from its text
            # dump predicts raw scores without the training-time group
            # layout; only an attempt to TRAIN with it errors
            def _no_train(*_a, **_k):
                raise ValueError(
                    "lambdarank training requires group_size (padded "
                    "group width); this objective instance is "
                    "scoring-only")
            return Objective("lambdarank", _no_train, lambda sc: sc, 1,
                             lambda y, w: jnp.float32(0.0))
        return _lambdarank(group_size, max_position, sigma, label_gain)
    raise ValueError(f"unknown objective {name!r}")


def score_transform(objective: str, num_class: int = 1, **kwargs):
    """Raw-margin -> prediction-space transform as ONE traceable function.

    ``[n, K] -> [n, K]`` for multiclass (softmax over classes), and
    ``[n, 1] -> [n]`` otherwise (the objective's own elementwise transform
    on the single score column) — exactly the shapes ``Booster.predict``
    has always returned. Split out so the device-resident inference
    program can fuse the transform into the compiled forest evaluator
    instead of re-uploading raw scores for a second host round-trip.

    The transform is pinned to f32 regardless of the predict lane's
    dtype (the quantized predictor's f32-epilogue contract, ROADMAP
    item 3): sigmoid/softmax in reduced precision would trade output
    fidelity for nothing — the epilogue is a vanishing share of the
    program's bytes.
    """
    if num_class > 1:
        return lambda raw: jax.nn.softmax(
            raw.astype(jnp.float32), axis=-1)
    transform = get_objective(objective, num_class, **kwargs).transform
    return lambda raw: transform(raw[:, 0].astype(jnp.float32))


# -- eval metrics for early stopping (reference: TrainUtils.scala:220-315) ------


HIGHER_IS_BETTER = {"ndcg", "auc", "map"}

# metric-param override support (reference: LightGBMParams `metric`): which
# eval metrics each objective family accepts. All evaluate on device, fused
# early stopping included. "auc" is a rank statistic, not a weighted mean:
# it is taken over all shards' rows at once (:func:`auc_device`) and comes
# back replicated, so it skips the psum combine of the others.
SUPPORTED_EVAL_METRICS = {
    "binary": ("binary_logloss", "binary_error", "auc"),
    "multiclass": ("multi_logloss", "multi_error"),
    "lambdarank": ("ndcg",),
    "_regression": ("rmse", "l2", "mae", "l1"),
}


def eval_metric(objective: Objective, scores, y, w,
                group_size: int = 0, max_position: int = 20,
                eval_at: int = 0, metric: str = None,
                label_gain=None, axis_name: str = None,
                **_unused) -> Tuple[str, jnp.ndarray]:
    """Per-objective eval metric (higher_is_better handled by caller).

    ``metric`` overrides the objective's default with another supported
    metric of the same family (LightGBM `metric` param; validated by the
    caller against SUPPORTED_EVAL_METRICS). Every value returned here is a
    LOCAL weighted mean — the training step re-combines across shards by
    weight, with the "rmse" name square/sqrt special case — except "auc"
    (:data:`GLOBAL_EVAL_METRICS`): with ``axis_name`` the rows of all
    shards are gathered first and every shard returns the same, final value.

    ``eval_at`` (the reference's evalAt positions) truncates the NDCG metric
    independently of the lambdarank training truncation ``max_position``.
    """
    name = objective.name
    if name == "lambdarank" and int(group_size) <= 0:
        raise ValueError(
            "lambdarank training/evaluation requires group_size (padded "
            "group width); a model loaded for scoring cannot train")
    if metric:
        if name == "binary" and metric == "auc":
            return "auc", auc_device(scores, y, w, axis_name=axis_name)
        if name == "binary" and metric == "binary_error":
            miss = ((scores > 0.0) != (y > 0.5)).astype(jnp.float32)
            return "binary_error", jnp.sum(miss * w) / jnp.sum(w)
        if name == "multiclass" and metric == "multi_error":
            pred = jnp.argmax(scores, axis=-1)
            miss = (pred != y.astype(jnp.int32)).astype(jnp.float32)
            return "multi_error", jnp.sum(miss * w) / jnp.sum(w)
        if name not in ("binary", "multiclass", "lambdarank"):
            pred = objective.transform(scores)
            if metric in ("mae", "l1"):
                # l1 is LightGBM's alias for mae; history keys track the
                # requested name
                return metric, jnp.sum(jnp.abs(pred - y) * w) / jnp.sum(w)
            if metric == "l2":
                # LightGBM l2 is MSE (not RMSE) — plain weighted mean, so
                # the cross-shard combine needs no special case
                return "l2", jnp.sum((pred - y) ** 2 * w) / jnp.sum(w)
        # remaining supported values are the family defaults
    if name == "lambdarank":
        S = int(group_size)
        if scores.shape[0] < S or scores.shape[0] % S != 0:
            return "ndcg", jnp.float32(0.0)  # shape probe only
        vals = _ndcg_metric(scores, y, w, S, eval_at or max_position,
                            label_gain)
        return "ndcg", jnp.sum(vals * w) / jnp.maximum(jnp.sum(w), 1e-12)
    if name == "binary":
        p = jnp.clip(jax.nn.sigmoid(scores), 1e-15, 1 - 1e-15)
        ll = -(y * jnp.log(p) + (1 - y) * jnp.log1p(-p))
        return "binary_logloss", jnp.sum(ll * w) / jnp.sum(w)
    if name == "multiclass":
        logp = jax.nn.log_softmax(scores, axis=-1)
        pick = jnp.take_along_axis(logp, y.astype(jnp.int32)[:, None], axis=1)[:, 0]
        return "multi_logloss", -jnp.sum(pick * w) / jnp.sum(w)
    pred = objective.transform(scores)
    se = (pred - y) ** 2
    return "rmse", jnp.sqrt(jnp.sum(se * w) / jnp.sum(w))


# metrics that :func:`eval_metric` returns whole (over every shard's rows,
# replicated) and not as a local weighted mean to be combined
GLOBAL_EVAL_METRICS = frozenset({"auc"})

_AUC_BLOCK = 1024


def _blocked_sum(x):
    """Sum of ``x`` [n] (n a multiple of ``_AUC_BLOCK``) in two levels, so
    that no partial sum is carried across more than a block's, then a block
    count's, additions whatever order the backend reduces in."""
    return jnp.sum(jnp.sum(x.reshape(-1, _AUC_BLOCK), axis=1))


def auc_device(scores, y, w, axis_name: str = None):
    """Exact weighted AUC with ties counted half (LightGBM's binary ``auc``),
    on the device: f32 scalar; 0.5 where a class is absent.

    One sort of the margin carries the rows' weight along, signed by class. With ``C`` the running sum of negative weight in that order, a
    positive row is above the negative weight before its tie group and level
    with the group's own, so it scores ``(C before the group + C through the
    group) / 2``; both are handed to every row of a group by a running
    maximum from the group's first row and a reverse running minimum from
    its last (``C`` never falls), so there is no gather and no segment
    arithmetic. Accumulation is f32 in **blocked sums**: the running sum is
    log-depth, exact for whole-number weights below 2^24 in all (unit
    weights up to 16.7 M rows); each row's score is divided by the negative
    weight before the last sum, so what is summed lies in [0, 1], and that
    sum is taken in blocks of 1024 and then over the blocks: at 2.95 M rows
    the rounding left is a few 1e-8 of the value.

    Inside ``shard_map`` give ``axis_name``: margin, label and weight are
    gathered over it first, and every shard computes the same value from the
    same arrays in the same order, so a loop condition that reads it stays
    replicated."""
    with jax.named_scope("gbdt_valid_metric"):
        scores = scores.astype(jnp.float32)
        pos = y > 0.5
        w = w.astype(jnp.float32)
        if axis_name is not None:
            scores, pos, w = (lax.all_gather(a, axis_name, tiled=True)
                              for a in (scores, pos, w))
        pad = -scores.shape[0] % _AUC_BLOCK
        # weightless rows at +inf: last in the order, nothing in any sum.
        # One payload, the weight signed by the class (a weightless row has
        # no class to keep); the order inside a tie group is free
        scores = jnp.pad(scores, (0, pad), constant_values=jnp.inf)
        s, signed = lax.sort((scores, jnp.pad(jnp.where(pos, w, -w),
                                              (0, pad))),
                             num_keys=1, is_stable=False)
        wpos, wneg = jnp.maximum(signed, 0.0), jnp.maximum(-signed, 0.0)
        edge, end = s[1:] != s[:-1], jnp.ones(1, bool)
        first = jnp.concatenate([end, edge])
        last = jnp.concatenate([edge, end])
        through = jnp.cumsum(wneg)
        below = lax.cummax(jnp.where(first, through - wneg, -jnp.inf))
        level = lax.cummin(jnp.where(last, through, jnp.inf), reverse=True)
        tp, tn = _blocked_sum(wpos), _blocked_sum(wneg)
        rank = _blocked_sum(wpos * (0.5 * (below + level)
                                    / jnp.maximum(tn, 1e-30)))
        return jnp.where((tp > 0) & (tn > 0),
                         rank / jnp.maximum(tp, 1e-30), jnp.float32(0.5))
