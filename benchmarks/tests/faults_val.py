"""Faults of the validated fit, planted as ``faults.py`` plants its own:
under the timed path, with the compiled steps dropped so that the next fit
builds the broken program.

``ties_by_position``: the device metric ranks tied rows by where the sort
left them, not half and half. ``margin_not_carried``: every round scores the
held-out rows from the base margin, so the metric after the second tree is
that tree's alone (a tree missing from the margin; the scorer cannot tell
trees apart, so the fault sits in the round loop's state). ``training_labels``:
the held-out rows carry the labels of training chunks (the driver's table,
not the program). All three must fail ``auc_gap``.
"""

import jax.numpy as jnp
from jax import lax

FAULTS = ("ties_by_position", "margin_not_carried", "training_labels")


def _auc_ties_by_position(scores, y, w, axis_name=None):
    if axis_name is not None:
        scores, y, w = (lax.all_gather(a, axis_name, tiled=True)
                        for a in (scores, y, w))
    _, signed = lax.sort((scores.astype(jnp.float32),
                          jnp.where(y > 0.5, w, -w)), num_keys=1,
                         is_stable=False)
    wpos, wneg = jnp.maximum(signed, 0.0), jnp.maximum(-signed, 0.0)
    tp, tn = jnp.sum(wpos), jnp.sum(wneg)
    below = jnp.cumsum(wneg) - wneg          # every row a group of its own
    return jnp.sum(wpos * (below / jnp.maximum(tn, 1e-30))) / jnp.maximum(
        tp, 1e-30)


class _SwappedLabels:
    """``lib.datagen`` with the labels of chunk ``c >= first`` taken from
    chunk ``c - first``."""

    def __init__(self, real):
        self._real, self.first = real, None

    def __getattr__(self, name):
        return getattr(self._real, name)

    def gen_chunk(self, key, c, chunk_rows, data):
        X, _ = self._real.gen_chunk(key, c, chunk_rows, data)
        _, y = self._real.gen_chunk(
            key, jnp.where(c >= self.first, c - self.first, c), chunk_rows,
            data)
        return X, y


def plant(name: str):
    """Break the program (or the driver's held-out labels); returns a
    function that mends it again."""
    from lib import gbdt_trainval
    from mmlspark_tpu.models.gbdt import booster as gb
    from mmlspark_tpu.models.gbdt import objectives
    real_auc, real_scan = objectives.auc_device, gb._fused_es_scan
    real_datagen, real_set_up = (gbdt_trainval.datagen,
                                 gbdt_trainval.Driver.set_up)

    def forgetful_scan(one_iter, state0, *args, **kw):
        def from_the_base(it, state):
            return one_iter(it, (state[0], state0[1]))
        return real_scan(from_the_base, state0, *args, **kw)

    if name not in FAULTS:
        raise ValueError(f"fault must be one of {FAULTS}, got {name!r}")
    if name == "ties_by_position":
        objectives.auc_device = _auc_ties_by_position
    elif name == "margin_not_carried":
        gb._fused_es_scan = forgetful_scan
    else:
        swapped = _SwappedLabels(real_datagen)

        def set_up(self):
            swapped.first = self.valid_plan["first_chunk"]
            return real_set_up(self)

        gbdt_trainval.datagen = swapped
        gbdt_trainval.Driver.set_up = set_up
    gb._STEP_CACHE.clear()

    def mend():
        objectives.auc_device, gb._fused_es_scan = real_auc, real_scan
        gbdt_trainval.datagen = real_datagen
        gbdt_trainval.Driver.set_up = real_set_up
        gb._STEP_CACHE.clear()
    return mend
