import json
import os

import jax
import numpy as np

from conftest import BENCH
from lib import datagen

DATA = json.load(open(os.path.join(
    BENCH, "configs", "criteo-lgbm-255.json")))["data"]


def _chunk(seed, index, rows=4096):
    X, y = jax.jit(lambda k: datagen.gen_chunk(k, index, rows, DATA))(
        datagen.seed_key(seed))
    return np.asarray(X), np.asarray(y)


def test_same_seed_same_rows_chunk_by_chunk():
    for index in (0, 3):
        a, b = _chunk(7, index), _chunk(7, index)
        np.testing.assert_array_equal(a[0], b[0])
        np.testing.assert_array_equal(a[1], b[1])
    assert not np.array_equal(_chunk(7, 0)[0], _chunk(7, 3)[0])


def test_other_seed_other_rows_and_large_seeds_work():
    assert not np.array_equal(_chunk(7, 0)[0], _chunk(8, 0)[0])
    big = 2 ** 31 + 12345
    assert not np.array_equal(_chunk(big, 0)[0], _chunk(12345, 0)[0])


def test_shape_of_the_table():
    X, y = _chunk(3, 0, rows=65536)
    n_num, n_cat, cats = datagen.feature_layout(DATA)
    assert X.shape == (65536, n_num + n_cat) and cats == tuple(range(13, 39))
    num, cat = X[:, :n_num], X[:, n_num:]
    miss = np.isnan(num).mean(axis=0)
    np.testing.assert_allclose(miss, DATA["numeric"]["missing"], atol=0.02)
    assert np.nanmin(num) >= 0 and not np.isnan(cat).any()
    assert (cat == np.floor(cat)).all() and cat.min() >= 0
    assert (cat.max(axis=0) < np.asarray(
        DATA["categorical"]["cardinality"])).all()
    assert set(np.unique(y)) == {0.0, 1.0} and 0.01 < y.mean() < 0.10


def test_chunk_plan():
    assert datagen.chunk_plan(68321280, DATA) == (491520, 139)
    assert datagen.chunk_plan(16384, DATA) == (16384, 1)
