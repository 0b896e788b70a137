"""Native host-runtime tests: C++ <-> Python exact parity.

The native library is the NativeLoader analog (reference:
core/env/NativeLoader.java:28-140): compiled on first use, with pure-Python
fallbacks. Hashing defines feature identity, so parity must be bit-for-bit.
"""

import os

import numpy as np
import pytest

from mmlspark_tpu.native import (bin_batch, csv_read_floats, get_lib,
                                 murmur3_batch, native_available)

needs_native = pytest.mark.skipif(not native_available(),
                                  reason="no C++ toolchain on this host")


def _py_murmur(data, seed):
    # reference pure-Python implementation, independent of the native dispatch
    import importlib

    import mmlspark_tpu.ops.murmur as m
    if isinstance(data, str):
        data = data.encode("utf-8")
    n = len(data)
    h = seed & 0xFFFFFFFF
    C1, C2, MASK = 0xCC9E2D51, 0x1B873593, 0xFFFFFFFF

    def rotl(x, r):
        return ((x << r) | (x >> (32 - r))) & MASK

    for i in range(n // 4):
        k = int.from_bytes(data[4 * i:4 * i + 4], "little")
        k = rotl((k * C1) & MASK, 15) * C2 & MASK
        h ^= k
        h = (rotl(h, 13) * 5 + 0xE6546B64) & MASK
    k = 0
    tail = data[(n // 4) * 4:]
    if len(tail) >= 3:
        k ^= tail[2] << 16
    if len(tail) >= 2:
        k ^= tail[1] << 8
    if len(tail) >= 1:
        k ^= tail[0]
        k = rotl((k * C1) & MASK, 15) * C2 & MASK
        h ^= k
    h ^= n
    h ^= h >> 16
    h = (h * 0x85EBCA6B) & MASK
    h ^= h >> 13
    h = (h * 0xC2B2AE35) & MASK
    return h ^ (h >> 16)


def test_murmur_known_vectors():
    """Public MurmurHash3_x86_32 test vectors."""
    from mmlspark_tpu.ops.murmur import murmur3_32
    assert murmur3_32(b"", 0) == 0
    assert murmur3_32(b"", 1) == 0x514E28B7
    assert murmur3_32(b"hello", 0) == 0x248BFA47
    assert murmur3_32(b"hello, world", 0) == 0x149BBB7F
    assert murmur3_32(b"The quick brown fox jumps over the lazy dog", 0) \
        == 0x2E4FF723


@needs_native
def test_native_matches_python_murmur():
    rng = np.random.default_rng(0)
    strings, seeds = [], []
    for n in range(0, 40):
        s = bytes(rng.integers(0, 256, n).astype(np.uint8)).decode(
            "latin-1")
        strings.append(s)
        seeds.append(int(rng.integers(0, 2 ** 32)))
    strings += ["", "a", "héllo wörld", "日本語テキスト", "x" * 1000]
    seeds += [0, 1, 42, 7, 2 ** 32 - 1]
    got = murmur3_batch(strings, seeds)
    expect = np.asarray([_py_murmur(s, seed) for s, seed
                         in zip(strings, seeds)], dtype=np.uint32)
    np.testing.assert_array_equal(got, expect)


@needs_native
def test_native_single_hash_dispatch():
    from mmlspark_tpu.ops.murmur import murmur3_32
    assert get_lib() is not None
    for s in (b"", b"abc", "unicode☃".encode("utf-8")):
        assert murmur3_32(s, 123) == _py_murmur(s, 123)


@needs_native
def test_bin_batch_matches_numpy():
    rng = np.random.default_rng(1)
    X = rng.normal(size=(200, 5)).astype(np.float32)
    X[rng.random(X.shape) < 0.1] = np.nan
    ub = np.sort(rng.normal(size=(5, 15)).astype(np.float32), axis=1)
    got = bin_batch(X, ub)
    expect = np.empty_like(got)
    for f in range(5):
        expect[:, f] = np.searchsorted(ub[f], X[:, f], side="left")
    expect[np.isnan(X)] = 0
    np.testing.assert_array_equal(got, expect)


def test_binner_uses_dispatch():
    from mmlspark_tpu.ops.binning import QuantileBinner
    rng = np.random.default_rng(2)
    X = rng.normal(size=(300, 3)).astype(np.float32)
    binner = QuantileBinner(max_bin=16).fit(X)
    bins = binner.transform(X)
    assert bins.shape == X.shape and bins.dtype == np.int32
    assert bins.min() >= 0 and bins.max() <= 15
    # monotone: larger value -> same or larger bin (per feature)
    order = np.argsort(X[:, 0])
    assert np.all(np.diff(bins[order, 0]) >= 0)


def test_csv_read_floats():
    text = "1.5,2,3\n4,,nan\n7,8.25,-9\n"
    out = csv_read_floats(text, 3)
    assert out.shape == (3, 3)
    np.testing.assert_allclose(out[0], [1.5, 2, 3])
    assert np.isnan(out[1, 1]) and np.isnan(out[1, 2])
    np.testing.assert_allclose(out[2], [7, 8.25, -9])


def test_csv_read_floats_ragged_raises():
    with pytest.raises(ValueError):
        csv_read_floats("1,2,3\n4,5\n", 3)


@needs_native
def test_csv_edge_cases_match_fallback(monkeypatch):
    """Leading blank lines, padded fields, bad fields: identical on both
    paths (behavior must not depend on toolchain availability)."""
    import mmlspark_tpu.native as nat
    long_field = "1." + "0" * 200 + "5"     # >128 chars, still a valid float
    text = f"\n1, 2 ,3\n\n4,abc,  \n7,8,{long_field}\n"
    native_out = csv_read_floats(text, 3)
    monkeypatch.setattr(nat, "_lib", None)
    monkeypatch.setattr(nat, "_lib_tried", True)
    py_out = csv_read_floats(text, 3)
    assert native_out.shape == py_out.shape == (3, 3)
    np.testing.assert_allclose(native_out[0], [1, 2, 3])
    np.testing.assert_allclose(native_out[2], [7, 8, 1.0])
    assert np.isnan(native_out[1, 1]) and np.isnan(native_out[1, 2])
    np.testing.assert_array_equal(np.isnan(native_out), np.isnan(py_out))
    np.testing.assert_allclose(native_out[~np.isnan(native_out)],
                               py_out[~np.isnan(py_out)])


@needs_native
def test_csv_native_matches_python_fallback(monkeypatch):
    text = "\n".join(",".join(str(v) for v in row)
                     for row in np.random.default_rng(3)
                     .normal(size=(50, 4)).round(4))
    native_out = csv_read_floats(text, 4)
    import mmlspark_tpu.native as nat
    monkeypatch.setattr(nat, "_lib", None)
    monkeypatch.setattr(nat, "_lib_tried", True)
    py_out = csv_read_floats(text, 4)
    np.testing.assert_allclose(native_out, py_out, rtol=1e-6)


def test_worker_pool_paths_match_serial(tmp_path):
    """The pool's parallel code paths never engage on a 1-core host
    (hardware_concurrency == 1 -> zero workers), so force a 4-thread pool
    via the env override in a subprocess and pin every pooled entry point
    — treeshap, bin_batch, murmur3_batch, csv_read_floats — bitwise equal
    to this process's serial results. Inputs are built ONCE here and
    shipped to the subprocess as files, so the two sides cannot drift."""
    import subprocess
    import sys

    from mmlspark_tpu import native
    if not native.native_available():
        pytest.skip("no native toolchain")

    rng = np.random.default_rng(0)
    n, F, B = 80_000, 16, 62
    X = rng.normal(size=(n, F)).astype(np.float32)
    ub = np.sort(rng.normal(size=(F, B)).astype(np.float32), axis=1)
    np.save(tmp_path / "X.npy", X)
    np.save(tmp_path / "ub.npy", ub)
    strings = [f"w{i % 997}_{i}" for i in range(70_000)]
    seeds = (np.arange(len(strings)) % 7).astype(np.uint32)
    np.save(tmp_path / "seeds.npy", seeds)
    rows = [",".join(f"{v:.5g}" for v in r) for r in X[:50_000]]
    rows[100] = ""   # blank-line skip crosses span boundaries
    (tmp_path / "data.csv").write_text("\n".join(rows))
    # a small booster for the pooled treeshap path (deep enough to be
    # nontrivial, tiny enough to train fast)
    from mmlspark_tpu.models.gbdt.booster import train_booster
    from mmlspark_tpu.models.gbdt.growth import GrowConfig
    y = (X[:, 0] > 0).astype(np.float32)
    booster = train_booster(X[:8000], y[:8000], objective="binary",
                            num_iterations=5,
                            cfg=GrowConfig(num_leaves=15), max_bin=31)
    import pickle
    (tmp_path / "booster.pkl").write_bytes(pickle.dumps(booster))

    script = r"""
import numpy as np, os, pickle, sys
from mmlspark_tpu import native
assert native.native_available()
d = sys.argv[1]
X = np.load(d + "/X.npy"); ub = np.load(d + "/ub.npy")
seeds = np.load(d + "/seeds.npy")
strings = [f"w{i % 997}_{i}" for i in range(len(seeds))]
np.save(d + "/bins.npy", native.bin_batch(X, ub))
np.save(d + "/hash.npy", native.murmur3_batch(strings, seeds))
np.save(d + "/csv.npy", native.csv_read_floats(
    open(d + "/data.csv").read(), X.shape[1]))
booster = pickle.loads(open(d + "/booster.pkl", "rb").read())
os.environ["MMLSPARK_TPU_SHAP_HOST"] = "1"
np.save(d + "/shap.npy", booster.predict_contrib(X[:4096]))
print("SUB_OK")
"""
    env = dict(os.environ)
    env.update({"JAX_PLATFORMS": "cpu",
                "MMLSPARK_TPU_NATIVE_THREADS": "4"})
    r = subprocess.run([sys.executable, "-c", script, str(tmp_path)],
                       capture_output=True, text=True, timeout=420,
                       env=env, cwd=os.path.dirname(os.path.dirname(
                           os.path.abspath(__file__))))
    assert "SUB_OK" in r.stdout, r.stderr[-2000:]

    np.testing.assert_array_equal(np.load(tmp_path / "bins.npy"),
                                  native.bin_batch(X, ub))
    np.testing.assert_array_equal(np.load(tmp_path / "hash.npy"),
                                  native.murmur3_batch(strings, seeds))
    np.testing.assert_array_equal(
        np.load(tmp_path / "csv.npy"),
        native.csv_read_floats((tmp_path / "data.csv").read_text(), F))
    os.environ["MMLSPARK_TPU_SHAP_HOST"] = "1"
    try:
        np.testing.assert_array_equal(np.load(tmp_path / "shap.npy"),
                                      booster.predict_contrib(X[:4096]))
    finally:
        os.environ.pop("MMLSPARK_TPU_SHAP_HOST", None)
