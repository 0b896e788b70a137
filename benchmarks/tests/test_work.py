import pytest

from lib import work


def test_three_node_tree_by_hand():
    # root with 10 rows splits into leaves of 7 and 3: 20 rows-in-nodes;
    # 4 features of one byte each
    w = work.needed_work(node_cnt_sum=10 + 7 + 3, num_features=4, bin_bytes=1)
    assert w["ops"] == 6 * 4 * 20 == 480
    assert w["bytes"] == 20 * (4 * 1 + 12) == 320
    least = work.least_seconds(w, "TPU v5 lite", "bf16")
    assert least["bound"] == "memory"
    assert least["seconds"] == pytest.approx(320 / 819e9)
    assert work.least_seconds(w, "TPU v5e", "int8", chips=4)["seconds"] == \
        pytest.approx(320 / 819e9 / 4)


def test_compute_bound_when_bins_are_narrow_and_features_many():
    w = {"ops": 197e12, "bytes": 1.0}
    assert work.least_seconds(w, "TPU v5e", "bf16") == {
        "seconds": pytest.approx(1.0), "bound": "compute"}
    assert work.least_seconds(w, "TPU v5e", "int8")["seconds"] == \
        pytest.approx(197 / 393)


def test_unknown_device_and_dtype_raise():
    with pytest.raises(KeyError):
        work.peaks_for("TPU v4")
    with pytest.raises(KeyError):
        work.least_seconds({"ops": 1, "bytes": 1}, "TPU v5e", "fp8")


def test_share_over_100_fails_instead_of_clipping():
    assert work.share_pct(1.0, 4.0, "x") == 25.0
    with pytest.raises(ValueError):
        work.share_pct(2.0, 1.0, "x")
