"""Needed work of GBDT training and the chips' peaks: the yardstick that the
roofline and utilization shares are taken against.

Needed work is the same whatever implements it. Every node of every grown
tree must have had its histogram: ``node_cnt x F`` accumulations of three
statistics. So, over all nodes of all trees,

    ops   = 6 * F * sum(node_cnt)           (a multiply and an add, three times)
    bytes = sum(node_cnt) * (F * bin_bytes + 3 * 4)

There is no factor for the bins, none for padding, and no credit for rows
that were streamed and are not in the node.
"""

from __future__ import annotations

# Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 393 TOP/s int8,
# 819 GB/s HBM per chip. An unknown device_kind is an error, not a default.
PEAKS_SOURCE = "Google Cloud documentation, TPU v5e"
_V5E = {"bf16": 197e12, "int8": 393e12, "bytes_per_s": 819e9}
PEAKS = {"TPU v5 lite": _V5E, "TPU v5e": _V5E}


def peaks_for(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no peaks for device_kind {device_kind!r}; "
                       f"the table holds {sorted(PEAKS)} ({PEAKS_SOURCE})")
    return PEAKS[device_kind]


def needed_work(node_cnt_sum: float, num_features: int,
                bin_bytes: int) -> dict:
    """Operations and bytes that ``node_cnt_sum`` rows-in-nodes need."""
    return {"ops": 6.0 * num_features * node_cnt_sum,
            "bytes": node_cnt_sum * (num_features * bin_bytes + 3 * 4)}


def least_seconds(work: dict, device_kind: str, stats_dtype: str,
                  chips: int = 1) -> dict:
    """Least time the chips could take for ``work`` and which bound holds."""
    peaks = peaks_for(device_kind)
    if stats_dtype not in ("bf16", "int8"):
        raise KeyError(f"stats_dtype {stats_dtype!r} has no peak")
    t_ops = work["ops"] / (peaks[stats_dtype] * chips)
    t_bytes = work["bytes"] / (peaks["bytes_per_s"] * chips)
    return {"seconds": max(t_ops, t_bytes),
            "bound": "compute" if t_ops >= t_bytes else "memory"}


def share_pct(least_s: float, taken_s: float, what: str) -> float:
    """``least_s / taken_s`` in percent; over 100 is a miscount and fails
    the run rather than being clipped."""
    pct = 100.0 * least_s / taken_s
    if pct > 100.0:
        raise ValueError(f"{what} reads {pct:.1f}% of the peak: the needed "
                         "work is counted too high or the time leaves out "
                         "part of it")
    return pct
