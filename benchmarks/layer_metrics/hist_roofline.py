"""Least time for the histograms the grown trees needed (``lib/work.py``:
every node's own rows, once, at the statistics' peak) over the histogram
kernel's device time. The bound that holds is memory for both
configurations of this PR (51 bytes a row against 234 operations)."""

from lib import trace, work

UNIT, LAYER, MOVES, SOURCE = ("%", "histogram kernel", "train_trees_per_s",
                              "device_trace")


def read(ctx):
    if not ctx["trace"]:
        return None
    f = ctx["facts"]
    seconds, launches = trace.mosaic_kernels(ctx["trace"]["ops"], f["rows"])
    if not launches:
        return None
    least = work.least_seconds(
        work.needed_work(f["node_cnt_sum"], f["num_features"],
                         f["bin_bytes"]),
        ctx["device"]["kind"], f["stats_dtype"], ctx["chips"])
    return work.share_pct(least["seconds"],
                          seconds / ctx["trace"]["devices"],
                          "hist_roofline")
