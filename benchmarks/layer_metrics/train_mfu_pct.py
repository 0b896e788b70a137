"""The whole step's share of the chip's peak: least time for the needed work
of every tree grown in the window (``lib/work.py``) over the window's wall
time. It still bounds a gain once a later PR takes the histogram kernel off
the path and leaves that kernel's roofline silent."""

from lib import work

UNIT, LAYER, MOVES, SOURCE = ("%", "whole step", "train_trees_per_s",
                              "host_clock")


def read(ctx):
    f = ctx["facts"]
    least = work.least_seconds(
        work.needed_work(f["node_cnt_sum"], f["num_features"],
                         f["bin_bytes"]),
        ctx["device"]["kind"], f["stats_dtype"], ctx["chips"])
    return work.share_pct(least["seconds"], f["window_s"], "train_mfu_pct")
