"""Histogram-kernel launches in the traced window over the trees grown in
it: how many times a tree streams the whole binned matrix."""

from lib import trace

UNIT, LAYER, MOVES, SOURCE = ("passes/tree", "tree growth",
                              "train_trees_per_s", "device_trace")


def read(ctx):
    if not ctx["trace"]:
        return None
    _, launches = trace.mosaic_kernels(ctx["trace"]["ops"],
                                       ctx["facts"]["rows"])
    if not launches:
        return None
    return launches / (ctx["facts"]["trees"] * ctx["trace"]["devices"])
