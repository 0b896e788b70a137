"""The histogram kernel's device time as a share of the traced window."""

from lib import trace

UNIT, LAYER, MOVES, SOURCE = ("%", "histogram kernel", "train_trees_per_s",
                              "device_trace")


def read(ctx):
    if not ctx["trace"]:
        return None
    seconds, launches = trace.mosaic_kernels(ctx["trace"]["ops"],
                                             ctx["facts"]["rows"])
    if not launches:
        return None
    return 100.0 * seconds / (ctx["trace"]["devices"] * ctx["window_s"])
