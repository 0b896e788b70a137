"""MiB that the cross-shard reductions of tree growth leave on one shard, a
tree. The program counts every reduction's result bytes where it is staged
out, from its static shape, with how often the built program runs it
(``gbdt_allreduce_bytes_total{what, per}``); the driver reads what building
the fit's program added. A leafwise tree runs the ``tree`` sites once and
the ``round`` sites once a round, and its rounds are its histogram passes on
the device but the root's (``hist_passes_per_tree``, from the trace). The
``level`` sites of a depthwise program count once each. ``None`` where the
program counted none: a one-chip fit, or a program without the counter."""

from lib import trace

UNIT, LAYER, MOVES, SOURCE = ("MiB/tree", "collectives", "train_trees_per_s",
                              "program_counter")


def read(ctx):
    f = ctx["facts"]
    staged = f.get("allreduce_bytes") or {}
    if not ctx["trace"] or not sum(staged.values()):
        return None
    _, launches = trace.mosaic_kernels(ctx["trace"]["ops"], f["rows"])
    if not launches:
        return None
    passes = launches / (f["trees"] * ctx["trace"]["devices"])
    return (staged["tree"] + staged["level"]
            + staged["round"] * (passes - 1)) / 2 ** 20
