"""What a fit costs beside the device's work: over the window's fits, the
median of the program's ``gbdt_fit`` span less its ``gbdt_fit_wait`` (the
host blocked on the device). Prepare, program lookup, dispatch, download and
finalize are what is left: the time between one fit's device work and the
next's."""

import statistics

from lib import spantree

UNIT, LAYER, MOVES, SOURCE = ("ms", "round loop", "train_trees_per_s",
                              "program_span")


def read(ctx):
    _, window = spantree.of_run(ctx)
    host = []
    for fit, inside in window:
        wait = spantree.union_s(spantree.named(inside, "gbdt_fit_wait"))
        if wait is None:
            return None
        host.append(fit["dur"] * 1e-3 - wait * 1e3)
    return statistics.median(host) if host else None
