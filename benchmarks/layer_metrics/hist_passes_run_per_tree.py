"""Histogram passes that the window's fits ran, a tree, as the program
counted them on the device: every ``gbdt_fit`` span tells its fit's run tally
(``passes``, ``launches``, ``slots``, ``live``; the tally rides the download
that brings the trees, tracing or not). The twin of ``hist_passes_per_tree``,
which counts kernel launches in a device trace. ``None`` on a program whose
fits do not tell it."""

from lib import spantree

UNIT, LAYER, MOVES, SOURCE = ("passes/tree", "tree growth",
                              "train_trees_per_s", "program_span")


def told(ctx, attribute):
    """The sum of ``attribute`` over the window's fits; ``None`` unless every
    one of them tells it."""
    _, window = spantree.of_run(ctx)
    values = [fit.get("args", {}).get(attribute) for fit, _ in window]
    if not values or None in values:
        return None
    return sum(values)


def read(ctx):
    passes = told(ctx, "passes")
    return None if passes is None else passes / ctx["facts"]["trees"]
