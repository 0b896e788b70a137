"""Node slots that a tree's histogram passes ran at, summed: a pass is paid
for by the node axis a tile of stats rows at a time, so this is the quantity
the widths of a round's pass move (``slots`` of the fits' run tally: the
root's 1, then the width each round or level ran at). ``None`` on a program
whose fits do not tell it."""

from layer_metrics.hist_passes_run_per_tree import told

UNIT, LAYER, MOVES, SOURCE = ("slots/tree", "tree growth",
                              "train_trees_per_s", "program_span")


def read(ctx):
    slots = told(ctx, "slots")
    return None if slots is None else slots / ctx["facts"]["trees"]
