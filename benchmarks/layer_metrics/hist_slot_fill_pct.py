"""The useful share of the node axis the histogram passes ran at: node
positions that held rows (``live`` of the fits' run tally: a round's splits
where the sibling is derived, both children where it is summed, the root's 1)
over the node slots of the widths that ran (``slots``). ``None`` on a program
whose fits do not tell them."""

from layer_metrics.hist_passes_run_per_tree import told

UNIT, LAYER, MOVES, SOURCE = ("%", "tree growth", "train_trees_per_s",
                              "program_span")


def read(ctx):
    live, slots = told(ctx, "live"), told(ctx, "slots")
    return None if live is None or not slots else 100.0 * live / slots
