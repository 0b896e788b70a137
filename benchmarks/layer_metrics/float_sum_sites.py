"""Sites of tree growth's float path that sum at the node's own magnitude
where they once subtracted at its parent's: the distinct ``site`` labels of
the program's ``gbdt_float_sums_total{site, form}`` that building the fit's
program counted (``right_side``, ``child_totals``, ``node_totals``, and
``kernel_accum`` once the kernel's accumulator has a second level). The
program counts a site where it is staged out; the driver reads what the
warm-up fit's build added. A later change that puts a subtraction back, and
takes the site's count with it, reads lower. ``None`` where the program
counted none: an int8 fit, or a program without the counter."""

UNIT, LAYER, MOVES, SOURCE = ("count", "tree growth", "train_trees_per_s",
                              "program_counter")


def read(ctx):
    staged = ctx["facts"].get("float_sum_sites") or {}
    sites = sum(1 for v in staged.values() if v > 0)
    return float(sites) if sites else None
