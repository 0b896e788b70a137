"""Evaluations of the validation metric that the window's round loops read
on the host: the program's ``gbdt_valid_metric_total{where=host}``, after the
window minus before. The fused path takes every evaluation inside the
device's ``while_loop`` and reads 0; a fit that fell to the host loop reads
one a tree. ``None`` on a program without the counter's facts. Read on
the chip only, as every per-layer metric but ``round_loop_compiles``."""

UNIT, LAYER, MOVES, SOURCE = ("count", "round loop", "train_trees_per_s",
                              "program_counter")


def read(ctx):
    evals = ctx["facts"].get("valid_metric_evals")
    return None if evals is None else float(evals["host"])
