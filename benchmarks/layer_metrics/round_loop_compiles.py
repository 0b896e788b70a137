"""Programs built or compiled cold inside the window: the program's
``gbdt_program_builds_total`` plus ``persistent_compile_cache_misses_total``,
after the window minus before. A warmed window reads 0."""

UNIT, LAYER, MOVES, SOURCE = ("count", "round loop", "train_trees_per_s",
                              "program_counter")
NEEDS_CHIP = False


def read(ctx):
    return float(ctx["facts"]["compiles_in_window"])
