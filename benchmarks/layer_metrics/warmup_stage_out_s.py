"""Python's share of the warm-up fit: seconds of jax tracing
(``gbdt_jax_trace``) and lowering to MLIR (``gbdt_jax_lower``) inside the
process's first ``gbdt_fit``, nested stages counted once. Neither a cache
nor a chip shortens it; only the program's shape does."""

from lib import spantree

UNIT, LAYER, MOVES, SOURCE = ("s", "round loop", "setup_s", "program_span")


def read(ctx):
    warmup, _ = spantree.of_run(ctx)
    if warmup is None:
        return None
    return spantree.union_s(spantree.named(warmup[1], "gbdt_jax_trace",
                                           "gbdt_jax_lower"))
