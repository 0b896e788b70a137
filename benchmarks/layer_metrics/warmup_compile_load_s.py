"""The compiler's and the cache's share of the warm-up fit: seconds of
``gbdt_xla_compile`` (jax's ``backend_compile_duration``: an XLA compile, or
the read of the persistent cache in its place) inside the process's first
``gbdt_fit``. ``gbdt_cache_load`` inside it says how much was the read."""

from lib import spantree

UNIT, LAYER, MOVES, SOURCE = ("s", "compile cache", "setup_s",
                              "program_span")


def read(ctx):
    warmup, _ = spantree.of_run(ctx)
    if warmup is None:
        return None
    return spantree.union_s(spantree.named(warmup[1], "gbdt_xla_compile"))
