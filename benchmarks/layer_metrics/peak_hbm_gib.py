"""``memory_stats()["peak_bytes_in_use"]`` of the fullest device after the
window, before the reference runs."""

UNIT, LAYER, MOVES, SOURCE = ("GiB", "device", "train_trees_per_s",
                              "program_counter")


def read(ctx):
    peak = ctx["device"].get("memory_peak_bytes")
    return None if peak is None else peak / 2 ** 30
