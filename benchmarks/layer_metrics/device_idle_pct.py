"""Share of the traced window in which no operation ran on the device:
1 - the union of the leaf operations' intervals over the window."""

UNIT, LAYER, MOVES, SOURCE = ("%", "device", "train_trees_per_s",
                              "device_trace")


def read(ctx):
    tr = ctx["trace"]
    if not tr or not tr["devices"]:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / ctx["window_s"])
