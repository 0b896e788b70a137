"""The held-out rows' share of the device's busy time: seconds of the traced
window's leaf operations whose result or operand shape carries the held-out
row count (``2949120`` in ``criteo255q.trainval``: the scorer's passes over
the rows, the metric's sort and running sums, the margin's update), over the
device's busy seconds. The operations tell themselves apart by shape, as the
kernel's variants do in ``breakdown.device_ops``; the training rows' count
is another number. ``None`` where the run held no rows out or the trace
shows no such operation, never 0."""

import re

UNIT, LAYER, MOVES, SOURCE = ("%", "validation", "train_trees_per_s",
                              "device_trace")


def held_out_seconds(ops: dict, valid_rows: int):
    """(seconds, launches) of the operations in ``ops`` (``{hlo text:
    [seconds, launches]}``) whose text has a dimension of ``valid_rows``."""
    dim = re.compile(r"[\[,]" + str(int(valid_rows)) + r"[\],]")
    seconds, launches = 0.0, 0
    for name, (s, c) in ops.items():
        if dim.search(name):
            seconds += s
            launches += c
    return seconds, launches


def read(ctx):
    valid_rows = ctx["facts"].get("valid_rows")
    if not ctx["trace"] or not valid_rows or not ctx["trace"]["busy_s"]:
        return None
    seconds, launches = held_out_seconds(ctx["trace"]["ops"], valid_rows)
    if not launches:
        return None
    return 100.0 * seconds / (ctx["trace"]["devices"]
                              * ctx["trace"]["busy_s"])
