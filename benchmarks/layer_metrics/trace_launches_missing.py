"""Kernel launches that the window's fits ran and the device trace does not
show: the fits' own count (``launches`` of their run tally, a shard) times
the devices, less the Mosaic launches found in the trace (the call
``hist_passes_per_tree`` makes). A check of the trace, not a quantity to
drive down: 0 on a whole trace, and any other value, of either sign, voids
every metric of the line that is read from the trace. Above 0 the profiler
dropped device events and says nothing of it; below 0 the trace holds
launches that the window's fits did not tell. Such a run is traced again, not
quoted. (``MOVES`` and ``better`` are the schema's: it has no neutral
value.) ``None`` without a trace, or on a program whose fits do not tell
their launches."""

from layer_metrics.hist_passes_run_per_tree import told
from lib import trace

UNIT, LAYER, MOVES, SOURCE = ("count", "device", "train_trees_per_s",
                              "device_trace")


def read(ctx):
    if not ctx["trace"]:
        return None
    launches = told(ctx, "launches")
    if launches is None:
        return None
    _, found = trace.mosaic_kernels(ctx["trace"]["ops"],
                                    ctx["facts"]["rows"])
    return launches * ctx["trace"]["devices"] - found
