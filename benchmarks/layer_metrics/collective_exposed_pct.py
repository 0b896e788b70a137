"""Device time in cross-chip collectives as a share of the traced window:
the leaf operations whose HLO opcode is ``all-reduce``, ``all-gather``,
``reduce-scatter``, ``collective-permute`` or ``all-to-all`` (an asynchronous
one's ``-start`` and ``-done`` halves too: what lies between them is other
operations' time), seconds summed over the devices, over devices x window.
The opcode, not the instruction's name: XLA names a reduction after the jax
primitive (``%psum.21 = f32[39,48,255] all-reduce(...)``) unless it has
combined several (``%all-reduce.42 = (f32[39,3,255], f32[3]) all-reduce(``). The device
runs one operation at a time, so this is the time the collectives were not
hidden behind compute, a shard's wait for the slowest shard included.
``None`` where no collective ran: a one-chip program has none."""

import re
import sys

UNIT, LAYER, MOVES, SOURCE = ("%", "collectives", "train_trees_per_s",
                              "device_trace")

_COLLECTIVE = re.compile(
    r"^%\S+ = .*?[\])}] "
    r"(all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all)"
    r"(-start|-done)?\(")


def read(ctx):
    tr = ctx["trace"]
    if not tr or not tr["devices"]:
        return None
    hits = {n: v for n, v in tr["ops"].items() if _COLLECTIVE.match(n)}
    if not hits:
        return None
    for name, (seconds, launches) in sorted(hits.items(),
                                            key=lambda kv: -kv[1][0]):
        print(f"collective {seconds:.6f} s in {launches} launches: "
              f"{name[:160]}", file=sys.stderr)
    return (100.0 * sum(s for s, _ in hits.values())
            / (tr["devices"] * ctx["window_s"]))
