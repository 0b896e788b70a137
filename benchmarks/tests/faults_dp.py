"""Faults of the sharded fit, planted as ``faults.py`` plants its own: under
the timed path, with the compiled steps dropped so that the next fit builds
the broken program."""

import jax.numpy as jnp
from jax import lax

FAULTS = ("shard_dropped", "coarse_levels")


def plant(name: str):
    """Break the program; returns a function that mends it again."""
    from mmlspark_tpu.models.gbdt import booster as gb
    from mmlspark_tpu.models.gbdt import growth
    from mmlspark_tpu.ops import histogram
    real, real_q_max = growth._allreduce, histogram.quant_q_max

    def allreduce(x, axis_name, what, *args, **kw):
        if axis_name is not None and what == "hist":
            # shard 1's histogram never reaches the sum; the totals do, so
            # every right child inherits what the left ones lack
            x = jnp.where(lax.axis_index(axis_name) == 1,
                          jnp.zeros_like(x), x)
        return real(x, axis_name, what, *args, **kw)

    def q_max(rows):
        # the program quantizes to a quarter of the levels the configuration
        # states (31 -> 7 at the cell's size: the table's rows, not the
        # shard's), a precision below the stated one
        return float(max(1, real_q_max(rows) // 4))

    if name not in FAULTS:
        raise ValueError(f"fault must be one of {FAULTS}, got {name!r}")
    if name == "shard_dropped":
        growth._allreduce = allreduce
    else:
        histogram.quant_q_max = growth.quant_q_max = q_max
    gb._STEP_CACHE.clear()

    def mend():
        growth._allreduce = real
        histogram.quant_q_max = growth.quant_q_max = real_q_max
        gb._STEP_CACHE.clear()
    return mend
