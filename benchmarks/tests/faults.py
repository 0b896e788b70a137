"""Faults planted underneath the timed path, for the tests that must see
``correct`` come out false and for ``tools/readings.py``'s chip readings.
Each wraps the program's ``grow_tree`` as ``booster.step_local`` calls it and
drops the compiled steps, so the next fit builds the broken program."""

import jax.numpy as jnp

FAULTS = ("state_unchanged", "half_batch", "altered_answer")


def plant(name: str):
    """Break the program; returns a function that mends it again."""
    from mmlspark_tpu.models.gbdt import booster as gb
    real = gb.grow_tree

    def grow(binned_t, grad, hess, valid, fmask, cfg, **kw):
        if name == "half_batch":
            # half of the rows left out, every mean taken over the rest
            valid = valid * (jnp.arange(valid.shape[0]) % 2 == 0)
        tree, row_node = real(binned_t, grad, hess, valid, fmask, cfg, **kw)
        if name == "state_unchanged":
            # every row reads the root's slot, whose leaf value is 0 once it
            # has split: the round returns the scores as it got them
            row_node = jnp.zeros_like(row_node)
        elif name == "altered_answer":
            tree = tree._replace(leaf_value=tree.leaf_value * 1.5)
        return tree, row_node

    if name not in FAULTS:
        raise ValueError(f"fault must be one of {FAULTS}, got {name!r}")
    gb.grow_tree = grow
    gb._STEP_CACHE.clear()

    def mend():
        gb.grow_tree = real
        gb._STEP_CACHE.clear()
    return mend
