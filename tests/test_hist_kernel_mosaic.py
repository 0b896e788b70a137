"""The histogram kernels compiled by the TPU's own compiler, without a TPU.

libtpu compiles for a chip that is described and not attached, so what
Mosaic refuses (an unaligned slice, a scoped-VMEM overrun at the row block
``_pick_row_block`` chose) fails here and costs no chip time. Nothing runs:
these cases say nothing about results or speed — the interpreter cases of
tests/test_histogram.py hold the results, chip_smoke.py both on the chip.
Shapes are the benchmark cells' (BENCHMARK.json: 68,321,280 x 39 uint8 bins,
W = 1 for a root and 4, 8, 16 for the widths a leafwise round is staged at
with ``leaf_batch = 8``, 2 beside them) and the widths on both sides of the
folded layout's gate.

All in one file, the topology described inside a fixture: one process at a
time may load the TPU's library, and pytest-xdist gives a file to one worker.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from mmlspark_tpu.ops import histogram as H

ROWS, F = 68_321_280, 39


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no libtpu, or it is taken
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # an entry compiled for a described chip cannot be read back without
    # one: keep these compiles out of the persistent cache
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


# (B, W, stats, layout, accumulator block the kernel's custom call returns)
CASES = [
    (255, 1, "int8", "folded", "s32[39,32,128]"),
    (255, 16, "int8", "folded", "s32[39,96,128]"),
    (255, 21, "int8", "folded", "s32[39,128,128]"),
    (255, 16, "bf16", "folded", "f32[39,96,128]"),
    (255, 22, "int8", "plain", "s32[39,80,256]"),
    (63, 16, "int8", "plain", "s32[40,48,64]"),
    # the packed-word one-hot of the plain layout at P = 2, 1, 4, 8, 16
    (63, 1, "int8", "plain", "s32[40,16,64]"),
    (128, 16, "int8", "plain", "s32[39,48,128]"),
    (31, 16, "int8", "plain", "s32[40,48,32]"),
    (16, 16, "int8", "plain", "s32[40,48,16]"),
    (8, 16, "int8", "plain", "s32[48,48,8]"),
    # the narrow widths of a leafwise round (growth._pass_widths, PR 34: 4
    # and 8; 2 nodes run as 4, and stay here as the shape under the rule)
    (255, 2, "int8", "folded", "s32[39,32,128]"),
    (255, 4, "int8", "folded", "s32[39,32,128]"),
    (255, 8, "int8", "folded", "s32[39,64,128]"),
    (255, 2, "bf16", "folded", "f32[39,16,128]"),
    (255, 4, "bf16", "folded", "f32[39,32,128]"),
    (255, 8, "bf16", "folded", "f32[39,48,128]"),
    (63, 2, "int8", "plain", "s32[40,16,64]"),
    (63, 4, "int8", "plain", "s32[40,16,64]"),
    (63, 8, "int8", "plain", "s32[40,32,64]"),
]


@pytest.mark.parametrize("B,W,stats,layout,block", CASES)
def test_node_kernel_compiles_for_v5e_at_the_cells_shape(
        one_chip, B, W, stats, layout, block, monkeypatch):
    monkeypatch.delenv("MMLSPARK_TPU_PALLAS_INTERPRET", raising=False)
    quantized = stats == "int8"
    assert bool(H._fold_words(B, 3 * W, 1 if quantized else 2)) == (
        layout == "folded")
    assert H._pick_row_block(ROWS, F, 3 * W, B, fused_w=W,
                             quantized=quantized) >= 4096
    args = (jax.ShapeDtypeStruct((F, ROWS), jnp.uint8, sharding=one_chip),
            jax.ShapeDtypeStruct((ROWS,), jnp.int32, sharding=one_chip),
            jax.ShapeDtypeStruct((3, ROWS),
                                 jnp.int8 if quantized else jnp.float32,
                                 sharding=one_chip))
    compiled = jax.jit(lambda b, p, s: H._node_hist_pallas(
        b, p, s, W, B, quantized=quantized)).lower(*args).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "gbdt_node_hist_kernel" in text
    assert block in text, layout
