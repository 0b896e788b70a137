#!/usr/bin/env python3
"""Static schedule of the histogram kernel, from the TPU compiler, no chip.

    python tools/kernel_bundles.py --bins 255 --nodes 16 --stats int8
    python tools/kernel_bundles.py --bins 63 --nodes 1 --features 39

libtpu compiles ``ops.histogram._node_hist_pallas`` for a described v5e and
dumps its final VLIW schedule (``--xla_jf_dump_to``). The kernel is one
straight line of bundles a grid step, so ``bundles x grid steps / clock`` is
the pass: 39,818 bundles a 4096-row step gave 0.4428 s for the 255-bin
W = 16 pass that measured 0.444 s on the chip, 35,251 gave 0.3920 for the
root's 0.391 (PERF.md §5, PR 29). The slot table says which unit a pass is
bound by: on v5e the 4 VALU slots that build the one-hot, not the MXU's.
``--nodes 4|8`` are shapes the program runs since PR 34: the widths a
leafwise round's pass is staged at (``growth._pass_widths``; 2 is not: a
2-node pass measured 1-3 ms under the 4-node one).

A computed figure, not a measurement: report it as "static", never as a
device time. The compile runs in a child process, because the dump ends in
an abort once the files are written (a report template libtpu does not
ship); this process never loads jax or the TPU's library.
"""

from __future__ import annotations

import argparse
import glob
import os
import subprocess
import sys
import tempfile

CLOCK_HZ = 1.5e9      # v5e TensorCore: 197e12 / (2 x 4 MXUs x 128 x 128)

_CHILD = r"""
import os, sys
import jax, jax.numpy as jnp
from jax.experimental import topologies
from jax.sharding import SingleDeviceSharding
sys.path.insert(0, {root!r})
from mmlspark_tpu.ops import histogram as H
one = SingleDeviceSharding(topologies.get_topology_desc(
    platform="tpu", topology_name="v5e:2x2").devices[0])
F, n, B, W, quantized = {features}, {rows}, {bins}, {nodes}, {quantized}
rb = H._pick_row_block(n, F, 3 * W, B, fused_w=W, quantized=quantized)
print("ROW_BLOCK", rb, "FOLD_WORDS",
      H._fold_words(B, 3 * W, 1 if quantized else 2), flush=True)
args = (jax.ShapeDtypeStruct((F, n), jnp.dtype({bin_dtype!r}), sharding=one),
        jax.ShapeDtypeStruct((n,), jnp.int32, sharding=one),
        jax.ShapeDtypeStruct((3, n), jnp.int8 if quantized else jnp.float32,
                             sharding=one))
jax.jit(lambda b, p, s: H._node_hist_pallas(
    b, p, s, W, B, quantized=quantized)).lower(*args).compile()
"""


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--bins", type=int, default=255)
    ap.add_argument("--nodes", type=int, default=16, help="frontier width W")
    ap.add_argument("--stats", choices=("int8", "bf16"), default="int8")
    ap.add_argument("--features", type=int, default=39)
    ap.add_argument("--rows", type=int, default=68_321_280)
    ap.add_argument("--bin-dtype", default="uint8")
    args = ap.parse_args(argv)

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with tempfile.TemporaryDirectory() as dump:
        env = dict(os.environ, JAX_PLATFORMS="cpu", TPU_LOG_DIR="disabled",
                   JAX_ENABLE_COMPILATION_CACHE="false",
                   LIBTPU_INIT_ARGS=f"--xla_jf_dump_to={dump} "
                                    "--xla_jf_dump_llo_text=true")
        child = subprocess.run(
            [sys.executable, "-c", _CHILD.format(
                root=root, features=args.features, rows=args.rows,
                bins=args.bins, nodes=args.nodes,
                quantized=args.stats == "int8", bin_dtype=args.bin_dtype)],
            env=env, capture_output=True, text=True, check=False)
        facts = [ln for ln in child.stdout.splitlines()
                 if ln.startswith("ROW_BLOCK")]
        found = glob.glob(os.path.join(
            dump, "*gbdt_node_hist_kernel*final_hlo-static-per-bundle-"
                  "utilization.txt"))
        if not facts or not found:
            sys.stderr.write(child.stderr[-3000:])
            raise SystemExit("the compile left no schedule behind "
                             f"(child exit {child.returncode})")
        _, rb, _, fold = facts[0].split()
        lines = open(found[0]).read().splitlines()
    units = [u.strip() for u in lines[1].split(",")]
    slots = [int(x) for x in lines[2].split()]
    start = lines.index("== UTILIZATION:") + 1
    rows = [[int(x) for x in ln.split()] for ln in lines[start:] if ln.strip()]
    steps = -(-args.rows // int(rb))
    print(f"bins={args.bins} W={args.nodes} stats={args.stats} "
          f"F={args.features} rows={args.rows}: row_block={rb} "
          f"layout={'folded' if int(fold) else 'plain'}")
    print(f"bundles a grid step: {len(rows)}; static pass = bundles x "
          f"{steps} steps / {CLOCK_HZ / 1e9} GHz = "
          f"{len(rows) * steps / CLOCK_HZ:.4f} s (computed, not measured)")
    for k, unit in enumerate(units):
        ops = sum(r[k] for r in rows)
        if ops:
            print(f"  {unit:13s} {ops:8d} ops in {slots[k]} slots: "
                  f"{100.0 * ops / slots[k] / len(rows):5.1f}% of the "
                  "schedule")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
