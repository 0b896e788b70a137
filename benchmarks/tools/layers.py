"""One traced fit of a cell, read by layer: device seconds by the program's
``jax.named_scope`` names, seconds by kernel name, and the program's own span
tree of the warm-up fit and of the traced fit.

    python3 benchmarks/tools/layers.py --workload <cell> --seed <n> \\
        [--rows N] [--dump file.json]
    python3 benchmarks/tools/layers.py --xplane <file.xplane.pb[.gz]>

The driver's ``set_up`` (dataset and warm-up fit), then one fit under the
profiler, then one JSON line; ``--xplane`` reduces a trace that is already
there. On a TPU an ``XLA Ops`` event's scope is in the ``tf_op`` stat of its
*event metadata* (``jit(multi_local)/.../gbdt_route/gather:``), which
``jax.profiler.ProfileData`` does not hand out (its ``event.stats`` are the
event's own: ``device_offset_ps``, ``device_duration_ps``), so the trace is
read with the xplane schema itself. The harness hands its readers event names
only and deletes the trace before they run, so device time by scope is read
here until the harness keeps that stat (PERF.md, Open questions). ``--dump``
writes the loaded events, cut to the longest and a sample of the rest, for
``tests/data``. Not part of a benchmark run.
"""

import argparse
import json
import os
import re
import shutil
import sys
import tempfile

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
for path in (os.path.dirname(BENCH), BENCH):
    sys.path.insert(0, path)

OP_NAME_STAT = "tf_op"     # the HLO metadata's op_name, on the event metadata
_SCOPE = re.compile(r"gbdt_[a-z_]+")
_KERNEL = re.compile(r"gbdt_\w*kernel")
UNSCOPED = "unscoped"


def _xplane_pb2():
    """The xplane schema. tensorflow ships it as one generated file that
    needs protobuf alone, so that file is loaded by path and tensorflow
    itself is not imported."""
    import importlib.util
    found = importlib.util.find_spec("tensorflow")
    if found is None or not found.submodule_search_locations:
        raise SystemExit("no xplane schema: tensorflow is not installed")
    spec = importlib.util.spec_from_file_location("_xplane_pb2", os.path.join(
        list(found.submodule_search_locations)[0], "tsl", "profiler",
        "protobuf", "xplane_pb2.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_scoped(path: str) -> dict:
    """As ``lib.trace.load_events`` gives the device planes, with each
    event's name a pair ``[hlo text, op name]`` (``""`` where the event's
    metadata has no :data:`OP_NAME_STAT`). ``path`` is an ``.xplane.pb``,
    gzipped or not, or a profiler log directory."""
    import glob
    import gzip
    from lib import trace
    if os.path.isdir(path):
        paths = sorted(glob.glob(os.path.join(
            path, "plugins", "profile", "*", "*.xplane.pb")))
        if not paths:
            raise FileNotFoundError(f"no .xplane.pb under {path}")
        path = paths[-1]
    with (gzip.open if path.endswith(".gz") else open)(path, "rb") as f:
        space = _xplane_pb2().XSpace.FromString(f.read())
    planes = []
    for plane in space.planes:
        if not plane.name.startswith(trace._DEVICE_PLANE):
            continue
        stat_names = {k: v.name for k, v in plane.stat_metadata.items()}

        def op_name(metadata):
            for stat in metadata.stats:
                if stat_names.get(stat.metadata_id) == OP_NAME_STAT:
                    return (stat.str_value
                            or stat_names.get(stat.ref_value, ""))
            return ""

        names = {k: [m.name, op_name(m)]
                 for k, m in plane.event_metadata.items()}
        lines = []
        for line in plane.lines:
            if line.name != trace._OPS_LINE:
                continue
            t0 = line.timestamp_ns
            lines.append({"name": line.name, "events": [
                [names[e.metadata_id], t0 + e.offset_ps * 1e-3,
                 e.duration_ps * 1e-3] for e in line.events]})
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def reduce_scoped(doc: dict) -> dict:
    """Leaf device events (``lib.trace``'s rule) summed two ways:
    ``by_scope`` ``{scope: seconds}`` under the first ``gbdt_*`` component
    of the event's op name, :data:`UNSCOPED` where it has none, and
    ``by_kernel`` ``{kernel name: [seconds, launches]}`` for Mosaic calls,
    named by the ``gbdt_*kernel`` in their HLO text. ``busy_s`` is the sum
    of the leaves, one device's share."""
    from lib import trace
    by_scope, by_kernel, devices = {}, {}, 0
    for plane in doc["planes"]:
        leaves = [e for line in plane["lines"]
                  for e in trace._leaves(line["events"])]
        devices += bool(leaves)
        for (hlo, op), _, dur in leaves:
            found = _SCOPE.search(op)
            scope = found.group(0) if found else UNSCOPED
            by_scope[scope] = by_scope.get(scope, 0.0) + dur * 1e-9
            if "tpu_custom_call" in hlo:
                kernel = _KERNEL.search(hlo)
                slot = by_kernel.setdefault(
                    kernel.group(0) if kernel else "unnamed", [0.0, 0])
                slot[0] += dur * 1e-9
                slot[1] += 1
    devices = max(devices, 1)
    busy = sum(by_scope.values())
    return {
        "busy_s": busy / devices,
        "by_scope": {k: v / devices for k, v in sorted(
            by_scope.items(), key=lambda kv: -kv[1])},
        "unscoped_pct": (100.0 * by_scope.get(UNSCOPED, 0.0) / busy
                         if busy else None),
        "by_kernel": {k: [v[0] / devices, v[1]]
                      for k, v in by_kernel.items()},
    }


def cut(doc: dict, longest: int = 120, every: int = 40) -> dict:
    """A small copy for ``tests/data``: per line the ``longest`` events and
    every ``every``-th of the rest, HLO text cut to 160 characters except a
    Mosaic call's."""
    planes = []
    for plane in doc["planes"]:
        lines = []
        for line in plane["lines"]:
            order = sorted(range(len(line["events"])),
                           key=lambda i: -line["events"][i][2])
            keep = set(order[:longest]) | set(order[longest::every])
            lines.append({"name": line["name"], "events": [
                [[hlo if "tpu_custom_call" in hlo else hlo[:160], op], s, d]
                for i, ((hlo, op), s, d) in enumerate(line["events"])
                if i in keep]})
        planes.append({"name": plane["name"], "lines": lines})
    return {"planes": planes}


def span_trees(attempted: int = 1) -> dict:
    """The program's span tree of the warm-up fit and of the last fit."""
    from lib import spantree
    evs = spantree.events()
    warmup, window = spantree.warmup_and_window(evs, attempted)
    out = {}
    for label, pair in (("warmup_fit", warmup),
                        ("traced_fit", window[-1] if window else None)):
        if pair is not None:
            fit, inside = pair
            out[label] = spantree.tree([fit], inside)
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", default="")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rows", type=int, default=0,
                    help="table size of a CPU rehearsal (refused on a chip)")
    ap.add_argument("--xplane", default="",
                    help="reduce this trace and run nothing")
    ap.add_argument("--dump", default="")
    args = ap.parse_args()
    if bool(args.xplane) == bool(args.workload):
        ap.error("give --workload (with --seed) or --xplane")

    out, sys.stdout = sys.stdout, sys.stderr
    if args.xplane:
        doc, result = load_scoped(args.xplane), {"xplane": args.xplane}
    else:
        doc, result = traced_fit(args)
        result["spans"] = span_trees()
    result.update(reduce_scoped(doc))
    if args.dump:
        os.makedirs(os.path.dirname(os.path.abspath(args.dump)),
                    exist_ok=True)
        with open(args.dump, "w") as f:
            json.dump(cut(doc), f)
    print(json.dumps(result), file=out, flush=True)
    return 0


def traced_fit(args) -> tuple:
    """``(events, facts)`` of one fit under the profiler after the
    driver's own set-up."""
    import run as harness
    from lib import gbdt_train, trace
    spec = harness.load_cell(args.workload)
    device = harness.chip_gate(int(spec["cell"]["chips"]))
    on_chip = device["platform"] == "tpu"
    if args.rows and on_chip:
        raise SystemExit("--rows is for a CPU rehearsal")
    driver = gbdt_train.Driver({
        "config": spec["config"], "workload": spec["workload"],
        "cell": spec["cell"], "seed": args.seed,
        "platform": device["platform"],
        "rows": args.rows or (0 if on_chip else harness.TOY_ROWS)})
    facts = driver.set_up()
    log_dir = tempfile.mkdtemp(prefix="layers_trace_")
    try:
        trace.start(log_dir)
        driver._fit()
        trace.stop()
        doc = load_scoped(log_dir)
    finally:
        shutil.rmtree(log_dir, ignore_errors=True)
    return doc, {"workload": args.workload, "seed": args.seed,
                 "device": device,
                 "setup": {k: v for k, v in facts.items()
                           if isinstance(v, float)}}


if __name__ == "__main__":
    sys.exit(main())
