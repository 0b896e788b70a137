"""The benchmark's command.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything about one cell is data that this file finds by name: the cell's
entry in ``BENCHMARK.json``, ``workloads/<cell>.json`` (traffic parameters,
the window driver's ``kind``, the limits of the comparison),
the configuration's file, the driver ``lib/<kind>.py`` and, in a traced run,
one reader ``layer_metrics/<metric>.py`` per per-layer metric. The last line
of standard output is the result; everything else goes to standard error.
"""

from __future__ import annotations

import time

_T0 = time.perf_counter()              # set-up counts from the process's start

import argparse                        # noqa: E402
import importlib                       # noqa: E402
import json                            # noqa: E402
import os                              # noqa: E402
import shutil                          # noqa: E402
import sys                             # noqa: E402
import tempfile                        # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
TOY_ROWS = 32768                       # a CPU rehearsal's table


def log(*parts) -> None:
    print(*parts, file=sys.stderr, flush=True)


def load_cell(name: str) -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json; it has "
                         f"{sorted(cells)}")
    cell = cells[name]
    config_entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    with open(os.path.join(ROOT, config_entry["file"])) as f:
        config = json.load(f)
    with open(os.path.join(HERE, "workloads", name + ".json")) as f:
        workload = json.load(f)
    if workload["config"] != cell["config"]:
        raise SystemExit(f"workloads/{name}.json names config "
                         f"{workload['config']!r}, BENCHMARK.json "
                         f"{cell['config']!r}")
    return {"bench": bench, "cell": cell, "config": config,
            "workload": workload}


def chip_gate(chips: int) -> dict:
    """The device as JAX reports it. No accelerator, or fewer chips than the
    cell asks for, ends the run with no result; a CPU counts only where
    ``JAX_PLATFORMS=cpu`` asks for a rehearsal by name."""
    import jax
    devices = jax.devices()
    d0 = devices[0]
    asked_cpu = os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu"
    if d0.platform != "tpu" and not (d0.platform == "cpu" and asked_cpu):
        raise SystemExit(f"no accelerator: jax found {d0.platform!r} "
                         f"({d0.device_kind}); a CPU rehearsal needs "
                         "JAX_PLATFORMS=cpu")
    if d0.platform == "tpu" and len(devices) < chips:
        raise SystemExit(f"the cell needs {chips} chips, jax found "
                         f"{len(devices)}")
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(devices)}


def memory_peak_bytes():
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.local_devices()]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def applies(metric: dict, cell_name: str) -> bool:
    return cell_name in metric.get("workloads", [cell_name])


def judge(readings: dict, limits: dict) -> tuple:
    """``({name: {"value", "limit"}}, correct)``: the workload's file names
    every number that is compared, with its limit; a named number that the
    driver did not read fails."""
    table, ok = {}, bool(limits)
    for name, limit in limits.items():
        value = readings.get(name)
        ok &= value is not None and value <= limit
        table[name] = {"value": value, "limit": limit}
    return table, ok


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rows", type=int, default=0,
                    help="table size of a CPU rehearsal (refused on a chip)")
    ap.add_argument("--control", type=int, choices=(0, 1), default=0,
                    help="1: judge the lower-precision control in the "
                         "program's place; correct has to come out false")
    ap.add_argument("--dump-trace", default="",
                    help="write the traced run's event lists to this file")
    args = ap.parse_args(argv)

    if not os.path.isdir(os.path.join(ROOT, "mmlspark_tpu")):
        raise SystemExit("the program is not in this checkout: "
                         f"{ROOT}/mmlspark_tpu is missing")
    for path in (ROOT, HERE):
        if path not in sys.path:
            sys.path.insert(0, path)
    out, sys.stdout = sys.stdout, sys.stderr     # the last line is ours alone
    try:
        return _run(args, out)
    finally:
        sys.stdout = out


def _run(args, out) -> int:
    spec = load_cell(args.workload)
    cell, workload = spec["cell"], spec["workload"]
    device = chip_gate(int(cell["chips"]))
    on_chip = device["platform"] == "tpu"
    if args.rows and on_chip:
        raise SystemExit("--rows is for a CPU rehearsal; a chip runs the "
                         "configuration's own size")
    ctx = {"config": spec["config"], "workload": workload, "cell": cell,
           "seed": args.seed, "platform": device["platform"],
           "rows": args.rows or (0 if on_chip else TOY_ROWS)}

    from lib import trace as tracelib
    driver = importlib.import_module("lib." + workload["kind"]).Driver(ctx)
    setup_facts = driver.set_up()
    setup_s = time.perf_counter() - _T0
    log("set-up", json.dumps(setup_facts), f"setup_s={setup_s:.3f}")

    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if args.trace else ""
    try:
        if args.trace:
            tracelib.start(trace_dir)
        t_w = time.perf_counter()
        facts = driver.window(args.seconds)
        traced_s = time.perf_counter() - t_w
        reduction = None
        if args.trace:
            tracelib.stop()
            doc = tracelib.load_events(trace_dir)
            reduction = tracelib.reduce_events(doc)
            if args.dump_trace:
                os.makedirs(os.path.dirname(os.path.abspath(
                    args.dump_trace)), exist_ok=True)
                with open(args.dump_trace, "w") as f:
                    json.dump(doc, f)
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
    device["memory_peak_bytes"] = memory_peak_bytes()
    log("window", json.dumps({k: v for k, v in facts.items()
                              if k != "end_to_end"}))

    driver.release()
    t_c = time.perf_counter()
    readings = driver.compare(control=bool(args.control))
    table, agree = judge(readings, workload.get("limits", {}))
    correct = bool(agree and facts["failed"] == 0)
    log(f"comparison took {time.perf_counter() - t_c:.1f} s; read",
        json.dumps(readings))

    bench, metrics = spec["bench"], {}
    if not args.trace:
        values = dict(facts["end_to_end"], setup_s=setup_s)
        for m in bench["end_to_end"]:
            if applies(m, cell["name"]):
                metrics[m["name"]] = {"value": values[m["name"]],
                                      "unit": m["unit"]}
    else:
        if on_chip:
            device["busy_s"] = reduction["busy_s"]
            device["window_s"] = traced_s
        rctx = {"facts": facts, "trace": reduction, "window_s": traced_s,
                "device": device, "chips": int(cell["chips"]),
                "config": spec["config"], "workload": workload}
        for m in bench["per_layer"]:
            if not applies(m, cell["name"]):
                continue
            reader = importlib.import_module("layer_metrics." + m["name"])
            value = reader.read(rctx) if on_chip or not getattr(
                reader, "NEEDS_CHIP", True) else None
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    result = {"correct": correct, "attempted": facts["attempted"],
              "failed": facts["failed"], "metrics": metrics, "device": device}
    if args.trace:
        result["breakdown"] = {
            "device_ops": tracelib.top_ops(reduction["ops"]),
            "idle_gaps": reduction["idle_gaps"]}
    result["compared"] = table
    for name, row in table.items():
        verdict = ("ok" if row["value"] is not None
                   and row["value"] <= row["limit"] else "FAIL")
        log(f"compared {name} = {row['value']!r} (limit {row['limit']!r}) "
            f"{verdict}")
    print(json.dumps(result), file=out, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
