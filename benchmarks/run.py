"""The benchmark's one command: one process, one cell, the cell's chips.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Prints earlier lines of JSON (anything worth keeping) and, as the LAST line of
standard output, one JSON object with exactly ``correct``, ``attempted``,
``failed``, ``metrics``, ``device`` (and ``breakdown`` when traced). With
``--trace 0`` the metrics are the cell's end-to-end metrics, with ``--trace 1``
its per-layer metrics. See benchmarks/README.md.

It measures a TPU or nothing: with no TPU it exits non-zero and prints no
result, unless ``--rehearse <size>`` (never used by the driver) selects a toy
size — a rehearsal reports ``platform`` as it is and no device metric.
"""

from __future__ import annotations

import time

T0 = time.perf_counter()  # set-up runs from here to the first window epoch

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

EXIT_NO_CHIP = 3
EXIT_BAD_CELL = 2


class Ctx:
    """What a per-layer reader is handed."""

    def __init__(self, trace, window, facts):
        self.trace, self.window, self.facts = trace, window, facts


def fail(code: int, msg: str):
    print(f"benchmarks/run.py: {msg}", file=sys.stderr, flush=True)
    raise SystemExit(code)


def device_report(cell, rehearse) -> dict:
    """The device as jax reports it; refuses anything but the chips the cell
    asks for. ``peak`` is the ``peaks.json`` entry of the device_kind."""
    import jax

    from benchmarks.lib import cells

    devs = jax.devices()
    platform, kind = devs[0].platform, devs[0].device_kind
    if platform != "tpu" and not rehearse:
        fail(EXIT_NO_CHIP, f"jax found platform {platform!r}, not a TPU: this "
             "benchmark measures the chip or nothing (--rehearse tiny runs a "
             "toy size off the chip)")
    if len(devs) < cell.chips:
        fail(EXIT_NO_CHIP, f"cell {cell.name} needs {cell.chips} chip(s), jax "
             f"reports {len(devs)}")
    peak = cells.peaks().get(kind)
    if peak is None and platform == "tpu":
        fail(EXIT_NO_CHIP, f"device_kind {kind!r} is not in benchmarks/"
             "peaks.json: add its published peaks, there is no default")
    return {"platform": platform, "kind": kind, "count": len(devs),
            "peak": peak}


def layer_metrics(cell, result, on_chip: bool):
    """``(metrics, breakdown, busy_s, window_s, left_out)`` of a traced run:
    every per-layer metric of the cell through its reader, found by name."""
    from benchmarks.lib import cells
    from benchmarks.trace import extract, reduce

    trace = extract.load(result["trace_dir"]) if result.get("trace_dir") else None
    window = trace.window() if trace is not None else None
    if trace is not None and window is None:
        trace = None
    ctx = Ctx(trace, window, result["facts"])
    metrics, left_out = {}, {}
    for entry in cell.per_layer:
        spec = cells.layer_metric(entry["name"])
        reader = importlib.import_module(
            "benchmarks.trace.readers." + spec["reader"])
        value = reader.read(ctx, **spec.get("args", {}))
        if value is None:
            left_out[entry["name"]] = "nothing to read"
        elif not on_chip and entry["source"] != "program_counter":
            left_out[entry["name"]] = f"cpu rehearsal, not a device number: {value}"
        else:
            metrics[entry["name"]] = {"value": value, "unit": entry["unit"]}
    breakdown, busy_s, window_s = None, None, None
    if trace is not None and trace.devices:
        busy = reduce.busy_seconds(trace, window)
        busy_s = sum(busy.values()) / len(busy)
        window_s = window[1] - window[0]
        breakdown = {"device_ops": reduce.top_ops(trace, window),
                     "idle_gaps": reduce.idle_gaps(trace, window)}
    return metrics, breakdown, busy_s, window_s, left_out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", default=None, metavar="SIZE",
                    help="toy size of the cell's files (e.g. tiny); off the "
                         "chip; prints no device metric; not for the driver")
    args = ap.parse_args(argv)

    from benchmarks.lib import cells

    try:
        cell = cells.load_cell(args.workload)
    except (FileNotFoundError, KeyError, StopIteration) as exc:
        fail(EXIT_BAD_CELL, str(exc))
    device = device_report(cell, args.rehearse)
    on_chip = device["platform"] == "tpu"
    out_dir = os.path.join(ROOT, "bench_out", cell.name)
    os.makedirs(out_dir, exist_ok=True)
    driver = importlib.import_module(
        "benchmarks.drivers." + cell.traffic.get("kind", "train"))
    result = driver.run(cell, args, T0, device, out_dir)

    line = {"correct": result["correct"], "attempted": result["attempted"],
            "failed": result["failed"]}
    dev = {"platform": device["platform"], "kind": device["kind"],
           "count": device["count"],
           "memory_peak_bytes": result["memory_peak_bytes"]}
    if args.trace:
        metrics, breakdown, busy_s, window_s, left_out = layer_metrics(
            cell, result, on_chip)
        if left_out:
            print(json.dumps({"per_layer_left_out": left_out}), flush=True)
        line["metrics"] = metrics
        line["device"] = dev
        if on_chip and busy_s is not None:
            dev.update(busy_s=busy_s, window_s=window_s)
            line["breakdown"] = breakdown
        elif busy_s is not None:
            print(json.dumps({"cpu_rehearsal_not_device_numbers": {
                "busy_s": busy_s, "window_s": window_s,
                "breakdown": breakdown}}), flush=True)
    else:
        values = result["values"]
        if not on_chip:
            print(json.dumps({"cpu_rehearsal_not_device_numbers": values}),
                  flush=True)
            values = {}
        line["metrics"] = {
            m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
            for m in cell.end_to_end if m["name"] in values}
        line["device"] = dev
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
