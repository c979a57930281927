"""One run of one benchmark cell, in a fresh process.

    python benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Resolves the cell's configuration, traffic mix, driver and metric readers
by name from BENCHMARK.json and the data files under benchmarks/ (this
file holds no cell, configuration or metric name), runs the driver, and
prints the contract's one JSON object as the last line of stdout: the
cell's end-to-end metrics with `--trace 0`, its per-layer metrics (and
`breakdown`) with `--trace 1`.  Any failure is one line on stderr and a
non-zero exit with no result line; there is no CPU continuation.
"""

from __future__ import annotations

import time

PROCESS_T0 = time.perf_counter()   # set-up is counted from here

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def metric_line(value: float, unit: str) -> dict:
    return {"value": float(value), "unit": unit}


def collect_metrics(cell, result: dict, trace: bool) -> dict:
    from benchmarks import manifest

    metrics = {}
    if not trace:
        for entry in cell.end_to_end:
            value = result["end_to_end"].get(entry["name"])
            if value is not None:
                metrics[entry["name"]] = metric_line(value, entry["unit"])
        return metrics
    for entry in cell.per_layer:
        spec = manifest.load_layer_metric(cell, entry["name"])
        reader = manifest.import_by_name("readers", spec["reader"])
        value = reader.read(spec.get("params", {}), result["context"])
        if value is not None:   # a reader that finds nothing says nothing
            metrics[entry["name"]] = metric_line(value, entry["unit"])
    return metrics


def run(argv=None) -> dict:
    args = parse_args(argv)
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    from benchmarks import manifest

    cell = manifest.resolve_cell(manifest.load_manifest(), args.workload)
    driver = manifest.import_by_name("drivers", cell.traffic["driver"])
    result = driver.run(
        cell, args.seed, args.seconds, bool(args.trace), PROCESS_T0
    )
    line = {
        "correct": bool(result["correct"]),
        "attempted": int(result["attempted"]),
        "failed": int(result["failed"]),
        "metrics": collect_metrics(cell, result, bool(args.trace)),
        "device": result["device"],
    }
    trace = result["context"].get("trace")
    if args.trace and trace is not None:
        line["breakdown"] = trace["breakdown"]
    return line


def main(argv=None) -> int:
    try:
        line = run(argv)
    except ImportError as exc:
        print(f"benchmark: cannot import the program: {exc}",
              file=sys.stderr)
        return 1
    except Exception as exc:
        name = type(exc).__name__
        if name != "BenchmarkError":
            import traceback

            traceback.print_exc()
        print(f"benchmark: {name}: {exc}", file=sys.stderr)
        return 1
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
