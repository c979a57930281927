"""BENCHMARK.json and the data files it names, resolved by name.

Nothing here knows a cell, a configuration or a metric: a later PR adds
`configs/<config>.json`, `traffic/<traffic>.json`,
`layer_metrics/<metric>.json` (and, for new code, `drivers/<driver>.py`,
`readers/<reader>.py`, `reference/<model>.py`) plus manifest entries, and
edits no file that is there.  Stdlib only.
"""

from __future__ import annotations

import importlib
import json
import os
from dataclasses import dataclass

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


class BenchmarkError(Exception):
    """A failure `run.py` reports as one line and a non-zero exit."""


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_manifest(root: str = ROOT) -> dict:
    return load_json(os.path.join(root, "BENCHMARK.json"))


@dataclass
class Cell:
    """One entry of `workloads` with everything its names resolve to."""

    name: str
    chips: int
    config_name: str
    config: dict       # benchmarks/configs/<config>.json
    traffic_name: str
    traffic: dict      # benchmarks/traffic/<traffic>.json
    end_to_end: list   # manifest entries this cell reports
    per_layer: list
    bench_dir: str = BENCH_DIR


def _reported_by(metric: dict, cell_name: str) -> bool:
    return "workloads" not in metric or cell_name in metric["workloads"]


def resolve_cell(manifest: dict, workload: str, root: str = ROOT) -> Cell:
    entries = {w["name"]: w for w in manifest["workloads"]}
    if workload not in entries:
        raise BenchmarkError(
            f"unknown workload {workload!r}; BENCHMARK.json has "
            f"{sorted(entries)}"
        )
    entry = entries[workload]
    configs = {c["name"]: c for c in manifest["configs"]}
    config_file = os.path.join(root, configs[entry["config"]]["file"])
    bench_dir = os.path.join(root, manifest["paths"][0])
    traffic_file = os.path.join(
        bench_dir, "traffic", entry["traffic"] + ".json"
    )
    return Cell(
        name=workload,
        chips=int(entry["chips"]),
        config_name=entry["config"],
        config=load_json(config_file),
        traffic_name=entry["traffic"],
        traffic=load_json(traffic_file),
        end_to_end=[
            m for m in manifest["end_to_end"] if _reported_by(m, workload)
        ],
        per_layer=[
            m for m in manifest["per_layer"] if _reported_by(m, workload)
        ],
        bench_dir=bench_dir,
    )


def load_layer_metric(cell: Cell, name: str) -> dict:
    return load_json(
        os.path.join(cell.bench_dir, "layer_metrics", name + ".json")
    )


def load_peaks(cell: Cell) -> dict:
    return load_json(os.path.join(cell.bench_dir, "peaks.json"))


def import_by_name(kind: str, name: str):
    """`benchmarks.<kind>.<name>` — drivers, readers and references are
    found by the name a data file gives, never listed in code."""
    if not name.replace("_", "").isalnum():
        raise BenchmarkError(f"bad {kind} name {name!r}")
    try:
        return importlib.import_module(f"benchmarks.{kind}.{name}")
    except ModuleNotFoundError as exc:
        if exc.name == f"benchmarks.{kind}.{name}":
            raise BenchmarkError(
                f"no benchmarks/{kind}/{name}.py"
            ) from exc
        raise
