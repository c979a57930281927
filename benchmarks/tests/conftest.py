"""Tests beside the benchmark.  Nothing here touches libtpu at import:
jax is held to the CPU before any test imports it."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)
)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import copy  # noqa: E402
import json  # noqa: E402

import pytest  # noqa: E402


def with_waiting_cells(bench: dict) -> dict:
    """BENCHMARK.json plus the cells of tests/data/waiting_cells.json,
    added the way a later PR adds a cell: new entries, and the cell's
    name in the `workloads` of every metric it shares."""
    here = os.path.dirname(os.path.abspath(__file__))
    with open(os.path.join(here, "data", "waiting_cells.json")) as f:
        waiting = json.load(f)
    bench = copy.deepcopy(bench)
    bench["configs"] += waiting["configs"]
    bench["workloads"] += waiting["workloads"]
    bench["per_layer"] += waiting["per_layer"]
    names = [w["name"] for w in waiting["workloads"]]
    for metric in bench["end_to_end"] + bench["per_layer"]:
        if metric["name"] in waiting["shared_metrics"]:
            metric["workloads"] = metric["workloads"] + names
    return bench


@pytest.fixture(scope="session")
def bench_with_waiting_cells():
    from benchmarks import manifest

    return with_waiting_cells(manifest.load_manifest())
