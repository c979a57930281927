"""BENCHMARK.json against the contract's rules that a test can hold, and
the add-only promise: a configuration, a traffic mix and a per-layer
metric each arrive as new files plus manifest entries."""

import json
import os
import re
import shutil

import pytest

from benchmarks import manifest, run as run_module

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module", params=["as it is", "with waiting cells"])
def bench(request, bench_with_waiting_cells):
    """The manifest, and the manifest a PR that admits the waiting BERT
    cells would write: both keep every rule."""
    if request.param == "as it is":
        return manifest.load_manifest()
    return bench_with_waiting_cells


def test_keys_names_and_units(bench):
    assert set(bench) == {
        "command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer",
    }
    assert 1 <= bench["run_seconds"] <= 51
    for metric in bench["end_to_end"] + bench["per_layer"]:
        assert NAME.match(metric["name"]), metric["name"]
        assert UNIT.match(metric["unit"]), metric
        assert metric["better"] in ("lower", "higher")
        assert metric["source"] in SOURCES
    for metric in bench["end_to_end"]:
        assert set(metric) <= {"name", "unit", "better", "bound", "source",
                               "workloads"}
        assert metric["source"] in ("host_clock", "device_trace")
        assert 0.01 <= metric["bound"] <= 0.1
    for metric in bench["per_layer"]:
        assert set(metric) <= {"name", "unit", "better", "source", "layer",
                               "moves", "workloads"}
        assert 1 <= len(metric["layer"]) <= 200
    names = [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    assert len(names) == len(set(names))
    assert "setup_s" in names
    for cell in bench["workloads"]:
        assert set(cell) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(cell["name"]) and NAME.match(cell["traffic"])
        assert cell["chips"] in (1, 4) and len(cell["why"]) <= 200
        assert cell["name"] == cell["config"] + "." + cell["traffic"]
    four = [c for c in bench["workloads"] if c["chips"] == 4]
    assert len(four) <= max(1, len(bench["workloads"]) // 4)
    for config in bench["configs"]:
        assert set(config) == {"name", "source", "file", "reduced", "why"}
        assert config["file"].startswith(bench["paths"][0] + "/")
        assert any(c["config"] == config["name"]
                   for c in bench["workloads"])
    assert len(json.dumps(bench)) < 64 * 1024


def test_every_cell_reports_what_the_contract_asks(bench):
    for entry in bench["workloads"]:
        cell = manifest.resolve_cell(bench, entry["name"])
        e2e = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in e2e and len(e2e) >= 2
        assert cell.per_layer
        for metric in cell.per_layer:
            # `moves` names an end-to-end metric this very cell reports
            assert metric["moves"] in e2e, (entry["name"], metric["name"])
            spec = manifest.load_layer_metric(cell, metric["name"])
            assert spec["layer"] == metric["layer"]
            assert spec["moves"] == metric["moves"]
            assert spec["unit"] == metric["unit"]
            reader = manifest.import_by_name("readers", spec["reader"])
            assert callable(reader.read)
        manifest.import_by_name("drivers", cell.traffic["driver"])
        manifest.import_by_name("reference", cell.config["reference"])
        declared = next(
            c for c in bench["configs"] if c["name"] == entry["config"]
        )
        assert cell.config["reduced"] == declared["reduced"]
        assert cell.config["source"] == declared["source"]
        assert not any(
            re.search(r"(_dim|_rank|hidden|intermediate|mlp)", key)
            for key in declared["reduced"]
        ), "no width is ever cut"


def test_files_under_paths_use_the_allowed_characters(bench):
    for base, _, files in os.walk(os.path.join(manifest.ROOT, "benchmarks")):
        if "__pycache__" in base:
            continue
        for name in files:
            assert re.match(r"^[A-Za-z0-9_.\-]+$", name), name


def test_run_py_names_no_cell_configuration_or_metric(bench):
    with open(os.path.join(manifest.BENCH_DIR, "run.py")) as f:
        text = f.read()
    with open(os.path.join(manifest.BENCH_DIR, "manifest.py")) as f:
        text += f.read()
    names = [w["name"] for w in bench["workloads"]]
    names += [c["name"] for c in bench["configs"]]
    names += [w["traffic"] for w in bench["workloads"]]
    names += [m["name"] for m in bench["per_layer"]]
    names += [m["name"] for m in bench["end_to_end"]]
    for name in names:
        assert name not in text, name


def test_add_only(tmp_path):
    """A later PR's configuration, traffic mix and per-layer metric: new
    files and new manifest entries, no edit to a file that is there."""
    root = tmp_path / "repo"
    shutil.copytree(
        manifest.BENCH_DIR, root / "benchmarks",
        ignore=shutil.ignore_patterns("__pycache__"),
    )
    shutil.copy(os.path.join(manifest.ROOT, "BENCHMARK.json"), root)
    before = {
        p: p.read_bytes() for p in (root / "benchmarks").rglob("*")
        if p.is_file()
    }
    bench = manifest.load_manifest(str(root))
    config = manifest.load_json(
        root / "benchmarks/configs/deepfm-criteo-kaggle.json"
    )
    config.update(name="deepfm-wide", embed_dim=32)
    (root / "benchmarks/configs/deepfm-wide.json").write_text(
        json.dumps(config)
    )
    traffic = manifest.load_json(
        root / "benchmarks/traffic/train-stream.json"
    )
    traffic["data"]["zipf_exponent"] = 1.05
    (root / "benchmarks/traffic/train-stream-flat.json").write_text(
        json.dumps(traffic)
    )
    (root / "benchmarks/layer_metrics/h2d_us_per_example.json").write_text(
        json.dumps({
            "name": "h2d_us_per_example", "layer": "input pipeline",
            "unit": "us", "moves": "train_examples_per_s",
            "reader": "phase_timer",
            "params": {"phase": "h2d_stage", "per": "us_per_example"},
        })
    )
    cell_name = "deepfm-wide.train-stream-flat"
    bench["configs"].append({
        "name": "deepfm-wide", "source": "https://example.org/wide",
        "file": "benchmarks/configs/deepfm-wide.json", "reduced": [],
        "why": "a wider table row",
    })
    bench["workloads"].append({
        "name": cell_name, "config": "deepfm-wide",
        "traffic": "train-stream-flat", "chips": 1, "why": "flat ids",
    })
    for metric in bench["end_to_end"]:
        if metric["name"] == "train_examples_per_s":
            metric["workloads"].append(cell_name)
    bench["per_layer"].append({
        "name": "h2d_us_per_example", "unit": "us", "better": "lower",
        "source": "program_span", "layer": "input pipeline",
        "moves": "train_examples_per_s", "workloads": [cell_name],
    })
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    cell = manifest.resolve_cell(
        manifest.load_manifest(str(root)), cell_name, str(root)
    )
    assert cell.config["embed_dim"] == 32
    assert cell.traffic["data"]["zipf_exponent"] == 1.05
    assert [m["name"] for m in cell.per_layer] == ["h2d_us_per_example"]
    result = {
        "end_to_end": {"train_examples_per_s": 1.0, "setup_s": 2.0},
        "context": {
            "phases": {"h2d_stage": 0.5}, "examples": 1000,
            "window_s": 10.0,
        },
    }
    traced = run_module.collect_metrics(cell, result, trace=True)
    assert traced == {"h2d_us_per_example": {"value": 500.0, "unit": "us"}}
    plain = run_module.collect_metrics(cell, result, trace=False)
    assert set(plain) == {"train_examples_per_s", "setup_s"}
    for path, content in before.items():
        assert path.read_bytes() == content, f"{path} was edited"


def test_a_reader_that_finds_nothing_leaves_the_metric_out():
    bench = manifest.load_manifest()
    cell = manifest.resolve_cell(bench, "deepfm-criteo-kaggle.train-stream")
    result = {"end_to_end": {}, "context": {"cell": cell}}
    assert run_module.collect_metrics(cell, result, trace=True) == {}
