"""Without a TPU the benchmark fails: one line, no result, no CPU number."""

import os
import shutil
import subprocess
import sys

import pytest

from benchmarks import manifest

CELLS = [w["name"] for w in manifest.load_manifest()["workloads"]]


def run_cell(cwd, workload, extra_env=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.update(extra_env or {})
    return subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", workload,
         "--seed", "2147483659", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300,
    )


@pytest.mark.parametrize("workload", CELLS)
def test_on_cpu_every_cell_exits_non_zero_with_one_line(workload):
    done = run_cell(manifest.ROOT, workload)
    assert done.returncode != 0
    assert '"metrics"' not in done.stdout
    lines = [x for x in done.stderr.splitlines() if x.startswith("benchmark:")]
    assert len(lines) == 1 and "not 'tpu'" in lines[0], done.stderr[-2000:]


def test_unknown_workload_is_one_line():
    done = run_cell(manifest.ROOT, "no-such.cell")
    assert done.returncode != 0 and "unknown workload" in done.stderr
    assert '"metrics"' not in done.stdout


def test_without_the_program_it_fails(tmp_path):
    """A directory that holds only BENCHMARK.json and the benchmark."""
    shutil.copytree(
        manifest.BENCH_DIR, tmp_path / "benchmarks",
        ignore=shutil.ignore_patterns("__pycache__"),
    )
    shutil.copy(os.path.join(manifest.ROOT, "BENCHMARK.json"), tmp_path)
    for workload in CELLS[:1]:
        done = run_cell(str(tmp_path), workload, {"PYTHONPATH": ""})
        assert done.returncode != 0
        assert '"metrics"' not in done.stdout
        assert "benchmark:" in done.stderr
