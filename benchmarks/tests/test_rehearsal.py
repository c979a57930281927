"""Every cell end to end on the CPU at a tiny size, before chip time is
spent: the train cells through the CLI, the task window and the check of
the job's own train step, the dp4 cell on four virtual devices.  The size
override and the stand-ins for the TPU's preflight and memory counters
live HERE (a temporary copy of the benchmark's data files and a wrapper
script); the benchmark itself has neither.  Nothing these runs time is a
measurement."""

import json
import os
import re
import shutil
import subprocess
import sys

import pytest

from benchmarks import manifest

WRAPPER = '''
import json, sys, time
T0 = time.perf_counter()
sys.path.insert(0, {repo!r})
import jax
from benchmarks import manifest
from benchmarks.drivers import train

train.preflight = lambda cell: {{
    "platform": "cpu-rehearsal", "kind": "TPU v5 lite",
    "count": len(jax.devices()),
}}
train.live_bytes = lambda: [0] * len(jax.devices())   # no counters on CPU
train.memory_peak_bytes = lambda live: {{
    "peak": 1, "peak_live": 1, "live": 0, "scratch": 1,
}}
workload, root = sys.argv[1:3]
cell = manifest.resolve_cell(manifest.load_manifest(root), workload, root)
driver = manifest.import_by_name("drivers", cell.traffic["driver"])
result = driver.run(cell, 2 ** 31 + 11, 1.5, False, T0)
del result["context"]
print(json.dumps(result))
'''

TINY_CONFIG = {
    "deepfm-criteo-kaggle": {
        "vocab_capacity": 4096, "mlp_dims": [24, 16, 8], "use_bf16": False,
        "model_params":
            "vocab_capacity={vocab_capacity};embed_dim={embed_dim};"
            "mlp_dims={mlp_dims};bf16=False",
    },
    "bert-base-uncased": {
        "hidden_size": 32, "num_hidden_layers": 2, "num_attention_heads": 2,
        "intermediate_size": 64, "max_position_embeddings": 16,
        "vocab_size": 100, "use_bf16": False,
        "model_params":
            "hidden={hidden_size};num_layers={num_hidden_layers};"
            "heads={num_attention_heads};mlp_dim={intermediate_size};"
            "max_len={max_position_embeddings};vocab_size={vocab_size};"
            "bf16=False",
    },
}
TINY_TOKENS = {"format": "tokens", "seq_len": 16, "vocab_size": 100}
TINY_TRAFFIC = {
    "train-stream": {"minibatch_size": 64, "records_per_task": 512},
    "train-l512": {"minibatch_size": 8, "records_per_task": 64,
                   "seq_len": 16, "data": TINY_TOKENS},
    "train-l512-dp4": {"minibatch_size": 16, "records_per_task": 128,
                       "seq_len": 16, "data": TINY_TOKENS},
}


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    root = tmp_path_factory.mktemp("tiny")
    shutil.copytree(
        manifest.BENCH_DIR, root / "benchmarks",
        ignore=shutil.ignore_patterns("__pycache__", "tests"),
    )
    shutil.copy(os.path.join(manifest.ROOT, "BENCHMARK.json"), root)
    for kind, table in (("configs", TINY_CONFIG), ("traffic", TINY_TRAFFIC)):
        for name, override in table.items():
            path = root / "benchmarks" / kind / (name + ".json")
            data = json.loads(path.read_text())
            data.update(override)
            path.write_text(json.dumps(data))
    # the BERT cells wait outside the manifest (PERF.md section 6): added
    # here as the PR that admits them will add them
    from conftest import with_waiting_cells

    bench = with_waiting_cells(
        json.loads((root / "BENCHMARK.json").read_text())
    )
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    (root / "wrapper.py").write_text(WRAPPER.format(repo=manifest.ROOT))
    return root


def rehearse(root, workload, devices):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    done = subprocess.run(
        [sys.executable, str(root / "wrapper.py"), workload, str(root)],
        env=env, capture_output=True, text=True, timeout=600,
        cwd=manifest.ROOT,
    )
    assert done.returncode == 0, done.stderr[-3000:] + done.stdout[-2000:]
    return json.loads(done.stdout.strip().splitlines()[-1]), done.stdout


@pytest.mark.parametrize("workload, devices", [
    ("deepfm-criteo-kaggle.train-stream", 1),
    ("bert-base-uncased.train-l512", 1),
    ("bert-base-uncased.train-l512-dp4", 4),
])
def test_train_cells(tiny_root, workload, devices):
    result, out = rehearse(tiny_root, workload, devices)
    assert result["device"]["count"] == devices
    assert result["correct"] is True, out[-2000:]
    assert result["attempted"] >= 3 and result["failed"] == 0
    assert result["end_to_end"]["train_examples_per_s"] > 0
    assert result["end_to_end"]["setup_s"] > 0
    assert "whole tasks" in out
    # f32 on both sides here: the step's gradient, read back from Adam's
    # moments, agrees with the reference far inside the chip's bounds
    check = re.search(
        r"relative L2 worst ([0-9.e+-]+) .* optimizer arithmetic worst "
        r"([0-9.e+-]+)", out,
    )
    assert check and float(check.group(1)) < 1e-3, out[-2000:]
    assert float(check.group(2)) <= 1.0
    # every number compared stands beside its limit, the cosine too
    angle = re.search(r"1 - cosine ([0-9.e+-]+) \(at most ([0-9.e+-]+)", out)
    assert angle and float(angle.group(1)) <= float(angle.group(2))
