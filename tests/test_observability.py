"""Observability integration (SURVEY.md §5 — round-2 verdict gap #3):
`--tensorboard_log_dir` must yield real event files from BOTH sides —
worker scalars (train/loss, train/steps_per_sec, eval/*) and the master's
aggregated eval curve — and the worker must have written a step rate.
"""

import glob
import os

import pytest

from elasticdl_tpu.client.main import main as cli_main


@pytest.fixture(scope="module")
def mnist_data(tmp_path_factory):
    from model_zoo.mnist.data import write_dataset

    root = tmp_path_factory.mktemp("mnist_obs")
    return write_dataset(str(root), n_train=256, n_val=64)


def _events(path):
    return glob.glob(
        os.path.join(path, "**", "events.out.tfevents.*"), recursive=True
    )


def test_local_job_writes_tensorboard_events(mnist_data, tmp_path):
    train_dir, val_dir = mnist_data
    tb_dir = str(tmp_path / "tb")
    rc = cli_main(
        [
            "train",
            "--model_zoo", "model_zoo",
            "--model_def", "mnist.mnist_functional_api.custom_model",
            "--training_data", train_dir,
            "--validation_data", val_dir,
            "--distribution_strategy", "Local",
            "--num_epochs", "1",
            "--minibatch_size", "32",
            "--records_per_task", "64",
            "--num_workers", "2",
            "--tensorboard_log_dir", tb_dir,
        ]
    )
    assert rc == 0
    worker_events = _events(os.path.join(tb_dir, "worker-0")) + _events(
        os.path.join(tb_dir, "worker-1")
    )
    assert worker_events, f"no worker event files under {tb_dir}"
    master_events = _events(os.path.join(tb_dir, "master"))
    assert master_events, f"no master event files under {tb_dir}"

    # the scalars are really in there (read back through TF's event reader)
    import tensorflow as tf

    tags = set()
    for path in worker_events + master_events:
        for record in tf.compat.v1.train.summary_iterator(path):
            for value in record.summary.value:
                tags.add(value.tag)
    assert "train/loss" in tags, tags
    assert "train/steps_per_sec" in tags, tags
    assert any(t.startswith("eval/") for t in tags), tags


def test_no_tensorboard_dir_is_noop(mnist_data):
    """Without the flag the writers must be inert no-ops."""
    from elasticdl_tpu.common.summary import SummaryWriter

    writer = SummaryWriter(None)
    writer.scalars({"x": 1.0}, step=0)  # must not raise
    writer.flush()
    writer.close()


def test_profile_dir_captures_device_trace(mnist_data, tmp_path):
    """--profile_dir writes a JAX profiler trace (XPlane/Perfetto files
    TensorBoard can open) of the first training task."""
    train_dir, _ = mnist_data
    profile_dir = str(tmp_path / "trace")
    rc = cli_main(
        [
            "train",
            "--model_zoo", "model_zoo",
            "--model_def", "mnist.mnist_functional_api.custom_model",
            "--training_data", train_dir,
            "--distribution_strategy", "Local",
            "--num_epochs", "1",
            "--minibatch_size", "32",
            "--records_per_task", "64",
            "--profile_dir", profile_dir,
        ]
    )
    assert rc == 0
    traces = glob.glob(
        os.path.join(profile_dir, "**", "*.xplane.pb"), recursive=True
    ) + glob.glob(
        os.path.join(profile_dir, "**", "*.trace.json*"), recursive=True
    )
    assert traces, f"no profiler trace under {profile_dir}"
