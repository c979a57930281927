"""A sown value's metric is declared beside its `sow`, and the train loops
publish through that one table (elasticdl_tpu/layers/step_metrics.py).

The toy layer below declares a name no file of the tree knows.  Its gauge
must be set after a train task of the threaded loop and of the SPMD loop,
with neither loop edited; the value it sows under no declared name must
reach the summary.  There is one train program and one dispatch path.
"""

import flax.linen as nn
import numpy as np
import pytest

from elasticdl_tpu.common import metrics as metrics_lib
from elasticdl_tpu.common import programs
from elasticdl_tpu.common.model_handler import get_model_spec
from elasticdl_tpu.layers import step_metrics

REGISTRY = metrics_lib.default_registry()

TOY_KEPT = step_metrics.declare(
    "toy_kept_ratio",
    REGISTRY.gauge(
        "worker_toy_kept_ratio", "what the toy layer sows",
        labelnames=("layer",),
    ),
)


class Probe(nn.Module):
    @nn.compact
    def __call__(self, x):
        step_metrics.sow_step_metric(self, "toy_kept_ratio", 0.25)
        step_metrics.sow_step_metric(self, "toy_plain", 3.0)
        return nn.Dense(16)(x)


class Toy(nn.Module):
    @nn.compact
    def __call__(self, x):
        return nn.Dense(10)(nn.relu(Probe(name="probe")(x)))


class Summary:
    """Stands where a loop's SummaryWriter does."""

    def __init__(self):
        self.written = []

    def scalars(self, scalars, step):
        self.written.append((dict(scalars), step))

    def close(self):
        pass


@pytest.fixture(scope="module")
def mnist_data(tmp_path_factory):
    from model_zoo.mnist.data import write_dataset

    root = tmp_path_factory.mktemp("mnist_step_metrics")
    return write_dataset(str(root), n_train=128, n_val=32)


def toy_spec():
    spec = get_model_spec(
        "model_zoo", "mnist.mnist_functional_api.custom_model"
    )
    spec.model = Toy()
    return spec


def local_worker(train_dir):
    from elasticdl_tpu.data.reader import TFRecordDataReader
    from elasticdl_tpu.master.servicer import MasterServicer
    from elasticdl_tpu.master.task_manager import (
        TaskManager,
        create_shards_from_ranges,
    )
    from elasticdl_tpu.proto.service import InProcessMasterClient
    from elasticdl_tpu.worker.worker import Worker

    reader = TFRecordDataReader(train_dir)
    tm = TaskManager(
        training_shards=create_shards_from_ranges(
            reader.create_shards(), records_per_task=64
        ),
        num_epochs=1,
    )
    return Worker(
        worker_id=0,
        master_client=InProcessMasterClient(MasterServicer(tm)),
        data_reader=reader,
        spec=toy_spec(),
        minibatch_size=32,
    )


def spmd_worker(train_dir):
    from elasticdl_tpu.common.args import parse_master_args
    from elasticdl_tpu.data.reader import TFRecordDataReader
    from elasticdl_tpu.master.main import Master
    from elasticdl_tpu.proto.service import InProcessMasterClient
    from elasticdl_tpu.worker.spmd import SPMDWorker

    master = Master(parse_master_args([
        "--training_data", train_dir,
        "--records_per_task", "64",
        "--num_epochs", "1",
    ]))
    return SPMDWorker(
        worker_id=0,
        master_client=InProcessMasterClient(master.servicer),
        data_reader=TFRecordDataReader(train_dir),
        spec=toy_spec(),
        minibatch_size=32,
    )


@pytest.mark.parametrize("build", [local_worker, spmd_worker])
def test_a_layer_s_own_declaration_reaches_its_gauge_through_either_loop(
    build, mnist_data
):
    TOY_KEPT.reset()
    worker = build(mnist_data[0])
    worker._summary = summary = Summary()
    assert worker.run()
    assert int(worker.state.step) == 4
    assert TOY_KEPT.child_values() == {("probe",): 0.25}
    # two tasks, one summary write each; the undeclared name lands there
    # under its path, the declared one does not
    assert [step for _, step in summary.written] == [2, 4]
    for scalars, _ in summary.written:
        assert scalars["train/probe/toy_plain"] == 3.0
        assert "train/probe/toy_kept_ratio" not in scalars
        assert np.isfinite(scalars["train/loss"])
    assert REGISTRY.value("worker_steps_per_sec") > 0.0


def test_one_name_feeds_one_metric():
    other = REGISTRY.gauge(
        "worker_toy_other_ratio", "", labelnames=("layer",)
    )
    with pytest.raises(ValueError, match="worker_toy_kept_ratio"):
        step_metrics.declare("toy_kept_ratio", other)
    # the same declaration again (a zoo module loaded twice) is the same
    assert step_metrics.declare("toy_kept_ratio", TOY_KEPT) is TOY_KEPT
    assert step_metrics.declared()["toy_kept_ratio"] is TOY_KEPT


@pytest.mark.parametrize("leaf, metric, labels, first, second", [
    ("toy_layer_ratio",
     REGISTRY.gauge("worker_toy_layer_ratio", "", labelnames=("layer",)),
     {"layer": "layer_1/mixer"}, 0.5, 0.75),
    ("toy_table_ratio",
     REGISTRY.gauge("worker_toy_table_ratio", "", labelnames=("table",)),
     {"table": "layer_1/mixer"}, 0.5, 0.75),
    ("toy_dropped",
     REGISTRY.counter("worker_toy_dropped_total", ""), {}, 2.0, 5.0),
])
def test_publish_sets_a_gauge_under_its_own_label_and_adds_to_a_counter(
    leaf, metric, labels, first, second
):
    metric.reset()
    step_metrics.declare(leaf, metric)
    path = "layer_1/mixer/" + leaf
    assert step_metrics.publish({path: first, "loose": 1.5}) == {
        "loose": 1.5
    }
    assert metric.value(**labels) == first
    # a gauge holds the last task's value; a counter sums them
    assert step_metrics.publish({path: second - first}) == {}
    assert metric.value(**labels) == (
        second if metric.kind == metrics_lib.COUNTER else second - first
    )


def test_one_train_program_and_no_flag_for_another(mnist_data):
    from elasticdl_tpu.client.main import main as cli_main

    rc = cli_main([
        "train", "--model_zoo", "model_zoo",
        "--model_def", "mnist.mnist_functional_api.custom_model",
        "--distribution_strategy", "Local",
        "--training_data", mnist_data[0],
        "--minibatch_size", "32", "--records_per_task", "64",
        "--num_epochs", "1",
    ])
    assert rc == 0
    ledger = programs.default_program_registry().ledger()
    assert "worker_train_step" in ledger
    assert "worker_train_step_many" not in ledger
    with pytest.raises(SystemExit):
        cli_main(["train", "--steps_per_execution", "8"])


def test_a_checkpoint_lands_on_every_multiple_of_checkpoint_steps():
    from elasticdl_tpu.worker.sync import ModelOwner
    from elasticdl_tpu.worker.trainer import Trainer

    class Saver:
        def __init__(self):
            self.steps = []

        def maybe_restore(self, state):
            return None

        def save(self, state, force=False):
            self.steps.append(int(state.step))

    spec = get_model_spec(
        "model_zoo", "mnist.mnist_functional_api.custom_model"
    )
    saver = Saver()
    owner = ModelOwner(
        Trainer(model=spec.model, optimizer=spec.optimizer,
                loss_fn=spec.loss),
        checkpoint_saver=saver, checkpoint_steps=3,
    )
    rng = np.random.RandomState(0)
    for _ in range(7):
        owner.train_batch({
            "features": rng.rand(8, 784).astype(np.float32),
            "labels": rng.randint(0, 10, 8).astype(np.int32),
        })
    assert saver.steps == [3, 6]
