"""DeepFM on synthetic Criteo: the north-star config's correctness path —
full job (sharded embedding tables on a data×model mesh, train + final
eval) must learn the planted structure (AUC well above chance)."""

import jax
import numpy as np
import pytest

from elasticdl_tpu.common.args import parse_master_args
from elasticdl_tpu.common.model_handler import get_model_spec
from elasticdl_tpu.data.reader import TFRecordDataReader
from elasticdl_tpu.master.main import Master
from elasticdl_tpu.parallel import mesh as mesh_lib
from elasticdl_tpu.proto.service import InProcessMasterClient
from elasticdl_tpu.worker.worker import Worker


@pytest.fixture(scope="module")
def criteo_data(tmp_path_factory):
    from model_zoo.deepfm.data import write_dataset

    root = tmp_path_factory.mktemp("criteo")
    return write_dataset(str(root), n_train=8192, n_val=2048)


@pytest.fixture(scope="module")
def spec():
    return get_model_spec(
        "model_zoo",
        "deepfm.deepfm_functional_api.custom_model",
        model_params="vocab_capacity=65536;embed_dim=8;lr=0.005",
    )


def test_deepfm_learns_planted_structure(criteo_data, spec):
    train_dir, val_dir = criteo_data
    args = parse_master_args(
        [
            "--training_data", train_dir,
            "--validation_data", val_dir,
            "--records_per_task", "1024",
            "--num_epochs", "3",
            "--minibatch_size", "256",
        ]
    )
    master = Master(args)
    client = InProcessMasterClient(master.servicer)
    mesh = mesh_lib.create_mesh(jax.devices(), data=4, model=2)
    worker = Worker(
        worker_id=0,
        master_client=client,
        data_reader=TFRecordDataReader(train_dir),
        spec=spec,
        minibatch_size=256,
        mesh=mesh,
    )
    assert worker.run()
    assert master.task_manager.finished
    metrics = master.evaluation_service.latest_metrics()
    assert metrics is not None
    # Bayes-optimal AUC on this synthetic set is ~0.85; the 0.70 bar
    # requires the embeddings and FM interactions to genuinely learn.
    assert metrics["auc"] > 0.70, f"AUC too low: {metrics}"
    # embedding table sharded across the model axis
    table = worker.state.params["params"]["fm_embedding"]["embedding"]
    assert table.addressable_shards[0].data.shape[0] == table.shape[0] // 2
    losses = [float(l) for l in worker.losses]
    assert losses[-1] < losses[0]


def test_the_job_says_which_route_its_lookups_took(
    criteo_data, spec, monkeypatch
):
    """A whole job with both tables' forwards on the distinct-row route
    (trips of 64 rows patched in; the tables row-sharded over `model`):
    the sown `lookup_compact` rides to each task's one fetch and sets
    `worker_arena_lookup_compact_ratio{table}` beside the distinct share."""
    from elasticdl_tpu.common import metrics as metrics_lib
    from elasticdl_tpu.layers import embedding

    monkeypatch.setattr(embedding, "CHUNK", 64)
    monkeypatch.setattr(embedding, "_COMPACT_CHUNKS", 104)
    train_dir, _ = criteo_data
    args = parse_master_args([
        "--training_data", train_dir, "--records_per_task", "1024",
        "--num_epochs", "1", "--minibatch_size", "256",
    ])
    master = Master(args)
    worker = Worker(
        worker_id=0,
        master_client=InProcessMasterClient(master.servicer),
        data_reader=TFRecordDataReader(train_dir),
        spec=spec,
        minibatch_size=256,
        mesh=mesh_lib.create_mesh(jax.devices(), data=4, model=2),
    )
    assert worker.run()
    registry = metrics_lib.default_registry()
    shares = {
        table: registry.value("worker_arena_distinct_rows_ratio", table=table)
        for table in ("fm_embedding", "fm_linear")
    }
    # 256 x 26 ids, the same for both tables
    assert 0.0 < shares["fm_embedding"] == shares["fm_linear"] < 1.0
    for table in shares:
        assert registry.value(
            "worker_arena_lookup_compact_ratio", table=table
        ) == 1.0
    losses = [float(l) for l in worker.losses]
    assert np.isfinite(losses).all() and losses[-1] < losses[0]
