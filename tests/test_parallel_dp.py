"""Data-parallel training over the virtual 8-device CPU mesh.

Validates the TPU-native replacement for the reference's two DP paths
(PS-mode and Horovod AllReduce — SURVEY.md §2 parallelism table): the same
Trainer code runs on a 1-device and an 8-device mesh and produces the same
optimization trajectory, with gradient reduction inserted by XLA from the
shardings.
"""

import jax
import numpy as np
import optax
import pytest

from elasticdl_tpu.parallel import mesh as mesh_lib
from elasticdl_tpu.worker.trainer import Trainer


def _spec():
    import model_zoo.mnist.mnist_functional_api as m

    return m


def _batch(n=64, seed=0):
    rng = np.random.RandomState(seed)
    return {
        "features": rng.rand(n, 784).astype(np.float32),
        "labels": rng.randint(0, 10, size=n).astype(np.int32),
    }


def test_eight_devices_visible():
    assert len(jax.devices()) == 8


def _train(mesh, steps=4):
    m = _spec()
    trainer = Trainer(
        model=m.custom_model(),
        optimizer=optax.sgd(0.1),
        loss_fn=m.loss,
        mesh=mesh,
    )
    state = trainer.init_state(jax.random.PRNGKey(0), _batch()["features"])
    losses = []
    for i in range(steps):
        state, loss = trainer.train_on_batch(state, _batch(seed=i))
        losses.append(float(loss))
    return losses, state


def test_dp_mesh_matches_single_device_trajectory():
    losses8, state8 = _train(mesh_lib.create_mesh(jax.devices(), data=8))
    losses1, state1 = _train(mesh_lib.create_mesh(jax.devices()[:1], data=1))
    np.testing.assert_allclose(losses8, losses1, rtol=2e-4)
    # final params agree across meshes
    l8 = jax.tree.leaves(state8.params)
    l1 = jax.tree.leaves(state1.params)
    for a, b in zip(l8, l1):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5)


def test_batch_actually_sharded_across_devices():
    mesh = mesh_lib.create_mesh(jax.devices(), data=8)
    batch = mesh_lib.shard_batch(_batch(64), mesh)
    x = batch["features"]
    assert len(x.sharding.device_set) == 8
    # each device holds 1/8 of the batch
    shard = x.addressable_shards[0]
    assert shard.data.shape[0] == 8


def test_mesh_axis_validation():
    with pytest.raises(ValueError):
        mesh_lib.create_mesh(jax.devices(), data=3)  # 3*1*1*1 != 8
    with pytest.raises(ValueError):
        mesh_lib.create_mesh(jax.devices(), data=-1, model=3)  # 8 % 3


def test_pad_to_multiple_wraps_and_reports_true_count():
    batch = {"features": np.arange(10, dtype=np.float32).reshape(5, 2)}
    padded, real = mesh_lib.pad_to_multiple(batch, 4)
    assert real == 5
    assert padded["features"].shape == (8, 2)
    np.testing.assert_array_equal(
        padded["features"][5:], batch["features"][:3]
    )


@pytest.mark.parametrize("rows", [4, 5])
@pytest.mark.parametrize("key", mesh_lib.STORE_KEYS)
def test_pad_to_multiple_carries_a_stores_bookkeeping(rows, key):
    """A tiered store's feed hangs an admission plan (any object) or the
    raw sparse batch with its ranking (arrays of another length) on the
    batch: neither is a row of the batch.  Without this every task of a
    Local tiered job failed in the pad (`ragged batch`), and the tests of
    that job passed on counters an earlier test had left."""
    sparse = np.arange(rows * 3).reshape(rows, 3)
    ranking = (np.arange(7), np.arange(7))
    carried = object() if key == "__store_plan__" else (sparse, ranking)
    batch = {"features": np.arange(rows * 2.0).reshape(rows, 2), key: carried}
    padded, real = mesh_lib.pad_to_multiple(batch, 4)
    assert real == rows
    assert padded["features"].shape == (-(-rows // 4) * 4, 2)
    if key == "__store_plan__" or rows == 4:
        assert padded[key] is carried
    else:
        # wrapped rows repeat ids: the ranking is the trainer's to redo
        wrapped, ranked = padded[key]
        assert ranked is None
        np.testing.assert_array_equal(wrapped[:rows], sparse)
        np.testing.assert_array_equal(wrapped[rows:], sparse[:3])
