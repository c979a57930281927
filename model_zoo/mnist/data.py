"""Synthetic MNIST-like data generation (no network in this environment, so
datasets are generated deterministically; the record format is the real
one the TFRecord reader serves: 784 image bytes + 1 label byte)."""

from __future__ import annotations

import os

import numpy as np

from elasticdl_tpu.data.record_io import write_tfrecords


def synthetic_mnist(n: int, seed: int = 0):
    """Class-conditional blobs over 784 dims: learnable but non-trivial."""
    rng = np.random.RandomState(seed)
    labels = rng.randint(0, 10, size=n)
    proto = np.random.RandomState(1234).rand(10, 784) * 255
    images = proto[labels] + rng.randn(n, 784) * 32
    images = np.clip(images, 0, 255).astype(np.uint8)
    return images, labels.astype(np.uint8)


def records(images, labels):
    for img, lbl in zip(images, labels):
        yield img.tobytes() + bytes([int(lbl)])


def grain_dataset(n: int = 2048, seed: int = 0):
    """`grain://` factory example (see data/reader/grain_reader.py): a
    random-access Grain MapDataset serving the same 785-byte records the
    TFRecord pipeline does — submit with
    --training_data 'grain://mnist.data:grain_dataset?n=2048'."""
    import grain

    images, labels = synthetic_mnist(n, seed)
    return grain.MapDataset.source(
        [
            images[i].tobytes() + bytes([int(labels[i])])
            for i in range(n)
        ]
    )


def write_dataset(directory: str, n_train: int = 2048, n_val: int = 512,
                  seed: int = 0):
    os.makedirs(os.path.join(directory, "train"), exist_ok=True)
    os.makedirs(os.path.join(directory, "val"), exist_ok=True)
    xi, yi = synthetic_mnist(n_train, seed)
    write_tfrecords(
        os.path.join(directory, "train", "mnist-00000.tfrecord"),
        records(xi, yi),
    )
    xv, yv = synthetic_mnist(n_val, seed + 1)
    write_tfrecords(
        os.path.join(directory, "val", "mnist-00000.tfrecord"),
        records(xv, yv),
    )
    return os.path.join(directory, "train"), os.path.join(directory, "val")
