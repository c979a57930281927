"""Click-through-rate MLP for the online continuous-learning pipeline.

Consumes the synthetic click-stream records the StreamReader windows
(`data/reader/stream_reader.py`: dicts of user, item, clicked,
event_unix_s) through the standard zoo contract, so the online
orchestrator (elasticdl_tpu/online/pipeline.py) and its tests
train and serve it with the same Trainer/ServingEngine every batch model
uses.  Deliberately tiny: the online loop's subject is the
stream→train→reload plumbing, not the model.

Features are hashed one-hots — user into the first HASH_USER buckets,
item into the next HASH_ITEM — the classic CTR trick that keeps the
serving input a fixed dense (B, DIM) matrix whatever the id spaces grow
to (the stream's lazy vocabulary never forces a model rebuild).
"""

from __future__ import annotations

import flax.linen as nn
import jax.numpy as jnp
import numpy as np
import optax

HASH_USER = 64
HASH_ITEM = 64
DIM = HASH_USER + HASH_ITEM


class CtrMLP(nn.Module):
    @nn.compact
    def __call__(self, x):
        x = nn.Dense(32)(x)
        x = nn.relu(x)
        return nn.Dense(2)(x)  # [no-click, click] logits


def custom_model():
    return CtrMLP()


def loss(labels, predictions):
    return optax.softmax_cross_entropy_with_integer_labels(
        predictions, labels.astype(jnp.int32)
    ).mean()


def optimizer(lr: float = 1e-2):
    return optax.adam(lr)


def encode(users: np.ndarray, items: np.ndarray) -> np.ndarray:
    """(B,) user ids + (B,) item ids -> (B, DIM) hashed one-hots.
    Shared by feed() and the bench's predict-load generator so training
    and serving agree on the feature space byte-for-byte."""
    users = np.asarray(users, np.int64)
    items = np.asarray(items, np.int64)
    out = np.zeros((users.shape[0], DIM), np.float32)
    out[np.arange(users.shape[0]), users % HASH_USER] = 1.0
    out[np.arange(items.shape[0]), HASH_USER + items % HASH_ITEM] = 1.0
    return out


def feed(records, metadata=None):
    users, items, labels = [], [], []
    for record in records:
        users.append(int(record["user"]))
        items.append(int(record["item"]))
        labels.append(int(record["clicked"]))
    return {
        "features": encode(np.asarray(users), np.asarray(items)),
        "labels": np.asarray(labels, np.int32),
    }


def eval_metrics_fn():
    return {
        "accuracy": lambda labels, predictions: float(
            np.mean(np.argmax(predictions, axis=-1) == labels)
        ),
    }
