"""DeepFM for Criteo-style CTR data — the north-star config
(BASELINE.md #4).  Zoo-contract port of the reference's
model_zoo/deepfm* (SURVEY.md C20) re-designed TPU-first:

- all 26 sparse fields share ONE embedding table (a single-feature
  `EmbeddingArena`, row-sharded over the mesh `model` axis) addressed by
  field-offset ids — a single large gather per step instead of 26 small
  ones keeps the lookup and its scatter-add gradient efficient on TPU;
  `arena_dtype="int8"` switches the table to quantized storage
  (docs/PERF.md "Quantized arena");
- FM second-order term uses the square-of-sum trick (two reductions, no
  O(fields^2) pairwise products);
- the deep tower is a plain MLP on the MXU.

Record format (TFRecord payload): 13 float32 dense | 26 int32 sparse ids |
1 uint8 label = 157 bytes (see model_zoo.deepfm.data).
"""

from __future__ import annotations

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import optax

from elasticdl_tpu.layers.arena import EmbeddingArena
from elasticdl_tpu.layers.embedding import embedding_param_sharding
from model_zoo.common.metrics import auc, binary_accuracy

NUM_DENSE = 13
NUM_SPARSE = 26


def field_offset_ids(sparse: jnp.ndarray) -> jnp.ndarray:
    """(B, 26) raw ids -> field-offset ids for the ONE shared table:
    separates fields before hashing (hash mixing declusters the
    offsets).  Shared by every CTR model on this record format so the
    id scheme cannot drift between them."""
    offsets = jnp.arange(NUM_SPARSE, dtype=jnp.int32) * jnp.int32(
        0x61C88647  # int32-safe odd mixing constant (2^32/phi >> 1)
    )
    return sparse.astype(jnp.int32) + offsets[None, :]


def sparse_ids(features) -> jnp.ndarray:
    """(B, 26) int ids from `features["sparse"]`, whatever wire format it
    arrived in (plain int32, or the compact b22/uint24 packings from
    elasticdl_tpu.data.wire).  Shared by every CTR model on this record
    format so compact-wire support cannot drift between them."""
    sparse = features["sparse"]
    from elasticdl_tpu.data.wire import (
        is_packed_b22,
        is_packed_uint24,
        unpack_b22,
        unpack_uint24,
    )

    if is_packed_b22(sparse):
        return unpack_b22(sparse)
    if is_packed_uint24(sparse):
        return unpack_uint24(sparse)
    return sparse


def sparse_field_rows(features, vocab_capacity: int):
    """(B, 26) rows into the shared table, plus whether they are already
    hashed.  The dedup'd wire format (feed_bulk_dedup) ships PRE-HASHED
    rows — the device reconstructs them with a scatter-patch + gather
    (wire.unpack_rows_dedup) and the embeddings skip their own hash/mod
    (`prehashed=True`).  Every other format goes through the usual
    field-offset + on-device hash path."""
    sparse = features["sparse"]
    from elasticdl_tpu.data.wire import is_packed_dedup, unpack_rows_dedup

    if is_packed_dedup(sparse):
        return unpack_rows_dedup(sparse), True
    return field_offset_ids(sparse_ids(features)), False


def hash_field_rows_host(sparse, vocab_capacity: int):
    """Host-side numpy replica of `field_offset_ids` + the embeddings'
    `hash_ids(..., mix=True)` — bit-exact vs the traced path (uint32
    wraparound everywhere).  Raises if any post-offset id equals the
    pad sentinel (-1): the device path would zero-mask that position and
    the prehashed fast path cannot represent it (probability ~26/2^32
    per example on real streams)."""
    from elasticdl_tpu.layers.embedding import hash_ids_host

    sparse = np.asarray(sparse)
    offsets = (
        np.arange(NUM_SPARSE, dtype=np.uint32) * np.uint32(0x61C88647)
    )
    with np.errstate(over="ignore"):
        field_ids = sparse.astype(np.uint32) + offsets[None, :]
    if np.any(field_ids == np.uint32(0xFFFFFFFF)):
        raise ValueError(
            "dedup packing: a field-offset id equals the pad sentinel "
            "(-1); this batch must ship on the non-dedup wire format"
        )
    return hash_ids_host(field_ids, vocab_capacity, mix=True)


def normalize_dense(dense: jnp.ndarray) -> jnp.ndarray:
    """Signed log1p squashing of the 13 dense counters (Criteo-style
    heavy-tailed counts)."""
    dense = dense.astype(jnp.float32)
    return jnp.log1p(jnp.abs(dense)) * jnp.sign(dense)


def arena_field_lookup(arena, field_ids, prehashed):
    """Call a single-feature `EmbeddingArena` with DeepFM's (B, 26)
    shared-hash-space field rows: prehashed rows go straight through
    (arena rows == table rows at offset 0); raw ids route through the
    dict path under the one feature name.  Numerically identical to the
    `DistributedEmbedding` call it replaced (same param path/init, same
    hash, offset 0) — `tests/test_sparse_path.py` pins that."""
    if prehashed:
        return arena(field_ids, prehashed=True)
    return arena({"sparse": field_ids})["sparse"]


def deepfm_tail(emb, first, dense, mlp_dims, compute_dtype):
    """Everything after the embedding lookups: FM reductions, wide head,
    deep tower.  A plain function called from inside an `@nn.compact`
    __call__ (flax resolves the Dense submodules against the CALLING
    module), shared by `DeepFM` and the tiered variant
    (model_zoo/deepfm/deepfm_tiered.py) so the two stay numerically
    identical layer-for-layer — same names, hence the SAME path-based
    init — and the tiered parity bench can compare them exactly."""
    # FM second order: 0.5 * sum_k [ (sum_f v)^2 - sum_f v^2 ]
    sum_f = jnp.sum(emb, axis=1)
    fm2 = 0.5 * jnp.sum(
        sum_f * sum_f - jnp.sum(emb * emb, axis=1), axis=-1
    )

    dense_n = normalize_dense(dense)                   # (B, 13)
    wide = nn.Dense(1, name="dense_linear")(dense_n)[..., 0]

    deep_in = jnp.concatenate(
        [dense_n, emb.reshape(emb.shape[0], -1)], axis=-1
    )
    h = deep_in.astype(compute_dtype)
    for i, width in enumerate(mlp_dims):
        h = nn.relu(
            nn.Dense(width, name=f"mlp_{i}", dtype=compute_dtype)(h)
        )
    deep = nn.Dense(1, name="mlp_out", dtype=compute_dtype)(h)[
        ..., 0
    ].astype(jnp.float32)

    return wide + jnp.sum(first[..., 0], axis=1) + fm2 + deep  # logits


class DeepFM(nn.Module):
    vocab_capacity: int = 1 << 18  # shared table rows (hash space)
    embed_dim: int = 16
    mlp_dims: tuple = (256, 128)
    # bf16 puts the MLP matmuls on the MXU at full rate; params stay f32
    # (flax Dense computes in `dtype`, accumulates/stores kernels in
    # param_dtype=f32 by default) and the FM reductions stay f32 for
    # numerical safety.
    compute_dtype: jnp.dtype = jnp.float32
    # "int8": quantized arena storage (docs/PERF.md "Quantized arena")
    arena_dtype: str = "float32"

    @nn.compact
    def __call__(self, features):
        # (B, 26) rows; prehashed=True on the dedup'd wire format (the
        # host already hashed — both tables then skip their hash/mod)
        field_ids, prehashed = sparse_field_rows(
            features, self.vocab_capacity
        )

        # second-order / deep embeddings: (B, 26, k)
        emb = arena_field_lookup(EmbeddingArena(
            (("sparse", self.vocab_capacity),), self.embed_dim,
            hash_input=True, name="fm_embedding",
            arena_dtype=self.arena_dtype,
        ), field_ids, prehashed)
        # first-order weights: (B, 26, 1)
        first = arena_field_lookup(EmbeddingArena(
            (("sparse", self.vocab_capacity),), 1,
            hash_input=True, name="fm_linear",
            arena_dtype=self.arena_dtype,
        ), field_ids, prehashed)

        with jax.named_scope("deepfm/tower"):
            return deepfm_tail(
                emb, first, features["dense"], self.mlp_dims,
                self.compute_dtype,
            )


def custom_model(
    vocab_capacity: int = 1 << 18, embed_dim: int = 16, bf16: bool = False,
    arena_dtype: str = "float32",
):
    global DEDUP_VOCAB_CAPACITY
    # the dedup feed hashes on the HOST, so it must use the capacity the
    # model in this process was built with (feeds get no model handle)
    DEDUP_VOCAB_CAPACITY = int(vocab_capacity)
    return DeepFM(
        vocab_capacity=vocab_capacity,
        embed_dim=embed_dim,
        compute_dtype=jnp.bfloat16 if bf16 else jnp.float32,
        arena_dtype=arena_dtype,
    )


def loss(labels, predictions):
    return optax.sigmoid_binary_cross_entropy(
        predictions, labels.astype(jnp.float32)
    ).mean()


def optimizer(lr: float = 1e-3):
    return optax.adam(lr)


RECORD_BYTES = NUM_DENSE * 4 + NUM_SPARSE * 4 + 1


def feed(records, metadata=None):
    dense = np.empty((len(records), NUM_DENSE), np.float32)
    sparse = np.empty((len(records), NUM_SPARSE), np.int32)
    labels = np.empty((len(records),), np.int32)
    for i, record in enumerate(records):
        if isinstance(record, dict):
            dense[i] = record["dense"]
            sparse[i] = record["sparse"]
            labels[i] = record["label"]
        else:
            dense[i] = np.frombuffer(record, np.float32, NUM_DENSE, 0)
            sparse[i] = np.frombuffer(
                record, np.int32, NUM_SPARSE, NUM_DENSE * 4
            )
            labels[i] = record[RECORD_BYTES - 1]
    return {
        "features": {"dense": dense, "sparse": sparse},
        "labels": labels,
    }


def feed_bulk(buffer, sizes, metadata=None):
    """Vectorized parse of the fixed 157-byte record: one reshape over the
    reader's contiguous payload buffer instead of a per-record Python loop
    (~100x the `feed` path's throughput; the e2e bench rides this)."""
    n = len(sizes)
    if n == 0 or not (np.asarray(sizes) == RECORD_BYTES).all():
        raise ValueError(
            f"deepfm feed_bulk expects fixed {RECORD_BYTES}-byte records"
        )
    arr = np.frombuffer(buffer, np.uint8).reshape(n, RECORD_BYTES)
    dense = np.ascontiguousarray(arr[:, : NUM_DENSE * 4]).view("<f4")
    sparse = np.ascontiguousarray(
        arr[:, NUM_DENSE * 4 : NUM_DENSE * 4 + NUM_SPARSE * 4]
    ).view("<i4")
    labels = arr[:, RECORD_BYTES - 1].astype(np.int32)
    return {
        "features": {"dense": dense, "sparse": sparse},
        "labels": labels,
    }


def feed_bulk_compact(buffer, sizes, metadata=None):
    """feed_bulk with the compact device wire format
    (elasticdl_tpu.data.wire): dense bf16, sparse b22-packed (uint16
    low halves + bit-packed high 6), labels uint8 — 99 bytes/example on
    the link instead of 160.  The model unpacks on device (fused by
    XLA); dense values round through bf16 (<0.4% relative — they feed a
    log1p squash recomputed in f32).  This zoo's record format
    guarantees ids < 2^22, the b22 bound."""
    from elasticdl_tpu.data.wire import pack_f32_to_bf16, pack_int_to_b22

    batch = feed_bulk(buffer, sizes, metadata)
    features = batch["features"]
    return {
        "features": {
            "dense": pack_f32_to_bf16(features["dense"]),
            "sparse": pack_int_to_b22(features["sparse"]),
        },
        "labels": batch["labels"].astype(np.uint8),
    }


DEDUP_VOCAB_CAPACITY = 1 << 18   # updated by custom_model()
_DEDUP_PACKER = None


def feed_bulk_dedup(buffer, sizes, metadata=None):
    """feed_bulk with the dedup'd device wire format
    (elasticdl_tpu.data.wire, PFOR-style): ids are field-offset +
    hashed HOST-side into shared-table rows, dedup'd per field into a
    frequency-ranked unique list + a 1-byte inverse plane with
    escape-coded exceptions.  On zipf-skewed CTR streams this is ~60-65
    bytes/example on the link vs the b22 compact format's 99 and the
    plain format's 160 — and the device also skips the hash/mod (the
    embeddings consume rows directly).  Pad caps are sticky
    (wire.DedupPacker) so consecutive batches keep identical shapes."""
    global _DEDUP_PACKER
    from elasticdl_tpu.data.wire import DedupPacker, pack_f32_to_bf16

    if _DEDUP_PACKER is None:
        _DEDUP_PACKER = DedupPacker()
    batch = feed_bulk(buffer, sizes, metadata)
    features = batch["features"]
    rows = hash_field_rows_host(features["sparse"], DEDUP_VOCAB_CAPACITY)
    return {
        "features": {
            "dense": pack_f32_to_bf16(features["dense"]),
            "sparse": _DEDUP_PACKER.pack(rows),
        },
        "labels": batch["labels"].astype(np.uint8),
    }


def eval_metrics_fn():
    return {"auc": auc, "accuracy": binary_accuracy}


param_sharding = embedding_param_sharding
