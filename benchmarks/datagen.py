"""Seeded inputs.  Copied from `chip_smoke.py`'s writers (listed in
PERF.md for a later PR to delete there) and seeded from `--seed`.

Every seed gives the same *amount* and *shape* of work: one pool of
`records_per_task` records is drawn once and each task of the file is a
seeded row permutation of it, so no seed has more distinct rows, longer
records or another mix of sizes than any other.  numpy only.
"""

from __future__ import annotations

import numpy as np


def rng_for(seed: int, stream: int = 0) -> np.random.Generator:
    """`--seed` may be any whole number past 2**31; a Generator takes it
    as it is.  `stream` separates the uses of one seed."""
    return np.random.default_rng([int(seed), int(stream)])


def zipf_ids(rng, rows: int, exponent: float, cardinalities) -> np.ndarray:
    """(rows, fields) ids: zipf ranks (0 the most frequent) folded into
    each field's own number of values, so a field of 3 values and one of
    10 million both look like themselves."""
    sizes = np.asarray(cardinalities, np.int64)
    ranks = rng.zipf(exponent, size=(rows, len(sizes))) - 1
    return (ranks % sizes[None, :]).astype(np.int32)


def criteo_features(rng, rows: int, data: dict) -> dict:
    """`rows` examples of the Criteo layout: 13 dense f32, 26 int32 ids."""
    if len(data["field_cardinalities"]) != data["num_sparse"]:
        raise ValueError("one cardinality a sparse field")
    return {
        "dense": rng.random((rows, data["num_dense"]), dtype=np.float32),
        "sparse": zipf_ids(
            rng, rows, data["zipf_exponent"], data["field_cardinalities"]
        ),
    }


def criteo_records(rng, rows: int, data: dict) -> np.ndarray:
    """(rows, 157) uint8: 13 f32 dense | 26 i32 ids | 1 label byte."""
    features = criteo_features(rng, rows, data)
    dense_bytes = data["num_dense"] * 4
    sparse_bytes = data["num_sparse"] * 4
    arr = np.empty((rows, dense_bytes + sparse_bytes + 1), np.uint8)
    arr[:, :dense_bytes] = features["dense"].view(np.uint8)
    arr[:, dense_bytes:-1] = features["sparse"].view(np.uint8)
    arr[:, -1] = rng.integers(0, 2, rows, dtype=np.uint8)
    return arr


def token_records(rng, rows: int, data: dict) -> np.ndarray:
    """(rows, 4*seq_len+1) uint8: seq_len int32 token ids | 1 label."""
    seq_len = data["seq_len"]
    arr = np.empty((rows, seq_len * 4 + 1), np.uint8)
    arr[:, :-1] = rng.integers(
        0, data["vocab_size"], (rows, seq_len), dtype=np.int32
    ).view(np.uint8)
    arr[:, -1] = rng.integers(0, 2, rows, dtype=np.uint8)
    return arr


RECORD_MAKERS = {"criteo": criteo_records, "tokens": token_records}


def write_task_file(path, seed, data: dict, records_per_task, file_tasks):
    """A TFRecord file of `file_tasks` tasks.  Returns the records of the
    first task, (records_per_task, record_bytes) uint8, for the
    correctness check."""
    from elasticdl_tpu.data.record_io import write_tfrecords_bulk

    rng = rng_for(seed, 1)
    pool = RECORD_MAKERS[data["format"]](rng, records_per_task, data)
    record_bytes = pool.shape[1]
    out = np.empty((file_tasks, records_per_task, record_bytes), np.uint8)
    out[0] = pool
    for task in range(1, file_tasks):
        out[task] = pool[rng.permutation(records_per_task)]
    write_tfrecords_bulk(
        path, out.reshape(-1),
        np.full(file_tasks * records_per_task, record_bytes, np.int64),
    )
    return pool


def parse_criteo(records: np.ndarray, data: dict) -> dict:
    dense_bytes = data["num_dense"] * 4
    return {
        "features": {
            "dense": np.ascontiguousarray(
                records[:, :dense_bytes]).view("<f4"),
            "sparse": np.ascontiguousarray(
                records[:, dense_bytes:-1]).view("<i4"),
        },
        "labels": records[:, -1].astype(np.int32),
    }


def parse_tokens(records: np.ndarray, data: dict) -> dict:
    return {
        "features": {
            "input_ids": np.ascontiguousarray(records[:, :-1]).view("<i4"),
        },
        "labels": records[:, -1].astype(np.int32),
    }


RECORD_PARSERS = {"criteo": parse_criteo, "tokens": parse_tokens}
