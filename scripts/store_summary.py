"""Tiered-store efficacy summary for CI.

Runs the store's pure-numpy host side — LazyVocabulary growth +
HotRowCache admission — over a deterministic zipfian id stream and
prints one machine-readable line:

    STORE_SUMMARY hit_rate=<r> growth_rows=<n> cache_dtype=<d> \
        device_cache_bytes=<b> int8_bytes_reduction=<x> \
        per_chip_cache_bytes=<b/8>

`scripts/run_tests.sh` emits it next to TIER1_SUMMARY so CI can watch
cache efficacy drift (no cell of the benchmark runs the tiered store
yet: ROADMAP.md Reach 7).  No jax, no devices: the whole check is
host math, which is the point — a cache-policy regression shows up
here in well under a second.  The byte fields are the ISSUE-18 analytic
model (store/cache.py cache_value_bytes_per_row): fp32 vs int8 device
cache VALUE bytes at this config's capacity, and the per-chip share
over the 8-device mesh the MULTICHIP harness drives.

tests/test_tiered_store.py asserts on `zipfian_summary()` directly, so
the printed numbers and the tested numbers cannot diverge.
"""

from __future__ import annotations

import numpy as np

# The canonical zipfian config: a skewed stream where a 4k-row cache
# over a ~8k-row working vocabulary should hold the hot head (hit rate
# >= 0.9).
NUM_FIELDS = 26
BATCH = 128
STEPS = 60
CACHE_ROWS = 4096
IDS_PER_FIELD = 2000
ZIPF_A = 1.6
SEED = 0x5EED


def zipfian_batches(
    steps: int = STEPS,
    batch: int = BATCH,
    num_fields: int = NUM_FIELDS,
    ids_per_field: int = IDS_PER_FIELD,
    a: float = ZIPF_A,
    seed: int = SEED,
):
    """Deterministic (steps, batch, fields) zipfian id stream.  Rank r
    is drawn with probability ∝ 1/r^a, then permuted per field so hot
    ids differ across fields."""
    rng = np.random.default_rng(seed)
    ranks = np.minimum(
        rng.zipf(a, size=(steps, batch, num_fields)), ids_per_field
    ) - 1
    perms = np.stack(
        [rng.permutation(ids_per_field) for _ in range(num_fields)]
    )
    fields = np.arange(num_fields)[None, None, :]
    return perms[fields, ranks].astype(np.int64)


def zipfian_summary(cache_rows: int = CACHE_ROWS, **stream_kw):
    """(hit_rate, growth_rows) of the host-side store over the zipfian
    stream — the shared compute behind STORE_SUMMARY and the unit test."""
    from elasticdl_tpu.store.cache import HotRowCache
    from elasticdl_tpu.store.host_tier import LazyVocabulary

    stream = zipfian_batches(**stream_kw)
    vocab = LazyVocabulary(num_fields=stream.shape[2])
    cache = HotRowCache(cache_rows)
    hits = misses = 0
    for sparse in stream:
        rows, _, _, _ = vocab.assign(sparse)
        plan = cache.plan(rows)
        hits += plan.hits
        misses += plan.misses
    return hits / max(hits + misses, 1), vocab.size


# The byte model reports deepfm_tiered's default plane set at this
# config's cache capacity (store_planes(): embedding dim 16 + linear 1).
EMBED_DIM = 16
MESH_SHARDS = 8


def byte_summary(cache_rows: int = CACHE_ROWS,
                 embed_dim: int = EMBED_DIM,
                 mesh_shards: int = MESH_SHARDS):
    """(fp32_bytes, int8_bytes, reduction, per_chip_int8_bytes) — the
    analytic device-cache VALUE bytes both STORE_SUMMARY and the unit
    test report (same single-source pattern as zipfian_summary)."""
    from elasticdl_tpu.store.cache import device_cache_bytes

    planes = {"fm_embedding": embed_dim, "fm_linear": 1}
    fp32 = device_cache_bytes(planes, cache_rows, "float32")
    int8 = device_cache_bytes(planes, cache_rows, "int8")
    return fp32, int8, fp32 / int8, int8 // mesh_shards


def main() -> int:
    hit_rate, growth_rows = zipfian_summary()
    fp32, int8, reduction, per_chip = byte_summary()
    print(f"STORE_SUMMARY hit_rate={hit_rate:.4f} "
          f"growth_rows={growth_rows} "
          f"cache_dtype=float32 device_cache_bytes={fp32} "
          f"int8_bytes_reduction={reduction:.2f} "
          f"per_chip_cache_bytes={per_chip}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
