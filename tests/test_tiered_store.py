"""Tiered embedding store (elasticdl_tpu/store): host-RAM bulk tier,
device hot-row cache, lazy vocabulary growth.

Covers the store's contracts end to end:

* lazy growth is deterministic (same id stream -> same id->row map);
* cache admission bookkeeping (hit counting, victim selection outside
  the current batch, over-capacity refusal);
* EXACT train parity vs the flat arena on an all-hot working set —
  losses and trained rows bitwise equal (predict compiles a separate
  program per model, so it only gets a few-ulp bound);
* checkpoint sidecar round-trip and tiered<->flat migration in BOTH
  directions;
* serving: Predict on a never-trained id, known-but-cold overlays, and
  a hot swap with zero dropped requests;
* the Local runner starts the store's background threads (client/api.py
  owns that call — Master.start() never runs in the Local path).
"""

import os
import sys
import threading

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from elasticdl_tpu.layers.embedding import hash_ids_host
from elasticdl_tpu.store import checkpoint as store_ckpt
from elasticdl_tpu.store.cache import HotRowCache
from elasticdl_tpu.store.host_tier import HostTier, LazyVocabulary
from elasticdl_tpu.store.tiered import TieredStore
from elasticdl_tpu.worker.trainer import TrainState
from scripts.store_summary import zipfian_batches, zipfian_summary

NUM_FIELDS = 26  # the deepfm field count the zoo models are built for


def hash_rows(fields, ids, cap):
    """Host replica of the flat deepfm hashing for arbitrary
    (field, id) pairs (field-offset ids + mixed modular hash)."""
    with np.errstate(over="ignore"):
        fid = (
            np.asarray(ids).astype(np.uint32)
            + np.asarray(fields).astype(np.uint32) * np.uint32(0x61C88647)
        )
    return hash_ids_host(fid, cap, mix=True)


# ---- lazy vocabulary growth -------------------------------------------


def test_lazy_growth_deterministic():
    stream = zipfian_batches(steps=6, batch=32, ids_per_field=200)
    a = LazyVocabulary(num_fields=NUM_FIELDS)
    b = LazyVocabulary(num_fields=NUM_FIELDS)
    for sparse in stream:
        rows_a, *_ = a.assign(sparse)
        rows_b, *_ = b.assign(sparse)
        np.testing.assert_array_equal(rows_a, rows_b)
    assert a.size == b.size
    for x, y in zip(a.state_arrays(), b.state_arrays()):
        np.testing.assert_array_equal(x, y)
    # replaying the same stream after the fact grows nothing
    before = a.size
    for sparse in stream:
        _, new_fields, _, _ = a.assign(sparse)
        assert new_fields.size == 0
    assert a.size == before


def test_growth_only_on_first_lookup():
    vocab = LazyVocabulary(num_fields=2)
    sparse = np.array([[5, 7]], np.int64)
    rows1, new1, *_ = vocab.assign(sparse)
    assert new1.size == 2
    rows2, new2, *_ = vocab.assign(sparse)
    assert new2.size == 0
    np.testing.assert_array_equal(rows1, rows2)
    # lookup never grows; unknown ids come back -1
    probe = np.array([[5, 999]], np.int64)
    looked = vocab.lookup(probe)
    assert looked[0, 0] == rows1[0, 0]
    assert looked[0, 1] == -1
    assert vocab.size == 2
    # the same raw id in a DIFFERENT field is a different row
    rows3, new3, *_ = vocab.assign(np.array([[7, 5]], np.int64))
    assert new3.size == 2
    assert rows3[0, 0] != rows1[0, 1]


def test_zipfian_summary_meets_hit_rate_floor():
    # The exact numbers scripts/run_tests.sh prints as STORE_SUMMARY —
    # this test owns the hard floor the CI line only reports.
    hit_rate, growth_rows = zipfian_summary()
    assert hit_rate >= 0.9
    assert growth_rows > 4096  # vocabulary outgrew the cache


# ---- hot-row cache bookkeeping ----------------------------------------


def test_cache_over_capacity_raises():
    cache = HotRowCache(8)
    with pytest.raises(ValueError, match="unique rows"):
        cache.plan(np.arange(9, dtype=np.int64))


def test_cache_hit_counting_counts_occurrences():
    cache = HotRowCache(8)
    p1 = cache.plan(np.array([1, 1, 2], np.int64))
    assert (p1.hits, p1.misses) == (0, 3)
    assert p1.admit_rows.size == 2
    p2 = cache.plan(np.array([1, 2, 2, 3], np.int64))
    assert p2.hits == 3  # 1 once + 2 twice
    assert p2.misses == 1
    assert list(p2.admit_rows) == [3]


def test_cache_never_evicts_current_batch_rows():
    cache = HotRowCache(4)
    cache.plan(np.array([10, 11, 12, 13], np.int64))  # fill
    p = cache.plan(np.array([10, 11, 20], np.int64))
    assert set(p.evict_rows.tolist()).isdisjoint({10, 11, 20})
    assert p.evict_rows.size == 1
    # the evicted row's slot is exactly the admitted row's slot
    assert set(p.admit_slots.tolist()) == set(p.evict_slots.tolist())
    # re-planning the evicted row admits it again (it is gone)
    evicted = int(p.evict_rows[0])
    p3 = cache.plan(np.array([evicted], np.int64))
    assert evicted in p3.admit_rows.tolist()


def test_cache_state_arrays_round_trip():
    cache = HotRowCache(4)
    cache.plan(np.array([7, 8], np.int64))
    row_of, score, dtype = cache.state_arrays()
    assert dtype == "float32"
    clone = HotRowCache(4)
    clone.load_state_arrays(row_of, score, dtype=dtype)
    p = clone.plan(np.array([7, 8], np.int64))
    assert p.misses == 0 and p.hits == 2


def test_cache_rejects_ranking_with_wrong_coverage():
    cache = HotRowCache(8)
    with pytest.raises(ValueError, match="lookups"):
        cache.plan(
            np.array([1, 1, 2], np.int64),
            ranked=(np.array([1, 2], np.int64), np.array([1, 1], np.int64)),
        )


def test_cache_ranked_plan_matches_unranked_twin():
    """Feeding the wire's precomputed ranking must be a pure optimisation:
    every plan field and the post-plan cache state stay identical to a
    twin cache that re-derives the ranking itself."""
    from elasticdl_tpu.data.wire import frequency_rank

    ranked_c, plain_c = HotRowCache(64), HotRowCache(64)
    rng = np.random.RandomState(21)
    for _ in range(6):
        rows = (rng.zipf(1.3, size=(64,)) % 40).astype(np.int64)
        a = ranked_c.plan(rows, ranked=frequency_rank(rows))
        b = plain_c.plan(rows)
        np.testing.assert_array_equal(a.slots, b.slots)
        np.testing.assert_array_equal(a.admit_rows, b.admit_rows)
        np.testing.assert_array_equal(a.admit_slots, b.admit_slots)
        np.testing.assert_array_equal(a.evict_rows, b.evict_rows)
        np.testing.assert_array_equal(a.evict_slots, b.evict_slots)
        assert (a.hits, a.misses) == (b.hits, b.misses)
    for x, y in zip(ranked_c.state_arrays(), plain_c.state_arrays()):
        np.testing.assert_array_equal(x, y)


# ---- wire-ranked admission through the store ---------------------------


def _twin_stores(cache_rows=256):
    mk = lambda: TieredStore(
        {"fm_embedding": 4, "fm_linear": 1}, NUM_FIELDS, cache_rows
    )
    return mk(), mk()


def test_store_ranked_prepare_matches_unranked_twin():
    """The full producer contract: DedupPacker over
    `wire.field_disjoint_ids(sparse)` fed to `prepare(ranked=)` plans
    byte-identically to a twin store that re-ranks internally — on
    batches whose raw ids collide across fields (the per-field-vocab
    case a raw-id ranking would silently mistranslate)."""
    from elasticdl_tpu.data.wire import DedupPacker, field_disjoint_ids

    ranked_s, plain_s = _twin_stores()
    packer = DedupPacker()
    rng = np.random.RandomState(13)
    for _ in range(4):
        # ids 0..4 in every field: heavy cross-field raw-id collisions
        sparse = rng.randint(0, 5, size=(4, NUM_FIELDS)).astype(np.int64)
        packer.pack(field_disjoint_ids(sparse))
        slots_a, plan_a = ranked_s.prepare(
            sparse, ranked=packer.last_ranking
        )
        slots_b, plan_b = plain_s.prepare(sparse)
        np.testing.assert_array_equal(slots_a, slots_b)
        np.testing.assert_array_equal(plan_a.admit_rows, plan_b.admit_rows)
        np.testing.assert_array_equal(plan_a.evict_rows, plan_b.evict_rows)
        assert (plan_a.hits, plan_a.misses) == (plan_b.hits, plan_b.misses)
    assert ranked_s.host.size == plain_s.host.size
    for x, y in zip(
        ranked_s.cache.state_arrays(), plain_s.cache.state_arrays()
    ):
        np.testing.assert_array_equal(x, y)


def test_store_attach_consumes_dedup_ranking_key():
    """`attach` pops `__dedup_ranking__` (never shipped to the trainer)
    and produces the same slots as an unranked twin."""
    from elasticdl_tpu.data.wire import DedupPacker, field_disjoint_ids

    ranked_s, plain_s = _twin_stores()
    rng = np.random.RandomState(14)
    sparse = rng.randint(0, 5, size=(4, NUM_FIELDS)).astype(np.int64)
    packer = DedupPacker()
    packer.pack(field_disjoint_ids(sparse))
    batch = {
        "features": {"dense": np.zeros((4, 13), np.float32),
                     "sparse": sparse},
        "labels": np.zeros(4, np.int32),
        "__dedup_ranking__": packer.last_ranking,
    }
    out = ranked_s.attach(batch)
    assert "__dedup_ranking__" not in out
    assert "sparse" not in out["features"]
    twin = plain_s.attach({
        "features": {"dense": np.zeros((4, 13), np.float32),
                     "sparse": sparse},
        "labels": np.zeros(4, np.int32),
    })
    np.testing.assert_array_equal(
        out["features"]["slots"], twin["features"]["slots"]
    )


def test_store_rejects_raw_id_ranking():
    """A ranking over RAW per-field ids (the encoding the per-field
    vocabulary makes unsound) is refused loudly instead of silently
    mistranslating cache slots."""
    from elasticdl_tpu.data.wire import frequency_rank

    store, _ = _twin_stores()
    sparse = np.arange(NUM_FIELDS, dtype=np.int64)[None, :] + 100
    with pytest.raises(ValueError, match="field_disjoint_ids"):
        store.prepare(
            sparse, ranked=frequency_rank(sparse.reshape(-1))
        )


# ---- host tier ---------------------------------------------------------


@pytest.mark.parametrize("host_dtype", ["fp32", "int8"])
def test_host_tier_set_gather_round_trip(host_dtype):
    tier = HostTier({"emb": 4}, num_fields=2, host_dtype=host_dtype)
    rows, n_new = tier.assign(np.array([[1, 2], [3, 4]], np.int64))
    assert n_new == 4
    want = np.arange(16, dtype=np.float32).reshape(4, 4) / 7.0
    flat_rows = rows.reshape(-1)
    tier.set_rows(flat_rows, {"emb": want})
    got = tier.gather(flat_rows)["emb"]
    if host_dtype == "fp32":
        np.testing.assert_array_equal(got, want)
    else:
        # int8 per-row scales: bounded quantization error
        scale = np.abs(want).max(axis=1, keepdims=True) / 127.0
        assert np.all(np.abs(got - want) <= scale + 1e-7)


def test_host_tier_backfill_seeds_new_rows():
    tier = HostTier({"emb": 2}, num_fields=1)
    tier.set_backfill(
        lambda plane, fields, ids: np.stack(
            [ids.astype(np.float32), fields.astype(np.float32)], axis=1
        )
    )
    rows, _ = tier.assign(np.array([[41], [42]], np.int64))
    got = tier.gather(rows.reshape(-1))["emb"]
    np.testing.assert_array_equal(got[:, 0], [41.0, 42.0])


# ---- store + fake train state (device seam, sidecar, serving) ----------


CACHE_ROWS = 32
DIM = 4


def _fake_state(cache_rows=CACHE_ROWS, dim=DIM, fill=0.0):
    params = {
        "params": {
            "fm_embedding": {
                "embedding": jnp.full((cache_rows, dim), fill, jnp.float32)
            },
            "fm_linear": {
                "embedding": jnp.full((cache_rows, 1), fill, jnp.float32)
            },
        }
    }
    return TrainState(
        step=jnp.asarray(0, jnp.int32),
        params=params,
        opt_state=optax.adam(1e-3).init(params),
        model_state={},
    )


def _driven_store(perturb=1.0):
    """A store driven through two batches on a fake state, sized so the
    second batch evicts part of the first: afterwards the vocabulary
    holds known-but-cold rows alongside resident ones.  `perturb` is
    then added to the device cache tables — a stand-in for training, so
    resident rows' values visibly differ from the host tier's."""
    store = TieredStore(
        {"fm_embedding": DIM, "fm_linear": 1}, NUM_FIELDS, CACHE_ROWS
    )
    # deterministic, recognisable host values: the raw id in every lane
    store.host.set_backfill(
        lambda plane, fields, ids: np.repeat(
            ids.astype(np.float32)[:, None],
            store.planes[plane], axis=1,
        )
    )
    state = _fake_state()
    batches = [
        np.arange(NUM_FIELDS, dtype=np.int64)[None, :] + 100,
        np.arange(NUM_FIELDS, dtype=np.int64)[None, :] + 500,
    ]
    for sparse in batches:
        slots, plan = store.prepare(sparse)
        state = store.apply_plan(state, plan)
    if perturb:
        params = jax.tree.map(lambda t: t + perturb, state.params)
        state = state.replace(params=params)
    return store, state, batches


def test_apply_plan_scatters_admitted_values():
    store, state, batches = _driven_store(perturb=0.0)
    emb = np.asarray(
        state.params["params"]["fm_embedding"]["embedding"]
    )
    # batch-2 ids are resident; their cache slots carry the host-tier
    # value (the backfill writes the raw id into every lane)
    rows = store.host.lookup(batches[1]).reshape(-1)
    slot_of_row = {int(r): s for s, r in enumerate(store.cache.row_of)
                   if r >= 0}
    for raw_id, r in zip(batches[1].reshape(-1), rows):
        s = slot_of_row[int(r)]
        np.testing.assert_array_equal(
            emb[s], np.full(DIM, float(raw_id))
        )


def test_sidecar_round_trip_and_latest_row_values(tmp_path):
    store, state, batches = _driven_store()
    d = store_ckpt.save_sidecar(str(tmp_path), 7, store, state)
    assert store_ckpt.has_sidecar(str(tmp_path), 7)
    sidecar = store_ckpt.load_sidecar(str(tmp_path), 7)
    assert sidecar.meta["cache_rows"] == CACHE_ROWS
    assert sidecar.meta["vocab_rows"] == store.host.size == 2 * NUM_FIELDS
    fields, ids, rows = sidecar.vocab_arrays()
    assert set(ids.tolist()) == set(
        np.concatenate(batches, axis=0).reshape(-1).tolist()
    )
    # every vocabulary row's latest value survives: resident rows carry
    # the CACHE value (host value + the post-drive "training" perturb),
    # evicted rows carry the host value their eviction folded back
    latest = sidecar.latest_row_values("fm_embedding")
    assert latest.shape == (store.host.size, DIM)
    resident_rows = set(
        int(r) for r in sidecar.row_of[sidecar.row_of >= 0]
    )
    assert 0 < len(resident_rows) < store.host.size  # both kinds exist
    id_of_row = {int(r): int(i) for i, r in zip(ids, rows)}
    for r in range(store.host.size):
        want = float(id_of_row[r]) + (1.0 if r in resident_rows else 0.0)
        np.testing.assert_array_equal(latest[r], np.full(DIM, want))


def test_keep_max_prunes_sidecars_in_lockstep(tmp_path):
    """Keep-last-K rotates `.tiered/<step>/` sidecars together with the
    orbax step dirs and their manifests — a surviving step always has
    its sidecar, a rotated step never leaves one behind (docs/ONLINE.md
    "Checkpoints: cadence, keep-last-K, pinning")."""
    from elasticdl_tpu.common.save_utils import CheckpointSaver

    store, state, _ = _driven_store(perturb=0.0)
    ckpt = str(tmp_path / "ckpt")
    saver = CheckpointSaver(ckpt, keep_max=2, async_save=False)
    saver.attach_tiered_store(store)
    for i in range(1, 5):
        assert saver.save(
            state.replace(step=jnp.asarray(i, jnp.int32)), force=True
        )
    saver.wait_until_finished()
    assert set(saver._mngr.all_steps()) == {3, 4}
    for step in (1, 2):
        assert not store_ckpt.has_sidecar(ckpt, step)
    for step in (3, 4):
        assert store_ckpt.has_sidecar(ckpt, step)
    leftover = {
        n for n in os.listdir(os.path.join(ckpt, store_ckpt.SIDECAR_ROOT))
        if n.isdigit()
    }
    assert leftover == {"3", "4"}
    saver.close()


def test_migration_tiered_to_flat_and_back(tmp_path):
    cap = 1 << 12
    store, state, batches = _driven_store()
    store_ckpt.save_sidecar(str(tmp_path), 3, store, state)
    sidecar = store_ckpt.load_sidecar(str(tmp_path), 3)

    def hash_fn(fields, ids):
        return hash_rows(fields, ids, cap)

    templates = {
        "fm_embedding": np.full((cap, DIM), -1.0, np.float32),
        "fm_linear": np.full((cap, 1), -1.0, np.float32),
    }
    flat = store_ckpt.flat_tables_from_sidecar(sidecar, templates, hash_fn)
    assert flat["fm_embedding"].shape == (cap, DIM)
    # every vocabulary id landed its latest value on its flat hash row
    fields, ids, rows = sidecar.vocab_arrays()
    latest = sidecar.latest_row_values("fm_embedding")
    flat_rows = hash_fn(fields, ids)
    assert np.unique(flat_rows).size == flat_rows.size  # collision-free
    np.testing.assert_array_equal(
        flat["fm_embedding"][flat_rows], latest[rows]
    )
    # untouched flat rows keep the template init
    untouched = np.setdiff1d(np.arange(cap), flat_rows)[:5]
    np.testing.assert_array_equal(
        flat["fm_embedding"][untouched],
        np.full((untouched.size, DIM), -1.0),
    )

    # flat -> tiered: a fresh store lazily backfills from the flat tables
    store2 = TieredStore(
        {"fm_embedding": DIM, "fm_linear": 1}, NUM_FIELDS, CACHE_ROWS
    )
    store2.host.set_backfill(store_ckpt.flat_backfill(flat, hash_fn))
    sparse = batches[0]
    new_rows, _ = store2.host.assign(sparse)
    got = store2.host.gather(new_rows.reshape(-1))["fm_embedding"]
    want = flat["fm_embedding"][
        hash_fn(
            np.repeat(
                np.arange(NUM_FIELDS)[None, :], sparse.shape[0], 0
            ).reshape(-1),
            sparse.reshape(-1),
        )
    ]
    np.testing.assert_array_equal(got, want)


def test_fill_matching_copies_dense_skips_mismatched_arenas():
    template = {
        "params": {
            "dense0": {"kernel": np.zeros((3, 2), np.float32)},
            "fm_embedding": {"embedding": np.zeros((4, 2), np.float32)},
        }
    }
    raw = {
        "params": {
            "dense0": {"kernel": np.ones((3, 2), np.float64)},
            # flat arena: different shape than the tiered cache table
            "fm_embedding": {"embedding": np.ones((16, 2), np.float32)},
        }
    }
    out = store_ckpt.fill_matching(template, raw)
    np.testing.assert_array_equal(
        out["params"]["dense0"]["kernel"], np.ones((3, 2))
    )
    assert out["params"]["dense0"]["kernel"].dtype == np.float32
    np.testing.assert_array_equal(
        out["params"]["fm_embedding"]["embedding"], np.zeros((4, 2))
    )


# ---- exact parity vs the flat arena (the tentpole claim) ---------------


@pytest.fixture(scope="module")
def parity():
    """Flat and tiered DeepFM trained side by side on an all-hot,
    collision-free working set; the host tier is backfilled from the
    flat init so both runs share their step-0 state exactly."""
    from elasticdl_tpu.common.model_handler import get_model_spec
    from elasticdl_tpu.worker.trainer import Trainer

    cap, dim, cache_rows, ids_per_field, batch, steps = (
        1 << 13, 4, 1024, 8, 32, 3
    )
    rng = np.random.RandomState(7)
    cand = rng.randint(0, 1 << 22, size=(NUM_FIELDS, ids_per_field * 8))
    cand_rows = hash_rows(
        np.repeat(np.arange(NUM_FIELDS)[:, None], cand.shape[1], 1),
        cand, cap,
    )
    seen = set()
    sel = np.zeros((NUM_FIELDS, ids_per_field), np.int32)
    for f in range(NUM_FIELDS):
        picked = 0
        for j in range(cand.shape[1]):
            row = int(cand_rows[f, j])
            if row not in seen:
                seen.add(row)
                sel[f, picked] = cand[f, j]
                picked += 1
                if picked == ids_per_field:
                    break
        assert picked == ids_per_field

    def batch_at(step):
        brng = np.random.RandomState(1000 + step)
        pick = brng.randint(0, ids_per_field, (batch, NUM_FIELDS))
        return {
            "features": {
                "dense": brng.rand(batch, 13).astype(np.float32),
                "sparse": sel[np.arange(NUM_FIELDS)[None, :], pick],
            },
            "labels": brng.randint(0, 2, batch).astype(np.int32),
        }

    def trainer_for(model_def, model_params):
        spec = get_model_spec("model_zoo", model_def,
                              model_params=model_params)
        return spec, Trainer(
            model=spec.model, optimizer=spec.optimizer, loss_fn=spec.loss,
            param_sharding_fn=spec.param_sharding,
        )

    _, flat_tr = trainer_for(
        "deepfm.deepfm_functional_api.custom_model",
        f"vocab_capacity={cap};embed_dim={dim}",
    )
    _, tier_tr = trainer_for(
        "deepfm.deepfm_tiered.custom_model",
        f"cache_rows={cache_rows};embed_dim={dim}",
    )
    b0 = batch_at(0)
    flat_state = flat_tr.init_state(jax.random.PRNGKey(0), b0["features"])
    tier_state = tier_tr.init_state(
        jax.random.PRNGKey(0),
        {"dense": b0["features"]["dense"],
         "slots": np.zeros((batch, NUM_FIELDS), np.int32)},
    )
    flat_init = {
        name: np.array(
            flat_state.params["params"][name]["embedding"], np.float32
        )
        for name in ("fm_embedding", "fm_linear")
    }
    store = TieredStore(
        {"fm_embedding": dim, "fm_linear": 1}, NUM_FIELDS, cache_rows
    )
    store.host.set_backfill(
        lambda plane, fields, ids: flat_init[plane][
            hash_rows(fields, ids, cap)
        ]
    )
    tier_tr.tiered_store = store

    losses = []
    for step in range(steps):
        b = batch_at(step)
        flat_state, fl = flat_tr.train_on_batch(flat_state, b)
        tier_state, tl = tier_tr.train_on_batch(
            tier_state,
            store.attach({"features": dict(b["features"]),
                          "labels": b["labels"]}),
        )
        losses.append((float(jax.device_get(fl)),
                       float(jax.device_get(tl))))
    return {
        "flat_tr": flat_tr, "tier_tr": tier_tr,
        "flat_state": flat_state, "tier_state": tier_state,
        "store": store, "losses": losses, "batch_at": batch_at,
        "cap": cap, "dim": dim, "sel": sel,
    }


def test_parity_losses_bitwise_equal(parity):
    # the forward is bitwise the same program on the same admitted
    # values; the steps after the first also carry the backward, which
    # sums a row's duplicates as a tree over their positions in the
    # SORTED batch (layers/embedding.py: scatter_add_rows), and a cache
    # slot sorts elsewhere than its table row: a few ulps
    losses = parity["losses"]
    assert losses[0][0] == losses[0][1]
    for fl, tl in losses:
        assert fl == pytest.approx(tl, rel=4 * 2.0 ** -23)


def test_parity_trained_rows_bitwise_equal(parity):
    probe = parity["batch_at"](10_000)
    store = parity["store"]
    slots, _ = store.prepare(probe["features"]["sparse"])
    flat_emb = np.asarray(jax.device_get(
        parity["flat_state"].params["params"]["fm_embedding"]["embedding"]
    ))
    tier_emb = np.asarray(jax.device_get(
        parity["tier_state"].params["params"]["fm_embedding"]["embedding"]
    ))
    rows = hash_rows(
        np.arange(NUM_FIELDS)[None, :], probe["features"]["sparse"],
        parity["cap"],
    )
    # to a few ulps of the largest trained value (see the losses' test)
    np.testing.assert_allclose(
        flat_emb[rows], tier_emb[slots], rtol=0,
        atol=4 * 2.0 ** -23 * np.abs(flat_emb[rows]).max(),
    )


def test_parity_predict_within_few_ulp(parity):
    # predict compiles a SEPARATE program per model (different gather
    # table shapes -> different fusion order), so this path is allowed a
    # few ulp — the bitwise claim lives on the train path above
    probe = parity["batch_at"](10_001)
    store = parity["store"]
    slots, _ = store.prepare(probe["features"]["sparse"])
    flat_pred = np.asarray(jax.device_get(
        parity["flat_tr"].predict_on_batch(
            parity["flat_state"], probe["features"]
        )
    ))
    tier_pred = np.asarray(jax.device_get(
        parity["tier_tr"].predict_on_batch(
            parity["tier_state"],
            {"dense": probe["features"]["dense"], "slots": slots},
        )
    ))
    assert np.abs(flat_pred - tier_pred).max() <= 4 * np.finfo(np.float32).eps


# ---- serving -----------------------------------------------------------


@pytest.fixture(scope="module")
def tiered_serving(tmp_path_factory):
    from elasticdl_tpu.common.model_handler import get_model_spec
    from elasticdl_tpu.serving.engine import ServingEngine
    from elasticdl_tpu.store.serving import TieredServingEngine

    ckpt_dir = str(tmp_path_factory.mktemp("tiered_serving"))
    spec = get_model_spec(
        "model_zoo", "deepfm.deepfm_tiered.custom_model",
        model_params=f"cache_rows={CACHE_ROWS};embed_dim={DIM}",
    )
    store, state, batches = _driven_store()
    store_ckpt.save_sidecar(ckpt_dir, 1, store, state)

    feats = {
        "dense": np.zeros((2, 13), np.float32),
        "slots": np.zeros((2, NUM_FIELDS), np.int32),
        "cold_fm": np.zeros((2, NUM_FIELDS, DIM), np.float32),
        "cold_linear": np.zeros((2, NUM_FIELDS, 1), np.float32),
    }
    variables = dict(spec.model.init(jax.random.PRNGKey(0), feats))
    feature_spec = {
        k: {"shape": list(v.shape[1:]), "dtype": str(v.dtype)}
        for k, v in feats.items()
    }
    engine = ServingEngine(
        spec.model, variables, step=1, feature_spec=feature_spec,
        buckets=(4,),
    )
    tiered = TieredServingEngine(
        engine, ckpt_dir, 1,
        overlay_features={"fm_embedding": "cold_fm",
                          "fm_linear": "cold_linear"},
    )
    return {
        "engine": tiered, "ckpt_dir": ckpt_dir, "store": store,
        "state": state, "batches": batches, "variables": variables,
    }


def test_serving_translate_known_cold_and_unknown(tiered_serving):
    eng = tiered_serving["engine"]
    batches = tiered_serving["batches"]
    # batch 2 ids are resident; batch 1 ids partially evicted (cold);
    # huge ids were never seen by the trainer at all
    known_hot = batches[1]
    known_any = batches[0]
    unknown = np.full((1, NUM_FIELDS), 10**9, np.int64)
    slots_hot, ov_hot = eng.translate(known_hot)
    assert (slots_hot >= 0).all()
    assert not np.any(ov_hot["cold_fm"])
    slots_any, ov_any = eng.translate(known_any)
    cold = slots_any < 0
    assert cold.any()  # part of batch 1 was evicted by batch 2
    # cold KNOWN rows carry their host-tier value in the overlay
    got = ov_any["cold_fm"][cold]
    want = np.repeat(
        known_any[cold].astype(np.float32)[:, None], DIM, axis=1
    )
    np.testing.assert_array_equal(got, want)
    slots_u, ov_u = eng.translate(unknown)
    assert (slots_u == -1).all()
    assert not np.any(ov_u["cold_fm"])  # unknown id -> zeros (bias path)


def test_serving_predict_never_trained_id(tiered_serving):
    eng = tiered_serving["engine"]
    feats = {
        "dense": np.random.RandomState(0).rand(1, 13).astype(np.float32),
        "sparse": np.full((1, NUM_FIELDS), 987654321, np.int64),
    }
    preds, step = eng.predict(feats, 1)
    assert step == 1
    assert np.isfinite(np.asarray(preds)).all()


def test_hot_swap_zero_dropped_requests(tiered_serving):
    eng = tiered_serving["engine"]
    store = tiered_serving["store"]
    state = tiered_serving["state"]
    ckpt_dir = tiered_serving["ckpt_dir"]
    store_ckpt.save_sidecar(ckpt_dir, 2, store, state)

    feats = {
        "dense": np.zeros((1, 13), np.float32),
        "sparse": np.asarray(tiered_serving["batches"][1], np.int64)[:1],
    }
    errors = []
    stop = threading.Event()

    def hammer():
        while not stop.is_set():
            try:
                preds, step = eng.predict(feats, 1)
                assert step in (1, 2)
                assert np.isfinite(np.asarray(preds)).all()
            except Exception as exc:  # pragma: no cover - the assertion
                errors.append(exc)
                return

    t = threading.Thread(target=hammer)
    t.start()
    try:
        eng.swap(tiered_serving["variables"], 2)
    finally:
        stop.set()
        t.join(timeout=30)
    assert not errors
    assert eng.step == 2
    assert eng.swap_count == 1


def test_swap_without_sidecar_rejected_keeps_serving(tiered_serving):
    eng = tiered_serving["engine"]
    step_before = eng.step
    with pytest.raises(RuntimeError, match="no tiered sidecar"):
        eng.swap(tiered_serving["variables"], 99)
    assert eng.step == step_before  # current generation still serves
    feats = {
        "dense": np.zeros((1, 13), np.float32),
        "sparse": np.full((1, NUM_FIELDS), 3, np.int64),
    }
    preds, _ = eng.predict(feats, 1)
    assert np.isfinite(np.asarray(preds)).all()


# ---- the Local runner starts the store's threads -----------------------


def test_local_run_starts_store_background_threads(tmp_path):
    """Regression for the Local-path gotcha: client/api.py never calls
    Master.start(), so it must start the store's prefetch/fold threads
    itself — this asserts they actually ticked during a real run."""
    from elasticdl_tpu.client.main import main as cli_main
    from model_zoo.deepfm.data import write_dataset

    train_dir, _val_dir = write_dataset(
        str(tmp_path / "data"), n_train=512, n_val=64
    )
    rc = cli_main(
        [
            "train",
            "--model_zoo", "model_zoo",
            "--model_def", "deepfm.deepfm_tiered.custom_model",
            "--model_params", "cache_rows=2048;embed_dim=4",
            "--training_data", train_dir,
            "--distribution_strategy", "Local",
            "--num_epochs", "1",
            "--minibatch_size", "64",
            "--records_per_task", "128",
        ]
    )
    assert rc == 0
    store = sys.modules["deepfm.deepfm_tiered"]._LAST_STORE
    assert store is not None
    assert store.prefetch_ticks > 0, (
        "cold-miss prefetcher never ticked: the Local path did not "
        "start the store's background threads"
    )
    stats = store.stats()
    assert stats["growth_rows"] > 0
    assert stats["vocab_rows"] == stats["growth_rows"]
    assert stats["cold_gather_overlap_share"] > 0.0
    assert not store._started  # runner stopped the threads at job end


def test_local_multiworker_run_uses_deferred_planning(tmp_path):
    """The lifted num_workers>1 rejection, end to end: two feed
    producers over one tiered store via deferred planning (PERF.md §4).
    The deferred feed must still ship a complete feature structure —
    model.init sees a placeholder `slots` the trainer later overwrites —
    and every cold gather runs sync inside the step-serialized region
    (overlap share exactly 0, the honest attribution)."""
    from elasticdl_tpu.client.main import main as cli_main
    from model_zoo.deepfm.data import write_dataset

    train_dir, _val_dir = write_dataset(
        str(tmp_path / "data"), n_train=512, n_val=64
    )
    rc = cli_main(
        [
            "train",
            "--model_zoo", "model_zoo",
            "--model_def", "deepfm.deepfm_tiered.custom_model",
            "--model_params", "cache_rows=2048;embed_dim=4",
            "--training_data", train_dir,
            "--distribution_strategy", "Local",
            "--num_epochs", "1",
            "--minibatch_size", "64",
            "--records_per_task", "128",
            "--num_workers", "2",
        ]
    )
    assert rc == 0
    store = sys.modules["deepfm.deepfm_tiered"]._LAST_STORE
    assert store.deferred_prepare
    stats = store.stats()
    assert stats["growth_rows"] > 0
    # THIS store's, where `growth_rows` is a registry counter that an
    # earlier test's store may have raised: rows sit in the cache only
    # if the trainer planned and ran steps
    assert stats["cache_occupancy_rows"] > 0
    assert stats["hit_rate"] > 0.5
    assert stats["cold_gather_overlap_share"] == 0.0


# ---- int8 device cache / mesh seam / fused blocks (ISSUE 18) -----------


def _fake_state_int8(cache_rows=CACHE_ROWS, dim=DIM):
    """TrainState shaped like an int8 TieredDeepFM: zero fp32 carriers
    under "params", q8/scale planes under model_state["quantized"]."""
    base = _fake_state(cache_rows, dim, fill=0.0)
    quantized = {
        "fm_embedding": {"embedding": {
            "q8": jnp.zeros((cache_rows, dim), jnp.int8),
            "scale": jnp.ones((cache_rows, 1), jnp.float32),
        }},
        "fm_linear": {"embedding": {
            "q8": jnp.zeros((cache_rows, 1), jnp.int8),
            "scale": jnp.ones((cache_rows, 1), jnp.float32),
        }},
    }
    return base.replace(model_state={"quantized": quantized})


def test_int8_admission_round_trip_within_half_scale():
    """Admit fp32 rows into an int8 cache, read them back: per-element
    error is bounded by half the row's quantization bin (scale/2 with
    scale = max|row|/127), and the fp32 carrier rows stay zero."""
    from elasticdl_tpu.store import device as store_device

    state = _fake_state_int8()
    paths = {"fm_embedding": ("params", "fm_embedding", "embedding"),
             "fm_linear": ("params", "fm_linear", "embedding")}
    slots = np.array([3, 7, 11, 19], np.int32)
    rng = np.random.RandomState(0)
    values = {
        "fm_embedding": (rng.randn(4, DIM) * 3).astype(np.float32),
        "fm_linear": (rng.randn(4, 1) * 3).astype(np.float32),
    }
    state = store_device.apply_admissions(
        state, paths, slots, values, cache_dtype="int8"
    )
    got = store_device.read_rows(state, paths, slots, cache_dtype="int8")
    for name in paths:
        scale = np.abs(values[name]).max(axis=1, keepdims=True) / 127.0
        err = np.abs(got[name] - values[name])
        assert (err <= scale / 2 + 1e-7).all(), (name, err.max())
    carrier = np.asarray(
        state.params["params"]["fm_embedding"]["embedding"]
    )
    np.testing.assert_array_equal(carrier[slots], 0.0)


def test_int8_read_rows_requires_quantized_collection():
    from elasticdl_tpu.store import device as store_device

    state = _fake_state()  # fp32 state: no "quantized" collection
    paths = {"fm_embedding": ("params", "fm_embedding", "embedding")}
    with pytest.raises(ValueError, match="quantized"):
        store_device.read_rows(
            state, paths, np.array([0], np.int32), cache_dtype="int8"
        )


def test_fold_determinism_keyed_step_and_path():
    """The write-back's stochastic rounding is keyed on (step, plane
    path): same step folds identically across calls (the data-parallel
    replica contract), a different step or a different path draws a
    different rounding."""
    from elasticdl_tpu.layers.arena import fold_quantized_updates

    rows, dim = 8, DIM
    rng = np.random.RandomState(1)
    planes = {
        "q8": jnp.asarray(rng.randint(-127, 128, (rows, dim)), jnp.int8),
        "scale": jnp.asarray(
            rng.rand(rows, 1).astype(np.float32) + 0.01
        ),
    }
    # a fractional delta that cannot round exactly: the stochastic draw
    # decides each element, so differing keys are visible in the codes
    delta = jnp.asarray(
        (rng.rand(rows, dim).astype(np.float32) - 0.5) * 0.3
    )

    def fold(name, step):
        params = {"params": {name: {"embedding": delta}}}
        state = {"quantized": {name: {"embedding": dict(planes)}}}
        new_params, new_state = fold_quantized_updates(
            params, state, step
        )
        out = new_state["quantized"][name]["embedding"]
        # carrier zeroed for the next step
        np.testing.assert_array_equal(
            np.asarray(new_params["params"][name]["embedding"]), 0.0
        )
        return np.asarray(out["q8"])

    np.testing.assert_array_equal(fold("fm_embedding", 5),
                                  fold("fm_embedding", 5))
    assert (fold("fm_embedding", 5) != fold("fm_embedding", 6)).any()
    assert (fold("fm_embedding", 5) != fold("fm_linear", 5)).any()


def _driven_store_int8():
    """int8 twin of `_driven_store`: same two batches, quantized cache."""
    store = TieredStore(
        {"fm_embedding": DIM, "fm_linear": 1}, NUM_FIELDS, CACHE_ROWS,
        cache_dtype="int8",
    )
    store.host.set_backfill(
        lambda plane, fields, ids: np.repeat(
            ids.astype(np.float32)[:, None],
            store.planes[plane], axis=1,
        )
    )
    state = _fake_state_int8()
    batches = [
        np.arange(NUM_FIELDS, dtype=np.int64)[None, :] + 100,
        np.arange(NUM_FIELDS, dtype=np.int64)[None, :] + 500,
    ]
    for sparse in batches:
        slots, plan = store.prepare(sparse)
        state = store.apply_plan(state, plan)
    return store, state, batches


def test_int8_store_stats_and_sidecar_round_trip(tmp_path):
    store, state, batches = _driven_store_int8()
    stats = store.stats()
    assert stats["cache_dtype"] == "int8"
    # analytic value bytes: (dim + 4) per row per plane
    assert stats["device_cache_bytes"] == CACHE_ROWS * ((DIM + 4) + (1 + 4))
    store_ckpt.save_sidecar(str(tmp_path), 2, store, state)
    sidecar = store_ckpt.load_sidecar(str(tmp_path), 2)
    assert sidecar.cache_dtype == "int8"
    # raw planes ride in the sidecar; cache_values is their dequant view
    assert set(sidecar.cache_planes) == {"fm_embedding", "fm_linear"}
    from elasticdl_tpu.layers.arena import dequantize_rows_host

    planes = sidecar.cache_planes["fm_embedding"]
    assert planes["q8"].dtype == np.int8
    np.testing.assert_array_equal(
        sidecar.cache_values["fm_embedding"],
        dequantize_rows_host(planes["q8"], planes["scale"]),
    )
    # ids are small integers (<= 525): codes quantize within half a bin
    ids = batches[1].reshape(-1).astype(np.float32)
    rows = store.host.lookup(batches[1]).reshape(-1)
    slot_of_row = {int(r): s for s, r in enumerate(store.cache.row_of)
                   if r >= 0}
    vals = sidecar.cache_values["fm_embedding"]
    for raw, r in zip(ids, rows):
        err = np.abs(vals[slot_of_row[int(r)]] - raw)
        assert (err <= raw / 127.0 / 2 + 1e-6).all()


def test_sidecar_dtype_migration_raises_without_convert(tmp_path):
    """int8 sidecar into an fp32 store (and the reverse) must fail
    loudly unless the caller acknowledges the device values were
    migrated (save_utils passes convert=True after arena_convert)."""
    store8, state8, _ = _driven_store_int8()
    store_ckpt.save_sidecar(str(tmp_path), 1, store8, state8)
    sidecar = store_ckpt.load_sidecar(str(tmp_path), 1)
    assert sidecar.cache_dtype == "int8"

    fp32_twin = TieredStore(
        {"fm_embedding": DIM, "fm_linear": 1}, NUM_FIELDS, CACHE_ROWS
    )
    with pytest.raises(ValueError, match="dtype mismatch"):
        fp32_twin.load_sidecar_state(
            sidecar.host_state, sidecar.row_of, sidecar.score,
            cache_dtype=sidecar.cache_dtype,
        )
    fp32_twin.load_sidecar_state(
        sidecar.host_state, sidecar.row_of, sidecar.score,
        cache_dtype=sidecar.cache_dtype, convert=True,
    )
    np.testing.assert_array_equal(fp32_twin.cache.row_of, store8.cache.row_of)

    # reverse direction: fp32 sidecar into an int8 store
    store32, state32, _ = _driven_store(perturb=0.0)
    store_ckpt.save_sidecar(str(tmp_path), 9, store32, state32)
    side32 = store_ckpt.load_sidecar(str(tmp_path), 9)
    assert side32.cache_dtype == "float32"
    int8_twin = TieredStore(
        {"fm_embedding": DIM, "fm_linear": 1}, NUM_FIELDS, CACHE_ROWS,
        cache_dtype="int8",
    )
    with pytest.raises(ValueError, match="dtype mismatch"):
        int8_twin.load_sidecar_state(
            side32.host_state, side32.row_of, side32.score,
            cache_dtype=side32.cache_dtype,
        )
    int8_twin.load_sidecar_state(
        side32.host_state, side32.row_of, side32.score,
        cache_dtype=side32.cache_dtype, convert=True,
    )
    np.testing.assert_array_equal(
        int8_twin.cache.row_of, store32.cache.row_of
    )


def test_partition_plan_union_equals_unsharded_plan():
    """Mesh seam accounting: the per-device sub-plans are an exact,
    order-preserving partition of the parent plan — their union IS the
    unsharded plan, every slot lands on its owning device's block."""
    from elasticdl_tpu.store.cache import partition_plan

    cache_rows, shards = 64, 4
    cache = HotRowCache(cache_rows)
    plan1 = cache.plan(np.arange(60))
    plan2 = cache.plan(np.arange(40, 100))  # evicts + admits
    for plan in (plan1, plan2):
        subs = partition_plan(plan, shards, cache_rows)
        assert len(subs) == shards
        block = cache_rows // shards
        for d, sp in enumerate(subs):
            assert sp["device"] == d
            assert sp["slot_lo"] == d * block
            assert sp["slot_hi"] == (d + 1) * block
            for key in ("admit_slots", "evict_slots"):
                s = sp[key]
                assert ((s >= sp["slot_lo"]) & (s < sp["slot_hi"])).all()
        for kind in ("admit", "evict"):
            got_slots = np.concatenate(
                [sp[f"{kind}_slots"] for sp in subs]
            )
            got_rows = np.concatenate([sp[f"{kind}_rows"] for sp in subs])
            want_slots = getattr(plan, f"{kind}_slots")
            want_rows = getattr(plan, f"{kind}_rows")
            order = np.argsort(want_slots, kind="stable")
            np.testing.assert_array_equal(
                np.sort(got_slots), want_slots[order]
            )
            np.testing.assert_array_equal(
                got_rows[np.argsort(got_slots, kind="stable")],
                want_rows[order],
            )
    with pytest.raises(ValueError):
        partition_plan(plan1, 7, cache_rows)  # 64 % 7 != 0


def test_store_emits_sub_plans_when_mesh_sharded():
    store, _, _ = _driven_store(perturb=0.0)
    assert store.stats()["mesh_shards"] == 1
    store.set_mesh_shards(4)
    slots, plan = store.prepare(
        np.arange(NUM_FIELDS, dtype=np.int64)[None, :] + 900
    )
    assert plan.sub_plans is not None and len(plan.sub_plans) == 4
    assert sum(
        sp["admit_slots"].size for sp in plan.sub_plans
    ) == plan.admit_slots.size
    with pytest.raises(ValueError):
        store.set_mesh_shards(5)  # CACHE_ROWS=32 % 5 != 0
