"""The fused sparse path end to end (PR: arena + dedup'd wire):

- dedup wire format round-trips BIT-EXACT for arbitrary id streams
  (zipf-skewed, uniform, constant, huge-range fallback), padded or not;
- the sticky packer keeps consecutive batch shapes identical (the jit
  cache contract) without ever changing values;
- the fused EmbeddingArena is numerically IDENTICAL to per-feature
  DistributedEmbedding tables — forward vectors and backward
  gradients — via the arena_table_from_feature_tables bridge;
- the dedup'd feed produces the same model outputs as the compact feed
  (host hash + device reconstruction == device hash), bit for bit.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from elasticdl_tpu.data.wire import (
    DEDUP_ESCAPE,
    DedupPacker,
    is_packed_dedup,
    pack_rows_dedup,
    pad_dedup,
    unpack_rows_dedup,
)


def _unpack(packed):
    return np.asarray(unpack_rows_dedup(packed))


def _zipf_rows(rng, b, f, mod=50021):
    return (rng.zipf(1.3, size=(b, f)) % mod).astype(np.int32)


# ---- wire format property tests -------------------------------------------


@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize(
    "dist", ["zipf", "uniform", "constant", "huge_range"]
)
def test_pack_unpack_bit_exact(seed, dist):
    rng = np.random.RandomState(seed)
    b = int(rng.choice([1, 7, 253, 1000]))
    f = int(rng.choice([1, 3, 26]))
    if dist == "zipf":
        rows = _zipf_rows(rng, b, f)
    elif dist == "uniform":
        # mostly-unique: nearly every position escapes the uint8 plane
        rows = rng.randint(0, 1 << 20, size=(b, f)).astype(np.int32)
    elif dist == "constant":
        rows = np.full((b, f), 7, np.int32)  # zero escapes
    else:
        # id range past the bincount budget: exercises the np.unique
        # ranking fallback inside pack_rows_dedup
        rows = rng.randint(0, 1 << 28, size=(b, f)).astype(np.int32)
    packed = pack_rows_dedup(rows)
    assert is_packed_dedup(packed)
    np.testing.assert_array_equal(_unpack(packed), rows)


def test_pack_unpack_bit_exact_with_padding():
    rng = np.random.RandomState(3)
    rows = _zipf_rows(rng, 512, 26)
    exact = pack_rows_dedup(rows)
    padded = pad_dedup(
        exact,
        unique_pad=exact["unique"].shape[0] + 999,
        exc_pad=exact["exc_val"].shape[0] + 517,
    )
    np.testing.assert_array_equal(_unpack(padded), rows)


def test_escape_plane_is_actually_used_on_skewed_streams():
    """The property tests must cover both planes: verify a zipf batch
    big enough to overflow uint8 ranks really has escapes (else the
    exc_val path is dead code in this suite)."""
    rng = np.random.RandomState(4)
    rows = _zipf_rows(rng, 4096, 26)
    packed = pack_rows_dedup(rows)
    assert int((packed["inverse8"] == DEDUP_ESCAPE).sum()) > 0
    assert packed["exc_val"].shape[0] > 0


def test_sticky_packer_keeps_shapes_and_round_trips():
    """Consecutive batches must pack to IDENTICAL plane shapes (one jit
    program), while values still round-trip exactly."""
    packer = DedupPacker()
    shapes = set()
    for seed in range(5):
        rng = np.random.RandomState(100 + seed)
        rows = _zipf_rows(rng, 2048, 26)
        packed = packer.pack(rows)
        np.testing.assert_array_equal(_unpack(packed), rows)
        shapes.add(
            tuple((k, packed[k].shape) for k in sorted(packed))
        )
    assert len(shapes) == 1


# ---- packer ranking == store admission signal -----------------------------


def test_packer_ranking_matches_frequency_rank():
    """`DedupPacker.last_ranking` IS `frequency_rank` of the same flat
    batch — values, order, AND tie-breaks — across both ranking paths
    (bincount LUT and the huge-range np.unique fallback), and asking for
    the ranking changes no wire bytes.  The tiered store admits on this
    signal (HotRowCache.plan `ranked=`), so drift here would silently
    change which rows the cache pins."""
    from elasticdl_tpu.data.wire import frequency_rank

    packer = DedupPacker()
    for seed, big in [(0, False), (1, False), (2, True)]:
        rng = np.random.RandomState(40 + seed)
        if big:
            # id range past the bincount budget: np.unique fallback
            rows = rng.randint(0, 1 << 28, size=(257, 26)).astype(np.int64)
        else:
            rows = _zipf_rows(rng, 2048, 26)
        packed = packer.pack(rows)
        uniq, counts = packer.last_ranking
        exp_uniq, exp_counts = frequency_rank(rows.reshape(-1))
        np.testing.assert_array_equal(uniq, exp_uniq)
        np.testing.assert_array_equal(counts, exp_counts)
        assert int(counts.sum()) == rows.size
        # the ranking rides along without perturbing the wire struct
        assert is_packed_dedup(packed)
        np.testing.assert_array_equal(_unpack(packed), rows)


def test_field_disjoint_ids_is_a_per_field_bijection():
    """The store-admission encoding (`id * F + field`): raw ids that
    collide across fields encode to distinct values, the encoding is
    invertible, and malformed inputs are rejected."""
    from elasticdl_tpu.data.wire import field_disjoint_ids

    rng = np.random.RandomState(9)
    sparse = rng.randint(0, 1000, size=(64, 26)).astype(np.int32)
    enc = field_disjoint_ids(sparse)
    assert enc.dtype == np.int64 and enc.shape == sparse.shape
    np.testing.assert_array_equal(enc // 26, sparse)
    np.testing.assert_array_equal(
        enc % 26, np.broadcast_to(np.arange(26), sparse.shape)
    )
    # same raw id, different fields -> different encoded values
    same = np.full((4, 26), 7, np.int32)
    assert len(np.unique(field_disjoint_ids(same))) == 26
    with pytest.raises(ValueError):
        field_disjoint_ids(np.arange(4))
    with pytest.raises(ValueError):
        field_disjoint_ids(
            np.full((1, 26), np.iinfo(np.int64).max // 2, np.int64)
        )


# ---- arena vs per-feature numerical identity ------------------------------


def test_arena_matches_per_feature_tables_bit_exact():
    from elasticdl_tpu.layers.arena import (
        EmbeddingArena,
        arena_table_from_feature_tables,
    )
    from elasticdl_tpu.layers.embedding import DistributedEmbedding

    feats = (("a", 64), ("b", 128), ("c", 64))
    dim = 8
    rng = np.random.RandomState(0)
    ids = {
        name: rng.randint(0, 10000, size=(16,)).astype(np.int32)
        for name, _ in feats
    }

    # independent per-feature tables (each its own init)
    tables, per_feature_out, per_feature_grads = {}, {}, {}
    for i, (name, cap) in enumerate(feats):
        module = DistributedEmbedding(cap, dim, hash_input=True)
        params = module.init(jax.random.PRNGKey(i), ids[name])
        tables[name] = params["params"]["embedding"]
        per_feature_out[name] = module.apply(params, ids[name])

        def loss(p):
            vecs = module.apply(p, ids[name])
            return jnp.sum(vecs * vecs)

        per_feature_grads[name] = jax.grad(loss)(params)["params"][
            "embedding"
        ]

    arena = EmbeddingArena(feats, dim)
    arena_params = {
        "params": {
            "embedding": arena_table_from_feature_tables(feats, tables)
        }
    }
    arena_out = arena.apply(arena_params, ids)
    for name, _ in feats:
        np.testing.assert_array_equal(
            np.asarray(arena_out[name]),
            np.asarray(per_feature_out[name]),
        )

    # backward: the arena's single scatter-add must land each feature's
    # gradient in its own row range, identical to the isolated tables
    def arena_loss(p):
        vecs = arena.apply(p, ids)
        return sum(jnp.sum(v * v) for v in vecs.values())

    arena_grad = jax.grad(arena_loss)(arena_params)["params"]["embedding"]
    # to a few ulps, not bit for bit: the backward sums each run of equal
    # rows as a tree over its positions in the SORTED batch, and a row's
    # position differs between the two layouts
    offset = 0
    for name, cap in feats:
        np.testing.assert_allclose(
            np.asarray(arena_grad[offset:offset + cap]),
            np.asarray(per_feature_grads[name]),
            rtol=4 * 2.0 ** -23, atol=1e-7,
        )
        offset += cap


def test_arena_sows_the_share_of_distinct_rows_per_table():
    """Both wire paths (hashed on the device, prehashed on the host) sow
    distinct rows / looked-up rows; a (rows, 1) table keeps XLA's scalar
    scatter and sows nothing."""
    from elasticdl_tpu.layers.arena import EmbeddingArena

    feats = (("x", 32), ("y", 96))
    rng = np.random.RandomState(2)
    ids = {
        "x": (rng.zipf(1.5, size=(64,)) % 5000).astype(np.int32),
        "y": (rng.zipf(1.5, size=(64, 2)) % 5000).astype(np.int32),
    }
    assert "step_metrics" not in EmbeddingArena(feats, 1).init(
        jax.random.PRNGKey(0), ids
    )
    for dim in (16, 2):
        arena = EmbeddingArena(feats, dim)
        variables = arena.init(jax.random.PRNGKey(0), ids)
        rows = arena.arena_rows_host(
            {k: v.reshape(64, -1) for k, v in ids.items()}
        )
        want = len(np.unique(rows)) / rows.size
        assert want < 0.5
        _, sown = arena.apply(variables, ids, mutable=["step_metrics"])
        assert float(
            sown["step_metrics"]["distinct_rows_ratio"]
        ) == pytest.approx(want)
        _, sown = arena.apply(
            variables, rows, prehashed=True, mutable=["step_metrics"]
        )
        assert float(
            sown["step_metrics"]["distinct_rows_ratio"]
        ) == pytest.approx(want)


def test_arena_prehashed_matches_hashed_path():
    from elasticdl_tpu.layers.arena import EmbeddingArena

    feats = (("x", 32), ("y", 96))
    arena = EmbeddingArena(feats, 4)
    rng = np.random.RandomState(1)
    ids = {
        name: rng.randint(0, 5000, size=(8,)).astype(np.int32)
        for name, _ in feats
    }
    params = arena.init(jax.random.PRNGKey(0), ids)
    hashed = arena.apply(params, ids)
    rows = arena.arena_rows_host(ids)               # (8, 2) int32
    pre = arena.apply(params, rows, prehashed=True)
    np.testing.assert_array_equal(
        np.asarray(pre[:, 0]), np.asarray(hashed["x"])
    )
    np.testing.assert_array_equal(
        np.asarray(pre[:, 1]), np.asarray(hashed["y"])
    )


# ---- dedup feed == compact feed through the real model --------------------


def test_dedup_feed_matches_compact_feed_bit_exact():
    from model_zoo.deepfm import deepfm_functional_api as zoo

    n = 512
    rng = np.random.RandomState(5)
    dense = rng.rand(n, zoo.NUM_DENSE).astype(np.float32)
    sparse = (rng.zipf(1.4, size=(n, zoo.NUM_SPARSE)) % (1 << 22)).astype(
        np.int32
    )
    labels = rng.randint(0, 2, n).astype(np.uint8)
    buffer = b"".join(
        dense[i].tobytes() + sparse[i].tobytes() + bytes([labels[i]])
        for i in range(n)
    )
    sizes = [zoo.RECORD_BYTES] * n

    model = zoo.custom_model(vocab_capacity=4096, embed_dim=4)
    compact = zoo.feed_bulk_compact(buffer, sizes)
    zoo._DEDUP_PACKER = None      # fresh sticky caps for this test
    dedup = zoo.feed_bulk_dedup(buffer, sizes)

    assert is_packed_dedup(dedup["features"]["sparse"])
    np.testing.assert_array_equal(dedup["labels"], compact["labels"])

    params = model.init(jax.random.PRNGKey(0), compact["features"])
    out_compact = model.apply(params, compact["features"])
    out_dedup = model.apply(params, dedup["features"])
    # same bf16 dense, same table rows (host hash == device hash), same
    # float consumers: outputs must agree bit for bit
    np.testing.assert_array_equal(
        np.asarray(out_compact), np.asarray(out_dedup)
    )


def test_dedup_eval_path_replicates_side_planes():
    """predict_on_batch must place the dedup side planes replicated, not
    data-sharded: `starts` is (F,) = (26,) and does not divide the data
    axis — the eval path used to crash on exactly this (regression for
    the --wire_format dedup CLI eval task failure)."""
    from elasticdl_tpu.common.model_handler import get_model_spec
    from elasticdl_tpu.worker.trainer import Trainer
    from model_zoo.deepfm import deepfm_functional_api as zoo

    n = 256
    rng = np.random.RandomState(11)
    dense = rng.rand(n, zoo.NUM_DENSE).astype(np.float32)
    sparse = (rng.zipf(1.4, size=(n, zoo.NUM_SPARSE)) % (1 << 22)).astype(
        np.int32
    )
    labels = rng.randint(0, 2, n).astype(np.uint8)
    buffer = b"".join(
        dense[i].tobytes() + sparse[i].tobytes() + bytes([labels[i]])
        for i in range(n)
    )
    sizes = [zoo.RECORD_BYTES] * n

    spec = get_model_spec(
        "model_zoo", "deepfm.deepfm_functional_api.custom_model",
        model_params="vocab_capacity=4096;embed_dim=4",
    )
    # the feeds MUST come from the spec (get_model_spec loads the zoo as
    # its own module instance, so its DEDUP_VOCAB_CAPACITY is the one the
    # model_params set — the directly-imported `zoo` above still has the
    # default and would host-hash with the wrong capacity)
    compact = spec.feed_bulk_compact(buffer, sizes)
    spec.module._DEDUP_PACKER = None   # fresh sticky caps for this test
    dedup = spec.feed_bulk_dedup(buffer, sizes)
    assert is_packed_dedup(dedup["features"]["sparse"])

    trainer = Trainer(
        model=spec.model, optimizer=spec.optimizer, loss_fn=spec.loss,
        param_sharding_fn=spec.param_sharding,
    )
    state = trainer.init_state(
        jax.random.PRNGKey(0), compact["features"]
    )
    p_compact = trainer.predict_on_batch(state, compact["features"])
    p_dedup = trainer.predict_on_batch(state, dedup["features"])
    # the two feeds jit to different programs (device hash vs unique-row
    # gather), so fusion order may drift in the last ulp; bit-exactness
    # of the feed itself is asserted through model.apply above
    np.testing.assert_allclose(p_compact, p_dedup, rtol=2e-5, atol=1e-6)


def test_host_hash_replica_is_bit_exact():
    from model_zoo.deepfm import deepfm_functional_api as zoo
    from model_zoo.deepfm.deepfm_functional_api import field_offset_ids

    from elasticdl_tpu.layers.embedding import hash_ids

    rng = np.random.RandomState(6)
    sparse = rng.randint(
        -(1 << 20), 1 << 22, size=(64, zoo.NUM_SPARSE)
    ).astype(np.int32)
    host = zoo.hash_field_rows_host(sparse, 4096)
    device = np.asarray(
        hash_ids(field_offset_ids(jnp.asarray(sparse)), 4096, mix=True)
    )
    np.testing.assert_array_equal(host, device)


# ---- the forward's distinct-row route through the arena (PR 48) -----------


def _patched_route(monkeypatch, chunk=16, most=8, least=4):
    from elasticdl_tpu.layers import embedding

    monkeypatch.setattr(embedding, "CHUNK", chunk)
    monkeypatch.setattr(embedding, "_COMPACT_CHUNKS", most)
    monkeypatch.setattr(embedding, "_COMPACT_MIN_CHUNKS", least)


@pytest.mark.parametrize("dim", [1, 2, 16])
@pytest.mark.parametrize("wire", ["hashed", "prehashed"])
@pytest.mark.parametrize("values", [40, 5000])
def test_arena_on_the_route_is_the_plain_gather_to_the_bit(
    values, wire, dim, monkeypatch
):
    """Both wire paths, few values a field (the compact buffer) and many
    (the `cond`'s plain side): the vectors are the table's rows, pads
    zero, and the sown `lookup_compact` says which side ran."""
    from elasticdl_tpu.layers.arena import EmbeddingArena

    _patched_route(monkeypatch)
    feats = (("x", 256), ("y", 768))
    rng = np.random.RandomState(3)
    ids = {
        "x": rng.randint(0, values, size=(64,)).astype(np.int32),
        "y": rng.randint(0, values, size=(64, 2)).astype(np.int32),
    }
    if wire == "hashed":
        ids["y"][::7, 0] = -1                       # pads
    arena = EmbeddingArena(feats, dim)
    variables = arena.init(jax.random.PRNGKey(0), ids)
    table = np.asarray(variables["params"]["embedding"])
    clean = {k: np.where(v == -1, 0, v) for k, v in ids.items()}
    rows = arena.arena_rows_host(
        {k: v.reshape(64, -1) for k, v in clean.items()}
    )
    distinct = len(np.unique(rows))
    assert (distinct <= 128) == (values == 40)
    if wire == "hashed":
        out, sown = jax.jit(lambda v: arena.apply(
            v, ids, mutable=["step_metrics"]
        ))(variables)
        got = np.concatenate(
            [np.asarray(out[k]).reshape(64, -1, dim) for k in ("x", "y")],
            axis=1,
        )
        valid = np.concatenate(
            [(ids[k] != -1).reshape(64, -1) for k in ("x", "y")], axis=1
        )
    else:
        got, sown = jax.jit(lambda v: arena.apply(
            v, rows, prehashed=True, mutable=["step_metrics"]
        ))(variables)
        got, valid = np.asarray(got), np.ones(rows.shape, bool)
    want = np.where(valid[..., None], table[rows], 0.0).astype(np.float32)
    np.testing.assert_array_equal(got.view(np.int32), want.view(np.int32))
    assert float(sown["step_metrics"]["lookup_compact"]) == float(
        values == 40
    )
    assert float(sown["step_metrics"]["distinct_rows_ratio"]) == (
        pytest.approx(distinct / rows.size)
    )


def test_deepfm_step_sorts_its_ids_once_for_both_tables(monkeypatch):
    """`fm_embedding` and `fm_linear` look the same (B, 26) rows up: the
    compiled train step holds ONE sort of the ids, one of the run ends
    and one that carries the runs back, not one a table (the CPU's
    scalar scatter sorts nothing of its own)."""
    import optax

    from model_zoo.deepfm import deepfm_functional_api as zoo

    _patched_route(monkeypatch, chunk=64, most=104, least=4)
    model = zoo.custom_model(vocab_capacity=4096, embed_dim=16)
    rng = np.random.RandomState(0)
    features = {
        "dense": rng.rand(64, 13).astype(np.float32),
        "sparse": (rng.zipf(1.5, size=(64, 26)) % 1000).astype(np.int32),
    }
    labels = rng.randint(0, 2, size=64).astype(np.int32)
    params = model.init(jax.random.PRNGKey(0), features)["params"]
    optimizer = optax.adam(1e-3)

    def step(params, opt_state):
        def loss(p):
            out, sown = model.apply(
                {"params": p}, features, mutable=["step_metrics"]
            )
            return zoo.loss(labels, out), sown["step_metrics"]

        (value, sown), grads = jax.value_and_grad(loss, has_aux=True)(params)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, value, sown

    compiled = jax.jit(step).lower(params, optimizer.init(params)).compile()
    assert compiled.as_text().count(" sort(") == 3
    _, _, _, sown = compiled(params, optimizer.init(params))
    assert float(sown["fm_embedding"]["lookup_compact"]) == 1.0
    assert float(sown["fm_linear"]["lookup_compact"]) == 1.0
    assert float(sown["fm_linear"]["distinct_rows_ratio"]) == float(
        sown["fm_embedding"]["distinct_rows_ratio"]
    )


@pytest.mark.parametrize("metric,gauge", [
    ("arena_distinct_rows_share", "worker_arena_distinct_rows_ratio"),
    ("arena_lookup_compact_share", "worker_arena_lookup_compact_ratio"),
])
def test_the_benchmark_reads_the_lookups_gauges(metric, gauge):
    """The two data files of PR 48 under the manifest's own loader: the
    DeepFM cell reports both, by the reader that takes a gauge of the
    program's registry, under the layer's name."""
    from benchmarks import manifest
    from elasticdl_tpu.common import metrics as metrics_lib
    from elasticdl_tpu.worker import worker  # noqa: F401  (the gauges)

    cell = manifest.resolve_cell(
        manifest.load_manifest(), "deepfm-criteo-kaggle.train-stream"
    )
    (entry,) = [m for m in cell.per_layer if m["name"] == metric]
    spec = manifest.load_layer_metric(cell, metric)
    assert spec["name"] == metric and spec["reader"] == "registry_gauge"
    assert spec["layer"] == entry["layer"] == "kernels, sparse"
    assert spec["moves"] == entry["moves"] == "train_examples_per_s"
    assert entry["workloads"] == ["deepfm-criteo-kaggle.train-stream"]
    assert spec["params"] == {"metric": gauge, "stat": "mean"}
    reader = manifest.import_by_name("readers", spec["reader"])
    family = metrics_lib.default_registry().gauge(gauge, labelnames=("table",))
    family.labels(table="a").set(1.0)
    family.labels(table="b").set(0.0)
    values = list(family.child_values().values())
    assert {0.0, 1.0} <= set(values)
    assert reader.read(spec["params"], {}) == sum(values) / len(values)
