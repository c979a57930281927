"""Both plain references against the zoo models they stand beside, at a
tiny size on the CPU in float32, where the two must agree to rounding:
1e-4 leaves room for f32 summation order and nothing else.  And the
read-back of a train step from Adam's moments against optax itself."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from benchmarks import adam_check, datagen, manifest, trees
from benchmarks.reference import bert, deepfm

TOL = 1e-4
CRITEO = {"num_dense": 13, "num_sparse": 26, "zipf_exponent": 1.5,
          "field_cardinalities": manifest.load_json(os.path.join(
              manifest.BENCH_DIR, "configs", "deepfm-criteo-kaggle.json"
          ))["dataset"]["field_cardinalities"]}
DEEPFM = {"vocab_capacity": 4096, "embed_dim": 16, "mlp_dims": [24, 16, 8]}
DEEPFM_ZOO = ("benchmarks/zoo", "deepfm_tower.custom_model",
              "vocab_capacity=4096;embed_dim=16;mlp_dims=[24, 16, 8];"
              "bf16=False")


def zoo_loss_and_grads(zoo, model_def, model_params, batch):
    from elasticdl_tpu.common.model_handler import get_model_spec

    spec = get_model_spec(
        os.path.join(manifest.ROOT, zoo), model_def,
        model_params=model_params,
    )
    variables = spec.model.init(jax.random.PRNGKey(3), batch["features"])

    def loss_of(params):
        return spec.loss(
            batch["labels"],
            spec.model.apply({"params": params}, batch["features"]),
        )

    loss, grads = jax.value_and_grad(loss_of)(variables["params"])
    return spec, variables["params"], loss, grads


def assert_close(got: dict, want: dict):
    assert set(got) == set(want)
    for name in want:
        assert adam_check.rel_l2(got[name], want[name]) < TOL, name


def criteo_batch(seed, rows):
    rng = datagen.rng_for(seed)
    return datagen.parse_criteo(
        datagen.criteo_records(rng, rows, CRITEO), CRITEO
    )


def test_criteo_ids_stay_inside_each_fields_cardinality():
    sparse = criteo_batch(2 ** 31 + 7, 4096)["features"]["sparse"]
    sizes = np.asarray(CRITEO["field_cardinalities"])
    assert sum(CRITEO["field_cardinalities"]) == 33762577
    assert (sparse >= 0).all() and (sparse < sizes[None, :]).all()
    assert len(np.unique(sparse[:, 8])) == 3          # a field of 3 values
    assert len(np.unique(sparse[:, 2])) > 200         # one of 10 million


def test_deepfm_reference_matches_the_zoo_model_at_the_configured_tower():
    batch = criteo_batch(2 ** 31 + 7, 96)
    spec, params, loss, grads = zoo_loss_and_grads(*DEEPFM_ZOO, batch)
    assert spec.model.mlp_dims == (24, 16, 8)
    assert spec.feed_bulk is not None     # the zoo module's own names
    features = batch["features"]
    want_loss, want = deepfm.loss_and_grads(
        deepfm.cut(params, features, DEEPFM), features, batch["labels"],
        DEEPFM,
    )
    assert abs(float(loss) - float(want_loss)) < TOL
    assert_close(deepfm.cut(grads, features, DEEPFM), want)
    # untouched rows of the system's table gradient are exactly zero
    rows, _ = deepfm.touched(features["sparse"], DEEPFM)
    table_grad = np.asarray(grads["fm_embedding"]["embedding"])
    untouched = np.setdiff1d(np.arange(4096), rows)
    assert not table_grad[untouched].any()


@pytest.mark.parametrize("rows, chunk", [(8, 8), (16, 4)])
def test_bert_reference_matches_the_zoo_model(rows, chunk, monkeypatch):
    monkeypatch.setattr(bert, "CHUNK", chunk)
    config = {"hidden_size": 32, "num_hidden_layers": 2,
              "num_attention_heads": 2, "intermediate_size": 64}
    data = {"seq_len": 16, "vocab_size": 100}
    rng = datagen.rng_for(5)
    batch = datagen.parse_tokens(
        datagen.token_records(rng, rows, data), data
    )
    _, params, loss, grads = zoo_loss_and_grads(
        "model_zoo", "bert.bert_finetune.custom_model",
        "hidden=32;num_layers=2;heads=2;mlp_dim=64;max_len=16;"
        "vocab_size=100;bf16=False", batch,
    )
    features = batch["features"]
    want_loss, want = bert.loss_and_grads(
        bert.cut(params, features, config), features, batch["labels"],
        config,
    )
    assert abs(float(loss) - float(want_loss)) < TOL
    assert_close(bert.cut(grads, features, config), want)


def test_the_tolerances_would_catch_a_dropped_term():
    """Without the FM second-order term the loss moves by more than
    LOSS_ATOL, so a system that dropped it would fail."""
    batch = criteo_batch(1, 256)
    _, params, _, _ = zoo_loss_and_grads(*DEEPFM_ZOO, batch)
    features = batch["features"]
    flat = {
        k: np.asarray(v) for k, v in
        deepfm.cut(params, features, DEEPFM).items()
    }
    flat["fm_embedding"] = flat["fm_embedding"] * 8.0
    full, _ = deepfm.loss_and_grads(flat, features, batch["labels"], DEEPFM)
    _, inverse = deepfm.touched(features["sparse"], DEEPFM)
    rest = trees.nested({
        k: jnp.asarray(v) for k, v in flat.items()
        if k not in deepfm.TABLES
    })
    without = deepfm.bce_with_logits(
        deepfm.forward(
            jnp.zeros_like(flat["fm_embedding"]),
            jnp.asarray(flat["fm_linear"]), rest, jnp.asarray(inverse),
            jnp.asarray(features["dense"]), DEEPFM,
        ) + deepfm.forward(
            jnp.asarray(flat["fm_embedding"]),
            jnp.asarray(flat["fm_linear"]), rest, jnp.asarray(inverse),
            jnp.asarray(features["dense"]), DEEPFM,
        ) * 0,
        jnp.asarray(batch["labels"]),
    )
    assert abs(float(full) - float(without)) > deepfm.LOSS_ATOL


@pytest.mark.parametrize("name, make, config", [
    ("adam", lambda: optax.adam(1e-3),
     {"optimizer": "adam", "learning_rate": 1e-3}),
    ("adamw", lambda: optax.adamw(2e-5, weight_decay=0.01),
     {"optimizer": "adamw", "learning_rate": 2e-5, "weight_decay": 0.01}),
])
def test_a_step_is_read_back_from_adams_moments(name, make, config):
    """Three optax steps; from the states around the third alone, the
    gradient comes back and the update follows in closed form."""
    rng = np.random.default_rng(7)
    params = {"w": jnp.asarray(rng.normal(size=(64, 8)), jnp.float32),
              "b": jnp.asarray(rng.normal(size=(8,)), jnp.float32)}
    optimizer = make()
    state = optimizer.init(params)
    for step in range(3):
        grads = jax.tree.map(
            lambda p: jnp.asarray(
                rng.normal(size=p.shape) * 10.0 ** -step, jnp.float32
            ), params,
        )
        before = (params, state)
        updates, state = optimizer.update(grads, state, params)
        params = optax.apply_updates(params, updates)
    h = adam_check.hyper(config)
    (params0, state0), moments = before, state[0]
    assert int(moments.count) == 3
    for leaf in params:
        got = adam_check.recovered_gradient(
            state0[0].mu[leaf], moments.mu[leaf], h["b1"]
        )
        assert adam_check.rel_l2(got, grads[leaf]) < 1e-4
        assert adam_check.excess(
            np.asarray(moments.nu[leaf]) - np.float32(h["b2"])
            * np.asarray(state0[0].nu[leaf]),
            np.float32(1 - h["b2"]) * np.square(got),
            1e-2, state0[0].nu[leaf],
        ) <= 1.0
        delta = adam_check.expected_delta(
            params0[leaf], moments.mu[leaf], moments.nu[leaf], 3, h
        )
        moved = np.asarray(params[leaf]) - np.asarray(params0[leaf])
        assert adam_check.excess(moved, delta, 1e-2, params0[leaf]) <= 1.0
        # and it tells another learning rate or a missing moment apart
        assert adam_check.excess(
            moved, 2.0 * delta, 1e-2, params0[leaf]
        ) > 10.0
    assert adam_check.cosine(
        {k: np.asarray(v) for k, v in grads.items()},
        {k: np.asarray(v) for k, v in grads.items()},
    ) == pytest.approx(1.0)
