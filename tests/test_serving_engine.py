"""ServingEngine: bucketed precompilation, export loading with signature
validation, request validation, atomic hot swap."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from elasticdl_tpu.common.export import export_model, load_exported
from elasticdl_tpu.common.model_handler import get_model_spec
from elasticdl_tpu.serving.engine import ServingEngine
from elasticdl_tpu.worker.trainer import TrainState

MODEL_DEF = "mnist.mnist_functional_api.custom_model"


@pytest.fixture(scope="module")
def spec():
    return get_model_spec("model_zoo", MODEL_DEF)


@pytest.fixture(scope="module")
def export_dir(spec, tmp_path_factory):
    x = np.random.RandomState(0).rand(2, 784).astype(np.float32)
    variables = dict(spec.model.init(jax.random.PRNGKey(0), x))
    params = {"params": variables.pop("params")}
    state = TrainState(
        step=jnp.asarray(11, jnp.int32), params=params,
        opt_state=spec.optimizer.init(params), model_state=variables,
    )
    out = str(tmp_path_factory.mktemp("serving_export"))
    export_model(state, spec, out, sample_features=x)
    return out


@pytest.fixture(scope="module")
def engine(spec, export_dir):
    return ServingEngine.from_export(export_dir, spec, buckets=(2, 8))


def test_export_meta_records_feature_signature(export_dir):
    meta = json.load(open(os.path.join(export_dir, "export_meta.json")))
    assert meta["features"] == {
        "features": {"shape": [784], "dtype": "float32"}
    }


def test_warmup_compiles_once_per_bucket(engine):
    assert engine.buckets == (2, 8)
    assert engine.compile_count == 2
    assert engine.step == 11


def test_no_recompile_across_request_sizes(spec, engine):
    x = np.random.RandomState(1).rand(8, 784).astype(np.float32)
    before = engine.compile_count
    for rows in (1, 2, 3, 5, 8):
        preds, step = engine.predict({"features": x[:rows]}, rows)
        assert preds.shape == (rows, 10)
        assert step == 11
        # padding never leaks into real rows
        ref = spec.model.apply(engine._variables, x[:rows])
        np.testing.assert_allclose(preds, np.asarray(ref), atol=1e-5)
    assert engine.compile_count == before


def test_oversized_batch_raises(engine):
    x = np.zeros((9, 784), np.float32)
    with pytest.raises(ValueError, match="exceeds largest bucket"):
        engine.predict({"features": x}, 9)


def test_validate_rejects_malformed_requests(engine):
    ok = {"features": np.zeros((1, 784), np.float32)}
    assert engine.validate(ok) is None
    assert "keys" in engine.validate({"dense": ok["features"]})
    assert "dtype" in engine.validate(
        {"features": np.zeros((1, 784), np.float64)}
    )
    assert "shape" in engine.validate(
        {"features": np.zeros((1, 42), np.float32)}
    )
    assert "0 rows" in engine.validate(
        {"features": np.zeros((0, 784), np.float32)}
    )


def test_swap_rejects_mismatched_tree(engine):
    bad = jax.tree.map(
        lambda a: np.zeros(a.shape[:-1] + (a.shape[-1] + 1,), a.dtype)
        if hasattr(a, "shape") and a.ndim else a,
        engine._variables,
    )
    with pytest.raises(ValueError, match="swap rejected"):
        engine.swap(bad, step=99)
    assert engine.step == 11


def test_swap_changes_outputs_without_recompile(spec, export_dir):
    local = ServingEngine.from_export(export_dir, spec, buckets=(4,))
    x = np.random.RandomState(2).rand(4, 784).astype(np.float32)
    before_preds, _ = local.predict({"features": x}, 4)
    compiles = local.compile_count
    doubled = jax.tree.map(lambda a: a * 2, local._variables)
    local.swap(doubled, step=12)
    after_preds, step = local.predict({"features": x}, 4)
    assert step == 12
    assert local.swap_count == 1
    assert local.compile_count == compiles  # same avals, no retrace
    assert not np.allclose(before_preds, after_preds)


def test_load_exported_rejects_feature_key_drift(export_dir):
    with pytest.raises(ValueError, match="drifted since export"):
        load_exported(
            export_dir, template={},
            expected_features=["dense", "sparse"],
        )


def test_from_export_rejects_signature_mismatch(spec, export_dir):
    wrong_sample = {
        "dense": np.zeros((1, 13), np.float32),
        "sparse": np.zeros((1, 26), np.int32),
    }
    with pytest.raises(ValueError, match="drifted since export"):
        ServingEngine.from_export(
            export_dir, spec, buckets=(2,),
            sample_features=wrong_sample,
        )


def test_packed_predict_payload_matches_native():
    """A Predict client may ship integer id planes uint24-packed
    (engine.packed_feature_spec, 3 B/id on the request instead of 4);
    the zoo model unpacks inside the jitted forward, so packed and
    native payloads must produce the same predictions.  They run as two
    separately compiled programs (different input dtypes), whose fusion
    and so float32 rounding order may differ: compared at a few float32
    ulps, not bitwise."""
    from elasticdl_tpu.common.export import feature_meta
    from elasticdl_tpu.data.wire import pack_int_to_uint24
    from elasticdl_tpu.serving.engine import packed_feature_spec

    spec = get_model_spec(
        "model_zoo", "deepfm.deepfm_functional_api.custom_model",
        model_params="vocab_capacity=4096;embed_dim=4",
    )
    rng = np.random.RandomState(0)
    sample = {
        "dense": rng.rand(2, 13).astype(np.float32),
        "sparse": rng.randint(0, 1 << 22, (2, 26)).astype(np.int32),
    }
    variables = dict(spec.model.init(jax.random.PRNGKey(0), sample))
    engine = ServingEngine(
        spec.model, variables, step=3,
        feature_spec=feature_meta(sample), buckets=(4,),
    )

    pspec = packed_feature_spec(engine.feature_spec)
    assert pspec["sparse"] == {"shape": [26, 3], "dtype": "uint8"}
    assert pspec["dense"] == engine.feature_spec["dense"]

    x = {
        "dense": rng.rand(3, 13).astype(np.float32),
        "sparse": rng.randint(0, 1 << 22, (3, 26)).astype(np.int32),
    }
    packed = {"dense": x["dense"],
              "sparse": pack_int_to_uint24(x["sparse"])}
    assert engine.validate(x) is None
    assert engine.validate(packed) is None
    # wrong packed width is still rejected
    bad = {"dense": x["dense"],
           "sparse": np.zeros((3, 26, 2), np.uint8)}
    assert "uint24" in engine.validate(bad)

    native_preds, _ = engine.predict(x, 3)
    packed_preds, _ = engine.predict(packed, 3)
    np.testing.assert_allclose(
        native_preds, packed_preds, rtol=1e-6, atol=1e-6
    )


def test_from_export_requires_signature_when_meta_lacks_one(
    spec, export_dir, tmp_path
):
    legacy = tmp_path / "legacy_export"
    legacy.mkdir()
    meta_path = os.path.join(export_dir, "export_meta.json")
    meta = json.load(open(meta_path))
    del meta["features"]
    (legacy / "export_meta.json").write_text(json.dumps(meta))
    (legacy / "params.msgpack").write_bytes(
        open(os.path.join(export_dir, "params.msgpack"), "rb").read()
    )
    with pytest.raises(ValueError, match="predates feature signatures"):
        ServingEngine.from_export(str(legacy), spec, buckets=(2,))
    # explicit sample_features unblocks a legacy export
    x = np.zeros((1, 784), np.float32)
    eng = ServingEngine.from_export(
        str(legacy), spec, buckets=(2,), sample_features=x,
    )
    assert eng.compile_count == 1
