"""Quantized embedding arena (ISSUE 9, docs/PERF.md "Quantized arena"):
int8 codes + per-row fp32 scales behind the same fused gather.

Covers the numerics (per-row round-trip error bound, stochastic-rounding
unbiasedness), exact fp32/int8 forward parity on integer rows, the
post-optimizer fold semantics (carrier zeroed, untouched rows
bit-stable), checkpoint dtype migration in BOTH directions plus the
clear `ArenaDtypeMismatch` error, manifest arena metadata, serving
(Predict through the dequantizing gather; `swap()` aval check covering
the scale plane), and the DeepFM convergence band at int8 per the
docs/CONVERGENCE.md protocol.
"""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from elasticdl_tpu.common.model_handler import get_model_spec
from elasticdl_tpu.common.save_utils import (
    ArenaDtypeMismatch,
    CheckpointSaver,
)
from elasticdl_tpu.layers.arena import (
    EmbeddingArena,
    dequantize_rows,
    fold_quantized_updates,
    quantize_rows,
    stochastic_round,
)
from elasticdl_tpu.worker.trainer import Trainer

FEATS = (("a", 64), ("b", 32))
DIM = 8


def _arena(arena_dtype):
    return EmbeddingArena(FEATS, DIM, arena_dtype=arena_dtype)


def _ids(seed=0, batch=16):
    rng = np.random.RandomState(seed)
    return {
        "a": rng.randint(0, 1 << 20, size=(batch,)).astype(np.int32),
        "b": rng.randint(0, 1 << 20, size=(batch, 3)).astype(np.int32),
    }


# ---- numerics -----------------------------------------------------------


def test_roundtrip_error_bounded_by_half_scale_per_row():
    rng = np.random.RandomState(0)
    table = rng.randn(96, DIM).astype(np.float32) * np.logspace(
        -3, 1, 96
    ).reshape(-1, 1).astype(np.float32)
    table[17] = 0.0  # all-zero row must round-trip exactly
    q8, scale = quantize_rows(table)
    assert q8.dtype == jnp.int8 and scale.shape == (96, 1)
    err = np.abs(np.asarray(dequantize_rows(q8, scale)) - table)
    # round-to-nearest: per-element error <= scale/2 for that row
    assert np.all(err <= np.asarray(scale) / 2 + 1e-7)
    np.testing.assert_array_equal(np.asarray(q8[17]), 0)
    assert float(scale[17, 0]) == 1.0


def test_stochastic_round_is_unbiased_and_integer_exact():
    x = jnp.full((4096,), 2.3, jnp.float32)
    rounded = np.stack([
        np.asarray(stochastic_round(x, jax.random.PRNGKey(k)))
        for k in range(8)
    ]).astype(np.float64)
    # E[floor(2.3 + U)] = 2.3; 8x4096 samples, sigma ~ 0.0025
    assert abs(rounded.mean() - 2.3) < 0.01
    assert set(np.unique(rounded)) <= {2.0, 3.0}
    # exact integers never move, whatever the key
    ints = jnp.arange(-127, 128, dtype=jnp.float32)
    np.testing.assert_array_equal(
        np.asarray(stochastic_round(ints, jax.random.PRNGKey(9))),
        np.asarray(ints, np.int8),
    )


def test_forward_parity_fp32_vs_int8_on_integer_rows():
    """With integer-valued rows and scale=1 the int8 path is EXACT, so
    fp32 and int8 arenas agree bit-for-bit on the same ids."""
    rows = sum(c for _, c in FEATS)
    codes = np.random.RandomState(1).randint(
        -127, 128, size=(rows, DIM)
    ).astype(np.int8)
    ids = _ids()
    fp32 = _arena("float32")
    v32 = fp32.init(jax.random.PRNGKey(0), ids)
    v32 = {"params": {"embedding": jnp.asarray(codes, jnp.float32)}}
    out32 = fp32.apply(v32, ids)

    q = _arena("int8")
    vq = q.init(jax.random.PRNGKey(0), ids)
    vq = {
        "params": {"embedding": jnp.zeros((rows, DIM), jnp.float32)},
        "quantized": {"embedding": {
            "q8": jnp.asarray(codes),
            "scale": jnp.ones((rows, 1), jnp.float32),
        }},
    }
    outq = q.apply(vq, ids)
    for name in out32:
        np.testing.assert_array_equal(
            np.asarray(out32[name]), np.asarray(outq[name])
        )


def test_int8_carrier_takes_the_fp32_arenas_backward(monkeypatch):
    """`_grad_tap`'s backward is the fp32 table's (`scatter_add_rows`):
    duplicates combined, the distinct rows walked in chunks, the share
    of distinct rows sown under the int8 arena's own path."""
    from elasticdl_tpu.layers import embedding

    monkeypatch.setattr(embedding, "CHUNK", 16)   # several trips
    ids = _ids(seed=4, batch=256)                 # 1,024 lookups, 96 rows
    weights = {
        name: jnp.asarray(np.random.RandomState(5).randn(
            *np.shape(value), DIM).astype(np.float32))
        for name, value in ids.items()
    }

    def grad_of(arena_dtype):
        arena = _arena(arena_dtype)
        variables = arena.init(jax.random.PRNGKey(0), ids)
        rest = {k: v for k, v in variables.items() if k != "params"}

        def loss(params):
            out, sown = arena.apply(
                {"params": params, **rest}, ids, mutable=["step_metrics"]
            )
            return sum(
                jnp.sum(out[name] * weights[name]) for name in out
            ), sown["step_metrics"]["distinct_rows_ratio"]

        grad, ratio = jax.grad(loss, has_aux=True)(variables["params"])
        return np.asarray(grad["embedding"]), float(ratio)

    g32, ratio32 = grad_of("float32")
    g8, ratio8 = grad_of("int8")
    rows = _arena("float32").arena_rows_host(
        {k: v.reshape(len(v), -1) for k, v in ids.items()}
    )
    assert ratio32 == ratio8 == pytest.approx(
        len(np.unique(rows)) / rows.size
    )
    assert len(np.unique(rows)) > 4 * 16
    np.testing.assert_array_equal(g8, g32)
    want = np.zeros(g32.shape, np.float64)
    np.add.at(
        want, rows.reshape(-1),
        np.concatenate([
            np.asarray(weights[name], np.float64).reshape(256, -1, DIM)
            for name, _ in FEATS
        ], axis=1).reshape(-1, DIM),
    )
    np.testing.assert_allclose(g32, want, atol=1e-5)


def test_bad_arena_dtype_rejected():
    with pytest.raises(ValueError, match="arena_dtype"):
        _arena("int4").init(jax.random.PRNGKey(0), _ids())


# ---- fold semantics -----------------------------------------------------


def test_fold_zeroes_carrier_and_keeps_untouched_rows_bit_stable():
    rows = sum(c for _, c in FEATS)
    rng = np.random.RandomState(2)
    q8, scale = quantize_rows(rng.randn(rows, DIM).astype(np.float32))
    delta = np.zeros((rows, DIM), np.float32)
    touched = [0, 5, 40]
    delta[touched] = rng.randn(len(touched), DIM) * 0.05
    params = {"params": {"arena": {"embedding": jnp.asarray(delta)}}}
    model_state = {
        "quantized": {"arena": {"embedding": {
            "q8": q8, "scale": scale,
        }}},
    }
    new_params, new_state = fold_quantized_updates(
        params, model_state, step=7
    )
    carrier = np.asarray(new_params["params"]["arena"]["embedding"])
    np.testing.assert_array_equal(carrier, 0.0)
    planes = new_state["quantized"]["arena"]["embedding"]
    mask = np.ones(rows, bool)
    mask[touched] = False
    np.testing.assert_array_equal(
        np.asarray(planes["q8"])[mask], np.asarray(q8)[mask]
    )
    np.testing.assert_array_equal(
        np.asarray(planes["scale"])[mask], np.asarray(scale)[mask]
    )
    # touched rows absorbed the delta to within stochastic-round error
    want = np.asarray(dequantize_rows(q8, scale))[touched] + delta[touched]
    got = np.asarray(
        dequantize_rows(planes["q8"], planes["scale"])
    )[touched]
    assert np.all(np.abs(got - want) <= np.asarray(planes["scale"])[touched]
                  + 1e-7)


def test_fold_is_identity_without_quantized_collection():
    params = {"params": {"w": jnp.ones((2, 2))}}
    model_state = {"batch_stats": {"m": jnp.zeros((2,))}}
    p2, s2 = fold_quantized_updates(params, model_state, step=0)
    assert p2 is params and s2 is model_state


def test_fold_is_deterministic_in_step_and_path():
    rows = sum(c for _, c in FEATS)
    rng = np.random.RandomState(3)
    q8, scale = quantize_rows(rng.randn(rows, DIM).astype(np.float32))
    delta = jnp.asarray(rng.randn(rows, DIM).astype(np.float32) * 0.03)
    params = {"params": {"arena": {"embedding": delta}}}
    state = {"quantized": {"arena": {"embedding": {
        "q8": q8, "scale": scale,
    }}}}
    a = fold_quantized_updates(params, state, step=11)[1]
    b = fold_quantized_updates(params, state, step=11)[1]
    c = fold_quantized_updates(params, state, step=12)[1]
    pa = a["quantized"]["arena"]["embedding"]["q8"]
    pb = b["quantized"]["arena"]["embedding"]["q8"]
    pc = c["quantized"]["arena"]["embedding"]["q8"]
    np.testing.assert_array_equal(np.asarray(pa), np.asarray(pb))
    assert np.any(np.asarray(pa) != np.asarray(pc))


# ---- training + checkpoint migration ------------------------------------

DEEPFM_SMALL = "vocab_capacity=4096;embed_dim=8;lr=0.01"


def _deepfm_trainer(arena_dtype):
    spec = get_model_spec(
        "model_zoo", "deepfm.deepfm_functional_api.custom_model",
        model_params=f"{DEEPFM_SMALL};arena_dtype='{arena_dtype}'",
    )
    trainer = Trainer(
        model=spec.model, optimizer=spec.optimizer, loss_fn=spec.loss,
        param_sharding_fn=spec.param_sharding,
    )
    return spec, trainer


def _criteo_batch(seed=0, batch=256):
    from model_zoo.deepfm.data import synthetic_criteo

    dense, sparse, labels = synthetic_criteo(batch, seed=seed)
    return {
        "features": {"dense": dense, "sparse": sparse},
        "labels": labels.astype(np.int32),
    }


def _trained_state(trainer, steps=3):
    state = trainer.init_state(
        jax.random.PRNGKey(0), _criteo_batch()["features"]
    )
    for i in range(steps):
        state, _ = trainer.train_on_batch(state, _criteo_batch(i))
    return state


def test_int8_deepfm_trains_and_carrier_stays_zero():
    _, trainer = _deepfm_trainer("int8")
    state = trainer.init_state(
        jax.random.PRNGKey(0), _criteo_batch()["features"]
    )
    batch = _criteo_batch(0)
    losses = []
    for _ in range(4):
        state, loss = trainer.train_on_batch(state, batch)
        losses.append(float(loss))
    assert losses[-1] < losses[0]  # repeated batch: loss must drop
    for leaf in jax.tree.leaves(state.params):
        arr = np.asarray(leaf)
        if arr.shape[:1] == (4096,):  # the arena carriers
            np.testing.assert_array_equal(arr, 0.0)
    assert "quantized" in state.model_state


def test_manifest_records_arena_dtype_and_plane_shapes(tmp_path):
    _, trainer = _deepfm_trainer("int8")
    state = _trained_state(trainer)
    saver = CheckpointSaver(str(tmp_path / "ckpt"), async_save=False)
    assert saver.save(state, force=True)
    saver.wait_until_finished()
    step = saver.latest_step()
    manifest = json.load(open(saver._manifest_path(step)))
    arena = manifest["arena"]
    assert arena["arena_dtype"] == "int8"
    assert arena["planes"]  # per-plane rows/dim/scale_shape recorded
    for info in arena["planes"].values():
        assert info["scale_shape"] == [info["rows"], 1]
    saver.close()


def test_dtype_mismatch_is_a_clear_error_not_an_aval_crash(tmp_path):
    _, trainer8 = _deepfm_trainer("int8")
    saver = CheckpointSaver(str(tmp_path / "ckpt"), async_save=False)
    saver.save(_trained_state(trainer8), force=True)
    saver.wait_until_finished()
    step = saver.latest_step()

    _, trainer32 = _deepfm_trainer("float32")
    template = trainer32.init_state(
        jax.random.PRNGKey(1), _criteo_batch()["features"]
    )
    with pytest.raises(ArenaDtypeMismatch, match="arena_convert"):
        saver.restore_step(step, template)
    # maybe_restore must surface the same error, not fall back silently
    with pytest.raises(ArenaDtypeMismatch):
        saver.maybe_restore(template)
    saver.close()


def test_checkpoint_from_before_the_model_sowed_step_metrics(tmp_path):
    """The share of distinct rows rides in `model_state`; a checkpoint
    written when DeepFM's was empty still restores, the scalars as
    `init` leaves them."""
    _, trainer = _deepfm_trainer("float32")
    state = _trained_state(trainer)
    assert set(state.model_state) == {"step_metrics"}
    saver = CheckpointSaver(str(tmp_path / "ckpt"), async_save=False)
    assert saver.save(state.replace(model_state={}), force=True)
    saver.wait_until_finished()
    saver.close()
    saver = CheckpointSaver(str(tmp_path / "ckpt"), async_save=False)
    restored = saver.maybe_restore(state)
    saver.close()
    assert int(restored.step) == int(state.step)
    assert jax.tree.leaves(restored.model_state) == [0.0]
    for got, want in zip(
        jax.tree.leaves(restored.params), jax.tree.leaves(state.params)
    ):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_checkpoint_migrates_int8_to_fp32(tmp_path):
    _, trainer8 = _deepfm_trainer("int8")
    state8 = _trained_state(trainer8)
    saver = CheckpointSaver(str(tmp_path / "ckpt"), async_save=False)
    saver.save(state8, force=True)
    saver.wait_until_finished()

    _, trainer32 = _deepfm_trainer("float32")
    template = trainer32.init_state(
        jax.random.PRNGKey(1), _criteo_batch()["features"]
    )
    restored = saver.restore_step(
        saver.latest_step(), template, arena_convert=True
    )
    assert restored is not None
    assert "quantized" not in restored.model_state
    # fp32 tables == dequantized planes (carrier is zero between steps)
    quant = state8.model_state["quantized"]
    for path in ("fm_embedding", "fm_linear"):
        planes = quant[path]["embedding"]
        want = np.asarray(
            dequantize_rows(planes["q8"], planes["scale"])
        )
        got = np.asarray(restored.params["params"][path]["embedding"])
        np.testing.assert_allclose(got, want, rtol=0, atol=1e-7)
    # the converted state trains on the fp32 trainer
    s2, loss = trainer32.train_on_batch(restored, _criteo_batch(9))
    assert np.isfinite(float(loss))
    saver.close()


def test_checkpoint_migrates_fp32_to_int8(tmp_path):
    _, trainer32 = _deepfm_trainer("float32")
    state32 = _trained_state(trainer32)
    saver = CheckpointSaver(str(tmp_path / "ckpt"), async_save=False)
    saver.save(state32, force=True)
    saver.wait_until_finished()

    _, trainer8 = _deepfm_trainer("int8")
    template = trainer8.init_state(
        jax.random.PRNGKey(1), _criteo_batch()["features"]
    )
    restored = saver.restore_step(
        saver.latest_step(), template, arena_convert=True
    )
    assert restored is not None
    quant = restored.model_state["quantized"]
    for path in ("fm_embedding", "fm_linear"):
        table = np.asarray(state32.params["params"][path]["embedding"])
        planes = quant[path]["embedding"]
        wq8, wscale = quantize_rows(table)
        np.testing.assert_array_equal(
            np.asarray(planes["q8"]), np.asarray(wq8)
        )
        np.testing.assert_allclose(
            np.asarray(planes["scale"]), np.asarray(wscale), rtol=1e-6
        )
        # carrier slot is the zero delta accumulator
        np.testing.assert_array_equal(
            np.asarray(restored.params["params"][path]["embedding"]), 0.0
        )
    s2, loss = trainer8.train_on_batch(restored, _criteo_batch(9))
    assert np.isfinite(float(loss))
    saver.close()


# ---- serving ------------------------------------------------------------


def test_serving_predicts_through_quantized_gather(tmp_path):
    from elasticdl_tpu.serving.engine import ServingEngine

    spec, trainer8 = _deepfm_trainer("int8")
    state8 = _trained_state(trainer8)
    saver = CheckpointSaver(str(tmp_path / "ckpt"), async_save=False)
    saver.save(state8, force=True)
    saver.wait_until_finished()
    saver.close()

    feats = _criteo_batch(3, batch=8)["features"]
    engine = ServingEngine.from_checkpoint(
        str(tmp_path / "ckpt"), spec, feats, buckets=(8,),
        precompile=False,
    )
    preds, step = engine.predict(feats, 8)
    assert preds.shape[0] == 8 and np.all(np.isfinite(preds))
    assert step == int(state8.step)
    # and it matches the trainer's own forward on the same state
    want = np.asarray(trainer8.predict_on_batch(state8, feats))
    np.testing.assert_allclose(preds, want, rtol=1e-5, atol=1e-6)


def test_serving_swap_aval_check_covers_scale_plane(tmp_path):
    from elasticdl_tpu.serving.engine import ServingEngine

    spec, trainer8 = _deepfm_trainer("int8")
    state8 = _trained_state(trainer8)
    saver = CheckpointSaver(str(tmp_path / "ckpt"), async_save=False)
    saver.save(state8, force=True)
    saver.wait_until_finished()
    saver.close()

    feats = _criteo_batch(3, batch=8)["features"]
    engine = ServingEngine.from_checkpoint(
        str(tmp_path / "ckpt"), spec, feats, buckets=(8,),
        precompile=False,
    )
    good = {**state8.params, **state8.model_state}
    engine.swap(good, step=int(state8.step) + 1)
    assert engine.step == int(state8.step) + 1

    # a scale plane with drifted shape/dtype must be rejected: the
    # compiled buckets bake the plane avals in
    bad = jax.tree.map(lambda x: x, good)
    planes = bad["quantized"]["fm_embedding"]["embedding"]
    planes["scale"] = jnp.squeeze(planes["scale"], axis=1)
    with pytest.raises(ValueError, match="swap rejected"):
        engine.swap(bad, step=int(state8.step) + 2)


def test_serving_dtype_mismatch_raises_without_convert(tmp_path):
    from elasticdl_tpu.serving.engine import ServingEngine

    spec8, trainer8 = _deepfm_trainer("int8")
    saver = CheckpointSaver(str(tmp_path / "ckpt"), async_save=False)
    saver.save(_trained_state(trainer8), force=True)
    saver.wait_until_finished()
    saver.close()

    spec32, _ = _deepfm_trainer("float32")
    feats = _criteo_batch(3, batch=8)["features"]
    with pytest.raises(ArenaDtypeMismatch):
        ServingEngine.from_checkpoint(
            str(tmp_path / "ckpt"), spec32, feats, buckets=(8,),
            precompile=False,
        )
    # with conversion the same fp32 config serves the int8 checkpoint
    engine = ServingEngine.from_checkpoint(
        str(tmp_path / "ckpt"), spec32, feats, buckets=(8,),
        precompile=False, arena_convert=True,
    )
    preds, _ = engine.predict(feats, 8)
    assert np.all(np.isfinite(preds))


# ---- convergence (docs/CONVERGENCE.md protocol) -------------------------


def test_deepfm_int8_converges_into_band():
    """The docs/CONVERGENCE.md DeepFM recipe with `arena_dtype='int8'`:
    fixed seeds, synthetic Criteo, final AUC inside the recorded fp32
    band (quantization noise at dim 16 sits far inside the [0.79, 0.86]
    tolerance; bench-measured delta vs fp32 is ~0.001)."""
    from model_zoo.common.metrics import auc
    from model_zoo.deepfm.data import synthetic_criteo

    spec = get_model_spec(
        "model_zoo", "deepfm.deepfm_functional_api.custom_model",
        model_params=(
            "vocab_capacity=262144;embed_dim=16;lr=0.005;"
            "arena_dtype='int8'"
        ),
    )
    trainer = Trainer(
        model=spec.model, optimizer=spec.optimizer, loss_fn=spec.loss,
        param_sharding_fn=spec.param_sharding,
    )
    bs, steps = 4096, 32
    dense, sparse, labels = synthetic_criteo(bs * steps, seed=0)
    state = trainer.init_state(
        jax.random.PRNGKey(0),
        {"dense": dense[:bs], "sparse": sparse[:bs]},
    )
    first = None
    vd, vs, vy = synthetic_criteo(16384, seed=1000)
    for i in range(steps):
        sl = slice(i * bs, (i + 1) * bs)
        state, _ = trainer.train_on_batch(state, {
            "features": {"dense": dense[sl], "sparse": sparse[sl]},
            "labels": labels[sl].astype(np.int32),
        })
        if i + 1 == 8:
            first = float(auc(vy, trainer.predict_on_batch(
                state, {"dense": vd, "sparse": vs}
            )))
    final = float(auc(vy, trainer.predict_on_batch(
        state, {"dense": vd, "sparse": vs}
    )))
    assert 0.79 <= final <= 0.86, (
        f"int8 DeepFM final AUC {final} outside the recorded band "
        "[0.79, 0.86] (docs/CONVERGENCE.md)"
    )
    assert final > first, "int8 DeepFM did not improve over training"
