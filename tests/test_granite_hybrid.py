"""The granite hybrid decoder (model_zoo/granite/granite_hybrid.py) at tiny
widths on the CPU, seeded weights: Mamba-2 blocks (the biased SiLU conv
over x | B | C, the scalar-decay scan, the gate before one norm over all
channels) and a grouped-query attention block without positions at the
configuration's scale, the gated MLP in every block, the four multipliers
and the tied head against the plain float32 reference leaf by leaf (its
Mamba-2 the token-by-token recurrence), through the jnp forms and through
the interpreted kernels, controls that each part of the mathematics must
fail, bfloat16 inside the twin's rule, the sown gauge through the
Trainer, the published sizes' parameter count, and a two-task job through
the CLI."""

import functools
import json
import os
import threading
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import datagen, trees
from benchmarks.reference import granite_hybrid as reference
from elasticdl_tpu.layers.moe import ROUTER_STATE
from elasticdl_tpu.layers.step_metrics import AUX_LOSS, STEP_METRICS
from elasticdl_tpu.ops import short_conv
from elasticdl_tpu.ops import ssd as ssd_ops
from model_zoo.common import decoder, mamba
from model_zoo.granite import granite_hybrid as zoo
from tests import remat_cases

ROOT = os.path.join(os.path.dirname(__file__), "..")
# the published pattern's first period cut to five layers (attention at
# the third): 4 state-space heads of 8 over 16 state columns, 4 query
# heads over 2 K/V heads of 8
CONFIG = dict(
    hidden_size=32, num_hidden_layers=5,
    layer_types=["mamba", "mamba", "attention", "mamba", "mamba", "mamba"],
    layers_held=[0, 1, 2, 3, 4], num_attention_heads=4,
    num_key_value_heads=2, mamba_n_heads=4, mamba_d_head=8,
    mamba_d_state=16, mamba_n_groups=1, mamba_d_conv=4,
    shared_intermediate_size=48, vocab_size=50, rms_norm_eps=1e-5,
    embedding_multiplier=12, attention_multiplier=0.015625 * 8,
    residual_multiplier=0.22, logits_scaling=8, use_bf16=True,
)
MUTABLE = [AUX_LOSS, STEP_METRICS, ROUTER_STATE]
MAMBA_LEAVES, ATTENTION_LEAVES = 8, 4


def model_of(config, **overrides):
    sizes = dict(
        hidden=config["hidden_size"], layer_types=config["layer_types"],
        layers=config["layers_held"], heads=config["num_attention_heads"],
        kv_heads=config["num_key_value_heads"],
        mamba_heads=config["mamba_n_heads"],
        mamba_head_dim=config["mamba_d_head"],
        mamba_state=config["mamba_d_state"],
        mamba_groups=config["mamba_n_groups"],
        conv_kernel=config["mamba_d_conv"],
        dense_width=config["shared_intermediate_size"],
        embedding_multiplier=config["embedding_multiplier"],
        attention_multiplier=config["attention_multiplier"],
        residual_multiplier=config["residual_multiplier"],
        logits_scaling=config["logits_scaling"],
        vocab_size=config["vocab_size"], eps=config["rms_norm_eps"],
        remat=True,
    )
    sizes.update(overrides)
    return zoo.custom_model(**sizes)


def ids_of(rows, length=80, seed=0):
    return np.random.RandomState(seed).randint(
        0, CONFIG["vocab_size"], (rows, length)
    ).astype(np.int32)


def loss_and_grads(model, variables, ids, room=None):
    """The objective the Trainer builds: the mean of the model's
    per-position losses (this model sows no auxiliary loss)."""
    state = {k: v for k, v in variables.items() if k != "params"}

    def loss_of(params):
        out, _ = model.apply(
            {"params": params, **state}, {"input_ids": ids}, mutable=MUTABLE,
            **({} if room is None else {"room": room}),
        )
        return zoo.loss(None, out.astype(jnp.float32))

    with jax.default_matmul_precision("highest"):
        loss, grads = jax.value_and_grad(loss_of)(variables["params"])
    return float(loss), {
        k: np.asarray(v, np.float32) for k, v in trees.flat(grads).items()
    }


def seeded_of(config, ids):
    model = model_of(config)
    variables = model.init(jax.random.PRNGKey(0), {"input_ids": ids})
    flat = {
        k: np.asarray(v) for k, v in trees.flat(variables["params"]).items()
    }
    want_loss, want = reference.loss_and_grads(
        flat, {"input_ids": ids}, None, config
    )
    return types.SimpleNamespace(
        ids=ids, variables=variables, flat=flat, want_loss=want_loss,
        want={k: np.asarray(v) for k, v in want.items()},
    )


@pytest.fixture(scope="module")
def seeded():
    # 80 positions: the scan's jnp form pads them to one chunk of 256.
    # `A_log`, `D` and `dt_bias` hold ONE number a head: with four heads
    # a leaf's error against the twin's is the ratio of a few draws, not
    # an average over a leaf, and under the twins' rule the worst of them
    # reads 0.7-0.8 on seeds 4 and 5 and 1.0-1.5 on seeds 0-3, 6 and 7,
    # where every other leaf reads under 0.5 (the cell is held to shares
    # of a leaf's norm, `reference.LEAF_REL_L2`, not to the twin)
    return seeded_of(CONFIG, ids_of(8, seed=5))


def worst_leaf(got, want):
    assert set(got) == set(want)
    errors = {
        name: np.linalg.norm(got[name] - ref) / np.linalg.norm(ref)
        for name, ref in want.items()
    }
    name = max(errors, key=errors.get)
    return name, errors[name]


def test_float32_matches_reference_leaf_by_leaf(seeded):
    model = model_of(CONFIG)
    assert list(model.config.layers) == [
        zoo.MAMBA, zoo.MAMBA, zoo.ATTENTION, zoo.MAMBA, zoo.MAMBA,
    ]
    loss, got = loss_and_grads(model, seeded.variables, seeded.ids)
    assert abs(loss - seeded.want_loss) < 1e-4 * abs(seeded.want_loss)
    # a Mamba-2 mixer's 8 leaves (in_proj, taps and their bias, A_log,
    # dt_bias, D, the gated norm, out_proj), attention's 4, two norms and
    # the MLP's two kernels a block, the tied table and the final norm
    assert len(got) == (
        4 * (MAMBA_LEAVES + 4) + (ATTENTION_LEAVES + 4) + 2
    )
    assert "lm_head_kernel" not in got
    name, error = worst_leaf(got, seeded.want)
    assert error < 1e-4, (name, error)


def test_the_tied_table_carries_both_gradients(seeded, monkeypatch):
    """The table is ONE leaf read twice: with the lookup's road cut
    (`stop_gradient` on the looked-up rows) what is left is the head's
    part, which reaches every row; the rest is the lookup's, which reaches
    only the rows the batch names; the reference's leaf is their sum."""
    ids = seeded.ids % 40                          # rows 40..49 unseen
    want = reference.loss_and_grads(
        seeded.flat, {"input_ids": ids}, None, CONFIG
    )[1]["token_embedding/embedding"]
    name = "token_embedding/embedding"
    whole = loss_and_grads(model_of(CONFIG), seeded.variables, ids)[1][name]

    class LookupCut(zoo.DistributedEmbedding):
        def __call__(self, ids):
            return jax.lax.stop_gradient(super().__call__(ids))

    monkeypatch.setattr(zoo, "DistributedEmbedding", LookupCut)
    head = loss_and_grads(model_of(CONFIG), seeded.variables, ids)[1][name]
    lookup = whole - head
    assert np.abs(head).max(axis=1).min() > 0.0    # every row, as a column
    assert not lookup[40:].any()
    assert np.abs(lookup[np.unique(ids)]).max(axis=1).min() > 0.0
    assert np.linalg.norm(lookup) > 0.1 * np.linalg.norm(head)
    assert np.linalg.norm(whole - want) < 1e-4 * np.linalg.norm(want)


def test_kernels_match_reference_leaf_by_leaf():
    """Two state-space heads of 64 over 128 state columns at 512
    positions (two chunks: the state crosses a boundary), the biased SiLU
    conv at 384 columns and the streaming attention at heads of 64, all
    interpreted here."""
    from elasticdl_tpu.ops.flash_attention import stream_shapes_ok

    config = dict(
        CONFIG, hidden_size=128, mamba_n_heads=2, mamba_d_head=64,
        mamba_d_state=128, num_attention_heads=2, num_key_value_heads=1,
        layers_held=[1, 2], num_hidden_layers=2,
        attention_multiplier=0.125,
    )
    assert ssd_ops.ssd_shapes_ok((1, 512, 2, 64), (1, 512, 1, 128))
    assert short_conv.silu_conv_shapes_ok((1, 512, 384), (4, 384), True)
    assert stream_shapes_ok((1, 512, 2, 64), (1, 512, 1, 64),
                            (1, 512, 1, 64))
    seeded = seeded_of(config, ids_of(1, length=512, seed=2))
    loss, got = loss_and_grads(model_of(config), seeded.variables, seeded.ids)
    assert abs(loss - seeded.want_loss) < 1e-4 * abs(seeded.want_loss)
    name, error = worst_leaf(got, seeded.want)
    assert error < 2e-4, (name, error)


def _rotated(monkeypatch):
    """A rotary turn of q and k that the published model does not have."""
    from model_zoo.common.decoder import rotary

    plain = decoder.flash_attention.causal_attention
    monkeypatch.setattr(
        decoder.flash_attention, "causal_attention",
        lambda q, k, v, scale, window=None: plain(
            rotary(q, 1e4), rotary(k, 1e4), v, scale=scale
        ),
    )


def _norm_before_gate(monkeypatch):
    from model_zoo.common.decoder import rms_norm

    class NormThenGate(mamba.GatedRMSNorm):
        @zoo.nn.compact
        def __call__(self, y, z):
            scale = self.param(
                "scale", zoo.nn.initializers.ones, (y.shape[-1],)
            )
            return (
                rms_norm(y, scale, self.eps) * jax.nn.silu(z)
            ).astype(self.dtype)

    monkeypatch.setattr(mamba, "GatedRMSNorm", NormThenGate)


def _no_conv_bias(monkeypatch):
    plain = mamba.silu_short_conv
    monkeypatch.setattr(
        mamba, "silu_short_conv", lambda u, w, b: plain(u, w)
    )


def _one_tap_dropped(monkeypatch):
    plain = mamba.silu_short_conv
    monkeypatch.setattr(
        mamba, "silu_short_conv",
        lambda u, w, b: plain(u, w.at[0].set(0.0), b),
    )


CONTROLS = {
    "embedding_multiplier_1": dict(embedding_multiplier=1.0),
    "attention_scale_rsqrt_d": dict(attention_multiplier=8 ** -0.5),
    "attention_multiplier_1": dict(attention_multiplier=1.0),
    "residual_multiplier_1": dict(residual_multiplier=1.0),
    "logits_scaling_1": dict(logits_scaling=1.0),
    "conv_bias_dropped": _no_conv_bias,
    "norm_before_gate": _norm_before_gate,
    "rotary_applied": _rotated,
    "one_tap_dropped": _one_tap_dropped,
}


@pytest.mark.parametrize("control", sorted(CONTROLS))
def test_a_departure_from_the_mathematics_fails_the_comparison(
        seeded, monkeypatch, control):
    """The comparison that passes the model fails each of these: every
    multiplier at 1, the softmax scale D^-1/2, the conv's bias dropped,
    the norm before the gate, a rotary applied, one tap dropped."""
    change = CONTROLS[control]
    overrides = change if isinstance(change, dict) else {}
    if not overrides:
        change(monkeypatch)
    loss, got = loss_and_grads(
        model_of(CONFIG, **overrides), seeded.variables, seeded.ids
    )
    name, error = worst_leaf(got, seeded.want)
    assert (
        abs(loss - seeded.want_loss) > 1e-3 * abs(seeded.want_loss)
        or error > 1e-2
    ), (control, loss, seeded.want_loss, name, error)


def test_each_part_of_the_reference_is_seen(seeded):
    """The reference is held to the model above; this holds it to the
    configuration: the decay's A, the skip D, the step's bias, the conv's
    bias and another layer list each move what is computed."""
    features = {"input_ids": seeded.ids}

    def loss_with(config=CONFIG, **leaves):
        return reference.loss_and_grads(
            {**seeded.flat, **leaves}, features, None, config
        )[0]

    for leaf in ("layer_1/mamba/A_log", "layer_0/mamba/D",
                 "layer_3/mamba/dt_bias", "layer_4/mamba/conv_bias",
                 "layer_0/mamba/norm/scale"):
        assert abs(
            loss_with(**{leaf: seeded.flat[leaf] + np.log(2.0)})
            - seeded.want_loss
        ) > 1e-6, leaf
    # published layer 5 (mamba) in layer 4's place is layer 4 again; an
    # attention layer in a mamba layer's place finds no attention weights
    assert loss_with(dict(CONFIG, layers_held=[0, 1, 2, 3, 5])) == (
        pytest.approx(seeded.want_loss, abs=1e-7)
    )
    with pytest.raises(KeyError):
        loss_with(dict(CONFIG, layers_held=[0, 2, 2, 3, 4]))
    # attention is causal and knows no position: a prefix alone gives the
    # prefix's rows, and a token that sees only copies of itself gives
    # the same row at position 1 as at position 0
    sizes = reference.sizes_of(CONFIG, None)
    p = trees.nested(seeded.flat)["layer_2"]["attn"]
    x = jnp.asarray(np.random.RandomState(3).randn(12, 32), jnp.float32)
    with jax.default_matmul_precision("highest"):
        whole = reference.attention(x, p, sizes, lambda t: t)
        early = reference.attention(x[:5], p, sizes, lambda t: t)
        doubled = reference.attention(
            jnp.concatenate([x[:1], x[:5]]), p, sizes, lambda t: t
        )
    np.testing.assert_allclose(whole[:5], early, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(doubled[1], doubled[0], rtol=1e-5, atol=1e-6)


def test_no_multiplier_has_a_default():
    with pytest.raises(TypeError):
        zoo.custom_model()
    for name in ("embedding_multiplier", "attention_multiplier",
                 "residual_multiplier", "logits_scaling"):
        sizes = dict(
            embedding_multiplier=12, attention_multiplier=0.015625,
            residual_multiplier=0.22, logits_scaling=8,
        )
        del sizes[name]
        with pytest.raises(TypeError, match=name):
            zoo.custom_model(**sizes)
    with pytest.raises(ValueError):
        model_of(CONFIG, layer_types=["mamba", "conv"], layers=[0])
    with pytest.raises(ValueError):
        model_of(CONFIG, layers=[9])


@pytest.fixture(scope="module")
def saved_core(seeded):
    """bf16 -> (loss, gradients) of the model as the cells run it."""
    return functools.lru_cache(None)(lambda bf16: loss_and_grads(
        model_of(CONFIG, bf16=bf16), seeded.variables, seeded.ids
    ))


@pytest.mark.parametrize("bf16", [False, True], ids=["float32", "bfloat16"])
@pytest.mark.parametrize("other", remat_cases.OTHERS)
def test_the_remat_policy_changes_no_bit(seeded, saved_core, monkeypatch,
                                         other, bf16):
    """`remat=True` against the plain `nn.remat` and against no remat at
    all, bit for bit."""
    remat_cases.assert_saving_changes_nothing(
        zoo, monkeypatch, other,
        lambda remat, room=None: loss_and_grads(
            model_of(CONFIG, bf16=bf16, remat=remat), seeded.variables,
            seeded.ids, room,
        ),
        saved_core(bf16),
    )


def test_bfloat16_inside_the_twins_rule(seeded):
    """The model computing in bfloat16 is held as the benchmark holds a
    cell that states it: to the reference's own bfloat16 twin, leaf by
    leaf and on the angle (`check_gradient`), where the float8 control
    in the step's place fails."""
    from benchmarks.drivers import train

    held = types.SimpleNamespace(
        **{k: getattr(reference, k) for k in dir(reference)
           if not k.startswith("__")},
        STATED_RATIO=reference.TWIN_RATIO,
    )
    features = {"input_ids": seeded.ids}
    labels = np.zeros(len(seeded.ids), np.int32)
    _, got = loss_and_grads(
        model_of(CONFIG, bf16=True), seeded.variables, seeded.ids
    )
    check = train.check_gradient(
        held, seeded.flat, features, labels, CONFIG, seeded.want, got
    )
    assert check["ok"], sorted(
        check["shares"].items(), key=lambda kv: -kv[1]
    )[:4]
    _, control = reference.loss_and_grads(
        seeded.flat, features, labels, CONFIG, tower="float8_e4m3fn"
    )
    control = {k: np.asarray(v, np.float32) for k, v in control.items()}
    assert not train.check_gradient(
        held, seeded.flat, features, labels, CONFIG, seeded.want, control
    )["ok"]


def test_published_sizes_hold_what_the_configuration_states():
    """The parameters of the cut model at the published widths, counted
    from the built model's shapes: the numbers in the configuration's
    `deployment` and its `parameters_held`, part by part."""
    with open(os.path.join(
        ROOT, "benchmarks", "configs", "granite-4.0-h-micro.json"
    )) as f:
        config = json.load(f)
    from elasticdl_tpu.common.model_handler import _call_with_params

    model = _call_with_params(
        zoo.custom_model, config["model_params"].format(**config)
    )
    assert list(model.config.layers) == [zoo.MAMBA] * 5 + [zoo.ATTENTION] + [
        zoo.MAMBA
    ] * 4
    assert len(model.config.layers) == config["num_hidden_layers"]
    assert tuple(config["layer_types"]) == zoo.PUBLISHED_LAYER_TYPES
    assert len(config["layer_types"]) == config["num_hidden_layers_published"]
    c = model.config
    assert (c.embedding_multiplier, c.attention_multiplier,
            c.residual_multiplier, c.logits_scaling) == (
        12.0, 0.015625, 0.22, 8.0
    )
    assert c.dtype == jnp.bfloat16 and c.remat
    shapes = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), {"input_ids": jnp.zeros((1, 512), jnp.int32)}
    ))
    flat = {
        name: int(np.prod(leaf.shape))
        for name, leaf in trees.flat(shapes["params"]).items()
    }
    by_top = {}
    for name, size in flat.items():
        top = name.split("/")[0]
        by_top[top] = by_top.get(top, 0) + size
    assert by_top == {
        **{f"layer_{i}": 76_182_976 for i in range(10) if i != 5},
        "layer_5": 60_821_504, "token_embedding": 25_690_112,
        "final_norm": 2_048,
    }
    mixer = {
        k.split("/", 2)[2]: v for k, v in flat.items()
        if k.startswith("layer_0/mamba/")
    }
    assert mixer == {
        "in_proj/kernel": 17_432_576, "conv_kernel": 17_408,
        "conv_bias": 4_352, "A_log": 64, "D": 64, "dt_bias": 64,
        "norm/scale": 4_096, "out_proj/kernel": 8_388_608,
    }
    assert sum(mixer.values()) == 25_847_232
    assert sum(
        v for k, v in flat.items() if k.startswith("layer_5/attn/")
    ) == 10_485_760
    assert sum(
        v for k, v in flat.items() if k.startswith("layer_0/mlp/")
    ) == 50_331_648
    total = sum(by_top.values())
    assert total == config["parameters_held"] == 772_160_448
    assert "772,160,448" in config["deployment"]
    assert 12 * total > 0.25 * 16.9e9          # over the floor, held alone


# ---- through the system ---------------------------------------------------


def test_trainer_carries_the_state_kept_gauge(seeded):
    from elasticdl_tpu.worker.sync import ModelOwner
    from elasticdl_tpu.worker.trainer import Trainer

    trainer = Trainer(
        model=model_of(CONFIG), optimizer=zoo.optimizer(1e-3),
        loss_fn=zoo.loss,
    )
    batch = {"features": {"input_ids": seeded.ids},
             "labels": np.zeros(len(seeded.ids), np.int32)}
    state = trainer.init_state(jax.random.PRNGKey(0), batch["features"])
    state, loss = trainer.train_on_batch(state, batch)
    assert float(loss) == pytest.approx(seeded.want_loss, rel=1e-3)
    owner = ModelOwner.__new__(ModelOwner)
    owner.state, owner.lock = state, threading.Lock()
    value, metrics = owner.fetch_loss(loss)
    assert value == pytest.approx(float(loss))
    for layer in (0, 1, 3, 4):
        assert 0.0 < metrics[f"layer_{layer}/mamba/ssm_state_kept_ratio"] < 1.0
    assert "layer_2/mamba/ssm_state_kept_ratio" not in metrics     # attention


@pytest.mark.parametrize("room, share", [
    pytest.param(None, 0.0, id="no-room"),
    pytest.param(remat_cases.ALL_THE_ROOM, 1.0, id="all-the-room"),
])
def test_trainer_hands_the_room_to_the_train_step_alone(seeded, monkeypatch,
                                                        room, share):
    """The Trainer reads the device's room once the state is placed and
    hands it to the TRAIN step as static data; the share of the named
    products' bytes the step keeps is in the registry once the step is
    traced, and the eval step, which is given no room, plans nothing and
    leaves it there."""
    from elasticdl_tpu.common import metrics as metrics_lib
    from elasticdl_tpu.worker import trainer as trainer_lib

    if room is not None:
        monkeypatch.setattr(trainer_lib, "device_room", lambda mesh: room)
    gauge = metrics_lib.default_registry().gauge("worker_remat_kept_ratio")
    gauge.set(0.5)
    trainer = trainer_lib.Trainer(
        model=model_of(CONFIG, remat=True), optimizer=zoo.optimizer(1e-3),
        loss_fn=zoo.loss,
    )
    batch = {"features": {"input_ids": seeded.ids},
             "labels": np.zeros(len(seeded.ids), np.int32)}
    state = trainer.init_state(jax.random.PRNGKey(0), batch["features"])
    assert "remat_kept_ratio" not in state.model_state["step_metrics"]
    assert trainer._room is None and gauge.value() == 0.5
    state, _ = trainer.train_on_batch(state, batch)
    assert trainer._room == (room or 0) and gauge.value() == share
    gauge.set(0.5)
    trainer.predict_on_batch(state, batch["features"])
    state, _ = trainer.train_on_batch(state, batch)   # traced once
    assert gauge.value() == 0.5
    # a new mesh is a new room
    trainer.set_mesh(trainer.mesh)
    assert trainer._room is None


def test_cli_job_of_two_tasks_with_a_falling_loss(tmp_path, monkeypatch):
    from elasticdl_tpu.client.main import main as cli_main
    from elasticdl_tpu.common import metrics as metrics_lib
    from elasticdl_tpu.worker.worker import Worker
    from elasticdl_tpu.worker import trainer as trainer_lib

    # a device with room for every named product: the gauge reads 1
    monkeypatch.setattr(
        trainer_lib, "device_room", lambda mesh: remat_cases.ALL_THE_ROOM
    )

    path = str(tmp_path / "train.tfrecord")
    datagen.write_task_file(
        path, 7, {"format": "tokens", "seq_len": 32, "vocab_size": 50},
        64, 2,
    )
    workers = []
    init = Worker.__init__

    def recording_init(self, *args, **kwargs):
        init(self, *args, **kwargs)
        workers.append(self)

    Worker.__init__ = recording_init
    try:
        rc = cli_main([
            "train", "--model_zoo", os.path.join(ROOT, "model_zoo"),
            "--model_def", "granite.granite_hybrid.custom_model",
            "--model_params",
            "hidden=32;layer_types=['mamba','attention','mamba','mamba'];"
            "layers=[0,1,2];heads=4;kv_heads=2;mamba_heads=4;"
            "mamba_head_dim=8;mamba_state=16;dense_width=48;"
            "embedding_multiplier=12;attention_multiplier=0.125;"
            "residual_multiplier=0.22;logits_scaling=8;vocab_size=50;"
            "remat=True;lr=0.03",
            "--distribution_strategy", "Local", "--training_data", path,
            "--minibatch_size", "8", "--records_per_task", "64",
            "--num_epochs", "1",
        ])
    finally:
        Worker.__init__ = init
    assert rc == 0
    losses = [float(x) for x in workers[0].losses]
    assert len(losses) == 16                      # two tasks of 8 steps
    # logits over 8 and branches times 0.22: the loss falls slowly
    assert np.mean(losses[-4:]) < np.mean(losses[:4]) - 0.05
    registry = metrics_lib.default_registry()
    for layer in (0, 2):
        assert 0.0 < registry.value(
            "worker_ssm_state_kept_ratio", layer=f"layer_{layer}/mamba"
        ) < 1.0
    assert registry.value("worker_remat_kept_ratio") == 1.0
