"""The LFM2 decoder (model_zoo/lfm2/lfm2_moe.py) at tiny widths on the CPU,
seeded weights: conv and attention layers (the gated short convolution,
grouped K/V at an RMSNorm a head, a dense and routed feed-forwards with
no shared expert, the tied head) against the plain float32 reference leaf
by leaf, through the jnp forms and through the interpreted kernels,
bfloat16 inside the twin's rule, the eight shares of an expert-parallel
deployment adding up to the uncut layer, the short convolution's kernels
against its shifted form, the sown gauge through the Trainer, the
published sizes' parameter count, and a two-task job through the CLI."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import lfm2_moe as reference
from elasticdl_tpu.layers.moe import ROUTER_STATE, RoutedExperts
from elasticdl_tpu.layers.step_metrics import STEP_METRICS
from elasticdl_tpu.ops import short_conv
from elasticdl_tpu.ops.flash_attention import stream_shapes_ok
from model_zoo.common.decoder import MoEFFN
from model_zoo.lfm2 import lfm2_moe as zoo
from tests import decoder_cases
from tests.decoder_cases import MUTABLE, computed, seeded  # noqa: F401

# the published pattern's first eight entries; the cut's layers 0, 2..5;
# 4 query heads of 8 over 2 K/V heads, 16 experts of which 4 are held
CONFIG = dict(
    hidden_size=32, num_hidden_layers=5,
    layer_types=list(zoo.PUBLISHED_LAYER_TYPES[:8]),
    layers_held=[0, 2, 3, 4, 5], num_dense_layers_published=2,
    num_attention_heads=4, num_key_value_heads=2, conv_L_cache=3,
    rope_parameters={"rope_theta": 1000000, "rope_type": "default"},
    intermediate_size=48, moe_intermediate_size=16,
    num_experts_published=16, num_experts_per_tok=2, held_experts=[4, 4],
    routed_scaling_factor=1, renorm_eps=1e-6, vocab_size=50, norm_eps=1e-5,
    learning_rate=1e-3, use_bf16=True,
)


def the_head_is_tied(model, seeded, got):
    assert "lm_head_kernel" not in got


def published_also(model, config, shapes, flat, by_top):
    assert list(model.config.layers) == [
        (zoo.CONV, False), (zoo.FULL, True), (zoo.CONV, True),
        (zoo.CONV, True), (zoo.CONV, True),
    ]
    assert len(model.config.layers) == config["num_hidden_layers"]
    assert tuple(config["layer_types"]) == zoo.PUBLISHED_LAYER_TYPES
    assert model.config.hidden // model.config.heads == config["head_dim"]
    assert model.config.renorm_eps == 1e-6
    assert sum(
        v for k, v in flat.items() if k.startswith("layer_2/conv/")
    ) == 16_783_360
    assert sum(
        v for k, v in flat.items() if k.startswith("layer_1/attn/")
    ) == 10_485_888


def the_conv_gauge_is_carried(metrics, state, loss, seeded):
    for layer in (0, 2, 3, 4):
        assert 0.01 < metrics[f"layer_{layer}/conv/out_rms_ratio"] < 2.0
    assert "layer_1/conv/out_rms_ratio" not in metrics      # attention
    assert "layer_0/moe/routed/routed_here_ratio" not in metrics
    assert metrics["layer_1/moe/routed/dropped_tokens"] == 0.0
    assert 0.0 < metrics["layer_4/moe/routed/routed_here_ratio"] < 1.0


def job_gauges(registry):
    assert registry.value("worker_moe_dropped_tokens_total") == 0.0
    assert 0.0 < registry.value(
        "worker_moe_routed_here_ratio", layer="layer_1/moe/routed"
    ) < 1.0
    for layer in (0, 2):
        assert 0.01 < registry.value(
            "worker_short_conv_out_rms_ratio", layer=f"layer_{layer}/conv"
        ) < 2.0


DECODER = decoder_cases.Decoder(
    zoo=zoo, reference=reference, cell="lfm2-24b-a2b", config=CONFIG,
    # ids of seed 0 land one router slot on a bfloat16 tie: its leaf reads
    # 3.3 twin's errors where the rule allows 3 (seeds 1-3 read under 0.7)
    length=64, seed=1,
    # four conv blocks of 3 operator leaves and one attention block of 6,
    # two norms a block, 2 (dense) or 3 (routed) feed-forward leaves, the
    # tied embedding and the final norm: no head leaf
    leaves=(3 + 2 + 2) + (6 + 2 + 3) + 3 * (3 + 2 + 3) + 2,
    float32_also=the_head_is_tied,
    # width 128 in 2 heads of 64 over ONE K/V head, 256 positions: the
    # streaming attention kernels at half a lane tile and the short-conv
    # kernels (two tiles of 128 rows), all interpreted here
    kernels=decoder_cases.Kernels(
        config=dict(
            hidden_size=128, num_attention_heads=2, num_key_value_heads=1,
            layers_held=[0, 2, 3], num_hidden_layers=3,
        ),
        length=256,
        admitted=(
            (stream_shapes_ok, (1, 256, 2, 64), (1, 256, 1, 64),
             (1, 256, 1, 64)),
            (short_conv.short_conv_shapes_ok, (1, 256, 384), (3, 128)),
        ),
    ),
    published=decoder_cases.Published(
        by_top={
            "layer_0": 89_139_200, "layer_1": 86_118_528,
            "layer_2": 92_416_000, "layer_3": 92_416_000,
            "layer_4": 92_416_000, "token_embedding": 16_777_216,
            "final_norm": 2_048,
        },
        total=469_284_992, also=published_also,
    ),
    trainer_gauges=the_conv_gauge_is_carried,
    job=decoder_cases.Job(
        params=(
            "hidden=32;layer_types=['conv','conv','full_attention','conv'];"
            "num_dense_layers=2;layers=[0,2,3];heads=4;kv_heads=2;"
            "dense_width=48;expert_width=16;num_experts=16;top_k=2;"
            "held_experts=(0,8);vocab_size=50;remat=True;lr=0.01"
        ),
        gauges=job_gauges, falls_by=0.1, all_the_room=False,
    ),
)
model_of = DECODER.model_of
TestConformance = decoder_cases.conformance(DECODER)


def test_each_part_of_the_mathematics_is_seen(seeded):
    """The reference is held to the model above; this holds BOTH to the
    configuration: a conv tap dropped, a QK-norm's scale doubled (a norm
    left out would not see it), another layer list or no epsilon in the
    renormalisation each move what is computed."""
    features = {"input_ids": seeded.ids}

    def loss_with(config=CONFIG, **leaves):
        return reference.loss_and_grads(
            {**seeded.flat, **leaves}, features, None, config
        )[0]

    taps = seeded.flat["layer_2/conv/conv_kernel"].copy()
    taps[0] = 0.0                                  # the tap two rows back
    assert abs(
        loss_with(**{"layer_2/conv/conv_kernel": taps}) - seeded.want_loss
    ) > 1e-4
    scale = 2.0 * seeded.flat["layer_1/attn/q_norm/scale"]
    assert abs(
        loss_with(**{"layer_1/attn/q_norm/scale": scale}) - seeded.want_loss
    ) > 1e-5
    # published layer 1 (conv, dense) in layer 0's place is layer 0 again;
    # published layer 6 (attention) in layer 5's (conv) place is not
    with pytest.raises(KeyError):
        loss_with(dict(CONFIG, layers_held=[0, 2, 3, 4, 6]))
    assert loss_with(dict(CONFIG, layers_held=[1, 2, 3, 4, 5])) == (
        pytest.approx(seeded.want_loss, abs=1e-7)
    )
    # the epsilon is in the weights: 1e-6 of a sum of two sigmoids
    x = jnp.asarray(np.random.RandomState(1).randn(8, 32), jnp.float32)
    p = {k.rsplit("/", 1)[1]: jnp.asarray(v) for k, v in seeded.flat.items()
         if k.startswith("layer_1/moe/routed/")}
    sizes = reference.sizes_of(CONFIG, None)
    with_eps = reference.routed(x, p, sizes, lambda t: t)
    without = reference.routed(
        x, p, sizes._replace(renorm_eps=0.0), lambda t: t
    )
    shift = np.abs(np.asarray(with_eps - without)).max()
    assert 0.0 < shift < 1e-5 * np.abs(np.asarray(without)).max()


# ---- the routed layer: the epsilon, no shared expert, the shares ----------


def test_the_eight_shares_add_up_to_the_uncut_layer():
    """64 experts, top-4 of all 64, over 8 shares of 8 (experts 0-7 ...
    56-63), no shared expert, the 1e-6 in every share's weights: the
    routed parts of all shares equal the uncut reference's layer."""
    config = dict(CONFIG, held_experts=[0, 64], num_experts_per_tok=4,
                  num_experts_published=64)
    sizes = reference.sizes_of(config, None)
    x = jnp.asarray(
        np.random.RandomState(1).randn(48, 32).astype(np.float32)
    )
    whole = MoEFFN(32, 64, 4, 16, 0, None, 1.0, 0.0, renorm_eps=1e-6)
    variables = whole.init(jax.random.PRNGKey(3), x)
    p = variables["params"]
    assert set(p) == {"routed"}                     # no shared expert built
    with jax.default_matmul_precision("highest"):
        want = reference.routed(x, p["routed"], sizes, lambda t: t)
        np.testing.assert_allclose(
            whole.apply(variables, x, mutable=MUTABLE)[0], want,
            rtol=2e-4, atol=2e-5,
        )
        total = np.zeros_like(np.asarray(want))
        for share in range(8):
            first = 8 * share
            held = {
                "router_kernel": p["routed"]["router_kernel"],
                "expert_w_gate_up":
                    p["routed"]["expert_w_gate_up"][first:first + 8],
                "expert_w_down":
                    p["routed"]["expert_w_down"][first:first + 8],
            }
            part = RoutedExperts(
                num_experts=64, top_k=4, ffn_dim=16, held_experts=(first, 8),
                renorm_eps=1e-6,
            ).apply(
                {"params": held,
                 ROUTER_STATE: variables[ROUTER_STATE]["routed"]}, x
            )
            total += np.asarray(part)
    np.testing.assert_allclose(total, want, rtol=2e-4, atol=2e-5)
    # and no share alone is the layer
    assert np.abs(np.asarray(part) - np.asarray(want)).max() > (
        0.5 * np.abs(np.asarray(want)).max()
    )


def test_the_epsilon_is_a_field_and_zero_adds_nothing():
    x = jnp.asarray(np.random.RandomState(4).randn(16, 32), jnp.float32)

    def layer(**kwargs):
        return RoutedExperts(num_experts=8, top_k=2, ffn_dim=16, **kwargs)

    variables = layer().init(jax.random.PRNGKey(0), x)

    def text(module):
        return str(jax.make_jaxpr(lambda v, x: module.apply(
            v, x, mutable=[STEP_METRICS]
        )[0])(variables, x))

    assert text(layer()) == text(layer(renorm_eps=0.0))
    assert text(layer()) != text(layer(renorm_eps=1e-6))
    # a large epsilon shrinks every weight: the output with it
    small = layer(renorm_eps=1.0).apply(variables, x, mutable=[STEP_METRICS])
    plain = layer().apply(variables, x, mutable=[STEP_METRICS])
    assert np.abs(small[0]).sum() < 0.9 * np.abs(plain[0]).sum()


# ---- the gated short convolution's kernels --------------------------------


def conv_inputs(batch, length, width, taps, seed=0, dtype=jnp.float32):
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (
        jax.random.normal(keys[0], (batch, length, 3 * width)).astype(dtype),
        jax.random.normal(keys[1], (taps, width)),
        jax.random.normal(keys[2], (batch, length, width)).astype(dtype),
    )


# (batch, L, d, K): three tiles of 16 (48 is no multiple of the next tile,
# 32), five of 16, two of 256 at four taps, one tile alone
CONV_SHAPES = [(2, 48, 128, 3), (1, 80, 256, 3), (2, 512, 128, 4),
               (1, 16, 128, 2)]


@pytest.mark.parametrize("batch, length, width, taps", CONV_SHAPES)
def test_short_conv_kernels_match_the_shifted_form(batch, length, width,
                                                   taps):
    bcu, weight, g = conv_inputs(batch, length, width, taps)
    assert short_conv.short_conv_shapes_ok(bcu.shape, weight.shape)
    np.testing.assert_allclose(
        short_conv.gated_short_conv(bcu, weight),
        short_conv.shifted_short_conv(bcu, weight), rtol=1e-5, atol=1e-5,
    )

    def grads(fn):
        return jax.grad(
            lambda a, b: (fn(a, b) * g).sum(), argnums=(0, 1)
        )(bcu, weight)

    for name, got, want in zip(
        ("d(bcu)", "d(weight)"), grads(short_conv.gated_short_conv),
        grads(short_conv.shifted_short_conv),
    ):
        np.testing.assert_allclose(
            got, want, rtol=1e-4, atol=1e-4, err_msg=name
        )


def test_short_conv_halo_at_the_first_rows():
    """Zeros stand left of t = 0: row 0 sees its own tap alone, row 1 two
    taps, and a later tile's first rows see the tile before them."""
    bcu, weight, _ = conv_inputs(1, 48, 128, 3, seed=3)
    b, c, u = (np.asarray(t[0]) for t in jnp.split(bcu, 3, axis=-1))
    w, x = np.asarray(weight), b * u
    y = np.asarray(short_conv.gated_short_conv(bcu, weight)[0])
    np.testing.assert_allclose(y[0], c[0] * w[2] * x[0], rtol=1e-5,
                               atol=1e-6)
    np.testing.assert_allclose(
        y[1], c[1] * (w[2] * x[1] + w[1] * x[0]), rtol=1e-5, atol=1e-6
    )
    np.testing.assert_allclose(
        y[16], c[16] * (w[2] * x[16] + w[1] * x[15] + w[0] * x[14]),
        rtol=1e-5, atol=1e-6,
    )


def test_short_conv_admission_names_and_types():
    ok = short_conv.short_conv_shapes_ok
    assert ok((4, 8192, 6144), (3, 2048))                   # the cell's
    assert not ok((4, 8200, 6144), (3, 2048))               # L off the halo
    assert not ok((4, 8192, 6144), (3, 2000))               # 3d is not bcu's
    assert not ok((4, 8192, 96), (3, 32))                   # d off the lanes
    assert not ok((4, 8192, 6144), (9, 2048))               # taps past it
    bcu, weight, g = conv_inputs(2, 64, 32, 3)               # the jnp form
    assert short_conv.gated_short_conv(bcu, weight).shape == (2, 64, 32)
    bcu, weight, g = conv_inputs(1, 64, 128, 3, dtype=jnp.bfloat16)
    jaxpr = str(jax.make_jaxpr(jax.grad(
        lambda a, b: short_conv.gated_short_conv(a, b).astype(
            jnp.float32
        ).sum(), argnums=(0, 1),
    ))(bcu, weight))
    assert sorted(set(re.findall(r"\bshort_conv_\w+\b", jaxpr))) == [
        "short_conv_bwd", "short_conv_fwd",
    ]
    y = short_conv.gated_short_conv(bcu, weight)
    assert y.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        y.astype(jnp.float32),
        short_conv.shifted_short_conv(bcu, weight).astype(jnp.float32),
        rtol=2e-2, atol=2e-2,
    )
    dbcu, dweight = jax.grad(
        lambda a, b: (short_conv.gated_short_conv(a, b) * g).astype(
            jnp.float32
        ).sum(), argnums=(0, 1),
    )(bcu, weight)
    assert dbcu.dtype == jnp.bfloat16 and dweight.dtype == jnp.float32


# ---- the ungated entry: silu(conv_K(u)) -----------------------------------

# (batch, L, d, K): three tiles of 16, two of 256 at four taps over two
# blocks of 512 columns, one tile alone, eight taps (the most)
SILU_SHAPES = [(2, 48, 128, 4), (1, 512, 1024, 4), (1, 16, 128, 2),
               (1, 64, 256, 8)]


@pytest.mark.parametrize("batch, length, width, taps", SILU_SHAPES)
def test_silu_conv_kernels_match_the_shifted_form(batch, length, width,
                                                  taps):
    keys = jax.random.split(jax.random.PRNGKey(7), 3)
    u = jax.random.normal(keys[0], (batch, length, width))
    weight = jax.random.normal(keys[1], (taps, width)) * 0.5
    g = jax.random.normal(keys[2], u.shape)
    assert short_conv.silu_conv_shapes_ok(u.shape, weight.shape)
    np.testing.assert_allclose(
        short_conv.silu_short_conv(u, weight),
        short_conv.shifted_silu_conv(u, weight), rtol=1e-5, atol=1e-5,
    )

    def grads(fn):
        return jax.grad(
            lambda a, b: (fn(a, b) * g).sum(), argnums=(0, 1)
        )(u, weight)

    for name, got, want in zip(
        ("du", "d(weight)"), grads(short_conv.silu_short_conv),
        grads(short_conv.shifted_silu_conv),
    ):
        np.testing.assert_allclose(
            got, want, rtol=1e-4, atol=1e-4, err_msg=name
        )


def test_silu_conv_halo_names_and_types():
    """Zeros stand left of t = 0 and a later tile's first rows see the
    tile before them; the kernels carry the names the benchmark's rule
    for the short conv finds; bfloat16 in, bfloat16 out."""
    u = jax.random.normal(jax.random.PRNGKey(8), (1, 48, 128))
    w = jax.random.normal(jax.random.PRNGKey(9), (4, 128))
    y = np.asarray(short_conv.silu_short_conv(u, w)[0])
    x, taps = np.asarray(u[0]), np.asarray(w)

    def silu(z):
        return z / (1.0 + np.exp(-z))

    np.testing.assert_allclose(y[0], silu(taps[3] * x[0]), rtol=1e-5,
                               atol=1e-6)
    np.testing.assert_allclose(
        y[16], silu(taps[3] * x[16] + taps[2] * x[15] + taps[1] * x[14]
                    + taps[0] * x[13]), rtol=1e-5, atol=1e-6,
    )
    ok = short_conv.silu_conv_shapes_ok
    assert ok((2, 8192, 12288), (4, 12288))                 # the cell's
    assert not ok((2, 8200, 12288), (4, 12288))
    assert not ok((2, 8192, 12288), (4, 4096))
    assert not ok((2, 8192, 96), (4, 96))
    assert not ok((2, 8192, 12288), (9, 12288))
    assert short_conv.silu_short_conv(u[:, :, :32], w[:, :32]).shape == (
        1, 48, 32,
    )                                                       # the jnp form
    u16 = u.astype(jnp.bfloat16)
    jaxpr = str(jax.make_jaxpr(jax.grad(
        lambda a, b: short_conv.silu_short_conv(a, b).astype(
            jnp.float32
        ).sum(), argnums=(0, 1),
    ))(u16, w))
    names = sorted(set(re.findall(r"\b\w*short_conv_(?:fwd|bwd)\b", jaxpr)))
    assert names == ["silu_short_conv_bwd", "silu_short_conv_fwd"]
    for name in names:
        assert re.match(r"^(\w+_)?short_conv_(fwd|bwd)$", name)
    du, dw = jax.grad(
        lambda a, b: short_conv.silu_short_conv(a, b).astype(
            jnp.float32
        ).sum(), argnums=(0, 1),
    )(u16, w)
    assert du.dtype == jnp.bfloat16 and dw.dtype == jnp.float32
    assert short_conv.silu_short_conv(u16, w).dtype == jnp.bfloat16


# sha256 of str(make_jaxpr(grad(gated_short_conv ...))) at the LFM2 cell's
# bfloat16 shape (4, 8192, 6144) under (3, 2048), recorded at the commit
# before `silu_short_conv` joined the file: the gated entry did not move.
GATED_JAXPR = (
    "d08b105fa5d35519f7e31c45005bd126bfd74c8de2f5e4f5108cce5e4e633404"
)


def test_gated_short_conv_jaxpr_is_the_parents():
    import hashlib

    text = str(jax.make_jaxpr(jax.grad(
        lambda a, b: short_conv.gated_short_conv(a, b).astype(
            jnp.float32
        ).sum(), argnums=(0, 1),
    ))(
        jax.ShapeDtypeStruct((4, 8192, 6144), jnp.bfloat16),
        jax.ShapeDtypeStruct((3, 2048), jnp.float32),
    ))
    assert "0x" not in text
    assert hashlib.sha256(text.encode()).hexdigest() == GATED_JAXPR


# ---- through the system ---------------------------------------------------


