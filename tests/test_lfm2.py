"""The LFM2 decoder (model_zoo/lfm2/lfm2_moe.py) at tiny widths on the CPU,
seeded weights: conv and attention layers (the gated short convolution,
grouped K/V at an RMSNorm a head, a dense and routed feed-forwards with
no shared expert, the tied head) against the plain float32 reference leaf
by leaf, through the jnp forms and through the interpreted kernels,
bfloat16 inside the twin's rule, the eight shares of an expert-parallel
deployment adding up to the uncut layer, the short convolution's kernels
against its shifted form, the sown gauge through the Trainer, the
published sizes' parameter count, and a two-task job through the CLI."""

import functools
import json
import os
import re
import threading
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import datagen, trees
from benchmarks.reference import lfm2_moe as reference
from elasticdl_tpu.layers.moe import ROUTER_STATE, RoutedExperts
from elasticdl_tpu.layers.step_metrics import AUX_LOSS, STEP_METRICS
from elasticdl_tpu.ops import short_conv
from model_zoo.common.decoder import MoEFFN
from model_zoo.lfm2 import lfm2_moe as zoo
from tests import remat_cases

ROOT = os.path.join(os.path.dirname(__file__), "..")
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
    use_bf16=True,
)
MUTABLE = [AUX_LOSS, STEP_METRICS, ROUTER_STATE]


def model_of(config, **overrides):
    sizes = dict(
        hidden=config["hidden_size"], layer_types=config["layer_types"],
        num_dense_layers=config["num_dense_layers_published"],
        layers=config["layers_held"], heads=config["num_attention_heads"],
        kv_heads=config["num_key_value_heads"],
        conv_kernel=config["conv_L_cache"],
        rope_theta=config["rope_parameters"]["rope_theta"],
        dense_width=config["intermediate_size"],
        expert_width=config["moe_intermediate_size"],
        num_experts=config["num_experts_published"],
        top_k=config["num_experts_per_tok"],
        held_experts=config["held_experts"],
        routed_scaling=config["routed_scaling_factor"],
        renorm_eps=config["renorm_eps"], vocab_size=config["vocab_size"],
        eps=config["norm_eps"], remat=True,
    )
    sizes.update(overrides)
    return zoo.custom_model(**sizes)


def ids_of(rows, length=64, seed=0):
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
    # ids of seed 0 land one router slot on a bfloat16 tie: its leaf reads
    # 3.3 twin's errors where the rule allows 3 (seeds 1-3 read under 0.7)
    return seeded_of(CONFIG, ids_of(8, seed=1))


def assert_leaf_by_leaf(got, want, limit=1e-4):
    assert set(got) == set(want)
    for name, ref in want.items():
        error = np.linalg.norm(got[name] - ref) / np.linalg.norm(ref)
        assert error < limit, (name, error)


def test_float32_matches_reference_leaf_by_leaf(seeded):
    loss, got = loss_and_grads(model_of(CONFIG), seeded.variables, seeded.ids)
    assert abs(loss - seeded.want_loss) < 1e-4 * abs(seeded.want_loss)
    # four conv blocks of 3 operator leaves and one attention block of 6,
    # two norms a block, 2 (dense) or 3 (routed) feed-forward leaves, the
    # tied embedding and the final norm: no head leaf
    assert len(got) == (3 + 2 + 2) + (6 + 2 + 3) + 3 * (3 + 2 + 3) + 2
    assert "lm_head_kernel" not in got
    assert_leaf_by_leaf(got, seeded.want)


def test_kernels_match_reference_leaf_by_leaf():
    """Width 128 in 2 heads of 64 over ONE K/V head, 256 positions: the
    streaming attention kernels at half a lane tile and the short-conv
    kernels (two tiles of 128 rows), all interpreted here."""
    from elasticdl_tpu.ops.flash_attention import stream_shapes_ok

    config = dict(
        CONFIG, hidden_size=128, num_attention_heads=2,
        num_key_value_heads=1, layers_held=[0, 2, 3], num_hidden_layers=3,
    )
    assert stream_shapes_ok((1, 256, 2, 64), (1, 256, 1, 64),
                            (1, 256, 1, 64))
    assert short_conv.short_conv_shapes_ok((1, 256, 384), (3, 128))
    seeded = seeded_of(config, ids_of(1, length=256, seed=2))
    loss, got = loss_and_grads(model_of(config), seeded.variables, seeded.ids)
    assert abs(loss - seeded.want_loss) < 1e-4 * abs(seeded.want_loss)
    assert_leaf_by_leaf(got, seeded.want, 2e-4)


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


@pytest.fixture(scope="module")
def saved_core(seeded):
    """bf16 -> (loss, gradients) of the model as the cells run it."""
    return functools.lru_cache(None)(lambda bf16: loss_and_grads(
        model_of(CONFIG, bf16=bf16), seeded.variables, seeded.ids
    ))


@pytest.mark.parametrize("bf16", [False, True], ids=["float32", "bfloat16"])
@pytest.mark.parametrize("other", remat_cases.OTHERS)
def test_saving_the_attention_core_changes_no_bit(seeded, saved_core,
                                                  monkeypatch, other, bf16):
    """`remat=True` against the plain `nn.remat` every commit before ran
    and against no remat at all, bit for bit."""
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
    `deployment` and its `parameters_held`."""
    with open(os.path.join(
        ROOT, "benchmarks", "configs", "lfm2-24b-a2b.json"
    )) as f:
        config = json.load(f)
    from elasticdl_tpu.common.model_handler import _call_with_params

    model = _call_with_params(
        zoo.custom_model, config["model_params"].format(**config)
    )
    assert list(model.config.layers) == [
        (zoo.CONV, False), (zoo.FULL, True), (zoo.CONV, True),
        (zoo.CONV, True), (zoo.CONV, True),
    ]
    assert len(model.config.layers) == config["num_hidden_layers"]
    assert tuple(config["layer_types"]) == zoo.PUBLISHED_LAYER_TYPES
    assert model.config.hidden // model.config.heads == config["head_dim"]
    assert model.config.renorm_eps == 1e-6
    assert model.config.dtype == jnp.bfloat16 and model.config.remat
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
        "layer_0": 89_139_200, "layer_1": 86_118_528,
        "layer_2": 92_416_000, "layer_3": 92_416_000,
        "layer_4": 92_416_000, "token_embedding": 16_777_216,
        "final_norm": 2_048,
    }
    assert sum(
        v for k, v in flat.items() if k.startswith("layer_2/conv/")
    ) == 16_783_360
    assert sum(
        v for k, v in flat.items() if k.startswith("layer_1/attn/")
    ) == 10_485_888
    total = sum(by_top.values())
    assert total == config["parameters_held"] == 469_284_992
    assert "469,284,992" in config["deployment"]
    assert 12 * total > 0.25 * 16.9e9          # over the floor, held alone


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


def test_trainer_carries_the_conv_gauge(seeded):
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
    for layer in (0, 2, 3, 4):
        assert 0.01 < metrics[f"layer_{layer}/conv/out_rms_ratio"] < 2.0
    assert "layer_1/conv/out_rms_ratio" not in metrics      # attention
    assert "layer_0/moe/routed/routed_here_ratio" not in metrics
    assert metrics["layer_1/moe/routed/dropped_tokens"] == 0.0
    assert 0.0 < metrics["layer_4/moe/routed/routed_here_ratio"] < 1.0


def test_cli_job_of_two_tasks_with_a_falling_loss(tmp_path):
    from elasticdl_tpu.client.main import main as cli_main
    from elasticdl_tpu.common import metrics as metrics_lib
    from elasticdl_tpu.worker.worker import Worker

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
            "--model_def", "lfm2.lfm2_moe.custom_model",
            "--model_params",
            "hidden=32;layer_types=['conv','conv','full_attention','conv'];"
            "num_dense_layers=2;layers=[0,2,3];heads=4;kv_heads=2;"
            "dense_width=48;expert_width=16;num_experts=16;top_k=2;"
            "held_experts=(0,8);vocab_size=50;remat=True;lr=0.01",
            "--distribution_strategy", "Local", "--training_data", path,
            "--minibatch_size", "8", "--records_per_task", "64",
            "--num_epochs", "1",
        ])
    finally:
        Worker.__init__ = init
    assert rc == 0
    losses = [float(x) for x in workers[0].losses]
    assert len(losses) == 16                      # two tasks of 8 steps
    assert np.mean(losses[-4:]) < np.mean(losses[:4]) - 0.1
    registry = metrics_lib.default_registry()
    assert registry.value("worker_moe_dropped_tokens_total") == 0.0
    assert 0.0 < registry.value(
        "worker_moe_routed_here_ratio", layer="layer_1/moe/routed"
    ) < 1.0
    for layer in (0, 2):
        assert 0.01 < registry.value(
            "worker_short_conv_out_rms_ratio", layer=f"layer_{layer}/conv"
        ) < 2.0
