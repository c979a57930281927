"""The Olmo-Hybrid decoder (model_zoo/olmo_hybrid/olmo_hybrid.py) at tiny
widths on the CPU, seeded weights, HEADS held as a share: delta-rule heads
96 wide in keys and 192 in values writing at beta = 2 sigmoid(b),
whole-width QK-normed attention without positions and OLMo's
norm-after-the-sublayer block against the plain float32 reference leaf by
leaf (its delta rule the token-by-token recurrence), through the jnp forms
and through the interpreted kernels; the SHARE tests (three head shares
under one named axis are the uncut layer, the norm's statistic exchanged;
one chip's share alone is the reference given the same share; the whole
model under one axis is the uncut model); controls
that each part of the mathematics must fail; bfloat16 inside the twin's
rule; the sown gauges; the published sizes' parameter count; and a
two-task job through the CLI."""

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import olmo_hybrid as reference
from elasticdl_tpu.layers.step_metrics import STEP_METRICS
from elasticdl_tpu.ops import gdn as gdn_ops
from elasticdl_tpu.ops import short_conv
from elasticdl_tpu.ops.flash_attention import stream_shapes_ok
from model_zoo.common import decoder, delta_net
from model_zoo.olmo_hybrid import olmo_hybrid as zoo
from tests import decoder_cases
from tests.decoder_cases import MUTABLE, computed, seeded  # noqa: F401

# one whole period of the published pattern (delta, delta, delta,
# attention) at the PUBLISHED head widths: 6 delta-rule heads of 96 | 192
# and 6 attention heads of 16 published, heads 2-3 of each held here; a
# conv of 4 taps over the held 768 channels; an MLP 48 wide
PATTERN = ["linear_attention"] * 3 + ["full_attention"]
CONFIG = dict(
    hidden_size=32, intermediate_size=48, layer_types=PATTERN * 2,
    num_hidden_layers=4, num_hidden_layers_published=8,
    layers_held=[0, 1, 2, 3], heads_held=[2, 2],
    num_attention_heads=2, num_key_value_heads=2,
    num_attention_heads_published=6, num_key_value_heads_published=6,
    head_dim=16, linear_num_key_heads=2, linear_num_value_heads=2,
    linear_num_key_heads_published=6, linear_num_value_heads_published=6,
    linear_key_head_dim=96, linear_value_head_dim=192,
    linear_conv_kernel_dim=4, linear_allow_neg_eigval=True, vocab_size=50,
    rms_norm_eps=1e-6, learning_rate=1e-3, use_bf16=True,
)
GDN_LEAVES, ATTENTION_LEAVES, BLOCK_LEAVES = 11, 6, 4


# ---- heads as a share -----------------------------------------------------


SHARES, EACH = 3, 2        # three holders of two heads each
HIDDEN, LENGTH = 32, 40


def whole_layer(kind, axis_name=None, held=None):
    """One mixer of `kind` at six heads, told `held` and the axis."""
    if kind == zoo.LINEAR:
        return delta_net.GatedDeltaNet(
            HIDDEN, SHARES * EACH, SHARES * EACH, 96, 192, 4, 1e-6,
            jnp.float32, "olmo_hybrid/gdn", fused=False, beta_scale=2.0,
            held_heads=held, axis_name=axis_name,
        )
    return decoder.GroupedAttention(
        HIDDEN, SHARES * EACH, SHARES * EACH, 16, 16 ** -0.5, jnp.float32,
        "olmo_hybrid/attn", qk_norm_eps=1e-6, qk_norm_whole=True,
        held_heads=held, axis_name=axis_name,
    )


# leaf -> (the axis the heads lie along, a head's width) of each mixer
HEAD_AXES = {
    zoo.LINEAR: {
        "q/kernel": (1, 96), "k/kernel": (1, 96), "v/kernel": (1, 192),
        "z/kernel": (1, 192), "a/kernel": (1, 1), "b/kernel": (1, 1),
        "A_log": (0, 1), "dt_bias": (0, 1), "o/kernel": (0, 192),
    },
    zoo.FULL: {
        "q/kernel": (1, 16), "k/kernel": (1, 16), "v/kernel": (1, 16),
        "q_norm/scale": (0, 16), "k_norm/scale": (0, 16),
        "o/kernel": (0, 16),
    },
}


def share_of(params, kind, first, count):
    """The leaves a holder of heads `first` .. `first + count` has: its
    columns (or rows) of every leaf that has a head axis, the conv's taps
    over its q | k | v channels, and the shared output scale whole."""
    from benchmarks import trees

    flat, heads = trees.flat(params), SHARES * EACH
    out = {}
    for name, leaf in flat.items():
        if name == "conv_kernel":
            q, k, v = jnp.split(leaf, [heads * 96, 2 * heads * 96], axis=1)
            out[name] = jnp.concatenate([
                q[:, first * 96:(first + count) * 96],
                k[:, first * 96:(first + count) * 96],
                v[:, first * 192:(first + count) * 192],
            ], axis=1)
        elif name in HEAD_AXES[kind]:
            axis, width = HEAD_AXES[kind][name]
            out[name] = jax.lax.slice_in_dim(
                leaf, first * width, (first + count) * width, axis=axis
            )
        else:
            assert name == "o_norm/scale", name
            out[name] = leaf
    return trees.nested(out)


def reference_layer(kind, params, x, heads):
    """The plain reference's mixer of `kind` over `heads` heads."""
    sizes = reference.sizes_of(dict(
        CONFIG, num_attention_heads=heads, num_key_value_heads=heads,
        linear_num_key_heads=heads, linear_num_value_heads=heads,
    ), None)
    layer = reference.gdn if kind == zoo.LINEAR else reference.attention
    return jax.vmap(lambda row: layer(row, params, sizes, lambda t: t))(x)


@pytest.fixture(scope="module", params=[zoo.LINEAR, zoo.FULL])
def shared(request):
    """(kind, x, the uncut layer's parameters, the uncut reference's
    output, each holder's leaves)."""
    kind = request.param
    x = jnp.asarray(
        np.random.RandomState(1).randn(2, LENGTH, HIDDEN), jnp.float32
    )
    whole = whole_layer(kind).init(jax.random.PRNGKey(3), x)["params"]
    # off their seeds: every scale and every head's decay its own
    rng = np.random.RandomState(4)
    whole = jax.tree.map(
        lambda leaf: leaf * (1.0 + 0.3 * rng.randn(*leaf.shape)).astype(
            np.float32
        ) if leaf.ndim == 1 else leaf, whole,
    )
    with jax.default_matmul_precision("highest"):
        want = reference_layer(kind, whole, x, SHARES * EACH)
    parts = [
        share_of(whole, kind, holder * EACH, EACH)
        for holder in range(SHARES)
    ]
    return kind, x, whole, want, parts


def test_three_head_shares_under_one_axis_are_the_uncut_layer(shared):
    """Head parallelism's partial sums: the three holders run under ONE
    `jax.vmap(..., axis_name=)`, so the `psum`s ARE the exchange (the
    mixer's output and, in attention, the QK-norm's sum of squares); what
    every holder then has is the uncut reference's layer output."""
    kind, x, whole, want, parts = shared
    stacked = jax.tree.map(lambda *leaves: jnp.stack(leaves), *parts)

    def holder(params):
        layer = whole_layer(kind, "heads", (0, EACH))
        return layer.apply({"params": params}, x, mutable=MUTABLE)[0]

    with jax.default_matmul_precision("highest"):
        got = jax.vmap(holder, axis_name="heads")(stacked)
        uncut, _ = whole_layer(kind).apply(
            {"params": whole}, x, mutable=MUTABLE
        )
    for out in got:
        np.testing.assert_allclose(out, want, rtol=2e-5, atol=2e-6)
    np.testing.assert_allclose(uncut, want, rtol=2e-5, atol=2e-6)
    # no holder's own part is the layer
    alone, _ = whole_layer(kind, None, (0, EACH)).apply(
        {"params": parts[0]}, x, mutable=MUTABLE
    )
    assert np.abs(np.asarray(alone - want)).max() > 1e-3


def test_one_chips_share_is_the_reference_given_the_same_share(shared):
    """Without an axis nothing is exchanged: a holder's output is the
    reference's on the same leaves (the QK-norm's statistic over the HELD
    columns).  The delta-rule layer is head-wise throughout, so its parts
    still add up to the uncut layer; attention's do not, by its norm."""
    kind, x, whole, want, parts = shared
    outs = []
    with jax.default_matmul_precision("highest"):
        for holder, params in enumerate(parts):
            got, _ = whole_layer(kind, None, (holder * EACH, EACH)).apply(
                {"params": params}, x, mutable=MUTABLE
            )
            np.testing.assert_allclose(
                got, reference_layer(kind, params, x, EACH),
                rtol=2e-5, atol=2e-6,
            )
            outs.append(got)
    off = np.abs(np.asarray(sum(outs) - want)).max()
    if kind == zoo.LINEAR:
        np.testing.assert_allclose(sum(outs), want, rtol=2e-5, atol=2e-6)
    else:
        assert off > 1e-3


def test_the_whole_model_under_one_axis_is_the_uncut_model():
    """One block of each kind with every head built, against three
    holders of two heads each under ONE named axis: each holder computes
    the MLPs, the norms, the embedding and the head alike (counted once:
    nothing of them is summed) and its own heads' part of each mixer, and
    every holder's per-position losses are the uncut model's and the
    uncut reference's."""
    from benchmarks import trees

    uncut = dict(
        CONFIG, layers_held=[2, 3], num_hidden_layers=2, heads_held=None,
        num_attention_heads=6, num_key_value_heads=6,
        linear_num_key_heads=6, linear_num_value_heads=6,
    )
    ids = DECODER.ids_of(2, length=LENGTH, seed=9)
    whole = model_of(uncut).init(
        jax.random.PRNGKey(0), {"input_ids": ids}
    )["params"]
    mixers = {"layer_0": ("gdn", zoo.LINEAR), "layer_1": ("attn", zoo.FULL)}

    def holders_leaves(holder):
        return {
            name: dict(sub, **{mixers[name][0]: share_of(
                sub[mixers[name][0]], mixers[name][1], holder * EACH, EACH
            )}) if name in mixers else sub
            for name, sub in whole.items()
        }

    stacked = jax.tree.map(
        lambda *leaves: jnp.stack(leaves),
        *(holders_leaves(holder) for holder in range(SHARES)),
    )
    held = model_of(uncut, held_heads=[0, EACH], axis_name="heads")

    def losses(model, params):
        return model.apply(
            {"params": params}, {"input_ids": ids}, mutable=MUTABLE
        )[0]

    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda p: losses(model_of(uncut), p))(whole)
        got = jax.jit(jax.vmap(
            lambda params: losses(held, params), axis_name="heads"
        ))(stacked)
        reference_loss, _ = reference.loss_and_grads(
            {k: np.asarray(v) for k, v in trees.flat(whole).items()},
            {"input_ids": ids}, None, uncut,
        )
    for out in got:
        np.testing.assert_allclose(out, want, rtol=2e-5, atol=2e-6)
    assert float(want.mean()) == pytest.approx(reference_loss, rel=1e-5)


def test_a_share_holds_whole_heads_inside_the_published_count():
    with pytest.raises(ValueError):
        model_of(CONFIG, held_heads=[5, 2])
    with pytest.raises(ValueError):
        model_of(CONFIG, held_heads=[0, 0])
    assert decoder.held_of(30, None) == 30
    assert decoder.held_of(30, (20, 10)) == 10
    model = model_of(CONFIG, held_heads=None)
    shapes = jax.eval_shape(
        model.init, jax.random.PRNGKey(0),
        {"input_ids": jnp.zeros((1, 16), jnp.int32)},
    )["params"]
    assert shapes["layer_0"]["gdn"]["q"]["kernel"].shape == (32, 6 * 96)
    assert shapes["layer_3"]["attn"]["q_norm"]["scale"].shape == (6 * 16,)


# ---- controls: each part of the mathematics must fail the comparison ------


def _qk_norm_a_head(monkeypatch):
    """One statistic a HEAD under the same whole-width scale."""
    class AHead(decoder.WholeWidthNorm):
        @nn.compact
        def __call__(self, x):
            scale = self.param("scale", nn.initializers.ones, (x.shape[-1],))
            by_head = x.reshape(*x.shape[:-1], -1, CONFIG["head_dim"])
            return (
                decoder.rms_norm(by_head, 1.0, self.eps).reshape(x.shape)
                * scale
            ).astype(self.dtype)

    monkeypatch.setattr(decoder, "WholeWidthNorm", AHead)


def _norm_before_the_sublayer(monkeypatch):
    """The pre-norm block of every other decoder of the zoo, over the
    same leaves."""
    class PreNorm(zoo.Block):
        @nn.compact
        def __call__(self, x):
            c = self.config
            y = self.mix(decoder.RMSNorm(c.eps, c.dtype, name="mix_norm")(x))
            h = x + y
            y = decoder.RMSNorm(c.eps, c.dtype, name="ffn_norm")(h)
            return h + decoder.SwiGLU(
                c.hidden, c.dense_width, c.dtype, name="mlp"
            )(y)

    monkeypatch.setattr(zoo, "Block", PreNorm)


def _a_rotated_head(monkeypatch):
    plain = zoo.GroupedAttention
    monkeypatch.setattr(
        zoo, "GroupedAttention", lambda *args, **kwargs: plain(
            *args, **kwargs, rope=decoder.plain_rope(CONFIG["head_dim"], 1e4)
        ),
    )


CONTROLS = {
    "beta_under_one": dict(allow_neg_eigval=False),
    "qk_norm_a_head": _qk_norm_a_head,
    "norm_before_the_sublayer": _norm_before_the_sublayer,
    "a_rotated_head": _a_rotated_head,
}
# (the shared delta-rule layer's own parts, its conv, decay, L2 norms and
# output norm's order, are failed one by one in tests/test_qwen3_next.py)


def moved_off_their_seeds(params):
    """Every norm's scale off 1 (a whole-width scale and a head-wise one
    of ones are the same numbers) and b's kernel larger, so that beta
    spreads over (0, 2)."""
    rng = np.random.RandomState(11)

    def moved(path, leaf):
        name = jax.tree_util.keystr(path)
        if "scale" in name:
            return leaf * (1.0 + 0.3 * rng.randn(*leaf.shape)).astype(
                np.float32
            )
        if "['b']" in name:
            return leaf * 4.0
        return leaf

    return jax.tree_util.tree_map_with_path(moved, params)


def float32_also(model, seeded, got):
    c = model.config
    assert list(c.layers) == PATTERN and c.beta_scale == 2.0
    assert c.held_heads == (2, 2) and c.axis_name is None
    assert set(seeded.variables) == {"params", STEP_METRICS}
    assert got["layer_0/gdn/q/kernel"].shape == (32, 2 * 96)
    assert got["layer_0/gdn/v/kernel"].shape == (32, 2 * 192)
    assert got["layer_0/gdn/b/kernel"].shape == (32, 2)
    assert got["layer_0/gdn/conv_kernel"].shape == (4, 2 * (96 + 96 + 192))
    assert got["layer_0/gdn/A_log"].shape == (2,)
    assert got["layer_0/gdn/o_norm/scale"].shape == (192,)
    assert got["layer_0/gdn/o/kernel"].shape == (2 * 192, 32)
    assert got["layer_3/attn/q/kernel"].shape == (32, 2 * 16)
    assert got["layer_3/attn/q_norm/scale"].shape == (2 * 16,)
    assert got["layer_1/mlp/gate_up/kernel"].shape == (32, 96)


def published_also(model, config, shapes, flat, by_top):
    """Part by part, and every number of the catalog row under its own
    key."""
    assert config["layers_held"] == [0, 1, 2, 3]
    assert len(config["layer_types"]) == 32
    assert tuple(config["layer_types"]) == zoo.PUBLISHED_LAYER_TYPES
    c = model.config
    assert list(c.layers) == PATTERN
    assert (c.hidden, c.dense_width, c.eps) == (3840, 11008, 1e-6)
    assert (c.heads, c.kv_heads, c.head_dim) == (30, 30, 128)
    assert (c.gdn_key_heads, c.gdn_value_heads) == (30, 30)
    assert (c.gdn_key_dim, c.gdn_value_dim, c.conv_kernel) == (96, 192, 4)
    assert c.beta_scale == 2.0 and c.held_heads == (0, 10)
    for key in ("num_attention_heads", "num_key_value_heads",
                "linear_num_key_heads", "linear_num_value_heads"):
        assert config[key] == 10 and config[key + "_published"] == 30
        assert key in config["reduced"]
    assert config["vocab_size"] * 8 == config["vocab_size_published"]
    assert set(shapes) == {"params", STEP_METRICS}

    def part(prefix):
        return {
            k[len(prefix):]: v for k, v in flat.items() if k.startswith(prefix)
        }

    assert part("layer_0/gdn/") == {
        "q/kernel": 3_686_400, "k/kernel": 3_686_400,
        "v/kernel": 7_372_800, "z/kernel": 7_372_800, "a/kernel": 38_400,
        "b/kernel": 38_400, "conv_kernel": 15_360, "A_log": 10,
        "dt_bias": 10, "o_norm/scale": 192, "o/kernel": 7_372_800,
    }
    assert part("layer_3/attn/") == {
        "q/kernel": 4_915_200, "k/kernel": 4_915_200, "v/kernel": 4_915_200,
        "q_norm/scale": 1_280, "k_norm/scale": 1_280, "o/kernel": 4_915_200,
    }
    assert part("layer_1/mlp/") == {
        "gate_up/kernel": 84_541_440, "down/kernel": 42_270_720,
    }


def trainer_gauges(metrics, state, loss, seeded):
    for layer in range(3):
        path = f"layer_{layer}/gdn"
        assert 0.0 < metrics[f"{path}/gdn_decay_mean_ratio"] < 1.0
        assert 0.0 < metrics[f"{path}/gdn_beta_mean_ratio"] < 2.0
        assert 0.0 < metrics[f"{path}/gdn_beta_over_one_ratio"] < 1.0
        # 80 positions go the plain form: no kernel, no padding
        assert f"{path}/gdn_padded_lanes_ratio" not in metrics
    assert "layer_3/gdn/gdn_decay_mean_ratio" not in metrics


def job_gauges(registry):
    for name in ("worker_gdn_decay_mean_ratio",
                 "worker_gdn_beta_over_one_ratio"):
        assert 0.0 < registry.value(name, layer="layer_0/gdn") < 1.0
    assert 0.0 < registry.value(
        "worker_gdn_beta_mean_ratio", layer="layer_0/gdn"
    ) < 2.0


DECODER = decoder_cases.Decoder(
    zoo=zoo, reference=reference, cell="olmo-hybrid-7b", config=CONFIG,
    # 80 positions: the scan's jnp form pads them to two chunks of 64
    length=80, seed=5, reseed=moved_off_their_seeds,
    # a delta-rule mixer's 11 leaves or attention's 6 beside a block's two
    # norms and two MLP kernels; the embedding, the untied head, the final
    # norm
    leaves=3 * GDN_LEAVES + ATTENTION_LEAVES + 4 * BLOCK_LEAVES + 3,
    float32_also=float32_also,
    # two delta-rule heads of 96 | 192 at 128 positions (two chunks: the
    # state crosses a boundary; the kernels see them padded to 128 | 256),
    # the SiLU conv at 768 columns and the streaming attention at two
    # heads of 128, all interpreted here
    kernels=decoder_cases.Kernels(
        config=dict(
            hidden_size=128, head_dim=128, layers_held=[2, 3],
            num_hidden_layers=2,
        ),
        length=128,
        admitted=(
            (gdn_ops.gdn_shapes_ok, (1, 128, 2, 96), (1, 128, 2, 96),
             (1, 128, 2, 192)),
            (short_conv.silu_conv_shapes_ok, (1, 128, 768), (4, 768)),
            (stream_shapes_ok, (1, 128, 2, 128), (1, 128, 2, 128),
             (1, 128, 2, 128)),
        ),
    ),
    # (the block's four named products, `ffn_out` among them)
    remat_types=(False,),
    controls=CONTROLS,
    published=decoder_cases.Published(
        by_top={
            "layer_0": 156_403_412, "layer_1": 156_403_412,
            "layer_2": 156_403_412, "layer_3": 146_483_200,
            "token_embedding": 48_168_960, "lm_head_kernel": 48_168_960,
            "final_norm": 3_840,
        },
        total=712_035_196, bytes_a_parameter=16, also=published_also,
    ),
    trainer_gauges=trainer_gauges,
    # the job's model is one block of each kind (published layers 2 and 3)
    # at narrow heads, two of six held
    job=decoder_cases.Job(
        params=(
            "hidden=32;layers=[2,3];heads=6;kv_heads=6;head_dim=16;"
            "gdn_key_heads=6;gdn_value_heads=6;gdn_key_dim=8;"
            "gdn_value_dim=16;held_heads=[2,2];dense_width=48;"
            "vocab_size=50;remat=True;lr=0.03"
        ),
        gauges=job_gauges,
    ),
    scopes=decoder_cases.Scopes(
        prefix="olmo_hybrid",
        names=("embed", "gdn/proj", "gdn/conv", "gdn/decay", "gdn/core",
               "gdn/out", "attn", "attn/qk_norm", "dense_ffn", "norm",
               "head_ce"),
        remat=True,
    ),
)
model_of = DECODER.model_of
TestConformance = decoder_cases.conformance(DECODER)


def test_each_part_of_the_reference_is_seen(seeded):
    """The reference is held to the model above; this holds it to the
    configuration: the decay's A and the step's bias, the output norm's
    scale, the whole-width scales, both of a block's norms, beta's range
    and another layer list each move what is computed."""
    features = {"input_ids": seeded.ids}

    def loss_with(config=CONFIG, **leaves):
        return reference.loss_and_grads(
            {**seeded.flat, **leaves}, features, None, config
        )[0]

    for leaf in ("layer_0/gdn/A_log", "layer_1/gdn/dt_bias",
                 "layer_2/gdn/o_norm/scale", "layer_3/attn/q_norm/scale",
                 "layer_0/mix_norm/scale", "layer_2/ffn_norm/scale"):
        assert abs(
            loss_with(**{leaf: seeded.flat[leaf] + np.log(2.0)})
            - seeded.want_loss
        ) > 1e-6, leaf
    assert abs(
        loss_with(dict(CONFIG, linear_allow_neg_eigval=False))
        - seeded.want_loss
    ) > 1e-4
    # published layer 4 (a delta-rule layer) in layer 2's place is layer 2
    # again; an attention layer in a delta-rule layer's place finds no
    # attention weights
    assert loss_with(dict(CONFIG, layers_held=[0, 1, 4, 3])) == (
        pytest.approx(seeded.want_loss, abs=1e-7)
    )
    with pytest.raises(KeyError):
        loss_with(dict(CONFIG, layers_held=[0, 1, 7, 3]))


def test_the_pattern_names_every_layer():
    model = zoo.custom_model(hidden=32, vocab_size=50)
    assert len(model.config.layers) == 32
    assert [i for i, kind in enumerate(model.config.layers)
            if kind == zoo.FULL] == list(range(3, 32, 4))
    assert model.config.held_heads is None
    with pytest.raises(ValueError):
        model_of(CONFIG, layers=[8])
    with pytest.raises(ValueError):
        model_of(CONFIG, layer_types=["mamba"])
    with pytest.raises(ValueError):
        model_of(CONFIG, kv_heads=4)


def test_beta_reaches_past_one_and_the_gauge_counts_it():
    """beta = 2 sigmoid(b): with b's kernel at its seeds half of the
    (token, head) pairs write at over 1, which `sigmoid(b)` never does."""
    x = jnp.asarray(
        np.random.RandomState(2).randn(1, 24, HIDDEN), jnp.float32
    )
    layer = whole_layer(zoo.LINEAR)
    variables = layer.init(jax.random.PRNGKey(0), x)
    _, sown = layer.apply(variables, x, mutable=MUTABLE)
    sown = sown[STEP_METRICS]
    assert 0.3 < float(sown["gdn_beta_over_one_ratio"]) < 0.7
    assert 0.8 < float(sown["gdn_beta_mean_ratio"]) < 1.2
    b = x @ variables["params"]["b"]["kernel"]
    assert float(sown["gdn_beta_over_one_ratio"]) == pytest.approx(
        float((b > 0).mean())
    )
    # at whole chunks of heads of 96 | 192 the kernels run, a quarter of
    # what they process padding; Qwen3-Next's heads of 128 report nothing
    x = jnp.zeros((1, 64, HIDDEN), jnp.float32)
    _, sown = layer.apply(variables, x, mutable=MUTABLE)
    assert float(sown[STEP_METRICS]["gdn_padded_lanes_ratio"]) == 0.25
