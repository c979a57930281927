"""The Qwen3-Next decoder (model_zoo/qwen3_next/qwen3_next.py) at tiny
widths on the CPU, seeded weights: the gated delta rule with one decay a
head and two value heads a key head, grouped attention with QK-norm, a
rotated quarter and a query-wide gate, softmax-routed experts beside a
sigmoid-gated shared one, zero-centred norms and the untied head against
the plain float32 reference leaf by leaf (its delta rule the
token-by-token recurrence, its experts a dense sum), through the jnp forms
and through the interpreted kernels; each mechanism alone; the SHARE test
(every holder's routed part plus the gated shared expert once is the uncut
layer); controls that each part of the mathematics must fail; bfloat16
inside the twin's rule; the sown gauges; the published sizes' parameter
count; and a two-task job through the CLI."""

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import qwen3_next as reference
from elasticdl_tpu.layers import moe
from elasticdl_tpu.layers.step_metrics import STEP_METRICS
from elasticdl_tpu.ops import gdn as gdn_ops
from elasticdl_tpu.ops import short_conv
from elasticdl_tpu.ops.flash_attention import stream_shapes_ok
from model_zoo.common import decoder, delta_net
from model_zoo.qwen3_next import qwen3_next as zoo
from tests import decoder_cases
from tests.decoder_cases import MUTABLE, computed, seeded  # noqa: F401

# one whole period of the published pattern (GDN, GDN, GDN, attention):
# 2 key heads and 4 value heads of 8, a conv of 4 taps over 48 channels,
# 4 query heads of 16 (hidden / heads is 8) over 2 K/V heads with the first
# 4 columns rotated, top-3 of 16 softmax-routed experts 24 wide with 8
# held, a gated shared expert 24 wide
CONFIG = dict(
    hidden_size=32, num_hidden_layers=4, num_hidden_layers_published=48,
    full_attention_interval=4,
    layers_held=[0, 1, 2, 3], num_attention_heads=4, num_key_value_heads=2,
    head_dim=16, partial_rotary_factor=0.25, rope_theta=1e7,
    linear_num_key_heads=2, linear_num_value_heads=4, linear_key_head_dim=8,
    linear_value_head_dim=8, linear_conv_kernel_dim=4,
    moe_intermediate_size=24, shared_expert_intermediate_size=24,
    num_experts=8, num_experts_published=16, num_experts_per_tok=3,
    held_experts=[4, 8], vocab_size=50, rms_norm_eps=1e-6,
    learning_rate=1e-3, use_bf16=True,
)
GDN_LEAVES, ATTENTION_LEAVES, EXPERT_LEAVES = 7, 6, 6


# ---- each mechanism alone --------------------------------------------------


def test_softmax_scores_renormalise_to_one_over_the_picked():
    """The router's weights are a softmax over ALL outputs, the top k
    renormalised: they sum to 1 a token, no buffer selects, and the layer
    with every expert held is the dense sum of the reference."""
    hidden, experts, width, top_k = 32, 16, 24, 3
    x = jnp.asarray(np.random.RandomState(0).randn(2, 24, hidden), jnp.float32)
    layer = moe.RoutedExperts(
        num_experts=experts, top_k=top_k, ffn_dim=width, scores=moe.SOFTMAX
    )
    variables = layer.init(jax.random.PRNGKey(1), x)
    assert set(variables) == {"params", STEP_METRICS}
    params = variables["params"]
    rows = x.reshape(-1, hidden)
    with jax.default_matmul_precision("highest"):
        chosen, weights = reference.routing(
            rows, params["router_kernel"], top_k
        )
        np.testing.assert_allclose(weights.sum(axis=1), 1.0, rtol=1e-6)
        scores = jax.nn.softmax(rows @ params["router_kernel"], axis=-1)
        # the picked are the k largest of the softmax, in its proportions
        np.testing.assert_allclose(
            weights[:, 0] / weights[:, 1],
            jnp.take_along_axis(scores, chosen, axis=1)[:, 0]
            / jnp.take_along_axis(scores, chosen, axis=1)[:, 1], rtol=1e-5,
        )
        sizes = reference.sizes_of(dict(
            CONFIG, num_experts_per_tok=top_k, held_experts=[0, experts],
        ), None)
        want = reference.routed(rows, params, sizes, lambda t: t)
        got, _ = layer.apply(variables, x, mutable=MUTABLE)
    np.testing.assert_allclose(
        got.reshape(-1, hidden), want, rtol=2e-5, atol=2e-6
    )
    # the sigmoid form of the same weights is another layer
    other, _ = moe.RoutedExperts(
        num_experts=experts, top_k=top_k, ffn_dim=width
    ).apply({"params": params}, x, mutable=MUTABLE)
    assert np.abs(np.asarray(other) - np.asarray(got)).max() > 1e-3


def test_the_shared_experts_gate_is_one_sigmoid_a_token():
    hidden, width = 32, 24
    x = jnp.asarray(np.random.RandomState(3).randn(2, 24, hidden), jnp.float32)

    def layer(gated):
        return decoder.MoEFFN(
            hidden, 16, 3, width, 1, None, 1.0, 0.0, jnp.float32,
            "qwen3_next/moe", scores=moe.SOFTMAX, shared_gate=gated,
        )

    variables = layer(True).init(jax.random.PRNGKey(4), x)
    params = variables["params"]
    assert params["shared_gate"]["kernel"].shape == (hidden, 1)
    ungated = {k: v for k, v in params.items() if k != "shared_gate"}
    with jax.default_matmul_precision("highest"):
        with_gate, sown = layer(True).apply(variables, x, mutable=MUTABLE)
        without, _ = layer(False).apply(
            {"params": ungated}, x, mutable=MUTABLE
        )
        plain = lambda t: t
        shared = reference.swiglu(x, params["shared"], plain)
        gate = jax.nn.sigmoid(x @ params["shared_gate"]["kernel"])
    np.testing.assert_allclose(
        with_gate - without, (gate - 1.0) * shared, rtol=2e-5, atol=2e-6
    )
    assert float(sown[STEP_METRICS]["shared_gate_mean_ratio"]) == (
        pytest.approx(float(gate.mean()), rel=1e-5)
    )


def test_norm_then_gate_is_not_gate_then_norm():
    """The delta rule's output norm: the statistic of y alone, a head's
    columns at a time, ONE scale of a head's width shared by the heads,
    then the gate; Mamba-2's order on the same numbers is another
    number."""
    heads, dim = 4, 8
    rng = np.random.RandomState(6)
    y, z = (jnp.asarray(rng.randn(2, 10, heads * dim), jnp.float32)
            for _ in range(2))
    scale = jnp.asarray(1.0 + 0.3 * rng.randn(dim), jnp.float32)
    then_gate = decoder.GatedRMSNorm(
        1e-6, jnp.float32, heads, gate_first=False, shared_scale=True
    )
    assert then_gate.init(jax.random.PRNGKey(0), y, z)["params"][
        "scale"
    ].shape == (dim,)
    got = then_gate.apply({"params": {"scale": scale}}, y, z)
    by_head = y.reshape(2, 10, heads, dim)
    want = (
        by_head * jax.lax.rsqrt(
            jnp.mean(jnp.square(by_head), axis=-1, keepdims=True) + 1e-6
        ) * scale * jax.nn.silu(z.reshape(by_head.shape))
    ).reshape(y.shape)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    first = decoder.GatedRMSNorm(1e-6, jnp.float32, heads).apply(
        {"params": {"scale": jnp.tile(scale, heads)}}, y, z
    )
    assert np.abs(np.asarray(first) - np.asarray(got)).max() > 0.05


def test_a_quarter_of_the_head_turns_and_the_rest_passes():
    rope = decoder.plain_rope(16, 1e7, 0.25)
    assert rope.columns == 4 and len(rope.inv_freq) == 2
    assert rope.inv_freq[1] == pytest.approx(1e7 ** -0.5)
    x = jnp.asarray(np.random.RandomState(8).randn(1, 6, 2, 16), jnp.float32)
    turned = decoder.partial_rotary(x, rope)
    np.testing.assert_array_equal(turned[..., 4:], x[..., 4:])
    np.testing.assert_array_equal(turned[:, 0], x[:, 0])     # position 0
    assert np.abs(np.asarray(turned[:, 1:, :, :4] - x[:, 1:, :, :4])).max() > 0.1
    # rotate-half pairing: column i with column i + 2, position 1
    cos, sin = np.cos(rope.inv_freq[0]), np.sin(rope.inv_freq[0])
    np.testing.assert_allclose(
        turned[0, 1, 0, 0], x[0, 1, 0, 0] * cos - x[0, 1, 0, 2] * sin,
        rtol=1e-5,
    )
    # norms do not change under the turn
    np.testing.assert_allclose(
        jnp.linalg.norm(turned, axis=-1), jnp.linalg.norm(x, axis=-1),
        rtol=1e-5,
    )


def test_the_query_wide_gate_and_the_qk_norm():
    """The q projection is twice as wide, a head's columns q | gate; q and
    k are normed a head under a 1 + w scale; the output is times
    sigmoid(gate) element by element: against the reference's layer."""
    hidden, heads, kv_heads, dim = 32, 4, 2, 16
    x = jnp.asarray(np.random.RandomState(9).randn(2, 24, hidden), jnp.float32)
    layer = decoder.GroupedAttention(
        hidden, heads, kv_heads, dim, dim ** -0.5, jnp.float32,
        "qwen3_next/attn", qk_norm_eps=1e-6,
        rope=decoder.plain_rope(dim, 1e7, 0.25), query_gate=True,
    )
    variables = layer.init(jax.random.PRNGKey(2), x)
    params = variables["params"]
    assert params["q"]["kernel"].shape == (hidden, heads * 2 * dim)
    assert params["q_norm"]["scale"].shape == (dim,)
    assert np.abs(np.asarray(params["q_norm"]["scale"])).max() < 0.6   # w
    sizes = reference.sizes_of(CONFIG, None)
    with jax.default_matmul_precision("highest"):
        got, sown = layer.apply(variables, x, mutable=MUTABLE)
        want = jax.vmap(
            lambda row: reference.attention(row, params, sizes, lambda t: t)
        )(x)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6)
    assert 0.0 < float(sown[STEP_METRICS]["query_gate_mean_ratio"]) < 1.0
    # the plain layer of the other decoders has none of the three
    plain = decoder.GroupedAttention(
        hidden, heads, kv_heads, dim, dim ** -0.5, jnp.float32
    ).init(jax.random.PRNGKey(2), x)["params"]
    assert set(plain) == {"q", "k", "v", "o"}
    assert plain["q"]["kernel"].shape == (hidden, heads * dim)


# ---- the share: what each of 16 holders computes, and the shared expert ----


def test_sixteen_holders_and_one_gated_shared_expert_are_the_uncut_layer():
    """Expert parallelism's partial sums: the routed parts of all 16
    holders (two experts of 32 each) plus the GATED shared expert counted
    ONCE equal the uncut reference's whole expert layer; a holder's own
    output is its part plus the gated shared expert, as every holder
    computes it."""
    hidden, experts, width, top_k, holders = 32, 32, 24, 10, 16
    each = experts // holders
    x = jnp.asarray(np.random.RandomState(1).randn(3, 40, hidden), jnp.float32)

    def layer(held):
        return decoder.MoEFFN(
            hidden, experts, top_k, width, 1, held, 1.0, 0.0, jnp.float32,
            "qwen3_next/moe", scores=moe.SOFTMAX, shared_gate=True,
        )

    whole = layer(None).init(jax.random.PRNGKey(3), x)["params"]
    assert whole["routed"]["expert_w_gate_up"].shape == (
        experts, hidden, 2 * width
    )
    sizes = reference.sizes_of(dict(
        CONFIG, num_experts_per_tok=top_k, held_experts=[0, experts],
    ), None)
    stacks = ("expert_w_gate_up", "expert_w_down")
    with jax.default_matmul_precision("highest"):
        plain = lambda t: t
        want_shared = jax.vmap(
            lambda row: reference.gated_shared(row, whole, plain)
        )(x)
        want = jax.vmap(lambda row: reference.routed(
            row, whole["routed"], sizes, plain
        ))(x) + want_shared
        parts = []
        for holder in range(holders):
            first = holder * each
            routed = dict(whole["routed"], **{
                name: whole["routed"][name][first:first + each]
                for name in stacks
            })
            out, _ = layer((first, each)).apply(
                {"params": dict(whole, routed=routed)}, x, mutable=MUTABLE,
            )
            parts.append(out - want_shared)
    total = sum(parts) + want_shared
    np.testing.assert_allclose(total, want, rtol=2e-5, atol=2e-6)
    # no holder alone is the layer, and the shared expert is not nothing
    assert np.abs(parts[0] + want_shared - want).max() > 1e-3
    assert np.abs(want_shared).max() > 0.01


# ---- controls: each part of the mathematics must fail the comparison ------


def _gate_before_norm(monkeypatch):
    plain = delta_net.GatedRMSNorm
    monkeypatch.setattr(
        delta_net, "GatedRMSNorm",
        lambda eps, dtype, groups, gate_first, shared_scale, name: plain(
            eps, dtype, groups, gate_first=True, shared_scale=shared_scale,
            name=name,
        ),
    )


def _one_norm_over_all_heads(monkeypatch):
    """One statistic over all the value heads' columns, the scale tiled."""
    class Whole(delta_net.GatedRMSNorm):
        @nn.compact
        def __call__(self, y, z):
            scale = jnp.tile(self.param(
                "scale", nn.initializers.ones, (y.shape[-1] // self.groups,)
            ), self.groups)
            return (
                decoder.rms_norm(y, scale, self.eps) * jax.nn.silu(z)
            ).astype(self.dtype)

    monkeypatch.setattr(delta_net, "GatedRMSNorm", Whole)


def _plain_scales(monkeypatch):
    """Every zero-centred norm read as a plain scale w."""
    plain = decoder.RMSNorm

    class Plain(plain):
        @nn.compact
        def __call__(self, x):
            scale = self.param(
                "scale", nn.initializers.normal(0.1), (x.shape[-1],)
            )
            return decoder.rms_norm(x, scale, self.eps).astype(self.dtype)

    monkeypatch.setattr(zoo, "RMSNorm", Plain)


def _values_on_other_key_heads(monkeypatch):
    """Value head h reading key head h % 2 (interleaved), not h // 2."""
    plain = delta_net.gdn

    def regrouped(q, k, v, g, beta, qk_norm):
        order = jnp.asarray([0, 2, 1, 3])
        return plain(
            q, k, v[:, :, order], g[:, :, order], beta[:, :, order],
            qk_norm=qk_norm,
        )[:, :, order]

    monkeypatch.setattr(delta_net, "gdn", regrouped)


def _decay_dropped(monkeypatch):
    plain = delta_net.gdn
    monkeypatch.setattr(
        delta_net, "gdn", lambda q, k, v, g, beta, qk_norm: plain(
            q, k, v, jnp.zeros_like(g), beta, qk_norm=qk_norm
        ),
    )


def _l2_norms_dropped(monkeypatch):
    plain = delta_net.gdn
    monkeypatch.setattr(
        delta_net, "gdn", lambda q, k, v, g, beta, qk_norm: plain(
            q, k, v, g, beta
        ),
    )


def _no_conv(monkeypatch):
    monkeypatch.setattr(
        delta_net, "silu_short_conv", lambda u, w: jax.nn.silu(u * w[-1])
    )


def _attention_change(**changes):
    def change(monkeypatch):
        plain = zoo.GroupedAttention

        def built(*args, **kwargs):
            return plain(*args, **{**kwargs, **changes})

        monkeypatch.setattr(zoo, "GroupedAttention", built)

    return change


def _gate_a_head(monkeypatch):
    """sigmoid of the MEAN of a head's gate columns: one gate a head, as
    `laguna.py`'s attention has it."""
    plain = jax.nn.sigmoid

    def sigmoid(x):
        if x.ndim == 4 and x.shape[-1] == CONFIG["head_dim"]:
            return plain(x.mean(axis=-1, keepdims=True))
        return plain(x)

    monkeypatch.setattr(decoder.jax.nn, "sigmoid", sigmoid)


def _moe_change(**changes):
    def change(monkeypatch):
        plain = zoo.MoEFFN

        def built(*args, **kwargs):
            return plain(*args, **{**kwargs, **changes})

        monkeypatch.setattr(zoo, "MoEFFN", built)

    return change


def _weights_not_renormalised(monkeypatch):
    """w_i = p_i: the sum over the chosen left out."""
    named = moe._named_flat

    class Unsummed:
        def __init__(self, picked):
            self.picked = picked

        def sum(self, axis, keepdims):
            return jnp.ones_like(self.picked[:, :1])

        def __rmul__(self, scale):
            return scale * self.picked

    def picked_unsummed(x, name):
        out = named(x, name)
        return Unsummed(out) if name == moe.PICKED_NAME else out

    monkeypatch.setattr(moe, "_named_flat", picked_unsummed)


CONTROLS = {
    "gate_before_norm": _gate_before_norm,
    "one_norm_over_all_heads": _one_norm_over_all_heads,
    "norm_scales_not_zero_centred": _plain_scales,
    "values_on_other_key_heads": _values_on_other_key_heads,
    "decay_dropped": _decay_dropped,
    "l2_norms_dropped": _l2_norms_dropped,
    "conv_dropped": _no_conv,
    "whole_head_rotated": dict(partial_rotary_factor=1.0),
    "nothing_rotated": _attention_change(rope=None),
    "gate_a_head": _gate_a_head,
    "sigmoid_scores": _moe_change(scores=moe.SIGMOID),
    "weights_not_renormalised": _weights_not_renormalised,
}


def float32_also(model, seeded, got):
    assert list(model.config.layers) == [True, True, True, False]
    assert set(seeded.variables) == {"params", STEP_METRICS}   # no buffer
    assert got["layer_0/gdn/qkvz/kernel"].shape == (32, 16 + 16 + 32 + 32)
    assert got["layer_0/gdn/ba/kernel"].shape == (32, 8)
    assert got["layer_0/gdn/conv_kernel"].shape == (4, 64)
    assert got["layer_0/gdn/A_log"].shape == (4,)
    assert got["layer_0/gdn/o_norm/scale"].shape == (8,)
    assert got["layer_3/attn/q/kernel"].shape == (32, 4 * 2 * 16)
    assert got["layer_3/attn/k/kernel"].shape == (32, 32)
    assert got["layer_3/attn/q_norm/scale"].shape == (16,)
    assert got["layer_1/moe/routed/router_kernel"].shape == (32, 16)
    assert got["layer_1/moe/routed/expert_w_gate_up"].shape == (8, 32, 48)
    assert got["layer_1/moe/shared_gate/kernel"].shape == (32, 1)


def published_also(model, config, shapes, flat, by_top):
    """Part by part, and every number of the catalog row under its own
    key."""
    held = config["layers_held"]
    assert held == [0, 1, 2, 3] and len(held) == config["num_hidden_layers"]
    c = model.config
    assert c.layers == (True, True, True, False)
    assert (c.num_experts, c.top_k, c.held_experts) == (512, 10, (0, 32))
    assert (c.heads, c.kv_heads, c.head_dim, c.rope.columns) == (
        16, 2, 256, 64
    )
    assert (c.gdn_key_heads, c.gdn_value_heads, c.gdn_head_dim) == (
        16, 32, 128
    )
    assert config["linear_value_head_dim"] == c.gdn_head_dim
    assert c.rope.inv_freq[-1] == pytest.approx(1e7 ** (-62 / 64))
    assert c.eps == 1e-6
    assert set(shapes) == {"params", STEP_METRICS}

    def part(prefix):
        return {
            k[len(prefix):]: v for k, v in flat.items() if k.startswith(prefix)
        }

    assert part("layer_0/gdn/") == {
        "qkvz/kernel": 25_165_824, "ba/kernel": 131_072,
        "conv_kernel": 32_768, "A_log": 32, "dt_bias": 32,
        "o_norm/scale": 128, "o/kernel": 8_388_608,
    }
    assert part("layer_3/attn/") == {
        "q/kernel": 16_777_216, "k/kernel": 1_048_576, "v/kernel": 1_048_576,
        "q_norm/scale": 256, "k_norm/scale": 256, "o/kernel": 8_388_608,
    }
    assert part("layer_1/moe/") == {
        "routed/router_kernel": 1_048_576,
        "routed/expert_w_gate_up": 32 * 2_097_152,
        "routed/expert_w_down": 32 * 1_048_576,
        "shared/gate_up/kernel": 2_097_152, "shared/down/kernel": 1_048_576,
        "shared_gate/kernel": 2_048,
    }


def trainer_gauges(metrics, state, loss, seeded):
    for layer in range(3):
        assert 0.0 < metrics[f"layer_{layer}/gdn/gdn_decay_mean_ratio"] < 1.0
        assert 0.0 < metrics[f"layer_{layer}/gdn/gdn_beta_mean_ratio"] < 1.0
    assert 0.0 < metrics["layer_3/attn/query_gate_mean_ratio"] < 1.0
    assert "layer_3/gdn/gdn_decay_mean_ratio" not in metrics
    for layer in range(4):
        path = f"layer_{layer}/moe"
        assert 0.0 < metrics[f"{path}/shared_gate_mean_ratio"] < 1.0
        assert metrics[f"{path}/routed/expert_load_imbalance_ratio"] >= 1.0
        assert 0.0 < metrics[f"{path}/routed/routed_here_ratio"] < 1.0
        assert metrics[f"{path}/routed/dropped_tokens"] == 0


def job_gauges(registry):
    assert 0.0 < registry.value(
        "worker_gdn_decay_mean_ratio", layer="layer_0/gdn"
    ) < 1.0
    assert 0.0 < registry.value(
        "worker_gdn_beta_mean_ratio", layer="layer_0/gdn"
    ) < 1.0
    assert 0.0 < registry.value(
        "worker_attention_query_gate_mean_ratio", layer="layer_1/attn"
    ) < 1.0
    for layer in range(2):
        assert 0.0 < registry.value(
            "worker_moe_shared_gate_mean_ratio", layer=f"layer_{layer}/moe"
        ) < 1.0
        assert 0.0 < registry.value(
            "worker_moe_routed_here_ratio", layer=f"layer_{layer}/moe/routed"
        ) < 1.0


def scopes_also(text):
    """The scan's gate scope is `decay`: `attn_proj_ms_per_step` takes
    every `*/gate`."""
    from elasticdl_tpu.common import profiler

    for part in ("router", "dispatch", "experts", "shared", "combine"):
        assert f"qwen3_next/moe/{part}" in text.replace("routed/", ""), part
    assert "qwen3_next/gdn/gate" not in profiler.DEVICE_SCOPES


DECODER = decoder_cases.Decoder(
    zoo=zoo, reference=reference, cell="qwen3-next-80b-a3b", config=CONFIG,
    # 80 positions: the scan's jnp form pads them to two chunks of 64
    length=80, seed=5,
    # two norms a layer beside a GDN mixer's 7 leaves or attention's 6,
    # and the routed layer's 6 (router, two stacks, the shared expert's
    # two kernels, its gate); the embedding, the untied head, the final
    # norm
    leaves=3 * GDN_LEAVES + ATTENTION_LEAVES + 4 * (EXPERT_LEAVES + 2) + 3,
    float32_also=float32_also,
    # one key head and two value heads of 128 at 128 positions (two
    # chunks: the state crosses a boundary), the SiLU conv at 512 columns,
    # the streaming attention at two query heads of 128 over one K/V head
    # with 32 columns rotated, and the routed layers, all interpreted here
    kernels=decoder_cases.Kernels(
        config=dict(
            hidden_size=128, linear_num_key_heads=1,
            linear_num_value_heads=2, linear_key_head_dim=128,
            linear_value_head_dim=128, num_attention_heads=2,
            num_key_value_heads=1, head_dim=128, layers_held=[2, 3],
            num_hidden_layers=2,
        ),
        length=128,
        admitted=(
            (gdn_ops.gdn_shapes_ok, (1, 128, 1, 128), (1, 128, 1, 128),
             (1, 128, 2, 128)),
            (short_conv.silu_conv_shapes_ok, (1, 128, 512), (4, 512)),
            (stream_shapes_ok, (1, 128, 2, 128), (1, 128, 1, 128),
             (1, 128, 1, 128)),
        ),
    ),
    # the gate before the output norm, one statistic over all heads, plain
    # norm scales, value heads on other key heads, no decay, no L2 norms,
    # no conv, the whole head rotated or none of it, one gate a head,
    # sigmoid scores, weights not renormalised
    controls=CONTROLS,
    published=decoder_cases.Published(
        by_top={
            "layer_0": 138_582_208, "layer_1": 138_582_208,
            "layer_2": 138_582_208, "layer_3": 132_127_232,
            "token_embedding": 38_895_616, "lm_head_kernel": 38_895_616,
            "final_norm": 2_048,
        },
        total=625_667_136, bytes_a_parameter=16, also=published_also,
    ),
    trainer_gauges=trainer_gauges,
    # the job's model is one block of each kind (published layers 2 and 3:
    # the delta rule, attention), each over the routed layer
    job=decoder_cases.Job(
        params=(
            "hidden=32;layers=[2,3];heads=4;kv_heads=2;head_dim=16;"
            "gdn_key_heads=2;gdn_value_heads=4;gdn_head_dim=8;"
            "expert_width=24;shared_width=24;num_experts=16;top_k=3;"
            "held_experts=[4,8];vocab_size=50;remat=True;lr=0.03"
        ),
        gauges=job_gauges,
    ),
    scopes=decoder_cases.Scopes(
        prefix="qwen3_next",
        names=("gdn/proj", "gdn/conv", "gdn/decay", "gdn/core", "gdn/out",
               "attn", "moe", "norm", "embed", "head_ce"),
        also=scopes_also,
    ),
)
model_of = DECODER.model_of
TestConformance = decoder_cases.conformance(DECODER)


def test_each_part_of_the_reference_is_seen(seeded):
    """The reference is held to the model above; this holds it to the
    configuration: the decay's A and the step's bias, the output norm's
    scale, a zero-centred scale, the shared gate, another held range,
    another top-k and another layer list each move what is computed."""
    features = {"input_ids": seeded.ids}

    def loss_with(config=CONFIG, **leaves):
        return reference.loss_and_grads(
            {**seeded.flat, **leaves}, features, None, config
        )[0]

    for leaf in ("layer_0/gdn/A_log", "layer_1/gdn/dt_bias",
                 "layer_2/gdn/o_norm/scale", "layer_3/attn/q_norm/scale",
                 "layer_3/attn/k_norm/scale", "layer_0/mix_norm/scale",
                 "layer_2/moe/shared_gate/kernel", "final_norm/scale"):
        assert abs(
            loss_with(**{leaf: seeded.flat[leaf] + np.log(2.0)})
            - seeded.want_loss
        ) > 1e-6, leaf
    for change in (dict(held_experts=[0, 8]), dict(num_experts_per_tok=2),
                   dict(partial_rotary_factor=0.5), dict(rope_theta=1e2)):
        assert abs(
            loss_with(dict(CONFIG, **change)) - seeded.want_loss
        ) > 1e-6, change
    # published layer 4 (a delta-rule layer) in layer 2's place is layer
    # 2 again; an attention layer in a delta-rule layer's place finds no
    # attention weights
    assert loss_with(dict(CONFIG, layers_held=[0, 1, 4, 3])) == (
        pytest.approx(seeded.want_loss, abs=1e-7)
    )
    with pytest.raises(KeyError):
        loss_with(dict(CONFIG, layers_held=[0, 1, 7, 3]))
    # the interval is read: at 2, layers 1 and 3 would be attention
    with pytest.raises(KeyError):
        loss_with(dict(CONFIG, full_attention_interval=2))


def test_the_interval_names_every_layer():
    model = zoo.custom_model(hidden=32, vocab_size=50)
    assert len(model.config.layers) == 48
    assert [i for i, is_gdn in enumerate(model.config.layers)
            if not is_gdn] == list(range(3, 48, 4))
    with pytest.raises(ValueError):
        model_of(CONFIG, layers=[48])
    with pytest.raises(ValueError):
        model_of(CONFIG, gdn_key_heads=3)
    with pytest.raises(ValueError):
        model_of(CONFIG, kv_heads=3)


# ---- through the system ---------------------------------------------------


# sha256 of str(make_jaxpr(value_and_grad(loss))) of the sibling cells'
# models at their published sizes (bfloat16, remat; abstract: nothing
# runs), the remat policy's address blanked, recorded at the commit before
# `RoutedExperts` learnt softmax scores, `MoEFFN` the shared gate,
# `GroupedAttention` its norms, turn and gate, and `GatedRMSNorm` its
# order (2260048): their programs are the parent's.  ISSUE 58 expected the
# six routed entries re-recorded; PR 58's probe found the walk's scatter-add
# slow at ONE width, 2,560 columns, which none of these has: all seven STAND.
# ALL SEVEN RE-RECORDED ON PURPOSE in PR 60: every one of them calls the
# streaming attention kernels, whose saved log-sum-exp is lane-major now
# (`ops/flash_attention.py`: the two kernels run inside `_stream_fwd_rows` /
# `_stream_bwd_rows` and hand a float32 (B, H, 1, L) row where a (B, H, L, 1)
# column was); nothing else of their programs moved (the commit before gave
# f75e00cd..., bdb4d28b..., 7aa997f0..., 62c29f19..., bd4a5d63..., e24bc98a...,
# 10e12632...), and `tests/test_flash_attention.py::
# test_the_lane_major_log_sum_exp_keeps_the_parents_bits` holds the kernels'
# results to the parent's bits.
# ALL SEVEN RE-RECORDED ON PURPOSE in PR 62: every one of them reads its head
# through `decoder.blocked_nll`, whose forward loop makes the gradient's two
# products beside the losses (a `custom_vjp`; no `checkpoint` around a
# block's logits any more); nothing else of their programs moved (the commit
# before gave 8da14260..., 7823daed..., 690fd4a6..., 0287421f..., 43cd4ec7...,
# e8401b42..., b223f00c...), and the attention calls' digests in
# `tests/test_flash_attention.py` stand.
# THE SIX ROUTED ONES RE-RECORDED ON PURPOSE in PR 65 (Granite's, with no
# routed layer, STANDS): `layers/moe.py: RoutedExperts` names what a block's
# backward reads of its routing and `decoder.SAVED_NAMES` keeps it, so each
# text gains five `name`s and three flat views' `reshape`s a routed layer,
# the score function's derivative reads the named array (`moe._kept`;
# Qwen3-Next's softmax is written out in `moe._softmax`), and the `checkpoint`
# equations' rebuilt forwards lose the router's product, the scores, `top_k`,
# the picks' gather, `expert_loads` and `argsort`; nothing else moved (the
# commit before gave 0c307c8e..., 6283dd03..., 7156f5ff..., 48dcc8a8...,
# d4beacaf..., 0a5b5ed0...), the gradients are the parent's bit for bit
# (`tests/test_remat_plan.py::test_a_rematerialised_routed_block_routes_once`)
# and the attention and scan calls' digests stand.
# THE FOUR THAT TURN THEIR QUERIES AND KEYS RE-RECORDED ON PURPOSE in PR 66
# (GLM, Laguna, LFM2, Qwen3-Next; Kimi's MLA carries no positions, Nemotron
# and Granite no rotary: all three STAND): `decoder.rotary_turn` is
# `ops/rotary.py`'s one-pass kernel at the cells' shapes, so each text gains
# a `rotary_turn` `pallas_call` for q and one for k a layer (a
# `custom_vjp`; its backward the same kernel as `rotary_turn_bwd`) over the
# (B, L, heads x dim) view and two (L, period) float32 tables, and loses the
# float32 halves' `split` / `concatenate` (and MLA's split and join of nope |
# rope: the kernel passes the 192 columns through); each attention layer
# sows one more constant (`rope_one_pass_ratio`); nothing else moved (the
# commit before gave da0d7384..., 7842ef69..., 6dcfa8f7..., 246718d5...).
# On the chip the kernel's results are the halves' bit for bit, forward and
# VJP, at every one of these shapes (`PERF.md` §6, PR 66), and
# `tests/test_rotary.py` holds it to them in the interpreter.
PARENTS_JAXPRS = {
    "kimi-linear-48b-a3b": (
        "kimi.kimi_linear", (2, 8192),
        # re-recorded on purpose in PR 54 (the program holds the KDA
        # kernels' bodies, whose substitution changed: `ops/kda.py`) and
        # in PR 56 (the KDA layer's norm a head, output gate and decay
        # over (B, L, heads x dim): `model_zoo/kimi/kimi_linear.py`)
        "da38540e7b7a3f2a3271acacbefdf16ecccef58e7a4983ac8c0949f8d60c866a",
    ),
    "nemotron-3-nano-30b-a3b": (
        "nemotron.nemotron_h", (2, 8192),
        "5d356b71ddf91c23d97fac2154dc04d7cec0ef8e5bda1e891d3ba712236fe982",
    ),
    "glm-4.7-flash": (
        "glm.glm_moe_lite", (4, 4096),
        "d5b268bd43f02491e5bb314273d93da8625e6a80726c62488c1d45c83a33d5a5",
    ),
    # the four below recorded at the commit before `RoutedExperts` and
    # `MoEFFN` learnt the routing's source, `FORMS` ReGLU and
    # `GroupedAttention` its band (87e4d23), where the three above read
    # what they read here
    "laguna-xs.2": (
        "laguna.laguna", (2, 8192),
        "b68d9770cb3753e5d0b14ad3fc3cb4219371e7a1025dd392485030a18c11e2d0",
    ),
    "lfm2-24b-a2b": (
        "lfm2.lfm2_moe", (4, 8192),
        "16b77a167fb2813ed329be2157f53d3e7116a5807f28f1a07017d09c21b5ac96",
    ),
    "qwen3-next-80b-a3b": (
        "qwen3_next.qwen3_next", (2, 8192),
        "2bb09e25a5ce3a1a239e70fe020df8725931d6e54e49f030863f9ca945960f3d",
    ),
    "granite-4.0-h-micro": (
        "granite.granite_hybrid", (1, 8192),
        "f4937eab9f6cdc257a27a6bb648daeac93ccea7d56a6149ef9bd313010e4b0f5",
    ),
}


@pytest.mark.parametrize("name", sorted(PARENTS_JAXPRS))
def test_a_sibling_cells_program_is_the_parents(name):
    import hashlib
    import importlib
    import re

    from elasticdl_tpu.common.model_handler import _call_with_params

    module, shape, digest = PARENTS_JAXPRS[name]
    sibling = importlib.import_module(f"model_zoo.{module}")
    config = decoder_cases.cell_config(name)
    model = _call_with_params(
        sibling.custom_model, config["model_params"].format(**config)
    )
    ids = jax.ShapeDtypeStruct(shape, jnp.int32)
    variables = jax.eval_shape(
        model.init, jax.random.PRNGKey(0), {"input_ids": ids}
    )
    state = {k: v for k, v in variables.items() if k != "params"}

    def loss_of(params, state, ids):
        out, _ = model.apply(
            {"params": params, **state}, {"input_ids": ids},
            mutable=MUTABLE,
        )
        return sibling.loss(None, out.astype(jnp.float32))

    text = str(jax.make_jaxpr(jax.value_and_grad(loss_of))(
        variables["params"], state, ids
    ))
    text = re.sub(r" at 0x[0-9a-f]+", "", text)
    assert "0x" not in text
    assert hashlib.sha256(text.encode()).hexdigest() == digest
