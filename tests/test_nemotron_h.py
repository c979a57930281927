"""The Nemotron-H decoder (model_zoo/nemotron/nemotron_h.py) at tiny widths
on the CPU, seeded weights: layers that are ONE norm and ONE branch
(Mamba-2 with B and C in groups and a norm statistic a group | routed
squared-ReLU experts without a gate beside a shared expert of its own
width | grouped-query attention without positions at heads wider than
hidden / heads) and the untied head against the plain float32 reference
leaf by leaf (its Mamba-2 the token-by-token recurrence by group, its
experts a dense sum), through the jnp forms and through the interpreted
kernels; the SHARE test (every holder's routed part plus the shared expert
once is the uncut layer); controls that each part of the mathematics must
fail; bfloat16 inside the twin's rule; the sown gauges by layer kind; the
published sizes' parameter count; the pattern string through
`--model_params`; and a two-task job through the CLI."""

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import nemotron_h as reference
from elasticdl_tpu.layers import moe
from elasticdl_tpu.ops import short_conv
from elasticdl_tpu.ops import ssd as ssd_ops
from elasticdl_tpu.ops.flash_attention import stream_shapes_ok
from model_zoo.common import decoder, mamba
from model_zoo.nemotron import nemotron_h as zoo
from tests import decoder_cases
from tests.decoder_cases import MUTABLE, computed, seeded  # noqa: F401

# the published pattern's first seven letters with five of them held
# (`MEM*E`): 4 state-space heads of 8 over 16 state columns in 2 groups,
# 4 query heads of 16 (hidden / heads is 8) over 2 K/V heads, top-3 of 16
# experts 24 wide with 8 held, a shared expert 40 wide
CONFIG = dict(
    hidden_size=32, num_hidden_layers=5, hybrid_override_pattern="MEMEM*E",
    layers_held=[0, 1, 2, 5, 6], num_attention_heads=4,
    num_key_value_heads=2, head_dim=16, mamba_num_heads=4, mamba_head_dim=8,
    ssm_state_size=16, n_groups=2, conv_kernel=4, moe_intermediate_size=24,
    moe_shared_expert_intermediate_size=40, n_routed_experts=8,
    n_routed_experts_published=16, num_experts_per_tok=3,
    held_experts=[4, 8], routed_scaling_factor=2.5, bias_update_rate=0.0,
    vocab_size=50, layer_norm_epsilon=1e-5, learning_rate=1e-3,
    use_bf16=True,
)
MAMBA_LEAVES, EXPERT_LEAVES, ATTENTION_LEAVES = 8, 5, 4


# ---- the share: what each of 16 holders computes, and the shared expert ----


def test_sixteen_holders_and_one_shared_expert_are_the_uncut_layer():
    """Expert parallelism's partial sums: the routed parts of all 16
    holders (two experts of 32 each) plus the shared expert counted ONCE
    equal the uncut reference's whole expert layer; a holder's own output
    is its part plus the shared expert, as every holder computes it."""
    hidden, experts, width, top_k, holders = 32, 32, 24, 6, 16
    each = experts // holders
    x = jnp.asarray(np.random.RandomState(1).randn(3, 40, hidden), jnp.float32)

    def layer(held):
        return decoder.MoEFFN(
            hidden, experts, top_k, width, 1, held, 2.5, 0.0, jnp.float32,
            "nemotron/moe", form=moe.RELU2, shared_width=40,
        )

    whole = layer(None).init(jax.random.PRNGKey(3), x)["params"]
    assert whole["routed"]["expert_w_up"].shape == (experts, hidden, width)
    sizes = reference.sizes_of(dict(
        CONFIG, num_experts_per_tok=top_k, held_experts=[0, experts],
    ), None)
    shared = whole["shared"]
    with jax.default_matmul_precision("highest"):
        plain = lambda t: t
        want_shared = jax.vmap(lambda row: reference.relu2_mlp(
            row, shared["up"]["kernel"], shared["down"]["kernel"], plain
        ))(x)
        want = jax.vmap(lambda row: reference.routed(
            row, whole["routed"], sizes, plain
        ))(x) + want_shared
        parts = []
        for holder in range(holders):
            first = holder * each
            routed = dict(
                whole["routed"], **{
                    name: whole["routed"][name][first:first + each]
                    for name in ("expert_w_up", "expert_w_down")
                },
            )
            out, _ = layer((first, each)).apply(
                {"params": {"routed": routed, "shared": shared}}, x,
                mutable=MUTABLE,
            )
            parts.append(out - want_shared)
    total = sum(parts) + want_shared
    np.testing.assert_allclose(total, want, rtol=2e-5, atol=2e-6)
    # no holder alone is the layer, and the shared expert is not nothing
    assert np.abs(parts[0] + want_shared - want).max() > 0.01
    assert np.abs(want_shared).max() > 0.01


# ---- controls: each part of the mathematics must fail the comparison ------


def _one_norm_over_all_channels(monkeypatch):
    plain = mamba.GatedRMSNorm
    monkeypatch.setattr(
        mamba, "GatedRMSNorm",
        lambda eps, dtype, groups, name: plain(eps, dtype, 1, name=name),
    )


def _norm_before_gate(monkeypatch):
    class NormThenGate(mamba.GatedRMSNorm):
        @nn.compact
        def __call__(self, y, z):
            scale = self.param("scale", nn.initializers.ones, (y.shape[-1],))
            by_group = (*y.shape[:-1], self.groups, -1)
            normed = decoder.rms_norm(
                y.reshape(by_group), scale.reshape(self.groups, -1), self.eps
            ).reshape(y.shape)
            return (normed * jax.nn.silu(z)).astype(self.dtype)

    monkeypatch.setattr(mamba, "GatedRMSNorm", NormThenGate)


def _activation(act):
    """Every expert's activation, routed and shared alike."""
    def change(monkeypatch):
        name, width, _ = moe.FORMS[moe.RELU2]
        monkeypatch.setitem(moe.FORMS, moe.RELU2, (name, width, act))

        class Shared(decoder.ReLU2MLP):
            @nn.compact
            def __call__(self, x):
                up = decoder.dense(self.width, "up", self.dtype)(x)
                return decoder.dense(self.hidden, "down", self.dtype)(act(up))

        monkeypatch.setitem(decoder.MLP_OF_FORM, moe.RELU2, Shared)

    return change


class _Unsummed:
    """The picked scores, whose sum over the chosen reads 1."""

    def __init__(self, picked):
        self.picked = picked

    def sum(self, axis, keepdims):
        return jnp.ones_like(self.picked[:, :1])

    def __rmul__(self, scale):
        return scale * self.picked


def _weights_not_renormalised(monkeypatch):
    """w_i = 2.5 s_i: the sum over the chosen six left out."""
    named = moe._named_flat

    def picked_unsummed(x, name):
        out = named(x, name)
        return _Unsummed(out) if name == moe.PICKED_NAME else out

    monkeypatch.setattr(moe, "_named_flat", picked_unsummed)


def _rotated(monkeypatch):
    """A rotary turn of q and k that the model is assumed not to have."""
    plain = decoder.flash_attention.causal_attention
    monkeypatch.setattr(
        decoder.flash_attention, "causal_attention",
        lambda q, k, v, scale, window=None: plain(
            decoder.rotary(q, 1e4), decoder.rotary(k, 1e4), v, scale=scale
        ),
    )


def _other_query_heads_a_kv_head(monkeypatch):
    """Query head h reading K/V head h % 2 (the heads interleaved), not h
    // 2: another grouping of the same weights."""
    plain = decoder.flash_attention.causal_attention

    def regrouped(q, k, v, scale, window=None):
        order = jnp.asarray([0, 2, 1, 3])
        return plain(q[:, :, order], k, v, scale=scale)[:, :, order]

    monkeypatch.setattr(
        decoder.flash_attention, "causal_attention", regrouped
    )


def _no_conv_bias(monkeypatch):
    plain = mamba.silu_short_conv
    monkeypatch.setattr(
        mamba, "silu_short_conv", lambda u, w, b: plain(u, w)
    )


def _second_branch(monkeypatch):
    """A layer whose branch runs twice (its weights again on what it
    made), as a block of mixer AND feed-forward part would."""
    plain = zoo.Block

    class Twice(nn.Module):
        config: zoo.NemotronConfig
        kind: str

        @nn.compact
        def __call__(self, x):
            layer = plain(self.config, self.kind, name="inner")
            return layer(layer(x))

    monkeypatch.setattr(zoo, "Block", Twice)


CONTROLS = {
    "one_norm_over_all_channels": _one_norm_over_all_channels,
    "norm_before_gate": _norm_before_gate,
    "experts_gated": _activation(lambda up: jax.nn.silu(up) * up),
    "relu_unsquared": _activation(jax.nn.relu),
    "scaling_dropped": dict(routed_scaling=1.0),
    "weights_not_renormalised": _weights_not_renormalised,
    "rotary_applied": _rotated,
    "other_query_heads_a_kv_head": _other_query_heads_a_kv_head,
    "conv_bias_dropped": _no_conv_bias,
    "second_branch_in_a_layer": _second_branch,
}


def float32_also(model, seeded, got):
    assert list(model.config.layers) == ["M", "E", "M", "*", "E"]
    assert "layer_1/moe/routed/expert_w_up" in got
    assert not any("gate" in name for name in got)
    assert got["layer_1/moe/routed/expert_w_up"].shape == (8, 32, 24)
    assert got["layer_1/moe/routed/router_kernel"].shape == (32, 16)
    assert got["layer_1/moe/shared/up/kernel"].shape == (32, 40)
    assert got["layer_3/attn/q/kernel"].shape == (32, 64)


def twice_the_branch(control, variables):
    """The second branch's block holds a layer's leaves under `inner`."""
    if control != "second_branch_in_a_layer":
        return variables
    return {**variables, "params": {
        name: {"inner": leaf} if name.startswith("layer_") else leaf
        for name, leaf in variables["params"].items()
    }}


def published_also(model, config, shapes, flat, by_top):
    """Part by part; the pattern string reaches `custom_model` raw
    through `--model_params`."""
    held = config["layers_held"]
    assert config["hybrid_override_pattern"] == zoo.PUBLISHED_PATTERN
    assert "".join(model.config.layers) == "".join(
        zoo.PUBLISHED_PATTERN[i] for i in held
    ) == "MEMEM*EME"[:len(held)]
    assert len(held) == config["num_hidden_layers"]
    assert len(zoo.PUBLISHED_PATTERN) == config["num_hidden_layers_published"]
    c = model.config
    assert (c.num_experts, c.top_k, c.held_experts, c.routed_scaling) == (
        128, 6, (0, 8), 2.5
    )
    assert (c.heads, c.kv_heads, c.head_dim, c.mamba_groups) == (32, 2, 128, 8)
    sizes = {"M": 38_744_896, "E": 100_125_312, "*": 23_399_040}
    assert by_top == {
        **{f"layer_{i}": sizes[kind]
           for i, kind in enumerate(model.config.layers)},
        "token_embedding": 44_040_192, "lm_head_kernel": 44_040_192,
        "final_norm": 2_688,
    }
    mixer = {
        k.split("/", 2)[2]: v for k, v in flat.items()
        if k.startswith("layer_0/mamba/")
    }
    assert mixer == {
        "in_proj/kernel": 27_697_152, "conv_kernel": 24_576,
        "conv_bias": 6_144, "A_log": 64, "D": 64, "dt_bias": 64,
        "norm/scale": 4_096, "out_proj/kernel": 11_010_048,
    }
    experts = {
        k.split("/", 2)[2]: v for k, v in flat.items()
        if k.startswith("layer_1/moe/")
    }
    assert experts == {
        "routed/router_kernel": 344_064,
        "routed/expert_w_up": 8 * 4_988_928,
        "routed/expert_w_down": 8 * 4_988_928,
        "shared/up/kernel": 9_977_856, "shared/down/kernel": 9_977_856,
    }
    assert {
        k.split("/", 2)[2]: v for k, v in flat.items()
        if k.startswith("layer_5/attn/")
    } == {
        "q/kernel": 11_010_048, "k/kernel": 688_128, "v/kernel": 688_128,
        "o/kernel": 11_010_048,
    }
    total = sum(by_top.values())
    assert total == config["parameters_held"]
    assert total == {9: 666_962_944, 7: 528_092_736}[len(held)]


def trainer_gauges(metrics, state, loss, seeded):
    for layer in (0, 2):
        assert 0.0 < metrics[f"layer_{layer}/mamba/ssm_state_kept_ratio"] < 1.0
    for layer in (1, 4):
        path = f"layer_{layer}/moe/routed"
        assert metrics[f"{path}/expert_load_imbalance_ratio"] >= 1.0
        assert 0.0 < metrics[f"{path}/routed_here_ratio"] < 1.0
        assert metrics[f"{path}/live_chunks_ratio"] == 1.0
        # tiny widths; a constant, set as the step was traced and not sown
        assert moe.padded_work_ratio.value(layer=path) > 0.0
        assert f"{path}/padded_work_ratio" not in metrics
        assert metrics[f"{path}/dropped_tokens"] == 0
    assert not any(name.startswith("layer_3/") for name in metrics)  # `*`


def job_gauges(registry):
    assert 0.0 < registry.value(
        "worker_ssm_state_kept_ratio", layer="layer_0/mamba"
    ) < 1.0
    for layer in (1, 3):
        assert 0.0 < registry.value(
            "worker_moe_routed_here_ratio", layer=f"layer_{layer}/moe/routed"
        ) < 1.0


DECODER = decoder_cases.Decoder(
    zoo=zoo, reference=reference, cell="nemotron-3-nano-30b-a3b",
    config=CONFIG,
    # 80 positions: the scan's jnp form pads them to one chunk of 256
    length=80, seed=5,
    # ONE norm a layer beside its branch: a Mamba-2 mixer's 8 leaves, the
    # routed layer's 5 (router, two stacks, the shared expert's two
    # kernels), attention's 4; the embedding, the untied head and the
    # final norm
    leaves=(
        2 * (MAMBA_LEAVES + 1) + 2 * (EXPERT_LEAVES + 1)
        + (ATTENTION_LEAVES + 1) + 3
    ),
    float32_also=float32_also,
    # four state-space heads of 64 in TWO groups over 128 state columns
    # at 512 positions (two chunks: the state crosses a boundary; a grid
    # step a group), the biased SiLU conv at 768 columns, the streaming
    # attention at two query heads of 128 over one K/V head, and a routed
    # layer, all interpreted here
    kernels=decoder_cases.Kernels(
        config=dict(
            hidden_size=128, mamba_num_heads=4, mamba_head_dim=64,
            ssm_state_size=128, n_groups=2, num_attention_heads=2,
            num_key_value_heads=1, head_dim=128, layers_held=[0, 1, 5],
            num_hidden_layers=3,
        ),
        length=512,
        admitted=(
            (ssd_ops.ssd_shapes_ok, (1, 512, 4, 64), (1, 512, 2, 128)),
            (short_conv.silu_conv_shapes_ok, (1, 512, 768), (4, 768), True),
            (stream_shapes_ok, (1, 512, 2, 128), (1, 512, 1, 128),
             (1, 512, 1, 128)),
        ),
    ),
    # one norm over all the mixer's channels, the norm before the gate, a
    # gated expert (silu(u) * u), the ReLU unsquared, the scaling 2.5
    # dropped, the weights not renormalised, a rotary applied, query heads
    # grouped otherwise, the conv's bias dropped, a second branch in a
    # layer
    controls=CONTROLS, control_variables=twice_the_branch,
    control_leaves=lambda control, got: {
        name.replace("/inner/", "/"): leaf for name, leaf in got.items()
    },
    published=decoder_cases.Published(
        by_top=None, total=None, also=published_also,
    ),
    trainer_gauges=trainer_gauges,
    job=decoder_cases.Job(
        params=(
            "hidden=32;pattern=MEMEM*E;layers=[0,1,5,6];heads=4;kv_heads=2;"
            "head_dim=16;mamba_heads=4;mamba_head_dim=8;mamba_state=16;"
            "mamba_groups=2;expert_width=24;shared_width=40;num_experts=16;"
            "top_k=3;held_experts=[4,8];vocab_size=50;remat=True;lr=0.03"
        ),
        gauges=job_gauges,
    ),
)
model_of = DECODER.model_of
TestConformance = decoder_cases.conformance(DECODER)


def test_each_part_of_the_reference_is_seen(seeded):
    """The reference is held to the model above; this holds it to the
    configuration: the decay's A, the skip D, the step's bias, the conv's
    bias, the gated norm's scale, another held range, another top-k and
    another layer list each move what is computed."""
    features = {"input_ids": seeded.ids}

    def loss_with(config=CONFIG, **leaves):
        return reference.loss_and_grads(
            {**seeded.flat, **leaves}, features, None, config
        )[0]

    for leaf in ("layer_0/mamba/A_log", "layer_0/mamba/D",
                 "layer_2/mamba/dt_bias", "layer_2/mamba/conv_bias",
                 "layer_0/mamba/norm/scale"):
        assert abs(
            loss_with(**{leaf: seeded.flat[leaf] + np.log(2.0)})
            - seeded.want_loss
        ) > 1e-6, leaf
    for change in (dict(held_experts=[0, 8]), dict(num_experts_per_tok=2),
                   dict(routed_scaling_factor=1.0)):
        assert abs(
            loss_with(dict(CONFIG, **change)) - seeded.want_loss
        ) > 1e-6, change
    # published layer 4 (`M`) in layer 2's place is layer 2 again; an
    # expert layer in a Mamba-2 layer's place finds no expert weights
    assert loss_with(dict(CONFIG, layers_held=[0, 1, 4, 5, 6])) == (
        pytest.approx(seeded.want_loss, abs=1e-7)
    )
    with pytest.raises(KeyError):
        loss_with(dict(CONFIG, layers_held=[0, 1, 3, 5, 6]))


def test_the_pattern_names_every_layer():
    assert len(zoo.PUBLISHED_PATTERN) == 52
    counts = {kind: zoo.PUBLISHED_PATTERN.count(kind) for kind in zoo.KINDS}
    assert counts == {"M": 23, "E": 23, "*": 6}
    assert [i for i, kind in enumerate(zoo.PUBLISHED_PATTERN)
            if kind == "*"] == [5, 12, 19, 26, 33, 42]
    with pytest.raises(ValueError):
        model_of(CONFIG, pattern="ME-M", layers=[0])
    with pytest.raises(ValueError):
        model_of(CONFIG, layers=[7])
    with pytest.raises(ValueError):
        model_of(CONFIG, mamba_groups=3)


# ---- through the system ---------------------------------------------------


@pytest.mark.parametrize("prefix", ["nemotron/ssm", "granite/ssm"])
def test_the_mixers_scopes_reach_the_lowered_operations(prefix):
    """The shared mixer's five scopes carry the MODEL's prefix into the
    operations' names (a field called `scope` would be flax's own, and
    the device time of every Mamba-2 layer would read as no scope's)."""
    mixer = mamba.Mamba2(32, 4, 8, 16, 2, 4, 1e-5, jnp.float32, prefix)
    x = jnp.zeros((1, 64, 32))
    variables = mixer.init(jax.random.PRNGKey(0), x)
    text = jax.jit(
        lambda v, x: mixer.apply(v, x, mutable=MUTABLE)[0]
    ).lower(variables, x).as_text(debug_info=True)
    for part in ("proj", "conv", "core", "gated_norm", "out"):
        assert f"{prefix}/{part}/" in text, part
    assert "Scope object" not in text


# sha256 of str(make_jaxpr(value_and_grad(loss))) of the Granite cell's
# model at its published sizes (ten layers, bfloat16, remat, one sequence
# of 8,192; abstract: nothing runs), the remat policy's address blanked,
# recorded at the commit before the mixer moved to `common/mamba.py`
# (1daa31a): Granite's program is the parent's, the scan's one-group
# kernels, the norm over all channels and every projection.
# RE-RECORDED ON PURPOSE in PR 60 (the attention layers' streaming kernels
# save their log-sum-exp lane-major, a float32 (B, H, 1, L) row from inside
# `_stream_fwd_rows`: `ops/flash_attention.py`; the commit before gave
# ee43040c...); the Mamba-2 layers' text is as it was.
# RE-RECORDED ON PURPOSE in PR 62 (the cross-entropy over the tied table makes
# its gradient in the pass that makes the logits: `decoder.blocked_nll`; the
# commit before gave 72aabf11...); every layer's text is as it was.
GRANITE_JAXPR = (
    "9c0711c238b841c89477dfda9c82ffc50191a8029fef83941aa6725027380502"
)


def test_granites_program_is_the_parents():
    import hashlib
    import re

    from elasticdl_tpu.common.model_handler import _call_with_params
    from model_zoo.granite import granite_hybrid

    config = decoder_cases.cell_config("granite-4.0-h-micro")
    model = _call_with_params(
        granite_hybrid.custom_model, config["model_params"].format(**config)
    )
    ids = jax.ShapeDtypeStruct((1, 8192), jnp.int32)
    variables = jax.eval_shape(
        model.init, jax.random.PRNGKey(0), {"input_ids": ids}
    )

    def loss_of(params, ids):
        out, _ = model.apply(
            {"params": params}, {"input_ids": ids}, mutable=MUTABLE
        )
        return granite_hybrid.loss(None, out.astype(jnp.float32))

    text = str(jax.make_jaxpr(jax.value_and_grad(loss_of))(
        variables["params"], ids
    ))
    text = re.sub(r" at 0x[0-9a-f]+", "", text)
    assert "0x" not in text
    assert hashlib.sha256(text.encode()).hexdigest() == GRANITE_JAXPR
