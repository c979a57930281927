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

import functools
import json
import os
import threading
import types

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import datagen, trees
from benchmarks.reference import nemotron_h as reference
from elasticdl_tpu.layers import moe
from elasticdl_tpu.layers.moe import ROUTER_STATE
from elasticdl_tpu.layers.step_metrics import AUX_LOSS, STEP_METRICS
from elasticdl_tpu.ops import short_conv
from elasticdl_tpu.ops import ssd as ssd_ops
from model_zoo.common import decoder, mamba
from model_zoo.nemotron import nemotron_h as zoo
from tests import remat_cases

ROOT = os.path.join(os.path.dirname(__file__), "..")
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
    held_experts=[4, 8], routed_scaling_factor=2.5, vocab_size=50,
    layer_norm_epsilon=1e-5, use_bf16=True,
)
MUTABLE = [AUX_LOSS, STEP_METRICS, ROUTER_STATE]
MAMBA_LEAVES, EXPERT_LEAVES, ATTENTION_LEAVES = 8, 5, 4


def model_of(config, **overrides):
    sizes = dict(
        hidden=config["hidden_size"],
        pattern=config["hybrid_override_pattern"],
        layers=config["layers_held"], heads=config["num_attention_heads"],
        kv_heads=config["num_key_value_heads"], head_dim=config["head_dim"],
        mamba_heads=config["mamba_num_heads"],
        mamba_head_dim=config["mamba_head_dim"],
        mamba_state=config["ssm_state_size"],
        mamba_groups=config["n_groups"], conv_kernel=config["conv_kernel"],
        expert_width=config["moe_intermediate_size"],
        shared_width=config["moe_shared_expert_intermediate_size"],
        num_experts=config["n_routed_experts_published"],
        top_k=config["num_experts_per_tok"],
        held_experts=config["held_experts"],
        routed_scaling=config["routed_scaling_factor"],
        vocab_size=config["vocab_size"], eps=config["layer_norm_epsilon"],
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
    # 80 positions: the scan's jnp form pads them to one chunk of 256
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
    assert list(model.config.layers) == ["M", "E", "M", "*", "E"]
    loss, got = loss_and_grads(model, seeded.variables, seeded.ids)
    assert abs(loss - seeded.want_loss) < 1e-4 * abs(seeded.want_loss)
    # ONE norm a layer beside its branch: a Mamba-2 mixer's 8 leaves, the
    # routed layer's 5 (router, two stacks, the shared expert's two
    # kernels), attention's 4; the embedding, the untied head and the
    # final norm
    assert len(got) == (
        2 * (MAMBA_LEAVES + 1) + 2 * (EXPERT_LEAVES + 1)
        + (ATTENTION_LEAVES + 1) + 3
    )
    assert "layer_1/moe/routed/expert_w_up" in got
    assert not any("gate" in name for name in got)
    assert got["layer_1/moe/routed/expert_w_up"].shape == (8, 32, 24)
    assert got["layer_1/moe/routed/router_kernel"].shape == (32, 16)
    assert got["layer_1/moe/shared/up/kernel"].shape == (32, 40)
    assert got["layer_3/attn/q/kernel"].shape == (32, 64)
    name, error = worst_leaf(got, seeded.want)
    assert error < 1e-4, (name, error)


def test_kernels_match_reference_leaf_by_leaf():
    """Four state-space heads of 64 in TWO groups over 128 state columns
    at 512 positions (two chunks: the state crosses a boundary; a grid
    step a group), the biased SiLU conv at 768 columns, the streaming
    attention at two query heads of 128 over one K/V head, and a routed
    layer, all interpreted here."""
    from elasticdl_tpu.ops.flash_attention import stream_shapes_ok

    config = dict(
        CONFIG, hidden_size=128, mamba_num_heads=4, mamba_head_dim=64,
        ssm_state_size=128, n_groups=2, num_attention_heads=2,
        num_key_value_heads=1, head_dim=128, layers_held=[0, 1, 5],
        num_hidden_layers=3,
    )
    assert ssd_ops.ssd_shapes_ok((1, 512, 4, 64), (1, 512, 2, 128))
    assert short_conv.silu_conv_shapes_ok((1, 512, 768), (4, 768), True)
    assert stream_shapes_ok((1, 512, 2, 128), (1, 512, 1, 128),
                            (1, 512, 1, 128))
    seeded = seeded_of(config, ids_of(1, length=512, seed=2))
    loss, got = loss_and_grads(model_of(config), seeded.variables, seeded.ids)
    assert abs(loss - seeded.want_loss) < 1e-4 * abs(seeded.want_loss)
    name, error = worst_leaf(got, seeded.want)
    assert error < 2e-4, (name, error)


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
    take = jnp.take_along_axis

    def taken(values, idx, axis):
        out = take(values, idx, axis=axis)
        # the router's picked scores alone: (tokens, top_k) of all experts'
        routers = (values.shape[1], idx.shape[1]) == (
            CONFIG["n_routed_experts_published"],
            CONFIG["num_experts_per_tok"],
        )
        return _Unsummed(out) if routers else out

    monkeypatch.setattr(moe.jnp, "take_along_axis", taken)


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


@pytest.mark.parametrize("control", sorted(CONTROLS))
def test_a_departure_from_the_mathematics_fails_the_comparison(
        seeded, monkeypatch, control):
    """The comparison that passes the model fails each of these: one norm
    over all the mixer's channels, the norm before the gate, a gated
    expert (silu(u) * u), the ReLU unsquared, the scaling 2.5 dropped, the
    weights not renormalised, a rotary applied, query heads grouped
    otherwise, the conv's bias dropped, a second branch in a layer."""
    change = CONTROLS[control]
    overrides = change if isinstance(change, dict) else {}
    if not overrides:
        change(monkeypatch)
    variables = seeded.variables
    if control == "second_branch_in_a_layer":
        variables = {**variables, "params": {
            name: {"inner": leaf} if name.startswith("layer_") else leaf
            for name, leaf in variables["params"].items()
        }}
    loss, got = loss_and_grads(
        model_of(CONFIG, **overrides), variables, seeded.ids
    )
    got = {name.replace("/inner/", "/"): leaf for name, leaf in got.items()}
    name, error = worst_leaf(got, seeded.want)
    assert (
        abs(loss - seeded.want_loss) > 1e-3 * abs(seeded.want_loss)
        or error > 1e-2
    ), (control, loss, seeded.want_loss, name, error)


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
    `deployment` and its `parameters_held`, part by part; the pattern
    string reaches `custom_model` raw through `--model_params`."""
    with open(os.path.join(
        ROOT, "benchmarks", "configs", "nemotron-3-nano-30b-a3b.json"
    )) as f:
        config = json.load(f)
    from elasticdl_tpu.common.model_handler import _call_with_params

    model = _call_with_params(
        zoo.custom_model, config["model_params"].format(**config)
    )
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
    assert f"{total:,}" in config["deployment"]
    assert total == {9: 666_962_944, 7: 528_092_736}[len(held)]
    assert 12 * total > 0.25 * 16.9e9          # over the floor, held alone


# ---- through the system ---------------------------------------------------


def test_trainer_carries_each_layer_kinds_gauges(seeded):
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
            "--model_def", "nemotron.nemotron_h.custom_model",
            "--model_params",
            "hidden=32;pattern=MEMEM*E;layers=[0,1,5,6];heads=4;kv_heads=2;"
            "head_dim=16;mamba_heads=4;mamba_head_dim=8;mamba_state=16;"
            "mamba_groups=2;expert_width=24;shared_width=40;num_experts=16;"
            "top_k=3;held_experts=[4,8];vocab_size=50;remat=True;lr=0.03",
            "--distribution_strategy", "Local", "--training_data", path,
            "--minibatch_size", "8", "--records_per_task", "64",
            "--num_epochs", "1",
        ])
    finally:
        Worker.__init__ = init
    assert rc == 0
    losses = [float(x) for x in workers[0].losses]
    assert len(losses) == 16                      # two tasks of 8 steps
    assert np.mean(losses[-4:]) < np.mean(losses[:4]) - 0.05
    registry = metrics_lib.default_registry()
    assert 0.0 < registry.value(
        "worker_ssm_state_kept_ratio", layer="layer_0/mamba"
    ) < 1.0
    for layer in (1, 3):
        assert 0.0 < registry.value(
            "worker_moe_routed_here_ratio", layer=f"layer_{layer}/moe/routed"
        ) < 1.0
    assert registry.value("worker_remat_kept_ratio") == 1.0


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
GRANITE_JAXPR = (
    "72aabf1114aa7f9906c6d53608f3b911ce62052b58de52214d786c9ff66f8494"
)


def test_granites_program_is_the_parents():
    import hashlib
    import re

    from elasticdl_tpu.common.model_handler import _call_with_params
    from model_zoo.granite import granite_hybrid

    with open(os.path.join(
        ROOT, "benchmarks", "configs", "granite-4.0-h-micro.json"
    )) as f:
        config = json.load(f)
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
