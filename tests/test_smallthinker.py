"""The SmallThinker decoder (model_zoo/smallthinker/smallthinker.py) at
tiny widths on the CPU, seeded weights: a router that reads the block's
input ahead of attention, ReGLU experts under a softmax over the chosen,
grouped attention at a group of three that sees a band with rotary in
three layers of four and the whole sequence with no positions in the
fourth, against the plain float32 reference leaf by leaf, through the jnp
forms and through the interpreted kernels (a group of seven there); each
mechanism alone; the SHARE test (the eight holders' routed parts are the
uncut layer); controls that each part of the mathematics must fail;
bfloat16 inside the twin's rule; the sown gauges; the published sizes'
parameter count; and a two-task job through the CLI.  (The routed siblings'
programs, which the routing's new argument may not move, are held by
tests/test_qwen3_next.py: `PARENTS_JAXPRS`.)"""

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
from benchmarks.reference import smallthinker as reference
from elasticdl_tpu.layers import moe
from elasticdl_tpu.layers.step_metrics import AUX_LOSS, STEP_METRICS
from model_zoo.common import decoder
from model_zoo.smallthinker import smallthinker as zoo
from tests import remat_cases

ROOT = os.path.join(os.path.dirname(__file__), "..")
LAYOUT = [0, 1, 1, 1, 0, 1, 1, 1]
# one whole period of the published pattern (full, band, band, band): 6
# query heads of 16 over 2 K/V heads (groups of 3), a band of 24 over 64
# positions, top-3 of 16 softmax-routed ReGLU experts 24 wide with 8 held
CONFIG = dict(
    hidden_size=32, num_hidden_layers=4, layers_held=[0, 1, 2, 3],
    sliding_window_layout=LAYOUT, rope_layout=LAYOUT,
    num_attention_heads=6, num_key_value_heads=2, head_dim=16,
    sliding_window_size=24, rope_theta=1.5e6, moe_ffn_hidden_size=24,
    moe_num_primary_experts=8, moe_num_primary_experts_published=16,
    moe_num_active_primary_experts=3, held_experts=[4, 8], vocab_size=50,
    rms_norm_eps=1e-6, use_bf16=True,
)
# (`ROUTER_STATE` is no collection of this model: the sigmoid-scored
# controls and the sibling models fill it)
MUTABLE = [AUX_LOSS, STEP_METRICS, moe.ROUTER_STATE]
ATTENTION_LEAVES, EXPERT_LEAVES = 4, 3


def model_of(config, **overrides):
    sizes = dict(
        hidden=config["hidden_size"],
        num_layers=len(config["sliding_window_layout"]),
        sliding_window_layout=config["sliding_window_layout"],
        rope_layout=config["rope_layout"], layers=config["layers_held"],
        heads=config["num_attention_heads"],
        kv_heads=config["num_key_value_heads"], head_dim=config["head_dim"],
        window=config["sliding_window_size"],
        rope_theta=config["rope_theta"],
        expert_width=config["moe_ffn_hidden_size"],
        num_experts=config["moe_num_primary_experts_published"],
        top_k=config["moe_num_active_primary_experts"],
        held_experts=config["held_experts"],
        vocab_size=config["vocab_size"], eps=config["rms_norm_eps"],
        remat=True,
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
    assert list(model.config.layers) == [False, True, True, True]
    assert set(seeded.variables) == {"params", STEP_METRICS}   # no buffer
    loss, got = loss_and_grads(model, seeded.variables, seeded.ids)
    assert abs(loss - seeded.want_loss) < 1e-4 * abs(seeded.want_loss)
    # two norms a layer beside attention's 4 kernels and the routed
    # layer's 3 (router, two stacks: no shared expert); the embedding, the
    # untied head, the final norm
    assert len(got) == 4 * (ATTENTION_LEAVES + EXPERT_LEAVES + 2) + 3
    assert got["layer_0/attn/q/kernel"].shape == (32, 6 * 16)
    assert got["layer_0/attn/k/kernel"].shape == (32, 2 * 16)
    assert got["layer_0/attn/o/kernel"].shape == (6 * 16, 32)
    assert got["layer_1/moe/routed/router_kernel"].shape == (32, 16)
    assert got["layer_1/moe/routed/expert_w_gate_up"].shape == (8, 32, 48)
    assert got["layer_1/moe/routed/expert_w_down"].shape == (8, 24, 32)
    name, error = worst_leaf(got, seeded.want)
    assert error < 1e-4, (name, error)


def test_kernels_match_reference_leaf_by_leaf():
    """A group of SEVEN query heads of 128 over one K/V head at two tiles
    of 128 positions: the streaming kernels (interpreted here), a full
    layer with no positions and a band layer whose band is longer than a
    tile and shorter than the sequence."""
    from elasticdl_tpu.ops.flash_attention import stream_shapes_ok

    config = dict(
        CONFIG, hidden_size=64, num_attention_heads=7,
        num_key_value_heads=1, head_dim=128, sliding_window_size=160,
        layers_held=[0, 1], num_hidden_layers=2,
    )
    assert stream_shapes_ok((1, 256, 7, 128), (1, 256, 1, 128),
                            (1, 256, 1, 128))
    seeded = seeded_of(config, ids_of(1, length=256, seed=2))
    loss, got = loss_and_grads(model_of(config), seeded.variables, seeded.ids)
    assert abs(loss - seeded.want_loss) < 1e-4 * abs(seeded.want_loss)
    name, error = worst_leaf(got, seeded.want)
    assert error < 2e-4, (name, error)


# ---- each mechanism alone --------------------------------------------------


def test_top_six_then_softmax_is_softmax_then_renormalise():
    """The published routing (the top k of the logits, a softmax over
    them) and the program's (a softmax over all, the picked renormalised)
    are one number, and the layer with every expert held and its routing
    read from ANOTHER tensor than its rows is the reference's dense sum."""
    hidden, experts, width, top_k = 32, 16, 24, 3
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(2, 24, hidden), jnp.float32)
    source = jnp.asarray(rng.randn(2, 24, hidden), jnp.float32)
    layer = moe.RoutedExperts(
        num_experts=experts, top_k=top_k, ffn_dim=width, form=moe.REGLU,
        scores=moe.SOFTMAX,
    )
    variables = layer.init(jax.random.PRNGKey(1), x, source)
    assert set(variables) == {"params", STEP_METRICS}
    params = variables["params"]
    rows, routes = x.reshape(-1, hidden), source.reshape(-1, hidden)
    with jax.default_matmul_precision("highest"):
        chosen, weights = reference.routing(
            routes, params["router_kernel"], top_k
        )
        np.testing.assert_allclose(weights.sum(axis=1), 1.0, rtol=1e-6)
        scores = jax.nn.softmax(routes @ params["router_kernel"], axis=-1)
        _, picked_idx = jax.lax.top_k(scores, top_k)
        np.testing.assert_array_equal(picked_idx, chosen)
        picked = jnp.take_along_axis(scores, picked_idx, axis=1)
        np.testing.assert_allclose(
            picked / picked.sum(axis=1, keepdims=True), weights, rtol=2e-6
        )
        sizes = reference.sizes_of(dict(
            CONFIG, moe_num_active_primary_experts=top_k,
            held_experts=[0, experts],
        ), None)
        want = reference.experts(rows, chosen, weights, params, sizes,
                                 lambda t: t)
        got, _ = layer.apply(variables, x, source, mutable=MUTABLE)
        own, _ = layer.apply(variables, x, mutable=MUTABLE)
    np.testing.assert_allclose(
        got.reshape(-1, hidden), want, rtol=2e-5, atol=2e-6
    )
    # routed from its own rows it is another layer
    assert np.abs(np.asarray(own) - np.asarray(got)).max() > 1e-3


def test_a_band_is_exact_at_both_edges():
    """Query t sees the keys s with t - window < s <= t and no other: a
    change to row s of the layer's input moves rows s .. s + window - 1 of
    its output and leaves every other row as it was, with rotary and
    without."""
    length, window, at = 64, 24, 10
    x = np.random.RandomState(3).randn(1, length, 32).astype(np.float32)
    moved = x.copy()
    moved[0, at] += 1.0
    for rope in (decoder.plain_rope(16, 1.5e6), None):
        layer = decoder.GroupedAttention(
            32, 6, 2, 16, 0.25, rope=rope, window=window
        )
        variables = layer.init(jax.random.PRNGKey(0), x)
        delta = np.abs(np.asarray(
            layer.apply(variables, moved) - layer.apply(variables, x)
        )).max(axis=-1)[0]
        assert (delta[at:at + window] > 1e-6).all()
        assert not delta[:at].any() and not delta[at + window:].any()
        whole = decoder.GroupedAttention(32, 6, 2, 16, 0.25, rope=rope)
        delta = np.abs(np.asarray(
            whole.apply(variables, moved) - whole.apply(variables, x)
        )).max(axis=-1)[0]
        assert (delta[at:] > 1e-6).all() and not delta[:at].any()


def test_the_two_published_lists_name_every_layer():
    model = zoo.custom_model(hidden=32, vocab_size=50)
    assert len(model.config.layers) == 52
    assert [i for i, banded in enumerate(model.config.layers)
            if not banded] == list(range(0, 52, 4))
    # layers 0 and 4 carry no positions and no band, the others both
    for layer, banded in zip(
        model_of(CONFIG, layers=[0, 1, 4, 7]).config.layers,
        (False, True, False, True),
    ):
        assert layer == banded
    with pytest.raises(ValueError, match="layer 1"):
        model_of(CONFIG, rope_layout=[0, 0, 1, 1, 0, 1, 1, 1])
    with pytest.raises(ValueError, match="layer 1"):
        reference.layers_of(dict(CONFIG, rope_layout=[0, 0, 1, 1]))
    # a layer that is not built may disagree: it is not this chip's
    model_of(CONFIG, sliding_window_layout=[0, 1, 1, 1, 0, 1, 1, 0])
    with pytest.raises(ValueError):
        model_of(CONFIG, layers=[8])
    with pytest.raises(ValueError):
        model_of(CONFIG, rope_layout=LAYOUT[:4])
    with pytest.raises(ValueError):
        model_of(CONFIG, kv_heads=4)


def test_eight_holders_parts_are_the_uncut_layer():
    """Expert parallelism's partial sums: the routed parts of all 8
    holders (two experts of 16 each), every holder routing from the
    block's input, equal the uncut reference's whole expert layer; there
    is no shared expert to count."""
    hidden, experts, width, top_k, holders = 32, 16, 24, 6, 8
    each = experts // holders
    rng = np.random.RandomState(1)
    rows = jnp.asarray(rng.randn(3, 40, hidden), jnp.float32)
    source = jnp.asarray(rng.randn(3, 40, hidden), jnp.float32)

    def layer(held):
        return decoder.MoEFFN(
            hidden, experts, top_k, width, 0, held, 1.0, 0.0, jnp.float32,
            "smallthinker/moe", form=moe.REGLU, scores=moe.SOFTMAX,
            route_scope="smallthinker/route",
        )

    whole = layer(None).init(jax.random.PRNGKey(3), rows, source)["params"]
    assert set(whole) == {"routed"}                    # no shared expert
    assert whole["routed"]["expert_w_gate_up"].shape == (
        experts, hidden, 2 * width
    )
    sizes = reference.sizes_of(dict(
        CONFIG, moe_num_active_primary_experts=top_k,
        held_experts=[0, experts],
    ), None)
    stacks = ("expert_w_gate_up", "expert_w_down")
    with jax.default_matmul_precision("highest"):
        def uncut(m, x):
            chosen, weights = reference.routing(
                x, whole["routed"]["router_kernel"], top_k
            )
            return reference.experts(
                m, chosen, weights, whole["routed"], sizes, lambda t: t
            )

        want = jax.vmap(uncut)(rows, source)
        parts = []
        for holder in range(holders):
            first = holder * each
            routed = dict(whole["routed"], **{
                name: whole["routed"][name][first:first + each]
                for name in stacks
            })
            out, _ = layer((first, each)).apply(
                {"params": {"routed": routed}}, rows, source,
                mutable=MUTABLE,
            )
            parts.append(out)
    np.testing.assert_allclose(sum(parts), want, rtol=2e-5, atol=2e-6)
    # no holder alone is the layer
    assert np.abs(parts[0] - want).max() > 1e-3


# ---- controls: each part of the mathematics must fail the comparison ------


def _moe_reads(tensor):
    """The router reads `tensor` of (the experts' rows, the block's
    input) instead of the block's input."""
    def change(monkeypatch):
        plain = zoo.MoEFFN

        class Rerouted:
            def __init__(self, *args, **kwargs):
                self.layer = plain(*args, **kwargs)

            def __call__(self, rows, route_from):
                return self.layer(*tensor(rows, route_from))

        monkeypatch.setattr(zoo, "MoEFFN", Rerouted)

    return change


def _moe_change(**changes):
    def change(monkeypatch):
        plain = zoo.MoEFFN

        def built(*args, **kwargs):
            return plain(*args, **{**kwargs, **changes})

        monkeypatch.setattr(zoo, "MoEFFN", built)

    return change


def _weights_times_two(monkeypatch):
    """`routed_scaling` (the seventh positional size) 2 instead of 1."""
    plain = zoo.MoEFFN

    def built(*args, **kwargs):
        return plain(*args[:6], 2.0, *args[7:], **kwargs)

    monkeypatch.setattr(zoo, "MoEFFN", built)


def _form(activation):
    """Another activation over the same fused gate-and-up stack."""
    def change(monkeypatch):
        monkeypatch.setitem(
            moe.FORMS, moe.REGLU, ("expert_w_gate_up", 2, activation)
        )

    return change


def _attention_change(**changes):
    def change(monkeypatch):
        plain = zoo.GroupedAttention

        def built(*args, **kwargs):
            return plain(*args, **{**kwargs, **changes})

        monkeypatch.setattr(zoo, "GroupedAttention", built)

    return change


def _keys_on_other_heads(monkeypatch):
    """Query head h on K/V head h % 2 instead of h // 3."""
    plain = decoder.flash_attention.causal_attention

    def dealt(q, k, v, **kwargs):
        heads = q.shape[2]
        order = np.argsort(np.arange(heads) % k.shape[2], kind="stable")
        out = plain(q[:, :, order], k, v, **kwargs)
        return out[:, :, np.argsort(order)]

    monkeypatch.setattr(
        decoder.flash_attention, "causal_attention", dealt
    )


def _normed_input(rows, route_from):
    return rows, decoder.rms_norm(route_from, 1.0, 1e-6)


CONTROLS = {
    # the routing's source: the normed post-attention rows the experts
    # read (where every sibling routes), or the block's input normed
    "routed_from_the_experts_rows": _moe_reads(lambda rows, x: (rows,)),
    "routed_from_the_normed_input": _moe_reads(_normed_input),
    "swiglu_experts": _form(moe._swiglu),
    "squared_relu_experts": _form(
        lambda gate_up: jnp.square(nn.relu(jnp.split(gate_up, 2, -1)[0]))
    ),
    "sigmoid_scores": _moe_change(scores=moe.SIGMOID),
    "weights_times_two": _weights_times_two,
    "every_layer_turned": dict(rope_layout=[1] * 8,
                               sliding_window_layout=[1] * 8),
    "no_layer_turned": _attention_change(rope=None),
    "no_band": _attention_change(window=None),
    "a_band_one_key_short": dict(window=23),
    "another_theta": dict(rope_theta=1e4),
    "keys_on_other_heads": _keys_on_other_heads,
}


@pytest.mark.parametrize("control", sorted(CONTROLS))
def test_a_departure_from_the_mathematics_fails_the_comparison(
        seeded, monkeypatch, control):
    """The comparison that passes the model fails each of these: a router
    that reads the experts' rows or the normed input, SwiGLU or squared
    ReLU in ReGLU's place, sigmoid scores, weights that do not sum to 1,
    rotary and a band in every layer or rotary in none, no band, a band a
    key short, another theta, query heads dealt to other K/V heads."""
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
    configuration: the norms' scales, another held range, another top-k,
    another band, another theta and another layer list each move what is
    computed."""
    features = {"input_ids": seeded.ids}

    def loss_with(config=CONFIG, **leaves):
        return reference.loss_and_grads(
            {**seeded.flat, **leaves}, features, None, config
        )[0]

    for leaf in ("layer_0/attn_norm/scale", "layer_2/ffn_norm/scale",
                 "final_norm/scale"):
        assert abs(
            loss_with(**{leaf: seeded.flat[leaf] * 2.0}) - seeded.want_loss
        ) > 1e-6, leaf
    for change in (dict(held_experts=[0, 8]),
                   dict(moe_num_active_primary_experts=2),
                   dict(sliding_window_size=23), dict(rope_theta=1e2),
                   dict(layers_held=[1, 1, 2, 3])):
        assert abs(
            loss_with(dict(CONFIG, **change)) - seeded.want_loss
        ) > 1e-6, change
    # published layer 4 (a full layer) in layer 0's place is layer 0 again
    assert loss_with(dict(CONFIG, layers_held=[4, 1, 2, 3])) == (
        pytest.approx(seeded.want_loss, abs=1e-7)
    )


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
        ROOT, "benchmarks", "configs", "smallthinker-21b-a3b.json"
    )) as f:
        config = json.load(f)
    from elasticdl_tpu.common.model_handler import _call_with_params

    model = _call_with_params(
        zoo.custom_model, config["model_params"].format(**config)
    )
    held = config["layers_held"]
    assert held == [0, 1, 2, 3] and len(held) == config["num_hidden_layers"]
    c = model.config
    assert c.layers == (False, True, True, True)
    assert (c.num_experts, c.top_k, c.held_experts) == (64, 6, (0, 8))
    assert (c.heads, c.kv_heads, c.head_dim, c.window) == (28, 4, 128, 4096)
    assert c.rope.columns == 128
    assert c.rope.inv_freq[-1] == pytest.approx(1.5e6 ** (-126 / 128))
    assert c.dtype == jnp.bfloat16 and c.remat and c.eps == 1e-6
    assert config["sliding_window_layout"] == config["rope_layout"]
    assert len(config["rope_layout"]) == 52
    assert [i for i, v in enumerate(config["rope_layout"]) if not v] == list(
        range(0, 52, 4)
    )
    shapes = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), {"input_ids": jnp.zeros((1, 512), jnp.int32)}
    ))
    assert set(shapes) == {"params", STEP_METRICS}
    flat = {
        name: int(np.prod(leaf.shape))
        for name, leaf in trees.flat(shapes["params"]).items()
    }
    by_top = {}
    for name, size in flat.items():
        top = name.split("/")[0]
        by_top[top] = by_top.get(top, 0) + size
    assert by_top == {
        **{f"layer_{i}": 68_326_400 for i in range(4)},
        "token_embedding": 48_619_520, "lm_head_kernel": 48_619_520,
        "final_norm": 2_560,
    }

    def part(prefix):
        return {
            k[len(prefix):]: v for k, v in flat.items() if k.startswith(prefix)
        }

    assert part("layer_1/attn/") == {
        "q/kernel": 9_175_040, "k/kernel": 1_310_720, "v/kernel": 1_310_720,
        "o/kernel": 9_175_040,
    }
    assert part("layer_1/moe/") == {
        "routed/router_kernel": 163_840,
        "routed/expert_w_gate_up": 8 * 3_932_160,
        "routed/expert_w_down": 8 * 1_966_080,
    }
    total = sum(by_top.values())
    assert total == config["parameters_held"] == 370_547_200
    assert f"{total:,}" in config["deployment"]
    assert 16 * total > 0.25 * 16.9e9          # over the floor, held alone
    # the published widths are whole tiles: the walk pads nothing
    assert moe.padded_work(2560, 768) == 0.0


# ---- through the system ---------------------------------------------------


def test_trainer_carries_every_layers_gauges(seeded):
    from elasticdl_tpu.common import metrics as metrics_lib
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
    for layer in range(4):
        path = f"layer_{layer}/moe/routed"
        assert metrics[f"{path}/expert_load_imbalance_ratio"] >= 1.0
        assert 0.0 < metrics[f"{path}/routed_here_ratio"] < 1.0
        assert metrics[f"{path}/live_chunks_ratio"] == 1.0
        assert metrics[f"{path}/dropped_tokens"] == 0
        # the tiny widths are no whole tiles
        assert metrics_lib.default_registry().value(
            "worker_moe_padded_work_ratio", layer=path
        ) == pytest.approx(moe.padded_work(32, 24))


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
            "--model_def", "smallthinker.smallthinker.custom_model",
            "--model_params",
            "hidden=32;layers=[0,1,2,3];heads=6;kv_heads=2;head_dim=16;"
            "window=12;expert_width=24;num_experts=16;top_k=3;"
            "held_experts=[4,8];vocab_size=50;remat=True;lr=0.03",
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
    assert registry.value("worker_moe_dropped_tokens_total") == 0.0
    for layer in range(4):
        assert 0.0 < registry.value(
            "worker_moe_routed_here_ratio", layer=f"layer_{layer}/moe/routed"
        ) < 1.0
    assert registry.value("worker_remat_kept_ratio") == 1.0


def test_the_layers_scopes_reach_the_lowered_operations():
    """Both attention kinds', the expert layer's and the routing's scopes
    carry the model's prefix into the operations' names; the routing's
    operations lie under `smallthinker/route` AND under `router` or
    `dispatch`, which stay their innermost catalogue entries, so that
    `moe_walk_ms_per_step` reads them where it reads every sibling's."""
    from elasticdl_tpu.common import profiler

    model = model_of(CONFIG, remat=False)
    ids = ids_of(1, length=16)
    variables = model.init(jax.random.PRNGKey(0), {"input_ids": ids})
    text = jax.jit(
        lambda v, ids: model.apply(v, {"input_ids": ids}, mutable=MUTABLE)[0]
    ).lower(variables, ids).as_text(debug_info=True)
    for scope in ("attn_full", "attn_window", "moe", "norm", "embed",
                  "head_ce"):
        assert f"smallthinker/{scope}" in profiler.DEVICE_SCOPES
        assert f"smallthinker/{scope}/" in text, scope
    for part in ("dispatch", "experts", "combine"):
        assert f"smallthinker/moe/routed/{part}" in text, part
    for part in ("router", "dispatch"):
        assert f"smallthinker/moe/routed/smallthinker/route/{part}" in text
    # nothing routes outside the scope: the only `router` is under it
    assert "routed/router" not in text
    assert "smallthinker/route" not in profiler.DEVICE_SCOPES
    for part in ("router", "dispatch"):
        assert profiler.catalogue_scope(
            f"layer_1/moe/smallthinker/moe/routed/smallthinker/route/{part}"
        ) == part
    assert "Scope object" not in text


def test_the_routing_hangs_on_the_blocks_input_alone():
    """What `smallthinker/route` names waits on nothing the block
    computes after its input: with attention's output projection zeroed
    or doubled the chosen experts and their weights are what they were,
    while a router on the experts' rows would see another tensor."""
    x = jnp.asarray(
        np.random.RandomState(4).randn(1, 16, 32), jnp.float32
    )
    block = zoo.Block(model_of(CONFIG).config, True)
    variables = block.init(jax.random.PRNGKey(0), x)

    def loads(scale):
        params = jax.tree.map(lambda leaf: leaf, variables["params"])
        params["attn"]["o"]["kernel"] = params["attn"]["o"]["kernel"] * scale
        _, sown = block.apply(
            {"params": params}, x, mutable=[STEP_METRICS]
        )
        sown = sown[STEP_METRICS]["moe"]["routed"]
        return (float(sown["expert_load_imbalance_ratio"]),
                float(sown["routed_here_ratio"]))

    assert loads(0.0) == loads(1.0) == loads(50.0)
