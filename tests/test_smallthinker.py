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

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import smallthinker as reference
from elasticdl_tpu.layers import moe
from elasticdl_tpu.layers.step_metrics import STEP_METRICS
from elasticdl_tpu.ops.flash_attention import stream_shapes_ok
from model_zoo.common import decoder
from model_zoo.smallthinker import smallthinker as zoo
from tests import decoder_cases
from tests.decoder_cases import MUTABLE, computed, seeded  # noqa: F401

LAYOUT = [0, 1, 1, 1, 0, 1, 1, 1]
# one whole period of the published pattern (full, band, band, band): 6
# query heads of 16 over 2 K/V heads (groups of 3), a band of 24 over 64
# positions, top-3 of 16 softmax-routed ReGLU experts 24 wide with 8 held
CONFIG = dict(
    hidden_size=32, num_hidden_layers=4, num_hidden_layers_published=8,
    layers_held=[0, 1, 2, 3],
    sliding_window_layout=LAYOUT, rope_layout=LAYOUT,
    num_attention_heads=6, num_key_value_heads=2, head_dim=16,
    sliding_window_size=24, rope_theta=1.5e6, moe_ffn_hidden_size=24,
    moe_num_primary_experts=8, moe_num_primary_experts_published=16,
    moe_num_active_primary_experts=3, held_experts=[4, 8], vocab_size=50,
    rms_norm_eps=1e-6, learning_rate=1e-3, use_bf16=True,
)
ATTENTION_LEAVES, EXPERT_LEAVES = 4, 3


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


def float32_also(model, seeded, got):
    assert list(model.config.layers) == [False, True, True, True]
    assert set(seeded.variables) == {"params", STEP_METRICS}   # no buffer
    assert got["layer_0/attn/q/kernel"].shape == (32, 6 * 16)
    assert got["layer_0/attn/k/kernel"].shape == (32, 2 * 16)
    assert got["layer_0/attn/o/kernel"].shape == (6 * 16, 32)
    assert got["layer_1/moe/routed/router_kernel"].shape == (32, 16)
    assert got["layer_1/moe/routed/expert_w_gate_up"].shape == (8, 32, 48)
    assert got["layer_1/moe/routed/expert_w_down"].shape == (8, 24, 32)


def published_also(model, config, shapes, flat, by_top):
    held = config["layers_held"]
    assert held == [0, 1, 2, 3] and len(held) == config["num_hidden_layers"]
    c = model.config
    assert c.layers == (False, True, True, True)
    assert (c.num_experts, c.top_k, c.held_experts) == (64, 6, (0, 8))
    assert (c.heads, c.kv_heads, c.head_dim, c.window) == (28, 4, 128, 4096)
    assert c.rope.columns == 128
    assert c.rope.inv_freq[-1] == pytest.approx(1.5e6 ** (-126 / 128))
    assert c.eps == 1e-6
    assert config["sliding_window_layout"] == config["rope_layout"]
    assert len(config["rope_layout"]) == 52
    assert [i for i, v in enumerate(config["rope_layout"]) if not v] == list(
        range(0, 52, 4)
    )
    assert set(shapes) == {"params", STEP_METRICS}

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
    # the published widths are whole tiles: the walk pads nothing
    assert moe.padded_work(2560, 768) == 0.0


def trainer_gauges(metrics, state, loss, seeded):
    from elasticdl_tpu.common import metrics as metrics_lib

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


def job_gauges(registry):
    assert registry.value("worker_moe_dropped_tokens_total") == 0.0
    for layer in range(2):
        assert 0.0 < registry.value(
            "worker_moe_routed_here_ratio", layer=f"layer_{layer}/moe/routed"
        ) < 1.0


def scopes_also(text):
    """The routing's operations lie under `smallthinker/route` AND under
    `router` or `dispatch`, which stay their innermost catalogue entries,
    so that `moe_walk_ms_per_step` reads them where it reads every
    sibling's."""
    from elasticdl_tpu.common import profiler

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


DECODER = decoder_cases.Decoder(
    zoo=zoo, reference=reference, cell="smallthinker-21b-a3b", config=CONFIG,
    length=64, seed=5,
    # two norms a layer beside attention's 4 kernels and the routed
    # layer's 3 (router, two stacks: no shared expert); the embedding, the
    # untied head, the final norm
    leaves=4 * (ATTENTION_LEAVES + EXPERT_LEAVES + 2) + 3,
    float32_also=float32_also,
    # a group of SEVEN query heads of 128 over one K/V head at two tiles
    # of 128 positions: the streaming kernels (interpreted here), a full
    # layer with no positions and a band layer whose band is longer than a
    # tile and shorter than the sequence
    kernels=decoder_cases.Kernels(
        config=dict(
            hidden_size=64, num_attention_heads=7, num_key_value_heads=1,
            head_dim=128, sliding_window_size=160, layers_held=[0, 1],
            num_hidden_layers=2,
        ),
        length=256,
        admitted=((stream_shapes_ok, (1, 256, 7, 128), (1, 256, 1, 128),
                   (1, 256, 1, 128)),),
    ),
    # a router that reads the experts' rows or the normed input, SwiGLU or
    # squared ReLU in ReGLU's place, sigmoid scores, weights that do not
    # sum to 1, rotary and a band in every layer or rotary in none, no
    # band, a band a key short, another theta, query heads dealt to other
    # K/V heads
    controls=CONTROLS,
    published=decoder_cases.Published(
        by_top={
            **{f"layer_{i}": 68_326_400 for i in range(4)},
            "token_embedding": 48_619_520, "lm_head_kernel": 48_619_520,
            "final_norm": 2_560,
        },
        total=370_547_200, bytes_a_parameter=16, also=published_also,
    ),
    trainer_gauges=trainer_gauges,
    # the job's model is one block of each kind (published layers 0 and 1:
    # a full layer with no positions, a band layer with rotary)
    job=decoder_cases.Job(
        params=(
            "hidden=32;layers=[0,1];heads=6;kv_heads=2;head_dim=16;"
            "window=12;expert_width=24;num_experts=16;top_k=3;"
            "held_experts=[4,8];vocab_size=50;remat=True;lr=0.03"
        ),
        gauges=job_gauges,
    ),
    # both attention kinds', the expert layer's and the routing's
    scopes=decoder_cases.Scopes(
        prefix="smallthinker",
        names=("attn_full", "attn_window", "moe", "norm", "embed",
               "head_ce"),
        also=scopes_also,
    ),
)
model_of = DECODER.model_of
TestConformance = decoder_cases.conformance(DECODER)


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


# ---- through the system ---------------------------------------------------


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
