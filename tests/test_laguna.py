"""The Laguna-XS.2 decoder (model_zoo/laguna/laguna.py) at tiny widths on
the CPU, seeded weights: both kinds of layer (full and window attention at
unequal head counts over grouped K/V, partial YaRN and plain rotary, the
output gate, a dense and routed feed-forwards) against the plain float32
reference leaf by leaf, bfloat16 inside the twin's rule, the shares of an
expert-parallel deployment adding up to the uncut layer, the sown gate
through the Trainer, the published sizes' parameter count, and a two-task
job through the CLI."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import laguna as reference
from elasticdl_tpu.layers import moe
from elasticdl_tpu.layers.moe import ROUTER_STATE, RoutedExperts
from elasticdl_tpu.layers.step_metrics import STEP_METRICS
from elasticdl_tpu.ops.flash_attention import stream_shapes_ok
from model_zoo.laguna import laguna as zoo
from tests import decoder_cases
from tests.decoder_cases import MUTABLE, computed, seeded  # noqa: F401

ROPES = {
    "full_attention": {
        "rope_theta": 500000, "rope_type": "yarn", "factor": 64,
        "original_max_position_embeddings": 32, "beta_slow": 1,
        "beta_fast": 4, "attention_factor": 1.4158883083359672,
        "partial_rotary_factor": 0.5,
    },
    "sliding_attention": {
        "rope_type": "default", "rope_theta": 10000,
        "partial_rotary_factor": 1,
    },
    "original_max_position_embeddings": 32,
}
# 6 and 8 query heads over 2 K/V heads (groups of 3 and 4), a window of 24
# over 64 positions, 16 experts of which 4 are held
CONFIG = dict(
    hidden_size=32, num_hidden_layers=5,
    layer_types=[zoo.FULL, zoo.WINDOW, zoo.WINDOW, zoo.WINDOW, zoo.FULL,
                 zoo.WINDOW],
    mlp_layer_types=["dense"] + ["sparse"] * 5,
    num_attention_heads_per_layer=[6, 8, 8, 8, 6, 8],
    num_key_value_heads=2, head_dim=16, sliding_window=24,
    rope_parameters=ROPES, intermediate_size=48, moe_intermediate_size=16,
    shared_expert_intermediate_size=16, num_experts_published=16,
    num_experts_per_tok=2, held_experts=[4, 4],
    moe_routed_scaling_factor=2.5, vocab_size=50, rms_norm_eps=1e-6,
    learning_rate=1e-3, use_bf16=True,
)


def published_also(model, config, shapes, flat, by_top):
    assert [layer[:2] for layer in model.config.layers] == [
        (zoo.FULL, 48), (zoo.WINDOW, 64), (zoo.WINDOW, 64),
        (zoo.WINDOW, 64), (zoo.FULL, 48),
    ]
    assert [layer[2] for layer in model.config.layers] == [False] + [True] * 4


def the_gate_mean_is_carried(metrics, state, loss, seeded):
    for layer in range(5):
        # a seeded gate sits near a half; one that closes silences its layer
        assert 0.3 < metrics[f"layer_{layer}/attn/gate_mean"] < 0.7
    assert "layer_0/moe/routed/routed_here_ratio" not in metrics
    assert metrics["layer_1/moe/routed/dropped_tokens"] == 0.0
    assert 0.0 < metrics["layer_4/moe/routed/routed_here_ratio"] < 1.0


def job_gauges(registry):
    assert registry.value("worker_moe_dropped_tokens_total") == 0.0
    assert 0.0 < registry.value(
        "worker_moe_routed_here_ratio", layer="layer_1/moe/routed"
    ) < 1.0
    for layer in range(3):
        assert 0.2 < registry.value(
            "worker_attention_gate_mean_ratio", layer=f"layer_{layer}/attn"
        ) < 0.8


DECODER = decoder_cases.Decoder(
    zoo=zoo, reference=reference, cell="laguna-xs.2", config=CONFIG,
    length=64, seed=0,
    # 5 blocks of 7 attention leaves and 2 (dense) or 5 (routed)
    # feed-forward leaves, embedding, head, final norm
    leaves=9 + 4 * 12 + 3,
    # head width 128 and two tiles of 128 positions: the Pallas kernels
    # (interpreted here), a full layer of 2 heads and a window layer of 3
    # over ONE K/V head, the window longer than a tile and shorter than the
    # sequence
    kernels=decoder_cases.Kernels(
        config=dict(
            num_hidden_layers=2, num_attention_heads_per_layer=[2, 3],
            num_key_value_heads=1, head_dim=128, sliding_window=160,
        ),
        length=256,
        admitted=((stream_shapes_ok, (1, 256, 3, 128), (1, 256, 1, 128),
                   (1, 256, 1, 128)),),
    ),
    published=decoder_cases.Published(
        by_top={
            "layer_0": 79_794_176, "layer_1": 142_217_216,
            "layer_2": 142_217_216, "layer_3": 142_217_216,
            "layer_4": 133_795_840, "token_embedding": 25_690_112,
            "lm_head_kernel": 25_690_112, "final_norm": 2_048,
        },
        total=691_623_936, also=published_also,
    ),
    trainer_gauges=the_gate_mean_is_carried,
    job=decoder_cases.Job(
        params=(
            "hidden=32;num_layers=3;"
            "layer_types=['full_attention','sliding_attention',"
            "'sliding_attention'];"
            "mlp_layer_types=['dense','sparse','sparse'];"
            "heads_per_layer=[6,8,8];kv_heads=2;head_dim=16;window=12;"
            "dense_width=48;expert_width=16;shared_width=16;num_experts=16;"
            "top_k=2;held_experts=(0,8);vocab_size=50;remat=True;lr=0.01"
        ),
        gauges=job_gauges, falls_by=0.1, all_the_room=False,
    ),
)
model_of = DECODER.model_of
TestConformance = decoder_cases.conformance(DECODER)


def test_the_window_and_the_groups_are_seen(seeded):
    """The reference is held to the model above; this holds BOTH to the
    configuration: without the window, or with the K/V heads' groups
    dealt another way, the loss moves."""
    def loss_with(**changes):
        return reference.loss_and_grads(
            seeded.flat, {"input_ids": seeded.ids}, None,
            dict(CONFIG, **changes),
        )[0]

    assert abs(loss_with(sliding_window=64) - seeded.want_loss) > 1e-4
    assert abs(
        loss_with(layer_types=[zoo.FULL] * 6) - seeded.want_loss
    ) > 1e-4
    model = model_of(CONFIG, window=64)
    out = jax.jit(lambda variables: model.apply(
        variables, {"input_ids": seeded.ids}, mutable=MUTABLE
    )[0])(seeded.variables)
    assert abs(float(out.mean()) - seeded.want_loss) > 1e-4


def test_rotary_tables_of_model_and_reference_agree():
    """Two implementations of YaRN's frequencies, the program's and the
    reference's, at the published numbers: the fast pairs keep theta's
    frequency, the slow ones turn 64 times slower, and half a head
    turns."""
    config = decoder_cases.cell_config("laguna-xs.2")
    full = config["rope_parameters"]["full_attention"]
    ours, theirs = zoo.rope_of(full, 128), reference.rope_of(full, 128)
    assert ours.columns == theirs.columns == 64
    assert ours.factor == theirs.factor == pytest.approx(
        0.1 * np.log(64) + 1
    )
    np.testing.assert_allclose(ours.inv_freq, theirs.inv_freq, rtol=1e-12)
    plain = 500000.0 ** (-np.arange(0, 64, 2) / 64)
    assert ours.inv_freq[0] == plain[0] == 1.0
    assert ours.inv_freq[-1] == pytest.approx(plain[-1] / 64)
    window = zoo.rope_of(config["rope_parameters"]["sliding_attention"], 128)
    assert window.columns == 128 and window.factor == 1.0
    np.testing.assert_allclose(
        window.inv_freq, 10000.0 ** (-np.arange(0, 128, 2) / 128)
    )
    # only the turned half of a head moves
    x = jnp.ones((1, 4, 1, 128))
    turned = zoo.partial_rotary(x, ours)
    np.testing.assert_array_equal(turned[..., 64:], x[..., 64:])
    assert not np.allclose(turned[0, 1:, 0, :64], 1.0)


# ---- the routed layer at 256-style routing --------------------------------


def test_the_shares_add_up_to_the_uncut_layer():
    """16 experts, top-4 of all 16, over 4 shares of 4: the routed parts
    of all shares plus the shared expert counted ONCE equal the uncut
    reference's layer."""
    config = dict(CONFIG, held_experts=[0, 16], num_experts_per_tok=4)
    sizes = reference.sizes_of(config, None)
    x = jnp.asarray(
        np.random.RandomState(1).randn(48, 32).astype(np.float32)
    )
    whole = zoo.MoEFFN(32, 16, 4, 16, 1, None, 2.5, 0.0, name=None)
    variables = whole.init(jax.random.PRNGKey(3), x)
    p = variables["params"]
    with jax.default_matmul_precision("highest"):
        want = reference.routed(x, p["routed"], sizes, lambda t: t) + (
            reference.swiglu(x, p["shared"], lambda t: t)
        )
        total = np.zeros_like(np.asarray(want))
        for share in range(4):
            first = 4 * share
            held = {
                "router_kernel": p["routed"]["router_kernel"],
                "expert_w_gate_up":
                    p["routed"]["expert_w_gate_up"][first:first + 4],
                "expert_w_down":
                    p["routed"]["expert_w_down"][first:first + 4],
            }
            part = RoutedExperts(
                num_experts=16, top_k=4, ffn_dim=16, held_experts=(first, 4),
                routed_scaling=2.5,
            ).apply(
                {"params": held,
                 ROUTER_STATE: variables[ROUTER_STATE]["routed"]}, x
            )
            total += np.asarray(part)
        total += np.asarray(zoo.SwiGLU(32, 16).apply(
            {"params": p["shared"]}, x
        ))
    np.testing.assert_allclose(total, want, rtol=2e-4, atol=2e-5)
    # and no share alone is the layer
    assert np.abs(np.asarray(part) - np.asarray(want)).max() > 1e-2


def test_the_walk_at_this_models_routing(monkeypatch):
    """Top-8 of 256 with 32 held in the middle (the cell's routing): the
    layer walking its 512-slot buffer 48 rows a trip against the
    reference's every-held-expert-over-all-tokens form, output and every
    gradient."""
    monkeypatch.setattr(moe, "CHUNK", 48)
    config = dict(CONFIG, held_experts=[32, 32], num_experts_per_tok=8,
                  num_experts_published=256)
    sizes = reference.sizes_of(config, None)
    x = jnp.asarray(
        np.random.RandomState(1).randn(64, 32).astype(np.float32)
    )
    layer = RoutedExperts(
        num_experts=256, top_k=8, ffn_dim=16, held_experts=(32, 32),
        routed_scaling=2.5,
    )
    variables = layer.init(jax.random.PRNGKey(3), x)
    cotangent = jnp.asarray(
        np.random.RandomState(2).randn(64, 32).astype(np.float32)
    )

    def ours(params, x):
        out, sown = layer.apply(
            {**variables, "params": params}, x, mutable=[STEP_METRICS]
        )
        return (out * cotangent).sum(), (out, sown[STEP_METRICS])

    def plain(params, x):
        out = reference.routed(x, params, sizes, lambda t: t)
        return (out * cotangent).sum(), (out, None)

    with jax.default_matmul_precision("highest"):
        (_, (out, metrics)), grads = jax.value_and_grad(
            ours, argnums=(0, 1), has_aux=True
        )(variables["params"], x)
        (_, (want, _)), want_grads = jax.value_and_grad(
            plain, argnums=(0, 1), has_aux=True
        )(variables["params"], x)
    rows = round(float(metrics["routed_here_ratio"]) * 512)
    assert 48 < rows < 512 - 48           # some chunks walked, some not
    assert float(metrics["live_chunks_ratio"]) == pytest.approx(
        -(-rows // 48) / 11
    )
    assert float(metrics["dropped_tokens"]) == 0.0
    np.testing.assert_allclose(out, want, rtol=2e-4, atol=2e-5)
    for ours_leaf, want_leaf in zip(
        jax.tree_util.tree_leaves(grads),
        jax.tree_util.tree_leaves(want_grads),
    ):
        np.testing.assert_allclose(
            ours_leaf, want_leaf, rtol=2e-4, atol=2e-5
        )


# ---- through the system ---------------------------------------------------


