"""The Kimi-Linear decoder (model_zoo/kimi/kimi_linear.py) at tiny widths
on the CPU, seeded weights: KDA and MLA blocks (the SiLU conv over the
fused q | k | v, the L2 norms, the per-channel decay, the chunked scan,
the gated output norm; latent attention with no rotation at a key width
of its own), a dense and routed feed-forwards beside a shared expert and
the untied head against the plain float32 reference leaf by leaf (its
KDA the token-by-token recurrence), through the jnp forms and through
the interpreted kernels, bfloat16 inside the twin's rule, the shares of
an expert-parallel deployment adding up to the uncut layer, the sown
gauges through the Trainer, the published sizes' parameter count, and a
two-task job through the CLI."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import trees
from benchmarks.reference import kimi_linear as reference
from benchmarks.reference.glm_moe_lite import swiglu
from elasticdl_tpu.layers.moe import ROUTER_STATE, RoutedExperts
from elasticdl_tpu.layers.step_metrics import STEP_METRICS
from elasticdl_tpu.ops import kda as kda_ops
from elasticdl_tpu.ops import short_conv
from elasticdl_tpu.ops.flash_attention import stream_shapes_ok
from model_zoo.common.decoder import MoEFFN
from model_zoo.kimi import kimi_linear as zoo
from tests import decoder_cases
from tests.decoder_cases import MUTABLE, computed, seeded  # noqa: F401

# the published lists' first eight layers (full attention at 4 and 8); the
# cut's layers 0..4: KDA over the dense FFN, then KDA, KDA, MLA, KDA, all
# routed; 2 heads, 16 experts of which 4 are held
CONFIG = dict(
    hidden_size=32, num_hidden_layers=5, num_hidden_layers_published=8,
    linear_attn_config=dict(
        kda_layers=[1, 2, 3, 5, 6, 7], full_attn_layers=[4, 8], num_heads=2,
        head_dim=16, short_conv_kernel_size=4,
    ),
    layers_held=[0, 1, 2, 3, 4], first_k_dense_replace=1,
    num_attention_heads=2, kv_lora_rank=16, qk_nope_head_dim=16,
    qk_rope_head_dim=8, v_head_dim=16, intermediate_size=48,
    moe_intermediate_size=16, num_experts_published=16,
    num_experts_per_token=2, num_experts_per_tok=2, num_shared_experts=1,
    held_experts=[4, 4], routed_scaling_factor=2.446, vocab_size=50,
    rms_norm_eps=1e-5, learning_rate=1e-3, use_bf16=True,
)
KDA_LEAVES, MLA_LEAVES = 11, 5
KINDS = [
    (zoo.KDA_KIND, False), (zoo.KDA_KIND, True), (zoo.KDA_KIND, True),
    (zoo.MLA_KIND, True), (zoo.KDA_KIND, True),
]


def every_kind_of_block_is_present(model, seeded, got):
    """KDA over the dense FFN, KDA and MLA over the routed one."""
    assert list(model.config.layers) == KINDS
    assert "layer_3/mla/q/kernel" in got and "layer_3/mla/q_a/kernel" not in got


def published_also(model, config, shapes, flat, by_top):
    assert list(model.config.layers) == KINDS
    assert len(model.config.layers) == config["num_hidden_layers"]
    linear = config["linear_attn_config"]
    assert tuple(linear["kda_layers"]) == zoo.PUBLISHED_KDA_LAYERS
    assert tuple(linear["full_attn_layers"]) == zoo.PUBLISHED_FULL_ATTN_LAYERS
    assert config["num_experts_per_tok"] == config["num_experts_per_token"]
    assert sum(
        v for k, v in flat.items() if k.startswith("layer_2/kda/")
    ) == 39_514_272
    assert sum(
        v for k, v in flat.items() if k.startswith("layer_3/mla/")
    ) == 29_114_880


def trainer_gauges(metrics, state, loss, seeded):
    for layer in (0, 1, 2, 4):
        assert 0.0 < metrics[f"layer_{layer}/kda/kda_decay_mean_ratio"] < 1.0
        assert 0.2 < metrics[f"layer_{layer}/kda/kda_beta_mean_ratio"] < 0.8
    assert "layer_3/kda/kda_decay_mean_ratio" not in metrics       # MLA
    assert "layer_0/moe/routed/routed_here_ratio" not in metrics
    assert metrics["layer_1/moe/routed/dropped_tokens"] == 0.0
    assert 0.0 < metrics["layer_4/moe/routed/routed_here_ratio"] < 1.0


def job_gauges(registry):
    assert registry.value("worker_moe_dropped_tokens_total") == 0.0
    assert 0.0 < registry.value(
        "worker_moe_routed_here_ratio", layer="layer_1/moe/routed"
    ) < 1.0
    for layer in (0, 1):
        assert 0.0 < registry.value(
            "worker_kda_decay_mean_ratio", layer=f"layer_{layer}/kda"
        ) < 1.0
        assert 0.2 < registry.value(
            "worker_kda_beta_mean_ratio", layer=f"layer_{layer}/kda"
        ) < 0.8


DECODER = decoder_cases.Decoder(
    zoo=zoo, reference=reference, cell="kimi-linear-48b-a3b", config=CONFIG,
    # 80 positions: one whole chunk of the scan and a padded one.  `A_log`
    # holds ONE number a head: with two heads its error against the twin's
    # is the ratio of two draws, not an average over a leaf, and under the
    # twins' rule it reads 0.6-0.9 on seeds 3 and 5 and 1.3-6.9 on seeds
    # 1, 2 and 4, where every other leaf reads under 0.6 (the cell is held
    # to shares of a leaf's norm, `reference.LEAF_REL_L2`, not to the twin)
    length=80, seed=3,
    # a KDA mixer's 11 leaves (qkv, taps, A_log, dt_bias, two low-rank
    # gates of 2, beta, the output norm, o), MLA's 5 (q, kv_a and its
    # norm, kv_b, o), two norms a block, 2 (dense) or 5 (routed + shared)
    # feed-forward leaves, embedding, head and final norm
    leaves=(
        (KDA_LEAVES + 2 + 2) + 3 * (KDA_LEAVES + 2 + 5)
        + (MLA_LEAVES + 2 + 5) + 3
    ),
    float32_also=every_kind_of_block_is_present,
    # two KDA heads of 128 and two MLA heads of 128 + 64 over 128 at 128
    # positions: the scan's kernels (two chunks), the SiLU conv's and the
    # streaming attention at a key width of its own (padded to 256 inside
    # the op), all interpreted here; one block of each kind (KDA over the
    # dense FFN, MLA over the routed one: a second KDA block ran the same
    # kernels again at the same shapes)
    kernels=decoder_cases.Kernels(
        config=dict(
            hidden_size=64,
            linear_attn_config=dict(
                CONFIG["linear_attn_config"], head_dim=128,
            ),
            qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
            layers_held=[0, 3], num_hidden_layers=2,
        ),
        length=128,
        admitted=(
            (kda_ops.kda_shapes_ok, *[(1, 128, 2, 128)] * 3),
            (short_conv.silu_conv_shapes_ok, (1, 128, 768), (4, 768)),
            (stream_shapes_ok, (1, 128, 2, 192), (1, 128, 2, 192),
             (1, 128, 2, 128)),
        ),
    ),
    published=decoder_cases.Published(
        by_top={
            "layer_0": 103_219_872, "layer_1": 103_809_696,
            "layer_2": 103_809_696, "layer_3": 93_410_304,
            "layer_4": 103_809_696, "token_embedding": 47_185_920,
            "lm_head_kernel": 47_185_920, "final_norm": 2_304,
        },
        total=602_433_408, also=published_also,
    ),
    trainer_gauges=trainer_gauges,
    job=decoder_cases.Job(
        params=(
            "hidden=32;num_layers=4;kda_layers=[1,2,3];full_attn_layers=[4];"
            "layers=[0,2,3];heads=2;kda_heads=2;kda_head_dim=16;"
            "kv_lora_rank=16;qk_nope_head_dim=16;qk_rope_head_dim=8;"
            "v_head_dim=16;dense_width=48;expert_width=16;num_experts=16;"
            "top_k=2;held_experts=(0,8);vocab_size=50;remat=True;lr=0.01"
        ),
        gauges=job_gauges, falls_by=0.1, all_the_room=False,
    ),
)
model_of = DECODER.model_of
TestConformance = decoder_cases.conformance(DECODER)


def test_each_part_of_the_mathematics_is_seen(seeded):
    """The reference is held to the model above; this holds BOTH to the
    configuration: a conv tap dropped, the decay's A doubled, the output
    norm's scale doubled, a rotation that is not there, or another layer
    list each move what is computed."""
    features = {"input_ids": seeded.ids}

    def loss_with(config=CONFIG, **leaves):
        return reference.loss_and_grads(
            {**seeded.flat, **leaves}, features, None, config
        )[0]

    taps = seeded.flat["layer_2/kda/conv_kernel"].copy()
    taps[0] = 0.0                                  # the tap three rows back
    assert abs(
        loss_with(**{"layer_2/kda/conv_kernel": taps}) - seeded.want_loss
    ) > 1e-5
    for leaf in ("layer_1/kda/A_log", "layer_4/kda/o_norm/scale",
                 "layer_0/kda/dt_bias"):
        assert abs(
            loss_with(**{leaf: seeded.flat[leaf] + np.log(2.0)})
            - seeded.want_loss
        ) > 1e-6, leaf
    # published layer 7 (MLA) in layer 3's place is layer 3 again; layer 5
    # (KDA) in its place finds no KDA weights
    assert loss_with(dict(CONFIG, layers_held=[0, 1, 2, 7, 4])) == (
        pytest.approx(seeded.want_loss, abs=1e-7)
    )
    with pytest.raises(KeyError):
        loss_with(dict(CONFIG, layers_held=[0, 1, 2, 5, 4]))
    # the MLA layer is causal and knows no position: a prefix alone gives
    # the prefix's rows, and a token that sees only copies of itself gives
    # the same row at position 1 as at position 0 (a rotated key would not)
    sizes = reference.sizes_of(CONFIG, None)
    p = trees.nested(seeded.flat)["layer_3"]["mla"]
    x = jnp.asarray(np.random.RandomState(3).randn(12, 32), jnp.float32)
    with jax.default_matmul_precision("highest"):
        whole = reference.mla(x, p, sizes, lambda t: t)
        early = reference.mla(x[:5], p, sizes, lambda t: t)
        doubled = reference.mla(
            jnp.concatenate([x[:1], x[:5]]), p, sizes, lambda t: t
        )
    np.testing.assert_allclose(whole[:5], early, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(doubled[1], doubled[0], rtol=1e-5, atol=1e-6)


class ViewKDA(zoo.nn.Module):
    """The KDA layer as it stood before the channels stayed along the
    lanes, written out: the decay, the output norm (a plain `RMSNorm`) and
    the output gate over the (B, L, heads, dim) VIEW.  The same leaves
    under the same names as `zoo.KDA`."""

    hidden: int
    heads: int
    head_dim: int
    taps: int
    eps: float
    dtype: jnp.dtype = jnp.float32

    @zoo.nn.compact
    def __call__(self, x):
        batch, length, _ = x.shape
        heads, dim = self.heads, self.head_dim
        width = heads * dim
        by_head = (batch, length, heads, dim)
        qkv = zoo.dense(3 * width, "qkv", self.dtype, zoo.MIXER_IN)(x)
        weight = self.param("conv_kernel", zoo.tap_init, (self.taps, 3 * width))
        q, k, v = (
            t.reshape(by_head) for t in jnp.split(
                zoo.silu_short_conv(qkv, weight), 3, axis=-1
            )
        )
        a_log = self.param("A_log", zoo.a_log_init, (heads,))
        dt_bias = self.param("dt_bias", zoo.dt_bias_init, (width,))
        f = zoo.dense(width, "f_b", self.dtype)(
            zoo.dense(dim, "f_a", self.dtype)(x)
        )
        g = -jnp.exp(a_log)[:, None] * jax.nn.softplus(
            f.astype(jnp.float32).reshape(by_head)
            + dt_bias.reshape(heads, dim)
        )
        beta = jax.nn.sigmoid(
            zoo.dense(heads, "b", self.dtype)(x).astype(jnp.float32)
        )
        out = zoo.kda(q, k, v, g, beta, qk_norm=(zoo.L2_EPS, dim ** -0.5))
        gate = zoo.dense(width, "g_b", self.dtype)(
            zoo.dense(dim, "g_a", self.dtype)(x)
        ).reshape(by_head)
        out = zoo.RMSNorm(self.eps, self.dtype, name="o_norm")(out)
        return zoo.dense(self.hidden, "o", self.dtype, zoo.MIXER_OUT)(
            (out * jax.nn.sigmoid(gate)).reshape(batch, length, width)
        )


def kda_layer_pair(heads, dim, dtype=jnp.float32, hidden=32, length=80):
    """(the zoo's KDA layer, the view form, their one set of seeded
    leaves with the norm's scale off its seed of ones, x); 80 positions,
    a whole chunk and a padded one: the scan goes its `jnp` form."""
    sizes = (hidden, heads, dim, 4, 1e-5, dtype)
    x = jax.random.normal(jax.random.PRNGKey(1), (1, length, hidden))
    params = zoo.KDA(*sizes[:5]).init(jax.random.PRNGKey(0), x)["params"]
    params = dict(params, o_norm={"scale": 1.0 + 0.3 * jax.random.normal(
        jax.random.PRNGKey(2), (dim,)
    )})
    return zoo.KDA(*sizes), ViewKDA(*sizes), params, x


def kda_layer_readings(layer, params, x, monkeypatch):
    """{name: array}: the layer's output and the gradients of a weighted
    sum of it to every leaf, to x (through the four low-rank gate products
    and the projections) and to the scan's output (a zero array added to
    what `kda` returns)."""
    weight = jax.random.normal(jax.random.PRNGKey(3), x.shape[:2] + (
        layer.hidden,
    ))

    def run(patch, params, x, tap):
        patch.setattr(
            zoo, "kda", lambda *a, **kw: kda_ops.kda(*a, **kw) + tap.reshape(
                a[2].shape
            ).astype(a[2].dtype)
        )
        out, _ = layer.apply({"params": params}, x, mutable=[STEP_METRICS])
        out = out.astype(jnp.float32)
        return (out * weight).sum(), out

    tap = jnp.zeros(x.shape[:2] + (layer.heads * layer.head_dim,))
    with monkeypatch.context() as patch, jax.default_matmul_precision(
        "highest"
    ):
        (_, out), (d_params, d_x, d_tap) = jax.value_and_grad(
            functools.partial(run, patch), argnums=(0, 1, 2), has_aux=True
        )(params, x, tap)
    return {
        "out": np.asarray(out), "d_x": np.asarray(d_x),
        "d_scan_out": np.asarray(d_tap),
        **{f"d_{k}": np.asarray(v, np.float32)
           for k, v in trees.flat(d_params).items()},
    }


def rel_l2(got, want):
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


# the cell's heads and the tiny configuration's
KDA_HEADS = [pytest.param(32, 128, id="32x128"), pytest.param(2, 16, id="2x16")]


@pytest.mark.parametrize("heads, dim", KDA_HEADS)
def test_the_layer_along_the_lanes_is_the_view_form_in_float32(
    monkeypatch, heads, dim
):
    """Norm a head, output gate and decay taken over (B, L, heads x dim)
    against the view form written out above: the output, and the
    gradients to the scan's output, to x, to `o_norm/scale`, `A_log`,
    `dt_bias` and every other leaf, to 1e-6 of each one's norm (the sums
    add in another order, no more); 5e-6 for what the scan's backward
    carries back, whose sums cancel: the last bit of the cotangent it is
    handed reads 1e-6 to 2e-6 in the decay's leaves."""
    lanes, view, params, x = kda_layer_pair(heads, dim)
    got = kda_layer_readings(lanes, params, x, monkeypatch)
    want = kda_layer_readings(view, params, x, monkeypatch)
    assert set(got) == set(want) and {
        "out", "d_x", "d_scan_out", "d_o_norm/scale", "d_A_log", "d_dt_bias",
        "d_g_a/kernel", "d_g_b/kernel", "d_f_a/kernel", "d_f_b/kernel",
    } <= set(got)
    after_the_scan = {
        "out", "d_scan_out", "d_o_norm/scale", "d_g_a/kernel", "d_g_b/kernel",
        "d_o/kernel",
    }
    for name in want:
        assert np.linalg.norm(want[name]) > 0, name
        assert rel_l2(got[name], want[name]) < (
            1e-6 if name in after_the_scan else 5e-6
        ), name


@pytest.mark.parametrize("heads, dim", KDA_HEADS)
def test_the_layer_along_the_lanes_in_bfloat16_inside_the_twins_rule(
    monkeypatch, heads, dim
):
    """In bfloat16 the view form is the twin: the layer's distance from
    the float32 view form is at most `TWIN_RATIO` times the bfloat16 view
    form's own, reading by reading; and the rounding points are the
    parent's (the norm rounds BEFORE the gate, the gate is taken in
    bfloat16): the outputs of the two differ in few places."""
    _, view32, params, x = kda_layer_pair(heads, dim)
    lanes, view, _, _ = kda_layer_pair(heads, dim, jnp.bfloat16)
    want = kda_layer_readings(view32, params, x, monkeypatch)
    twin = kda_layer_readings(view, params, x, monkeypatch)
    got = kda_layer_readings(lanes, params, x, monkeypatch)
    for name in want:
        assert rel_l2(got[name], want[name]) <= (
            reference.TWIN_RATIO * rel_l2(twin[name], want[name])
        ), name
    # a float32 gate or a later cast would move most elements' last bit
    assert (got["out"] != twin["out"]).mean() < 0.1


def test_the_kda_layer_owns_the_leaves_it_always_did():
    """Paths and shapes of the layer's parameter tree, and a checkpoint
    of the view form's tree restored into this one leaf for leaf:
    `o_norm/scale` is ONE scale of a head's width, `dt_bias` one number a
    channel, `A_log` one a head."""
    from flax import serialization

    lanes, view, _, x = kda_layer_pair(2, 16)
    made = lanes.init(jax.random.PRNGKey(0), x)["params"]
    old = view.init(jax.random.PRNGKey(0), x)["params"]
    assert {k: v.shape for k, v in trees.flat(made).items()} == {
        "A_log": (2,), "dt_bias": (32,), "conv_kernel": (4, 96),
        "qkv/kernel": (32, 96), "f_a/kernel": (32, 16),
        "f_b/kernel": (16, 32), "b/kernel": (32, 2), "g_a/kernel": (32, 16),
        "g_b/kernel": (16, 32), "o_norm/scale": (16,), "o/kernel": (32, 32),
    }
    assert len(trees.flat(made)) == KDA_LEAVES
    assert jax.tree.structure(made) == jax.tree.structure(old)
    empty = jax.tree.map(jnp.zeros_like, made)
    restored = serialization.from_bytes(empty, serialization.to_bytes(old))
    for name, leaf in trees.flat(restored).items():
        np.testing.assert_array_equal(
            np.asarray(leaf), np.asarray(trees.flat(old)[name]), name
        )
    # and the seeds are the parent's: the same key makes the same leaves
    for name, leaf in trees.flat(made).items():
        np.testing.assert_array_equal(
            np.asarray(leaf), np.asarray(trees.flat(old)[name]), name
        )


# ---- the routed layer beside its shared expert: the shares ----------------


def test_the_shares_add_up_to_the_uncut_layer():
    """32 experts, top-4 of all 32, over 4 shares of 8 (experts 0-7 ...
    24-31) beside ONE shared expert every share computes alike: the routed
    parts of all shares and the shared expert counted once equal the uncut
    reference's layer."""
    config = dict(CONFIG, held_experts=[0, 32], num_experts_per_token=4,
                  num_experts_published=32)
    sizes = reference.sizes_of(config, None)
    x = jnp.asarray(
        np.random.RandomState(1).randn(48, 32).astype(np.float32)
    )
    whole = MoEFFN(32, 32, 4, 16, 1, None, 2.446, 0.0)
    variables = whole.init(jax.random.PRNGKey(3), x)
    p = variables["params"]
    assert set(p) == {"routed", "shared"}
    with jax.default_matmul_precision("highest"):
        shared = swiglu(x, p["shared"], lambda t: t)
        want = reference.routed(x, p["routed"], sizes, lambda t: t) + shared
        np.testing.assert_allclose(
            whole.apply(variables, x, mutable=MUTABLE)[0], want,
            rtol=2e-4, atol=2e-5,
        )
        total = np.asarray(shared).copy()          # counted once
        for share in range(4):
            first = 8 * share
            held = {
                "router_kernel": p["routed"]["router_kernel"],
                "expert_w_gate_up":
                    p["routed"]["expert_w_gate_up"][first:first + 8],
                "expert_w_down":
                    p["routed"]["expert_w_down"][first:first + 8],
            }
            part = RoutedExperts(
                num_experts=32, top_k=4, ffn_dim=16, held_experts=(first, 8),
                routed_scaling=2.446,
            ).apply(
                {"params": held,
                 ROUTER_STATE: variables[ROUTER_STATE]["routed"]}, x
            )
            total += np.asarray(part)
    np.testing.assert_allclose(total, want, rtol=2e-4, atol=2e-5)
    # and no share alone is the layer's routed part
    routed_whole = np.asarray(want) - np.asarray(shared)
    assert np.abs(np.asarray(part) - routed_whole).max() > (
        0.2 * np.abs(routed_whole).max()
    )


# ---- through the system ---------------------------------------------------


def test_the_init_program_drops_the_forward(seeded):
    """`Trainer.init_state` is one program whose results hang on nothing
    the forward computed: STEP_METRICS (the LAST step's scalars) is zeros
    until a step has run (`split_variables`, inside the program), so the
    compiler drops the forward that flax's `init` traced: no product is
    left, where the cell's init compiled a 16,384-token forward, kernels
    and all, for gauges that the first step overwrites."""
    from elasticdl_tpu.worker.trainer import split_variables

    model = model_of(CONFIG)
    features = {"input_ids": seeded.ids}
    key = jax.random.PRNGKey(0)
    whole = jax.jit(model.init)(key, features)
    program = jax.jit(lambda k, f: split_variables(model.init(k, f)))
    assert "dot_general" in jax.jit(model.init).lower(key, features).as_text()
    assert "dot_general" not in program.lower(key, features).as_text()
    params, state = program(key, features)
    for got, want in zip(jax.tree.leaves(params["params"]),
                         jax.tree.leaves(whole["params"])):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    gauges = jax.tree.leaves(state[STEP_METRICS])
    assert gauges and all(not np.asarray(g).any() for g in gauges)
    assert jax.tree.structure(state[STEP_METRICS]) == jax.tree.structure(
        whole[STEP_METRICS]
    )
    assert any(np.asarray(g).any() for g in jax.tree.leaves(
        whole[STEP_METRICS]
    ))


