"""`ops/rotary.py`: the one-pass kernel (in the Pallas interpreter here)
against `halves_turn`, the plain `jnp` arithmetic every decoder ran before
it, at every (turned columns, head width, heads) a cell has; the shapes
the kernel does not take; the gauge the attention layers sow."""

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.test_util import check_grads

from elasticdl_tpu.layers import step_metrics
from elasticdl_tpu.layers.step_metrics import STEP_METRICS
from elasticdl_tpu.ops import rotary
from model_zoo.common import decoder
from model_zoo.common.mla import MLA

YARN = 0.1 * np.log(64) + 1

# (B, L, heads, head width), turned columns, the first of them, factor
CELLS = [
    pytest.param((2, 32, 64, 128), 128, 0, 1.0, id="laguna-window-q"),
    pytest.param((1, 32, 48, 128), 64, 0, YARN, id="laguna-full-q-yarn"),
    pytest.param((1, 32, 8, 128), 64, 0, YARN, id="laguna-full-k-yarn"),
    pytest.param((1, 64, 28, 128), 128, 0, 1.0, id="smallthinker-q"),
    pytest.param((1, 64, 4, 128), 128, 0, 1.0, id="smallthinker-k"),
    pytest.param((1, 32, 16, 128), 128, 0, 1.0, id="ouro"),
    pytest.param((2, 32, 32, 64), 64, 0, 1.0, id="lfm2-q"),
    pytest.param((2, 32, 8, 64), 64, 0, 1.0, id="lfm2-k"),
    pytest.param((1, 32, 16, 256), 64, 0, 1.0, id="qwen3-next-q"),
    pytest.param((1, 32, 2, 256), 64, 0, 1.0, id="qwen3-next-k"),
    pytest.param((2, 32, 20, 256), 64, 192, 1.0, id="glm-mla-q"),
    pytest.param((2, 64, 1, 64), 64, 0, 1.0, id="glm-mla-shared-key"),
    # 1,040 rows in blocks of 16: the tables' row block follows the row
    # block of x, batch by batch
    pytest.param((2, 1040, 2, 128), 128, 0, 1.0, id="many-row-blocks"),
]
# what falls to `halves_turn`: H x D no whole lane tiles; no row block of
# 16 divides L; a whole head of 256 (its halves lie in two lane tiles); a
# run that starts mid-tile and crosses into the next
FALLBACKS = [
    pytest.param((2, 32, 3, 64), 64, 0, id="columns-192"),
    pytest.param((2, 24, 4, 128), 128, 0, id="rows-24"),
    pytest.param((1, 32, 2, 256), 256, 0, id="whole-head-256"),
    pytest.param((1, 32, 2, 256), 128, 64, id="run-over-two-tiles"),
    pytest.param((2, 128, 4, 16), 16, 0, id="test-models-heads-of-16"),
    pytest.param((1, 31, 1, 64), 64, 0, id="narrow-odd-length"),
]


def operands(shape, columns, dtype, seed=0):
    kx, kg = jax.random.split(jax.random.PRNGKey(seed))
    inv_freq = jnp.asarray(
        1e4 ** (-np.arange(0, columns, 2) / columns), jnp.float32
    )
    return (
        jax.random.normal(kx, shape, jnp.float32).astype(dtype),
        jax.random.normal(kg, shape, jnp.float32).astype(dtype), inv_freq,
    )


def value_and_vjp(turn, x, g, *rest):
    out, vjp = jax.vjp(lambda x: turn(x, *rest), x)
    return out, vjp(g)[0]


def names(jaxpr) -> set:
    """The names of every `pallas_call` a traced program holds."""
    found = set()
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found.add(eqn.params["name"])
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found |= names(sub)
    return found


def ulps(got, want, dtype):
    got, want = (np.asarray(t.astype(jnp.float32)) for t in (got, want))
    bit = 2.0 ** (-7 if dtype == jnp.bfloat16 else -23)
    return (np.abs(got - want) / (np.abs(want) * bit + 1e-30)).max()


@pytest.mark.parametrize("shape, columns, first, factor", CELLS)
def test_the_kernel_meets_the_halves_at_a_cells_shape(
    shape, columns, first, factor
):
    """bfloat16 in and out, the same float32 products inside: forward
    within ONE bfloat16 ulp of `halves_turn` (here: the same bits), the
    VJP within the usual two (its reference sums the halves' cotangents
    in another order)."""
    assert rotary.one_pass_ok(shape, columns, first)
    x, g, inv_freq = operands(shape, columns, jnp.bfloat16)
    rest = (inv_freq, factor, first)
    traced = jax.make_jaxpr(
        lambda x, g: value_and_vjp(rotary.rotary_turn, x, g, *rest)
    )(x, g)
    assert names(traced.jaxpr) == {"rotary_turn", "rotary_turn_bwd"}
    got = jax.jit(
        lambda x, g: value_and_vjp(rotary.rotary_turn, x, g, *rest)
    )(x, g)
    want = jax.jit(
        lambda x, g: value_and_vjp(rotary.halves_turn, x, g, *rest)
    )(x, g)
    assert got[0].dtype == got[1].dtype == jnp.bfloat16
    assert ulps(got[0], want[0], jnp.bfloat16) <= 1.0
    assert ulps(got[1], want[1], jnp.bfloat16) <= 2.0
    # the columns beside a head's turned run pass untouched
    kept = np.ones(shape[3], bool)
    kept[first:first + columns] = False
    np.testing.assert_array_equal(
        np.asarray(got[0].astype(jnp.float32))[..., kept],
        np.asarray(x.astype(jnp.float32))[..., kept],
    )


@pytest.mark.parametrize("shape, columns, first", FALLBACKS)
def test_a_shape_the_kernel_does_not_take_goes_the_halves_way(
    shape, columns, first
):
    assert not rotary.one_pass_ok(shape, columns, first)
    x, g, inv_freq = operands(shape, columns, jnp.bfloat16)
    traced = jax.make_jaxpr(
        lambda x, g: value_and_vjp(
            rotary.rotary_turn, x, g, inv_freq, 1.0, first
        )
    )(x, g)
    assert not names(traced.jaxpr)
    assert str(traced) == str(jax.make_jaxpr(
        lambda x, g: value_and_vjp(
            rotary.halves_turn, x, g, inv_freq, 1.0, first
        )
    )(x, g))


@pytest.mark.parametrize("shape, columns, first, factor", [
    ((2, 32, 4, 128), 128, 0, 1.0), ((1, 32, 2, 128), 64, 0, YARN),
    ((1, 32, 4, 64), 64, 0, 1.0), ((1, 32, 2, 256), 64, 192, 1.0),
    ((2, 32, 1, 64), 64, 0, 1.0),
], ids=["whole-tile", "yarn-half-tile", "two-heads-a-tile", "mla-q",
        "folded"])
def test_the_backward_is_the_transpose(shape, columns, first, factor):
    """float32 through the kernel: <turn(x), g> = <x, turn^T(g)>; the
    numerical gradient agrees (`check_grads`); and with `factor` 1 the
    turn is orthogonal, so its transpose undoes it."""
    x, g, inv_freq = operands(shape, columns, jnp.float32, seed=3)

    def turn(x):
        return rotary.rotary_turn(x, inv_freq, factor, first)

    out, back = value_and_vjp(rotary.rotary_turn, x, g, inv_freq, factor,
                              first)
    # two float32 sums of some ten thousand terms that cancel
    np.testing.assert_allclose(
        jnp.vdot(out, g), jnp.vdot(x, back),
        atol=1e-6 * float(jnp.linalg.norm(out) * jnp.linalg.norm(g)),
    )
    np.testing.assert_allclose(
        out, rotary.halves_turn(x, inv_freq, factor, first), atol=1e-6
    )
    check_grads(turn, (x,), order=1, modes=["rev"], atol=2e-2, rtol=2e-2)
    if factor == 1.0:
        undone = jax.vjp(turn, x)[1](out)[0]
        np.testing.assert_allclose(undone, x, atol=1e-5)


def test_rotary_from_a_column_on_is_the_split_turn_and_join():
    """`decoder.rotary(x, theta, first)` (MLA's query: 192 columns that
    carry no position before 64 that do) against the turn of the split-off
    part, through the kernel and through the halves."""
    for shape in ((2, 32, 4, 256), (2, 32, 4, 24)):
        first = shape[3] - shape[3] // 4
        x = jax.random.normal(jax.random.PRNGKey(1), shape, jnp.float32)
        passed, turned = jnp.split(x, [first], axis=-1)
        want = jnp.concatenate(
            [passed, decoder.rotary(turned, 1e4)], axis=-1
        )
        np.testing.assert_allclose(
            decoder.rotary(x, 1e4, first), want, atol=1e-6
        )


class Turned(nn.Module):
    """What an attention layer does around its turn."""

    rope: decoder.Rope

    @nn.compact
    def __call__(self, q, k):
        decoder.sow_rope_one_pass(self, self.rope.columns, q.shape, k.shape)
        return (
            decoder.partial_rotary(q, self.rope),
            decoder.partial_rotary(k, self.rope),
        )


@pytest.mark.parametrize("q_shape, k_shape, share", [
    ((2, 32, 8, 128), (2, 32, 2, 128), 1.0),
    # the K/V heads' 192 columns are no whole lane tiles: 8 of 11 heads
    ((2, 32, 8, 64), (2, 32, 3, 64), 8 / 11),
    ((2, 32, 4, 16), (2, 32, 2, 16), 0.0),
], ids=["all", "queries-only", "none"])
def test_a_layer_sows_the_share_of_its_turn_the_kernel_took(
    q_shape, k_shape, share
):
    rope = decoder.plain_rope(q_shape[3], 1e4)
    q, k = jnp.ones(q_shape, jnp.bfloat16), jnp.ones(k_shape, jnp.bfloat16)
    _, sown = Turned(rope).apply({}, q, k, mutable=[STEP_METRICS])
    assert float(sown[STEP_METRICS]["rope_one_pass_ratio"]) == (
        pytest.approx(share)
    )
    gauge = step_metrics.declared()["rope_one_pass_ratio"]
    assert gauge.name == "worker_rope_one_pass_ratio"
    assert gauge.labelnames == ("layer",)
    step_metrics.publish({"layer_3/attn/rope_one_pass_ratio": share})
    assert gauge.child_values()[("layer_3/attn",)] == pytest.approx(share)


@pytest.mark.parametrize("rotate", [True, False])
def test_mla_sows_the_share_only_where_it_turns(rotate):
    """GLM's layer turns 64 of a query head's 256 columns and the one
    shared 64-wide key part, both through the kernel; Kimi's (`rotate`
    False: no positions) turns nothing and reports nothing."""
    layer = MLA(
        hidden=64, heads=2, q_lora_rank=None, kv_lora_rank=32,
        qk_nope_head_dim=192, qk_rope_head_dim=64, v_head_dim=32,
        rope_theta=1e4, eps=1e-6, dtype=jnp.bfloat16, rotate=rotate,
    )
    x = jnp.ones((2, 32, 64), jnp.bfloat16)
    variables = layer.init(jax.random.PRNGKey(0), x)
    traced = jax.make_jaxpr(lambda v, x: layer.apply(
        v, x, mutable=[STEP_METRICS]
    ))({"params": variables["params"]}, x)
    _, sown = layer.apply(
        {"params": variables["params"]}, x, mutable=[STEP_METRICS]
    )
    if rotate:
        assert names(traced.jaxpr) >= {"rotary_turn"}
        assert float(sown[STEP_METRICS]["rope_one_pass_ratio"]) == 1.0
    else:
        assert "rotary_turn" not in names(traced.jaxpr)
        assert "rope_one_pass_ratio" not in sown.get(STEP_METRICS, {})
