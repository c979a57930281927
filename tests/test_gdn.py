"""The gated delta rule with ONE decay a head and value heads that share
key heads (elasticdl_tpu/ops/gdn.py): the chunked `jnp` form and the
Pallas kernels (interpreted here) against the token-by-token recurrence of
the plain reference (`benchmarks/reference/qwen3_next.py:
delta_recurrence`, which shares none of the chunked algebra), forward and
all five gradients, at one and at two value heads a key head, at no decay,
at a mild one and at one so strong that `1 / exp(G)` would overflow; a
length that is no whole number of chunks; the norms inside the op; the
tie to `ops/kda.py` (the scalar op is `kda` with g broadcast over the
channels); heads that are no whole lane tile and differ in width between
keys and values (96 | 192, padded to 128 | 256 for the kernels) under a
write strength in (1, 2); the admission rule, the names and the types."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference.qwen3_next import delta_recurrence
from elasticdl_tpu.ops import gdn as gdn_ops
from elasticdl_tpu.ops import kda as kda_ops


def recurrence(q, k, v, g, beta):
    """q, k (B, L, H_k, D), v (B, L, H_v, D), g and beta (B, L, H_v)
    through the one-head recurrence, value head h on key head h // r."""
    ratio = v.shape[2] // q.shape[2]
    q, k = (jnp.repeat(t, ratio, axis=2) for t in (q, k))
    one = jax.vmap(jax.vmap(delta_recurrence, in_axes=1, out_axes=1))
    with jax.default_matmul_precision("highest"):
        return one(q, k, v, g, beta)


def inputs(batch, length, key_heads, ratio, dim, g_min, seed=0,
           dtype=jnp.float32):
    """q and k L2-normed a head, as a model hands them over; g uniform in
    [g_min, 0] a token and value head."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    heads = key_heads * ratio
    key_shape = (batch, length, key_heads, dim)
    shape = (batch, length, heads, dim)

    def normed(key, scale):
        x = jax.random.normal(key, key_shape)
        return x / jnp.linalg.norm(x, axis=-1, keepdims=True) * scale

    return (
        normed(keys[0], dim ** -0.5).astype(dtype),
        normed(keys[1], 1.0).astype(dtype),
        jax.random.normal(keys[2], shape).astype(dtype),
        g_min * jax.random.uniform(keys[3], shape[:3]),
        jax.nn.sigmoid(jax.random.normal(keys[4], shape[:3])),
        jax.random.normal(keys[5], shape),
    )


def out_and_grads(fn, q, k, v, g, beta, weight):
    """(fn's output, the gradients of a weighted sum of it by the five
    operands) from ONE compiled program: walked a primitive at a time, an
    interpreted kernel's forward ran twice a test and its backward once,
    each an equation at a time."""
    def weighted(*operands):
        out = fn(*operands)
        return (out * weight).sum(), out

    with jax.default_matmul_precision("highest"):
        (_, out), grads = jax.jit(jax.value_and_grad(
            weighted, argnums=(0, 1, 2, 3, 4), has_aux=True
        ))(q, k, v, g, beta)
    return out, grads


def assert_close(got, want, limit, what):
    error = float(
        jnp.linalg.norm(got - want) / (jnp.linalg.norm(want) + 1e-30)
    )
    assert error < limit, (what, error)


# g down to -20 a token: over a chunk of 64 the running sum reaches -1280
# and exp(+1280) is far past float32 (and float64)
DECAYS = [
    pytest.param(0.0, id="no-decay"),
    pytest.param(-1.0, id="mild"),
    pytest.param(-20.0, id="strong"),
]
FORMS = [
    pytest.param(gdn_ops.chunked_gdn, id="jnp"),
    pytest.param(gdn_ops._gdn, id="kernels"),
]
RATIOS = [pytest.param(1, id="r1"), pytest.param(2, id="r2")]
NAMES = ("dq", "dk", "dv", "dg", "dbeta")


@pytest.mark.parametrize("g_min", DECAYS)
@pytest.mark.parametrize("ratio", RATIOS)
@pytest.mark.parametrize("form", FORMS)
def test_chunked_forms_match_the_recurrence(form, ratio, g_min):
    """Two chunks of two key heads of 128: the output, and the gradient
    of a weighted sum of it by q, k, v, g and beta."""
    args = inputs(1, 128, 2, ratio, 128, g_min)
    assert gdn_ops.gdn_shapes_ok(*(a.shape for a in args[:3]))
    want_out, want = out_and_grads(recurrence, *args)
    out, got = out_and_grads(form, *args)
    assert np.isfinite(np.asarray(out)).all()
    assert_close(out, want_out, 5e-5, "o")
    for name, a, b in zip(NAMES, got, want):
        assert a.shape == b.shape and np.isfinite(np.asarray(a)).all()
        assert_close(a, b, 2e-4, name)


def wide_inputs(batch, length, heads, dk, dv, g_min, beta_low, seed=0):
    """`inputs` at key heads `dk` wide under value heads `dv` wide, one a
    key head, raw q and k (the op norms them) and beta uniform in
    [beta_low, beta_low + 1]."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    shape = (batch, length, heads)
    return (
        3.0 * jax.random.normal(keys[0], (*shape, dk)) + 0.1,
        0.5 * jax.random.normal(keys[1], (*shape, dk)) - 0.05,
        jax.random.normal(keys[2], (*shape, dv)),
        g_min * jax.random.uniform(keys[3], shape),
        beta_low + jax.random.uniform(keys[4], shape),
        jax.random.normal(keys[5], (*shape, dv)),
    )


# the Olmo-Hybrid cell's heads: 96 | 192 is (dk, dv), neither a whole lane
# tile nor equal; r = 1; beta in (1, 2) is the negative-eigenvalue range
WIDE = [
    pytest.param(gdn_ops.chunked_gdn, -1.0, 1.0, id="jnp-mild-over-one"),
    pytest.param(gdn_ops.gdn, -1.0, 1.0, id="kernels-mild-over-one"),
    pytest.param(gdn_ops.gdn, -20.0, 1.0, id="kernels-strong-over-one"),
    pytest.param(gdn_ops.gdn, 0.0, 0.0, id="kernels-no-decay-under-one"),
]


@pytest.mark.parametrize("form, g_min, beta_low", WIDE)
def test_heads_of_96_and_192_match_the_recurrence(form, g_min, beta_low):
    """Two chunks of two heads 96 wide in keys and 192 in values, the L2
    norms inside the op at 96^-1/2: the output and all five gradients
    against the recurrence on operands normed outside.  `gdn` hands the
    kernels 128 | 256 zero-padded columns a head and slices back."""
    q, k, v, g, beta, weight = wide_inputs(1, 128, 2, 96, 192, g_min,
                                           beta_low, seed=3)
    assert gdn_ops.gdn_shapes_ok(q.shape, k.shape, v.shape)
    norm = (1e-6, 96 ** -0.5)

    def plain(q, k, v, g, beta):
        return recurrence(
            kda_ops.l2_normed(q, *norm), kda_ops.l2_normed(k, norm[0]),
            v, g, beta,
        )

    def inside(q, k, v, g, beta):
        return form(q, k, v, g, beta, norm)

    want_out, want = out_and_grads(plain, q, k, v, g, beta, weight)
    out, got = out_and_grads(inside, q, k, v, g, beta, weight)
    assert out.shape == (1, 128, 2, 192)
    assert np.isfinite(np.asarray(out)).all()
    assert_close(out, want_out, 5e-5, "o")
    for name, a, b in zip(NAMES, got, want):
        assert a.shape == b.shape and np.isfinite(np.asarray(a)).all()
        assert_close(a, b, 2e-4, name)


def test_the_kernels_see_padded_heads_and_the_caller_does_not():
    """At 96 | 192 the entry runs the two kernels on (B, L, H x 128) keys
    and (B, L, H x 256) values with a (256, 128) state a head; a quarter
    of those columns is padding, none at heads of whole tiles."""
    q, k, v, g, beta, _ = wide_inputs(1, 128, 2, 96, 192, -1.0, 1.0)
    jaxpr = str(jax.make_jaxpr(jax.grad(
        lambda *a: gdn_ops.gdn(*a).sum(), argnums=(0, 1, 2)
    ))(q, k, v, g, beta))
    assert "gdn_chunk_fwd" in jaxpr and "gdn_chunk_bwd" in jaxpr
    assert "f32[1,128,256]" in jaxpr and "f32[1,128,512]" in jaxpr
    assert "f32[1,2,2,256,128]" in jaxpr            # the boundary states
    assert gdn_ops.padded_lanes_ratio(q.shape, k.shape, v.shape) == 0.25
    whole = ((1, 128, 2, 128), (1, 128, 2, 128), (1, 128, 4, 128))
    assert gdn_ops.padded_lanes_ratio(*whole) == 0.0
    # no kernel runs on a test model's narrow heads: nothing is padded
    narrow = ((1, 128, 2, 8), (1, 128, 2, 8), (1, 128, 2, 16))
    assert gdn_ops.padded_lanes_ratio(*narrow) == 0.0
    assert gdn_ops._groups(10, 1, 256) == (2, 2)     # the cell's ten heads


@pytest.mark.parametrize("ratio", RATIOS)
def test_the_entry_pads_a_length_that_is_no_whole_chunk(ratio):
    """80 positions go the `jnp` form, padded to 128 with tokens that
    leave the state alone; the outputs and gradients are the first 80's."""
    args = inputs(2, 80, 2, ratio, 16, -2.0, seed=1)
    assert not gdn_ops.gdn_shapes_ok(*(a.shape for a in args[:3]))
    want_out, want = out_and_grads(recurrence, *args)
    out, got = out_and_grads(gdn_ops.gdn, *args)
    assert out.shape == (2, 80, 2 * ratio, 16)
    assert_close(out, want_out, 5e-5, "o")
    for name, a, b in zip(NAMES, got, want):
        assert_close(a, b, 2e-4, name)
    # a chunk of another size is the same number
    assert_close(
        gdn_ops.chunked_gdn(*args[:5], chunk=16), out, 5e-5, "chunk 16"
    )


@pytest.mark.parametrize("form", FORMS)
def test_the_l2_norms_inside_the_op(form):
    """`qk_norm` = (eps, q's scale): raw q and k go in, the op norms them
    once a key head; the recurrence on operands normed outside is the
    same number, and so are the gradients by the RAW q and k."""
    q, k, v, g, beta, weight = inputs(1, 128, 2, 2, 128, -1.0, seed=4)
    q, k = 3.0 * q + 0.1, 0.5 * k - 0.05           # no unit rows
    norm = (1e-6, 128 ** -0.5)

    def plain(q, k, v, g, beta):
        return recurrence(
            kda_ops.l2_normed(q, *norm), kda_ops.l2_normed(k, norm[0]),
            v, g, beta,
        )

    def inside(q, k, v, g, beta):
        return form(q, k, v, g, beta, norm)

    want_out, want = out_and_grads(plain, q, k, v, g, beta, weight)
    out, got = out_and_grads(inside, q, k, v, g, beta, weight)
    assert_close(out, want_out, 5e-5, "o")
    for name, a, b in zip(NAMES, got, want):
        assert_close(a, b, 2e-4, name)


def test_the_scalar_op_is_kda_with_g_broadcast_over_the_channels():
    """What ties the new case to the old: one decay a head, handed to
    `ops/kda.py` as 128 equal channels with q and k repeated to the value
    heads, is the same output and the same gradients (dg the sum over the
    channels).  (ONE key head under two value heads: the second key head
    of the first writing ran the same two kernels over twice the heads,
    and its compile was the longest of this file's tests.)"""
    q, k, v, g, beta, weight = inputs(1, 128, 1, 2, 128, -1.0, seed=7)

    def through_kda(q, k, v, g, beta):
        q, k = (jnp.repeat(t, 2, axis=2) for t in (q, k))
        wide = jnp.broadcast_to(g[..., None], (*g.shape, q.shape[-1]))
        return kda_ops.kda(q, k, v, wide, beta)

    want_out, want = out_and_grads(through_kda, q, k, v, g, beta, weight)
    out, got = out_and_grads(gdn_ops.gdn, q, k, v, g, beta, weight)
    assert_close(out, want_out, 5e-5, "o")
    for name, a, b in zip(NAMES, got, want):
        assert_close(a, b, 2e-4, name)


def test_bfloat16_operands_keep_float32_state_and_types():
    """bfloat16 q, k, v: the output is bfloat16, dg and dbeta float32, and
    the numbers those of the recurrence on the same rounded operands to
    bfloat16's rounding."""
    args = inputs(1, 128, 2, 2, 128, -1.0, seed=2, dtype=jnp.bfloat16)
    out = gdn_ops.gdn(*args[:5])
    assert out.dtype == jnp.bfloat16
    want = recurrence(*(a.astype(jnp.float32) for a in args[:5]))
    assert_close(out.astype(jnp.float32), want, 2e-2, "o")
    grads = jax.grad(
        lambda *a: (gdn_ops.gdn(*a).astype(jnp.float32) * args[5]).sum(),
        argnums=(0, 1, 2, 3, 4),
    )(*args[:5])
    assert [x.dtype for x in grads] == [
        jnp.bfloat16, jnp.bfloat16, jnp.bfloat16, jnp.float32, jnp.float32
    ]
    assert [x.shape for x in grads] == [a.shape for a in args[:5]]


@pytest.mark.parametrize("shapes,ok", [
    (((2, 128, 16, 128), (2, 128, 32, 128)), True),
    (((2, 128, 32, 128), (2, 128, 32, 128)), True),
    (((2, 128, 16, 128), (2, 128, 24, 128)), False),    # no whole ratio
    (((2, 100, 16, 128), (2, 100, 32, 128)), False),    # no whole chunks
    (((2, 128, 16, 64), (2, 128, 32, 128)), False),     # no whole lane tile
    (((2, 128, 2, 128), (2, 128, 32, 128)), False),     # 16 a key head
    # the Olmo-Hybrid cell's: 96 | 192, one value head a key head, padded
    (((1, 8192, 10, 96), (1, 8192, 10, 192)), True),
    (((1, 8192, 30, 96), (1, 8192, 30, 192)), True),
    (((1, 128, 2, 128), (1, 128, 2, 256)), True),       # dk != dv, whole
    (((1, 128, 2, 96), (1, 128, 2, 64)), False),        # half a tile pads
    (((1, 128, 2, 8), (1, 128, 2, 16)), False),         # a test's heads
    (((1, 128, 1, 96), (1, 128, 8, 192)), False),       # 8 x 256 a key head
])
def test_the_admission_rule(shapes, ok):
    qk, v = shapes
    assert gdn_ops.gdn_shapes_ok(qk, qk, v) is ok


@pytest.mark.parametrize("key_heads, ratio, width, want", [
    (16, 2, 128, (4, 8)),          # the cell's: eight value heads a step
    (32, 1, 128, (8, 8)),
    (2, 2, 128, (2, 4)),
    (2, 1, 128, (2, 2)),
    (3, 2, 128, (1, 2)),           # no half of four divides three
    (16, 2, 256, (2, 4)),          # a wider head, fewer of them
    (16, 16, 128, (1, 16)),        # one key head is the least
])
def test_the_heads_of_a_grid_step(key_heads, ratio, width, want):
    assert gdn_ops._groups(key_heads, ratio, width) == want
    assert key_heads % want[0] == 0


def test_the_kernels_carry_their_own_names():
    """A device trace tells the scalar kernels from KDA's by name
    (`kda_core_ms_per_step` takes `kda_*fwd|bwd` alone), and what the
    forward names for a block's remat is kept by no policy."""
    from model_zoo.common import decoder

    args = inputs(1, 128, 2, 2, 128, -1.0)
    jaxpr = str(jax.make_jaxpr(jax.grad(
        lambda *a: gdn_ops.gdn(*a).sum(), argnums=(0, 1, 2, 3, 4)
    ))(*args[:5]))
    names = sorted(set(re.findall(r"\b\w+_chunk_(?:fwd|bwd)\b", jaxpr)))
    assert names == ["gdn_chunk_bwd", "gdn_chunk_fwd"]
    assert not re.search(r"kda_\w*(fwd|bwd)", jaxpr)
    for name in gdn_ops.RESULT_NAMES:
        assert f"name={name}" in jaxpr
    assert gdn_ops.SAVED_NAMES == ()
    assert not set(gdn_ops.RESULT_NAMES) & set(decoder.SAVED_NAMES)
