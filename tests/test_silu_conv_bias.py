"""`ops/short_conv.py: silu_short_conv` with a BIAS (a state-space layer's
conv, `y = silu(conv_K(u) + b)`): the kernels against the shifted form,
forward and backward (d(bias) a column sum of dz), at the granite cell's
4,352 columns among others, and the unbiased call, which a
linear-attention layer makes, traced to what it was traced to before the
bias entered."""

import hashlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from elasticdl_tpu.ops import short_conv

# (batch, L, d, K): the cell's 34 lane tiles of columns (blocks of 256) over
# two tiles of 16 rows, three tiles of 16 at 128 columns, two of 256 over
# two blocks of 512, seven taps (the most a bias leaves room for)
SHAPES = [(1, 32, 4352, 4), (2, 48, 128, 4), (1, 512, 1024, 4),
          (1, 64, 256, 7)]


def given(batch, length, width, taps):
    keys = jax.random.split(jax.random.PRNGKey(11), 4)
    return (
        jax.random.normal(keys[0], (batch, length, width)),
        jax.random.normal(keys[1], (taps, width)) * 0.5,
        jax.random.normal(keys[2], (width,)),
        jax.random.normal(keys[3], (batch, length, width)),
    )


@pytest.mark.parametrize("batch, length, width, taps", SHAPES)
def test_biased_kernels_match_the_shifted_form(batch, length, width, taps):
    u, weight, bias, g = given(batch, length, width, taps)
    assert short_conv.silu_conv_shapes_ok(u.shape, weight.shape, True)
    np.testing.assert_allclose(
        short_conv.silu_short_conv(u, weight, bias),
        short_conv.shifted_silu_conv(u, weight, bias), rtol=1e-5, atol=1e-5,
    )

    def grads(fn):
        return jax.grad(
            lambda a, b, c: (fn(a, b, c) * g).sum(), argnums=(0, 1, 2)
        )(u, weight, bias)

    for name, got, want in zip(
        ("du", "d(weight)", "d(bias)"), grads(short_conv.silu_short_conv),
        grads(short_conv.shifted_silu_conv),
    ):
        np.testing.assert_allclose(
            got, want, rtol=1e-4, atol=1e-4, err_msg=name
        )


def test_the_bias_enters_and_its_gradient_is_a_column_sum():
    u, weight, bias, g = given(2, 48, 128, 4)
    plain = short_conv.silu_short_conv(u, weight)
    biased = short_conv.silu_short_conv(u, weight, bias)
    assert np.abs(np.asarray(biased - plain)).max() > 0.1
    np.testing.assert_allclose(
        short_conv.silu_short_conv(u, weight, jnp.zeros_like(bias)), plain,
        rtol=1e-6, atol=1e-6,
    )
    # d(bias) = sum over batch and rows of dz = g * silu'(z)
    x = jnp.pad(u, ((0, 0), (3, 0), (0, 0)))
    z = sum(weight[k] * x[:, k:k + 48] for k in range(4)) + bias
    s = jax.nn.sigmoid(z)
    want = (g * s * (1.0 + z * (1.0 - s))).sum(axis=(0, 1))
    got = jax.grad(
        lambda b: (short_conv.silu_short_conv(u, weight, b) * g).sum()
    )(bias)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


def test_biased_shapes_names_and_types():
    ok = short_conv.silu_conv_shapes_ok
    assert ok((1, 8192, 4352), (4, 4352), True)             # the cell's
    assert ok((1, 8192, 4352), (8, 4352))
    # the bias's gradient wants a row of the partial after the taps'
    assert not ok((1, 8192, 4352), (8, 4352), True)
    u, w, b, _ = given(1, 48, 128, 8)
    assert short_conv.silu_short_conv(u, w, b).shape == u.shape   # jnp form
    u, w, b, _ = given(1, 48, 128, 4)
    u16 = u.astype(jnp.bfloat16)
    grad = jax.grad(
        lambda a, w, b: short_conv.silu_short_conv(a, w, b).astype(
            jnp.float32
        ).sum(), argnums=(0, 1, 2),
    )
    names = sorted(set(re.findall(
        r"\b\w*short_conv_(?:fwd|bwd)\b", str(jax.make_jaxpr(grad)(u16, w, b))
    )))
    # the names the benchmark's rule for the short conv finds
    assert names == ["silu_short_conv_bwd", "silu_short_conv_fwd"]
    du, dw, db = grad(u16, w, b)
    assert (du.dtype, dw.dtype, db.dtype) == (
        jnp.bfloat16, jnp.float32, jnp.float32
    )
    assert short_conv.silu_short_conv(u16, w, b).dtype == jnp.bfloat16


# sha256 of str(make_jaxpr(grad(silu_short_conv ...))) at the Kimi cell's
# bfloat16 shape (2, 8192, 12288) under (4, 12288) WITHOUT a bias,
# recorded at the commit before the bias entered: the unbiased call
# compiles to what it compiled to.
UNBIASED_JAXPR = (
    "a9e30b19b9ae5d61e5244d0737b59a755e916168c8e2304baf01146c4e00d6c6"
)


def test_the_unbiased_call_is_the_parents():
    text = str(jax.make_jaxpr(jax.grad(
        lambda a, b: short_conv.silu_short_conv(a, b).astype(
            jnp.float32
        ).sum(), argnums=(0, 1),
    ))(
        jax.ShapeDtypeStruct((2, 8192, 12288), jnp.bfloat16),
        jax.ShapeDtypeStruct((4, 12288), jnp.float32),
    ))
    assert "0x" not in text
    assert hashlib.sha256(text.encode()).hexdigest() == UNBIASED_JAXPR
