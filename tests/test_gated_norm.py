"""`model_zoo/common/decoder.py: GatedRMSNorm` on the CPU: the grouped form
(the statistics as products with a 0/1 matrix, the channel axis never
split) against the plain view written out here, values and the gradients
to y, z and the scale, at a group of 64 channels (the tiny configs') and
of 512 (the Nemotron cell's: four whole lane tiles); and one group as the
program it always was, equation by equation."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from model_zoo.common import decoder

EPS = 1e-5


def _view_form(y, z, scale, groups, dtype):
    """The gate first, then `rms_norm` over a (..., groups, channels /
    groups) view: what the module computed before it kept the channels
    along one axis."""
    gated = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
    by_group = gated.reshape(*gated.shape[:-1], groups, -1)
    normed = by_group * jax.lax.rsqrt(
        jnp.mean(jnp.square(by_group), axis=-1, keepdims=True) + EPS
    ) * scale.reshape(groups, -1)
    return normed.reshape(gated.shape).astype(dtype)


def _module_form(y, z, scale, groups, dtype):
    return decoder.GatedRMSNorm(EPS, dtype, groups).apply(
        {"params": {"scale": scale}}, y, z
    )


def _inputs(groups, width, dtype, tokens=(2, 12)):
    rng = np.random.RandomState(groups * 1000 + width)
    channels = groups * width
    # groups of unlike size, so that a statistic over the wrong channels
    # is far off
    size = np.repeat(rng.uniform(0.2, 5.0, groups), width)
    y = jnp.asarray(rng.randn(*tokens, channels) * size, dtype)
    z = jnp.asarray(rng.randn(*tokens, channels), dtype)
    scale = jnp.asarray(1.0 + 0.1 * rng.randn(channels), jnp.float32)
    cotangent = jnp.asarray(rng.randn(*tokens, channels), jnp.float32)
    return y, z, scale, cotangent


def _value_and_grads(form, groups, dtype, y, z, scale, cotangent):
    def loss(y, z, scale):
        out = form(y, z, scale, groups, dtype)
        return (out.astype(jnp.float32) * cotangent).sum(), out

    (_, out), grads = jax.value_and_grad(
        loss, argnums=(0, 1, 2), has_aux=True
    )(y, z, scale)
    return (out, *grads)


@pytest.mark.parametrize("dtype, limit", [
    pytest.param(jnp.float32, 2e-6, id="float32"),
    # a bfloat16 array's last bit is 2^-8 of its value
    pytest.param(jnp.bfloat16, 2 ** -7, id="bfloat16"),
])
@pytest.mark.parametrize("width", [64, 512])
@pytest.mark.parametrize("groups", [2, 8])
def test_the_grouped_form_is_the_view_forms_mathematics(
    groups, width, dtype, limit
):
    y, z, scale, cotangent = _inputs(groups, width, dtype)
    got = _value_and_grads(_module_form, groups, dtype, y, z, scale, cotangent)
    want = _value_and_grads(_view_form, groups, dtype, y, z, scale, cotangent)
    for name, a, b in zip(("out", "dy", "dz", "dscale"), got, want):
        assert a.dtype == b.dtype and a.shape == b.shape, name
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        assert np.abs(a - b).max() <= limit * np.abs(b).max(), name
    # the statistic is a group's own: one norm over all the channels is
    # far outside the limit at these inputs
    whole = _module_form(y, z, scale, 1, dtype)
    assert np.abs(
        np.asarray(whole, np.float32) - np.asarray(want[0], np.float32)
    ).max() > 0.1 * np.abs(np.asarray(want[0], np.float32)).max()


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_one_group_is_the_program_it_was(dtype):
    """`groups == 1` (the Granite cell's nine layers) traces to the plain
    `rms_norm(y * silu(z))`, forward and gradient, equation by equation:
    the grouped form changes nothing there."""
    y, z, scale, cotangent = _inputs(1, 128, dtype)

    def written_out(y, z, scale, groups, dtype):
        x = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
        return (x * jax.lax.rsqrt(
            jnp.mean(jnp.square(x), axis=-1, keepdims=True) + EPS
        ) * scale).astype(dtype)

    def program(form):
        return str(jax.make_jaxpr(
            lambda *args: _value_and_grads(form, 1, dtype, *args)
        )(y, z, scale, cotangent))

    assert program(_module_form) == program(written_out)
    assert "dot_general" not in program(_module_form)
    # and the grouped form is another program
    assert "dot_general" in str(jax.make_jaxpr(
        lambda *args: _module_form(*args, 2, dtype)
    )(y, z, scale))
