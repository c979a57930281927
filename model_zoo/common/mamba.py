"""The Mamba-2 token mixer the zoo's state-space decoders share
(`model_zoo/granite/granite_hybrid.py`, `model_zoo/nemotron/nemotron_h.py`),
as `model_zoo/common/mla.py` keeps what the latent-attention models share:

    [z | xBC | dt] = x W_in;  xBC = silu(conv_K(xBC) + b)
    [x | B | C] = split(xBC);  dt = softplus(dt + dt_bias)
    S_t = exp(-exp(A_log) dt_t) S_{t-1} + dt_t x_t B_{t,g}^T
    y_t = S_t C_{t,g} + D x_t                     head h reads group g(h)
    Mix = (RMSNorm_g(y * silu(z)) * w) W_out      a statistic a group

The scan is `ops/ssd.py: ssd`, the convolution `ops/short_conv.py:
silu_short_conv` with its bias, the gated norm `decoder.GatedRMSNorm`.
The mixer's five named scopes (`<scope>/proj`, `/conv`, `/core`,
`/gated_norm`, `/out`) take their prefix from the model, and each layer
sows `ssm_state_kept_ratio`, declared here once for every model.
"""

from __future__ import annotations

import flax.linen as nn
import jax
import jax.numpy as jnp

from elasticdl_tpu.common import metrics as metrics_lib
from elasticdl_tpu.layers import step_metrics
from elasticdl_tpu.layers.step_metrics import sow_step_metric
from elasticdl_tpu.ops import ssd as ssd_ops
from elasticdl_tpu.ops.short_conv import silu_short_conv
from model_zoo.common.decoder import (
    MIXER_IN,
    MIXER_OUT,
    GatedRMSNorm,
    a_log_init,
    dense,
    dt_bias_init,
    tap_init,
)


def conv_bias_init(taps: int):
    """Uniform in +-1 / sqrt(K), the taps' own bound (their fan-in)."""
    def init(key, shape, dtype=jnp.float32):
        bound = taps ** -0.5
        return jax.random.uniform(key, shape, dtype, -bound, bound)

    return init


# What a Mamba-2 layer sows into STEP_METRICS, read once a task with the
# loss: leaf name -> gauge by layer.
step_metrics.declare(
    "ssm_state_kept_ratio",
    metrics_lib.default_registry().gauge(
        "worker_ssm_state_kept_ratio",
        "mean over heads and chunks of exp(sum of log a over a chunk of "
        "256 tokens) of a state-space layer, last step of the task: the "
        "share of a state that outlives a chunk (0: the carried path "
        "does no work at these weights; 1: nothing is ever forgotten)",
        labelnames=("layer",),
    ),
)


class Mamba2(nn.Module):
    """`heads` heads of `head_dim` channels over `state` state columns,
    B and C shared by the heads of each of `groups` groups (and the
    gated norm's statistic by a group's channels), x, B and C through one
    `taps`-tap causal depthwise conv with a bias; `trace_scope` prefixes the
    mixer's named scopes."""

    hidden: int
    heads: int
    head_dim: int
    state: int
    groups: int
    taps: int
    eps: float
    dtype: jnp.dtype = jnp.float32
    trace_scope: str = "ssm"

    @nn.compact
    def __call__(self, x):
        batch, length, _ = x.shape
        heads, dim = self.heads, self.head_dim
        inner, shared = heads * dim, self.groups * self.state
        with jax.named_scope(f"{self.trace_scope}/proj"):
            z, xbc, dt = jnp.split(
                dense(
                    2 * inner + 2 * shared + heads, "in_proj", self.dtype,
                    MIXER_IN,
                )(x), [inner, 2 * inner + 2 * shared], axis=-1,
            )
        with jax.named_scope(f"{self.trace_scope}/conv"):
            weight = self.param(
                "conv_kernel", tap_init, (self.taps, inner + 2 * shared)
            )
            bias = self.param(
                "conv_bias", conv_bias_init(self.taps), (inner + 2 * shared,)
            )
            xs, b, c = jnp.split(
                silu_short_conv(xbc, weight, bias), [inner, inner + shared],
                axis=-1,
            )
        with jax.named_scope(f"{self.trace_scope}/core"):
            a_log = self.param("A_log", a_log_init, (heads,))
            dt_bias = self.param("dt_bias", dt_bias_init, (heads,))
            skip = self.param("D", nn.initializers.ones, (heads,))
            dt = jax.nn.softplus(dt.astype(jnp.float32) + dt_bias)
            rate = -jnp.exp(a_log)
            # what share of a state outlives a chunk: whether the carried
            # path does work at the weights the run has
            chunk = ssd_ops.CHUNK if length % ssd_ops.CHUNK == 0 else length
            sow_step_metric(self, "ssm_state_kept_ratio", jnp.exp(
                (rate * dt).reshape(batch, -1, chunk, heads).sum(axis=2)
            ).mean())
            by_group = (batch, length, self.groups, self.state)
            y = ssd_ops.ssd(
                xs.reshape(batch, length, heads, dim), dt, rate,
                b.reshape(by_group), c.reshape(by_group), skip,
            ).reshape(batch, length, inner)
        with jax.named_scope(f"{self.trace_scope}/gated_norm"):
            # the gate FIRST, then the norm, a statistic a group
            y = GatedRMSNorm(
                self.eps, self.dtype, self.groups, name="norm"
            )(y, z)
        with jax.named_scope(f"{self.trace_scope}/out"):
            return dense(self.hidden, "out_proj", self.dtype, MIXER_OUT)(y)
