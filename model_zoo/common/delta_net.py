"""The gated delta rule's layer, shared by the zoo's decoders whose linear
mixer has ONE decay a head (`model_zoo/qwen3_next/qwen3_next.py`,
`model_zoo/olmo_hybrid/olmo_hybrid.py`), beside `model_zoo/common/mamba.py`:

    q, k (H_k heads of dk), v, z (H_v heads of dv), a, b (H_v) = x W...
    [q | k | v] = silu(conv_K([q | k | v]))   causal, depthwise, no bias
    q, k L2-normed a head;  q times dk^-1/2            (inside the op)
    g = -exp(A_log) softplus(a + dt_bias)     beta = beta_scale sigmoid(b)
    S_t = exp(g_t) S_{t-1};  S_t += k_t (beta_t (v_t - S_t^T k_t))^T
    o_t = S_t^T q_t                           S in R^{dk x dv} a value head
    Mix = (rms_norm_head(o) w * silu(z)) Wo   w one scale of dv

`fused` lays the projections out as Qwen3-Next's checkpoint does (ONE
kernel q | k | v | z and one b | a); without it each of the six is a
kernel of its own, which a head's share slices by columns.  `beta_scale`
2 is a configuration's `allow_neg_eigval`: the transition exp(g)(I - beta
k k^T) then has an eigenvalue in (-1, 1) along k.  The scan is `ops/gdn.py:
gdn`, the convolution `ops/short_conv.py: silu_short_conv`.

HEADS as a chip's share: with `held_heads` = (first, count) the layer
builds only those value heads' columns of every projection, of the conv,
of A_log and dt_bias, and those rows of Wo (whole key heads with them),
and returns ITS part of the mixer's output; every step above is head-wise,
so the shares' parts add up to the uncut layer exactly.  Where an
`axis_name` is given (head-parallel chips under one `shard_map` / `vmap`)
the parts are summed over it; where none is, nothing is emitted.
"""

from __future__ import annotations

from typing import Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from elasticdl_tpu.common import metrics as metrics_lib
from elasticdl_tpu.layers import step_metrics
from elasticdl_tpu.layers.step_metrics import sow_step_metric
from elasticdl_tpu.ops.gdn import gdn, padded_lanes_ratio
from elasticdl_tpu.ops.short_conv import silu_short_conv
from model_zoo.common.decoder import (
    MIXER_IN,
    MIXER_OUT,
    GatedRMSNorm,
    a_log_init,
    dense,
    dt_bias_init,
    held_of,
    summed_over,
    tap_init,
)

L2_EPS = 1e-6


# What a delta-rule layer sows into STEP_METRICS, read once a task with
# the loss: leaf name -> gauge by layer.
step_metrics.declare(
    "gdn_decay_mean_ratio",
    metrics_lib.default_registry().gauge(
        "worker_gdn_decay_mean_ratio",
        "mean of a gated-delta-rule layer's per-head decay exp(g) over "
        "tokens and value heads, last step of the task (0 forgets "
        "everything, 1 nothing: a decay that collapses is silent in the "
        "loss for long)",
        labelnames=("layer",),
    ),
)
step_metrics.declare(
    "gdn_beta_mean_ratio",
    metrics_lib.default_registry().gauge(
        "worker_gdn_beta_mean_ratio",
        "mean of a gated-delta-rule layer's write strength beta (sigmoid(b), "
        "twice it where the layer allows negative eigenvalues) over tokens "
        "and value heads, last step of the task",
        labelnames=("layer",),
    ),
)
step_metrics.declare(
    "gdn_beta_over_one_ratio",
    metrics_lib.default_registry().gauge(
        "worker_gdn_beta_over_one_ratio",
        "share of a gated-delta-rule layer's (token, value head) pairs "
        "whose write strength is over 1, last step of the task: whether "
        "the negative-eigenvalue regime of a layer that allows it (beta = "
        "2 sigmoid(b)) is exercised at all",
        labelnames=("layer",),
    ),
)
step_metrics.declare(
    "gdn_padded_lanes_ratio",
    metrics_lib.default_registry().gauge(
        "worker_gdn_padded_lanes_ratio",
        "share of the q, k and v columns a gated-delta-rule layer's scan "
        "kernels process that is padding to whole lane tiles (0 for heads "
        "of whole tiles, which report nothing; 0.25 at heads of 96 | 192)",
        labelnames=("layer",),
    ),
)


class GatedDeltaNet(nn.Module):
    """The gated delta rule: `value_heads` value heads of `value_dim`
    columns over `key_heads` key heads of `key_dim`, q, k and v through a
    `taps`-tap causal depthwise conv (module docstring)."""

    hidden: int
    key_heads: int
    value_heads: int
    key_dim: int
    value_dim: int
    taps: int
    eps: float
    dtype: jnp.dtype = jnp.float32
    trace_scope: str = "gdn"
    fused: bool = True
    beta_scale: float = 1.0
    held_heads: Optional[Tuple[int, int]] = None
    axis_name: Optional[str] = None

    def wide_projections(self, x, keys: int, values: int):
        """(q | k | v, z) of x."""
        if self.fused:
            return jnp.split(
                dense(2 * keys + 2 * values, "qkvz", self.dtype, MIXER_IN)(x),
                [2 * keys + values], axis=-1,
            )
        q, k, v, z = (
            dense(width, name, self.dtype, MIXER_IN)(x)
            for name, width in (
                ("q", keys), ("k", keys), ("v", values), ("z", values),
            )
        )
        return jnp.concatenate([q, k, v], axis=-1), z

    def head_projections(self, x, heads: int):
        """(b, a) of x, one number a token and value head each, float32."""
        if self.fused:
            return jnp.split(
                dense(2 * heads, "ba", self.dtype)(x).astype(jnp.float32), 2,
                axis=-1,
            )
        return tuple(
            dense(heads, name, self.dtype)(x).astype(jnp.float32)
            for name in ("b", "a")
        )

    @nn.compact
    def __call__(self, x):
        batch, length, _ = x.shape
        heads = held_of(self.value_heads, self.held_heads)
        ratio = self.value_heads // self.key_heads
        if heads % ratio:
            raise ValueError("a share holds whole key heads")
        dk, dv = self.key_dim, self.value_dim
        keys, values = heads // ratio * dk, heads * dv
        with jax.named_scope(f"{self.trace_scope}/proj"):
            qkv, z = self.wide_projections(x, keys, values)
        with jax.named_scope(f"{self.trace_scope}/conv"):
            weight = self.param(
                "conv_kernel", tap_init, (self.taps, 2 * keys + values)
            )
            q, k, v = (
                t.reshape(batch, length, -1, dim) for t, dim in zip(jnp.split(
                    silu_short_conv(qkv, weight), [keys, 2 * keys], axis=-1
                ), (dk, dk, dv))
            )
        # `decay`, not `gate`: `attn_proj_ms_per_step` takes every
        # model's `*/gate`
        with jax.named_scope(f"{self.trace_scope}/decay"):
            a_log = self.param("A_log", a_log_init, (heads,))
            dt_bias = self.param("dt_bias", dt_bias_init, (heads,))
            b, a = self.head_projections(x, heads)
            g = -jnp.exp(a_log) * jax.nn.softplus(a + dt_bias)
            beta = jax.nn.sigmoid(b)
            if self.beta_scale != 1.0:
                # float32; nothing below may take beta <= 1.  (A layer that
                # cannot write over 1 has nothing to report here.)
                beta = self.beta_scale * beta
                sow_step_metric(
                    self, "gdn_beta_over_one_ratio", (beta > 1.0).mean()
                )
            # a decay that collapses (0 forgets everything, 1 nothing) is
            # silent in the loss for a long while
            sow_step_metric(self, "gdn_decay_mean_ratio", jnp.exp(g).mean())
            sow_step_metric(self, "gdn_beta_mean_ratio", beta.mean())
        with jax.named_scope(f"{self.trace_scope}/core"):
            padded = padded_lanes_ratio(q.shape, k.shape, v.shape)
            if padded:       # (nor has a scan that pads nothing)
                sow_step_metric(self, "gdn_padded_lanes_ratio", padded)
            # q and k are L2-normed a head, q then times dk^-1/2, in the op
            out = gdn(q, k, v, g, beta, qk_norm=(L2_EPS, dk ** -0.5))
        with jax.named_scope(f"{self.trace_scope}/out"):
            # the norm FIRST, then the gate: a statistic a value head, one
            # scale of `value_dim` that the heads share
            out = GatedRMSNorm(
                self.eps, self.dtype, heads, gate_first=False,
                shared_scale=True, name="o_norm",
            )(out.reshape(batch, length, values), z)
            return summed_over(
                self.axis_name,
                dense(self.hidden, "o", self.dtype, MIXER_OUT)(out),
            )
