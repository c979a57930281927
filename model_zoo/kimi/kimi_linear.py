"""Kimi-Linear-48B-A3B (`model_type: kimi_linear`, arXiv:2510.26692) as a
causal language model on the train path: three layers in four mix tokens
by Kimi Delta Attention (KDA: the delta rule with a decay for every
channel of the key, a state carried along the whole sequence), the fourth
by multi-head latent attention that knows no positions (`mla_use_nope`),
over one leading dense SwiGLU layer and then layers of 256 sigmoid-routed
experts (top-8, weights renormalised and scaled) beside one shared
expert; the head is its own matrix.

    h = x + Mix_l(RMSNorm(x));  y = h + FFN_l(RMSNorm(h))
    KDA:  q, k, v = silu(conv_4(x Wqkv));  q, k L2-normed a head
          g = -exp(A_log) softplus(x Wfa Wfb + dt_bias);  b = sigmoid(x Wb)
          S_t = (I - b_t k_t k_t^T) Diag(exp g_t) S_{t-1} + b_t k_t v_t^T
          Mix = (RMSNorm_head(S_t^T q_t) * sigmoid(x Wga Wgb)) Wo
    MLA:  `model_zoo/common/mla.py`, no low-rank query, no rotation
    L = CE(RMSNorm(h_L) W_head, x_{t+1})

The layer equations are written out in `benchmarks/reference/
kimi_linear.py`, the plain float32 reference this model is held to leaf
by leaf (tests/decoder_cases.py), its KDA the token-by-token
recurrence.  What it shares with the zoo's other decoders (norms, SwiGLU,
the routed block, the blocked cross-entropy, MLA) is `model_zoo/common/`;
the scan is `ops/kda.py: kda`, the convolution `ops/short_conv.py:
silu_short_conv` over the fused q|k|v projection.

What a layer is comes from the PUBLISHED 1-indexed lists
(`kda_layers`, `full_attn_layers`) and `first_k_dense_replace`, read at
the published 0-based indices in `layers`.  With `remat` every block is
rebuilt in the backward but for what `decoder.remat_block` saves by name:
an MLA layer's core output and log-sum-exp; a KDA layer saves nothing
(`ops/kda.py: SAVED_NAMES`).

Record format: seq_len int32 token ids | 1 label byte (ignored), the
fixed-width record `model_zoo/bert` reads.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from elasticdl_tpu.common import metrics as metrics_lib
from elasticdl_tpu.layers.embedding import DistributedEmbedding
from elasticdl_tpu.layers import step_metrics
from elasticdl_tpu.layers.step_metrics import sow_step_metric
from elasticdl_tpu.ops.kda import kda
from elasticdl_tpu.ops.short_conv import silu_short_conv
from model_zoo.bert.bert_finetune import feed, feed_bulk  # noqa: F401
from model_zoo.common.decoder import (  # noqa: F401
    MIXER_IN,
    MIXER_OUT,
    MoEFFN,
    RMSNorm,
    SwiGLU,
    a_log_init,
    dense,
    dt_bias_init,
    eval_metrics_fn,
    grouped_rms_norm,
    loss,
    optimizer,
    param_sharding,
    remat_blocks,
    routed_walks,
    shifted_nll,
    tap_init,
)
from model_zoo.common.mla import MLA

KDA_KIND, MLA_KIND = "kda", "mla"
# the published pattern, 1-indexed: full attention at every fourth layer
# and at the last
PUBLISHED_FULL_ATTN_LAYERS = (4, 8, 12, 16, 20, 24, 27)
PUBLISHED_KDA_LAYERS = tuple(
    i for i in range(1, 28) if i not in PUBLISHED_FULL_ATTN_LAYERS
)
L2_EPS = 1e-6


# What a KDA layer sows into STEP_METRICS, read once a task with the
# loss: leaf name -> gauge by layer.
step_metrics.declare(
    "kda_decay_mean_ratio",
    metrics_lib.default_registry().gauge(
        "worker_kda_decay_mean_ratio",
        "mean of a KDA layer's per-channel decay exp(g) over tokens, heads "
        "and channels, last step of the task (0 forgets everything, 1 "
        "nothing: a decay that collapses is silent in the loss for long)",
        labelnames=("layer",),
    ),
)
step_metrics.declare(
    "kda_beta_mean_ratio",
    metrics_lib.default_registry().gauge(
        "worker_kda_beta_mean_ratio",
        "mean of a KDA layer's write strength sigmoid(x Wb) over tokens "
        "and heads, last step of the task",
        labelnames=("layer",),
    ),
)


class HeadRMSNorm(nn.Module):
    """RMSNorm a head over (..., heads x dim): one statistic for each of
    `heads` runs of the channels, one learned scale of dim that the heads
    share (the leaf `RMSNorm` over the (..., heads, dim) view owns), taken
    where the channels lie (`decoder.grouped_rms_norm` says what the view
    costs on the chip).  `decoder.GatedRMSNorm` is not it: that one takes
    its gate in float32 before it rounds, this model rounds the norm and
    gates in `dtype`, as its reference does."""

    eps: float
    dtype: jnp.dtype
    heads: int

    @nn.compact
    def __call__(self, x):
        scale = self.param(
            "scale", nn.initializers.ones, (x.shape[-1] // self.heads,)
        )
        return grouped_rms_norm(
            x, jnp.tile(scale, self.heads), self.eps, self.heads
        ).astype(self.dtype)


class KDA(nn.Module):
    """Kimi Delta Attention: `heads` heads of `head_dim` key and value
    columns, q, k and v through a `taps`-tap causal depthwise conv.  Every
    whole array outside `kda(...)` stays (B, L, heads x dim), the channels
    along the lanes; (B, L, heads, dim) is the kernels' operands only."""

    hidden: int
    heads: int
    head_dim: int
    taps: int
    eps: float
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        batch, length, _ = x.shape
        heads, dim = self.heads, self.head_dim
        width = heads * dim
        by_head = (batch, length, heads, dim)
        with jax.named_scope("kimi/kda/proj"):
            qkv = dense(3 * width, "qkv", self.dtype, MIXER_IN)(x)
        with jax.named_scope("kimi/kda/conv"):
            weight = self.param("conv_kernel", tap_init, (self.taps, 3 * width))
            q, k, v = (
                t.reshape(by_head)
                for t in jnp.split(silu_short_conv(qkv, weight), 3, axis=-1)
            )
        with jax.named_scope("kimi/kda/gate"):
            a_log = self.param("A_log", a_log_init, (heads,))
            dt_bias = self.param("dt_bias", dt_bias_init, (width,))
            f = dense(width, "f_b", self.dtype)(
                dense(dim, "f_a", self.dtype)(x)
            )
            # one A a head, spread over its channels: (B, L, heads, dim) is
            # the kernels' operand, not the arithmetic's
            g = (-jnp.repeat(jnp.exp(a_log), dim) * jax.nn.softplus(
                f.astype(jnp.float32) + dt_bias
            )).reshape(by_head)
            beta = jax.nn.sigmoid(
                dense(heads, "b", self.dtype)(x).astype(jnp.float32)
            )
            # a decay that collapses (0 forgets everything, 1 nothing) is
            # silent in the loss for a long while
            sow_step_metric(self, "kda_decay_mean_ratio", jnp.exp(g).mean())
            sow_step_metric(self, "kda_beta_mean_ratio", beta.mean())
        with jax.named_scope("kimi/kda/core"):
            # q and k are L2-normed a head, q then times d_k^-1/2, in the op
            out = kda(q, k, v, g, beta, qk_norm=(L2_EPS, dim ** -0.5))
        with jax.named_scope("kimi/kda/out"):
            gate = dense(width, "g_b", self.dtype)(
                dense(dim, "g_a", self.dtype)(x)
            )
            # the norm rounds to `dtype` BEFORE the gate, taken in `dtype`
            out = HeadRMSNorm(self.eps, self.dtype, heads, name="o_norm")(
                out.reshape(batch, length, width)
            )
            return dense(self.hidden, "o", self.dtype, MIXER_OUT)(
                out * jax.nn.sigmoid(gate)
            )


@dataclasses.dataclass(frozen=True)
class KimiConfig:
    """Every size of the model (`custom_model` documents them).
    `layers` is one (kind, routed?) a layer."""

    hidden: int
    layers: Tuple[Tuple[str, bool], ...]
    heads: int
    kda_heads: int
    kda_head_dim: int
    conv_kernel: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    dense_width: int
    expert_width: int
    num_experts: int
    top_k: int
    shared_experts: int
    held_experts: Optional[Tuple[int, int]]
    routed_scaling: float
    vocab_size: int
    eps: float
    dtype: Any
    remat: bool


class Block(nn.Module):
    """One pre-norm decoder block; `layer` says which."""

    config: KimiConfig
    layer: Tuple[str, bool]

    @nn.compact
    def __call__(self, x):
        c = self.config
        kind, routed = self.layer
        # norms and residual sums are `kimi/norm`: with the scopes of the
        # mixer and the feed-forward they tile the block
        # (profiler.DEVICE_SCOPES)
        with jax.named_scope("kimi/norm"):
            y = RMSNorm(c.eps, c.dtype, name="mix_norm")(x)
        if kind == KDA_KIND:
            y = KDA(
                c.hidden, c.kda_heads, c.kda_head_dim, c.conv_kernel, c.eps,
                c.dtype, name="kda",
            )(y)
        else:
            y = MLA(
                c.hidden, c.heads, None, c.kv_lora_rank, c.qk_nope_head_dim,
                c.qk_rope_head_dim, c.v_head_dim, 0.0, c.eps, c.dtype,
                rotate=False, trace_scope="kimi/mla", name="mla",
            )(y)
        with jax.named_scope("kimi/norm"):
            x = x + y
            y = RMSNorm(c.eps, c.dtype, name="ffn_norm")(x)
        if routed:
            y = MoEFFN(
                c.hidden, c.num_experts, c.top_k, c.expert_width,
                c.shared_experts, c.held_experts, c.routed_scaling, 0.0,
                c.dtype, "kimi/moe", name="moe",
            )(y)
        else:
            with jax.named_scope("kimi/dense_ffn"):
                y = SwiGLU(c.hidden, c.dense_width, c.dtype, name="mlp")(y)
        with jax.named_scope("kimi/norm"):
            return x + y


class KimiLinear(nn.Module):
    config: KimiConfig

    @nn.compact
    def __call__(self, features, room=None):
        c = self.config
        ids = features["input_ids"].astype(jnp.int32)        # (B, L)
        embedding = DistributedEmbedding(
            c.vocab_size, c.hidden, hash_input=False, name="token_embedding"
        )
        with jax.named_scope("kimi/embed"):
            x = embedding(ids).astype(c.dtype)
        classes = remat_blocks(
            Block, c, c.layers, x, room, c.vocab_size, routed_walks(
                x, [routed for _, routed in c.layers], c.top_k,
                c.expert_width,
            ),
        ) if c.remat else [Block] * len(c.layers)
        for i, (layer, block_cls) in enumerate(zip(c.layers, classes)):
            x = block_cls(c, layer, name=f"layer_{i}")(x)
        head = self.param(
            "lm_head_kernel", nn.initializers.lecun_normal(),
            (c.hidden, c.vocab_size),
        )
        with jax.named_scope("kimi/norm"):
            x = RMSNorm(c.eps, c.dtype, name="final_norm")(x)
        return shifted_nll(x, head, ids, 1, c.dtype, "kimi/head_ce")


def custom_model(
    hidden: int = 2304, num_layers: int = 27,
    kda_layers=PUBLISHED_KDA_LAYERS,
    full_attn_layers=PUBLISHED_FULL_ATTN_LAYERS,
    first_k_dense_replace: int = 1, layers=None, heads: int = 32,
    kda_heads: int = 32, kda_head_dim: int = 128, conv_kernel: int = 4,
    kv_lora_rank: int = 512, qk_nope_head_dim: int = 128,
    qk_rope_head_dim: int = 64, v_head_dim: int = 128,
    dense_width: int = 9216, expert_width: int = 1024,
    num_experts: int = 256, top_k: int = 8, shared_experts: int = 1,
    held_experts=None, routed_scaling: float = 2.446,
    vocab_size: int = 163840, eps: float = 1e-5, bf16: bool = False,
    remat: bool = False,
):
    """`kda_layers` and `full_attn_layers` are the published 1-INDEXED
    lists, whole; `layers` lists the published 0-BASED indices that are
    built, in order (None builds all `num_layers`): layer i is KDA where
    i + 1 is in `kda_layers`, MLA where it is in `full_attn_layers`, dense
    where i < `first_k_dense_replace`, routed after.  `held_experts` is
    (first, count) of the routed experts whose weights live in this
    process; None holds all `num_experts`."""
    kda_set, full_set = set(kda_layers), set(full_attn_layers)
    if kda_set & full_set or kda_set | full_set != set(
        range(1, num_layers + 1)
    ):
        raise ValueError(
            f"kda_layers and full_attn_layers do not split the layers "
            f"1..{num_layers}"
        )
    built = tuple(range(num_layers)) if layers is None else tuple(
        int(i) for i in layers
    )
    if not built or min(built) < 0 or max(built) >= num_layers:
        raise ValueError(f"layers {built} of {num_layers} published")
    return KimiLinear(KimiConfig(
        hidden=hidden,
        layers=tuple(
            (KDA_KIND if i + 1 in kda_set else MLA_KIND,
             i >= first_k_dense_replace)
            for i in built
        ),
        heads=heads, kda_heads=kda_heads, kda_head_dim=kda_head_dim,
        conv_kernel=int(conv_kernel), kv_lora_rank=kv_lora_rank,
        qk_nope_head_dim=qk_nope_head_dim,
        qk_rope_head_dim=qk_rope_head_dim, v_head_dim=v_head_dim,
        dense_width=dense_width, expert_width=expert_width,
        num_experts=num_experts, top_k=top_k, shared_experts=shared_experts,
        held_experts=None if held_experts is None else tuple(held_experts),
        routed_scaling=float(routed_scaling), vocab_size=vocab_size, eps=eps,
        dtype=jnp.bfloat16 if bf16 else jnp.float32, remat=remat,
    ))
