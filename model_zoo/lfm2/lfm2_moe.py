"""LFM2-24B-A2B (`model_type: lfm2_moe`) as a causal language model on the
train path: a conv/attention hybrid whose layers mix tokens either by a
gated short convolution or by grouped-query attention at head width 64
with an RMSNorm on every query and key head, over leading dense SwiGLU
layers and then layers of 64 sigmoid-routed experts (top-4, weights
renormalised with a 1e-6 in the denominator, no shared expert); the head
is the token embedding read again.

    h = x + Op_l(RMSNorm(x));  y = h + FFN_l(RMSNorm(h))
    conv:       [B, C, u] = x W_in;  Op = (C * conv_K(B * u)) W_out
    attention:  q, k = rope(RMSNorm_64(x Wq)), rope(RMSNorm_64(x Wk))
                Op = concat_h(softmax_causal(q_h k^T / 8) v) Wo
    L = CE(RMSNorm(h_L) E^T, x_{t+1})

The layer equations are written out in `benchmarks/reference/
lfm2_moe.py`, the plain float32 reference this model is held to leaf by
leaf (tests/decoder_cases.py).  What it shares with the zoo's other decoders
(norms, rotary, SwiGLU, the routed block, the blocked cross-entropy) is
`model_zoo/common/decoder.py`; the convolution's pass is
`ops/short_conv.py: gated_short_conv`, attention's core
`ops/flash_attention.py: causal_attention` over grouped K/V.

What a layer is comes from static per-layer tuples (`Lfm2Config.layers`):
its kind (`conv` | `full_attention`) and whether its feed-forward is
dense or routed, read from the PUBLISHED `layer_types` and
`num_dense_layers` at the published indices in `layers`.  With `remat`
every block is rebuilt in the backward but for an attention layer's core
output and log-sum-exp, which stay from the forward
(`decoder.remat_block`: the forward kernel runs once a step); a conv
block saves nothing.

Record format: seq_len int32 token ids | 1 label byte (ignored), the
fixed-width record `model_zoo/bert` reads.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from elasticdl_tpu.common import metrics as metrics_lib
from elasticdl_tpu.layers.embedding import DistributedEmbedding
from elasticdl_tpu.layers import step_metrics
from elasticdl_tpu.layers.step_metrics import sow_step_metric
from elasticdl_tpu.ops.flash_attention import causal_attention
from elasticdl_tpu.ops.short_conv import gated_short_conv
from model_zoo.bert.bert_finetune import feed, feed_bulk  # noqa: F401
from model_zoo.common.decoder import (  # noqa: F401
    MIXER_IN,
    MIXER_OUT,
    MoEFFN,
    RMSNorm,
    SwiGLU,
    dense,
    eval_metrics_fn,
    loss,
    optimizer,
    param_sharding,
    remat_blocks,
    routed_walks,
    rotary,
    shifted_nll,
    sow_rope_one_pass,
    tap_init,
)

CONV, FULL = "conv", "full_attention"
# the published pattern: attention at every fourth layer from the third
PUBLISHED_LAYER_TYPES = tuple(
    FULL if i % 4 == 2 else CONV for i in range(40)
)


def rms(x):
    return jnp.sqrt(jnp.mean(jnp.square(x.astype(jnp.float32))))


# What a gated short convolution sows into STEP_METRICS: the RMS of the
# operator's output over its input's.
step_metrics.declare(
    "out_rms_ratio",
    metrics_lib.default_registry().gauge(
        "worker_short_conv_out_rms_ratio",
        "RMS of the conv operator's output over the RMS of its (normed) "
        "input, last step of the task (gates that close silence the layer)",
        labelnames=("layer",),
    ),
)


class GatedShortConv(nn.Module):
    """(C * causal_depthwise_conv_K(B * u)) W_out, [B, C, u] = x W_in."""

    hidden: int
    taps: int
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        with jax.named_scope("lfm2/short_conv"):
            bcu = dense(3 * self.hidden, "in_proj", self.dtype, MIXER_IN)(x)
            weight = self.param(
                "conv_kernel", tap_init, (self.taps, self.hidden)
            )
            out = dense(self.hidden, "out_proj", self.dtype, MIXER_OUT)(
                gated_short_conv(bcu, weight)
            )
            # gates that close silence the layer
            sow_step_metric(
                self, "out_rms_ratio", rms(out) / jnp.maximum(rms(x), 1e-30)
            )
        return out


class NormedGroupedAttention(nn.Module):
    """`heads` query heads over `kv_heads` key/value heads, causal, an
    RMSNorm over each query and key head's columns before rotary."""

    hidden: int
    heads: int
    kv_heads: int
    head_dim: int
    theta: float
    eps: float
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        batch, length, _ = x.shape
        heads, kv_heads, dim = self.heads, self.kv_heads, self.head_dim
        with jax.named_scope("lfm2/attn"):
            q = dense(heads * dim, "q", self.dtype, MIXER_IN)(x).reshape(
                batch, length, heads, dim
            )
            k = dense(kv_heads * dim, "k", self.dtype, MIXER_IN)(x).reshape(
                batch, length, kv_heads, dim
            )
            v = dense(kv_heads * dim, "v", self.dtype, MIXER_IN)(x).reshape(
                batch, length, kv_heads, dim
            )
            q = RMSNorm(self.eps, self.dtype, name="q_norm")(q)
            k = RMSNorm(self.eps, self.dtype, name="k_norm")(k)
            sow_rope_one_pass(self, dim, q.shape, k.shape)
            out = causal_attention(
                rotary(q, self.theta), rotary(k, self.theta), v,
                scale=dim ** -0.5,
            )
            return dense(self.hidden, "o", self.dtype, MIXER_OUT)(
                out.reshape(batch, length, heads * dim)
            )


@dataclasses.dataclass(frozen=True)
class Lfm2Config:
    """Every size of the model (`custom_model` documents them).
    `layers` is one (kind, routed?) a layer."""

    hidden: int
    layers: Tuple[Tuple[str, bool], ...]
    heads: int
    kv_heads: int
    conv_kernel: int
    rope_theta: float
    dense_width: int
    expert_width: int
    num_experts: int
    top_k: int
    held_experts: Optional[Tuple[int, int]]
    routed_scaling: float
    renorm_eps: float
    vocab_size: int
    eps: float
    dtype: Any
    remat: bool


class Block(nn.Module):
    """One pre-norm decoder block; `layer` says which."""

    config: Lfm2Config
    layer: Tuple[str, bool]

    @nn.compact
    def __call__(self, x):
        c = self.config
        kind, routed = self.layer
        # norms and residual sums are `lfm2/norm`: with the scopes of the
        # operator and the feed-forward they tile the block
        # (profiler.DEVICE_SCOPES)
        with jax.named_scope("lfm2/norm"):
            y = RMSNorm(c.eps, c.dtype, name="op_norm")(x)
        if kind == CONV:
            y = GatedShortConv(c.hidden, c.conv_kernel, c.dtype,
                               name="conv")(y)
        else:
            y = NormedGroupedAttention(
                c.hidden, c.heads, c.kv_heads, c.hidden // c.heads,
                c.rope_theta, c.eps, c.dtype, name="attn",
            )(y)
        with jax.named_scope("lfm2/norm"):
            x = x + y
            y = RMSNorm(c.eps, c.dtype, name="ffn_norm")(x)
        if routed:
            y = MoEFFN(
                c.hidden, c.num_experts, c.top_k, c.expert_width, 0,
                c.held_experts, c.routed_scaling, 0.0, c.dtype, "lfm2/moe",
                c.renorm_eps, name="moe",
            )(y)
        else:
            with jax.named_scope("lfm2/dense_ffn"):
                y = SwiGLU(c.hidden, c.dense_width, c.dtype, name="mlp")(y)
        with jax.named_scope("lfm2/norm"):
            return x + y


class Lfm2Moe(nn.Module):
    config: Lfm2Config

    @nn.compact
    def __call__(self, features, room=None):
        c = self.config
        ids = features["input_ids"].astype(jnp.int32)        # (B, L)
        embedding = DistributedEmbedding(
            c.vocab_size, c.hidden, hash_input=False, name="token_embedding"
        )
        with jax.named_scope("lfm2/embed"):
            x = embedding(ids).astype(c.dtype)
        classes = remat_blocks(
            Block, c, c.layers, x, room, c.vocab_size, routed_walks(
                x, [routed for _, routed in c.layers], c.top_k,
                c.expert_width,
            ),
        ) if c.remat else [Block] * len(c.layers)
        for i, (layer, block_cls) in enumerate(zip(c.layers, classes)):
            x = block_cls(c, layer, name=f"layer_{i}")(x)
        # the tied head: the table read again, one leaf with two gradients
        table = embedding.variables["params"]["embedding"]
        with jax.named_scope("lfm2/norm"):
            x = RMSNorm(c.eps, c.dtype, name="final_norm")(x)
        return shifted_nll(x, table.T, ids, 1, c.dtype, "lfm2/head_ce")


def custom_model(
    hidden: int = 2048, layer_types=PUBLISHED_LAYER_TYPES,
    num_dense_layers: int = 2, layers=None, heads: int = 32,
    kv_heads: int = 8, conv_kernel: int = 3, rope_theta: float = 1e6,
    dense_width: int = 11776, expert_width: int = 1536,
    num_experts: int = 64, top_k: int = 4, held_experts=None,
    routed_scaling: float = 1.0, renorm_eps: float = 1e-6,
    vocab_size: int = 65536, eps: float = 1e-5, bf16: bool = False,
    remat: bool = False,
):
    """`layer_types` and `num_dense_layers` are the published ones;
    `layers` lists the PUBLISHED indices that are built, in order (None
    builds every entry of `layer_types`): layer i is dense where i <
    `num_dense_layers`, routed after.  A head is `hidden / heads` wide.
    `held_experts` is (first, count) of the routed experts whose weights
    live in this process; None holds all `num_experts`."""
    built = tuple(range(len(layer_types))) if layers is None else tuple(
        int(i) for i in layers
    )
    if not built or min(built) < 0 or max(built) >= len(layer_types):
        raise ValueError(
            f"layers {built} of {len(layer_types)} published entries"
        )
    if set(layer_types) - {CONV, FULL}:
        raise ValueError(f"layer_types {sorted(set(layer_types))}")
    if hidden % heads or heads % kv_heads:
        raise ValueError("heads divide the width, K/V heads the heads")
    return Lfm2Moe(Lfm2Config(
        hidden=hidden,
        layers=tuple(
            (layer_types[i], i >= num_dense_layers) for i in built
        ),
        heads=heads, kv_heads=kv_heads, conv_kernel=int(conv_kernel),
        rope_theta=float(rope_theta), dense_width=dense_width,
        expert_width=expert_width, num_experts=num_experts, top_k=top_k,
        held_experts=None if held_experts is None else tuple(held_experts),
        routed_scaling=float(routed_scaling), renorm_eps=float(renorm_eps),
        vocab_size=vocab_size, eps=eps,
        dtype=jnp.bfloat16 if bf16 else jnp.float32, remat=remat,
    ))
