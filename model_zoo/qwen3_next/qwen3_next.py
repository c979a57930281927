"""Qwen3-Next-80B-A3B-Instruct (`model_type: qwen3_next`) as a causal
language model on the train path: three layers in four mix tokens by the
gated delta rule (ONE decay a head, a state carried along the whole
sequence, 32 value heads that share 16 key heads), the fourth by
grouped-query attention at heads of 256 with a query-wide sigmoid gate,
QK-norm and a quarter of the head rotated; every layer's feed-forward is
512 softmax-routed experts (top-10, weights renormalised over the picked)
beside one shared expert under a sigmoid gate; the head is its own
matrix.

    Norm(x) = x rsqrt(mean x^2 + eps) (1 + w)         zero-centred scale
    h = x + Mix_l(Norm(x));  y = h + MoE_l(Norm(h))
    GDN:  [q | k | v | z] = x Wqkvz;  [b | a] = x Wba
          [q | k | v] = silu(conv_4([q | k | v]));  q, k L2-normed a head
          g = -exp(A_log) softplus(a + dt_bias);  beta = sigmoid(b)
          S_t = exp(g_t) S_{t-1};  S_t += k_t (beta_t (v_t - S_t^T k_t))^T
          Mix = (rms_norm_head(S_t^T q_t) w silu(z)) Wo   value head h on
                                                          key head h // 2
    attn: [q | gate] = x Wq;  q, k = rotary_64(Norm_head(q, k))
          Mix = (softmax_causal(q k^T 256^-1/2) v * sigmoid(gate)) Wo
    MoE:  p = softmax(x Wr);  S = top_10(p);  w_i = p_i / sum_S p_j
          sum_{i in S, i held here} w_i SwiGLU_i(x)
              + sigmoid(x w_sg) SwiGLU_shared(x)
    L = CE(Norm(h_L) W_head, x_{t+1})

The layer equations are written out in `benchmarks/reference/
qwen3_next.py`, the plain float32 reference this model is held to leaf by
leaf (tests/decoder_cases.py), its delta rule the token-by-token
recurrence.  What it shares with the zoo's other decoders (norms, the
gated output norm, grouped attention, the routed block, the blocked
cross-entropy, the blocks' remat) is `model_zoo/common/decoder.py`; the
delta-rule layer is `model_zoo/common/delta_net.py: GatedDeltaNet` with
its projections fused (shared with `olmo_hybrid/olmo_hybrid.py`), its
scan `ops/gdn.py: gdn`, its convolution `ops/short_conv.py:
silu_short_conv` over the q | k | v columns of the fused projection.

Layer i (a PUBLISHED 0-based index, listed in `layers`) mixes by
attention where (i + 1) % `full_attention_interval` == 0.  With `remat`
every block is rebuilt in the backward but for what `decoder.remat_block`
saves by name: the attention layer's core output and log-sum-exp; a
delta-rule layer saves nothing (`ops/gdn.py: SAVED_NAMES`).

Record format: seq_len int32 token ids | 1 label byte (ignored), the
fixed-width record `model_zoo/bert` reads.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from elasticdl_tpu.layers.embedding import DistributedEmbedding
from elasticdl_tpu.layers.moe import SOFTMAX
from model_zoo.bert.bert_finetune import feed, feed_bulk  # noqa: F401
from model_zoo.common.decoder import (  # noqa: F401
    GroupedAttention,
    MoEFFN,
    RMSNorm,
    Rope,
    eval_metrics_fn,
    loss,
    optimizer,
    param_sharding,
    plain_rope,
    remat_blocks,
    routed_walks,
    shifted_nll,
)
from model_zoo.common.delta_net import GatedDeltaNet

@dataclasses.dataclass(frozen=True)
class Qwen3NextConfig:
    """Every size of the model (`custom_model` documents them).
    `layers` is True a delta-rule layer, False an attention layer."""

    hidden: int
    layers: Tuple[bool, ...]
    heads: int
    kv_heads: int
    head_dim: int
    rope: Rope
    gdn_key_heads: int
    gdn_value_heads: int
    gdn_head_dim: int
    conv_kernel: int
    expert_width: int
    shared_width: int
    num_experts: int
    top_k: int
    held_experts: Optional[Tuple[int, int]]
    vocab_size: int
    eps: float
    dtype: Any
    remat: bool


class Block(nn.Module):
    """One pre-norm decoder block; `is_gdn` says which mixer."""

    config: Qwen3NextConfig
    is_gdn: bool

    @nn.compact
    def __call__(self, x):
        c = self.config
        # norms and residual sums are `qwen3_next/norm`: with the scopes
        # of the mixer and the experts they tile the block
        # (profiler.DEVICE_SCOPES)
        with jax.named_scope("qwen3_next/norm"):
            y = RMSNorm(c.eps, c.dtype, True, name="mix_norm")(x)
        if self.is_gdn:
            y = GatedDeltaNet(
                c.hidden, c.gdn_key_heads, c.gdn_value_heads, c.gdn_head_dim,
                c.gdn_head_dim, c.conv_kernel, c.eps, c.dtype,
                "qwen3_next/gdn", name="gdn",
            )(y)
        else:
            y = GroupedAttention(
                c.hidden, c.heads, c.kv_heads, c.head_dim,
                c.head_dim ** -0.5, c.dtype, "qwen3_next/attn",
                qk_norm_eps=c.eps, rope=c.rope, query_gate=True, name="attn",
            )(y)
        with jax.named_scope("qwen3_next/norm"):
            x = x + y
            y = RMSNorm(c.eps, c.dtype, True, name="ffn_norm")(x)
        y = MoEFFN(
            c.hidden, c.num_experts, c.top_k, c.expert_width, 1,
            c.held_experts, 1.0, 0.0, c.dtype, "qwen3_next/moe",
            shared_width=c.shared_width, scores=SOFTMAX, shared_gate=True,
            name="moe",
        )(y)
        with jax.named_scope("qwen3_next/norm"):
            return x + y


class Qwen3Next(nn.Module):
    config: Qwen3NextConfig

    @nn.compact
    def __call__(self, features, room=None):
        c = self.config
        ids = features["input_ids"].astype(jnp.int32)        # (B, L)
        embedding = DistributedEmbedding(
            c.vocab_size, c.hidden, hash_input=False, name="token_embedding"
        )
        with jax.named_scope("qwen3_next/embed"):
            x = embedding(ids).astype(c.dtype)
        classes = remat_blocks(
            Block, c, c.layers, x, room, c.vocab_size, routed_walks(
                x, [True] * len(c.layers), c.top_k, c.expert_width,
            ),
        ) if c.remat else [Block] * len(c.layers)
        for i, (is_gdn, block_cls) in enumerate(zip(c.layers, classes)):
            x = block_cls(c, is_gdn, name=f"layer_{i}")(x)
        head = self.param(
            "lm_head_kernel", nn.initializers.lecun_normal(),
            (c.hidden, c.vocab_size),
        )
        with jax.named_scope("qwen3_next/norm"):
            x = RMSNorm(c.eps, c.dtype, True, name="final_norm")(x)
        return shifted_nll(x, head, ids, 1, c.dtype, "qwen3_next/head_ce")


def custom_model(
    hidden: int = 2048, num_layers: int = 48,
    full_attention_interval: int = 4, layers=None, heads: int = 16,
    kv_heads: int = 2, head_dim: int = 256,
    partial_rotary_factor: float = 0.25, rope_theta: float = 1e7,
    gdn_key_heads: int = 16, gdn_value_heads: int = 32,
    gdn_head_dim: int = 128, conv_kernel: int = 4, expert_width: int = 512,
    shared_width: int = 512, num_experts: int = 512, top_k: int = 10,
    held_experts=None, vocab_size: int = 151936, eps: float = 1e-6,
    bf16: bool = False, remat: bool = False,
):
    """`layers` lists the published 0-BASED indices that are built, in
    order (None builds all `num_layers`): layer i mixes by attention where
    (i + 1) % `full_attention_interval` == 0, by the gated delta rule
    elsewhere; every layer is routed.  A query head is `head_dim` wide
    whatever `hidden / heads` is, and its first `partial_rotary_factor`
    turns; a delta-rule head is `gdn_head_dim` wide, key and value alike.
    `held_experts` is (first, count) of the routed experts whose weights
    live in this process; None holds all `num_experts`."""
    built = tuple(range(num_layers)) if layers is None else tuple(
        int(i) for i in layers
    )
    if not built or min(built) < 0 or max(built) >= num_layers:
        raise ValueError(f"layers {built} of {num_layers} published")
    if heads % kv_heads or gdn_value_heads % gdn_key_heads:
        raise ValueError(
            "K/V heads divide the query heads, key heads the value heads"
        )
    return Qwen3Next(Qwen3NextConfig(
        hidden=hidden,
        layers=tuple(
            (i + 1) % int(full_attention_interval) != 0 for i in built
        ),
        heads=heads, kv_heads=kv_heads, head_dim=head_dim,
        rope=plain_rope(head_dim, rope_theta, partial_rotary_factor),
        gdn_key_heads=gdn_key_heads, gdn_value_heads=gdn_value_heads,
        gdn_head_dim=gdn_head_dim, conv_kernel=int(conv_kernel),
        expert_width=expert_width, shared_width=shared_width,
        num_experts=num_experts, top_k=top_k,
        held_experts=None if held_experts is None else tuple(held_experts),
        vocab_size=vocab_size, eps=eps,
        dtype=jnp.bfloat16 if bf16 else jnp.float32, remat=remat,
    ))
