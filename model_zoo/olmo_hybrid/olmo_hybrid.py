"""Olmo-Hybrid-7B (`model_type: olmo_hybrid`) as a causal language model
on the train path: a DENSE decoder whose layers mix tokens by the gated
delta rule three times in four (heads 96 wide in keys and 192 in values,
a write strength beta = 2 sigmoid(b) in (0, 2): `linear_allow_neg_eigval`)
and by multi-head attention without positions the fourth (q and k normed
over their WHOLE width), in OLMo 2/3's block, which norms a sublayer's
OUTPUT inside the residual branch and nothing before it.

    Norm(x)  = x rsqrt(mean x^2 + 1e-6) w                     plain scale
    block    : h = x + Norm_a(Mix_l(x));  y = h + Norm_f(MLP(h))
               (NO norm before a sublayer, one on its output, inside the
               residual branch; both kinds of layer)
    MLP(h)   = (silu(h Wg) * (h Wu)) Wd                       11,008 wide
    linear_attention (layers 0,1,2, 4,5,6, ...):
      q = x Wq (30 x 96)   k = x Wk (30 x 96)   v = x Wv (30 x 192)
      z = x Wz (30 x 192)  a = x Wa (30)        b = x Wb (30)
      [q | k | v] = silu(conv_4([q | k | v]))   causal, depthwise, no bias
      q, k L2-normed a head;  q times 96^-1/2
      g = -exp(A_log) softplus(a + dt_bias)      beta = 2 sigmoid(b)
      S_t = exp(g_t) S_{t-1};  S_t += k_t (beta_t (v_t - S_t^T k_t))^T
      o_t = S_t^T q_t                            S in R^{96 x 192} a head
      Mix = (rms_norm_head(o) w * silu(z)) Wo    w one scale of 192
    full_attention (layers 3, 7, ...):
      q = x Wq, k = x Wk, v = x Wv               30 heads of 128, MHA
      q = Norm_q(q), k = Norm_k(k)               ONE statistic over all 3,840
                                                 columns, not one a head
      Mix = softmax_causal(q k^T 128^-1/2) v Wo  no rotary: rope_theta null
    L = CE(Norm(h_32) W_head, x_{t+1})

With beta in (0, 2) the state's transition exp(g)(I - beta k k^T) has an
eigenvalue in (-1, 1) along k, where every other delta rule of the zoo
keeps it in (0, 1).

The model is a DESCRIPTION over shared parts: the delta-rule layer is
`model_zoo/common/delta_net.py: GatedDeltaNet` with its six projections
apart and `beta_scale` 2 (Qwen3-Next builds the same class fused, at 1);
attention is `decoder.GroupedAttention` with `qk_norm_whole` and no
`rope`; `SwiGLU`, `RMSNorm`, the blocked cross-entropy and the blocks'
remat are `model_zoo/common/decoder.py`'s.  The equations are written out
in `benchmarks/reference/olmo_hybrid.py`, the plain float32 reference this
model is held to leaf by leaf (tests/decoder_cases.py), its delta rule the
token-by-token recurrence.

HEADS are a chip's share here (`held_heads` = (first, count), the same
for both kinds of mixer): a mixer builds its held heads' columns and rows
alone and returns its part of the output, which is what the block norms
and adds.  The delta-rule layer is head-wise throughout; the attention
layer's whole-width QK-norm is not, and its sum of squares is the one
number a token that head-parallel chips exchange beside the outputs' sum.
With `axis_name` (the named axis such chips run under) both are summed
over it; without one, as on one chip, nothing is emitted and the QK-norm's
statistic is the held columns' own mean.

With `remat` every block is rebuilt in the backward but for what
`decoder.remat_block` saves by name (the attention core's output and
log-sum-exp; a delta-rule layer saves nothing) and what `remat_blocks`
finds room for: the block's first operation is a projection of the
un-normed stream, and BOTH sublayers' last products are a norm's input,
so `o` and `down` carry names (`MIXER_OUT`, `FFN_OUT`).

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
from model_zoo.bert.bert_finetune import feed, feed_bulk  # noqa: F401
from model_zoo.common.decoder import (  # noqa: F401
    FFN_OUT,
    GroupedAttention,
    RMSNorm,
    SwiGLU,
    eval_metrics_fn,
    held_of,
    loss,
    optimizer,
    param_sharding,
    remat_blocks,
    shifted_nll,
)
from model_zoo.common.delta_net import GatedDeltaNet

LINEAR, FULL = "linear_attention", "full_attention"
# the published pattern: attention at the fourth layer of every four
PUBLISHED_LAYER_TYPES = tuple(
    FULL if i % 4 == 3 else LINEAR for i in range(32)
)


@dataclasses.dataclass(frozen=True)
class OlmoHybridConfig:
    """Every size of the model (`custom_model` documents them).
    `layers` is one kind a layer built."""

    hidden: int
    layers: Tuple[str, ...]
    heads: int
    kv_heads: int
    head_dim: int
    gdn_key_heads: int
    gdn_value_heads: int
    gdn_key_dim: int
    gdn_value_dim: int
    conv_kernel: int
    beta_scale: float
    held_heads: Optional[Tuple[int, int]]
    axis_name: Optional[str]
    dense_width: int
    vocab_size: int
    eps: float
    dtype: Any
    remat: bool


class Block(nn.Module):
    """One block in OLMo's order: each sublayer reads the stream as it
    is and its OUTPUT is normed inside the residual branch; `kind` is the
    layer's published type."""

    config: OlmoHybridConfig
    kind: str

    def mix(self, x):
        """This layer's mixer over x: its held heads' part of the output."""
        c = self.config
        if self.kind == LINEAR:
            return GatedDeltaNet(
                c.hidden, c.gdn_key_heads, c.gdn_value_heads, c.gdn_key_dim,
                c.gdn_value_dim, c.conv_kernel, c.eps, c.dtype,
                "olmo_hybrid/gdn", fused=False, beta_scale=c.beta_scale,
                held_heads=c.held_heads, axis_name=c.axis_name, name="gdn",
            )(x)
        return GroupedAttention(
            c.hidden, c.heads, c.kv_heads, c.head_dim, c.head_dim ** -0.5,
            c.dtype, "olmo_hybrid/attn", qk_norm_eps=c.eps,
            qk_norm_whole=True, held_heads=c.held_heads,
            axis_name=c.axis_name, name="attn",
        )(x)

    @nn.compact
    def __call__(self, x):
        c = self.config
        y = self.mix(x)
        # norms and residual sums are `olmo_hybrid/norm`: with the scopes
        # of the mixer and the MLP they tile the block
        # (profiler.DEVICE_SCOPES)
        with jax.named_scope("olmo_hybrid/norm"):
            h = x + RMSNorm(c.eps, c.dtype, name="mix_norm")(y)
        with jax.named_scope("olmo_hybrid/dense_ffn"):
            # `down`'s output is the second norm's input, which its
            # backward reads
            y = SwiGLU(
                c.hidden, c.dense_width, c.dtype, FFN_OUT, name="mlp"
            )(h)
        with jax.named_scope("olmo_hybrid/norm"):
            return h + RMSNorm(c.eps, c.dtype, name="ffn_norm")(y)


class OlmoHybrid(nn.Module):
    config: OlmoHybridConfig

    @nn.compact
    def __call__(self, features, room=None):
        c = self.config
        ids = features["input_ids"].astype(jnp.int32)        # (B, L)
        with jax.named_scope("olmo_hybrid/embed"):
            x = DistributedEmbedding(
                c.vocab_size, c.hidden, hash_input=False,
                name="token_embedding",
            )(ids).astype(c.dtype)
        classes = remat_blocks(
            Block, c, c.layers, x, room, c.vocab_size
        ) if c.remat else [Block] * len(c.layers)
        for i, (kind, block_cls) in enumerate(zip(c.layers, classes)):
            x = block_cls(c, kind, name=f"layer_{i}")(x)
        head = self.param(
            "lm_head_kernel", nn.initializers.lecun_normal(),
            (c.hidden, c.vocab_size),
        )
        with jax.named_scope("olmo_hybrid/norm"):
            x = RMSNorm(c.eps, c.dtype, name="final_norm")(x)
        return shifted_nll(x, head, ids, 1, c.dtype, "olmo_hybrid/head_ce")


def custom_model(
    hidden: int = 3840, layer_types=PUBLISHED_LAYER_TYPES, layers=None,
    heads: int = 30, kv_heads: int = 30, head_dim: int = 128,
    gdn_key_heads: int = 30, gdn_value_heads: int = 30,
    gdn_key_dim: int = 96, gdn_value_dim: int = 192, conv_kernel: int = 4,
    allow_neg_eigval: bool = True, held_heads=None, axis_name=None,
    dense_width: int = 11008, vocab_size: int = 100352, eps: float = 1e-6,
    bf16: bool = False, remat: bool = False,
):
    """`layer_types` is the published list, whole; `layers` lists the
    PUBLISHED indices that are built, in order (None builds every entry).
    A query head is `head_dim` wide, a delta-rule head `gdn_key_dim` in q
    and k and `gdn_value_dim` in v, z and the state's other side.
    `allow_neg_eigval` writes at beta = 2 sigmoid(b).  `held_heads` is
    (first, count) of the heads of BOTH kinds of mixer whose weights live
    in this process (the head counts given are the PUBLISHED ones); None
    holds all.  `axis_name` names the axis head-parallel holders run
    under (a test's `vmap`, a deployment's `shard_map`); None on one
    chip."""
    built = tuple(range(len(layer_types))) if layers is None else tuple(
        int(i) for i in layers
    )
    if not built or min(built) < 0 or max(built) >= len(layer_types):
        raise ValueError(
            f"layers {built} of {len(layer_types)} published entries"
        )
    if set(layer_types) - {LINEAR, FULL}:
        raise ValueError(f"layer_types {sorted(set(layer_types))}")
    if heads % kv_heads or gdn_value_heads % gdn_key_heads:
        raise ValueError(
            "K/V heads divide the query heads, key heads the value heads"
        )
    if held_heads is not None:
        held_heads = tuple(int(n) for n in held_heads)
        for count in (heads, gdn_value_heads):
            held_of(count, held_heads)
    return OlmoHybrid(OlmoHybridConfig(
        hidden=hidden, layers=tuple(layer_types[i] for i in built),
        heads=heads, kv_heads=kv_heads, head_dim=head_dim,
        gdn_key_heads=gdn_key_heads, gdn_value_heads=gdn_value_heads,
        gdn_key_dim=gdn_key_dim, gdn_value_dim=gdn_value_dim,
        conv_kernel=int(conv_kernel),
        beta_scale=2.0 if allow_neg_eigval else 1.0,
        held_heads=held_heads, axis_name=axis_name,
        dense_width=dense_width, vocab_size=vocab_size, eps=eps,
        dtype=jnp.bfloat16 if bf16 else jnp.float32, remat=remat,
    ))
