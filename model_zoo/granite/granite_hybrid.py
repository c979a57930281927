"""granite-4.0-h-micro (`model_type: granitemoehybrid`, dense: no routed
experts) as a causal language model on the train path: nine layers in ten
mix tokens by Mamba-2 (a state-space scan with ONE scalar decay a head and
ONE B and ONE C a token that all 64 heads read), the tenth by grouped-query
attention that knows no positions at scale 1/64, every layer over a gated
MLP; four scalar multipliers; the head is the token embedding read again.

    h_0 = m_emb E[ids]
    r = h + m_res Mix_l(RMSNorm(h));  h' = r + m_res MLP(RMSNorm(r))
    Mamba-2:    [z | xBC | dt] = x W_in;  xBC = silu(conv_K(xBC) + b)
                [x | B | C] = split(xBC);  dt = softplus(dt + dt_bias)
                S_t = exp(-exp(A_log) dt_t) S_{t-1} + dt_t x_t B_t^T
                y_t = S_t C_t + D x_t
                Mix = (RMSNorm_all(y * silu(z)) * w) W_out
    attention:  Mix = concat_h(softmax_causal(q_h k^T m_att) v) Wo
    L = CE(RMSNorm(h_L) E^T / m_logits, x_{t+1})

The layer equations are written out in `benchmarks/reference/
granite_hybrid.py`, the plain float32 reference this model is held to
leaf by leaf (tests/decoder_cases.py), its Mamba-2 the token-by-token
recurrence.  What it shares with the zoo's other decoders (norms, SwiGLU,
the blocked cross-entropy, the gated norm) is `model_zoo/common/
decoder.py`; the Mamba-2 mixer, which `model_zoo/nemotron/nemotron_h.py`
shares, is `model_zoo/common/mamba.py` (the scan `ops/ssd.py: ssd`, the
convolution `ops/short_conv.py: silu_short_conv` with its bias);
attention's core is `ops/flash_attention.py: causal_attention` over
grouped K/V.

What a layer is comes from the PUBLISHED `layer_types` (`mamba` |
`attention`), read at the published indices in `layers`.  The four
multipliers have NO default: a configuration states them.  With `remat`
every block is rebuilt in the backward but for what `decoder.remat_block`
saves by name: an attention layer's core output and log-sum-exp; a
Mamba-2 layer saves nothing (`ops/ssd.py: SAVED_NAMES`); and, in as many
blocks as the device has room for (`room`, static data of the train
step: `decoder.remat_blocks`), the outputs of the block's own
projections, which `dense` names.

Record format: seq_len int32 token ids | 1 label byte (ignored), the
fixed-width record `model_zoo/bert` reads.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from elasticdl_tpu.layers.embedding import DistributedEmbedding
from model_zoo.bert.bert_finetune import feed, feed_bulk  # noqa: F401
from model_zoo.common.decoder import (  # noqa: F401
    GroupedAttention,
    RMSNorm,
    SwiGLU,
    eval_metrics_fn,
    loss,
    optimizer,
    param_sharding,
    remat_blocks,
    shifted_nll,
)
from model_zoo.common.mamba import Mamba2

MAMBA, ATTENTION = "mamba", "attention"
# the published pattern: attention at the sixth layer of every ten
PUBLISHED_LAYER_TYPES = tuple(
    ATTENTION if i % 10 == 5 else MAMBA for i in range(40)
)


@dataclasses.dataclass(frozen=True)
class GraniteConfig:
    """Every size of the model (`custom_model` documents them).
    `layers` is one kind a layer."""

    hidden: int
    layers: Tuple[str, ...]
    heads: int
    kv_heads: int
    mamba_heads: int
    mamba_head_dim: int
    mamba_state: int
    mamba_groups: int
    conv_kernel: int
    dense_width: int
    embedding_multiplier: float
    attention_multiplier: float
    residual_multiplier: float
    logits_scaling: float
    vocab_size: int
    eps: float
    dtype: Any
    remat: bool


class Block(nn.Module):
    """One pre-norm decoder block, both branches times the residual
    multiplier; `kind` says which mixer."""

    config: GraniteConfig
    kind: str

    @nn.compact
    def __call__(self, x):
        c = self.config
        # norms and residual sums are `granite/norm`: with the scopes of
        # the mixer and the MLP they tile the block
        # (profiler.DEVICE_SCOPES)
        with jax.named_scope("granite/norm"):
            y = RMSNorm(c.eps, c.dtype, name="mix_norm")(x)
        if self.kind == MAMBA:
            y = Mamba2(
                c.hidden, c.mamba_heads, c.mamba_head_dim, c.mamba_state,
                c.mamba_groups, c.conv_kernel, c.eps, c.dtype, "granite/ssm",
                name="mamba",
            )(y)
        else:
            y = GroupedAttention(
                c.hidden, c.heads, c.kv_heads, c.hidden // c.heads,
                c.attention_multiplier, c.dtype, "granite/attn", name="attn",
            )(y)
        with jax.named_scope("granite/norm"):
            x = x + c.residual_multiplier * y
            y = RMSNorm(c.eps, c.dtype, name="ffn_norm")(x)
        with jax.named_scope("granite/dense_ffn"):
            y = SwiGLU(c.hidden, c.dense_width, c.dtype, name="mlp")(y)
        with jax.named_scope("granite/norm"):
            return x + c.residual_multiplier * y


class GraniteHybrid(nn.Module):
    config: GraniteConfig

    @nn.compact
    def __call__(self, features, room=None):
        c = self.config
        ids = features["input_ids"].astype(jnp.int32)        # (B, L)
        embedding = DistributedEmbedding(
            c.vocab_size, c.hidden, hash_input=False, name="token_embedding"
        )
        with jax.named_scope("granite/embed"):
            x = (c.embedding_multiplier * embedding(ids)).astype(c.dtype)
        classes = remat_blocks(
            Block, c, c.layers, x, room, c.vocab_size
        ) if c.remat else [Block] * len(c.layers)
        for i, (kind, block_cls) in enumerate(zip(c.layers, classes)):
            x = block_cls(c, kind, name=f"layer_{i}")(x)
        # the tied head: the table read again, one leaf with two gradients
        table = embedding.variables["params"]["embedding"]
        with jax.named_scope("granite/norm"):
            x = RMSNorm(c.eps, jnp.float32, name="final_norm")(x)
            # logits / m_logits: the head is linear, so its input is scaled
            x = (x / c.logits_scaling).astype(c.dtype)
        return shifted_nll(x, table.T, ids, 1, c.dtype, "granite/head_ce")


def custom_model(
    *, embedding_multiplier: float, attention_multiplier: float,
    residual_multiplier: float, logits_scaling: float,
    hidden: int = 2048, layer_types=PUBLISHED_LAYER_TYPES, layers=None,
    heads: int = 32, kv_heads: int = 8, mamba_heads: int = 64,
    mamba_head_dim: int = 64, mamba_state: int = 128, mamba_groups: int = 1,
    conv_kernel: int = 4, dense_width: int = 8192, vocab_size: int = 100352,
    eps: float = 1e-5, bf16: bool = False, remat: bool = False,
):
    """`layer_types` is the published list, whole; `layers` lists the
    PUBLISHED indices that are built, in order (None builds every entry).
    A query head is `hidden / heads` wide.  The four multipliers are the
    configuration's (`embedding_multiplier`, `attention_multiplier`: the
    softmax scale itself, `residual_multiplier`, `logits_scaling`: a
    divisor) and have no default."""
    built = tuple(range(len(layer_types))) if layers is None else tuple(
        int(i) for i in layers
    )
    if not built or min(built) < 0 or max(built) >= len(layer_types):
        raise ValueError(
            f"layers {built} of {len(layer_types)} published entries"
        )
    if set(layer_types) - {MAMBA, ATTENTION}:
        raise ValueError(f"layer_types {sorted(set(layer_types))}")
    if hidden % heads or heads % kv_heads or mamba_heads % mamba_groups:
        raise ValueError(
            "heads divide the width, K/V heads the heads, groups the "
            "state-space heads"
        )
    return GraniteHybrid(GraniteConfig(
        hidden=hidden, layers=tuple(layer_types[i] for i in built),
        heads=heads, kv_heads=kv_heads, mamba_heads=mamba_heads,
        mamba_head_dim=mamba_head_dim, mamba_state=mamba_state,
        mamba_groups=mamba_groups, conv_kernel=int(conv_kernel),
        dense_width=dense_width,
        embedding_multiplier=float(embedding_multiplier),
        attention_multiplier=float(attention_multiplier),
        residual_multiplier=float(residual_multiplier),
        logits_scaling=float(logits_scaling), vocab_size=vocab_size, eps=eps,
        dtype=jnp.bfloat16 if bf16 else jnp.float32, remat=remat,
    ))
