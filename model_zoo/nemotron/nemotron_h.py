"""NVIDIA-Nemotron-3-Nano-30B-A3B (`model_type: nemotron_h`) as a causal
language model on the train path: every layer is ONE norm and ONE branch,
a token mixer OR a feed-forward part alone, by the published
`hybrid_override_pattern` (`M` Mamba-2 with B and C in 8 groups | `E`
top-6 of 128 routed squared-ReLU experts without a gate projection beside
a double-width shared expert | `*` 32-over-2 grouped-query attention that
knows no positions); the head is untied.

    h_0 = E[ids];  h' = h + Mix_l(RMSNorm(h))
    M   [z | xBC | dt] = x W_in;  xBC = silu(conv_K(xBC) + b)
        [x | B | C] = split(xBC);  dt = softplus(dt + dt_bias)
        S_t = exp(-exp(A_log) dt_t) S_{t-1} + dt_t x_t B_{t,g}^T
        y_t = S_t C_{t,g} + D x_t                 head h reads group h // 8
        Mix = (RMSNorm_g(y * silu(z)) * w) W_out  a statistic a group
    *   Mix = concat_h(softmax_causal(q_h k_g(h)^T D^-1/2) v_g(h)) Wo
    E   s = sigmoid(x Wr);  S = top_k(s + b);  w_i = c s_i / sum_{j in S} s_j
        Mix = sum_{i in S, i held here} w_i (relu(x U_i))^2 D_i
              + (relu(x U_s))^2 D_s
    L = CE(RMSNorm(h_L) W_head, x_{t+1})

The layer equations are written out in `benchmarks/reference/
nemotron_h.py`, the plain float32 reference this model is held to leaf by
leaf (tests/decoder_cases.py), its Mamba-2 the token-by-token recurrence
with B and C by group.  The Mamba-2 mixer is `model_zoo/common/mamba.py`
(Granite's, at eight groups); norms, attention, the routed block with its
shared expert, the blocked cross-entropy and the blocks' remat are
`model_zoo/common/decoder.py`; the experts are `layers/moe.py:
RoutedExperts` in the `relu2` form.

What a layer is comes from the PUBLISHED pattern string, whole, read at
the published indices in `layers`.  With `remat` every block is rebuilt
in the backward but for what `decoder.remat_block` saves by name (the
attention layer's core output and log-sum-exp) and, in as many blocks as
the device has room for (`room`: `decoder.remat_blocks`), the outputs of
the block's own projections.

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
from elasticdl_tpu.layers.moe import RELU2
from model_zoo.bert.bert_finetune import feed, feed_bulk  # noqa: F401
from model_zoo.common.decoder import (  # noqa: F401
    GroupedAttention,
    MoEFFN,
    RMSNorm,
    eval_metrics_fn,
    loss,
    optimizer,
    param_sharding,
    remat_blocks,
    routed_walks,
    shifted_nll,
)
from model_zoo.common.mamba import Mamba2

MAMBA, EXPERTS, ATTENTION = KINDS = ("M", "E", "*")
PUBLISHED_PATTERN = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"


@dataclasses.dataclass(frozen=True)
class NemotronConfig:
    """Every size of the model (`custom_model` documents them).
    `layers` is one kind a layer."""

    hidden: int
    layers: Tuple[str, ...]
    heads: int
    kv_heads: int
    head_dim: int
    mamba_heads: int
    mamba_head_dim: int
    mamba_state: int
    mamba_groups: int
    conv_kernel: int
    expert_width: int
    shared_width: int
    num_experts: int
    top_k: int
    held_experts: Optional[Tuple[int, int]]
    routed_scaling: float
    bias_update_rate: float
    vocab_size: int
    eps: float
    dtype: Any
    remat: bool


class Block(nn.Module):
    """One pre-norm layer: ONE norm and ONE branch, which `kind` names."""

    config: NemotronConfig
    kind: str

    @nn.compact
    def __call__(self, x):
        c = self.config
        # the norm and the residual sum are `nemotron/norm`: with the
        # branch's scopes they tile the block (profiler.DEVICE_SCOPES)
        with jax.named_scope("nemotron/norm"):
            y = RMSNorm(c.eps, c.dtype, name="norm")(x)
        if self.kind == MAMBA:
            y = Mamba2(
                c.hidden, c.mamba_heads, c.mamba_head_dim, c.mamba_state,
                c.mamba_groups, c.conv_kernel, c.eps, c.dtype, "nemotron/ssm",
                name="mamba",
            )(y)
        elif self.kind == ATTENTION:
            y = GroupedAttention(
                c.hidden, c.heads, c.kv_heads, c.head_dim,
                c.head_dim ** -0.5, c.dtype, "nemotron/attn", name="attn",
            )(y)
        else:
            y = MoEFFN(
                c.hidden, c.num_experts, c.top_k, c.expert_width, 1,
                c.held_experts, c.routed_scaling, c.bias_update_rate,
                c.dtype, "nemotron/moe", form=RELU2,
                shared_width=c.shared_width, name="moe",
            )(y)
        with jax.named_scope("nemotron/norm"):
            return x + y


class NemotronH(nn.Module):
    config: NemotronConfig

    @nn.compact
    def __call__(self, features, room=None):
        c = self.config
        ids = features["input_ids"].astype(jnp.int32)        # (B, L)
        embedding = DistributedEmbedding(
            c.vocab_size, c.hidden, hash_input=False, name="token_embedding"
        )
        with jax.named_scope("nemotron/embed"):
            x = embedding(ids).astype(c.dtype)
        classes = remat_blocks(
            Block, c, c.layers, x, room, c.vocab_size, routed_walks(
                x, [kind == EXPERTS for kind in c.layers], c.top_k,
                c.expert_width, RELU2,
            ),
        ) if c.remat else [Block] * len(c.layers)
        for i, (kind, block_cls) in enumerate(zip(c.layers, classes)):
            x = block_cls(c, kind, name=f"layer_{i}")(x)
        head = self.param(
            "lm_head_kernel", nn.initializers.lecun_normal(),
            (c.hidden, c.vocab_size),
        )
        with jax.named_scope("nemotron/norm"):
            x = RMSNorm(c.eps, c.dtype, name="final_norm")(x)
        return shifted_nll(x, head, ids, 1, c.dtype, "nemotron/head_ce")


def custom_model(
    hidden: int = 2688, pattern: str = PUBLISHED_PATTERN, layers=None,
    heads: int = 32, kv_heads: int = 2, head_dim: int = 128,
    mamba_heads: int = 64, mamba_head_dim: int = 64, mamba_state: int = 128,
    mamba_groups: int = 8, conv_kernel: int = 4, expert_width: int = 1856,
    shared_width: int = 3712, num_experts: int = 128, top_k: int = 6,
    held_experts=None, routed_scaling: float = 2.5,
    bias_update_rate: float = 0.0, vocab_size: int = 131072,
    eps: float = 1e-5, bf16: bool = False, remat: bool = False,
):
    """`pattern` is the published `hybrid_override_pattern`, whole, one
    letter a layer (`M` Mamba-2, `E` routed experts, `*` attention);
    `layers` lists the PUBLISHED indices that are built, in order (None
    builds every letter).  A query head is `head_dim` wide whatever
    `hidden / heads` is; a state-space layer is `mamba_heads *
    mamba_head_dim` channels wide.  `held_experts` is (first, count) of
    the routed experts whose weights live in this process; None holds all
    `num_experts`."""
    pattern = str(pattern)
    if set(pattern) - set(KINDS):
        raise ValueError(
            f"pattern letters {sorted(set(pattern) - set(KINDS))}: a layer "
            f"is one of {KINDS}"
        )
    built = tuple(range(len(pattern))) if layers is None else tuple(
        int(i) for i in layers
    )
    if not built or min(built) < 0 or max(built) >= len(pattern):
        raise ValueError(f"layers {built} of {len(pattern)} published")
    if heads % kv_heads or mamba_heads % mamba_groups:
        raise ValueError(
            "K/V heads divide the heads, groups the state-space heads"
        )
    return NemotronH(NemotronConfig(
        hidden=hidden, layers=tuple(pattern[i] for i in built), heads=heads,
        kv_heads=kv_heads, head_dim=head_dim, mamba_heads=mamba_heads,
        mamba_head_dim=mamba_head_dim, mamba_state=mamba_state,
        mamba_groups=mamba_groups, conv_kernel=int(conv_kernel),
        expert_width=expert_width, shared_width=shared_width,
        num_experts=num_experts, top_k=top_k,
        held_experts=None if held_experts is None else tuple(held_experts),
        routed_scaling=float(routed_scaling),
        bias_update_rate=float(bias_update_rate), vocab_size=vocab_size,
        eps=eps, dtype=jnp.bfloat16 if bf16 else jnp.float32, remat=remat,
    ))
