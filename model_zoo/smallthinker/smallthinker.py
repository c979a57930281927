"""SmallThinker-21BA3B-Instruct (`model_name: smallthinker_21b_instruct`)
as a causal language model on the train path: a router that reads the
block's INPUT, before the attention norm, so that the routing waits on
nothing the block computes; 28 query heads over 4 K/V heads of 128 that
see a 4,096 band with rotary in three layers of four and the whole causal
sequence with NO positions in the fourth; every feed-forward 64
softmax-routed ReGLU experts (top-6, the softmax over the chosen six), no
shared expert, no dense layer; the head is its own matrix.

    r = x W_r;  S = top_6(r);  w_i = exp(r_i) / sum_{j in S} exp(r_j)
    a = RMSNorm(x);  q, k, v = a W_q, a W_k, a W_v
    band layer:  q, k turned by rotary; query t sees t - 4096 < s <= t
    full layer:  q, k as they are;      query t sees every s <= t
    h = x + softmax(q k^T 128^-1/2 + mask) v W_o       head h on K/V h // 7
    y = h + sum_{i in S, i held here} w_i (relu(m Wg_i) * (m Wu_i)) Wd_i
                                                       m = RMSNorm(h)
    L = CE(RMSNorm(y_L) W_head, x_{t+1})

The layer equations are written out in `benchmarks/reference/
smallthinker.py`, the plain float32 reference this model is held to leaf
by leaf (tests/decoder_cases.py).  What it shares with the zoo's other
decoders (norms, rotary's turn, grouped attention, the routed block, the
blocked cross-entropy, the blocks' remat) is `model_zoo/common/
decoder.py`.  The softmax over the chosen six is `layers/moe.py`'s
`softmax` scores renormalised over the picked (exp(r_i) / Z over the sum
of exp(r_j) / Z: the same number).

Layer i (a PUBLISHED 0-based index, listed in `layers`) is a band layer
where `sliding_window_layout[i]` is 1 and carries rotary where
`rope_layout[i]` is 1; the two published lists agree layer by layer (0 at
every fourth layer) and a pair that does not is refused.  With `remat`
every block is rebuilt in the backward but for the attention core's
output and log-sum-exp (`decoder.remat_block`).

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
from elasticdl_tpu.layers.moe import REGLU, SOFTMAX
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


# The seeded embedding's scale.  This model's router reads the residual
# stream UN-NORMED, and every branch that adds to the stream is built on a
# normed tensor, so its output is of order 1 whatever the stream's scale:
# at the zoo's usual normal(0.05) table the token's own part of the stream
# is a twentieth of what attention adds, which at seeded weights is nearly
# the same vector for every token (a mean over thousands of keys), and from
# the third layer on every token's router logits are the same logits: all
# 16,384 tokens pick the same six experts (largest load over the mean 7-10.6
# of at most 10.67, on the chip and in a CPU probe alike), and whether those
# six are among the eight held is the seed's luck (0.02-0.33 of the slots a
# layer).  At a table of unit scale the token's own part leads, the seeded
# router is near even in all four layers (1.2-1.4) and a held expert sees
# the 1,536 rows the cell's deployment states (`PERF.md` section 6, PR 57).
EMBED_STDDEV = 1.0


@dataclasses.dataclass(frozen=True)
class SmallThinkerConfig:
    """Every size of the model (`custom_model` documents them).
    `layers` is True a band layer (a window and rotary), False a full one
    (the whole causal sequence, no positions)."""

    hidden: int
    layers: Tuple[bool, ...]
    heads: int
    kv_heads: int
    head_dim: int
    window: int
    rope: Rope
    expert_width: int
    num_experts: int
    top_k: int
    held_experts: Optional[Tuple[int, int]]
    vocab_size: int
    eps: float
    dtype: Any
    remat: bool


class Block(nn.Module):
    """One pre-norm decoder block whose router reads the block's input;
    `banded` says which attention."""

    config: SmallThinkerConfig
    banded: bool

    @nn.compact
    def __call__(self, x):
        c = self.config
        # norms and residual sums are `smallthinker/norm`: with the scopes
        # of attention and the experts they tile the block
        # (profiler.DEVICE_SCOPES)
        with jax.named_scope("smallthinker/norm"):
            y = RMSNorm(c.eps, c.dtype, name="attn_norm")(x)
        y = GroupedAttention(
            c.hidden, c.heads, c.kv_heads, c.head_dim, c.head_dim ** -0.5,
            c.dtype,
            "smallthinker/attn_window" if self.banded
            else "smallthinker/attn_full",
            rope=c.rope if self.banded else None,
            window=c.window if self.banded else None, name="attn",
        )(y)
        with jax.named_scope("smallthinker/norm"):
            h = x + y
            y = RMSNorm(c.eps, c.dtype, name="ffn_norm")(h)
        # the experts read the normed residual after attention, the router
        # the block's input as it came in
        y = MoEFFN(
            c.hidden, c.num_experts, c.top_k, c.expert_width, 0,
            c.held_experts, 1.0, 0.0, c.dtype, "smallthinker/moe",
            form=REGLU, scores=SOFTMAX, route_scope="smallthinker/route",
            name="moe",
        )(y, route_from=x)
        with jax.named_scope("smallthinker/norm"):
            return h + y


class SmallThinker(nn.Module):
    config: SmallThinkerConfig

    @nn.compact
    def __call__(self, features, room=None):
        c = self.config
        ids = features["input_ids"].astype(jnp.int32)        # (B, L)
        with jax.named_scope("smallthinker/embed"):
            x = DistributedEmbedding(
                c.vocab_size, c.hidden, hash_input=False,
                init_stddev=EMBED_STDDEV, name="token_embedding",
            )(ids).astype(c.dtype)
        classes = remat_blocks(
            Block, c, c.layers, x, room, c.vocab_size, routed_walks(
                x, [True] * len(c.layers), c.top_k, c.expert_width, REGLU,
            ),
        ) if c.remat else [Block] * len(c.layers)
        for i, (banded, block_cls) in enumerate(zip(c.layers, classes)):
            x = block_cls(c, banded, name=f"layer_{i}")(x)
        head = self.param(
            "lm_head_kernel", nn.initializers.lecun_normal(),
            (c.hidden, c.vocab_size),
        )
        with jax.named_scope("smallthinker/norm"):
            x = RMSNorm(c.eps, c.dtype, name="final_norm")(x)
        return shifted_nll(x, head, ids, 1, c.dtype, "smallthinker/head_ce")


def custom_model(
    hidden: int = 2560, num_layers: int = 52, sliding_window_layout=None,
    rope_layout=None, layers=None, heads: int = 28, kv_heads: int = 4,
    head_dim: int = 128, window: int = 4096, rope_theta: float = 1.5e6,
    expert_width: int = 768, num_experts: int = 64, top_k: int = 6,
    held_experts=None, vocab_size: int = 151936, eps: float = 1e-6,
    bf16: bool = False, remat: bool = False,
):
    """`sliding_window_layout` and `rope_layout` are the published lists,
    one entry a published layer (None: 0 at every fourth layer, 1
    elsewhere): layer i sees a band of `window` keys where the first says
    1 and turns q and k where the second does, and a layer on which they
    disagree is refused.  `layers` lists the published 0-BASED indices
    that are built, in order (None builds all `num_layers`).
    `held_experts` is (first, count) of the routed experts whose weights
    live in this process; None holds all `num_experts`."""
    period = [int(i % 4 != 0) for i in range(num_layers)]
    bands = period if sliding_window_layout is None else [
        int(v) for v in sliding_window_layout
    ]
    turns = period if rope_layout is None else [int(v) for v in rope_layout]
    if len(bands) != num_layers or len(turns) != num_layers:
        raise ValueError(f"{num_layers} layers need as many entries a list")
    if not set(bands) | set(turns) <= {0, 1}:
        raise ValueError("a layout's entries are 0 or 1")
    built = tuple(range(num_layers)) if layers is None else tuple(
        int(i) for i in layers
    )
    if not built or min(built) < 0 or max(built) >= num_layers:
        raise ValueError(f"layers {built} of {num_layers} published")
    for i in built:
        if bands[i] != turns[i]:
            raise ValueError(
                f"layer {i}: sliding_window_layout {bands[i]}, rope_layout "
                f"{turns[i]}; a band layer carries rotary and a full layer "
                "no positions"
            )
    if heads % kv_heads:
        raise ValueError("K/V heads divide the query heads")
    return SmallThinker(SmallThinkerConfig(
        hidden=hidden, layers=tuple(bool(bands[i]) for i in built),
        heads=heads, kv_heads=kv_heads, head_dim=head_dim,
        window=int(window), rope=plain_rope(head_dim, rope_theta),
        expert_width=expert_width, num_experts=num_experts, top_k=top_k,
        held_experts=None if held_experts is None else tuple(held_experts),
        vocab_size=vocab_size, eps=eps,
        dtype=jnp.bfloat16 if bf16 else jnp.float32, remat=remat,
    ))
