"""Laguna-XS.2 (`model_type: laguna`) as a causal language model on the
train path: grouped-query attention whose layers differ one from the next
(window or full, each kind with its own head count and its own rotary
table), a per-head sigmoid gate on attention's output, one leading dense
SwiGLU layer, then layers of 256 sigmoid-routed experts (top-8,
renormalised weights times 2.5) beside one shared expert.

    h = x + Attn_l(RMSNorm(x));  y = h + FFN_l(RMSNorm(h))
    Attn_l = concat_h(sigmoid(x Wg)_h * softmax_band(q_h k^T / sqrt(D)) v) Wo
    L = CE(head(RMSNorm(h_L)), x_{t+1})

The layer equations are written out in `benchmarks/reference/laguna.py`,
the plain float32 reference this model is held to leaf by leaf
(tests/decoder_cases.py).  What it shares with the zoo's other decoder
(norms, rotary's turn, SwiGLU, the routed block, the blocked
cross-entropy) is `model_zoo/common/decoder.py`.

What a layer is comes from static per-layer tuples (`LagunaConfig.layers`):
its kind (`full_attention` | `sliding_attention`), its query heads and
whether its feed-forward is dense or routed.  K/V stay at their 8 heads
all the way into the kernels (`ops/flash_attention.py: causal_attention`
takes grouped K/V), and a window layer's kernels visit the band only.
With `remat` every block is rebuilt in the backward but for the attention
core's output and log-sum-exp, which stay from the forward
(`decoder.remat_block`: 201 MB a full layer and 268 MB a window layer at
the cell's shape, so that the forward kernel runs once a step).

Record format: seq_len int32 token ids | 1 label byte (ignored), the
fixed-width record `model_zoo/bert` reads.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from elasticdl_tpu.common import metrics as metrics_lib
from elasticdl_tpu.layers.embedding import DistributedEmbedding
from elasticdl_tpu.layers import step_metrics
from elasticdl_tpu.layers.step_metrics import sow_step_metric
from elasticdl_tpu.ops.flash_attention import causal_attention
from model_zoo.bert.bert_finetune import feed, feed_bulk  # noqa: F401
from model_zoo.common.decoder import (  # noqa: F401
    MIXER_IN,
    MIXER_OUT,
    MoEFFN,
    RMSNorm,
    Rope,
    SwiGLU,
    dense,
    eval_metrics_fn,
    loss,
    optimizer,
    param_sharding,
    partial_rotary,
    plain_rope,
    remat_blocks,
    routed_walks,
    shifted_nll,
    sow_rope_one_pass,
)

FULL, WINDOW = "full_attention", "sliding_attention"


def rope_of(parameters: dict, head_dim: int) -> Rope:
    """A `rope_parameters` group of the published config -> `Rope`.
    `default`: theta ** (-2i / R).  `yarn` (arXiv:2309.00071): the
    frequencies that turn more than `beta_fast` times over the original
    context stay, those that turn less than `beta_slow` times are divided
    by `factor`, a linear ramp between; cos and sin times
    `attention_factor`."""
    theta = float(parameters["rope_theta"])
    rope = plain_rope(
        head_dim, theta, parameters.get("partial_rotary_factor", 1)
    )
    if parameters.get("rope_type", "default") == "default":
        return rope
    columns, plain = rope.columns, np.asarray(rope.inv_freq)
    if parameters["rope_type"] != "yarn":
        raise ValueError(f"rope_type {parameters['rope_type']!r}")
    original = parameters["original_max_position_embeddings"]

    def turns_at(rotations):
        """The (fractional) column pair that turns `rotations` times
        over the original context."""
        return columns * math.log(
            original / (rotations * 2 * math.pi)
        ) / (2 * math.log(theta))

    low = max(math.floor(turns_at(parameters["beta_fast"])), 0)
    high = min(math.ceil(turns_at(parameters["beta_slow"])), columns - 1)
    ramp = np.clip(
        (np.arange(columns // 2) - low) / max(high - low, 1e-3), 0, 1
    )
    inv_freq = plain / parameters["factor"] * ramp + plain * (1 - ramp)
    return Rope(
        columns, tuple(inv_freq.tolist()),
        float(parameters["attention_factor"]),
    )


# What a gated attention layer sows into STEP_METRICS: the mean of its
# per-head sigmoid output gate.
step_metrics.declare(
    "gate_mean",
    metrics_lib.default_registry().gauge(
        "worker_attention_gate_mean_ratio",
        "mean of the attention layer's sigmoid output gate over tokens and "
        "heads, last step of the task (a gate that closes silences its layer)",
        labelnames=("layer",),
    ),
)


class GatedGroupedAttention(nn.Module):
    """`heads` query heads over `kv_heads` key/value heads (query head h
    reads K/V head h // (heads / kv_heads)), causal, over a `window` of
    keys where one is given, each head's output times its own sigmoid
    gate of the layer's input."""

    hidden: int
    heads: int
    kv_heads: int
    head_dim: int
    window: Optional[int]
    rope: Rope
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        batch, length, _ = x.shape
        heads, kv_heads, dim = self.heads, self.kv_heads, self.head_dim
        scope = "laguna/attn_window" if self.window else "laguna/attn_full"
        with jax.named_scope(scope):
            q = dense(heads * dim, "q", self.dtype, MIXER_IN)(x).reshape(
                batch, length, heads, dim
            )
            k = dense(kv_heads * dim, "k", self.dtype, MIXER_IN)(x).reshape(
                batch, length, kv_heads, dim
            )
            v = dense(kv_heads * dim, "v", self.dtype, MIXER_IN)(x).reshape(
                batch, length, kv_heads, dim
            )
            sow_rope_one_pass(self, self.rope.columns, q.shape, k.shape)
            out = causal_attention(
                partial_rotary(q, self.rope), partial_rotary(k, self.rope),
                v, scale=dim ** -0.5, window=self.window,
            )
        with jax.named_scope("laguna/gate"):
            gate = nn.sigmoid(dense(heads, "gate", self.dtype)(x))
            # a gate that closes silences its layer
            sow_step_metric(self, "gate_mean", gate.mean())
            out = out * gate[..., None]
        with jax.named_scope(scope):
            return dense(self.hidden, "o", self.dtype, MIXER_OUT)(
                out.reshape(batch, length, heads * dim)
            )


@dataclasses.dataclass(frozen=True)
class LagunaConfig:
    """Every size of the model (`custom_model` documents them).
    `layers` is one (kind, query heads, routed?) a layer."""

    hidden: int
    layers: Tuple[Tuple[str, int, bool], ...]
    kv_heads: int
    head_dim: int
    window: int
    full_rope: Rope
    window_rope: Rope
    dense_width: int
    expert_width: int
    shared_width: int
    num_experts: int
    top_k: int
    held_experts: Optional[Tuple[int, int]]
    routed_scaling: float
    vocab_size: int
    eps: float
    dtype: Any
    remat: bool


class Block(nn.Module):
    """One pre-norm decoder block; `layer` says which."""

    config: LagunaConfig
    layer: Tuple[str, int, bool]

    @nn.compact
    def __call__(self, x):
        c = self.config
        kind, heads, routed = self.layer
        windowed = kind == WINDOW
        # norms and residual sums are `laguna/norm`: with the scopes of
        # attention and the feed-forward they tile the block
        # (profiler.DEVICE_SCOPES)
        with jax.named_scope("laguna/norm"):
            y = RMSNorm(c.eps, c.dtype, name="attn_norm")(x)
        y = GatedGroupedAttention(
            c.hidden, heads, c.kv_heads, c.head_dim,
            c.window if windowed else None,
            c.window_rope if windowed else c.full_rope, c.dtype, name="attn",
        )(y)
        with jax.named_scope("laguna/norm"):
            x = x + y
            y = RMSNorm(c.eps, c.dtype, name="ffn_norm")(x)
        if routed:
            y = MoEFFN(
                c.hidden, c.num_experts, c.top_k, c.expert_width,
                c.shared_width // c.expert_width, c.held_experts,
                c.routed_scaling, 0.0, c.dtype, "laguna/moe", name="moe",
            )(y)
        else:
            with jax.named_scope("laguna/dense_ffn"):
                y = SwiGLU(c.hidden, c.dense_width, c.dtype, name="mlp")(y)
        with jax.named_scope("laguna/norm"):
            return x + y


class Laguna(nn.Module):
    config: LagunaConfig

    @nn.compact
    def __call__(self, features, room=None):
        c = self.config
        ids = features["input_ids"].astype(jnp.int32)        # (B, L)
        with jax.named_scope("laguna/embed"):
            x = DistributedEmbedding(
                c.vocab_size, c.hidden, hash_input=False,
                name="token_embedding",
            )(ids).astype(c.dtype)
        classes = remat_blocks(
            Block, c, c.layers, x, room, c.vocab_size, routed_walks(
                x, [routed for _, _, routed in c.layers], c.top_k,
                c.expert_width,
            ),
        ) if c.remat else [Block] * len(c.layers)
        for i, (layer, block_cls) in enumerate(zip(c.layers, classes)):
            x = block_cls(c, layer, name=f"layer_{i}")(x)
        head = self.param(
            "lm_head_kernel", nn.initializers.lecun_normal(),
            (c.hidden, c.vocab_size),
        )
        with jax.named_scope("laguna/norm"):
            x = RMSNorm(c.eps, c.dtype, name="final_norm")(x)
        return shifted_nll(x, head, ids, 1, c.dtype, "laguna/head_ce")


def custom_model(
    hidden: int = 2048, num_layers: int = 5,
    layer_types=(FULL, WINDOW, WINDOW, WINDOW, FULL),
    mlp_layer_types=("dense", "sparse", "sparse", "sparse", "sparse"),
    heads_per_layer=(48, 64, 64, 64, 48), kv_heads: int = 8,
    head_dim: int = 128, window: int = 512, rope_parameters=None,
    dense_width: int = 8192, expert_width: int = 512,
    shared_width: int = 512, num_experts: int = 256, top_k: int = 8,
    held_experts=None, routed_scaling: float = 2.5,
    vocab_size: int = 12544, eps: float = 1e-6, bf16: bool = False,
    remat: bool = False,
):
    """The per-layer lists are the published ones, of which the first
    `num_layers` entries are built.  `rope_parameters` is the published
    group ({"full_attention": {...}, "sliding_attention": {...}}; plain
    rotary at theta 10,000 where a kind has none).  `held_experts` is
    (first, count) of the routed experts whose weights live in this
    process; None holds all `num_experts`."""
    lists = (layer_types, mlp_layer_types, heads_per_layer)
    if min(len(x) for x in lists) < num_layers:
        raise ValueError(f"{num_layers} layers need as many entries a list")
    if set(layer_types) - {FULL, WINDOW}:
        raise ValueError(f"layer_types {sorted(set(layer_types))}")
    if shared_width % expert_width:
        raise ValueError("the shared expert is whole experts wide")
    ropes = rope_parameters or {}
    plain = {"rope_type": "default", "rope_theta": 10000.0}
    return Laguna(LagunaConfig(
        hidden=hidden,
        layers=tuple(
            (kind, int(heads), mlp == "sparse") for kind, mlp, heads in
            list(zip(*lists))[:num_layers]
        ),
        kv_heads=kv_heads, head_dim=head_dim, window=int(window),
        full_rope=rope_of(ropes.get(FULL, plain), head_dim),
        window_rope=rope_of(ropes.get(WINDOW, plain), head_dim),
        dense_width=dense_width, expert_width=expert_width,
        shared_width=shared_width, num_experts=num_experts, top_k=top_k,
        held_experts=None if held_experts is None else tuple(held_experts),
        routed_scaling=routed_scaling, vocab_size=vocab_size, eps=eps,
        dtype=jnp.bfloat16 if bf16 else jnp.float32, remat=remat,
    ))
