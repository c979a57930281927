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
leaf by leaf (tests/test_granite_hybrid.py), its Mamba-2 the token-by-token
recurrence.  What it shares with the zoo's other decoders (norms, SwiGLU,
the blocked cross-entropy, the gated norm) is `model_zoo/common/
decoder.py`; the scan is `ops/ssd.py: ssd`, the convolution
`ops/short_conv.py: silu_short_conv` with its bias, attention's core
`ops/flash_attention.py: causal_attention` over grouped K/V.

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

from elasticdl_tpu.common import metrics as metrics_lib
from elasticdl_tpu.layers.embedding import DistributedEmbedding
from elasticdl_tpu.layers import step_metrics
from elasticdl_tpu.layers.step_metrics import sow_step_metric
from elasticdl_tpu.ops import ssd as ssd_ops
from elasticdl_tpu.ops.flash_attention import causal_attention
from elasticdl_tpu.ops.short_conv import silu_short_conv
from model_zoo.bert.bert_finetune import feed, feed_bulk  # noqa: F401
from model_zoo.common.decoder import (  # noqa: F401
    MIXER_IN,
    MIXER_OUT,
    GatedRMSNorm,
    RMSNorm,
    SwiGLU,
    a_log_init,
    dense,
    dt_bias_init,
    eval_metrics_fn,
    loss,
    optimizer,
    param_sharding,
    remat_blocks,
    shifted_nll,
    tap_init,
)

MAMBA, ATTENTION = "mamba", "attention"
# the published pattern: attention at the sixth layer of every ten
PUBLISHED_LAYER_TYPES = tuple(
    ATTENTION if i % 10 == 5 else MAMBA for i in range(40)
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
    B and C shared by the heads of each of `groups` groups, x, B and C
    through one `taps`-tap causal depthwise conv with a bias."""

    hidden: int
    heads: int
    head_dim: int
    state: int
    groups: int
    taps: int
    eps: float
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        batch, length, _ = x.shape
        heads, dim = self.heads, self.head_dim
        inner, shared = heads * dim, self.groups * self.state
        with jax.named_scope("granite/ssm/proj"):
            z, xbc, dt = jnp.split(
                dense(
                    2 * inner + 2 * shared + heads, "in_proj", self.dtype,
                    MIXER_IN,
                )(x), [inner, 2 * inner + 2 * shared], axis=-1,
            )
        with jax.named_scope("granite/ssm/conv"):
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
        with jax.named_scope("granite/ssm/core"):
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
        with jax.named_scope("granite/ssm/gated_norm"):
            # the gate FIRST, then one norm over all the channels
            y = GatedRMSNorm(self.eps, self.dtype, name="norm")(y, z)
        with jax.named_scope("granite/ssm/out"):
            return dense(self.hidden, "out_proj", self.dtype, MIXER_OUT)(y)


class GroupedAttention(nn.Module):
    """`heads` query heads over `kv_heads` key/value heads, causal, no
    positions and no norms, the logits times `scale`."""

    hidden: int
    heads: int
    kv_heads: int
    head_dim: int
    scale: float
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        batch, length, _ = x.shape
        heads, kv_heads, dim = self.heads, self.kv_heads, self.head_dim
        with jax.named_scope("granite/attn"):
            q, k, v = (
                dense(count * dim, name, self.dtype, MIXER_IN)(x).reshape(
                    batch, length, count, dim
                )
                for name, count in (
                    ("q", heads), ("k", kv_heads), ("v", kv_heads)
                )
            )
            out = causal_attention(q, k, v, scale=self.scale)
            return dense(self.hidden, "o", self.dtype, MIXER_OUT)(
                out.reshape(batch, length, heads * dim)
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
                c.mamba_groups, c.conv_kernel, c.eps, c.dtype, name="mamba",
            )(y)
        else:
            y = GroupedAttention(
                c.hidden, c.heads, c.kv_heads, c.hidden // c.heads,
                c.attention_multiplier, c.dtype, name="attn",
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
