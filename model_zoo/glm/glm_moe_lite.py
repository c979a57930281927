"""GLM-4.7-Flash (`model_type: glm4_moe_lite`) as a causal language model
on the train path: multi-head latent attention, one leading dense SwiGLU
layer, then layers of 64 sigmoid-routed experts (top-4, `noaux_tc`
selection bias, renormalised weights times 1.8) beside one shared expert,
and one multi-token-prediction module (structure from DeepSeek-V3,
arXiv:2412.19437 section 2.2, which this family follows).

    h = x + MLA(RMSNorm(x));  y = h + FFN(RMSNorm(h))
    L = CE(head(RMSNorm(h_L)), x_{t+1}) + lambda * CE(head(norm(MTP)), x_{t+2})

The layer equations are written out in `benchmarks/reference/
glm_moe_lite.py`, the plain float32 reference this model is held to leaf
by leaf (tests/decoder_cases.py).  The attention layer is
`model_zoo/common/mla.py: MLA` (a low-rank query with its norm, rotary on
the 64-column part, scopes `glm/mla/*`), shared with the zoo's other
latent-attention decoder.

What makes it a citizen of THIS system rather than a port:

- the expert layer is told which experts it holds (`held_experts`),
  routes over all of them and computes its own experts' part
  (`layers/moe.py: RoutedExperts`): one chip's share of an
  expert-parallel deployment, and the layer expert parallelism over the
  mesh `expert` axis needs anyway;
- targets are the input ids shifted, taken INSIDE the model, and the
  model hands back per-position negative log-likelihoods: the logits
  (16,384 tokens x 19,360 rows in float32 are 1.27 GB, twice with MTP)
  never leave a cross-entropy taken in blocks of tokens;
- every block rematerialises in the backward (`remat`) but for the
  attention core's output and log-sum-exp, which stay from the forward
  (`decoder.remat_block`: 169 MB a layer at the cell's shape buys the
  streaming forward kernel, a block's costliest operation, run once a
  step and not twice); the MTP loss is a sown auxiliary objective, and
  router load and the two losses ride in STEP_METRICS to the worker's
  one fetch a task.

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
from elasticdl_tpu.layers.step_metrics import AUX_LOSS, sow_step_metric
from model_zoo.bert.bert_finetune import feed, feed_bulk  # noqa: F401
from model_zoo.common.decoder import (  # noqa: F401
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
    shifted_nll,
)
from model_zoo.common.mla import MLA


class Block(nn.Module):
    """One pre-norm decoder block; `moe` picks its feed-forward."""

    config: "GLMConfig"
    moe: bool

    @nn.compact
    def __call__(self, x):
        c = self.config
        # norms and residual sums are `glm/norm`: with the scopes of MLA
        # and the feed-forward they tile the block
        # (profiler.DEVICE_SCOPES)
        with jax.named_scope("glm/norm"):
            y = RMSNorm(c.eps, c.dtype, name="attn_norm")(x)
        y = MLA(
            c.hidden, c.heads, c.q_lora_rank, c.kv_lora_rank,
            c.qk_nope_head_dim, c.qk_rope_head_dim, c.v_head_dim,
            c.rope_theta, c.eps, c.dtype, name="mla",
        )(y)
        with jax.named_scope("glm/norm"):
            x = x + y
            y = RMSNorm(c.eps, c.dtype, name="ffn_norm")(x)
        if self.moe:
            y = MoEFFN(
                c.hidden, c.num_experts, c.top_k, c.expert_width,
                c.shared_experts, c.held_experts, c.routed_scaling,
                c.bias_update_rate, c.dtype, name="moe",
            )(y)
        else:
            with jax.named_scope("glm/dense_ffn"):
                y = SwiGLU(c.hidden, c.dense_width, c.dtype, name="mlp")(y)
        with jax.named_scope("glm/norm"):
            return x + y


@dataclasses.dataclass(frozen=True)
class GLMConfig:
    """Every size of the model (`custom_model` documents them)."""

    hidden: int
    num_layers: int
    dense_layers: int
    heads: int
    q_lora_rank: int
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
    bias_update_rate: float
    vocab_size: int
    mtp_layers: int
    mtp_loss_weight: float
    rope_theta: float
    eps: float
    dtype: Any
    remat: bool


class GLMMoELite(nn.Module):
    config: GLMConfig

    @nn.compact
    def __call__(self, features, room=None):
        c = self.config
        ids = features["input_ids"].astype(jnp.int32)        # (B, L)
        embed = DistributedEmbedding(
            c.vocab_size, c.hidden, hash_input=False, name="token_embedding"
        )
        with jax.named_scope("glm/embed"):
            x = embed(ids).astype(c.dtype)
        # one entry a layer, then the MTP module's block where there is one
        routed = [i >= c.dense_layers for i in range(c.num_layers)] + (
            [True] * c.mtp_layers
        )
        classes = remat_blocks(
            Block, c, routed, x, room, c.vocab_size,
            routed_walks(x, routed, c.top_k, c.expert_width),
        ) if c.remat else [Block] * len(routed)
        for i in range(c.num_layers):
            x = classes[i](c, routed[i], name=f"layer_{i}")(x)
        head = self.param(
            "lm_head_kernel", nn.initializers.lecun_normal(),
            (c.hidden, c.vocab_size),
        )

        def nll(h, shift):
            return shifted_nll(h, head, ids, shift, c.dtype, "glm/head_ce")

        with jax.named_scope("glm/norm"):
            final = RMSNorm(c.eps, c.dtype, name="final_norm")(x)
        main = nll(final, 1)
        sow_step_metric(self, "main_loss", main.mean())
        if c.mtp_layers:
            with jax.named_scope("glm/mtp"):
                # position t joins h_t with the embedding of x_{t+1} and
                # predicts x_{t+2}; the last position's partner wraps
                # round, is causal-masked from every other and has no
                # target
                joined = jnp.concatenate([
                    RMSNorm(c.eps, c.dtype, name="mtp_h_norm")(x),
                    RMSNorm(c.eps, c.dtype, name="mtp_e_norm")(
                        embed(jnp.roll(ids, -1, axis=1))
                    ),
                ], axis=-1)
                y = dense(c.hidden, "mtp_eh_proj", c.dtype)(joined)
                y = classes[-1](c, True, name="mtp_block")(y)
                y = RMSNorm(c.eps, c.dtype, name="mtp_final_norm")(y)
            mtp_loss = nll(y, 2).mean()
            sow_step_metric(self, "mtp_loss", mtp_loss)
            self.sow(AUX_LOSS, "mtp_loss", c.mtp_loss_weight * mtp_loss)
        return main


def custom_model(
    hidden: int = 2048, num_layers: int = 5, dense_layers: int = 1,
    heads: int = 20, q_lora_rank: int = 768, kv_lora_rank: int = 512,
    qk_nope_head_dim: int = 192, qk_rope_head_dim: int = 64,
    v_head_dim: int = 256, dense_width: int = 10240,
    expert_width: int = 1536, num_experts: int = 64, top_k: int = 4,
    shared_experts: int = 1, held_experts=None,
    routed_scaling: float = 1.8, bias_update_rate: float = 0.0,
    vocab_size: int = 19360, mtp_layers: int = 1,
    mtp_loss_weight: float = 0.3, rope_theta: float = 1e6,
    eps: float = 1e-5, bf16: bool = False, remat: bool = False,
):
    """`held_experts` is (first, count) of the routed experts whose
    weights live in this process; None holds all `num_experts`."""
    if mtp_layers not in (0, 1):
        raise ValueError("this family has one MTP module or none")
    return GLMMoELite(GLMConfig(
        hidden=hidden, num_layers=num_layers, dense_layers=dense_layers,
        heads=heads, q_lora_rank=q_lora_rank, kv_lora_rank=kv_lora_rank,
        qk_nope_head_dim=qk_nope_head_dim,
        qk_rope_head_dim=qk_rope_head_dim, v_head_dim=v_head_dim,
        dense_width=dense_width, expert_width=expert_width,
        num_experts=num_experts, top_k=top_k, shared_experts=shared_experts,
        held_experts=None if held_experts is None else tuple(held_experts),
        routed_scaling=routed_scaling, bias_update_rate=bias_update_rate,
        vocab_size=vocab_size, mtp_layers=mtp_layers,
        mtp_loss_weight=mtp_loss_weight, rope_theta=rope_theta, eps=eps,
        dtype=jnp.bfloat16 if bf16 else jnp.float32, remat=remat,
    ))
