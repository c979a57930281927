"""What the zoo's decoder language models have in common (`model_zoo/glm/
glm_moe_lite.py`, `model_zoo/laguna/laguna.py`): RMSNorm and its gated form, rotary's turn,
the seeds of a decay (`a_log_init`, `dt_bias_init`), the bias-free dense
layer and SwiGLU, the routed block around
`layers/moe.py: RoutedExperts` with its shared expert, the cross-entropy
taken in blocks of tokens, the per-position losses against the ids
shifted, the blocks' rematerialisation (`remat_block`), and the zoo
functions a next-token model shares (`loss`, `optimizer`,
`eval_metrics_fn`, `param_sharding`)."""

from __future__ import annotations

from typing import Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import optax

from elasticdl_tpu.layers.embedding import embedding_param_sharding
from elasticdl_tpu.layers.moe import RoutedExperts, moe_param_sharding
from elasticdl_tpu.ops import flash_attention, kda, ssd

# What a rematerialised block keeps from its forward, by name: ONE policy
# for every decoder of the zoo.
SAVED_NAMES = flash_attention.SAVED_NAMES + kda.SAVED_NAMES + ssd.SAVED_NAMES

# Tokens whose logits exist at once in the cross-entropy.
CE_BLOCK = 2048


def rms_norm(x, scale, eps: float):
    """Statistics in float32 whatever `x` is; float32 out."""
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(
        jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps
    ) * scale


class RMSNorm(nn.Module):
    eps: float = 1e-5
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],))
        return rms_norm(x, scale, self.eps).astype(self.dtype)


class GatedRMSNorm(nn.Module):
    """rms_norm(y * silu(z)) * scale over the WHOLE last axis: the gate
    first, then one norm across every channel (a state-space mixer's
    output norm with one group)."""

    eps: float = 1e-5
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, y, z):
        scale = self.param("scale", nn.initializers.ones, (y.shape[-1],))
        gated = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
        return rms_norm(gated, scale, self.eps).astype(self.dtype)


def rotary_turn(x, inv_freq, factor: float = 1.0):
    """Turn (B, L, H, R) by position = index along L at the R / 2
    frequencies `inv_freq`, the HALVES pairing: column i turns with
    column i + R/2; cos and sin times `factor`.  float32 inside."""
    length = x.shape[1]
    angle = jnp.arange(length, dtype=jnp.float32)[:, None] * inv_freq[None]
    cos, sin = jnp.cos(angle)[None, :, None], jnp.sin(angle)[None, :, None]
    if factor != 1.0:
        cos, sin = cos * factor, sin * factor
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
    ).astype(x.dtype)


def rotary(x, theta: float):
    """Plain rotary embedding over the whole last axis of (B, L, H, R)
    (the row of the catalog does not say which pairing the checkpoint
    uses; with seeded weights the two differ by a permutation of
    columns)."""
    width = x.shape[-1]
    inv_freq = theta ** (-jnp.arange(0, width, 2, dtype=jnp.float32) / width)
    return rotary_turn(x, inv_freq)


def tap_init(key, shape, dtype=jnp.float32):
    """A depthwise kernel (K, d): uniform in +-1 / sqrt(K), K its fan-in."""
    bound = shape[0] ** -0.5
    return jax.random.uniform(key, shape, dtype, -bound, bound)


def a_log_init(key, shape, dtype=jnp.float32):
    """log A, A uniform in [1, 16] a head, as the family's code seeds it."""
    return jnp.log(jax.random.uniform(key, shape, dtype, 1.0, 16.0))


def dt_bias_init(key, shape, dtype=jnp.float32):
    """softplus^-1(dt), dt log-uniform in [1e-3, 1e-1], as the family's
    code seeds it: the decay is neither 0 nor 1 at the seeded weights."""
    low, high = np.log(1e-3), np.log(1e-1)
    dt = jnp.exp(jax.random.uniform(key, shape, dtype, low, high))
    return dt + jnp.log(-jnp.expm1(-dt))


def dense(features: int, name: str, dtype):
    return nn.Dense(features, use_bias=False, name=name, dtype=dtype)


class SwiGLU(nn.Module):
    """(silu(x Wg) * (x Wu)) Wd, gate and up in one kernel."""

    hidden: int
    width: int
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        gate, up = jnp.split(
            dense(2 * self.width, "gate_up", self.dtype)(x), 2, axis=-1
        )
        return dense(self.hidden, "down", self.dtype)(nn.silu(gate) * up)


class MoEFFN(nn.Module):
    """The shared expert, computed by every holder alike, plus this
    holder's part of the routed experts; `shared_experts` 0 builds no
    shared expert."""

    hidden: int
    num_experts: int
    top_k: int
    expert_width: int
    shared_experts: int
    held_experts: Optional[Tuple[int, int]]
    routed_scaling: float
    bias_update_rate: float
    dtype: jnp.dtype = jnp.float32
    trace_scope: str = "glm/moe"
    renorm_eps: float = 0.0

    @nn.compact
    def __call__(self, x):
        with jax.named_scope(self.trace_scope):
            routed = RoutedExperts(
                num_experts=self.num_experts, top_k=self.top_k,
                ffn_dim=self.expert_width, held_experts=self.held_experts,
                routed_scaling=self.routed_scaling,
                bias_update_rate=self.bias_update_rate, dtype=self.dtype,
                renorm_eps=self.renorm_eps, name="routed",
            )(x)
            if not self.shared_experts:
                with jax.named_scope("combine"):
                    return routed.astype(self.dtype)
            with jax.named_scope("shared"):
                shared = SwiGLU(
                    self.hidden, self.shared_experts * self.expert_width,
                    self.dtype, name="shared",
                )(x)
            with jax.named_scope("combine"):
                return (
                    routed + shared.astype(jnp.float32)
                ).astype(self.dtype)


def remat_block(block_cls):
    """`block_cls` rebuilt in the backward but for the attention core's
    output and log-sum-exp, which stay from the forward (bfloat16 out +
    float32 lse a layer: 134-268 MB in the cells): the remat rebuilds
    the projections, rotary and norms that make q, k and v, and the
    streaming forward kernel, the block's costliest operation, runs once
    a step (`ops/flash_attention.py: SAVED_NAMES`).  What the chunked
    scan of a linear-attention layer names joins the same policy
    (`ops/kda.py: SAVED_NAMES`, which says what it keeps and why).  A
    block with neither in it saves nothing."""
    return nn.remat(
        block_cls,
        policy=jax.checkpoint_policies.save_only_these_names(*SAVED_NAMES),
    )


def blocked_nll(h, head_kernel, targets, dtype, block: int = CE_BLOCK):
    """(tokens,) float32 negative log-likelihood of `targets` under
    softmax(h @ head_kernel), `block` tokens' logits at a time, each block
    rebuilt in the backward: nothing (tokens, vocab)-shaped is ever held.
    The kernel is cast inside the block so that its gradient sums over
    the blocks in its own float32."""
    tokens, hidden = h.shape
    if tokens % block:
        block = tokens

    @jax.checkpoint
    def one(args):
        h_block, t_block = args
        logits = jnp.dot(
            h_block.astype(dtype), head_kernel.astype(dtype),
            preferred_element_type=jnp.float32,
        )
        picked = jnp.take_along_axis(logits, t_block[:, None], axis=1)[:, 0]
        return jax.nn.logsumexp(logits, axis=-1) - picked

    return jax.lax.map(one, (
        h.reshape(tokens // block, block, hidden),
        targets.reshape(tokens // block, block),
    )).reshape(tokens)


def shifted_nll(h, head_kernel, ids, shift: int, dtype, scope: str):
    """(B, L - shift) per-position loss of h (B, L, d) against the ids
    `shift` places on; the positions with no such id are left out."""
    batch, length = ids.shape
    targets = jnp.roll(ids, -shift, axis=1)
    with jax.named_scope(scope):
        out = blocked_nll(
            h.reshape(batch * length, h.shape[-1]), head_kernel,
            targets.reshape(-1), dtype,
        ).reshape(batch, length)
    return out[:, :length - shift]


# ---- the zoo functions of a next-token model ------------------------------


def loss(labels, predictions):
    """`predictions` are the model's per-position negative
    log-likelihoods of the next token; the record's label byte is not
    used."""
    return predictions.mean()


def optimizer(lr: float = 1e-4):
    return optax.adam(lr)


def eval_metrics_fn():
    return {
        "perplexity": lambda labels, predictions: float(
            np.exp(np.mean(predictions))
        ),
    }


def param_sharding(path, value):
    """Expert stacks over `expert`, the token embedding over `model`;
    everything else replicated."""
    spec = moe_param_sharding(path, value)
    if spec is not None:
        return spec
    return embedding_param_sharding(path, value)
