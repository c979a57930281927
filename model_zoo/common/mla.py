"""Multi-head latent attention for TRAINING, as the zoo's decoders that
have it share it (`model_zoo/glm/glm_moe_lite.py`, `model_zoo/kimi/
kimi_linear.py`): keys and values are materialised per head from the
latent (no cache, no absorbed form), and the part of the key that is not
from the latent is ONE head shared by all.

    q = x Wq                      or, with `q_lora_rank`, RMSNorm(x Wqa) Wqb
    [c, k_pe] = split(x Wkva);  c = RMSNorm(c)
    [k_nope_h, v_h] = split((c Wkvb)_h);  k_h = [k_nope_h, k_pe]
    out = concat_h(softmax_causal(q_h k_h^T / sqrt(nope + rope)) v_h) Wo

With `rotate` the `rope` columns of q and of k_pe turn by position
(rotary at `rope_theta`); without, nothing in the layer knows a position.
"""

from __future__ import annotations

from typing import Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from elasticdl_tpu.ops.flash_attention import causal_attention
from model_zoo.common.decoder import (
    KV_UP,
    MIXER_IN,
    MIXER_OUT,
    Q_UP,
    RMSNorm,
    dense,
    rotary,
    sow_rope_one_pass,
)


class MLA(nn.Module):
    hidden: int
    heads: int
    q_lora_rank: Optional[int]
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    rope_theta: float
    eps: float
    dtype: jnp.dtype = jnp.float32
    rotate: bool = True
    trace_scope: str = "glm/mla"

    @nn.compact
    def __call__(self, x):
        batch, length, _ = x.shape
        heads, nope, rope = (
            self.heads, self.qk_nope_head_dim, self.qk_rope_head_dim
        )
        with jax.named_scope(f"{self.trace_scope}/proj"):
            if self.q_lora_rank is None:
                q = dense(heads * (nope + rope), "q", self.dtype, MIXER_IN)(x)
            else:
                cq = RMSNorm(self.eps, self.dtype, name="q_a_norm")(
                    dense(self.q_lora_rank, "q_a", self.dtype, MIXER_IN)(x)
                )
                q = dense(heads * (nope + rope), "q_b", self.dtype, Q_UP)(cq)
            q = q.reshape(batch, length, heads, nope + rope)
            ckv, k_rope = jnp.split(
                dense(self.kv_lora_rank + rope, "kv_a", self.dtype, MIXER_IN)(
                    x
                ), [self.kv_lora_rank], axis=-1,
            )
            ckv = RMSNorm(self.eps, self.dtype, name="kv_a_norm")(ckv)
            k_nope, v = jnp.split(
                dense(
                    heads * (nope + self.v_head_dim), "kv_b", self.dtype,
                    KV_UP,
                )(ckv).reshape(batch, length, heads, nope + self.v_head_dim),
                [nope], axis=-1,
            )
            # q's turn before k_pe's, in the order the GLM model always
            # traced them (its jaxpr is held: tests/test_glm_moe_lite.py)
            if self.rotate:
                q = rotary(q, self.rope_theta, first=nope)
            k_rope = k_rope[:, :, None, :]
            if self.rotate:
                sow_rope_one_pass(self, rope, q.shape, k_rope.shape, nope)
                k_rope = rotary(k_rope, self.rope_theta)
            k = jnp.concatenate(
                [k_nope, jnp.broadcast_to(
                    k_rope, (batch, length, heads, rope)
                )], axis=-1,
            )
        with jax.named_scope(f"{self.trace_scope}/core"):
            out = causal_attention(q, k, v, scale=(nope + rope) ** -0.5)
        with jax.named_scope(f"{self.trace_scope}/out"):
            return dense(self.hidden, "o", self.dtype, MIXER_OUT)(
                out.reshape(batch, length, heads * self.v_head_dim)
            )
