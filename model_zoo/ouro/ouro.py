"""Ouro-2.6B (`model_type: ouro`) as a causal language model on the train
path: ONE stack of layers that the hidden state passes through `trips`
times over ONE set of weights, a block normed on both sides of each
sublayer, and a loss that reads the head after every trip and weighs the
trips by a learned exit distribution with an entropy term.

    block_l(x):  a = Norm1_l(x);  q, k, v = a Wq_l, a Wk_l, a Wv_l
                 q, k turned by rotary over the whole head (theta 1e6)
                 o = softmax(q k^T 128^-1/2 + causal mask) v
                 h = x + Norm2_l(o Wo_l)
                 m = Norm3_l(h)
                 y = h + Norm4_l((silu(m Wg_l) * (m Wu_l)) Wd_l)
    h_0 = embed(ids)
    for t = 1..R:  h_t = Norm_f(block_N(... block_1(h_{t-1}) ...))
                   g_t = h_t w_g + b_g          one float32 logit a token
                   nll_t = CE(h_t W_head, ids shifted by one)
    lambda_t = sigmoid(g_t);  S_0 = 1
    p_t = lambda_t S_{t-1},  S_t = S_{t-1} (1 - lambda_t)  for t < R
    p_R = S_{R-1}                               sum_t p_t = 1 a position
    H = - sum_t p_t ln p_t
    L = mean over positions of (sum_t p_t nll_t - beta H)

The SAME blocks and the SAME final norm every trip, and the NORMED state
is what the next trip reads.  The trips are one loop in the lowered
program (`nn.scan` over the rematerialised blocks, the parameters
broadcast): one set of leaves, `layer_0` .. `layer_{N-1}` with no trip in
any path, a leaf's gradient the sum over the trips, and a step whose
trace and compile are those of an N-layer model.  The four head passes
stand OUTSIDE the loop as one blocked cross-entropy over the four normed
states' rows (`decoder.weighed_nll`, the exit distribution the rows'
weights), so the head's float32 gradient sums over all of its blocks in one
place and no other loop carries a (hidden, vocabulary) array.

`predictions` are the per-position `sum_t p_t nll_t`; the entropy term
reaches the objective through `step_metrics.AUX_LOSS` as `-beta * mean H`,
so `decoder.loss` and the Trainer stay as they are, and the eval
`perplexity` is exp of that WEIGHTED sum's mean, not of any one trip's
loss.  With `trips` 1 there is no gate and no entropy: a plain decoder.

The equations are written out in `benchmarks/reference/ouro.py`, the plain
float32 reference this model is held to leaf by leaf (tests/decoder_cases.py).
What it shares with the zoo's other decoders (norms, rotary's turn,
grouped attention, SwiGLU, the blocked cross-entropy, the blocks' remat
and its plan over `trips`) is `model_zoo/common/decoder.py`.

What the loop leaves for the step's metrics comes out of it with a trip
axis and is published a trip (`TripGauges`: `trip_2/trip_loss`); a gauge
a BLOCK sows inside the loop does not leave it (the loop carries no such
collection; `layers/step_metrics.py: sow_step_metric`), so what the blocks'
attention would sow of its turn (`decoder.sow_rope_one_pass`: static, the
same shapes every block and trip) the model sows itself.

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
from elasticdl_tpu.layers import step_metrics
from elasticdl_tpu.layers.embedding import DistributedEmbedding
from elasticdl_tpu.layers.step_metrics import AUX_LOSS, sow_step_metric
from model_zoo.bert.bert_finetune import feed, feed_bulk  # noqa: F401
from model_zoo.common.decoder import (  # noqa: F401
    FFN_OUT,
    GroupedAttention,
    RMSNorm,
    Rope,
    SwiGLU,
    eval_metrics_fn,
    loss,
    optimizer,
    param_sharding,
    plain_rope,
    remat_blocks,
    shifted_nll,
    sow_rope_one_pass,
    weighed_nll,
)

# What the loop leaves a TRIP (`TripGauges`, the label the trip's path,
# `trip_1` .. `trip_R`) and a step.
step_metrics.declare(
    "trip_loss",
    metrics_lib.default_registry().gauge(
        "worker_trip_loss_nats",
        "mean next-token loss of the head read after one trip through a "
        "looped stack, last step of the task (the loop's point is that it "
        "falls with the trip once trained; at seeded weights it need not)",
        labelnames=("trip",),
    ),
)
step_metrics.declare(
    "trip_exit_mass",
    metrics_lib.default_registry().gauge(
        "worker_trip_exit_mass_ratio",
        "mean over positions of the exit distribution's mass on one trip "
        "of a looped stack, last step of the task (the trips' sum to 1)",
        labelnames=("trip",),
    ),
)
step_metrics.declare(
    "trip_exit_entropy_nats",
    metrics_lib.default_registry().gauge(
        "worker_trip_exit_entropy_nats",
        "mean over positions of the exit distribution's entropy, last "
        "step of the task: at most ln(trips); near 0 the gate has "
        "collapsed onto one trip and the other head passes train nothing",
    ),
)

# The one kind of layer the published `layer_types` has.
FULL_ATTENTION = "full_attention"


@dataclasses.dataclass(frozen=True)
class OuroConfig:
    """Every size of the model (`custom_model` documents them).
    `layers` is one kind a layer HELD; the stack is applied `trips`
    times."""

    hidden: int
    layers: Tuple[str, ...]
    heads: int
    kv_heads: int
    head_dim: int
    rope: Rope
    dense_width: int
    trips: int
    exit_beta: float
    vocab_size: int
    eps: float
    dtype: Any
    remat: bool


class Block(nn.Module):
    """One decoder block normed on BOTH sides of each sublayer, the second
    norm inside the residual branch; `kind` is the layer's published type
    (one exists)."""

    config: OuroConfig
    kind: str = FULL_ATTENTION

    @nn.compact
    def __call__(self, x):
        c = self.config

        def norm(name):
            return RMSNorm(c.eps, c.dtype, name=name)

        # norms and residual sums are `ouro/norm`: with the scopes of
        # attention and the MLP they tile the block (profiler.DEVICE_SCOPES)
        with jax.named_scope("ouro/norm"):
            y = norm("input_layernorm")(x)
        y = GroupedAttention(
            c.hidden, c.heads, c.kv_heads, c.head_dim, c.head_dim ** -0.5,
            c.dtype, "ouro/attn", rope=c.rope, name="attn",
        )(y)
        with jax.named_scope("ouro/norm"):
            h = x + norm("input_layernorm_2")(y)
            y = norm("post_attention_layernorm")(h)
        with jax.named_scope("ouro/dense_ffn"):
            # `down`'s output is Norm4's input, which its backward reads
            y = SwiGLU(
                c.hidden, c.dense_width, c.dtype, FFN_OUT, name="mlp"
            )(y)
        with jax.named_scope("ouro/norm"):
            return h + norm("post_attention_layernorm_2")(y)


def exit_distribution(logits):
    """log p (R, ...) from the first R - 1 trips' gate logits (R - 1, ...)
    in float32: p_t = sigmoid(g_t) prod_{s<t} (1 - sigmoid(g_s)) for t <
    R, and the last trip takes what survives."""
    stay = jnp.cumsum(jax.nn.log_sigmoid(-logits), axis=0)   # ln S_t
    before = jnp.concatenate([jnp.zeros_like(stay[:1]), stay[:-1]])
    return jnp.concatenate(
        [jax.nn.log_sigmoid(logits) + before, stay[-1:]]
    )


def trip_body(config, classes, norm_cls, h):
    """One pass of the state through the stack and the final norm ->
    (what the next trip reads, what the head and the gate read): the SAME
    normed state.  The final norm is rebuilt in the backward like the
    blocks (`norm_cls`): left as it is, the loop stacks its float32 input
    and statistics a trip."""
    c = config
    for i, (kind, block_cls) in enumerate(zip(c.layers, classes)):
        h = block_cls(c, kind, name=f"layer_{i}")(h)
    with jax.named_scope("ouro/norm"):
        h = norm_cls(c.eps, c.dtype, name="final_norm")(h)
    return h, h


class TripGauges(nn.Module):
    """What one trip leaves for the step's metrics, sown under the trip's
    own path (`trip_2/trip_loss`) so that each trip keeps its value."""

    @nn.compact
    def __call__(self, trip_loss, exit_mass):
        sow_step_metric(self, "trip_loss", trip_loss)
        sow_step_metric(self, "trip_exit_mass", exit_mass)


class Ouro(nn.Module):
    config: OuroConfig

    @nn.compact
    def __call__(self, features, room=None):
        c = self.config
        ids = features["input_ids"].astype(jnp.int32)        # (B, L)
        with jax.named_scope("ouro/embed"):
            x = DistributedEmbedding(
                c.vocab_size, c.hidden, hash_input=False,
                name="token_embedding",
            )(ids).astype(c.dtype)
        classes = remat_blocks(
            Block, c, c.layers, x, room, c.vocab_size, trips=c.trips
        ) if c.remat else [Block] * len(c.layers)

        norm_cls = nn.remat(RMSNorm) if c.remat else RMSNorm

        def trip(model, h, _):
            """The body of the trips' loop.  The modules are built inside
            it, over broadcast parameters, so every trip reads the same
            leaves."""
            del model
            return trip_body(c, classes, norm_cls, h)

        # `ouro/trips` is the loop's own work: the stacks a trip writes
        # what it keeps to and the backward reads it from, their layout
        # copies, a weight's gradient summed over the trips
        with jax.named_scope("ouro/trips"):
            _, states = nn.scan(
                trip, variable_broadcast="params",
                split_rngs={"params": False}, length=c.trips,
            )(self, x, None)                                 # (R, B, L, d)
        sow_rope_one_pass(self, c.rope.columns, *(
            (*ids.shape, heads, c.head_dim) for heads in (c.heads, c.kv_heads)
        ))
        head = self.param(
            "lm_head_kernel", nn.initializers.lecun_normal(),
            (c.hidden, c.vocab_size),
        )
        if c.trips == 1:
            return shifted_nll(
                states, head, ids, 1, c.dtype, "ouro/head_ce"
            )[0]
        positions = ids.shape[1] - 1
        with jax.named_scope("ouro/exit"):
            # the last trip has no gate to read: it takes what survives
            gate = nn.Dense(
                1, dtype=jnp.float32, precision="highest", name="exit_gate"
            )
            logits = gate(
                states[:-1, :, :positions].astype(jnp.float32)
            )[..., 0]
            log_p = exit_distribution(logits)                # (R, B, L - 1)
            p = jnp.exp(log_p)
            entropy = -jnp.sum(p * log_p, axis=0).mean()
        # the exit distribution weighs the losses INSIDE the head's pass
        # (the gate reads the states, not the losses), which so knows every
        # row's cotangent as it makes the row's logits
        weighed, nll = weighed_nll(
            states, head, ids, 1, c.dtype, "ouro/head_ce", p
        )
        weighed = jnp.sum(weighed, axis=0)
        for t in range(c.trips):
            TripGauges(name=f"trip_{t + 1}")(nll[t].mean(), p[t].mean())
        sow_step_metric(self, "trip_exit_entropy_nats", entropy)
        self.sow(AUX_LOSS, "exit_entropy", -c.exit_beta * entropy)
        return weighed


def custom_model(
    hidden: int = 2048, num_layers: int = 48, layers=None, heads: int = 16,
    kv_heads: int = 16, head_dim: int = 128, dense_width: int = 5632,
    trips: int = 4, exit_beta: float = 0.05, rope_theta: float = 1e6,
    vocab_size: int = 49152, eps: float = 1e-6, bf16: bool = False,
    remat: bool = False,
):
    """`num_layers` is the PUBLISHED depth; `layers` lists the published
    0-BASED indices that are built, in order (None builds all): every
    layer is the same kind, so the list says how many and which, and the
    stack of them is applied `trips` times (`total_ut_steps`).
    `exit_beta` weighs the exit distribution's entropy in the
    objective."""
    built = tuple(range(num_layers)) if layers is None else tuple(
        int(i) for i in layers
    )
    if not built or min(built) < 0 or max(built) >= num_layers:
        raise ValueError(f"layers {built} of {num_layers} published")
    if heads % kv_heads:
        raise ValueError("K/V heads divide the query heads")
    if trips < 1:
        raise ValueError("a stack is applied at least once")
    return Ouro(OuroConfig(
        hidden=hidden, layers=(FULL_ATTENTION,) * len(built), heads=heads,
        kv_heads=kv_heads, head_dim=head_dim,
        rope=plain_rope(head_dim, rope_theta), dense_width=dense_width,
        trips=int(trips), exit_beta=float(exit_beta), vocab_size=vocab_size,
        eps=eps, dtype=jnp.bfloat16 if bf16 else jnp.float32, remat=remat,
    ))
