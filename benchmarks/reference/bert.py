"""BERT encoder (arXiv:1810.04805) with the zoo's classification head, as
plain `jax.numpy` in float32 at the highest matmul precision: forward,
loss and gradients, with no flash kernel, no shard_map and no bf16.

Post-LayerNorm blocks as published: x = LN(x + Attn(x)); x = LN(x +
MLP(x)), full bidirectional softmax attention scaled by 1/sqrt(head),
learned position embeddings, LayerNorm over the summed embeddings.
Departures of the ZOO model from the published network, which this
reference follows because it is the model the configuration runs (each is
in the configuration file's `assumed`): no token-type embedding; tanh
GELU; LayerNorm epsilon 1e-6 (flax's default; the checkpoint says 1e-12);
the head is max-pool over positions and one Dense(2) in place of the
pooler and the pre-training heads.  Departure of the SYSTEM from this
reference, which the tolerances allow for: matmuls, LayerNorms and the
attention kernel compute in bfloat16 with f32 parameters.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks import trees

# The loss of a 12-layer bf16 network against f32 agreed within 1.2e-3 ..
# 3.6e-3 on 8 examples in fourteen chip runs (PERF.md section 6, PR 23); a
# missing layer, a wrong mask or a causal kernel moves it by O(0.1), and
# matmuls in an 8-bit type by a few 1e-2.
LOSS_ATOL = 1e-2
# L2 error allowed on a gradient leaf, RELATIVE TO the leaf's reference
# norm; the first pattern that matches the leaf's name holds.  This
# reference has no twin in bfloat16 (`reference/deepfm.py`: `STATED_RATIO`),
# so `classifier/bias`, two numbers under random labels, can cross zero
# and read over its share on a sound step: to settle before a BERT cell
# enters the manifest (PERF.md section 7).
#
# `classifier/*` takes its gradient from the pooled features and the
# logits alone, so where bf16 and f32 pool another position it does not
# care: it read 2e-3 .. 8e-3 in those runs, and holds the whole FORWARD
# pass (every layer's matmuls, LayerNorms and the attention kernel) to
# bf16: features carrying 8-bit noise of a few percent fail 3e-2.
#
# Every other leaf lies behind the head's max-pool over 512 positions.
# bf16 has 256 steps to a binade, so the top positions of a feature tie
# and jax splits the gradient among them, where f32 gives it to one: that
# feature's whole gradient is routed through other tokens.  A gradient
# program compiled without XLA's excess precision read 0.18 .. 0.34 on
# the worst leaf, 0.18 .. 0.21 on the median leaf.  (The zoo's train step
# as it stands reads 1.00 on these leaves, with 3% of the reference's
# norm, and fails: PERF.md section 6, finding 2.)  So 0.5 holds
# the BACKWARD pass only to this: every leaf live and correlated (a dead
# leaf reads exactly 1.0, a wrong kernel, mask or layer count O(1) and
# above); it cannot tell bf16 from 8 bits there.  A head that pools in
# f32 would let this bound fall to a few percent (PERF.md section 7).
LEAF_REL_L2 = (("^classifier/", 3e-2), ("", 5e-1))
# All leaves as one vector: its cosine against the reference's.
GRAD_COSINE_MIN = 0.9
# Examples a reference program takes at once (f32 attention weights of 8
# x 12 x 512 x 512 are 100 MB a layer); a batch is a mean over chunks.
CHUNK = 8


def layer_norm(x, p, eps=1e-6):
    mean = x.mean(axis=-1, keepdims=True)
    var = ((x - mean) ** 2).mean(axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]


def dense(x, p):
    return x @ p["kernel"] + p["bias"]


def gelu_tanh(x):
    return 0.5 * x * (
        1.0 + jnp.tanh(0.7978845608028654 * (x + 0.044715 * x ** 3))
    )


def attention(x, p, heads: int):
    batch, length, hidden = x.shape
    head = hidden // heads
    q, k, v = jnp.split(dense(x, p["qkv"]), 3, axis=-1)
    q, k, v = (
        t.reshape(batch, length, heads, head).transpose(0, 2, 1, 3)
        for t in (q, k, v)
    )
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) / jnp.sqrt(
        jnp.float32(head)
    )
    scores = scores - scores.max(axis=-1, keepdims=True)
    weights = jnp.exp(scores)
    weights = weights / weights.sum(axis=-1, keepdims=True)
    out = jnp.einsum("bhqk,bhkd->bhqd", weights, v)
    out = out.transpose(0, 2, 1, 3).reshape(batch, length, hidden)
    return dense(out, p["out"])


def forward(params, input_ids, config):
    length = input_ids.shape[1]
    x = (
        params["token_embedding"]["embedding"][input_ids]
        + params["position_embedding"][None, :length]
    )
    x = layer_norm(x, params["LayerNorm_0"])
    for i in range(config["num_hidden_layers"]):
        p = params[f"layer_{i}"]
        x = layer_norm(
            x + attention(x, p["attention"], config["num_attention_heads"]),
            p["LayerNorm_0"],
        )
        y = dense(gelu_tanh(dense(x, p["Dense_0"])), p["Dense_1"])
        x = layer_norm(x + y, p["LayerNorm_1"])
    return dense(x.max(axis=1), params["classifier"])


def cross_entropy(logits, labels):
    logp = logits - jax.scipy.special.logsumexp(
        logits, axis=-1, keepdims=True
    )
    return -jnp.mean(
        jnp.take_along_axis(logp, labels[:, None].astype(jnp.int32), axis=1)
    )


def cut(tree, features, config) -> dict:
    """{leaf name: array} of a parameter-shaped tree (parameters, Adam
    moments): every leaf whole, since a batch touches all of them."""
    return trees.flat(tree)


def loss_and_grads(params: dict, features, labels, config):
    """(loss, {leaf name: gradient}) of the batch's mean loss, from the
    flat parameters `cut` gives.  `CHUNK` examples at a time, summed."""
    params = trees.nested(
        {k: jnp.asarray(v, jnp.float32) for k, v in params.items()}
    )
    ids = jnp.asarray(features["input_ids"])
    labels = jnp.asarray(labels)
    rows = ids.shape[0]
    chunk = min(CHUNK, rows)
    if rows % chunk:
        raise ValueError(f"{rows} examples are not whole chunks of {chunk}")

    @jax.jit
    def chunk_loss_and_grads(params, ids, labels):
        return jax.value_and_grad(
            lambda p: cross_entropy(forward(p, ids, config), labels)
        )(params)

    loss, grads = 0.0, None
    with jax.default_matmul_precision("highest"):
        for start in range(0, rows, chunk):
            part, part_grads = chunk_loss_and_grads(
                params, ids[start:start + chunk],
                labels[start:start + chunk],
            )
            loss = loss + part
            grads = part_grads if grads is None else jax.tree.map(
                jnp.add, grads, part_grads
            )
    scale = chunk / rows
    return loss * scale, {k: v * scale for k, v in trees.flat(grads).items()}
