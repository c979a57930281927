"""Laguna-XS.2 (`laguna`) on the train path as plain `jax.numpy` in float32
at the highest matmul precision: forward, loss and gradients, with no
kernel, no sort, no remat and no bfloat16.

The equations, from the catalog row's `config` (hidden d, RMSNorm eps
1e-6, no biases, SiLU; `assumed` items are in the configuration file):

    block l  h = x + Attn_l(RMSNorm(x));  y = h + FFN_l(RMSNorm(h))
    Attn_l   H_l = num_attention_heads_per_layer[l] query heads over 8
             K/V heads of width D = 128: q = x Wq, k = x Wk, v = x Wv;
             query head h reads K/V head h // (H_l / 8)
             rotary (halves pairing) on q and k: a `full_attention`
             layer turns the first D / 2 columns at YaRN's frequencies
             with cos and sin times `attention_factor`, a
             `sliding_attention` layer all D columns at theta 10,000
             a_h = softmax_mask(q_h k^T / sqrt(D)) v, mask: s <= t, and in
             a `sliding_attention` layer also t - s < sliding_window
             g = sigmoid(x Wg), one number a head
             o = concat_h(g_h a_h) Wo
    dense    (silu(x Wg) * (x Wu)) Wd                       (layer 0)
    MoE      s = sigmoid(x Wr) over ALL experts, float32
             S = top_k(s)
             w_i = scaling * s_i / sum_{j in S} s_j        (all of S)
             FFN(x) = SwiGLU_shared(x) + sum_{i in S, i HELD} w_i SwiGLU_i(x)
    loss     CE(head(RMSNorm(h_L)), x_{t+1}), a mean over the positions
             that have a target

The cut is the configuration's: the held experts (`held_experts`), the
sliced vocabulary, the depth (the first `num_hidden_layers` entries of the
published per-layer lists).  What absent experts would add is left out
here as in the program.  Each held expert is applied to ALL tokens and
masked by its weight.

Departures of this file from a one-function reference, each for memory
beside the live train state (8.3 GB stays on the chip during the check):
the layers are walked with `jax.vjp`, ONE layer's float32 parameters and
gradient on the device at a time; the batch goes a sequence at a time;
attention is a dense masked softmax over ALL the sequence's keys, a K/V
head's group of query heads at a time and `QUERY_TILE` queries of it at a
time, rebuilt in the backward (64 heads x 8,192 x 8,192 float32 logits
are 17 GB whole; a tile of a group of 8 is 134 MB); the held experts go
one at a time (`lax.scan`); the head's logits go a block of tokens at a
time.  None of them changes a number past float32 summation order.

`tower` is the twin (`loss_and_grads(..., tower="bfloat16")`): the same
network with what the program rounds when the configuration states
bfloat16 rounded the same way (flax's `Dense(dtype=bfloat16)`: inputs,
kernel and output of every matmul, the backward signal with them; the
residual stream, the turned queries and keys, attention's operands and
probabilities, the gate and the gated output, the experts' grouped
products and the head's operands), and the router, every norm's
statistics, the softmax and the loss in float32 as the program keeps
them.  `tower="float8_e4m3fn"` is the check's control, the type below.
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import trees
from benchmarks.reference.deepfm import rounded_to
from benchmarks.reference.glm_moe_lite import (
    _add,
    _device,
    _embed,
    _embed_grads,
    _host,
    blocked_nll,
    matmul,
    rms_norm,
    swiglu,
)

# The loss is one mean over 16,382 positions of terms whose logits carry
# bfloat16's roundings, independent across positions: the MEAN moves far
# less than a term.  It is 9.9 at the seeded weights (ln 12,544 + 0.5) and
# 0.26-0.68 where the check lands: the job's 16 sequences are one pool
# that every task permutes, and the model has half memorised it by then
# (PERF.md section 7 (10)).  On the chip at the cell's size (PERF.md
# section 6, PR 33; eleven runs of the step, eight at step 57 and three
# traced ones at step 65; the float8 control at both landings): the job's
# step 6.1e-4 .. 1.1e-3 from this reference, the bfloat16 twin 4.4e-4 and
# 8.0e-4, the float8 control 0.19 and 0.29.  A dropped layer, a wrong
# shift, an un-renormalised router weight or a window off by a tile move
# it by O(0.1) and more.
LOSS_ATOL = 1e-2
# L2 error allowed on a gradient leaf (first matching pattern), RELATIVE
# TO the leaf's reference norm.  The cell's batch is 2 sequences and
# `drivers/train.py: sampling_noise` wants a batch of whole eighths, so
# the check runs WITHOUT the twin-held rule (no `STATED_RATIO` name below;
# `TWIN_RATIO` says why) and every leaf is held to these shares of its own
# norm.  Each limit stands between two readings on the chip at the cell's
# size (the runs above: the step's worst leaf of the class over its runs;
# the float8 control on the same state and batch, its best), near their
# geometric mean; the bfloat16 twin reads within 10% of the step on every
# class, so the step's error IS the stated type's.  They are larger than
# the GLM cell's because the gradient here is what is left of a loss the
# model has mostly memorised, while the stated type's roundings stay.
#
# expert stacks: a top-k flip between types moves a token to another
#   expert, so rows come and go from an expert's gradient: the step
#   0.150 .. 0.155 (the twin 0.136 and 0.141), the control 0.76 and 1.03.
# router: its gradient comes through the renormalised weights of the
#   chosen eight alone, and a flipped slot changes which eight: the step
#   0.147 .. 0.154 at step 57, 0.164 .. 0.178 at step 65 (the twin 0.142
#   and 0.155), the control 0.58 at step 57 and 0.85 at step 65.
# every other leaf: the step 0.071 .. 0.080 (the twin 0.067 and 0.075;
#   worst `layer_0/attn/k/kernel`), the control 1.26 and 1.33.
LEAF_REL_L2 = (
    ("expert_w_", 3.5e-1),
    ("router_kernel$", 3.5e-1),
    ("", 3e-1),
)
# All leaves as one vector against the reference's: the step reads 1 -
# cosine 1.30e-3 .. 1.55e-3 at step 57, 1.66e-3 .. 1.82e-3 at step 65 (the twin
# 1.20e-3 and 1.37e-3), the control 2.2e-1.
GRAD_COSINE_MIN = 0.98
# How many times the bfloat16 twin's error a step's may be, leaf by leaf
# and on the angle (drivers/train.py: `leaf_shares`, `cosine_floor`).  The
# twin is here (`tower=`) and `part_grads` is here, and the tests hold
# both to `check_gradient` at a batch of 8.  It is NOT named
# `STATED_RATIO`, for `reference/glm_moe_lite.py`'s reason: the driver
# would then ask `sampling_noise` to split the cell's batch of 2 sequences
# into 8 equal parts and raise (PERF.md section 7).
TWIN_RATIO = 3.0
# Sequences a reference program takes at once, queries a tile of the
# attention's logits.
CHUNK = 1
QUERY_TILE = 512

FULL, WINDOW = "full_attention", "sliding_attention"


class Rope(NamedTuple):
    columns: int
    inv_freq: Tuple[float, ...]
    factor: float


class Sizes(NamedTuple):
    """What the programs below are compiled for (hashable)."""

    kv_heads: int
    head_dim: int
    window: int
    full_rope: Rope
    window_rope: Rope
    eps: float
    top_k: int
    scaling: float
    held_first: int
    held_count: int
    tower: Optional[str]


class Layer(NamedTuple):
    windowed: bool
    heads: int
    routed: bool


def yarn_inv_freq(columns, theta, factor, original, beta_fast, beta_slow):
    """YaRN (arXiv:2309.00071, the form the published configs of this
    family are read by): pair i of `columns` / 2 turns theta^(-2i /
    columns) a position; the pairs that turn more than `beta_fast` times
    over the `original` context keep that, those that turn less than
    `beta_slow` times turn `factor` times slower, a linear ramp over the
    pairs between (bounds rounded outwards)."""
    pairs = np.arange(columns // 2, dtype=np.float64)
    plain = theta ** (-2.0 * pairs / columns)

    def pair_turning(times):
        return columns * math.log(original / (times * 2 * math.pi)) / (
            2 * math.log(theta)
        )

    low = max(math.floor(pair_turning(beta_fast)), 0)
    high = min(math.ceil(pair_turning(beta_slow)), columns - 1)
    slowed = np.clip((pairs - low) / max(high - low, 1e-3), 0.0, 1.0)
    return plain * (1.0 - slowed) + plain / factor * slowed


def rope_of(group: dict, head_dim: int) -> Rope:
    columns = int(head_dim * group.get("partial_rotary_factor", 1))
    theta = float(group["rope_theta"])
    if group.get("rope_type", "default") == "yarn":
        inv_freq = yarn_inv_freq(
            columns, theta, group["factor"],
            group["original_max_position_embeddings"], group["beta_fast"],
            group["beta_slow"],
        )
        return Rope(columns, tuple(inv_freq.tolist()),
                    float(group["attention_factor"]))
    pairs = np.arange(columns // 2, dtype=np.float64)
    return Rope(
        columns, tuple((theta ** (-2.0 * pairs / columns)).tolist()), 1.0
    )


def sizes_of(config: dict, tower) -> Sizes:
    first, count = config["held_experts"]
    ropes = config["rope_parameters"]
    return Sizes(
        kv_heads=config["num_key_value_heads"], head_dim=config["head_dim"],
        window=config["sliding_window"],
        full_rope=rope_of(ropes[FULL], config["head_dim"]),
        window_rope=rope_of(ropes[WINDOW], config["head_dim"]),
        eps=config["rms_norm_eps"], top_k=config["num_experts_per_tok"],
        scaling=config["moe_routed_scaling_factor"],
        held_first=first, held_count=count, tower=tower,
    )


def layers_of(config: dict):
    """The first `num_hidden_layers` entries of the published lists."""
    return [
        Layer(kind == WINDOW, int(heads), mlp == "sparse")
        for kind, heads, mlp in zip(
            config["layer_types"], config["num_attention_heads_per_layer"],
            config["mlp_layer_types"],
        )
    ][:config["num_hidden_layers"]]


# ---- the layers ---------------------------------------------------------


def rotary(x, rope: Rope):
    """(L, H, D): the first `rope.columns` columns turn, halves pairing,
    position = row; the others pass."""
    length = x.shape[0]
    angle = jnp.arange(length, dtype=jnp.float32)[:, None] * jnp.asarray(
        rope.inv_freq, jnp.float32
    )[None]
    cos = (jnp.cos(angle) * rope.factor)[:, None]
    sin = (jnp.sin(angle) * rope.factor)[:, None]
    turned, kept = x[..., :rope.columns], x[..., rope.columns:]
    x1, x2 = jnp.split(turned, 2, axis=-1)
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin, kept], axis=-1
    )


def group_attention(q, k, v, scale, window, quant):
    """One K/V head and the G query heads that read it: q (G, L, D), k and
    v (L, D) -> (G, L, D).  A dense masked softmax over all L keys,
    `QUERY_TILE` queries at a time."""
    group, length, dim = q.shape
    tile = QUERY_TILE if length % QUERY_TILE == 0 else length
    keys = jnp.arange(length)[None, :]

    @jax.checkpoint
    def rows(args):
        q_tile, start = args                               # (G, tile, D)
        logits = jnp.einsum("gtd,sd->gts", quant(q_tile), quant(k)) * scale
        at = start + jnp.arange(tile)[:, None]
        seen = at >= keys
        if window is not None:
            seen &= at - keys < window
        logits = jnp.where(seen[None], logits, -jnp.inf)
        logits = logits - logits.max(axis=-1, keepdims=True)
        weights = jnp.exp(logits)
        total = weights.sum(axis=-1, keepdims=True)
        # the program's kernels round the unnormalised weights to the
        # stated type for the product with v and divide after, in float32
        return quant(
            jnp.einsum("gts,sd->gtd", quant(weights), quant(v)) / total
        )

    out = jax.lax.map(rows, (
        q.reshape(group, length // tile, tile, dim).transpose(1, 0, 2, 3),
        jnp.arange(0, length, tile),
    ))                                                     # (n, G, tile, D)
    return out.transpose(1, 0, 2, 3).reshape(group, length, dim)


def attention(x, p, s: Sizes, layer: Layer, q):
    """x (L, d) -> (L, d), one sequence."""
    length = x.shape[0]
    heads, kv_heads, dim = layer.heads, s.kv_heads, s.head_dim
    rope = s.window_rope if layer.windowed else s.full_rope
    queries = q(rotary(
        matmul(x, p["q"]["kernel"], q).reshape(length, heads, dim), rope
    ))
    keys = q(rotary(
        matmul(x, p["k"]["kernel"], q).reshape(length, kv_heads, dim), rope
    ))
    values = matmul(x, p["v"]["kernel"], q).reshape(length, kv_heads, dim)
    one_group = jax.checkpoint(lambda args: group_attention(
        *args, scale=dim ** -0.5,
        window=s.window if layer.windowed else None, quant=q,
    ))
    out = jax.lax.map(one_group, (
        queries.reshape(length, kv_heads, heads // kv_heads, dim).transpose(
            1, 2, 0, 3
        ),
        keys.transpose(1, 0, 2), values.transpose(1, 0, 2),
    ))                                                     # (Hkv, G, L, D)
    out = out.transpose(2, 0, 1, 3).reshape(length, heads, dim)
    gate = q(jax.nn.sigmoid(matmul(x, p["gate"]["kernel"], q)))
    out = q(out * gate[..., None])
    return matmul(out.reshape(length, heads * dim), p["o"]["kernel"], q)


def routed(x, p, s: Sizes, q):
    """This holder's part of the routed experts, float32 out: every held
    expert over ALL tokens, times the weight the router gave it (zero
    where it was not among the token's top k)."""
    scores = jax.nn.sigmoid(x @ p["router_kernel"])        # float32 router
    _, chosen = jax.lax.top_k(jax.lax.stop_gradient(scores), s.top_k)
    picked = jnp.take_along_axis(scores, chosen, axis=1)
    weights = s.scaling * picked / picked.sum(axis=1, keepdims=True)

    @jax.checkpoint
    def expert(x, w_gate_up, w_down, weight):
        """One expert over all tokens, times its weight a token; rebuilt
        in the backward, so a layer keeps no expert's output."""
        gate, up = jnp.split(q(q(x) @ q(w_gate_up)), 2, axis=-1)
        return weight[:, None] * q(q(jax.nn.silu(gate) * up) @ q(w_down))

    def add_one(out, held):
        number, w_gate_up, w_down = held
        weight = jnp.sum(jnp.where(chosen == number, weights, 0.0), axis=1)
        return out + expert(x, w_gate_up, w_down, weight), None

    out, _ = jax.lax.scan(add_one, jnp.zeros_like(x), (
        s.held_first + jnp.arange(s.held_count),
        p["expert_w_gate_up"], p["expert_w_down"],
    ))
    return out


def block(p, x, s: Sizes, layer: Layer):
    """One decoder block over one sequence (L, d); the residual stream
    is in the stated type, as the program's is."""
    q = rounded_to(s.tower)
    x = q(x + attention(
        q(rms_norm(x, p["attn_norm"]["scale"], s.eps)), p["attn"], s, layer,
        q,
    ))
    y = q(rms_norm(x, p["ffn_norm"]["scale"], s.eps))
    if layer.routed:
        y = q(routed(y, p["moe"]["routed"], s, q)
              + swiglu(y, p["moe"]["shared"], q))
    else:
        y = swiglu(y, p["mlp"], q)
    return q(x + y)


def tail(p, x, ids, s: Sizes):
    """The final norm, the head and the loss of one sequence: x (L, d),
    ids (L,) -> the mean over the L - 1 positions that have a target."""
    q = rounded_to(s.tower)
    return blocked_nll(
        q(rms_norm(x, p["final_norm"]["scale"], s.eps)),
        p["lm_head_kernel"], jnp.roll(ids, -1), q,
    )[:ids.shape[0] - 1].mean()


# ---- the programs: jitted once, here ------------------------------------


@functools.partial(jax.jit, static_argnames=("s", "layer"))
def _block_fwd(p, x, s, layer):
    return jax.vmap(lambda row: block(p, row, s, layer))(x)


@functools.partial(jax.jit, static_argnames=("s", "layer"))
def _block_bwd(p, x, g, s, layer):
    """(gradient of the block's parameters, of its input)."""
    _, vjp = jax.vjp(
        lambda p, x: jax.vmap(lambda row: block(p, row, s, layer))(x), p, x
    )
    return vjp(g)


@functools.partial(jax.jit, static_argnames=("s",))
def _tail_grads(p, x, ids, weight, s):
    """(loss, (gradient of the tail's parameters, of its input)) of
    `weight` times the mean loss of the chunk's sequences."""
    def loss_of(p, x):
        return weight * jnp.mean(
            jax.vmap(lambda row, i: tail(p, row, i, s))(x, ids)
        )

    return jax.value_and_grad(loss_of, argnums=(0, 1))(p, x)


TAIL_KEYS = ("final_norm", "lm_head_kernel")


def _walk(params: dict, ids, config: dict, tower, weights):
    """(loss, nested gradient as host arrays) of sum_c weights[c] *
    (mean loss of chunk c), the chunks `CHUNK` sequences each in order.
    One layer's parameters and gradient are on the device at a time."""
    s = sizes_of(config, tower)
    layers = layers_of(config)
    tree = trees.nested(params)
    ids = np.asarray(ids, np.int32)
    if ids.shape[0] % CHUNK or ids.shape[0] // CHUNK != len(weights):
        raise ValueError(
            f"{ids.shape[0]} sequences are not {len(weights)} chunks of "
            f"{CHUNK}"
        )
    chunks = [
        jnp.asarray(ids[i:i + CHUNK]) for i in range(0, len(ids), CHUNK)
    ]
    with jax.default_matmul_precision("highest"):
        table = _device(tree["token_embedding"]["embedding"])
        acts = [[_embed(table, c, s)] for c in chunks]
        del table
        for i, layer in enumerate(layers):
            p = _device(tree[f"layer_{i}"])
            for a in acts:
                a.append(_block_fwd(p, a[-1], s, layer))
            del p
        p = _device({k: tree[k] for k in TAIL_KEYS})
        loss, tail_grad, flowing = 0.0, None, []
        for a, c, w in zip(acts, chunks, weights):
            part, (gp, gx) = _tail_grads(p, a.pop(), c, jnp.float32(w), s)
            loss = loss + part
            tail_grad = _add(tail_grad, gp)
            flowing.append(gx)
        grads = _host(tail_grad)
        del p, tail_grad
        for i, layer in reversed(list(enumerate(layers))):
            p = _device(tree[f"layer_{i}"])
            total = None
            for n, a in enumerate(acts):
                gp, flowing[n] = _block_bwd(p, a.pop(), flowing[n], s, layer)
                total = _add(total, gp)
            grads[f"layer_{i}"] = _host(total)
            del p, total
        rows = tree["token_embedding"]["embedding"].shape[0]
        through_input = None
        for c, g in zip(chunks, flowing):
            through_input = _add(through_input, _embed_grads(c, g, rows))
        grads["token_embedding"] = {"embedding": np.asarray(through_input)}
    return float(loss), grads


def cut(tree, features, config) -> dict:
    """{leaf name: array} of a parameter-shaped tree (parameters, Adam's
    moments): every leaf whole, since a batch touches all of them."""
    return trees.flat(tree)


def loss_and_grads(params: dict, features, labels, config, tower=None):
    """(loss, {leaf name: gradient}) of the batch's mean loss from the
    flat parameters `cut` gives; `labels` are not used (the targets are
    the ids shifted).  `tower` computes the twin (module docstring)."""
    ids = np.asarray(features["input_ids"])
    chunks = ids.shape[0] // CHUNK
    loss, grads = _walk(params, ids, config, tower, [1.0 / chunks] * chunks)
    return loss, trees.flat(grads)


def part_grads(params: dict, features, labels, config, parts: int) -> dict:
    """{leaf name: (parts, ...) gradients} over `parts` equal runs of the
    batch's sequences in turn, on the same parameters; their mean is the
    whole batch's gradient."""
    ids = np.asarray(features["input_ids"])
    if ids.shape[0] % parts:
        raise ValueError(f"{ids.shape[0]} sequences, {parts} parts")
    size = ids.shape[0] // parts
    each = [
        trees.flat(_walk(
            params, ids[i:i + size], config, None,
            [CHUNK / size] * (size // CHUNK),
        )[1]) for i in range(0, ids.shape[0], size)
    ]
    return {k: np.stack([g[k] for g in each]) for k in each[0]}
