"""Olmo-Hybrid-7B (`model_type: olmo_hybrid`) on the train path as plain
`jax.numpy` in float32 at the highest matmul precision: forward, loss and
gradients, with no kernel, no chunked scan, no remat policy and no
bfloat16.

The equations, from the catalog row's `config` (hidden d = 3,840, eps
1e-6, no biases anywhere; `assumed` items are in the configuration file):

    Norm(x)  x * rsqrt(mean x^2 + eps) * w           a plain scale
    block l  h = x + Norm_a(Mix_l(x));  y = h + Norm_f(MLP(h))
             OLMo 2/3's order: NO norm before a sublayer, one on its
             output, inside the residual branch; layer l is
             `layer_types[l]`
    MLP      (silu(h Wg) * (h Wu)) Wd                11,008 wide
    linear_attention   H = 30 heads, d_k = 96, d_v = 192
             q = x W_q, k = x W_k   d -> H d_k;  v = x W_v, z = x W_z
             d -> H d_v;  a = x W_a, b = x W_b   d -> H
             [q | k | v] <- silu(conv_4([q | k | v]))   depthwise, causal,
                 zeros left of t = 0, no bias
             beta = 2 sigmoid(b)   (`linear_allow_neg_eigval`; sigmoid(b)
                 without it);  g = -exp(A_log_h) softplus(a + dt_bias_h)
                 one number a token and head, float32
             q_h = q_h / sqrt(|q_h|^2 + 1e-6) * d_k^-1/2
             k_h = k_h / sqrt(|k_h|^2 + 1e-6)
             S_t = exp(g_t) S_{t-1}
             S_t += k_t (beta_t (v_t - S_t^T k_t))^T;  o_t = S_t^T q_t
                 S in R^{d_k x d_v} a head, S_0 = 0
             y = rms_norm(o; a head's 192 columns) * w * silu(z)
                 the norm FIRST, then the gate; ONE plain scale of 192
             Mix = y W_o                   H d_v -> d
    full_attention   30 query heads over 30 K/V heads, head 128
             q, k, v = x W_q, x W_k, x W_v
             q <- Norm(q), k <- Norm(k): ONE statistic over ALL of the
                 projection's columns (every head's together), a plain
                 scale of that width; no rotary (rope_theta null)
             Mix = softmax_causal(q k^T 128^-1/2) v W_o
    loss     CE(Norm(h_L) W_head, x_{t+1}), the head untied, a mean over
             the positions that have a target

The delta rule here is the RECURRENCE, token by token (`lax.scan` over
t): the program's chunked algebra (`ops/gdn.py`), and the zero columns it
pads a head's 96 | 192 with, are checked against something that shares
none of it.

The cut is the configuration's, and this file is given the SAME share as
the program: the published layers in `layers_held`, the sliced vocabulary,
and the HEADS held (the configuration's head counts are the held counts:
the kernels it is handed have those heads' columns and rows alone).  What
the absent heads would add to a mixer's output is left out here as in the
program, and the attention layer's whole-width statistic is over the held
columns, which is what one chip computes without the exchange; given every
head, it is the uncut layer.

Departures of this file from a one-function reference, each for memory
beside the live train state (8.5 GB stays on the chip during the check);
none changes a number past float32 summation order: the layers are walked
with `jax.vjp`, ONE layer's float32 parameters and gradient on the device
at a time; the batch goes a sequence at a time; the recurrence's backward
rebuilds `SCAN_BLOCK` steps at a time (a nested scan under
`jax.checkpoint`) and goes `HEAD_GROUP` heads at a time; attention is a
dense masked softmax a head and a tile of queries at a time, rebuilt in
the backward (`reference/laguna.py: group_attention` at a group of one);
the head's logits go a block of tokens at a time.

`tower` is the twin (`loss_and_grads(..., tower="bfloat16")`): the same
network with what the program rounds when the configuration states
bfloat16 rounded the same way (flax's `Dense(dtype=bfloat16)`: inputs,
kernel and output of every matmul, the backward signal with them; the
residual stream and every norm's output, q, k and v after the conv (the
L2 norms are float32 inside the scan's op and are not rounded), the
scan's output, the gated norm's product, attention's normed operands and
probabilities, the head's operands), and every norm's statistics, the
conv's taps and sums, g and beta, the state S, the softmax and the loss in
float32 as the program keeps them.  `tower="float8_e4m3fn"` is the check's
control, the type below.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

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
from benchmarks.reference.kimi_linear import l2_normed, silu_conv
from benchmarks.reference.laguna import group_attention
from benchmarks.reference.qwen3_next import delta_recurrence

# The loss is one mean over 8,191 positions.  At the configuration's 1e-4
# the job has MEMORISED its pool of eight sequences where the check lands
# (step 113 traced, 121 plain: the loss is 0.0021-0.0035 there, 0.2-2.6
# when the window opens), as the other cells at this rate do (PERF.md
# section 7 (10)).  On the chip at the cell's size (PERF.md section 6, PR 64;
# the bfloat16 twin and the float8 control on one state and batch at the
# plain landing, `.proof/margins_olmo.py`, seeds 2147483999 and 3210987654;
# the step on twelve runs): the job's step 9.4e-6 .. 1.4e-5 from this
# reference, the twin 4.7e-6 and 6.2e-6, the control 3.8e-3 and 4.2e-3 (it
# reads a loss of 0.0067-0.0069 where the reference reads 0.0027-0.0029).
# 1e-3 stands 73 times over the step's worst and 3.8 times under the
# control's better reading.
LOSS_ATOL = 1e-3
# L2 error allowed on a gradient leaf (first matching pattern), RELATIVE
# TO the leaf's reference norm.  The cell's batch is ONE sequence and
# `drivers/train.py: sampling_noise` wants a batch of whole eighths, so
# the check runs WITHOUT the twin-held rule (no `STATED_RATIO` name below;
# `TWIN_RATIO` says why) and every leaf is held to these shares of its own
# norm.  At a memorised pool a leaf's gradient is small and the stated
# type's own error a larger share of it than at seeded weights; the twin
# reads 0.75-0.85 of the step on the wide leaves, so the step's error IS
# the stated type's.  Readings (the step's worst leaf of the class over
# twelve runs on twelve seeds, eleven plain and one traced; the twin and
# the control on two of them, the control's BEST leaf of the class):
#
# a delta-rule layer's A_log and dt_bias (ten numbers each, their gradient
#   one sum over 8,192 tokens a head, so the landing's own noise moves them
#   most): the step 0.047 .. 0.134 (worst `layer_2/gdn/dt_bias`; the twin
#   0.039 and 0.029), the control 0.58 and 0.79.  No limit between the two
#   has three times of room on both sides; 0.3 stands 2.2 times over the
#   step's worst and 1.9 under the control's better reading, which the
#   other classes fail by wide margins.
# the block's norms, the final norm and the two whole-width QK-norm scales:
#   the step 0.003 .. 0.062 (worst `layer_0/ffn_norm/scale`; the twin 0.037
#   and 0.039), the control 1.00 and 1.15.  0.25 stands at the geometric
#   mean of 0.062 and 1.00: four times of room either way.
# every other leaf (a delta-rule mixer's kernels, conv taps and output
#   scale, attention's four kernels, the MLPs', embedding and head): the
#   step 0.028 .. 0.233 (worst `layer_2/gdn/q/kernel` on most runs, 0.131
#   .. 0.233 plain and 0.151 traced; the twin 0.112 and 0.118 there; the
#   delta rule's q and k lead, where Qwen3-Next's GDN leaves read 0.08-0.11
#   at beta under 1 and a loss of 7), the control 1.52 and 1.55.  0.6
#   stands at the geometric mean of 0.233 and 1.52: 2.6 times over the
#   step's worst, 2.5 under the control's best.
LEAF_REL_L2 = (
    ("gdn/(A_log|dt_bias)$", 3e-1),
    ("(mix_norm|ffn_norm|final_norm|q_norm|k_norm)/scale$", 2.5e-1),
    ("", 6e-1),
)
# All leaves as one vector against the reference's: the step reads 1 -
# cosine 3.3e-3 .. 4.9e-3 on twelve runs (the twin 2.3e-3 and 2.9e-3), the
# control 0.540 and 0.559.  5e-2 stands near their geometric mean: ten
# times over the step's worst, eleven under the control's better.
GRAD_COSINE_MIN = 0.95
# How many times the bfloat16 twin's error a step's may be, leaf by leaf
# and on the angle, in the tests (`check_gradient` at a batch of 8).  It
# is NOT named `STATED_RATIO`, for `reference/glm_moe_lite.py`'s reason:
# the driver would then ask `sampling_noise` to split the cell's batch of
# ONE sequence into 8 equal parts and raise (PERF.md section 7).
TWIN_RATIO = 3.0
# Sequences a reference program takes at once.
CHUNK = 1
# Heads that go through the recurrence at once.
HEAD_GROUP = 5

LINEAR, FULL = "linear_attention", "full_attention"


class Sizes(NamedTuple):
    """What the programs below are compiled for (hashable).  The head
    counts are those HELD: the kernels' columns say no more."""

    key_heads: int
    value_heads: int
    key_dim: int
    value_dim: int
    beta_scale: float
    heads: int
    kv_heads: int
    head_dim: int
    eps: float
    tower: Optional[str]


def sizes_of(config: dict, tower) -> Sizes:
    return Sizes(
        key_heads=config["linear_num_key_heads"],
        value_heads=config["linear_num_value_heads"],
        key_dim=config["linear_key_head_dim"],
        value_dim=config["linear_value_head_dim"],
        beta_scale=2.0 if config["linear_allow_neg_eigval"] else 1.0,
        heads=config["num_attention_heads"],
        kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"], eps=config["rms_norm_eps"], tower=tower,
    )


def layers_of(config: dict):
    """The published type of each layer the cut holds (`layers_held`,
    0-based indices into `layer_types`)."""
    return [config["layer_types"][i] for i in config["layers_held"]]


# ---- the layers ---------------------------------------------------------


def gdn(x, p, s: Sizes, q):
    """x (L, d) -> (L, d), one sequence: the held heads' part of the
    delta-rule mixer's output."""
    length = x.shape[0]
    ratio = s.value_heads // s.key_heads
    keys, values = s.key_heads * s.key_dim, s.value_heads * s.value_dim
    wide = jnp.concatenate(
        [matmul(x, p[name]["kernel"], q) for name in ("q", "k", "v")],
        axis=-1,
    )
    z = matmul(x, p["z"]["kernel"], q)
    a, b = (matmul(x, p[name]["kernel"], q) for name in ("a", "b"))
    queries, keys_, vals = jnp.split(
        q(silu_conv(wide, p["conv_kernel"])), [keys, 2 * keys], axis=-1
    )
    # normed in float32 inside the program's op: not rounded again
    queries = l2_normed(
        queries.reshape(length, s.key_heads, s.key_dim), s.key_dim ** -0.5
    )
    keys_ = l2_normed(keys_.reshape(length, s.key_heads, s.key_dim), 1.0)
    vals = vals.reshape(length, s.value_heads, s.value_dim)
    # in (0, 2) where negative eigenvalues are allowed
    beta = s.beta_scale * jax.nn.sigmoid(b)                # (L, H)
    g = -jnp.exp(p["A_log"]) * jax.nn.softplus(a + p["dt_bias"])
    heads = s.value_heads
    group = HEAD_GROUP if heads % HEAD_GROUP == 0 else heads

    def grouped(t):
        """(L, H, ...) -> (H / group, group, L, ...)."""
        t = jnp.moveaxis(t, 1, 0)
        return t.reshape(heads // group, group, *t.shape[1:])

    out = jax.lax.map(
        lambda args: jax.vmap(delta_recurrence)(*args),
        tuple(grouped(t) for t in (
            jnp.repeat(queries, ratio, axis=1),
            jnp.repeat(keys_, ratio, axis=1), vals, g, beta,
        )),
    )                                                      # (H/G, G, L, D)
    out = q(jnp.moveaxis(out.reshape(heads, length, s.value_dim), 0, 1))
    gated = q(
        rms_norm(out, p["o_norm"]["scale"], s.eps)
        * jax.nn.silu(z.reshape(length, heads, s.value_dim))
    )
    return matmul(gated.reshape(length, values), p["o"]["kernel"], q)


def attention(x, p, s: Sizes, q):
    """x (L, d) -> (L, d), one sequence: the held heads' part of the
    attention mixer's output.  No positions."""
    length = x.shape[0]
    heads, kv_heads, dim = s.heads, s.kv_heads, s.head_dim
    # ONE statistic over every held column of the projection
    queries = q(rms_norm(
        matmul(x, p["q"]["kernel"], q), p["q_norm"]["scale"], s.eps
    )).reshape(length, heads, dim)
    keys = q(rms_norm(
        matmul(x, p["k"]["kernel"], q), p["k_norm"]["scale"], s.eps
    )).reshape(length, kv_heads, dim)
    values = matmul(x, p["v"]["kernel"], q).reshape(length, kv_heads, dim)
    one_group = jax.checkpoint(lambda args: group_attention(
        *args, scale=dim ** -0.5, window=None, quant=q,
    ))
    out = jax.lax.map(one_group, (
        queries.reshape(length, kv_heads, heads // kv_heads, dim).transpose(
            1, 2, 0, 3
        ),
        keys.transpose(1, 0, 2), values.transpose(1, 0, 2),
    ))                                                     # (Hkv, G, L, D)
    out = out.transpose(2, 0, 1, 3).reshape(length, heads * dim)
    return matmul(out, p["o"]["kernel"], q)


def block(p, x, s: Sizes, kind: str):
    """One block over one sequence (L, d) in OLMo's order: a sublayer
    reads the stream as it is, and its output is normed inside the
    residual branch; the stream and every norm's output are in the stated
    type, as the program's are."""
    q = rounded_to(s.tower)
    if kind == LINEAR:
        y = gdn(x, p["gdn"], s, q)
    else:
        y = attention(x, p["attn"], s, q)
    h = q(x + q(rms_norm(y, p["mix_norm"]["scale"], s.eps)))
    y = swiglu(h, p["mlp"], q)
    return q(h + q(rms_norm(y, p["ffn_norm"]["scale"], s.eps)))


def tail(p, x, ids, s: Sizes):
    """The final norm, the head and the loss of one sequence: x (L, d),
    ids (L,) -> the mean over the L - 1 positions that have a target."""
    q = rounded_to(s.tower)
    return blocked_nll(
        q(rms_norm(x, p["final_norm"]["scale"], s.eps)), p["lm_head_kernel"],
        jnp.roll(ids, -1), q,
    )[:ids.shape[0] - 1].mean()


# ---- the programs: jitted once, here ------------------------------------


@functools.partial(jax.jit, static_argnames=("s", "kind"))
def _block_fwd(p, x, s, kind):
    return jax.vmap(lambda row: block(p, row, s, kind))(x)


@functools.partial(jax.jit, static_argnames=("s", "kind"))
def _block_bwd(p, x, g, s, kind):
    """(gradient of the block's parameters, of its input)."""
    _, vjp = jax.vjp(
        lambda p, x: jax.vmap(lambda row: block(p, row, s, kind))(x), p, x
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
        for i, kind in enumerate(layers):
            p = _device(tree[f"layer_{i}"])
            for a in acts:
                a.append(_block_fwd(p, a[-1], s, kind))
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
        for i, kind in reversed(list(enumerate(layers))):
            p = _device(tree[f"layer_{i}"])
            total = None
            for n, a in enumerate(acts):
                gp, flowing[n] = _block_bwd(p, a.pop(), flowing[n], s, kind)
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
