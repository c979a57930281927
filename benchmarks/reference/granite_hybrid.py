"""granite-4.0-h-micro (`granitemoehybrid`, dense) on the train path as plain
`jax.numpy` in float32 at the highest matmul precision: forward, loss and
gradients, with no kernel, no chunked scan, no remat and no bfloat16.

The equations, from the catalog row's `config` (hidden d = 2,048, RMSNorm
with a learned scale and eps 1e-5, no biases but the conv's, SiLU;
`assumed` items are in the configuration file):

    embed    h_0 = m_emb E[ids]                        m_emb = 12
    block l  r = h + m_res Mix_l(RMSNorm(h));  h' = r + m_res MLP(RMSNorm(r))
             m_res = 0.22; layer l is Mamba-2 where layer_types[l] is
             "mamba", attention where it is "attention"
    MLP      (silu(x Wg) * (x Wu)) Wd                  2,048 -> 2 x 8,192
    Mamba-2  H = 64 heads of P = 64 (4,096 channels), N = 128 state
             columns, G = 1 group, K = 4 taps
             [z | xBC | dt] = x W_in                   4,096 + 4,352 + 64
             xBC = silu(conv_K(xBC) + b)   depthwise, causal, zeros left
                                           of t = 0, WITH a bias
             [x | B | C] = split(xBC, [4,096, 128, 128])
             dt = softplus(dt + dt_bias);  a = exp(-exp(A_log) dt)
                 a scalar a head and token in (0, 1), float32
             S_t = a_t S_{t-1} + dt_t x_t B_t^T        S in R^{P x N}, S_0 = 0
             y_t = S_t C_t + D x_t
             Mix = (RMSNorm_4096(y * silu(z)) * w) W_out
                 the gate FIRST, then one norm over all 4,096 channels
    attn     q, k, v = x Wq, x Wk, x Wv    32 / 8 / 8 heads of 64, no
             rotation, no norm;  m_att = 0.015625 = 1/64 IS the scale
             Mix = concat_h(softmax_causal(q_h k_g(h)^T m_att) v_g(h)) Wo
    loss     CE(RMSNorm(h_L) E^T / m_logits, x_{t+1}), m_logits = 8, the
             head tied, a mean over the positions that have a target

Mamba-2 here is the RECURRENCE, token by token (`lax.scan` over t): the
program's chunked algebra (`ops/ssd.py`) is checked against something
that shares none of it.

The cut is the configuration's: the published layers in `layers_held`,
the sliced vocabulary.

Departures of this file from a one-function reference, each for memory
beside the live train state (9.3 GB stays on the chip during the check);
none changes a number past float32 summation order: the layers are walked
with `jax.vjp`, ONE layer's float32 parameters and gradient on the device
at a time; the batch goes a sequence at a time; the recurrence's backward
rebuilds `SCAN_BLOCK` steps at a time (a nested scan under
`jax.checkpoint`: 8,192 states of 64 heads x 32 KB would be 17 GB a
sequence) and goes `HEAD_GROUP` heads at a time; attention is a dense
masked softmax a K/V head and a tile of queries at a time, rebuilt in the
backward (`reference/laguna.py: group_attention`); the head's logits go a
block of tokens at a time.  ONE program is compiled a layer KIND (a
Mamba-2 block, an attention block, forward and backward each, and the
embedding and the head): the parameters are arguments, so ten layers cost
a cold check what two do.

`tower` is the twin (`loss_and_grads(..., tower="bfloat16")`): the same
network with what the program rounds when the configuration states
bfloat16 rounded the same way (flax's `Dense(dtype=bfloat16)`: inputs,
kernel and output of every matmul, the backward signal with them; the
residual stream and each scaled branch before it is added, the conv's
output, the scan's output, the gated norm's output, attention's operands
and probabilities and the head's operands), and every norm's statistics,
the conv's taps, bias and sums, dt, the decay, the state S, the gate's
product, the softmax and the loss in float32 as the program keeps them.
`tower="float8_e4m3fn"` is the check's control, the type below.
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
    _embed_grads,
    _host,
    blocked_nll,
    matmul,
    rms_norm,
    swiglu,
)
from benchmarks.reference.laguna import group_attention

# The loss is one mean over 8,191 positions of terms whose logits carry
# bfloat16's roundings, independent across positions: the MEAN moves far
# less than a term.  It is 7.4-8.4 when the window opens (step 17) and 0.84-
# 2.0 where the check lands: the job's 8 sequences are one pool that every
# task permutes, and at 8,192 tokens a step the model has only begun to
# memorise it (PERF.md section 7 (10); the siblings, at 16,384-32,768
# tokens a step, land at 0.01-0.1).  On the chip at the cell's size
# (PERF.md section 6, PR 46; the check at step 89, 97, 105 or 113; the
# bfloat16 twin and the float8 control on one state and batch at three of
# the landings, `.proof/margins.py`): the job's step 1.6e-3 .. 2.4e-3
# from this reference over 22 runs, the twin 5.7e-4 .. 6.3e-4, the
# control 1.5e-1 .. 1.7e-1.  1e-2 is the accepted decoder cells' limit:
# four times the step's worst reading, a fifteenth of the control's best.
# A multiplier at 1, the scale D^-1/2, a dropped conv bias or tap, the
# norm before the gate or a rotation move the loss by 1e-2 and far more
# at this loss (tests/test_granite_hybrid.py holds each at a small size).
LOSS_ATOL = 1e-2
# L2 error allowed on a gradient leaf (first matching pattern), RELATIVE
# TO the leaf's reference norm.  The cell's batch is ONE sequence and
# `drivers/train.py: sampling_noise` wants a batch of whole eighths, so
# the check runs WITHOUT the twin-held rule (no `STATED_RATIO` name below;
# `TWIN_RATIO` says why) and every leaf is held to this share of its own
# norm.  The limit stands between two readings on the chip at the cell's
# size (the runs above): the step's worst leaf over its runs 0.079 ..
# 0.137 (a Mamba-2 layer's `dt_bias`, one number a head, or the first
# block's norm scales; the median leaf 0.030 .. 0.056), the float8
# control's worst leaf on the same state and batch 1.19 .. 2.37 (its
# MEDIAN leaf 0.46 .. 0.61): 2.9 times of room over the step's, three
# under the control's.  The bfloat16 twin's worst leaf reads 0.063 ..
# 0.083, the same leaves: three fifths of the step's error IS the stated
# type's, the rest the scan's and the attention's bfloat16 products
# inside their kernels, which the twin's float32 recurrence and softmax
# do not round.  A dense model: no class of leaves (expert stacks, a
# router) needs a limit of its own.
LEAF_REL_L2 = (
    ("", 4e-1),
)
# All leaves as one vector against the reference's: the step reads 1 -
# cosine 6.5e-4 .. 1.70e-3 (the twin 4.0e-4 .. 6.7e-4), the control
# 1.0e-1 .. 1.6e-1: 1e-2 is 5.9 times the step's worst and a tenth of the
# control's best.
GRAD_COSINE_MIN = 0.99
# How many times the bfloat16 twin's error a step's may be, leaf by leaf
# and on the angle, in the tests (`check_gradient` at a batch of 8).  It
# is NOT named `STATED_RATIO`, for `reference/glm_moe_lite.py`'s reason:
# the driver would then ask `sampling_noise` to split the cell's batch of
# ONE sequence into 8 equal parts and raise (PERF.md section 7).
TWIN_RATIO = 3.0
# Sequences a reference program takes at once.
CHUNK = 1
# Steps of the recurrence rebuilt at once in its backward, and heads that
# go through it at once.
SCAN_BLOCK = 128
HEAD_GROUP = 32

MAMBA = "mamba"


class Sizes(NamedTuple):
    """What the programs below are compiled for (hashable)."""

    heads: int
    kv_heads: int
    m_heads: int
    m_dim: int
    m_state: int
    m_groups: int
    eps: float
    embedding_multiplier: float
    attention_multiplier: float
    residual_multiplier: float
    logits_scaling: float
    tower: Optional[str]


def sizes_of(config: dict, tower) -> Sizes:
    return Sizes(
        heads=config["num_attention_heads"],
        kv_heads=config["num_key_value_heads"],
        m_heads=config["mamba_n_heads"], m_dim=config["mamba_d_head"],
        m_state=config["mamba_d_state"], m_groups=config["mamba_n_groups"],
        eps=config["rms_norm_eps"],
        embedding_multiplier=float(config["embedding_multiplier"]),
        attention_multiplier=float(config["attention_multiplier"]),
        residual_multiplier=float(config["residual_multiplier"]),
        logits_scaling=float(config["logits_scaling"]), tower=tower,
    )


def layers_of(config: dict):
    """Whether each published layer the cut holds (`layers_held`) is a
    Mamba-2 layer, by the PUBLISHED `layer_types`."""
    return [config["layer_types"][i] == MAMBA for i in config["layers_held"]]


# ---- the layers ---------------------------------------------------------


def ssm_recurrence(x, dt, a, b, c, skip, block: int = SCAN_BLOCK):
    """The state-space recurrence of ONE head, token by token: x (L, P),
    dt and a (L,), b and c (L, N), skip a scalar -> y (L, P), all float32.
    The backward rebuilds `block` steps at a time from the state before
    them."""
    length, dim = x.shape
    if length % block:
        block = length

    def step(state, token):
        x_t, dt_t, a_t, b_t, c_t = token
        state = a_t * state + (dt_t * x_t)[:, None] * b_t[None, :]
        return state, (state * c_t[None, :]).sum(axis=1) + skip * x_t

    @jax.checkpoint
    def steps(state, tokens):
        return jax.lax.scan(step, state, tokens)

    _, out = jax.lax.scan(
        steps, jnp.zeros((dim, b.shape[1]), jnp.float32),
        tuple(
            t.reshape(length // block, block, *t.shape[1:])
            for t in (x, dt, a, b, c)
        ),
    )
    return out.reshape(length, dim)


def silu_conv(x, taps, bias):
    """silu(causal depthwise conv + bias) of x (L, c) under taps (K, c):
    the taps as explicit shifts."""
    length, reach = x.shape[0], taps.shape[0] - 1
    padded = jnp.pad(x, ((reach, 0), (0, 0)))
    return jax.nn.silu(
        sum(taps[k] * padded[k:k + length] for k in range(reach + 1)) + bias
    )


def mamba(x, p, s: Sizes, q):
    """x (L, d) -> (L, d), one sequence."""
    length = x.shape[0]
    heads, dim = s.m_heads, s.m_dim
    inner, shared = heads * dim, s.m_groups * s.m_state
    z, xbc, dt = jnp.split(
        matmul(x, p["in_proj"]["kernel"], q),
        [inner, 2 * inner + 2 * shared], axis=-1,
    )
    xs, b, c = jnp.split(
        q(silu_conv(xbc, p["conv_kernel"], p["conv_bias"])),
        [inner, inner + shared], axis=-1,
    )
    dt = jax.nn.softplus(dt + p["dt_bias"])                # (L, H)
    a = jnp.exp(-jnp.exp(p["A_log"]) * dt)
    each = heads // s.m_groups
    group = HEAD_GROUP if each % HEAD_GROUP == 0 else each

    def grouped(t, *tail):
        """(L, H, ...) -> (H / group, group, L, ...)."""
        t = jnp.moveaxis(t.reshape(length, heads, *tail), 1, 0)
        return t.reshape(heads // group, group, *t.shape[1:])

    def shared_by(t):
        """(L, G * N) -> (H / group, L, N): a group of heads' B or C."""
        t = jnp.moveaxis(t.reshape(length, s.m_groups, s.m_state), 1, 0)
        return jnp.repeat(t, each // group, axis=0)

    out = jax.lax.map(
        lambda args: jax.vmap(
            ssm_recurrence, in_axes=(0, 0, 0, None, None, 0)
        )(*args),
        (grouped(xs, dim), grouped(dt), grouped(a), shared_by(b),
         shared_by(c), p["D"].reshape(heads // group, group)),
    )                                                      # (H/g, g, L, P)
    y = q(jnp.moveaxis(out.reshape(heads, length, dim), 0, 1)).reshape(
        length, inner
    )
    # the gate first, then ONE norm over all the channels
    y = q(rms_norm(y * jax.nn.silu(z), p["norm"]["scale"], s.eps))
    return matmul(y, p["out_proj"]["kernel"], q)


def attention(x, p, s: Sizes, q):
    """x (L, d) -> (L, d), one sequence: no rotation, no norm, the scale
    the configuration's `attention_multiplier`."""
    length, hidden = x.shape
    heads, kv_heads = s.heads, s.kv_heads
    dim = hidden // heads
    queries, keys, values = (
        matmul(x, p[name]["kernel"], q).reshape(length, count, dim)
        for name, count in (("q", heads), ("k", kv_heads), ("v", kv_heads))
    )
    one_group = jax.checkpoint(lambda args: group_attention(
        *args, scale=s.attention_multiplier, window=None, quant=q,
    ))
    out = jax.lax.map(one_group, (
        queries.reshape(length, kv_heads, heads // kv_heads, dim).transpose(
            1, 2, 0, 3
        ),
        keys.transpose(1, 0, 2), values.transpose(1, 0, 2),
    ))                                                     # (Hkv, G, L, D)
    out = out.transpose(2, 0, 1, 3).reshape(length, heads * dim)
    return matmul(out, p["o"]["kernel"], q)


def block(p, x, s: Sizes, is_mamba: bool):
    """One decoder block over one sequence (L, d); the residual stream
    and each scaled branch are in the stated type, as the program's."""
    q = rounded_to(s.tower)
    y = q(rms_norm(x, p["mix_norm"]["scale"], s.eps))
    if is_mamba:
        y = mamba(y, p["mamba"], s, q)
    else:
        y = attention(y, p["attn"], s, q)
    x = q(x + q(s.residual_multiplier * y))
    y = swiglu(q(rms_norm(x, p["ffn_norm"]["scale"], s.eps)), p["mlp"], q)
    return q(x + q(s.residual_multiplier * y))


def tail(p, x, ids, s: Sizes):
    """The final norm, the tied head over `logits_scaling` and the loss
    of one sequence: x (L, d), ids (L,) -> the mean over the L - 1
    positions with a target."""
    q = rounded_to(s.tower)
    return blocked_nll(
        q(rms_norm(x, p["final_norm"]["scale"], s.eps) / s.logits_scaling),
        p["token_embedding"]["embedding"].T, jnp.roll(ids, -1), q,
    )[:ids.shape[0] - 1].mean()


# ---- the programs: jitted once, here ------------------------------------


@functools.partial(jax.jit, static_argnames=("s",))
def _embed(table, ids, s):
    return rounded_to(s.tower)(s.embedding_multiplier * table[ids])


@functools.partial(jax.jit, static_argnames=("s", "is_mamba"))
def _block_fwd(p, x, s, is_mamba):
    return jax.vmap(lambda row: block(p, row, s, is_mamba))(x)


@functools.partial(jax.jit, static_argnames=("s", "is_mamba"))
def _block_bwd(p, x, g, s, is_mamba):
    """(gradient of the block's parameters, of its input)."""
    _, vjp = jax.vjp(
        lambda p, x: jax.vmap(lambda row: block(p, row, s, is_mamba))(x),
        p, x,
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


TAIL_KEYS = ("final_norm", "token_embedding")


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
        for i, is_mamba in enumerate(layers):
            p = _device(tree[f"layer_{i}"])
            for a in acts:
                a.append(_block_fwd(p, a[-1], s, is_mamba))
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
        for i, is_mamba in reversed(list(enumerate(layers))):
            p = _device(tree[f"layer_{i}"])
            total = None
            for n, a in enumerate(acts):
                gp, flowing[n] = _block_bwd(
                    p, a.pop(), flowing[n], s, is_mamba
                )
                total = _add(total, gp)
            grads[f"layer_{i}"] = _host(total)
            del p, total
        rows = tree["token_embedding"]["embedding"].shape[0]
        through_input = None
        for c, g in zip(chunks, flowing):
            through_input = _add(through_input, _embed_grads(c, g, rows))
        # the tied table's two gradients: as the head, and as the lookup
        # (whose rows were times the embedding multiplier)
        grads["token_embedding"]["embedding"] = (
            grads["token_embedding"]["embedding"]
            + s.embedding_multiplier * np.asarray(through_input)
        )
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
