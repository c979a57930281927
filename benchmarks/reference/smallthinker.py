"""SmallThinker-21BA3B-Instruct (`smallthinker_21b_instruct`) on the train
path as plain `jax.numpy` in float32 at the highest matmul precision:
forward, loss and gradients, with no kernel, no sort, no remat and no
bfloat16.

The equations, from the catalog row's `config` and `described_as` (hidden
d = 2,560, RMSNorm eps 1e-6, no biases anywhere; `assumed` items are in the
configuration file).  Layer l is a PUBLISHED 0-based index:

    RMSNorm(x)  x * rsqrt(mean x^2 + eps) * w           a plain scale
    routing  r = x W_r          64 logits in float32 from the block's
                                INPUT x, un-normed, BEFORE attention
             S = top_6(r);  w_i = exp(r_i) / sum_{j in S} exp(r_j)
                                the softmax over the CHOSEN six
    attention  a = RMSNorm(x);  q, k, v = a W_q, a W_k, a W_v
             28 query heads over 4 K/V heads of 128; query head h reads
             K/V head h // 7
             `sliding_window_layout[l]` 1 (and `rope_layout[l]` 1): q, k
                 turned by rotary over the whole head (theta 1.5e6, halves
                 pairing, position = index); query t sees keys s with
                 t - 4096 < s <= t
             `sliding_window_layout[l]` 0 (and `rope_layout[l]` 0): q, k
                 as they are, NO positions; query t sees every s <= t
             o = softmax(q k^T 128^-1/2 + mask) v
    block l  h = x + o W_o;  m = RMSNorm(h)
             y = h + sum_{i in S, i HELD} w_i (relu(m Wg_i) * (m Wu_i)) Wd_i
                                ReGLU, 768 wide; no shared expert
    loss     CE(RMSNorm(y_L) W_head, x_{t+1}), the head untied, a mean
             over the positions that have a target; no auxiliary loss

The routing is written the published way (top-6 of the LOGITS, then a
softmax over the six); the program scores with a softmax over all 64 and
renormalises the picked, which is the same number (tests/
test_smallthinker.py holds the two to rounding).

The cut is the configuration's: the published layers in `layers_held`,
the held experts (`held_experts`), the sliced vocabulary.  What absent
experts would add is left out here as in the program.  Each held expert is
applied to ALL tokens and masked by its weight.

Departures of this file from a one-function reference, each for memory
beside the live train state (4.6 GB stays on the chip during the check);
none changes a number past float32 summation order: the layers are walked
with `jax.vjp`, ONE layer's float32 parameters and gradient on the device
at a time; the batch goes a sequence at a time; attention is a dense
masked softmax over ALL the sequence's keys, a K/V head's group of seven
query heads at a time and `QUERY_TILE` queries of it at a time, rebuilt in
the backward (`reference/laguna.py: group_attention`: 28 heads x 16,384 x
16,384 float32 logits are 30 GB whole; a tile of a group of 7 is 235 MB);
the held experts go one at a time (`lax.scan`); the head's logits go a
block of tokens at a time.

`tower` is the twin (`loss_and_grads(..., tower="bfloat16")`): the same
network with what the program rounds when the configuration states
bfloat16 rounded the same way (flax's `Dense(dtype=bfloat16)`: inputs,
kernel and output of every matmul, the backward signal with them; the
residual stream, the turned queries and keys, attention's operands and
probabilities, the experts' grouped products and the head's operands),
and the router (its product over the residual stream as it stands, its
softmax), every norm's statistics, the attention softmax and the loss in
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
)
from benchmarks.reference.laguna import Rope, group_attention, rotary

# The loss is one mean over 16,383 positions of terms whose logits carry
# bfloat16's roundings, independent across positions: the MEAN moves far
# less than a term.  At the configuration's 1e-5 the job has barely begun
# to learn its pool where the check lands (step 97 plain, 105 traced: the
# loss is 9.83-9.90 there, 10.23-10.30 when the window opens).  On the chip
# at the cell's size (PERF.md section 6, PR 57; the bfloat16 twin and the
# float8 control on one state and batch at the plain landing, `.proof`'s
# margins run, seeds 3000005732 and 2147484001): the job's step 1.0e-5 ..
# 2.5e-5 from this reference over six runs, the twin 8.6e-6 and 7.4e-5,
# the control 3.1e-5 and 2.0e-3: the loss does not tell the types apart
# here, so it keeps the accepted GLM, Laguna, Kimi and Qwen3-Next cells'
# limit, 400 times the step's worst reading, and the control is failed by
# the gradient's terms below.
LOSS_ATOL = 1e-2
# L2 error allowed on a gradient leaf (first matching pattern), RELATIVE
# TO the leaf's reference norm.  The cell's batch is ONE sequence and
# `drivers/train.py: sampling_noise` wants a batch of whole eighths, so
# the check runs WITHOUT the twin-held rule (no `STATED_RATIO` name below;
# `TWIN_RATIO` says why) and every leaf is held to these shares of its own
# norm.  The readings are the runs' above (the step's worst leaf of the
# class over six runs on six seeds; the float8 control on the same state
# and batch, its best of two); the bfloat16 twin reads within 5% of the
# step on every class, so the step's error IS the stated type's.
#
# expert stacks: a top-k flip between types moves a token to another
#   expert, so rows come and go from an expert's gradient: the step
#   0.056 .. 0.062 (worst `layer_0/moe/routed/expert_w_gate_up`; the twin
#   0.056 and 0.058), the control 0.224 and 0.227.  0.12 stands near their
#   geometric mean: 1.9 times of room over the step, 1.9 under the control.
# router: its gradient comes through the softmax over the chosen six
#   alone, and a flipped slot changes which six whatever the type: the
#   step 0.056 .. 0.070 (the twin 0.058 and 0.056), the control 0.093 and
#   0.097, a third above the step and no more.  No limit between the two
#   has room on both sides, so this one stands over the step (2.1 times of
#   room: fresh seeds read higher) and the control PASSES it; it is failed
#   by the other three.
# every other leaf (attention's four kernels, the norms, embedding and
#   head): the step 0.0127 .. 0.0134 (worst `layer_3/attn/q/kernel` on
#   every run; the twin 0.012 and 0.013), the control 0.146 and 0.147.
#   0.045 stands near their geometric mean: 3.4 times over the step, 3.2
#   under the control.
LEAF_REL_L2 = (
    ("expert_w_", 1.2e-1),
    ("router_kernel$", 1.5e-1),
    ("", 4.5e-2),
)
# All leaves as one vector against the reference's: the step reads 1 -
# cosine 9.5e-6 .. 1.08e-5 (the twin 1.3e-5 and 1.4e-5), the control 2.2e-3
# and 2.5e-3.  2e-4 stands near their geometric mean with the more room
# above the step's: 18 times over it, 11 under the control.
GRAD_COSINE_MIN = 0.9998
# How many times the bfloat16 twin's error a step's may be, leaf by leaf
# and on the angle, in the tests (`check_gradient` at a batch of 8).  It
# is NOT named `STATED_RATIO`, for `reference/glm_moe_lite.py`'s reason:
# the driver would then ask `sampling_noise` to split the cell's batch of
# ONE sequence into 8 equal parts and raise (PERF.md section 7).
TWIN_RATIO = 3.0
# Sequences a reference program takes at once.
CHUNK = 1


class Sizes(NamedTuple):
    """What the programs below are compiled for (hashable)."""

    heads: int
    kv_heads: int
    head_dim: int
    window: int
    rope: Rope
    eps: float
    top_k: int
    held_first: int
    held_count: int
    tower: Optional[str]


def sizes_of(config: dict, tower) -> Sizes:
    first, count = config["held_experts"]
    dim = config["head_dim"]
    inv_freq = float(config["rope_theta"]) ** (
        -np.arange(0, dim, 2, dtype=np.float64) / dim
    )
    return Sizes(
        heads=config["num_attention_heads"],
        kv_heads=config["num_key_value_heads"], head_dim=dim,
        window=config["sliding_window_size"],
        rope=Rope(dim, tuple(inv_freq.tolist()), 1.0),
        eps=config["rms_norm_eps"],
        top_k=config["moe_num_active_primary_experts"],
        held_first=first, held_count=count, tower=tower,
    )


def layers_of(config: dict):
    """For each published layer the cut holds (`layers_held`, 0-based):
    True where it sees a band and carries rotary, False where it sees the
    whole causal sequence with no positions.  The two published lists say
    the same of every layer."""
    bands, turns = config["sliding_window_layout"], config["rope_layout"]
    for i in config["layers_held"]:
        if bands[i] != turns[i]:
            raise ValueError(
                f"layer {i}: sliding_window_layout {bands[i]}, rope_layout "
                f"{turns[i]}"
            )
    return [bool(bands[i]) for i in config["layers_held"]]


# ---- the layers ---------------------------------------------------------


def routing(x, router_kernel, top_k: int):
    """(chosen (L, k) expert numbers, their weights (L, k)) from the
    BLOCK'S INPUT x (L, d): the top k of the float32 logits, then a
    softmax over those k."""
    logits = x @ router_kernel                             # float32 router
    _, chosen = jax.lax.top_k(jax.lax.stop_gradient(logits), top_k)
    return chosen, jax.nn.softmax(
        jnp.take_along_axis(logits, chosen, axis=1), axis=1
    )


def attention(x, p, s: Sizes, banded: bool, q):
    """x (L, d) -> (L, d), one sequence."""
    length = x.shape[0]
    heads, kv_heads, dim = s.heads, s.kv_heads, s.head_dim
    queries = matmul(x, p["q"]["kernel"], q).reshape(length, heads, dim)
    keys = matmul(x, p["k"]["kernel"], q).reshape(length, kv_heads, dim)
    if banded:
        queries, keys = q(rotary(queries, s.rope)), q(rotary(keys, s.rope))
    values = matmul(x, p["v"]["kernel"], q).reshape(length, kv_heads, dim)
    one_group = jax.checkpoint(lambda args: group_attention(
        *args, scale=dim ** -0.5, window=s.window if banded else None,
        quant=q,
    ))
    out = jax.lax.map(one_group, (
        queries.reshape(length, kv_heads, heads // kv_heads, dim).transpose(
            1, 2, 0, 3
        ),
        keys.transpose(1, 0, 2), values.transpose(1, 0, 2),
    ))                                                     # (Hkv, G, L, D)
    out = out.transpose(2, 0, 1, 3).reshape(length, heads * dim)
    return matmul(out, p["o"]["kernel"], q)


def experts(x, chosen, weights, p, s: Sizes, q):
    """This holder's part of the routed experts, float32 out: every held
    ReGLU expert over ALL tokens x (L, d), times the weight the routing
    gave it (zero where it was not among the token's top k)."""

    @jax.checkpoint
    def expert(x, w_gate_up, w_down, weight):
        """One expert over all tokens, times its weight a token; rebuilt
        in the backward, so a layer keeps no expert's output."""
        gate, up = jnp.split(q(q(x) @ q(w_gate_up)), 2, axis=-1)
        return weight[:, None] * q(q(jax.nn.relu(gate) * up) @ q(w_down))

    def add_one(out, held):
        number, w_gate_up, w_down = held
        weight = jnp.sum(jnp.where(chosen == number, weights, 0.0), axis=1)
        return out + expert(x, w_gate_up, w_down, weight), None

    out, _ = jax.lax.scan(add_one, jnp.zeros_like(x), (
        s.held_first + jnp.arange(s.held_count),
        p["expert_w_gate_up"], p["expert_w_down"],
    ))
    return out


def block(p, x, s: Sizes, banded: bool):
    """One decoder block over one sequence (L, d); the residual stream
    is in the stated type, as the program's is."""
    q = rounded_to(s.tower)
    held = p["moe"]["routed"]
    # before anything else of the block: the router reads x as it came in
    chosen, weights = routing(x, held["router_kernel"], s.top_k)
    y = q(rms_norm(x, p["attn_norm"]["scale"], s.eps))
    h = q(x + attention(y, p["attn"], s, banded, q))
    y = q(rms_norm(h, p["ffn_norm"]["scale"], s.eps))
    return q(h + q(experts(y, chosen, weights, held, s, q)))


def tail(p, x, ids, s: Sizes):
    """The final norm, the head and the loss of one sequence: x (L, d),
    ids (L,) -> the mean over the L - 1 positions that have a target."""
    q = rounded_to(s.tower)
    return blocked_nll(
        q(rms_norm(x, p["final_norm"]["scale"], s.eps)), p["lm_head_kernel"],
        jnp.roll(ids, -1), q,
    )[:ids.shape[0] - 1].mean()


# ---- the programs: jitted once, here ------------------------------------


@functools.partial(jax.jit, static_argnames=("s", "banded"))
def _block_fwd(p, x, s, banded):
    return jax.vmap(lambda row: block(p, row, s, banded))(x)


@functools.partial(jax.jit, static_argnames=("s", "banded"))
def _block_bwd(p, x, g, s, banded):
    """(gradient of the block's parameters, of its input)."""
    _, vjp = jax.vjp(
        lambda p, x: jax.vmap(lambda row: block(p, row, s, banded))(x), p, x
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
        for i, banded in enumerate(layers):
            p = _device(tree[f"layer_{i}"])
            for a in acts:
                a.append(_block_fwd(p, a[-1], s, banded))
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
        for i, banded in reversed(list(enumerate(layers))):
            p = _device(tree[f"layer_{i}"])
            total = None
            for n, a in enumerate(acts):
                gp, flowing[n] = _block_bwd(p, a.pop(), flowing[n], s, banded)
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
