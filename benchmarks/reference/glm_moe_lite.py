"""GLM-4.7-Flash (`glm4_moe_lite`) on the train path as plain `jax.numpy`
in float32 at the highest matmul precision: forward, loss and gradients,
with no kernel, no sort, no remat and no bfloat16.

The equations, from the catalog row's `config` (hidden d, RMSNorm eps
1e-5, no biases, SiLU; `assumed` items are in the configuration file):

    block    h = x + MLA(RMSNorm(x));  y = h + FFN(RMSNorm(h))
    MLA      cq = RMSNorm(x Wqa);  q = cq Wqb  -> heads x (nope + rope)
             [ckv, kr] = x Wkva;  ckv = RMSNorm(ckv)
             [k_nope, v] = ckv Wkvb  -> heads x (nope + v)
             rotary (theta, halves pairing) on q's rope columns and on
             kr, ONE head shared by all;  k = [k_nope, kr]
             o = softmax_causal(q k^T / sqrt(nope + rope)) v Wo
    dense    (silu(x Wg) * (x Wu)) Wd                       (layer 0)
    MoE      s = sigmoid(x Wr) over ALL experts, float32
             S = top_k(s + b)          b = 0 here: `params` carry no buffer
             w_i = scaling * s_i / sum_{j in S} s_j        (all of S)
             FFN(x) = SwiGLU_shared(x) + sum_{i in S, i HELD} w_i SwiGLU_i(x)
    MTP      h' = [RMSNorm(h_L) ; RMSNorm(Emb(x_{t+1}))] Weh, one more MoE
             block, its own final RMSNorm, the main embedding and head
    loss     CE(head(RMSNorm(h_L)), x_{t+1}) + lambda CE(head(MTP), x_{t+2}),
             each a mean over the positions that have a target

The cut is the configuration's: the held experts (`held_experts`), the
sliced vocabulary, the depth.  What absent experts would add is left out
here as in the program.  Each held expert is applied to ALL tokens and
masked by its weight.

Departures of this file from a one-function reference, each for memory
beside the live train state (8.5 GB stays on the chip during the check):
the layers are walked with `jax.vjp`, ONE layer's float32 parameters and
gradient on the device at a time; the batch goes a sequence at a time;
attention goes a head at a time (`lax.map`), rebuilt in the backward
(one head's 4096 x 4096 weights are 67 MB, twenty are 1.3 GB before their
gradient); the head's logits go a block of tokens at a time.  None of
them changes a number past float32 summation order.

`tower` is the twin (`loss_and_grads(..., tower="bfloat16")`): the same
network with what the program rounds when the configuration states
bfloat16 rounded the same way (flax's `Dense(dtype=bfloat16)`: inputs,
kernel and output of every matmul, the backward signal with them; the
residual stream, attention's operands and probabilities, the experts'
grouped products and the head's operands), and the router, every norm's
statistics, the softmax and the loss in float32 as the program keeps
them.  `tower="float8_e4m3fn"` is the check's control, the type below.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import trees
from benchmarks.reference.deepfm import rounded_to

# The loss is main + 0.3 MTP, 13.5 at the seeded weights (1.3 x (ln 19,360
# + 0.5)) and 5-7 where the check lands.  It is a mean over 16,380
# positions of terms whose logits carry bfloat16's roundings, and the
# errors are independent across positions, so the MEAN moves far less
# than one term does.  On the chip at the cell's size (PERF.md section 6,
# PR 29): the job's step 0.8e-3 .. 2.3e-3 from this reference on ten
# seeds, the bfloat16 twin 1.2e-3, the float8 control 0.27.  A dropped layer, a
# missing MTP term, a wrong shift or an un-renormalised router weight
# move it by O(0.1) and more.
LOSS_ATOL = 1e-2
# L2 error allowed on a gradient leaf (first matching pattern), RELATIVE
# TO the leaf's reference norm.  The cell's batch is 4 sequences and
# `drivers/train.py: sampling_noise` wants a batch of whole eighths, so
# the check runs WITHOUT the twin-held rule (no `STATED_RATIO` name
# below; `TWIN_RATIO` says why) and every leaf is held to these shares
# of its own norm.  No leaf's gradient crosses zero here: each is a sum
# over 16,384 tokens of a language-model loss with no labels to balance.
# Each limit stands between two readings on the chip at the cell's size
# (PERF.md section 6, PR 29: the job's step over its seeds, worst leaf of
# the class; the float8 control on the same state and batch), near their
# geometric mean; the bfloat16 twin reads within 8% of the step on every
# class, so the step's error IS the stated type's.
#
# expert stacks: a top-k flip between types moves a token to another
#   expert, so rows come and go from an expert's gradient: the step
#   0.150 .. 0.168 on ten seeds (the twin 0.147 where the step read
#   0.156), the control 0.61.  An error of 0.15 is what
#   about 1% of slots flipping gives (sqrt(2 x 0.011)): the scores of
#   neighbouring experts lie 1/64 of their spread apart and the hidden
#   state carries 2**-9.
# router: its gradient comes through the renormalised weights of the
#   chosen four alone, and a flipped slot changes which four: the step
#   0.063 .. 0.077 (twin 0.060 where the step read 0.065), the control
#   0.235.
# every other leaf: the step 0.027 (twin 0.025), the control 0.37.
LEAF_REL_L2 = (
    ("expert_w_", 3e-1),
    ("router_kernel$", 1.2e-1),
    ("", 1e-1),
)
# All leaves as one vector against the reference's: the step reads 1 -
# cosine 1.0e-4 .. 2.0e-4 (the twin 1.1e-4), the control 2.5e-2.
GRAD_COSINE_MIN = 0.998
# How many times the bfloat16 twin's error a step's may be, leaf by leaf
# and on the angle (drivers/train.py: `leaf_shares`, `cosine_floor`).
# The twin is here (`tower=`) and `part_grads` is here, and the tests
# hold both to `check_gradient` at a batch of 8.  It is NOT yet named
# `STATED_RATIO`: the driver would then ask `sampling_noise` to split the
# cell's batch of 4 sequences into 8 equal parts and raise.  The day that
# splits by something a batch of 4 has (tokens, or as many parts as there
# are examples), rename this and the cell's check is twin-held with no
# other change (PERF.md section 7).
TWIN_RATIO = 3.0
# Sequences a reference program takes at once, and the head's block.
CHUNK = 1
CE_BLOCK = 2048


class Sizes(NamedTuple):
    """What the programs below are compiled for (hashable)."""

    heads: int
    nope: int
    rope: int
    v_dim: int
    kv_rank: int
    theta: float
    eps: float
    top_k: int
    scaling: float
    held_first: int
    held_count: int
    mtp_weight: float
    tower: Optional[str]


def sizes_of(config: dict, tower) -> Sizes:
    first, count = config["held_experts"]
    return Sizes(
        heads=config["num_attention_heads"],
        nope=config["qk_nope_head_dim"], rope=config["qk_rope_head_dim"],
        v_dim=config["v_head_dim"], kv_rank=config["kv_lora_rank"],
        theta=float(config["rope_theta"]), eps=config["rms_norm_eps"],
        top_k=config["num_experts_per_tok"],
        scaling=config["routed_scaling_factor"],
        held_first=first, held_count=count,
        mtp_weight=config["mtp_loss_weight"], tower=tower,
    )


# ---- the layers ---------------------------------------------------------


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(
        jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps
    ) * scale


def matmul(x, kernel, q):
    """x @ kernel as flax's Dense(dtype=) rounds it under `q`."""
    return q(q(x) @ q(kernel))


def rotary(x, theta):
    """(L, H, R), halves pairing, position = row."""
    length, width = x.shape[0], x.shape[-1]
    inv_freq = theta ** (-jnp.arange(0, width, 2, dtype=jnp.float32) / width)
    angle = jnp.arange(length, dtype=jnp.float32)[:, None] * inv_freq[None]
    cos, sin = jnp.cos(angle)[:, None], jnp.sin(angle)[:, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def one_head(q, k, v, scale, quant):
    """(L, D) x (L, D) x (L, Dv): causal softmax attention of one head."""
    logits = (quant(q) @ quant(k).T) * scale
    length = q.shape[0]
    mask = jnp.arange(length)[:, None] >= jnp.arange(length)[None, :]
    logits = jnp.where(mask, logits, -jnp.inf)
    logits = logits - logits.max(axis=-1, keepdims=True)
    weights = jnp.exp(logits)
    total = weights.sum(axis=-1, keepdims=True)
    # the program's kernels round the unnormalised weights to the stated
    # type for the product with v and divide after, in float32
    return quant((quant(weights) @ quant(v)) / total)


def mla(x, p, s: Sizes, q):
    """x (L, d) -> (L, d), one sequence."""
    length = x.shape[0]
    cq = q(rms_norm(matmul(x, p["q_a"]["kernel"], q),
                    p["q_a_norm"]["scale"], s.eps))
    queries = matmul(cq, p["q_b"]["kernel"], q).reshape(
        length, s.heads, s.nope + s.rope
    )
    ckv, k_rope = jnp.split(
        matmul(x, p["kv_a"]["kernel"], q), [s.kv_rank], axis=-1
    )
    ckv = q(rms_norm(ckv, p["kv_a_norm"]["scale"], s.eps))
    k_nope, values = jnp.split(
        matmul(ckv, p["kv_b"]["kernel"], q).reshape(
            length, s.heads, s.nope + s.v_dim
        ), [s.nope], axis=-1,
    )
    q_nope, q_rope = jnp.split(queries, [s.nope], axis=-1)
    queries = jnp.concatenate([q_nope, q(rotary(q_rope, s.theta))], axis=-1)
    k_rope = q(rotary(k_rope[:, None, :], s.theta))
    keys = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope, (length, s.heads, s.rope))], -1
    )
    scale = (s.nope + s.rope) ** -0.5
    head = jax.checkpoint(
        lambda args: one_head(*args, scale=scale, quant=q)
    )
    out = jax.lax.map(head, tuple(
        t.transpose(1, 0, 2) for t in (queries, keys, values)
    ))                                                     # (H, L, Dv)
    out = out.transpose(1, 0, 2).reshape(length, s.heads * s.v_dim)
    return matmul(out, p["o"]["kernel"], q)


def swiglu(x, p, q):
    gate, up = jnp.split(matmul(x, p["gate_up"]["kernel"], q), 2, axis=-1)
    return matmul(jax.nn.silu(gate) * up, p["down"]["kernel"], q)


def routed(x, p, s: Sizes, q):
    """This holder's part of the routed experts, float32 out: every held
    expert over ALL tokens, times the weight the router gave it (zero
    where it was not among the token's top k)."""
    scores = jax.nn.sigmoid(x @ p["router_kernel"])        # float32 router
    _, chosen = jax.lax.top_k(jax.lax.stop_gradient(scores), s.top_k)
    picked = jnp.take_along_axis(scores, chosen, axis=1)
    weights = s.scaling * picked / picked.sum(axis=1, keepdims=True)

    @jax.checkpoint
    def expert(x, w_gate_up, w_down):
        gate, up = jnp.split(q(q(x) @ q(w_gate_up)), 2, axis=-1)
        return q(q(jax.nn.silu(gate) * up) @ q(w_down))

    out = jnp.zeros_like(x)
    for e in range(s.held_count):
        weight = jnp.sum(
            jnp.where(chosen == s.held_first + e, weights, 0.0), axis=1
        )
        out = out + weight[:, None] * expert(
            x, p["expert_w_gate_up"][e], p["expert_w_down"][e]
        )
    return out


def block(p, x, s: Sizes, moe: bool):
    """One decoder block over one sequence (L, d); the residual stream
    is in the stated type, as the program's is."""
    q = rounded_to(s.tower)
    x = q(x + mla(q(rms_norm(x, p["attn_norm"]["scale"], s.eps)),
                  p["mla"], s, q))
    y = q(rms_norm(x, p["ffn_norm"]["scale"], s.eps))
    if moe:
        y = q(routed(y, p["moe"]["routed"], s, q)
              + swiglu(y, p["moe"]["shared"], q))
    else:
        y = swiglu(y, p["mlp"], q)
    return q(x + y)


def blocked_nll(h, head_kernel, targets, q):
    """(tokens,) -log softmax(h @ head_kernel)[target], CE_BLOCK tokens
    at a time and rebuilt in the backward."""
    tokens, hidden = h.shape
    size = CE_BLOCK if tokens % CE_BLOCK == 0 else tokens

    @jax.checkpoint
    def one(args):
        h_block, t_block = args
        logits = q(h_block) @ q(head_kernel)               # float32 out
        picked = jnp.take_along_axis(logits, t_block[:, None], axis=1)[:, 0]
        return jax.nn.logsumexp(logits, axis=-1) - picked

    return jax.lax.map(one, (
        h.reshape(tokens // size, size, hidden),
        targets.reshape(tokens // size, size),
    )).reshape(tokens)


def tail(p, x, ids, s: Sizes):
    """The final norm, the head, the MTP module and the loss of one
    sequence: x (L, d), ids (L,) -> main + lambda MTP."""
    q = rounded_to(s.tower)
    length = ids.shape[0]
    head = p["lm_head_kernel"]
    main = blocked_nll(
        q(rms_norm(x, p["final_norm"]["scale"], s.eps)), head,
        jnp.roll(ids, -1), q,
    )[:length - 1].mean()
    if "mtp_block" not in p:
        return main
    table = p["token_embedding"]["embedding"]
    joined = jnp.concatenate([
        q(rms_norm(x, p["mtp_h_norm"]["scale"], s.eps)),
        q(rms_norm(q(table[jnp.roll(ids, -1)]), p["mtp_e_norm"]["scale"],
                   s.eps)),
    ], axis=-1)
    y = block(p["mtp_block"], matmul(joined, p["mtp_eh_proj"]["kernel"], q),
              s, True)
    y = q(rms_norm(y, p["mtp_final_norm"]["scale"], s.eps))
    mtp = blocked_nll(y, head, jnp.roll(ids, -2), q)[:length - 2].mean()
    return main + s.mtp_weight * mtp


# ---- the programs: jitted once, here ------------------------------------


@functools.partial(jax.jit, static_argnames=("s",))
def _embed(table, ids, s):
    return rounded_to(s.tower)(table[ids])


@functools.partial(jax.jit, static_argnames=("s", "moe"))
def _block_fwd(p, x, s, moe):
    return jax.vmap(lambda row: block(p, row, s, moe))(x)


@functools.partial(jax.jit, static_argnames=("s", "moe"))
def _block_bwd(p, x, g, s, moe):
    """(gradient of the block's parameters, of its input)."""
    _, vjp = jax.vjp(
        lambda p, x: jax.vmap(lambda row: block(p, row, s, moe))(x), p, x
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


@functools.partial(jax.jit, static_argnames=("rows",))
def _embed_grads(ids, g, rows):
    return jnp.zeros((rows, g.shape[-1]), jnp.float32).at[
        ids.reshape(-1)
    ].add(g.reshape(-1, g.shape[-1]))


TAIL_KEYS = ("final_norm", "lm_head_kernel", "token_embedding", "mtp_")


def _device(tree):
    return jax.tree.map(lambda v: jnp.asarray(v, jnp.float32), tree)


def _host(tree):
    return jax.tree.map(lambda v: np.asarray(v, np.float32), tree)


def _add(total, part):
    return part if total is None else jax.tree.map(jnp.add, total, part)


def _walk(params: dict, ids, config: dict, tower, weights):
    """(loss, nested gradient as host arrays) of sum_c weights[c] *
    (mean loss of chunk c), the chunks `CHUNK` sequences each in order.
    One layer's parameters and gradient are on the device at a time."""
    s = sizes_of(config, tower)
    tree = trees.nested(params)
    layers = config["num_hidden_layers"]
    dense_layers = config["first_k_dense_replace"]
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
        for i in range(layers):
            p = _device(tree[f"layer_{i}"])
            for a in acts:
                a.append(_block_fwd(p, a[-1], s, i >= dense_layers))
            del p
        p = _device({
            k: v for k, v in tree.items() if k.startswith(TAIL_KEYS)
        })
        loss, tail_grad, flowing = 0.0, None, []
        for a, c, w in zip(acts, chunks, weights):
            part, (gp, gx) = _tail_grads(p, a.pop(), c, jnp.float32(w), s)
            loss = loss + part
            tail_grad = _add(tail_grad, gp)
            flowing.append(gx)
        grads = _host(tail_grad)
        del p, tail_grad
        for i in reversed(range(layers)):
            p = _device(tree[f"layer_{i}"])
            total = None
            for n, a in enumerate(acts):
                gp, flowing[n] = _block_bwd(
                    p, a.pop(), flowing[n], s, i >= dense_layers
                )
                total = _add(total, gp)
            grads[f"layer_{i}"] = _host(total)
            del p, total
        rows = tree["token_embedding"]["embedding"].shape[0]
        through_input = None
        for c, g in zip(chunks, flowing):
            through_input = _add(through_input, _embed_grads(c, g, rows))
        table_grad = grads["token_embedding"]["embedding"]
        grads["token_embedding"]["embedding"] = table_grad + np.asarray(
            through_input
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
