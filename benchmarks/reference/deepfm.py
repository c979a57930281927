"""DeepFM (arXiv:1703.04247) as plain `jax.numpy` in float32 at the
highest matmul precision: forward, loss and gradients, with no arena, no
custom VJP, no bf16 and no wire format.

It follows the zoo model's published structure: ONE hashed table shared
by the 26 fields (field-offset ids, Knuth multiplicative hash, mod the
capacity — that addressing IS the configuration, so it is restated here
in numpy), FM first and second order, a dense linear term, and the deep
tower over [dense | embeddings].  Departures of the system from this
reference, which the tolerances below allow for: `--use_bf16` rounds the
13 dense FEATURES to bfloat16 before the log1p squash, and the tower's
matmuls run in bfloat16 with f32 parameters.  `forward(..., tower=)` is
the reference's twin that makes the same departures: the yardstick for
what they may cost a gradient leaf (`STATED_RATIO`).

The reference works on the rows the batch touches and differentiates
with respect to THOSE, so it holds a (touched, 16) slice and never a
second 33.5M-row table; `cut` takes the same rows out of any tree shaped
like the parameters (the parameters, Adam's moments).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import trees

# bf16 has 8 bits of mantissa: one rounding is 2**-9 = 2e-3 relative.
# The loss is a mean of per-example terms whose logits carry a few such
# roundings, so the means agree far inside 1e-2 (2.4e-07 .. 1.9e-06 on
# the chip, PERF.md section 6); a dropped term (FM, linear, tower) or a
# wrong hash moves the loss by O(0.1).
LOSS_ATOL = 1e-2
# L2 error allowed on a gradient leaf (first matching pattern), RELATIVE
# TO the leaf's reference norm: all of the rule where the configuration
# states float32; where it states bfloat16, of the part of the error
# ALONG the reference's gradient only, and there relative to the norm or
# to the leaf's sampling noise, whichever is larger (drivers/train.py:
# `leaf_shares`).  A dropped term or field, a leaf halved, doubled or of
# the wrong sign reads 3 to 100 times what it allows
# (benchmarks/tests/test_leaf_rule.py).
LEAF_REL_L2 = (("", 1e-1),)
# Where the configuration states bfloat16 (`use_bf16`), what the step is
# held to is this reference's own twin with its tower in bfloat16
# (`loss_and_grads(..., tower="bfloat16")`) on the same parameters and
# batch, and this is how many times the twin's error the step's may be.
# It governs (1) each leaf (drivers/train.py: `leaf_shares`): the part of
# its error across the reference's gradient, and the part along it, each
# against that part of the twin's; (2) since PR 28, all leaves as one
# vector (`cosine_floor`): the angle to the reference's gradient against
# the twin's angle, so 1 - cosine against 9 times the twin's.  Read on
# the chip at B=65536 from the trained state.  Leaves (PERF.md section
# 6, PR 26): the job's step 0.66 .. 1.47 times its twin on its worst leaf
# (32 samples on 11 seeds; the bound at 3), the control (the twin in
# float8_e4m3fn in the step's place) 7.0 .. 88 times on its worst leaf
# (21 samples): 3 stands at twice the step's largest and under half the
# control's smallest.  As a share of the leaf's norm alone the same
# samples do not separate: the step 0.3e-2 .. 5.9e-2, the control 3.5e-2
# .. 2.0, because the norm swings 25-60x from step to step and neither
# error follows it.  The angle (PERF.md section 6, PR 28; 96 samples on
# 24 seeds at steps 113 .. 180): the step's 1 - cosine 0.63 .. 1.64 times
# its twin's (the bound at 9 = 3 squared), the control's 25.7 .. 11,000
# times: 9 stands at 5.5 times the step's largest and at 0.35 of the
# control's smallest.
STATED_RATIO = 3.0
# All leaves as one vector: the least cosine against the reference's
# that passes, where the configuration states float32.  Where it states
# bfloat16 this constant governs nothing in this file's model since PR
# 28: the cosine is held to the twin's (`STATED_RATIO`).  The whole
# gradient's norm crosses zero from step to step (0.045 .. 3.85 in 96
# samples on the chip) while bfloat16's error across it stays, and grows
# with the step (2.7e-3 in the median at step 113, 7.1e-3 at 177), so the
# sound step's worst 1 - cosine climbed from 1.1% of this constant's room
# at step 113 to 74% at step 177, and the check lands later the faster
# the program is; the fp8 control passed the constant in 76 samples of
# 92 (PERF.md section 6, PR 28).  A reference with no twin
# (reference/bert.py) keeps its own constant of this name.
GRAD_COSINE_MIN = 0.99

_MIX = np.uint32(2654435761)           # Knuth, 2**32 / phi
_FIELD_STRIDE = np.uint32(0x61C88647)  # the zoo's field-offset constant


def table_rows(sparse: np.ndarray, config: dict) -> np.ndarray:
    """(B, 26) raw ids -> rows of the shared table, uint32 wraparound."""
    fields = sparse.shape[1]
    with np.errstate(over="ignore"):
        ids = sparse.astype(np.uint32) + (
            np.arange(fields, dtype=np.uint32) * _FIELD_STRIDE
        )[None, :]
        ids = ids * _MIX
    return (ids % np.uint32(config["vocab_capacity"])).astype(np.int64)


def touched(sparse: np.ndarray, config: dict):
    """(rows, inverse): the table rows the batch touches, in order, and
    `inverse[b, f]`, which of them example b's field f reads.  `rows` is
    padded to the next power of two with the row past the table's end
    (`cut` reads zeros there and no example points at it), so the
    reference's programs have one shape whatever the seed drew and
    compile once a checkout."""
    rows = table_rows(np.asarray(sparse), config)
    unique, inverse = np.unique(rows, return_inverse=True)
    padded = 1 << max(len(unique) - 1, 0).bit_length()
    unique = np.concatenate([
        unique,
        np.full(padded - len(unique), config["vocab_capacity"], np.int64),
    ])
    return unique, inverse.reshape(rows.shape)


def rounded_to(kind):
    """x -> x rounded to the type `kind`, on the way forward and its
    cotangent on the way back; float32 everywhere else.  "bfloat16" is a
    cast; "float8_e4m3fn" takes one scale a tensor (its largest value
    goes to 448, the type's largest)."""
    if kind is None:
        return lambda x: x
    dtype = jnp.dtype(kind)

    def rounded(x):
        if dtype == jnp.bfloat16:
            return x.astype(dtype).astype(jnp.float32)
        scale = (jnp.max(jnp.abs(x)) + 1e-30) / float(jnp.finfo(dtype).max)
        return (x / scale).astype(dtype).astype(jnp.float32) * scale

    @jax.custom_vjp
    def q(x):
        return rounded(x)

    q.defvjp(lambda x: (rounded(x), None), lambda _, g: (rounded(g),))
    return q


def forward(emb_rows, lin_rows, dense_params, inverse, dense, config,
            tower=None):
    """Logits from the touched rows.  `inverse[b, f]` indexes them.
    `tower` names the type the job computes in where `--use_bf16` lets
    it: the dense features rounded to it on entry (`Trainer._cast`), and
    the deep tower as flax's `Dense(dtype=...)` does it, inputs, kernel,
    bias and the layer's output rounded to it and the backward signal
    with them (`rounded_to`).  None is the reference proper."""
    q = rounded_to(tower)
    dense = q(dense)
    emb = emb_rows[inverse]                      # (B, 26, k)
    first = lin_rows[inverse][..., 0]            # (B, 26)
    sum_f = emb.sum(axis=1)
    fm2 = 0.5 * (sum_f * sum_f - (emb * emb).sum(axis=1)).sum(axis=-1)
    dense_n = jnp.log1p(jnp.abs(dense)) * jnp.sign(dense)
    wide = (
        dense_n @ dense_params["dense_linear"]["kernel"]
        + dense_params["dense_linear"]["bias"]
    )[..., 0]
    h = jnp.concatenate([dense_n, emb.reshape(emb.shape[0], -1)], axis=-1)
    for i in range(len(config["mlp_dims"])):
        layer = dense_params[f"mlp_{i}"]
        h = jax.nn.relu(q(q(h) @ q(layer["kernel"]) + q(layer["bias"])))
    out = dense_params["mlp_out"]
    deep = q(q(h) @ q(out["kernel"]) + q(out["bias"]))[..., 0]
    return wide + first.sum(axis=1) + fm2 + deep


def bce_with_logits(logits, labels):
    labels = labels.astype(jnp.float32)
    return jnp.mean(
        jnp.maximum(logits, 0) - logits * labels
        + jnp.log1p(jnp.exp(-jnp.abs(logits)))
    )


TABLES = ("fm_embedding", "fm_linear")


@functools.partial(jax.jit, static_argnames=("mlp_dims", "tower"))
def _loss_and_grads(params, inverse, dense, labels, mlp_dims, tower):
    config = {"mlp_dims": mlp_dims}

    def loss_of(params):
        rest = trees.nested({k: v for k, v in params.items() if k not in TABLES})
        return bce_with_logits(
            forward(params["fm_embedding"], params["fm_linear"], rest,
                    inverse, dense, config, tower),
            labels,
        )

    return jax.value_and_grad(loss_of)(params)


def loss_and_grads(params: dict, features, labels, config, tower=None):
    """(loss, {leaf name: gradient}) from the flat parameters `cut`
    gives: the two tables as their touched rows, the rest whole.  With
    `tower` the same, its deep tower computed in that type (`forward`):
    "bfloat16" is what a configuration with `use_bf16` states, and the
    type below it, "float8_e4m3fn", is the check's control.  The batch
    comes to one jitted program as arguments, so one program serves
    every batch of a shape."""
    _, inverse = touched(features["sparse"], config)
    with jax.default_matmul_precision("highest"):
        return _loss_and_grads(
            {k: jnp.asarray(v, jnp.float32) for k, v in params.items()},
            jnp.asarray(inverse),
            jnp.asarray(features["dense"], jnp.float32),
            jnp.asarray(labels),
            tuple(config["mlp_dims"]), tower,
        )


@functools.partial(jax.jit, static_argnames=("mlp_dims", "parts"))
def _part_grads(params, inverse, dense, labels, mlp_dims, parts):
    split = lambda x: x.reshape((parts, -1) + x.shape[1:])
    return jax.vmap(
        lambda i, d, y: _loss_and_grads(params, i, d, y, mlp_dims, None)[1]
    )(split(inverse), split(dense), split(labels))


def part_grads(params: dict, features, labels, config, parts: int) -> dict:
    """{leaf name: (parts, ...) gradients}: `loss_and_grads`' gradient
    over each of `parts` equal runs of the batch's examples in turn, all
    on the parameters and the rows of the whole batch, so that their
    mean is the whole batch's gradient.  One program."""
    _, inverse = touched(features["sparse"], config)
    with jax.default_matmul_precision("highest"):
        return _part_grads(
            {k: jnp.asarray(v, jnp.float32) for k, v in params.items()},
            jnp.asarray(inverse),
            jnp.asarray(features["dense"], jnp.float32),
            jnp.asarray(labels),
            tuple(config["mlp_dims"]), parts,
        )


def cut(tree, features, config) -> dict:
    """{leaf name: array} of a parameter-shaped tree: the two tables cut
    to the rows this batch touches (in `touched`'s order), the other
    leaves whole."""
    unique, _ = touched(features["sparse"], config)
    rows = jnp.asarray(unique)
    out = {
        name: tree[name]["embedding"].at[rows].get(
            mode="fill", fill_value=0
        ) for name in TABLES
    }
    out.update(trees.flat({k: v for k, v in tree.items() if k not in TABLES}))
    return out
