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
matmuls run in bfloat16 with f32 parameters.

The reference works on the rows the batch touches and differentiates
with respect to THOSE, so it holds a (touched, 16) slice and never a
second 33.5M-row table; `cut` takes the same rows out of any tree shaped
like the parameters (the parameters, Adam's moments).
"""

from __future__ import annotations

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
# Relative L2 error allowed on a gradient leaf (first matching pattern).
# It accumulates bf16 roundings of the tower's activations and of the
# back-propagated signal through four matmuls: 0.7e-2 .. 1.6e-2 measured
# on the chip; a tower in an 8-bit type gives several 1e-1, a table
# gather or FM term in bf16, or a dropped field, O(1).
LEAF_REL_L2 = (("", 1e-1),)
# All leaves as one vector: its cosine against the reference's.
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
    rows = table_rows(np.asarray(sparse), config)
    unique, inverse = np.unique(rows, return_inverse=True)
    return unique, inverse.reshape(rows.shape)


def forward(emb_rows, lin_rows, dense_params, inverse, dense, config):
    """Logits from the touched rows.  `inverse[b, f]` indexes them."""
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
        h = jax.nn.relu(h @ layer["kernel"] + layer["bias"])
    out = dense_params["mlp_out"]
    deep = (h @ out["kernel"] + out["bias"])[..., 0]
    return wide + first.sum(axis=1) + fm2 + deep


def bce_with_logits(logits, labels):
    labels = labels.astype(jnp.float32)
    return jnp.mean(
        jnp.maximum(logits, 0) - logits * labels
        + jnp.log1p(jnp.exp(-jnp.abs(logits)))
    )


TABLES = ("fm_embedding", "fm_linear")


def loss_and_grads(params: dict, features, labels, config):
    """(loss, {leaf name: gradient}) from the flat parameters `cut`
    gives: the two tables as their touched rows, the rest whole."""
    _, inverse = touched(features["sparse"], config)
    params = {k: jnp.asarray(v, jnp.float32) for k, v in params.items()}
    dense = jnp.asarray(features["dense"], jnp.float32)
    labels = jnp.asarray(labels)
    inverse = jnp.asarray(inverse)

    def loss_of(params):
        rest = trees.nested({k: v for k, v in params.items() if k not in TABLES})
        return bce_with_logits(
            forward(params["fm_embedding"], params["fm_linear"], rest,
                    inverse, dense, config),
            labels,
        )

    with jax.default_matmul_precision("highest"):
        return jax.jit(jax.value_and_grad(loss_of))(params)


def cut(tree, features, config) -> dict:
    """{leaf name: array} of a parameter-shaped tree: the two tables cut
    to the rows this batch touches (in `touched`'s order), the other
    leaves whole."""
    unique, _ = touched(features["sparse"], config)
    rows = jnp.asarray(unique)
    out = {name: tree[name]["embedding"][rows] for name in TABLES}
    out.update(trees.flat({k: v for k, v in tree.items() if k not in TABLES}))
    return out
