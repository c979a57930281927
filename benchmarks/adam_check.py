"""What one optimizer step did, read back from its state.

The train step keeps no gradient, but Adam's first moment does:
mu' = b1 mu + (1 - b1) g, so g = (mu' - b1 mu) / (1 - b1) is the gradient
the step's own program computed, all-reduce and all, to f32 rounding.
From it the second moment and the parameter update that optax's `adam` /
`adamw` must have made follow in closed form.  numpy on flat
{leaf name: array} dicts; the tests run it against optax.
"""

from __future__ import annotations

import numpy as np


def hyper(config: dict) -> dict:
    """The optimizer as the configuration file states it."""
    return {
        "lr": float(config["learning_rate"]),
        "b1": float(config.get("adam_b1", 0.9)),
        "b2": float(config.get("adam_b2", 0.999)),
        "eps": float(config.get("adam_eps", 1e-8)),
        "weight_decay": float(config.get("weight_decay", 0.0))
        if config["optimizer"] == "adamw" else 0.0,
    }


def recovered_gradient(mu_before, mu_after, b1: float):
    return (
        np.asarray(mu_after, np.float32)
        - np.float32(b1) * np.asarray(mu_before, np.float32)
    ) / np.float32(1.0 - b1)


def expected_nu(nu_before, gradient, b2: float):
    return np.float32(b2) * np.asarray(nu_before, np.float32) + np.float32(
        1.0 - b2
    ) * np.square(gradient)


def expected_delta(param_before, mu_after, nu_after, count_after: int, h):
    """parameter' - parameter of optax.adam / adamw at step `count_after`
    (1-based), from the moments the step left."""
    mu_hat = np.asarray(mu_after, np.float64) / (1.0 - h["b1"] ** count_after)
    nu_hat = np.asarray(nu_after, np.float64) / (1.0 - h["b2"] ** count_after)
    direction = mu_hat / (np.sqrt(nu_hat) + h["eps"])
    direction += h["weight_decay"] * np.asarray(param_before, np.float64)
    return (-h["lr"] * direction).astype(np.float32)


def rel_l2(got, want) -> float:
    """|got - want| / |want|; |got| where the wanted one is all zero."""
    got = np.asarray(got, np.float64).ravel()
    want = np.asarray(want, np.float64).ravel()
    norm = float(np.linalg.norm(want))
    return float(np.linalg.norm(got - want)) / norm if norm else float(
        np.linalg.norm(got)
    )


def share(error: float, allowed: float) -> float:
    """An error's size over what is allowed: at most 1 passes; with no
    room at all, 0 for no error and inf otherwise."""
    error = abs(float(error))
    return error / allowed if allowed else (0.0 if not error else float("inf"))


def along_across(error, want) -> tuple:
    """An error of `want` as (its signed length along `want`, the L2 norm
    of what is left across it).  Along `want` an error is a wrong scale
    of the leaf, one number; across it, a wrong shape.  Where `want` is
    all zero, all of the error is across."""
    error = np.asarray(error, np.float64).ravel()
    want = np.asarray(want, np.float64).ravel()
    norm = float(np.linalg.norm(want))
    along = float(np.dot(error, want)) / norm if norm else 0.0
    across = float(np.sqrt(max(float(np.dot(error, error)) - along ** 2, 0.0)))
    return along, across


def standard_error(parts, mean) -> float:
    """L2 standard error of `mean`, the mean of the K rows of `parts`
    taken as independent draws: sqrt(sum |row - mean|**2 / (K (K - 1)))."""
    parts = np.asarray(parts, np.float64)
    spread = parts.reshape(len(parts), -1) - np.asarray(
        mean, np.float64
    ).ravel()
    return float(np.sqrt(np.sum(np.square(spread)) / (
        len(parts) * (len(parts) - 1)
    )))


def excess(got, want, rel: float, rounded) -> float:
    """|got - want| over what is allowed: `rel` of |want| plus the f32
    rounding of the quantity both were rounded into (`rounded`: a sum
    keeps 2**-23 of its size, whatever the size of the term added).  At
    most 1 where the two agree."""
    got = np.asarray(got, np.float64).ravel()
    want = np.asarray(want, np.float64).ravel()
    allowed = rel * float(np.linalg.norm(want)) + 4 * 2.0 ** -23 * float(
        np.linalg.norm(np.asarray(rounded, np.float64))
    )
    return float(np.linalg.norm(got - want)) / allowed if allowed else (
        0.0 if not got.any() else float("inf")
    )


def cosine(got: dict, want: dict) -> float:
    """Of all leaves as one vector."""
    dot = sum(
        float(np.vdot(np.asarray(got[k], np.float64),
                      np.asarray(want[k], np.float64))) for k in want
    )
    norms = [
        np.sqrt(sum(float(np.sum(np.square(np.asarray(t[k], np.float64))))
                    for k in want)) for t in (got, want)
    ]
    return float(dot / (norms[0] * norms[1])) if norms[0] and norms[1] else 0.0


def cosine_floor(twin_cosine: float, ratio: float, eps: float) -> float:
    """The least cosine against the reference that passes where the
    stated type's own twin makes `twin_cosine` on the same parameters and
    batch: an angle of at most `ratio` times the twin's, or that many
    roundings (`eps` / 2, the type's) where the twin's has cancelled.
    1 - cosine is half the angle squared, so the ratio enters squared."""
    rounding = (eps / 2) ** 2 / 2
    return 1.0 - ratio ** 2 * max(1.0 - twin_cosine, rounding)

