"""Embedding-ceiling probes (VERDICT r4 stretch): row padding to 128B
lanes, id-sorted gather locality, and combined effects — measured with
the DCE-proof discipline of docs/embedding_design_note.md (anchored
fori_loop bodies whose results feed the carry; value-fetch sync).

Run on the TPU chip:  python scripts/probe_embedding_ceiling.py
Adopt nothing without a measured win; update the design note either way.
"""

from __future__ import annotations

import os
import sys
import time

import numpy as np

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _ROOT)

from elasticdl_tpu.common.virtual_mesh import (  # noqa: E402
    enable_compile_cache,
)

enable_compile_cache()

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402


def timed(fn, *args, iters=24):
    """Anchored loop: fn(*args) -> scalar contribution; the carry feeds
    back so XLA cannot hoist or DCE the body."""

    def loop(*a):
        def body(_, acc):
            return acc + fn(*a, acc)

        return jax.lax.fori_loop(0, iters, body, jnp.zeros((), jnp.float32))

    f = jax.jit(loop)
    jax.device_get(f(*args))
    t0 = time.perf_counter()
    jax.device_get(f(*args))
    return (time.perf_counter() - t0) / iters


def main():
    rows = 1 << 20
    n_ids = 1_703_936  # 65536 batch x 26 fields
    rng = np.random.RandomState(0)
    ids = jnp.asarray(
        (rng.zipf(1.5, size=n_ids) % rows).astype(np.int32)
    )
    ids_sorted = jnp.sort(ids)
    from elasticdl_tpu.layers.embedding import _lookup

    results = {}
    for width, label in [(16, "16 f32 (64B rows)"), (32, "32 f32 (128B rows)")]:
        table = jnp.asarray(
            rng.rand(rows, width).astype(np.float32)
        )

        def gather_probe(t, i, acc):
            # acc feeds the ids so the gather depends on the carry
            return _lookup(t, i + 0 * acc.astype(jnp.int32)).sum()

        dt = timed(gather_probe, table, ids)
        results[f"gather random {label}"] = dt
        dt_sorted = timed(gather_probe, table, ids_sorted)
        results[f"gather sorted {label}"] = dt_sorted

        def fwd_bwd_probe(t, i, acc):
            grad = jax.grad(lambda tt: (_lookup(tt, i) ** 2).sum())(
                t + 0.0 * acc
            )
            # consume the WHOLE gradient (warning 4: partial consumption
            # of a scatter output can elide most of its work)
            return grad.sum()

        dt_fb = timed(fwd_bwd_probe, table, ids, iters=12)
        results[f"fwd+bwd random {label}"] = dt_fb

    # sorted-forward variant: sort + gather + inverse permute vs plain
    def sorted_fwd_probe(t, i, acc):
        perm = jnp.argsort(i + 0 * acc.astype(jnp.int32))
        got = _lookup(t, i[perm])
        inv = jnp.zeros_like(perm).at[perm].set(
            jnp.arange(len(perm), dtype=perm.dtype)
        )
        return got[inv[0]].sum()

    table16 = jnp.asarray(rng.rand(rows, 16).astype(np.float32))
    results["sort+gather+unpermute 16 f32"] = timed(
        sorted_fwd_probe, table16, ids
    )

    # Scatter probes with the TABLE AS THE CARRY — design-note warning 4:
    # consuming only out[0,0] of a zero-initialized scatter lets XLA
    # elide most of the work (reads ~16ms instead of the real ~123ms).
    grads = jnp.asarray(rng.rand(n_ids, 16).astype(np.float32))

    def timed_carry(fn, init, *args, iters=12):
        def loop(init, *a):
            def body(_, carry):
                return fn(carry, *a)

            return jax.lax.fori_loop(0, iters, body, init)[0, 0]

        f = jax.jit(loop)
        jax.device_get(f(init, *args))
        t0 = time.perf_counter()
        jax.device_get(f(init, *args))
        return (time.perf_counter() - t0) / iters

    from jax.lax import GatherScatterMode as _GSM

    for mode, mlabel in [("drop", "drop"), (_GSM.PROMISE_IN_BOUNDS, "PIB")]:
        results[f"scatter-add zipf carried [{mlabel}]"] = timed_carry(
            lambda t, i, g, m=mode: t.at[i].add(g, mode=m),
            table16, ids, grads,
        )
    # unique-vs-duplicate at EQUAL id counts (1M each; a 1.7M 'unique'
    # set cannot exist in a 1M-row table)
    m = rows
    uniq_m = jnp.asarray(rng.permutation(rows).astype(np.int32))
    zipf_m = ids[:m]
    grads_m = grads[:m]
    results["scatter-add 1M all-unique carried"] = timed_carry(
        lambda t, i, g: t.at[i].add(g, mode=_GSM.PROMISE_IN_BOUNDS),
        table16, uniq_m, grads_m,
    )
    results["scatter-add 1M zipf carried"] = timed_carry(
        lambda t, i, g: t.at[i].add(g, mode=_GSM.PROMISE_IN_BOUNDS),
        table16, zipf_m, grads_m,
    )

    for name, dt in results.items():
        per_row = dt / n_ids
        print(
            f"{name:38s} {dt*1e3:8.2f} ms  "
            f"({n_ids/dt/1e6:6.1f}M rows/s)"
        )


if __name__ == "__main__":
    main()
