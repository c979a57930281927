"""Pallas embedding scatter-add probe (VERDICT r4 item 7 — the one
untested idea against the measured ~14M random rows/s XLA scatter
ceiling, docs/embedding_design_note.md).

Measurement discipline: carried-table probes only (design-note warning
4 — a scatter whose output is partially consumed is elided by XLA), and
fused fori_loop with the result feeding the carry.

The Pallas candidate is measured at its BEST possible configuration: a
table tile fully resident in VMEM (no HBM row traffic at all), ids
scalar-prefetched to SMEM, one serial dynamic-index vector add per id.
TPU vector units cannot scatter (no per-lane indexed store), so EVERY
Pallas scatter design bottoms out in this serial per-id update loop —
if the VMEM-resident floor is already slower per id than XLA's
HBM-random-access scatter, the whole family is rejected a fortiori
(real tables are 64MB+, which would ADD per-row HBM DMAs on top).

Usage: python scripts/probe_pallas_scatter.py [--ids 262144] [--rows 8192]
"""

from __future__ import annotations

import argparse
import functools
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
from jax.experimental import pallas as pl  # noqa: E402


def timed_carried(fn, table, *args, iters=8):
    """Fused loop; the written table IS the carry (warning 4)."""

    def loop(t, *a):
        def body(_, carry):
            return fn(carry, *a)

        out = jax.lax.fori_loop(0, iters, body, t)
        return out

    g = jax.jit(loop)
    jax.device_get(g(table, *args)[0, 0])
    t0 = time.perf_counter()
    jax.device_get(g(table, *args)[0, 0])
    return (time.perf_counter() - t0) / iters


def xla_scatter_add(table, ids, grads):
    return table.at[ids].add(
        grads, mode="drop", unique_indices=False
    )


def _pallas_kernel(ids_ref, grads_ref, table_in_ref, table_out_ref, *,
                   block_ids: int):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        table_out_ref[...] = table_in_ref[...]

    def body(j, _):
        row = ids_ref[i * block_ids + j]
        cur = table_out_ref[pl.ds(row, 1), :]
        table_out_ref[pl.ds(row, 1), :] = (
            cur + grads_ref[pl.ds(j, 1), :]
        )
        return 0

    jax.lax.fori_loop(0, block_ids, body, 0)


def pallas_scatter_add(table, ids, grads, block_ids=8192):
    n = ids.shape[0]
    rows, dim = table.shape
    grid = (n // block_ids,)
    from jax.experimental.pallas import tpu as pltpu

    return pl.pallas_call(
        functools.partial(_pallas_kernel, block_ids=block_ids),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,      # ids -> SMEM
            grid=grid,
            in_specs=[
                pl.BlockSpec((block_ids, dim), lambda i, ids: (i, 0)),
                pl.BlockSpec((rows, dim), lambda i, ids: (0, 0)),
            ],
            out_specs=pl.BlockSpec((rows, dim), lambda i, ids: (0, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((rows, dim), table.dtype),
        interpret=jax.default_backend() != "tpu",
    )(ids, grads, table)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--ids", type=int, default=262144)
    ap.add_argument("--rows", type=int, default=8192)
    ap.add_argument("--dim", type=int, default=16)
    ap.add_argument("--full-ids", type=int, default=26 * 65536)
    ap.add_argument("--full-rows", type=int, default=1 << 20)
    args = ap.parse_args()

    rng = np.random.RandomState(0)

    # XLA baseline at the true bench shape (1M x 16 table, 1.7M zipf)
    big_table = jnp.zeros((args.full_rows, args.dim), jnp.float32)
    big_ids = jnp.asarray(
        (rng.zipf(1.5, size=args.full_ids) % args.full_rows).astype(
            np.int32
        )
    )
    big_grads = jnp.asarray(
        rng.rand(args.full_ids, args.dim).astype(np.float32)
    )
    xla_s = timed_carried(xla_scatter_add, big_table, big_ids, big_grads)
    xla_rows_per_s = args.full_ids / xla_s
    print(
        f"XLA scatter-add {args.full_ids} zipf ids -> "
        f"({args.full_rows}x{args.dim}): {xla_s * 1e3:.1f} ms "
        f"({xla_rows_per_s / 1e6:.1f}M rows/s)"
    )

    # Pallas floor: VMEM-resident tile, serial per-id updates
    table = jnp.zeros((args.rows, args.dim), jnp.float32)
    ids = jnp.asarray(
        (rng.zipf(1.5, size=args.ids) % args.rows).astype(np.int32)
    )
    grads = jnp.asarray(rng.rand(args.ids, args.dim).astype(np.float32))
    try:
        pallas_s = timed_carried(
            pallas_scatter_add, table, ids, grads, iters=4
        )
        pallas_rows_per_s = args.ids / pallas_s
        print(
            f"Pallas VMEM-resident serial scatter {args.ids} ids -> "
            f"({args.rows}x{args.dim}): {pallas_s * 1e3:.1f} ms "
            f"({pallas_rows_per_s / 1e6:.2f}M rows/s)"
        )
        print(
            f"verdict: Pallas floor is "
            f"{xla_rows_per_s / pallas_rows_per_s:.1f}x SLOWER per id "
            f"than XLA's HBM scatter"
            if pallas_rows_per_s < xla_rows_per_s
            else "verdict: Pallas floor beats XLA — probe the HBM tier"
        )
    except Exception as exc:
        print(f"Pallas kernel failed: {exc!r}")

    # numerical check (small)
    small_ids = ids[:4096]
    small_grads = grads[:4096]
    want = np.asarray(xla_scatter_add(table, small_ids, small_grads))
    got = np.asarray(pallas_scatter_add(table, small_ids, small_grads))
    err = float(np.abs(want - got).max())
    print(f"max |pallas - xla| on 4096 ids: {err}")


if __name__ == "__main__":
    main()
