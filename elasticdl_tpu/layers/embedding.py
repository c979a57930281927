"""Distributed embedding layer: mesh-sharded tables.

Parity: reference python/elasticdl/layers/embedding.py (SURVEY.md C13) and
the PS-side embedding tables + id-hash routing (C10/C11/C16).  The
reference's `elasticdl.Embedding` stores its table in parameter servers,
pulls per-minibatch vectors over gRPC and pushes IndexedSlices gradients.

TPU-native design (SURVEY.md §7): the table is ONE array sharded over the
mesh's `model` axis (PartitionSpec("model", None) — row sharding, the same
layout as the reference's id-hash partition across PS shards).  Lookup is a
plain gather inside the jitted step: the XLA SPMD partitioner turns a
gather on a row-sharded operand into the broadcast-ids/local-mask-psum
routing the PS client did by hand, and the backward scatter-add becomes the
sparse gradient push.  No RPCs, no parameter server processes.

Dynamic-vocabulary semantics (the reference's lazy-init unbounded tables)
are emulated by a fixed capacity plus id hashing: any int id maps to a row
via a multiplicative mixer mod capacity.  Collisions are the documented
trade-off (SURVEY.md hard part 2) — capacity is user-set per feature.
"""

from __future__ import annotations

from typing import Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from elasticdl_tpu.layers.moe import sow_step_metric

# Knuth's multiplicative hash constant (2^32 / phi); enough mixing to
# de-cluster sequential ids before the mod.
_MIX = 2654435761

_PIB = lax.GatherScatterMode.PROMISE_IN_BOUNDS


# Distinct rows a trip of the backward's scatter loop writes: a constant
# of the code like a tile size.  The scatter's cost on the chip follows
# its STATIC update count (~100 ns an update, whatever the ids), so a
# trip costs what CHUNK updates cost and the batch decides how many
# trips.  Probed at 8,192 .. 131,072 (docs/embedding_design_note.md):
# 16,384 and under read the same, larger chunks pay for their padding.
CHUNK = 16384

# A row of this many bytes or more (one 128-lane f32 row) keeps XLA's
# plain scatter-add: `distinct_row_path`.
_WIDE_ROW_BYTES = 512


def distinct_row_path(shape, dtype, updates: int) -> bool:
    """True where `scatter_add_rows` combines duplicates first.  A static
    test on the table's row (and that there is an update at all), so a
    table's path never changes at run time and the other tables compile
    to the plain scatter's HLO.

    Placed by PR 32's chip probe (v5e, 1.7M updates of Criteo-shaped ids
    at zipf 1.5 | 1.05 into tables of 2 GB and under, plain against
    distinct, ms): width 2: 136 against 19 | 49; 8: 147 against 20 | 50;
    16: 178 against 33 | 70; 32: 218 against 108 | 166; 64: 336 against
    155 | 214 — the distinct path wins.  Width 128 (426k updates): 36
    against 44, and 16,384 token ids at widths 128 / 768 / 2048: 5.3 /
    1.4 / 2.8 against 11.2 / 3.1 / 15.3 — the plain scatter wins: the
    combine's passes over (N, width) cost more than the scatter they
    spare.  Width 1 is XLA's scalar scatter, another algorithm (it sorts
    by itself: 17 ms for the 1.7M updates) and wins too: 54 against 62
    for the two DeepFM tables in one program with the order shared.
    """
    width = shape[1]
    return updates > 0 and (
        1 < width and width * jnp.dtype(dtype).itemsize < _WIDE_ROW_BYTES
    )


def row_order(flat_ids):
    """(the ids sorted, the permutation that sorts them): the one sort
    the distinct-row backward and its counter share."""
    flat_ids = flat_ids.astype(jnp.int32)
    return lax.sort(
        (flat_ids, lax.iota(jnp.int32, flat_ids.shape[0])), num_keys=1,
        is_stable=False,
    )


def _run_ends(sorted_ids):
    """bool (N,): the last position of every run of equal ids."""
    return jnp.concatenate([
        sorted_ids[1:] != sorted_ids[:-1], jnp.ones((1,), bool)
    ])


def distinct_rows(order):
    """How many distinct rows `order` (from `row_order`) holds."""
    return _run_ends(order[0]).sum()


def _combine_runs(sorted_ids, g):
    """Inclusive sum of `g`'s rows within each run of equal ids, by
    doubling: after the pass at distance d a row holds the sum of the
    (at most) 2d rows of its run that end at it, so a run's LAST row ends
    with the run's total, summed as a tree.  Not a prefix-sum difference
    (that loses a small run's bits behind a large one's) and not
    `segment_sum` (that lowers to the N-update scatter)."""
    n = sorted_ids.shape[0]
    d = 1
    while d < n:
        same = sorted_ids[d:] == sorted_ids[:-d]
        g = jnp.concatenate([
            g[:d], g[d:] + jnp.where(same[:, None], g[:-d], 0)
        ])
        d *= 2
    return g


def scatter_add_rows(shape, flat_ids, g, order=None):
    """zeros(shape).at[flat_ids].add(g), scattering the batch's DISTINCT
    rows where `distinct_row_path` says so.

    XLA's scatter costs per update of its STATIC update count, the same
    for a live, a duplicate or a dropped one, in any order (v5e: 5.6 /
    10.6 / 30.2 / 177.8 ms for 16,384 / 65,536 / 262,144 / 1,703,936
    updates into f32[33554432,16], of which 4 ms zero the table).  So
    the duplicates are combined first (`arena/combine`: sort, permute,
    sum each run, compact the run totals' positions to the front; 26 ms
    for 1.7M rows of 16) and the totals are scattered CHUNK rows a trip
    in a loop of ceil(distinct / CHUNK) trips (`arena/scatter`): each
    scatter's static count is CHUNK and the number of scatters follows
    the batch.  Whole, for 1.7M updates: 33 ms at 2% distinct rows, 70
    at 21.5%, 225 at 97.5% (plain: 178 at each); it breaks even near
    three quarters distinct, which no batch with a field of few values
    comes near.  Rounds 2-3's collapse lost because its "head-only"
    scatter still held N updates (docs/embedding_design_note.md).
    `order` is `row_order(flat_ids)` where the caller already holds it.
    """
    n = flat_ids.shape[0]
    if not distinct_row_path(shape, g.dtype, n):
        with jax.named_scope("arena/scatter"):
            return jnp.zeros(shape, g.dtype).at[flat_ids].add(g, mode=_PIB)
    chunk = min(CHUNK, n)
    with jax.named_scope("arena/combine"):
        sorted_ids, perm = order if order is not None else row_order(
            flat_ids
        )
        totals = _combine_runs(sorted_ids, g.at[perm].get(mode=_PIB))
        ends = _run_ends(sorted_ids)
        distinct = ends.sum()
        # positions of the run totals first, n (past the end) after them
        ends_at = jnp.pad(
            lax.sort(
                jnp.where(ends, lax.iota(jnp.int32, n), n), is_stable=False
            ),
            (0, -n % chunk), constant_values=n,
        )
    # a trip's padding goes to rows past the table's end, distinct and
    # rising like the live ones, and is dropped there
    past = shape[0] + lax.iota(jnp.int32, chunk)

    def trip(c, out):
        at = lax.dynamic_slice(ends_at, (c * chunk,), (chunk,))
        rows = jnp.where(
            at < n, sorted_ids.at[at].get(mode="clip"), past
        )
        return out.at[rows].add(
            totals.at[at].get(mode="clip"), mode="drop",
            unique_indices=True, indices_are_sorted=True,
        )

    with jax.named_scope("arena/scatter"):
        return lax.fori_loop(
            0, (distinct + chunk - 1) // chunk, trip,
            jnp.zeros(shape, g.dtype),
        )


@jax.custom_vjp
def _lookup(table, flat_ids, order=None):
    """Gather rows; backward is `scatter_add_rows`.

    The FORWARD's custom part: ids are hashed mod capacity by
    construction, so the gather's bounds branch is provably dead —
    PROMISE_IN_BOUNDS makes that explicit.  `order` is
    `row_order(flat_ids)` or None; only the backward reads it.
    """
    with jax.named_scope("arena/lookup"):
        return table.at[flat_ids].get(mode=_PIB)


def _lookup_fwd(table, flat_ids, order):
    # the table itself is the residual (a reference, not a copy): only
    # its shape/dtype are read in the backward
    return _lookup(table, flat_ids), (table, flat_ids, order)


def _lookup_bwd(residuals, g):
    table, flat_ids, order = residuals
    dtable = scatter_add_rows(table.shape, flat_ids, g, order)
    return dtable.astype(table.dtype), None, None


_lookup.defvjp(_lookup_fwd, _lookup_bwd)


def lookup_rows(module, table, flat_ids, lookup=_lookup):
    """`lookup(table, flat_ids)` (`_lookup`, or the int8 arena's
    `_grad_tap`) from inside a flax `module`.  On the distinct-row path
    the ids are sorted HERE, once: the backward takes the order, and the
    module sows `distinct_rows_ratio` (distinct rows / looked-up rows)
    into STEP_METRICS, where it rides to the task's one fetch
    (`worker_arena_distinct_rows_ratio{table}`).  Outside a train step
    nothing reads the order and XLA drops the sort."""
    if not distinct_row_path(table.shape, table.dtype, flat_ids.shape[0]):
        return lookup(table, flat_ids)
    # the forward's sort is the lookup's cost (profiler.DEVICE_SCOPES)
    with jax.named_scope("arena/lookup"):
        order = row_order(flat_ids)
        sow_step_metric(
            module, "distinct_rows_ratio",
            distinct_rows(order) / flat_ids.shape[0],
        )
        return lookup(table, flat_ids, order)


def hash_ids(ids: jnp.ndarray, capacity: int, mix: bool = True) -> jnp.ndarray:
    ids = ids.astype(jnp.uint32)
    if mix:
        ids = ids * jnp.uint32(_MIX)
    return (ids % jnp.uint32(capacity)).astype(jnp.int32)


def hash_ids_host(ids, capacity: int, mix: bool = True):
    """Bit-exact numpy replica of `hash_ids` for HOST-side packers (the
    dedup'd wire format hashes in the prefetch thread so the device can
    skip the hash and consume table rows directly).  uint32 wraparound
    arithmetic matches the device path including negative-id
    reinterpretation."""
    import numpy as np

    ids = np.asarray(ids).astype(np.uint32)
    if mix:
        with np.errstate(over="ignore"):
            ids = ids * np.uint32(_MIX)
    return (ids % np.uint32(capacity)).astype(np.int32)


class DistributedEmbedding(nn.Module):
    """Drop-in equivalent of the reference's `elasticdl.Embedding`.

    input_dim:  table capacity (vocab size after hashing).
    output_dim: embedding dimension.
    combiner:   None -> per-id vectors (input (..., ) int ids ->
                (..., output_dim)); "sum" | "mean" | "sqrtn" -> bag
                reduction over the last input axis with `pad_id` masking
                (the reference's combiner semantics for multivalent
                features).
    hash_input: apply the multiplicative mixer (set False when ids are
                already uniform, e.g. pre-hashed Criteo features).
    """

    input_dim: int
    output_dim: int
    combiner: Optional[str] = None
    pad_id: int = -1
    hash_input: bool = True
    param_dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, ids, prehashed: bool = False):
        table = self.param(
            "embedding",
            nn.initializers.normal(stddev=0.05),
            (self.input_dim, self.output_dim),
            self.param_dtype,
        )
        ids = jnp.asarray(ids)
        if prehashed:
            # ids are already table rows in [0, input_dim) — computed on
            # the HOST by the dedup'd wire format (hash_ids_host) so the
            # device skips the hash/mod.  Pad masking does not apply:
            # the packer asserts the stream carries no pad ids.
            vecs = lookup_rows(self, table, ids.reshape(-1)).reshape(
                ids.shape + (self.output_dim,)
            )
            if self.combiner is None:
                return vecs
            valid = jnp.ones(ids.shape, bool)
            return self._combine(vecs, valid)
        valid = ids != self.pad_id
        rows = hash_ids(jnp.where(valid, ids, 0), self.input_dim,
                        mix=self.hash_input)
        vecs = lookup_rows(self, table, rows.reshape(-1)).reshape(
            rows.shape + (self.output_dim,)
        )
        vecs = jnp.where(valid[..., None], vecs, 0.0)
        if self.combiner is None:
            return vecs
        return self._combine(vecs, valid)

    def _combine(self, vecs, valid):
        count = jnp.maximum(
            jnp.sum(valid, axis=-1, keepdims=True).astype(vecs.dtype), 1.0
        )
        total = jnp.sum(vecs, axis=-2)
        if self.combiner == "sum":
            return total
        if self.combiner == "mean":
            return total / count
        if self.combiner == "sqrtn":
            return total / jnp.sqrt(count)
        raise ValueError(f"unknown combiner {self.combiner!r}")


def embedding_param_sharding(path, value) -> Optional[P]:
    """`param_sharding` helper for zoo modules: shard every
    DistributedEmbedding table over the `model` axis, replicate the rest.

    Usage in a model-zoo module:
        from elasticdl_tpu.layers.embedding import embedding_param_sharding
        param_sharding = embedding_param_sharding
    """
    names = [getattr(k, "key", str(k)) for k in path]
    if "embedding" in names and getattr(value, "ndim", 0) >= 2:
        return P("model", None)
    return None
