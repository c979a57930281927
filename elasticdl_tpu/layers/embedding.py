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

from elasticdl_tpu.common import metrics as metrics_lib
from elasticdl_tpu.layers import step_metrics
from elasticdl_tpu.layers.step_metrics import sow_step_metric

# Knuth's multiplicative hash constant (2^32 / phi); enough mixing to
# de-cluster sequential ids before the mod.
_MIX = 2654435761

_PIB = lax.GatherScatterMode.PROMISE_IN_BOUNDS

# What a narrow-row lookup sows into STEP_METRICS (`lookup_rows`): the
# share of the batch's looked-up rows that are distinct, by table.
step_metrics.declare(
    "distinct_rows_ratio",
    metrics_lib.default_registry().gauge(
        "worker_arena_distinct_rows_ratio",
        "distinct table rows / looked-up rows of the batch (what the "
        "embedding backward scatters over what it was handed), last step of "
        "the task",
        labelnames=("table",),
    ),
)
# ... and whether that lookup's forward read the table at the distinct
# rows only (`compact_lookup_path`) or gathered plainly.
step_metrics.declare(
    "lookup_compact",
    metrics_lib.default_registry().gauge(
        "worker_arena_lookup_compact_ratio",
        "1 where the table's forward lookup gathered the batch's distinct "
        "rows and expanded them, 0 where it gathered every looked-up row "
        "from the table, last step of the task",
        labelnames=("table",),
    ),
)


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


# The forward's distinct-row route (`compact_lookup_path`, `_gather_rows`).
# The most trips of its loop, so the rows of its compact buffer: a batch
# with more distinct rows takes the plain gather, on the device.  What
# makes the route fast is WHERE the compact buffer lives: up to 131,072
# rows (8 CHUNKs; 64 MiB padded to lanes) XLA keeps it row-major in the
# chip's fast memory and 1.7M rows expand from it in 3.1 ms + a 1.4 ms
# layout copy; at 425,984 rows and over the loop's result stays in HBM,
# where a row costs what it costs in the 2.1 GB table (38 against 40 ms).
# PR 48's chip probe, v5e (docs/embedding_design_note.md).
_COMPACT_CHUNKS = 8
# A lookup of fewer ids than this many CHUNKs keeps the plain gather,
# statically.  Probed with the route's own sort counted, plain | route at
# 2% distinct | route with the buffer nearly full, ms, width 16: 65,536
# ids 2.2 | 1.7 | 3.0; 131,072: 3.7 | 2.0 | 3.6; 262,144: 6.7 | 2.8 | 6.9;
# 524,288: 12.7 | 4.5 | 7.0; 1,703,936: 40.0 | 11.7 | 14.1 (width 1: 26.0
# | 11.5 | 13.0): from 16 CHUNKs on it never loses.
_COMPACT_MIN_CHUNKS = 16


def _narrow_row(shape, dtype) -> bool:
    return shape[1] * jnp.dtype(dtype).itemsize < _WIDE_ROW_BYTES


def distinct_row_path(shape, dtype, updates: int) -> bool:
    """True where `scatter_add_rows` combines duplicates first: the
    BACKWARD's rule (the forward's is `compact_lookup_path`).  A static
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
    return updates > 0 and 1 < shape[1] and _narrow_row(shape, dtype)


def compact_lookup_path(shape, dtype, lookups: int) -> bool:
    """True where `_lookup`'s FORWARD may read the table at the batch's
    distinct rows only and expand them (`_gather_rows`).  Static, like
    `distinct_row_path`: by the table's row (rows of 512 bytes and more
    lower to the plain gather's program) and by the count of ids
    (`_COMPACT_MIN_CHUNKS`).  A one-element row is on it (its BACKWARD
    is not on the distinct-row path): v5e, 1.7M lookups at 2% distinct
    rows, 26.0 ms plain against 5.9 beside a wider table that shares
    the sorts, 11.5 alone.  A count that is a symbol (a model exported
    for any batch size) is no count to hold against the rule: plain."""
    return (
        _narrow_row(shape, dtype)
        and not jax.export.is_symbolic_dim(lookups)
        and lookups >= _COMPACT_MIN_CHUNKS * CHUNK
    )


def compact_limit() -> int:
    """The most distinct rows the forward's route takes: the rows of its
    compact buffer, and what the device holds the batch's count
    against."""
    return _COMPACT_CHUNKS * CHUNK


def row_order(flat_ids):
    """(the ids sorted, the permutation that sorts them): the one sort
    the distinct-row backward, the forward's route and their counters
    share."""
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


def _run_ends_at(ends, chunk):
    """int32 (n rounded up to `chunk`,): the positions of the run ends
    first, rising, and n (past the end) after them."""
    n = ends.shape[0]
    return jnp.pad(
        lax.sort(
            jnp.where(ends, lax.iota(jnp.int32, n), n), is_stable=False
        ),
        (0, -n % chunk), constant_values=n,
    )


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


def scatter_add_rows(shape, flat_ids, g, order=None, ends_at=None):
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
    `order` is `row_order(flat_ids)` where the caller already holds it,
    and `ends_at` the run ends' positions where the forward's route
    already compacted them (`_gather_rows`).
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
        # positions of the run totals first
        if ends_at is None:
            ends_at = _run_ends_at(ends, chunk)
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


# Columns of the view a one-element row is expanded through; the compact
# buffer's 8 CHUNKs of rows are whole rows of it.
_VIEW = 16


def _expand_rows(compact, run_of):
    """compact[run_of].  A one-element row goes through a view of
    `_VIEW` columns: rows of 16 gathered at run_of / 16 and the column
    picked by a compare and an integer sum over the rows' bits (so -0.0
    stays -0.0).  A SCALAR gather costs 9 ns an element even from fast
    memory (15.4 ms for 1.7M, no better than from the table), this 3.1
    + 1.4 ms (PR 48's probe)."""
    if compact.shape[1] > 1:
        return compact.at[run_of].get(mode=_PIB)
    bits = lax.bitcast_convert_type(
        compact.reshape(-1, _VIEW).at[run_of // _VIEW].get(mode=_PIB),
        jnp.dtype(f"int{8 * compact.dtype.itemsize}"),
    )
    picked = lax.iota(jnp.int32, _VIEW)[None, :] == (run_of % _VIEW)[:, None]
    return lax.bitcast_convert_type(
        jnp.where(picked, bits, 0).sum(
            axis=1, keepdims=True, dtype=bits.dtype
        ),
        compact.dtype,
    )


def _gather_rows(table, flat_ids, order):
    """(table[flat_ids], the run ends' positions or None): `_lookup`'s
    forward.

    On the chip a gathered row costs by the MEMORY it is read from, not
    by the ids: 23.4 ns from `f32[33554432,16]` in HBM at 2%, 21.5% and
    97.5% distinct rows alike (40.0 ms for 1,703,936 lookups; 15.3 ns
    an element, 26.0 ms, from the `(rows, 1)` table), 1.8 ns from a
    buffer XLA keeps in fast memory.  So where `compact_lookup_path`
    says so and `order` = `row_order(flat_ids)` is at hand, the table
    is read at the batch's DISTINCT rows only: the run ends' positions
    are compacted to the front (`ends_at`: the sort the backward made
    until PR 48; it is handed on to `scatter_add_rows`), a loop of
    ceil(distinct / CHUNK) trips gathers CHUNK table rows a trip (0.5
    ms) into a buffer of `compact_limit()` rows, and every lookup
    expands from there at `run_of`, the index of its run, which a
    two-operand sort keyed by the permutation carries back to the ids'
    own order (1.8 ms; a 1.7M scalar scatter does it in 8.7).  One
    `lax.cond` on the count takes the plain gather where the batch
    holds more distinct rows than the buffer: there the route has cost
    its running sum and the `run_of` sort (1.3 + 1.8 ms at 1.7M ids)
    for nothing.  Whole, 1.7M lookups at 2% distinct rows: 10.9 ms
    against 40.0.  The rows are copies either way: the values are the
    plain gather's to the bit.
    """
    def plain():
        return table.at[flat_ids].get(mode=_PIB)

    with jax.named_scope("arena/lookup"):
        if order is None or not compact_lookup_path(
            table.shape, table.dtype, flat_ids.shape[0]
        ):
            return plain(), None
        sorted_ids, perm = order
        ends = _run_ends(sorted_ids)
        distinct = ends.sum()
        ends_at = _run_ends_at(ends, CHUNK)
        # the run a sorted position lies in, carried back to the ids'
        # own order by a sort keyed by the permutation.  Outside the
        # `cond`, where two tables looked up by the same ids share it
        # as they share the order
        run_id = jnp.cumsum(ends, dtype=jnp.int32) - ends
        run_of = lax.sort((perm, run_id), num_keys=1, is_stable=False)[1]

        def trip(c, compact):
            at = lax.dynamic_slice(ends_at, (c * CHUNK,), (CHUNK,))
            # a trip's padding reads the last id's row again
            rows = sorted_ids.at[at].get(mode="clip")
            return lax.dynamic_update_slice(
                compact,
                table.at[rows].get(mode=_PIB, indices_are_sorted=True),
                (c * CHUNK, 0),
            )

        def compacted():
            return _expand_rows(lax.fori_loop(
                0, (distinct + CHUNK - 1) // CHUNK, trip,
                jnp.zeros((compact_limit(), table.shape[1]), table.dtype),
            ), run_of)

        return lax.cond(
            distinct <= compact_limit(), compacted, plain
        ), ends_at


@jax.custom_vjp
def _lookup(table, flat_ids, order=None):
    """Gather rows; backward is `scatter_add_rows`.

    The FORWARD's custom part: ids are hashed mod capacity by
    construction, so the gather's bounds branch is provably dead —
    PROMISE_IN_BOUNDS makes that explicit.  `order` is
    `row_order(flat_ids)` or None: the backward combines by it, and
    the forward reads the table at the batch's distinct rows by it
    where `compact_lookup_path` says so (`_gather_rows`).
    """
    return _gather_rows(table, flat_ids, order)[0]


def _lookup_fwd(table, flat_ids, order):
    # the table itself is the residual (a reference, not a copy): only
    # its shape/dtype are read in the backward
    out, ends_at = _gather_rows(table, flat_ids, order)
    return out, (table, flat_ids, order, ends_at)


def _lookup_bwd(residuals, g):
    table, flat_ids, order, ends_at = residuals
    dtable = scatter_add_rows(table.shape, flat_ids, g, order, ends_at)
    return dtable.astype(table.dtype), None, None


_lookup.defvjp(_lookup_fwd, _lookup_bwd)


def lookup_rows(module, table, flat_ids, lookup=_lookup):
    """`lookup(table, flat_ids)` (`_lookup`, or the int8 arena's
    `_grad_tap`) from inside a flax `module`.  Where the backward is on
    the distinct-row path or the forward on its route the ids are
    sorted HERE, once: both take the order, and the module sows into
    STEP_METRICS, where they ride to the task's one fetch,
    `distinct_rows_ratio` (distinct rows / looked-up rows:
    `worker_arena_distinct_rows_ratio{table}`) and, where the forward is
    on its route, `lookup_compact` (which side of the `cond` this step
    took: 1.0 where it expanded the batch's distinct rows, 0.0 where it
    gathered plainly: `worker_arena_lookup_compact_ratio{table}`; a
    lookup the static rule keeps plain says nothing).  Two tables
    looked up by the same ids (DeepFM's) sort them once: XLA merges the
    equal sorts.  Outside a train step the forward's route still reads
    the order where it is taken; elsewhere nothing does and XLA drops
    the sort."""
    n = flat_ids.shape[0]
    # `_grad_tap`'s forward gathers nothing
    compact = lookup is _lookup and compact_lookup_path(
        table.shape, table.dtype, n
    )
    if not (compact or distinct_row_path(table.shape, table.dtype, n)):
        return lookup(table, flat_ids)
    # the forward's sort is the lookup's cost (profiler.DEVICE_SCOPES)
    with jax.named_scope("arena/lookup"):
        order = row_order(flat_ids)
        distinct = distinct_rows(order)
        sow_step_metric(module, "distinct_rows_ratio", distinct / n)
        if compact:
            sow_step_metric(
                module, "lookup_compact", distinct <= compact_limit()
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
    init_stddev: the seeded table is normal(0, init_stddev).
    """

    input_dim: int
    output_dim: int
    combiner: Optional[str] = None
    pad_id: int = -1
    hash_input: bool = True
    param_dtype: jnp.dtype = jnp.float32
    init_stddev: float = 0.05

    @nn.compact
    def __call__(self, ids, prehashed: bool = False):
        table = self.param(
            "embedding",
            nn.initializers.normal(stddev=self.init_stddev),
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
