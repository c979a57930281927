"""Device hot-row cache bookkeeping + per-batch admission plans.

Pure numpy/host bookkeeping — the cache's VALUES live in the model's
`TieredArena` param on device; this module only decides which store row
occupies which cache slot.

Admission is mandatory: every row a training batch touches must be
cache-resident before the step runs (gradients flow only through the
device table).  Per batch the cache:

  1. frequency-ranks the batch's unique rows (`wire.frequency_rank` —
     the dedup wire format's signal, reused as the admission policy);
  2. counts hits (resident BEFORE this batch's admissions) vs misses;
  3. fills empty slots first, then evicts the lowest-score resident
     rows NOT in the current batch (score = decayed lookup frequency;
     ties break on lowest slot index, so planning is deterministic);
  4. returns a `CachePlan` the TieredStore executes at apply time.

Raises if a single batch references more unique rows than the cache
holds — that configuration cannot satisfy the every-touched-row-resident
invariant and must fail loudly, not thrash.
"""

from __future__ import annotations

import threading
from dataclasses import dataclass, field
from typing import Dict, Optional

import numpy as np

from elasticdl_tpu.data.wire import frequency_rank

# Device cache storage modes (mirrors layers/arena.py ARENA_DTYPES —
# not imported: this module must stay jax-free numpy).
CACHE_DTYPES = ("float32", "int8")


def cache_value_bytes_per_row(dim: int, cache_dtype: str) -> int:
    """Bytes one cache row of one plane occupies on the fused GATHER
    path: fp32 streams 4*dim; int8 streams dim code bytes + one fp32
    scale.  (The fp32 carrier + Adam moments exist in BOTH modes and the
    forward never reads the carrier's bytes — XLA folds the exact-zero
    add away — so they cancel out of the comparison; docs/PERF.md §4.)"""
    if cache_dtype == "int8":
        return int(dim) * 1 + 4
    return int(dim) * 4


def device_cache_bytes(planes: Dict[str, int], cache_rows: int,
                       cache_dtype: str) -> int:
    """Analytic bytes of the gather-path cache storage across planes."""
    return sum(
        int(cache_rows) * cache_value_bytes_per_row(dim, cache_dtype)
        for dim in planes.values()
    )


def device_cache_bytes_per_step(planes: Dict[str, int], lookups: int,
                                cache_dtype: str) -> int:
    """Analytic gather-path bytes one train step streams from the cache:
    `lookups` row reads per plane (B*F for the dense slot batch, or the
    dedup'd unique count on the packed wire)."""
    return sum(
        int(lookups) * cache_value_bytes_per_row(dim, cache_dtype)
        for dim in planes.values()
    )


def partition_plan(plan: "CachePlan", num_shards: int,
                   cache_rows: int) -> list:
    """Split one admission plan into per-device sub-plans along the
    mesh-sharded slot arena.

    `embedding_param_sharding` row-shards the (cache_rows, dim) cache
    table over the mesh `model` axis in contiguous blocks of
    cache_rows/num_shards rows, so the device owning a slot is simply
    `slot // block`.  Each sub-plan keeps the parent plan's admission
    order within its device (order-preserving mask selection) and the
    union of the sub-plans is exactly the parent plan — the equivalence
    the sharded-seam test pins.  The scatter itself still executes as
    ONE fused program (XLA partitions it from the table sharding); the
    sub-plans are the per-chip accounting the bench and metrics report.
    """
    num_shards = int(num_shards)
    if num_shards < 1 or cache_rows % num_shards:
        raise ValueError(
            f"cache_rows={cache_rows} must divide evenly over "
            f"{num_shards} mesh shards (row-sharded table blocks)"
        )
    block = cache_rows // num_shards
    subs = []
    admit_dev = np.asarray(plan.admit_slots, np.int64) // block
    evict_dev = np.asarray(plan.evict_slots, np.int64) // block
    for d in range(num_shards):
        am = admit_dev == d
        em = evict_dev == d
        subs.append({
            "device": d,
            "slot_lo": d * block,
            "slot_hi": (d + 1) * block,
            "admit_slots": plan.admit_slots[am].copy(),
            "admit_rows": plan.admit_rows[am].copy(),
            "evict_slots": plan.evict_slots[em].copy(),
            "evict_rows": plan.evict_rows[em].copy(),
        })
    return subs


@dataclass
class CachePlan:
    """One batch's admission/eviction schedule.

    `slots` is what the model consumes; the admit/evict arrays are what
    `TieredStore.apply_plan` executes against device + host tiers.
    `deferred` marks admits whose host value is still in-flight on the
    fold queue (evicted recently, write-back pending) — those are
    gathered synchronously at apply time, after a fold-queue flush.
    """

    slots: np.ndarray                 # (B, F) int32 cache slots
    admit_slots: np.ndarray           # (K,) int32
    admit_rows: np.ndarray            # (K,) int64 store rows
    evict_slots: np.ndarray           # (E,) int32
    evict_rows: np.ndarray            # (E,) int64 store rows
    hits: int
    misses: int
    growth: int = 0                   # vocab rows grown by this batch
    deferred: Optional[np.ndarray] = None   # (K,) bool
    prefetch_rows: Optional[np.ndarray] = None  # admit_rows[~deferred]
    admit_values: Dict[str, np.ndarray] = field(default_factory=dict)
    ready: threading.Event = field(default_factory=threading.Event)
    # Mesh-sharded seam: per-device sub-plans over the row-sharded slot
    # arena (partition_plan); None on an unsharded (1-device) store.
    sub_plans: Optional[list] = None


class HotRowCache:
    """Slot bookkeeping for the device-resident hot-row cache.

    NOT thread-safe on its own — always driven under TieredStore's lock
    (plans must be produced sequentially anyway: slot assignment is
    stateful).
    """

    def __init__(self, capacity: int, decay: float = 0.999,
                 dtype: str = "float32"):
        if capacity < 1:
            raise ValueError("cache needs at least one row")
        if dtype not in CACHE_DTYPES:
            raise ValueError(
                f"cache dtype must be one of {CACHE_DTYPES}, got {dtype!r}"
            )
        self.capacity = int(capacity)
        # Storage dtype of the device VALUES this bookkeeping fronts —
        # carried through state_arrays() so a sidecar written by an int8
        # cache can never be silently re-interpreted as fp32 on restore.
        self.dtype = dtype
        self._decay = float(decay)
        self._slot_of: Dict[int, int] = {}      # store row -> slot
        self.row_of = np.full(self.capacity, -1, np.int64)
        self._score = np.zeros(self.capacity, np.float64)

    @property
    def occupancy(self) -> int:
        return len(self._slot_of)

    def slot_of(self, row: int) -> int:
        """Resident slot for a store row, or -1 (the serving path)."""
        return self._slot_of.get(int(row), -1)

    def plan(self, rows: np.ndarray, ranked=None) -> CachePlan:
        """`ranked` is an optional precomputed `(uniq, counts)` admission
        signal for exactly these rows — DedupPacker.last_ranking, merged
        batch-globally by the wire pack — so the cache doesn't re-derive
        the frequency view the packer already built.  Order and
        tie-breaks must match `frequency_rank(rows.reshape(-1))`
        (admission order is eviction-victim-visible); the wire pack
        guarantees that, and the parity test pins it."""
        rows = np.asarray(rows, np.int64)
        flat = rows.reshape(-1)
        if ranked is None:
            uniq, counts = frequency_rank(flat)
        else:
            uniq = np.asarray(ranked[0], np.int64)
            counts = np.asarray(ranked[1], np.int64)
            if int(counts.sum()) != flat.size:
                raise ValueError(
                    f"precomputed ranking covers {int(counts.sum())} "
                    f"lookups but the batch has {flat.size}"
                )
        if uniq.size > self.capacity:
            raise ValueError(
                f"batch touches {uniq.size} unique rows but the cache "
                f"holds {self.capacity}; shrink the batch or grow the "
                "cache — thrashing within one step is not supported"
            )
        resident = np.fromiter(
            (int(r) in self._slot_of for r in uniq), bool, uniq.size
        )
        hits = int(counts[resident].sum())
        misses = int(counts[~resident].sum())
        admit_rows = uniq[~resident]          # descending frequency

        # Victim selection: empty slots first, then lowest-score resident
        # rows outside the current batch (those are guaranteed to exist:
        # free + non-batch-resident >= capacity - batch_uniques >= admits).
        free = np.nonzero(self.row_of < 0)[0]
        n_free = min(free.size, admit_rows.size)
        admit_slots = free[:n_free].astype(np.int64)
        need = admit_rows.size - n_free
        if need > 0:
            cand = np.nonzero(
                (self.row_of >= 0) & ~np.isin(self.row_of, uniq)
            )[0]
            order = cand[np.lexsort((cand, self._score[cand]))]
            evict_slots = order[:need]
        else:
            evict_slots = np.empty(0, np.int64)
        evict_rows = self.row_of[evict_slots].copy()

        # Commit the bookkeeping NOW (plans are produced ahead of
        # execution; the next plan must see this one's assignments).
        for s, r in zip(evict_slots, evict_rows):
            del self._slot_of[int(r)]
        admit_slots = np.concatenate([admit_slots, evict_slots])
        for s, r in zip(admit_slots, admit_rows):
            self._slot_of[int(r)] = int(s)
            self.row_of[s] = r
            self._score[s] = 0.0

        # Frequency scores: decay everything, bump this batch's rows.
        self._score *= self._decay
        uniq_slots = np.fromiter(
            (self._slot_of[int(r)] for r in uniq), np.int64, uniq.size
        )
        self._score[uniq_slots] += counts

        # Row -> slot translation for the full batch.
        order = np.argsort(uniq, kind="stable")
        uniq_sorted, slot_sorted = uniq[order], uniq_slots[order]
        slots = slot_sorted[np.searchsorted(uniq_sorted, flat)]
        return CachePlan(
            slots=slots.reshape(rows.shape).astype(np.int32),
            admit_slots=admit_slots.astype(np.int32),
            admit_rows=admit_rows.copy(),
            evict_slots=evict_slots.astype(np.int32),
            evict_rows=evict_rows,
            hits=hits,
            misses=misses,
        )

    # ---- invalidation (shard handoff) ----------------------------------

    def reset(self) -> None:
        """Drop all residency and scores — a handed-off shard's
        successor starts cold and lets admission traffic rebuild."""
        self._slot_of.clear()
        self.row_of.fill(-1)
        self._score.fill(0.0)

    def invalidate_rows(self, rows: np.ndarray) -> int:
        """Evict specific store rows from the bookkeeping (no device
        traffic — pair with store.device.zero_cache_slots when the
        slots' on-device values must also be cleared).  Returns the
        number of rows that were resident."""
        n = 0
        for row in np.asarray(rows, np.int64).reshape(-1):
            slot = self._slot_of.pop(int(row), None)
            if slot is not None:
                self.row_of[slot] = -1
                self._score[slot] = 0.0
                n += 1
        return n

    # ---- serialization -------------------------------------------------

    def state_arrays(self):
        """(row_of, score, dtype) — residency map plus the PLANE DTYPE
        of the device values this map fronts.  The dtype travels with
        the sidecar so an int8 cache's values can never restore into an
        fp32 cache (or vice versa) without an explicit conversion."""
        return self.row_of.copy(), self._score.copy(), self.dtype

    def load_state_arrays(self, row_of: np.ndarray,
                          score: Optional[np.ndarray] = None,
                          dtype: Optional[str] = None,
                          convert: bool = False) -> None:
        """Adopt a saved residency map.  `dtype` is the saved cache's
        plane dtype (state_arrays()[2] / the sidecar's `cache_dtype`
        meta); a mismatch with this cache's dtype raises unless
        `convert=True` — the caller asserting the device VALUES were
        converted too (CheckpointSaver's arena_convert restore path)."""
        if dtype is not None and dtype != self.dtype and not convert:
            raise ValueError(
                f"cache plane dtype mismatch: sidecar holds {dtype!r} "
                f"values but this cache stores {self.dtype!r} — restore "
                "through CheckpointSaver (arena_convert migrates the "
                "device values) or pass convert=True after converting "
                "them yourself"
            )
        row_of = np.asarray(row_of, np.int64)
        if row_of.shape != (self.capacity,):
            raise ValueError(
                f"cache map shape {row_of.shape} != ({self.capacity},)"
            )
        self.row_of = row_of.copy()
        self._slot_of = {
            int(r): int(s) for s, r in enumerate(row_of) if r >= 0
        }
        self._score = (
            np.asarray(score, np.float64).copy()
            if score is not None else np.zeros(self.capacity, np.float64)
        )
