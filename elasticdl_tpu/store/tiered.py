"""TieredStore: the orchestrator tying host tier, hot-row cache, and
the device seam together.

Data flow per training batch (single producer, single consumer):

  prefetch producer thread (wrap_feed/wrap_feed_bulk):
      prepare(sparse) -> (slots, CachePlan)
        - lazy vocab growth (host tier assign)
        - cache admission plan (frequency-ranked, deterministic)
        - enqueue async host-gather of admit-row values

  cold-miss prefetcher thread:
      gathers admit values from the host tier -> plan.ready

  consumer thread (trainer.train_on_batch, just before the step):
      apply_plan(state, plan) -> state'
        - read evicted rows from device, enqueue host fold
        - wait for prefetched admit values (deferred rows: flush the
          fold queue, then gather synchronously)
        - scatter admits into the cache param + zero their moments

  host-fold worker thread:
      set_rows(evicted values) into the host tier

Ordering invariant: prepare() runs strictly in batch order on the ONE
producer thread, and apply_plan()/train run strictly in batch order on
the consumer — so plan k+1's bookkeeping always reflects plan k's
admissions, and eviction write-backs always carry the latest trained
value.  Two free-running producer threads would break this, so
multi-worker Local training uses DEFERRED planning instead
(`enable_deferred_prepare`): feeds attach the raw sparse batch and the
trainer runs prepare+apply back to back at train time, under the
ModelOwner lock that already serializes every step — strict order is
restored at the cost of the async cold-gather overlap (docs/PERF.md
§4).  Sharding the row space itself across workers is
store/sharding.py's job.

The stale-value hazard — a row evicted by plan k and re-admitted by
plan k+j while its fold is still queued — is handled by the
`_pending_writeback` set: such admits are marked `deferred`, and
apply_plan flushes the fold queue before gathering them.
"""

from __future__ import annotations

import queue
import threading
import time
from typing import Dict, Optional, Tuple

import numpy as np

from elasticdl_tpu.common import events
from elasticdl_tpu.common.log_utils import get_logger
from elasticdl_tpu.common.metrics import MetricsRegistry
from elasticdl_tpu.store import device as store_device
from elasticdl_tpu.store.cache import (
    CACHE_DTYPES,
    CachePlan,
    HotRowCache,
    device_cache_bytes,
    partition_plan,
)
from elasticdl_tpu.store.host_tier import HostTier

logger = get_logger(__name__)


class TieredStore:
    """One store instance manages every embedding plane of one model
    (DeepFM: fm_embedding + fm_linear), sharing one vocabulary and one
    cache slot numbering across planes."""

    def __init__(self, planes: Dict[str, int], num_fields: int,
                 cache_rows: int, host_dtype: str = "fp32",
                 seed: int = 0x5EED,
                 param_paths: Optional[Dict[str, Tuple[str, ...]]] = None,
                 registry: Optional[MetricsRegistry] = None,
                 phase_timer=None, cache_dtype: str = "float32"):
        if cache_dtype not in CACHE_DTYPES:
            raise ValueError(
                f"cache_dtype must be one of {CACHE_DTYPES}, "
                f"got {cache_dtype!r}"
            )
        self.planes = dict(planes)
        self.num_fields = int(num_fields)
        self.cache_rows = int(cache_rows)
        self.cache_dtype = cache_dtype
        # Mesh-sharded seam (ISSUE 18b): >1 means the cache slot arena is
        # row-sharded over the model axis and every plan carries per-chip
        # sub-plans (accounting + tests; execution stays ONE fused
        # program — XLA partitions it from the table sharding).
        self.mesh_shards = 1
        self.host = HostTier(planes, num_fields, host_dtype, seed)
        self.cache = HotRowCache(cache_rows, dtype=cache_dtype)
        self.param_paths = dict(param_paths) if param_paths else {
            name: ("params", name, "embedding") for name in planes
        }
        self.phase_timer = phase_timer
        self.registry = registry if registry is not None else MetricsRegistry()

        self._lock = threading.Lock()
        # Deferred mode (multi-worker Local path): attach() ships the raw
        # sparse batch instead of planning eagerly; the trainer prepares
        # AND applies at train time under the one step-serializing lock.
        self.deferred_prepare = False
        self._pending_writeback = set()     # store rows with fold in flight
        self._gather_q: "queue.Queue" = queue.Queue()
        self._fold_q: "queue.Queue" = queue.Queue()
        self._stop = threading.Event()
        self._threads = []
        self._started = False
        # Liveness counters the Local-path regression test asserts on.
        self.prefetch_ticks = 0
        self.fold_ticks = 0
        # Cold-gather seconds split by where they ran: the prefetcher
        # thread (overlapped with compute) vs the consumer at apply time
        # (on the critical path).  The bench reports the overlap share.
        self.gather_async_s = 0.0
        self.gather_sync_s = 0.0

        self._hits = self.registry.counter(
            "store_cache_hits_total",
            "Embedding lookups served by the device hot-row cache",
        )
        self._misses = self.registry.counter(
            "store_cache_misses_total",
            "Embedding lookups that needed a host-tier admission",
        )
        self._growth = self.registry.counter(
            "store_growth_rows_total",
            "Vocabulary rows lazily grown on first lookup",
        )
        self._gather_hist = self.registry.histogram(
            "store_cold_gather_seconds",
            "Host-tier gather latency for cold-row admissions",
        )
        self.registry.gauge_fn(
            "store_cache_occupancy_rows",
            lambda: float(self.cache.occupancy),
            "Resident rows in the device hot-row cache",
        )
        self.registry.gauge_fn(
            "store_cache_hit_ratio",
            self._hit_ratio,
            "Lifetime cache hit fraction of embedding lookups",
        )
        self.registry.gauge_fn(
            "store_device_cache_bytes",
            lambda: float(self.device_cache_bytes()),
            "Resident byte footprint of the device hot-row cache values",
        )
        self.registry.gauge_fn(
            "store_mesh_shards_count",
            lambda: float(self.mesh_shards),
            "Model-axis shards the cache slot arena is partitioned over",
        )

    def device_cache_bytes(self) -> int:
        """Analytic VALUE bytes of the device cache at full capacity —
        q8 codes + per-row scales for int8, 4 bytes/element for fp32.
        The fp32 carrier and optimizer moments are identical in both
        modes and excluded (store/cache.py cache_value_bytes_per_row)."""
        return device_cache_bytes(
            self.planes, self.cache_rows, self.cache_dtype
        )

    def set_mesh_shards(self, n: int) -> None:
        """Declare the model-axis mesh size the cache params are sharded
        over.  cache_rows must split evenly so every chip owns an equal
        contiguous slot block (same contiguous row-blocking jax uses for
        a P(\"model\", None) table)."""
        n = int(n)
        if n < 1 or self.cache_rows % n:
            raise ValueError(
                f"cache_rows={self.cache_rows} must divide evenly over "
                f"{n} mesh shards"
            )
        self.mesh_shards = n

    def _hit_ratio(self) -> float:
        hits = self._hits.value()
        total = hits + self._misses.value()
        return (hits / total) if total else 0.0

    # ---- background threads -------------------------------------------

    def start(self) -> None:
        """Start the cold-miss prefetcher and host-fold worker.  The
        Local path must call this too (it never goes through
        Master.start) — client/api.py owns that call."""
        if self._started:
            return
        self._started = True
        self._stop.clear()
        for name, fn in (("store-prefetch", self._gather_loop),
                         ("store-fold", self._fold_loop)):
            t = threading.Thread(target=fn, name=name, daemon=True)
            t.start()
            self._threads.append(t)

    def stop(self) -> None:
        if not self._started:
            return
        self._fold_q.join()      # drain pending write-backs first
        self._gather_q.put(None)
        self._fold_q.put(None)
        for t in self._threads:
            t.join(timeout=30.0)
        self._threads = []
        self._started = False

    def _gather_loop(self) -> None:
        while True:
            plan = self._gather_q.get()
            if plan is None:
                return
            try:
                t0 = time.perf_counter()
                plan.admit_values = self.host.gather(plan.prefetch_rows)
                dt = time.perf_counter() - t0
                self._gather_hist.record(dt)
                if self.phase_timer is not None:
                    self.phase_timer.add("cold_gather", dt, start=t0)
                self.gather_async_s += dt
                self.prefetch_ticks += 1
            except Exception:
                logger.exception("cold-row prefetch failed")
            finally:
                plan.ready.set()

    def _fold_loop(self) -> None:
        while True:
            item = self._fold_q.get()
            if item is None:
                self._fold_q.task_done()
                return
            rows, values = item
            try:
                self.host.set_rows(rows, values)
                with self._lock:
                    for r in rows:
                        self._pending_writeback.discard(int(r))
                self.fold_ticks += 1
            except Exception:
                logger.exception("host fold failed")
            finally:
                self._fold_q.task_done()

    # ---- producer side -------------------------------------------------

    def prepare(self, sparse: np.ndarray, ranked=None):
        """Producer-side planning: grow vocab, plan cache admissions,
        kick off the async host gather.  Returns (slots, plan).  MUST be
        called in batch order from a single thread.

        `ranked` is an optional `(uniq_ids, counts)` frequency ranking of
        THIS batch's FIELD-ENCODED ids (DedupPacker.last_ranking over
        `wire.field_disjoint_ids(sparse)` — the vocab keys (field, id),
        so raw ids colliding across fields must not merge).  Encoded
        value <-> (field, id) <-> store row is then a bijection on the
        batch, so the counts carry over to rows unchanged — only the
        unique VALUES need translating, one first-occurrence lookup
        instead of a full re-rank."""
        from elasticdl_tpu.data.wire import field_disjoint_ids

        with self._lock:
            rows, n_new = self.host.assign(sparse)
            if ranked is not None:
                uniq_ids = np.asarray(ranked[0], np.int64)
                flat_ids = field_disjoint_ids(sparse).reshape(-1)
                flat_rows = np.asarray(rows, np.int64).reshape(-1)
                sort_idx = np.argsort(flat_ids, kind="stable")
                sorted_ids = flat_ids[sort_idx]
                pos = np.searchsorted(sorted_ids, uniq_ids)
                if pos.size and (
                    int(pos.max(initial=0)) >= sorted_ids.size
                    or np.any(sorted_ids[np.minimum(
                        pos, sorted_ids.size - 1)] != uniq_ids)
                ):
                    raise ValueError(
                        "ranking does not match this batch's encoded "
                        "ids — rank wire.field_disjoint_ids(sparse), "
                        "not the raw per-field ids"
                    )
                rows_u = flat_rows[sort_idx[pos]]
                counts_u = np.asarray(ranked[1], np.int64)
                # Tie-break in ROW space: the wire ranking breaks count
                # ties toward the smaller encoded id, but admission order
                # must match `frequency_rank(rows)` (ties -> smaller row;
                # vocab rows are claimed in first-occurrence order, so
                # the two orders genuinely differ).  One lexsort over the
                # k uniques — still no re-count of the full batch.
                order = np.lexsort((rows_u, -counts_u))
                ranked = (rows_u[order], counts_u[order])
            plan = self.cache.plan(rows, ranked=ranked)
            self._finish_plan_locked(plan, n_new)
        self._publish_plan(plan, n_new)
        return plan.slots, plan

    def _finish_plan_locked(self, plan: CachePlan, n_new: int) -> None:
        plan.growth = n_new
        for r in plan.evict_rows:
            self._pending_writeback.add(int(r))
        plan.deferred = np.fromiter(
            (int(r) in self._pending_writeback
             for r in plan.admit_rows),
            bool, plan.admit_rows.size,
        )
        plan.prefetch_rows = plan.admit_rows[~plan.deferred]
        if self.mesh_shards > 1:
            plan.sub_plans = partition_plan(
                plan, self.mesh_shards, self.cache_rows
            )

    def _publish_plan(self, plan: CachePlan, n_new: int) -> None:
        self._hits.inc(plan.hits)
        self._misses.inc(plan.misses)
        if n_new:
            self._growth.inc(n_new)
            events.emit(events.STORE_GROWN, rows=n_new,
                        vocab_rows=self.host.size)
        if (plan.prefetch_rows.size and self._started
                and not self.deferred_prepare):
            self._gather_q.put(plan)
        else:
            # Nothing to prefetch (or threads not running: tests drive
            # apply_plan synchronously) — gather happens at apply time.
            # Deferred mode lands here on purpose: apply_plan runs
            # immediately after prepare, so bouncing the gather to the
            # prefetcher thread buys no overlap and would miscount the
            # wait as async; the sync gather is the honest attribution.
            plan.ready.set()

    # ---- consumer side -------------------------------------------------

    def apply_plan(self, state, plan: CachePlan):
        """Consumer-side execution, strictly before the train step that
        consumes `plan.slots`.  Returns the updated state."""
        if plan.evict_rows.size:
            evicted = store_device.read_rows(
                state, self.param_paths, plan.evict_slots,
                cache_dtype=self.cache_dtype,
            )
            self._fold_q.put((plan.evict_rows.copy(), evicted))
            if not self._started:
                self._drain_fold_queue_inline()
        if plan.admit_rows.size:
            plan.ready.wait()
            values = plan.admit_values
            missing = (
                plan.deferred
                if values
                else np.ones(plan.admit_rows.size, bool)
            )
            if missing.any():
                # Deferred rows: their latest value is on the fold queue
                # — flush it, then gather synchronously (attributed to
                # cold_gather on the consumer, i.e. NOT overlapped).
                t0 = time.perf_counter()
                self._fold_q.join()
                cold = self.host.gather(plan.admit_rows[missing])
                dt = time.perf_counter() - t0
                self._gather_hist.record(dt)
                if self.phase_timer is not None:
                    self.phase_timer.add("cold_gather", dt, start=t0)
                self.gather_sync_s += dt
                full = {}
                for name, dim in self.planes.items():
                    arr = np.empty(
                        (plan.admit_rows.size, dim), np.float32
                    )
                    if values:
                        arr[~missing] = values[name]
                    arr[missing] = cold[name]
                    full[name] = arr
                values = full
            state = store_device.apply_admissions(
                state, self.param_paths, plan.admit_slots, values,
                cache_dtype=self.cache_dtype,
            )
        return state

    def _drain_fold_queue_inline(self) -> None:
        """Synchronous fold for thread-less (unit-test) operation."""
        while True:
            try:
                item = self._fold_q.get_nowait()
            except queue.Empty:
                return
            if item is None:
                self._fold_q.task_done()
                continue
            rows, values = item
            try:
                self.host.set_rows(rows, values)
                with self._lock:
                    for r in rows:
                        self._pending_writeback.discard(int(r))
                self.fold_ticks += 1
            finally:
                self._fold_q.task_done()

    # ---- feed integration ---------------------------------------------

    def enable_deferred_prepare(self) -> None:
        """Multi-worker Local mode: move planning from the (no longer
        unique) feed producer to the trainer's step-serialized critical
        section.  prepare+apply then run back to back in the SAME order
        the steps run, which restores the strict-batch-order invariant
        with any number of producer threads — trading away the async
        cold-gather overlap (every gather becomes a sync gather)."""
        self.deferred_prepare = True

    def attach(self, batch: dict) -> dict:
        """Rewrite one feed batch: raw `sparse` ids become cache `slots`,
        and the plan rides along under `__store_plan__` (popped by the
        trainer before any tree_map sees the batch).  A feed that packed
        this batch through DedupPacker can leave the packer's ranking
        under `__dedup_ranking__` (popped here, never shipped) and the
        admission plan reuses it.  In deferred mode the raw sparse batch
        (+ ranking) rides under `__store_sparse__` instead and the
        trainer plans at train time."""
        features = dict(batch["features"])
        sparse = features.pop("sparse")
        out = dict(batch)
        ranked = out.pop("__dedup_ranking__", None)
        if self.deferred_prepare:
            sparse = np.asarray(sparse)
            # Placeholder keeps the feature structure complete for
            # model.init / export signatures; the trainer overwrites it
            # with the real planned slots inside the step-serialized
            # region (train_on_batch's __store_sparse__ branch).
            features["slots"] = np.zeros(sparse.shape, np.int32)
            out["features"] = features
            out["__store_sparse__"] = (sparse, ranked)
            return out
        slots, plan = self.prepare(sparse, ranked=ranked)
        features["slots"] = slots
        out["features"] = features
        out["__store_plan__"] = plan
        return out

    def wrap_feed(self, feed):
        """Wrap a feed/feed_bulk callable so every batch it produces is
        store-prepared.  Runs on the prefetch producer thread — the ONE
        sequential prepare() site."""
        if feed is None:
            return None

        def wrapped(*args, **kwargs):
            return self.attach(feed(*args, **kwargs))

        return wrapped

    # ---- checkpoint integration ---------------------------------------

    def load_sidecar_state(self, host_state: Dict[str, np.ndarray],
                           row_of: np.ndarray,
                           score: Optional[np.ndarray] = None,
                           cache_dtype: Optional[str] = None,
                           convert: bool = False) -> None:
        """Adopt a restored sidecar: host planes + vocab + cache map.
        Cache VALUES live in the restored TrainState (orbax), so only
        bookkeeping changes here.  `cache_dtype` is the sidecar's
        recorded plane dtype (None for pre-ISSUE-18 sidecars = fp32);
        a mismatch against this store's dtype raises unless `convert`
        acknowledges the values were migrated (CheckpointSaver's
        arena_convert path)."""
        with self._lock:
            self.host.load_state_dict(host_state)
            self.cache.load_state_arrays(
                row_of, score, dtype=cache_dtype, convert=convert
            )
            self._pending_writeback.clear()

    # ---- introspection -------------------------------------------------

    def stats(self) -> dict:
        hits = self._hits.value()
        misses = self._misses.value()
        total = hits + misses
        return {
            "hit_rate": (hits / total) if total else 0.0,
            "hits": int(hits),
            "misses": int(misses),
            "growth_rows": int(self._growth.value()),
            "vocab_rows": self.host.size,
            "cache_occupancy_rows": self.cache.occupancy,
            "cache_rows": self.cache_rows,
            "cache_dtype": self.cache_dtype,
            "device_cache_bytes": self.device_cache_bytes(),
            "mesh_shards": self.mesh_shards,
            "host_bytes": self.host.nbytes,
            "prefetch_ticks": self.prefetch_ticks,
            "fold_ticks": self.fold_ticks,
            "cold_gather_async_s": self.gather_async_s,
            "cold_gather_sync_s": self.gather_sync_s,
            "cold_gather_overlap_share": (
                self.gather_async_s
                / max(self.gather_async_s + self.gather_sync_s, 1e-12)
            ),
        }
