"""Profiling/tracing utilities.

The reference had only coarse log-line timing (SURVEY.md §5); here the
worker's timed regions are phases with totals AND spans on one clock
(`PhaseTimer`, one a process), the step rate comes from the loop's
per-task synchronised stamp (`SyncedStepRate`), and the JAX profiler is
one call away (Perfetto/XPlane traces TensorBoard can read, with the
same regions on the host plane as `edl:<phase>`).
"""

from __future__ import annotations

import contextlib
import threading
import time
from collections import deque
from typing import NamedTuple, Optional

from elasticdl_tpu.common.log_utils import get_logger

logger = get_logger(__name__)


#: The step-phase vocabulary (docs/OBSERVABILITY.md "Phase catalogue").
#: Every phase a worker attributes step time to; the labeled
#: `worker_step_phase_seconds{phase=...}` histogram uses exactly these.
#: LOOP_PHASES tile the worker loop's own thread from one task end to the
#: next; PRODUCER_PHASES run on the prefetch producer thread and overlap
#: them.
LOOP_PHASES = (
    "get_task", "data_wait", "h2d_stage", "compute", "task_sync", "report",
)
PRODUCER_PHASES = ("read", "pack", "queue_full")
STEP_PHASES = LOOP_PHASES + PRODUCER_PHASES + (
    # tiered embedding store (elasticdl_tpu/store): host-tier gathers for
    # cold rows — on the prefetcher thread when overlapped, on the
    # consumer when a deferred row forces a synchronous gather.  Its
    # `share` vs `compute` is the cold-tail overlap (no cell of the
    # benchmark reads it yet: ROADMAP.md Reach 7).
    "cold_gather",
)

#: Records the span ring keeps before the oldest fall out.  A train step
#: makes about five (measured: 4.6 in the benchmark's DeepFM cell), so
#: this is some 7,000 steps back.
SPAN_RING_RECORDS = 32768


class Span(NamedTuple):
    """One timed region as the ring keeps it.  `start` / `end` are
    `time.perf_counter()` seconds, `thread` the native thread id (the
    profiler's host lines carry the same), `step` the index of the batch
    in its task, `parent` the name of the enclosing span on that thread,
    `attrs` what the call site added (e.g. data_wait's queue `depth`)."""

    name: str
    start: float
    end: float
    thread: int
    task_id: Optional[int]
    step: Optional[int]
    parent: Optional[str]
    attrs: Optional[dict]


_KEEP = object()          # mark(): "leave this field as it is"


class _ThreadMarks:
    """What a PhaseTimer keeps for each thread that records into it."""

    __slots__ = ("native_id", "open", "task_id", "step")

    def __init__(self):
        self.native_id = threading.get_native_id()
        self.open = []        # names of the spans open on this thread
        self.task_id = self.step = None


class _OpenSpan:
    """The context manager `PhaseTimer.phase()` returns.  `task_id` and
    `step` start as the thread's marks and may be set until the region
    closes; `end` is readable afterwards (a synchronised stamp when the
    region ended in a device fetch)."""

    __slots__ = ("_timer", "_marks", "_annotation", "name", "start",
                 "end", "task_id", "step", "parent", "attrs")

    def __init__(self, timer, name, attrs):
        self._timer = timer
        self.name = name
        self.attrs = attrs or None
        self.end = None

    def __enter__(self):
        marks = self._marks = self._timer._thread_marks()
        self.task_id, self.step = marks.task_id, marks.step
        self.parent = marks.open[-1] if marks.open else None
        marks.open.append(self.name)
        # with no profiler session on this is a flag check; with one on,
        # the region lies on the trace's host plane, on the device's clock
        self._annotation = self._timer._annotate("edl:" + self.name)
        self._annotation.__enter__()
        self.start = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self.end = time.perf_counter()
        self._annotation.__exit__(*exc)
        self._marks.open.pop()
        self._timer._record(Span(
            self.name, self.start, self.end, self._marks.native_id,
            self.task_id, self.step, self.parent, self.attrs,
        ))
        return False


class PhaseTimer:
    """Attributes each train step's wall time to named phases, and keeps
    each timed region as a span.

    The worker loop wraps each region in `with timer.phase("compute"):`
    (or calls `add(name, seconds, start)` for regions timed elsewhere,
    e.g. the tiered store's gather thread) and calls `step_done()` once
    per executed step.  Per-phase seconds feed a labeled registry
    histogram when one is supplied, cumulative totals back the telemetry
    payload, and every `flush_every` steps the accumulated breakdown is
    emitted as ONE `step_phases` span event so the attribution survives
    into the cross-process event log (common/events.py) without a
    per-step write.

    Every region is also one `Span` in a bounded in-memory ring
    (`spans()`), and `phase()` opens `jax.profiler.TraceAnnotation(
    "edl:<name>")` around it, so under a profiler session the same
    regions lie on the `.xplane.pb` host plane.  `mark()` sets the task
    and step the calling thread's next spans belong to.  Nothing is
    written anywhere on the hot path.

    Thread-safe: the prefetch producer thread times `read` / `pack` /
    `queue_full` while the consumer loop runs `phase()`/`step_done()`.
    """

    def __init__(self, phases=STEP_PHASES, histogram=None,
                 flush_every: int = 50, ring: int = SPAN_RING_RECORDS):
        # here, not at import: the master reads this module's vocabulary
        # and never touches jax
        from jax.profiler import TraceAnnotation

        self._annotate = TraceAnnotation
        self.phases = tuple(phases)
        self._histogram = histogram   # labeled _HistogramFamily or None
        self._flush_every = max(1, int(flush_every))
        self._lock = threading.Lock()
        self._local = threading.local()   # .marks: this thread's own
        self._ring = deque(maxlen=int(ring))
        self._totals = {p: 0.0 for p in self.phases}      # job lifetime
        self._pending = {p: 0.0 for p in self.phases}     # since last flush
        self._steps = 0
        self._pending_steps = 0

    def mark(self, task_id=_KEEP, step=_KEEP) -> None:
        """The task and the step (index of the batch in its task) that
        this thread's following spans belong to."""
        marks = self._thread_marks()
        if task_id is not _KEEP:
            marks.task_id = task_id
        if step is not _KEEP:
            marks.step = step

    def marks(self) -> tuple:
        """(task_id, step) as marked on this thread: what a thread hands
        to one it starts."""
        marks = self._thread_marks()
        return marks.task_id, marks.step

    def _thread_marks(self) -> _ThreadMarks:
        try:
            return self._local.marks
        except AttributeError:
            marks = self._local.marks = _ThreadMarks()
            return marks

    def phase(self, name: str, **attrs) -> _OpenSpan:
        return _OpenSpan(self, name, attrs)

    def add(self, name: str, seconds: float,
            start: Optional[float] = None) -> None:
        """A region timed by the caller: `seconds` long, begun at `start`
        on `time.perf_counter()` (default: ended now)."""
        seconds = max(0.0, float(seconds))
        if start is None:
            start = time.perf_counter() - seconds
        marks = self._thread_marks()
        self._record(Span(
            name, start, start + seconds, marks.native_id,
            marks.task_id, marks.step,
            marks.open[-1] if marks.open else None, None,
        ))

    def _record(self, span: Span) -> None:
        name = span.name
        if name not in self._totals:
            return  # unknown phase: attribution must never raise
        seconds = span.end - span.start
        with self._lock:
            self._totals[name] += seconds
            self._pending[name] += seconds
            self._ring.append(span)
        if self._histogram is not None:
            try:
                self._histogram.labels(phase=name).record(seconds)
            except Exception:
                pass

    def spans(self) -> list:
        """The ring's records, oldest first (in the order regions ENDED;
        at most `ring` of them)."""
        with self._lock:
            return list(self._ring)

    def step_done(self) -> None:
        """Count one executed step; flush a `step_phases` span event at
        the flush interval."""
        with self._lock:
            self._steps += 1
            self._pending_steps += 1
            if self._pending_steps < self._flush_every:
                return
        self.flush()

    def flush(self) -> None:
        """Force out whatever accumulated since the last flush (end of a
        task/job: partial windows must not be lost)."""
        with self._lock:
            if not self._pending_steps:
                return
            payload = {
                p: round(v, 6) for p, v in self._pending.items()
            }
            steps = self._pending_steps
            for p in self._pending:
                self._pending[p] = 0.0
            self._pending_steps = 0
        from elasticdl_tpu.common import events

        events.emit(events.STEP_PHASES, phases=payload, steps=steps)

    @property
    def steps(self) -> int:
        with self._lock:
            return self._steps

    def snapshot(self) -> dict:
        """{phase: {"total_s", "mean_s", "share"}} over the job so far.
        `share` is the phase's fraction of all attributed time."""
        with self._lock:
            totals = dict(self._totals)
            steps = self._steps
        attributed = sum(totals.values())
        return {
            p: {
                "total_s": t,
                "mean_s": (t / steps) if steps else 0.0,
                "share": (t / attributed) if attributed else 0.0,
            }
            for p, t in totals.items()
        }

    def totals_milli(self) -> dict:
        """{phase: cumulative milliseconds} as ints — the shape the
        worker's int64 telemetry piggyback (report exec_counters) can
        carry."""
        with self._lock:
            return {
                p: int(round(v * 1000.0)) for p, v in self._totals.items()
            }


_process_timer: Optional[PhaseTimer] = None
_process_timer_lock = threading.Lock()


def process_phase_timer() -> PhaseTimer:
    """The process's one PhaseTimer, feeding the labeled
    `worker_step_phase_seconds` histogram of the default registry.  Both
    worker loops (threaded and SPMD) and the tiered store record into it:
    cluster mode runs one worker or rank a process, so per-process totals
    are per-worker totals."""
    global _process_timer
    with _process_timer_lock:
        if _process_timer is None:
            # here, not at import: common/metrics imports this module
            from elasticdl_tpu.common import metrics as metrics_lib

            histogram = metrics_lib.default_registry().histogram(
                "worker_step_phase_seconds",
                "per-step wall time attributed to a phase "
                "(profiler.STEP_PHASES)",
                labelnames=("phase",),
            )
            # Zero-initialize every catalogued phase so /metrics always
            # exposes the full vocabulary — phases a given run never
            # exercises (cold_gather is tiered-store-only) render with
            # count 0 instead of disappearing.
            for phase in STEP_PHASES:
                histogram.labels(phase=phase)
            _process_timer = PhaseTimer(histogram=histogram)
        return _process_timer


class SyncedStepRate:
    """Steps a second between synchronised stamps: the steps of a task
    over the time from the previous `task_sync` span's end to this one's.
    The loop fetches the task's last loss there, so at both stamps the
    device has finished what the host has counted, and nothing is
    synchronised that was not already."""

    def __init__(self):
        self._last_end: Optional[float] = None
        self.steps_per_sec = 0.0

    def task_synced(self, end: float, steps: int) -> None:
        if self._last_end is not None and end > self._last_end and steps:
            self.steps_per_sec = steps / (end - self._last_end)
        self._last_end = end


class LatencyHistogram:
    """Thread-safe log-bucketed latency histogram with quantile reads.

    Serving needs p50/p99 over an unbounded stream without keeping every
    sample; log-spaced buckets give a bounded-error quantile (each bucket
    spans `growth`x, so a reported quantile is within one growth factor of
    truth) at O(1) record cost under a lock — the batcher records from its
    dispatch threads while Health RPCs read concurrently.
    """

    def __init__(self, min_s: float = 1e-4, max_s: float = 60.0,
                 growth: float = 1.25):
        import math
        import threading

        self._min_s = min_s
        self._log_min = math.log(min_s)
        self._log_growth = math.log(growth)
        nbuckets = int(math.ceil(
            (math.log(max_s) - self._log_min) / self._log_growth
        )) + 1
        # bucket i covers [min_s * growth**i, min_s * growth**(i+1));
        # underflow clamps to 0, overflow to the last bucket
        self._uppers = [
            min_s * growth ** (i + 1) for i in range(nbuckets)
        ]
        self._counts = [0] * nbuckets
        self._total = 0
        self._sum_s = 0.0
        self._lock = threading.Lock()

    def record(self, seconds: float) -> None:
        import math

        if seconds < self._min_s:
            idx = 0
        else:
            idx = int((math.log(seconds) - self._log_min)
                      / self._log_growth)
            idx = min(idx, len(self._counts) - 1)
        with self._lock:
            self._counts[idx] += 1
            self._total += 1
            self._sum_s += seconds

    @property
    def count(self) -> int:
        with self._lock:
            return self._total

    def bucket_snapshot(self):
        """(uppers, counts, total, sum_s) copied under ONE lock
        acquisition — the consistent basis for quantiles and for the
        Prometheus histogram exposition (common/metrics.py), which needs
        the raw cumulative buckets, not just the derived quantiles."""
        with self._lock:
            return (
                list(self._uppers), list(self._counts),
                self._total, self._sum_s,
            )

    @staticmethod
    def _quantile_from(uppers, counts, total, q: float) -> float:
        if not total:
            return 0.0
        rank = q * (total - 1)
        seen = 0
        for idx, c in enumerate(counts):
            seen += c
            if seen > rank:
                return uppers[idx]
        return uppers[-1]

    def quantile(self, q: float) -> float:
        """Upper edge of the bucket holding the q-quantile, in seconds.
        Returns 0.0 before any sample."""
        uppers, counts, total, _ = self.bucket_snapshot()
        return self._quantile_from(uppers, counts, total, q)

    def snapshot(self) -> dict:
        """{count, mean_s, p50_s, p99_s} — one consistent read.  All four
        numbers derive from a single locked copy of the buckets; the old
        implementation re-acquired the lock per quantile, so a concurrent
        `record()` could make count/mean and p50/p99 describe different
        populations."""
        uppers, counts, total, sum_s = self.bucket_snapshot()
        return {
            "count": total,
            "mean_s": (sum_s / total) if total else 0.0,
            "p50_s": self._quantile_from(uppers, counts, total, 0.5),
            "p99_s": self._quantile_from(uppers, counts, total, 0.99),
        }


@contextlib.contextmanager
def trace(log_dir: str):
    """Capture a JAX profiler trace viewable in TensorBoard/Perfetto:

        with profiler.trace("/tmp/trace"):
            state, loss = trainer.train_on_batch(state, batch)
            jax.block_until_ready(loss)
    """
    import jax

    jax.profiler.start_trace(log_dir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()
        logger.info("Profiler trace written to %s", log_dir)


@contextlib.contextmanager
def annotate(name: str):
    """Name a region so it shows up in profiler timelines."""
    import jax

    with jax.profiler.TraceAnnotation(name):
        yield
