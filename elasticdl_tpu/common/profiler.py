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
import functools
import os
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

#: The start-up vocabulary (docs/OBSERVABILITY.md "Start-up catalogue"):
#: what a process does between its start and its loop's first `get_task`,
#: one after the other on its main path.  `boot`, `job_setup` and
#: `worker_setup` are recorded by `PhaseTimer.begin_startup()` /
#: `startup()`, `init_state` and `restore` through `phase()` where the
#: state is made (lazily, from the first batch's shapes: inside the first
#: task, on the loop thread).  Their totals feed the labeled gauge
#: `worker_startup_phase_seconds{phase=...}`, never the per-step
#: histogram.
STARTUP_PHASES = (
    "boot", "job_setup", "init_state", "restore", "worker_setup",
)
#: The stages of ONE compile, children of whatever span the compiling
#: thread is in (`init_state`, `compute`, ...), laid back from jax's own
#: monitoring events by `common/programs.py`; `attrs` name the `program`
#: and, on `compile_xla`, the persistent cache's answer (`cache`).
COMPILE_PHASES = ("compile_trace", "compile_lower", "compile_xla")

#: The device-scope vocabulary (docs/OBSERVABILITY.md "Device scope
#: catalogue"): the `jax.named_scope`s by which a train step's device
#: time is told apart.  A compiled instruction belongs to the INNERMOST
#: entry on its `op_name` path (`catalogue_scope`), so `dispatch` inside
#: `glm/moe` is `dispatch` and what `glm/moe` does outside its inner
#: scopes is `glm/moe`; together they tile the step
#: (`scope_unattributed_share` is the check).  Every entry is read by a
#: per-layer metric of the benchmark (PERF.md section 3 names it) and by
#: the operator's `scope_ms.json` summary; a scope nothing reads does
#: not enter.
DEVICE_SCOPES = (
    # worker/trainer.py: optimizer.update + apply_updates (+ the int8
    # arena's fold)
    "train/optimizer",
    # layers/embedding.py: the forward gather; the backward's two halves
    "arena/lookup", "arena/combine", "arena/scatter",
    # model_zoo/deepfm: everything after the lookups
    "deepfm/tower",
    # layers/moe.py, inside a model's `<model>/moe`
    "router", "dispatch", "experts", "combine",
    # model_zoo/common/decoder.py: the shared expert
    "shared",
    "glm/embed", "glm/norm", "glm/mla/proj", "glm/mla/core", "glm/mla/out",
    "glm/dense_ffn", "glm/moe", "glm/mtp", "glm/head_ce",
    "laguna/embed", "laguna/norm", "laguna/attn_full", "laguna/attn_window",
    "laguna/gate", "laguna/dense_ffn", "laguna/moe", "laguna/head_ce",
    "lfm2/embed", "lfm2/norm", "lfm2/short_conv", "lfm2/attn",
    "lfm2/dense_ffn", "lfm2/moe", "lfm2/head_ce",
    "kimi/embed", "kimi/norm", "kimi/kda/proj", "kimi/kda/conv",
    "kimi/kda/gate", "kimi/kda/core", "kimi/kda/out", "kimi/mla/proj",
    "kimi/mla/core", "kimi/mla/out", "kimi/dense_ffn", "kimi/moe",
    "kimi/head_ce",
    "granite/embed", "granite/norm", "granite/ssm/proj", "granite/ssm/conv",
    "granite/ssm/core", "granite/ssm/gated_norm", "granite/ssm/out",
    "granite/attn", "granite/dense_ffn", "granite/head_ce",
    "nemotron/embed", "nemotron/norm", "nemotron/ssm/proj",
    "nemotron/ssm/conv", "nemotron/ssm/core", "nemotron/ssm/gated_norm",
    "nemotron/ssm/out", "nemotron/attn", "nemotron/moe", "nemotron/head_ce",
    "qwen3_next/embed", "qwen3_next/norm", "qwen3_next/gdn/proj",
    "qwen3_next/gdn/conv", "qwen3_next/gdn/decay", "qwen3_next/gdn/core",
    "qwen3_next/gdn/out", "qwen3_next/attn", "qwen3_next/moe",
    "qwen3_next/head_ce",
    # (`smallthinker/route`, the routing ahead of attention, is a path and
    # no entry: its leaves are `router`'s and `dispatch`'s, and a rule
    # that names it reads them by the path)
    "smallthinker/embed", "smallthinker/norm", "smallthinker/attn_full",
    "smallthinker/attn_window", "smallthinker/moe", "smallthinker/head_ce",
    # model_zoo/ouro: the blocks run inside the trips' loop, whose `while`
    # counts as its body's operations; `ouro/trips` is what the loop does
    # beside its blocks (the stacks of what a trip keeps, written forward
    # and read backward, their layout copies, a weight's gradient summed
    # over the trips); `ouro/exit` is the gate, the exit distribution, its
    # entropy and the weighing of the trips' losses
    "ouro/embed", "ouro/trips", "ouro/norm", "ouro/attn", "ouro/dense_ffn",
    "ouro/exit", "ouro/head_ce",
    # model_zoo/olmo_hybrid: the delta-rule layer's five are
    # `model_zoo/common/delta_net.py`'s under this model's prefix;
    # `attn/qk_norm` is the whole-width QK-norm inside `attn` (its sum of
    # squares is what head-parallel chips exchange)
    "olmo_hybrid/embed", "olmo_hybrid/norm", "olmo_hybrid/gdn/proj",
    "olmo_hybrid/gdn/conv", "olmo_hybrid/gdn/decay", "olmo_hybrid/gdn/core",
    "olmo_hybrid/gdn/out", "olmo_hybrid/attn", "olmo_hybrid/attn/qk_norm",
    "olmo_hybrid/dense_ffn", "olmo_hybrid/head_ce",
)

#: Kernels the TPU's compiler makes from ONE primitive and names after
#: it, dropping the path the primitive was traced under (`lax.ragged_dot`
#: becomes `%ragged-dot-none.N` with `op_name="ragged-dot-none"`): the
#: scope this program calls that primitive under, by the instruction
#: name's prefix.  `layers/moe.py: grouped_matmul` and `_walk_bwd`'s two
#: stack gradients are the only callers, all under `experts`
#: (tests/test_tpu_compile.py compiles one for the chip and finds it
#: there).
COMPILER_NAMED_SCOPES = {"ragged-dot": "experts"}

#: Records the span ring keeps before the oldest fall out.  A train step
#: makes about five (measured: 4.6 in the benchmark's DeepFM cell), so
#: this is some 7,000 steps back.
SPAN_RING_RECORDS = 32768
#: Records of the start-up and compile vocabulary kept OUTSIDE the ring,
#: where no later record pushes them out: a job of a day still shows its
#: start.  A warm decoder job makes some hundreds before its first step
#: (three a compile); past this many (a retrace storm late in a long job)
#: they go into the ring like any other.
STARTUP_SPAN_RECORDS = 4096

_IMPORTED_AT = time.perf_counter()


@functools.lru_cache(maxsize=None)
def process_start() -> float:
    """The process's start on `time.perf_counter()`: the OS's own stamp
    (`/proc/self/stat`, field 22: clock ticks after boot) mapped once
    onto the spans' clock, so that the interpreter's start and every
    import lie inside `boot`; the first import of this module where the
    OS gives none."""
    try:
        with open("/proc/self/stat") as f:
            # the command may hold spaces and brackets: fields after it
            fields = f.read().rsplit(")", 1)[1].split()
        started = int(fields[19]) / os.sysconf("SC_CLK_TCK")
        age = time.clock_gettime(time.CLOCK_BOOTTIME) - started
    except (OSError, ValueError, IndexError, AttributeError):
        return _IMPORTED_AT
    if age < 0:
        return _IMPORTED_AT
    return min(time.perf_counter() - age, _IMPORTED_AT)


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

    Start-up (`STARTUP_PHASES`) and compile stages (`COMPILE_PHASES`)
    are the same `Span` records through the same `phase()` / `add()`,
    with totals of their own (a labeled gauge, `startup_gauge`; never
    the per-step histogram, the `step_phases` event or `snapshot()`).
    The first `STARTUP_SPAN_RECORDS` of them are kept in a list beside
    the ring, which `spans()` puts in front of it, so the ring's
    turnover never drops a job's start; later ones share the ring.
    `begin_startup()` / `startup()` carry the main path's phases from
    one function (and thread) to the next: each call closes the phase
    that is open and opens the next, so they cannot overlap.

    Thread-safe: the prefetch producer thread times `read` / `pack` /
    `queue_full` while the consumer loop runs `phase()`/`step_done()`.
    """

    def __init__(self, phases=STEP_PHASES, histogram=None,
                 flush_every: int = 50, ring: int = SPAN_RING_RECORDS,
                 startup_gauge=None):
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
        self._startup_gauge = startup_gauge   # labeled gauge or None
        self._startup_totals = {
            p: 0.0 for p in STARTUP_PHASES + COMPILE_PHASES
        }
        self._startup: list = []      # outside the ring: never pushed out
        self._startup_open = None     # (name, start, native thread id)
        self._booted = False

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
            start: Optional[float] = None, **attrs) -> None:
        """A region timed by the caller: `seconds` long, begun at `start`
        on `time.perf_counter()` (default: ended now)."""
        seconds = max(0.0, float(seconds))
        if start is None:
            start = time.perf_counter() - seconds
        marks = self._thread_marks()
        self._record(Span(
            name, start, start + seconds, marks.native_id,
            marks.task_id, marks.step,
            marks.open[-1] if marks.open else None, attrs or None,
        ))

    def begin_startup(self, entered: float) -> None:
        """At a process's entry point (`entered` on `perf_counter()`):
        `boot`, from the process's start to there, once a process, and
        `job_setup` opened there."""
        if not self._booted:
            self._booted = True
            self.add("boot", entered - process_start(), process_start())
        self.startup("job_setup", start=entered)

    def startup(self, name: Optional[str],
                start: Optional[float] = None) -> None:
        """Close the main path's open start-up phase, now, and open
        `name` (None: none; the loop's own spans carry on from here).
        The closed span belongs to the thread that OPENED it: the loop
        thread closes `worker_setup`, which the main thread was in."""
        now = time.perf_counter()
        with self._lock:
            closed = self._startup_open
            self._startup_open = None if name is None else (
                name, now if start is None else start,
                threading.get_native_id(),
            )
        if closed is not None:
            self._record(Span(
                closed[0], closed[1], max(now, closed[1]), closed[2],
                None, None, None, None,
            ))

    def _record(self, span: Span) -> None:
        name = span.name
        seconds = span.end - span.start
        if name in self._startup_totals:
            with self._lock:
                self._startup_totals[name] += seconds
                total = self._startup_totals[name]
                if len(self._startup) < STARTUP_SPAN_RECORDS:
                    self._startup.append(span)
                else:
                    self._ring.append(span)
            if self._startup_gauge is not None and name in STARTUP_PHASES:
                self._startup_gauge.labels(phase=name).set(total)
            return
        if name not in self._totals:
            return  # unknown phase: attribution must never raise
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
        """The kept start-up and compile records (at most
        `STARTUP_SPAN_RECORDS`), then the ring's (at most `ring`), each
        oldest first (in the order regions ENDED)."""
        with self._lock:
            return self._startup + list(self._ring)

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
            startup_gauge = metrics_lib.default_registry().gauge(
                "worker_startup_phase_seconds",
                "wall seconds of the process's start-up by phase "
                "(profiler.STARTUP_PHASES), summed where one recurs",
                labelnames=("phase",),
            )
            for phase in STARTUP_PHASES:
                startup_gauge.labels(phase=phase)
            _process_timer = PhaseTimer(
                histogram=histogram, startup_gauge=startup_gauge
            )
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


_SCOPE_PARTS = tuple(tuple(s.split("/")) for s in DEVICE_SCOPES)


def _ends_at(parts, wanted) -> int:
    """Index just past the LAST place `wanted` lies in `parts` as a
    contiguous run, 0 where it does not."""
    width = len(wanted)
    for start in range(len(parts) - width, -1, -1):
        if parts[start:start + width] == wanted:
            return start + width
    return 0


def catalogue_scope(scope: str) -> str:
    """The innermost `DEVICE_SCOPES` entry on the path `scope`
    (`layer_1/glm/moe/routed/dispatch` -> `dispatch`), "" for none."""
    parts = tuple(scope.split("/"))
    best, best_key = "", (0, 0)
    for entry, wanted in zip(DEVICE_SCOPES, _SCOPE_PARTS):
        key = (_ends_at(parts, wanted), len(wanted))
        if key[0] and key > best_key:
            best, best_key = entry, key
    return best


def instruction_name(hlo_text: str) -> str:
    """`%fusion.3 = f32[8,16]{...} fusion(...)` -> `fusion.3`: the name
    a trace event and the compiled text share."""
    return hlo_text.split(" ", 1)[0].lstrip("%")


def device_ms_by_scope(op_seconds, table, scopes=None, phase=None,
                       exclude_ops=None) -> dict:
    """Device time of a trace's operations by the program's scopes.

    `op_seconds` is {an operation's HLO text (or bare instruction name):
    time}, `table` the program's scope table (`ProgramRegistry.
    scope_table`).  Joined by the instruction's name; LEAVES only: a
    `while`, `conditional` or `call` lasts as long as the operations
    inside it, which the trace names too.  Kept are the leaves that
    belong to one of `scopes` (None keeps all): a catalogue entry names
    the leaves whose INNERMOST entry it is (`combine` is not the
    `experts` inside the loop `combine` wraps), any other name (a
    module's, `layer_1`) the leaves with it on their path; in `phase`
    (`forward`, `backward`, `rebuild`; None keeps all); whose text
    matches none of the `exclude_ops` patterns.  Returns, in
    `op_seconds`' unit,

        by_scope   {(innermost catalogue entry or "", phase): time}
        unjoined   time of operations the table does not hold (counted
                   whatever the filters: nothing says where they belong)
        mixed      time, within by_scope, of fusions whose fused
                   instructions lie in more than one catalogue entry:
                   how far a by-scope number can be off
        mixed_ops  {instruction name: time} of those fusions
    """
    import re

    exclude = [re.compile(p) for p in exclude_ops or ()]
    entries = set(scopes or ()) & set(DEVICE_SCOPES)
    named = [tuple(s.split("/")) for s in scopes or () if s not in entries]
    by_scope, mixed_ops, unjoined = {}, {}, 0.0
    for text, seconds in op_seconds.items():
        name = instruction_name(text)
        row = table.get(name)
        if row is None:
            unjoined += seconds
            continue
        if row.container or (phase is not None and row.phase != phase):
            continue
        if scopes and row.entry not in entries:
            parts = tuple(row.scope.split("/"))
            if not any(_ends_at(parts, other) for other in named):
                continue
        if any(p.search(text) for p in exclude):
            continue
        key = (row.entry, row.phase)
        by_scope[key] = by_scope.get(key, 0.0) + seconds
        if len(row.fused) > 1:
            mixed_ops[name] = mixed_ops.get(name, 0.0) + seconds
    return {
        "by_scope": by_scope, "unjoined": unjoined,
        "mixed": sum(mixed_ops.values()), "mixed_ops": mixed_ops,
    }


def scope_summary(op_seconds, table, steps: int) -> dict:
    """{scope: {"ms_per_step", "rebuilt_share"}} of one capture: each
    catalogue scope's device milliseconds a step and the share of them
    that JAX's remat spent rebuilding the forward; `(no scope)` is what
    lies under no catalogue entry, `(unjoined)` what the table lacks."""
    whole = device_ms_by_scope(op_seconds, table)
    scale = 1e3 / max(int(steps), 1)
    total, rebuilt = {"(unjoined)": whole["unjoined"]}, {}
    for (scope, phase), seconds in whole["by_scope"].items():
        scope = scope or "(no scope)"
        total[scope] = total.get(scope, 0.0) + seconds
        if phase == "rebuild":
            rebuilt[scope] = rebuilt.get(scope, 0.0) + seconds
    return {
        scope: {
            "ms_per_step": seconds * scale,
            "rebuilt_share": rebuilt.get(scope, 0.0) / seconds,
        }
        for scope, seconds in sorted(total.items(), key=lambda kv: -kv[1])
        if seconds
    }


def xla_op_seconds(log_dir: str) -> dict:
    """{operation's HLO text: seconds} summed over the `XLA Ops` line of
    the first device plane of the newest capture under `log_dir`; {}
    where there is none (the CPU backend writes no device plane)."""
    import glob
    import os

    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb"
    )))
    seconds: dict = {}
    for plane in ProfileData.from_file(paths[-1]).planes if paths else ():
        if seconds or not plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for event in line.events if line.name == "XLA Ops" else ():
                seconds[event.name] = (
                    seconds.get(event.name, 0.0) + event.duration_ns * 1e-9
                )
    return seconds


def _write_scope_summary(log_dir: str, steps: int) -> None:
    """`scope_ms.json` beside the capture and one log line a scope: the
    captured train steps' device time by `DEVICE_SCOPES`."""
    import json
    import os

    from elasticdl_tpu.common import programs

    op_seconds = xla_op_seconds(log_dir)
    table = programs.default_program_registry().scope_table(
        "worker_train_step"
    )
    if not op_seconds or not table or not steps:
        return
    summary = scope_summary(op_seconds, table, steps)
    with open(os.path.join(log_dir, "scope_ms.json"), "w") as f:
        json.dump({"steps": steps, "scopes": summary}, f, indent=1)
    for scope, row in summary.items():
        logger.info(
            "device scope %-22s %9.2f ms a step, rebuilt %4.1f%%", scope,
            row["ms_per_step"], 100.0 * row["rebuilt_share"],
        )


@contextlib.contextmanager
def trace(log_dir: str):
    """Capture a JAX profiler trace viewable in TensorBoard/Perfetto:

        with profiler.trace("/tmp/trace"):
            state, loss = trainer.train_on_batch(state, batch)
            jax.block_until_ready(loss)

    and, where the capture holds device operations of the train step,
    its device time by named scope (`scope_ms.json` in `log_dir` and one
    log line a scope: docs/OBSERVABILITY.md "Device scope catalogue").
    """
    import jax

    steps_before = process_phase_timer().steps
    jax.profiler.start_trace(log_dir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()
        logger.info("Profiler trace written to %s", log_dir)
        try:
            _write_scope_summary(
                log_dir, process_phase_timer().steps - steps_before
            )
        except Exception:   # a summary must never end the job it sums
            logger.exception("no scope summary for %s", log_dir)


@contextlib.contextmanager
def annotate(name: str):
    """Name a region so it shows up in profiler timelines."""
    import jax

    with jax.profiler.TraceAnnotation(name):
        yield
