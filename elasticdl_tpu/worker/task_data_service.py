"""Turns the stream of leased tasks into a stream of fixed-shape batches.

Parity: reference python/worker/task_data_service.py (SURVEY.md C8) — the
invariant preserved is *task completion ≡ data consumed*: a task is
reported back to the master only after every batch cut from its records has
been yielded to the train loop.  Unlike the reference (tf.data generator),
batches never span task boundaries; the final partial batch of a task is
padded by wrapping records so shapes stay static under jit (no recompiles),
with the true record count carried alongside for metrics.
"""

from __future__ import annotations

import contextlib
import itertools
import time
from typing import Callable, Iterator, Optional, Tuple

from elasticdl_tpu.common import resilience
from elasticdl_tpu.common.faults import InjectedFault
from elasticdl_tpu.common.log_utils import get_logger
from elasticdl_tpu.proto import elasticdl_pb2 as pb

logger = get_logger(__name__)


def _is_rpc_error(exc: Exception) -> bool:
    try:
        import grpc

        return isinstance(exc, grpc.RpcError)
    except ImportError:  # pragma: no cover
        return False


def _retryable(exc: BaseException) -> bool:
    """This service's historical contract: ANY RpcError retries (the
    master owns task semantics; every transport failure is transient to
    us), and injected faults behave like transport failures.  Anything
    else — application errors — propagates immediately."""
    return _is_rpc_error(exc) or isinstance(exc, InjectedFault)


def _phase(timer, name: str):
    """`timer.phase(name)`; where no timer is set, nothing to enter (and
    `as` gives None)."""
    return contextlib.nullcontext() if timer is None else timer.phase(name)


def prefetch_batches(iterator, depth: int = 2, device_stage=None,
                     device_depth: int = 1, phase_timer=None):
    """Run a host-side batch iterator (reader IO + feed parsing) in a
    background thread, keeping up to `depth` batches ready while the
    caller's thread drives the device — read/parse overlaps compute (the
    double-buffering every input pipeline wants; the benchmark's
    `task_head_wait_ms` and `steady_data_wait_ms` read what is left
    unoverlapped).  Pure host work only: the producer never touches device
    APIs, so it is safe on every backend including the virtual CPU mesh
    (scripts/check_host_device_boundary.py enforces this).

    `device_stage`, when given, adds a second buffering level for the
    host->device TRANSFER: batches pass through `device_stage(item)` on
    the CONSUMER thread (staging there honors the trainer's
    single-device-thread constraint: only ONE thread ever touches device
    APIs).  The staging rule: after staging a batch, stage ahead only
    what the queue ALREADY holds (a look that does not block, up to
    `device_depth` batches ahead); where it holds nothing, yield the
    oldest staged batch now.  The consumer never blocks on the queue
    while it holds a staged batch the caller has not had.  With the
    producer ahead that is the double buffer: batch k+1's device_put is
    issued before batch k is yielded and overlaps the caller's execution
    of batch k (JAX transfers are async — device_put returns as soon as
    the copy is enqueued).  With the producer behind nothing waits: the
    first batch of a task goes to the device as soon as it is read.

    Exceptions from the iterator re-raise at the consumer; a
    device_stage exception also re-raises at the consumer (in yield
    order, never ahead of earlier un-yielded batches).  Abandoning the
    generator (break / task failure) unblocks and stops the producer.

    `phase_timer` (common/profiler.PhaseTimer), when given, times both
    ends of the queue, each region with the step (index of the batch in
    the task) it belongs to.  The consumer's time on the queue's `get`
    is `data_wait`, one span a batch, with the queue's depth as the
    `get` found it.  It is a wait on the QUEUE: the loop takes batch k+1
    while the device still works on the steps enqueued before it, so the
    device is starved only where it has no queued step, which is the
    head of a task (`data_wait` of step 0); a later step's `data_wait`
    is the reader's pace under device work.  The producer's blocked
    `put` is `queue_full`, the opposite signal: the pipeline is ahead
    and the device paces the job.  The iterator's own regions (`read`,
    `pack`) run on the producer thread under the task the caller's
    thread is marked with."""
    import queue
    import threading

    q: "queue.Queue" = queue.Queue(maxsize=depth)
    sentinel = object()
    empty = object()
    stop = threading.Event()
    error = []
    task_id = None if phase_timer is None else phase_timer.marks()[0]

    def put(item) -> bool:
        """False when the consumer went away before there was room."""
        try:
            q.put_nowait(item)
            return True
        except queue.Full:
            pass
        with _phase(phase_timer, "queue_full"):
            while not stop.is_set():
                try:
                    q.put(item, timeout=0.5)
                    return True
                except queue.Full:
                    continue
        return False

    def produce():
        try:
            source = iter(iterator)
            step = 0
            while True:
                if phase_timer is not None:
                    phase_timer.mark(task_id=task_id, step=step)
                try:
                    item = next(source)
                except StopIteration:
                    break
                if not put(item):
                    return
                step += 1
        except BaseException as exc:  # re-raised at the consumer
            error.append(exc)
        finally:
            put(sentinel)

    thread = threading.Thread(target=produce, daemon=True)
    thread.start()

    step = 0          # index in the task of the next batch off the queue

    def take(block: bool = True):
        """The next item off the queue, `sentinel` at its end (a dead
        reader's exception raises there); with `block` false, `empty`
        where the queue holds nothing now (this thread is its only
        taker, so what it holds stays)."""
        nonlocal step
        if not block and q.empty():
            return empty
        if phase_timer is None:
            item = q.get()
        else:
            # h2d_stage of this batch follows on this thread
            phase_timer.mark(step=step)
            with phase_timer.phase("data_wait", depth=q.qsize()) as wait:
                item = q.get()
                if item is sentinel:
                    wait.step = None   # the task's end, not a step
        if item is not sentinel:
            step += 1
        elif error:
            raise error[0]
        return item

    try:
        if device_stage is None:
            while (item := take()) is not sentinel:
                yield item
            return
        from collections import deque

        staged: "deque" = deque()
        failure = None
        while True:
            try:
                # block only with nothing staged that the caller waits for
                item = take(block=not staged)
                if item is sentinel:
                    break
                if item is not empty:
                    staged.append(device_stage(item))
                    if len(staged) <= device_depth:
                        continue    # stage ahead what the queue holds
            except BaseException as exc:
                # reader died or the transfer failed: batches already
                # staged are good transfers — deliver them before
                # surfacing the failure
                failure = exc
                break
            yield staged.popleft()
        while staged:
            yield staged.popleft()
        if failure is not None:
            raise failure
    finally:
        stop.set()


class TaskDataService:
    # Step-phase attribution hook (common/profiler.PhaseTimer): feed /
    # feed_bulk parse time is the `pack` phase.  Class default so bare
    # instances (test scaffolding) run untimed; the worker runtime
    # assigns the process-wide timer.  The wrapped feeds usually run on
    # the prefetch PRODUCER thread — PhaseTimer is thread-safe.
    phase_timer = None

    def __init__(self, master_client, data_reader, worker_id: int,
                 wait_sleep_s: float = 0.5, master_grace_s: float = 30.0,
                 rpc_policy: Optional[resilience.RetryPolicy] = None):
        self._client = master_client
        self._reader = data_reader
        self._worker_id = worker_id
        self._wait_sleep_s = wait_sleep_s
        self.master_grace_s = master_grace_s
        base = (
            rpc_policy if rpc_policy is not None
            else resilience.default_policy()
        )
        # get_task gets the master-grace budget (exhaustion == the job is
        # over or the master is lost); reports get a short budget because
        # the lease reaper re-queues whatever a lost report covered.
        self._get_policy = base.with_overrides(
            max_elapsed_s=master_grace_s,
            initial_backoff_s=min(wait_sleep_s, 0.5),
            retryable=_retryable,
        )
        self._report_policy = base.with_overrides(
            max_elapsed_s=min(10.0, master_grace_s), retryable=_retryable
        )

    def get_task(
        self, task_type=None, should_stop=None
    ) -> Tuple[Optional[pb.Task], bool]:
        """Poll the master for a task.  Returns (task|None, job_finished);
        blocks through WAIT responses with backoff.  Transient RPC failures
        retry under the shared policy (backoff + jitter); a master
        unreachable past the `master_grace_s` budget means the job is over
        (master exits after completion) or lost — either way the worker
        must stop.

        `should_stop`: optional callable checked between WAIT polls; when
        it turns true, returns (None, False) so the caller regains control
        — without it a worker parked on WAIT (e.g. the last shard of an
        epoch leased to another worker) never notices a drain request
        until a task happens to arrive.

        The whole call, lease wait included, is the `get_task` phase."""
        with _phase(self.phase_timer, "get_task") as span:
            task, finished = self._poll_task(task_type, should_stop)
            if span is not None and task is not None:
                span.task_id = task.task_id
            return task, finished

    def _poll_task(self, task_type, should_stop):
        while True:
            req = pb.GetTaskRequest(worker_id=self._worker_id)
            if task_type is not None:
                req.task_type = task_type
                req.filter_by_type = True
            try:
                resp = self._get_policy.call(
                    lambda: self._client.get_task(req),
                    description="get_task",
                )
            except resilience.RetryBudgetExhausted:
                logger.error(
                    "Master unreachable for %.0fs; worker %d stopping",
                    self.master_grace_s, self._worker_id,
                )
                return None, True
            if resp.job_finished:
                return None, True
            task = resp.task
            if task.task_id < 0 or task.type == pb.WAIT:
                if should_stop is not None and should_stop():
                    return None, False
                time.sleep(self._wait_sleep_s)
                continue
            return task, False

    def report_task(self, task: pb.Task, err: str = "", records: int = 0,
                    transient: bool = False, model_version: int = -1,
                    telemetry: Optional[dict] = None):
        req = pb.ReportTaskResultRequest(
            task_id=task.task_id,
            err_message=err,
            worker_id=self._worker_id,
            transient=transient,
        )
        req.exec_counters["records"] = records
        if model_version >= 0:
            # Model step at completion: the master's task journal pairs a
            # done shard with this version, and on restart trusts it only
            # when a model checkpoint at >= this step exists (step-based
            # durability — no cross-host clock comparison).
            req.exec_counters["model_version"] = model_version
        # Worker telemetry rides the existing map field under a `__`
        # namespace (int64 values — callers pre-scale rates to milli
        # units); the master's servicer peels these into its snapshot
        # instead of treating them as execution counters.
        for key, value in (telemetry or {}).items():
            req.exec_counters[f"__{key}"] = int(value)
        try:
            self._report_policy.call(
                lambda: self._client.report_task_result(req),
                description="report_task_result",
            )
        except Exception as exc:
            if not (_is_rpc_error(exc)
                    or isinstance(exc, (InjectedFault,
                                        resilience.RetryBudgetExhausted))):
                raise
            # Lost report: the master's lease timeout / failure detector
            # re-queues the task (at-least-once contract).
            logger.warning(
                "report_task_result for task %d failed: %s",
                task.task_id, exc,
            )

    def _timed_pack(self, fn: Optional[Callable]) -> Optional[Callable]:
        """Wrap a feed/feed_bulk callable so its parse time lands in the
        `pack` phase.  Identity when no timer is configured."""
        timer = self.phase_timer
        if timer is None or fn is None:
            return fn

        def timed(*args, **kwargs):
            with timer.phase("pack"):
                return fn(*args, **kwargs)

        return timed

    def batches_for_task(
        self,
        task: pb.Task,
        batch_size: int,
        feed: Callable,
        feed_bulk: Optional[Callable] = None,
    ) -> Iterator[Tuple[dict, int]]:
        """Yield (batch, real_count) for one task.  `feed(records)` maps a
        list of raw records to a batch dict of arrays (zoo contract).  The
        final partial batch is wrap-padded to exactly `batch_size`
        (mesh.pad_to_multiple) so shapes stay static under jit.

        When both the reader exposes a bulk representation
        (`read_records_bulk`) and the zoo a vectorized parser
        (`feed_bulk(buffer, sizes)`), the records move as contiguous
        uint8 buffers, one batch a read — no per-record Python objects on
        the hot path, where a per-record loop would bound a wide batch's
        step rate from the host.  Each batch is read,
        packed and yielded before the next is read, so a task's first
        batch is ready after ONE batch's read and the buffer held at any
        moment is one batch's."""
        from elasticdl_tpu.parallel.mesh import pad_to_multiple

        feed = self._timed_pack(feed)
        feed_bulk = self._timed_pack(feed_bulk)
        if feed_bulk is not None:
            reader_bulk = getattr(self._reader, "read_records_bulk", None)
            if reader_bulk is not None:
                shard = task.shard
                used_bulk = False
                for start in range(shard.start, shard.end, batch_size):
                    sub = pb.Task(
                        task_id=task.task_id,
                        type=task.type,
                        shard=pb.Shard(
                            name=shard.name,
                            start=start,
                            end=min(start + batch_size, shard.end),
                        ),
                    )
                    with _phase(self.phase_timer, "read"):
                        bulk = reader_bulk(sub)
                    if bulk is None:
                        if used_bulk:
                            # a reader that served earlier batches must
                            # not silently truncate the task mid-stream
                            raise IOError(
                                f"bulk read failed mid-task at record "
                                f"{start - shard.start} of {task.task_id}"
                            )
                        # no bulk representation (e.g. unindexed
                        # source): fall to the streaming path
                        break
                    used_bulk = True
                    buffer, sizes = bulk
                    if len(sizes):   # none: the shard outruns its file
                        # the task's tail (if any) is wrap-padded to the
                        # static batch shape
                        yield pad_to_multiple(
                            feed_bulk(buffer, sizes), batch_size
                        )
                if used_bulk or shard.end <= shard.start:
                    return
        records = iter(self._reader.read_records(task))
        while True:
            # streaming path: one batch's record loop is one `read`
            with _phase(self.phase_timer, "read"):
                buf = list(itertools.islice(records, batch_size))
            if len(buf) == batch_size:
                yield feed(buf), batch_size
                continue
            if buf:
                yield pad_to_multiple(feed(buf), batch_size)
            return

    def local_batches_for_task(
        self,
        task: pb.Task,
        batch_size: int,
        feed: Callable,
        feed_bulk: Optional[Callable],
        local_start: int,
        local_stop: int,
    ) -> Iterator[Tuple[dict, int, bool]]:
        """SPMD slice-local variant: yield (batch, global_real, is_local).

        For each FULL global batch of `batch_size` records, this rank
        reads ONLY rows [local_start, local_stop) of the batch (its
        addressable slice of the data axis) — host IO drops from
        O(world_size * shard) to O(shard) in aggregate (SURVEY §3.3:
        per-worker disjoint reads; VERDICT r3 weak #4).  `is_local=True`
        batches hold just the local rows (pair with
        mesh.make_global_batch_from_local).  The task's final partial
        batch — if any — is read in full and wrap-padded identically on
        every rank (`is_local=False`), keeping padding bitwise-consistent
        without cross-rank coordination.
        """
        shard = task.shard
        total = shard.end - shard.start
        full = total // batch_size
        for i in range(full):
            base = shard.start + i * batch_size
            sub = pb.Task(
                task_id=task.task_id,
                type=task.type,
                shard=pb.Shard(
                    name=shard.name,
                    start=base + local_start,
                    end=base + local_stop,
                ),
            )
            for batch, _ in self.batches_for_task(
                sub, local_stop - local_start, feed, feed_bulk=feed_bulk
            ):
                yield batch, batch_size, True
        remaining = total - full * batch_size
        if remaining:
            tail = pb.Task(
                task_id=task.task_id,
                type=task.type,
                shard=pb.Shard(
                    name=shard.name,
                    start=shard.start + full * batch_size,
                    end=shard.end,
                ),
            )
            for batch, real in self.batches_for_task(
                tail, batch_size, feed, feed_bulk=feed_bulk
            ):
                yield batch, real, False
