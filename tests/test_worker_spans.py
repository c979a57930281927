"""The worker loop's and the prefetch producer's spans (common/profiler).

A timed region is a `Span` in the PhaseTimer's bounded ring: name, start
and end on `time.perf_counter()`, thread, task, step, enclosing span.  A
Local CPU job must tile the loop thread's time with them; under a profiler
session the same regions lie on the trace's host plane as `edl:<name>`;
the step rate comes from the per-task synchronised stamp.
"""

import glob
import threading
import time

import pytest

from elasticdl_tpu.common.profiler import (
    COMPILE_PHASES,
    LOOP_PHASES,
    PRODUCER_PHASES,
    STEP_PHASES,
    PhaseTimer,
    SyncedStepRate,
)

STEPS_PER_TASK = 8
MINIBATCH = 16
TASKS = 4


# ---------------------------------------------------------------------------
# the recorder
# ---------------------------------------------------------------------------


def test_span_record_fields_marks_and_nesting():
    timer = PhaseTimer()
    timer.mark(task_id=7, step=3)
    before = time.perf_counter()
    with timer.phase("compute"):
        with timer.phase("pack"):
            time.sleep(0.002)
    with timer.phase("data_wait", depth=2) as wait:
        wait.step = None
    after = time.perf_counter()
    pack, compute, wait = timer.spans()      # in the order they ENDED
    assert (pack.name, pack.parent) == ("pack", "compute")
    assert (compute.name, compute.parent) == ("compute", None)
    assert compute.start <= pack.start <= pack.end <= compute.end
    assert before <= compute.start and wait.end <= after
    assert pack.end - pack.start >= 0.002
    for span in (pack, compute):
        assert (span.task_id, span.step) == (7, 3)
        assert span.thread == threading.get_native_id()
    assert (wait.task_id, wait.step, wait.attrs) == (7, None, {"depth": 2})
    # the totals are what they were: the sum of the regions
    snap = timer.snapshot()
    assert snap["pack"]["total_s"] == pytest.approx(pack.end - pack.start)


def test_marks_are_per_thread_and_add_keeps_its_place_on_the_clock():
    timer = PhaseTimer()
    timer.mark(task_id=1, step=0)
    started = time.perf_counter() - 5.0

    def elsewhere():
        assert timer.marks() == (None, None)
        timer.mark(task_id=2, step=4)
        timer.add("cold_gather", 0.25, start=started)

    thread = threading.Thread(target=elsewhere)
    thread.start()
    thread.join()
    timer.add("report", 0.5)
    gather, report = timer.spans()
    assert (gather.start, gather.end) == (started, started + 0.25)
    assert (gather.task_id, gather.step) == (2, 4)
    assert gather.thread != report.thread
    assert (report.task_id, report.step) == (1, 0)
    assert report.end - report.start == pytest.approx(0.5)
    assert report.end == pytest.approx(time.perf_counter(), abs=0.05)


def test_ring_is_bounded_and_unknown_phases_are_dropped():
    timer = PhaseTimer(ring=16)
    for step in range(50):
        timer.mark(step=step)
        with timer.phase("compute"):
            pass
        with timer.phase("no_such_phase"):
            pass
    spans = timer.spans()
    assert len(spans) == 16
    assert [s.step for s in spans] == list(range(34, 50))
    assert {s.name for s in spans} == {"compute"}
    # falling out of the ring takes nothing from the totals
    assert timer.snapshot()["compute"]["total_s"] > 0.0


def test_phase_vocabulary_is_the_loop_the_producer_and_the_store():
    assert set(STEP_PHASES) == (
        set(LOOP_PHASES) | set(PRODUCER_PHASES) | {"cold_gather"}
    )
    assert set(LOOP_PHASES) == {
        "get_task", "data_wait", "h2d_stage", "compute", "task_sync",
        "report",
    }
    assert set(PRODUCER_PHASES) == {"read", "pack", "queue_full"}


def test_one_phase_timer_a_process():
    from elasticdl_tpu.common import profiler
    from elasticdl_tpu.worker import spmd, worker

    assert worker._phase_timer is profiler.process_phase_timer()
    assert spmd._phase_timer is worker._phase_timer
    assert not hasattr(profiler, "StepTimer")


# ---------------------------------------------------------------------------
# (d) the synchronised rate
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("ends, steps, want", [
    ((100.0,), (8,), 0.0),                    # one stamp: no interval yet
    ((100.0, 102.0), (8, 8), 4.0),            # 8 steps in 2 s
    ((100.0, 102.0, 102.5), (8, 8, 4), 8.0),  # the newest interval only
    ((100.0, 102.0, 103.0), (8, 8, 0), 4.0),  # no steps: rate stands
])
def test_synced_step_rate_is_steps_over_seconds(ends, steps, want):
    rate = SyncedStepRate()
    assert rate.steps_per_sec == 0.0
    for end, n in zip(ends, steps):
        rate.task_synced(end, n)
    assert rate.steps_per_sec == pytest.approx(want)


# ---------------------------------------------------------------------------
# the queue's two ends
# ---------------------------------------------------------------------------


def test_prefetch_spans_a_slow_consumer_blocks_the_producer():
    from elasticdl_tpu.worker.task_data_service import prefetch_batches

    timer = PhaseTimer()
    timer.mark(task_id=11, step=None)
    got = []
    for item in prefetch_batches(iter(range(6)), depth=2, phase_timer=timer):
        time.sleep(0.01)          # the "device" paces: the queue fills
        got.append(item)
    assert got == list(range(6))
    spans = timer.spans()
    loop = threading.get_native_id()
    waits = [s for s in spans if s.name == "data_wait"]
    # one a batch, and the get that found the task's end
    assert [s.step for s in waits] == [0, 1, 2, 3, 4, 5, None]
    assert all(s.thread == loop and s.task_id == 11 for s in waits)
    assert all(0 <= s.attrs["depth"] <= 2 for s in waits)
    assert any(s.attrs["depth"] == 2 for s in waits[1:])
    full = [s for s in spans if s.name == "queue_full"]
    assert full and all(s.thread != loop for s in full)
    assert all(s.task_id == 11 for s in full)
    assert [s.step for s in full] == sorted(s.step for s in full)
    assert sum(s.end - s.start for s in full) >= 0.02


def test_prefetch_spans_a_slow_producer_starves_the_consumer():
    from elasticdl_tpu.worker.task_data_service import prefetch_batches

    def slow():
        for i in range(3):
            time.sleep(0.01)
            yield i

    timer = PhaseTimer()
    assert list(prefetch_batches(slow(), phase_timer=timer)) == [0, 1, 2]
    spans = timer.spans()
    assert not [s for s in spans if s.name == "queue_full"]
    waits = [s for s in spans if s.name == "data_wait"]
    assert all(s.attrs["depth"] == 0 for s in waits[:3])
    assert sum(s.end - s.start for s in waits) >= 0.02


# ---------------------------------------------------------------------------
# (a) a Local CPU job of a few tasks, (b) the same regions in the trace
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def mnist_data(tmp_path_factory):
    from model_zoo.mnist.data import write_dataset

    root = tmp_path_factory.mktemp("mnist_spans")
    return write_dataset(
        str(root), n_train=TASKS * STEPS_PER_TASK * MINIBATCH, n_val=16
    )


class Stamps:
    """The zoo callback the benchmark's driver uses, cut to its stamps."""

    def __init__(self):
        self.ends = []

    def on_task_end(self, task, records):
        self.ends.append(time.perf_counter())


@pytest.fixture(scope="module")
def job(mnist_data, tmp_path_factory):
    """Train TASKS tasks in this process, the last ones under a profiler
    session; returns what the ring and the trace hold."""
    import jax

    from elasticdl_tpu.common.model_handler import get_model_spec
    from elasticdl_tpu.data.reader import TFRecordDataReader
    from elasticdl_tpu.master.servicer import MasterServicer
    from elasticdl_tpu.master.task_manager import (
        TaskManager,
        create_shards_from_ranges,
    )
    from elasticdl_tpu.proto.service import InProcessMasterClient
    from elasticdl_tpu.worker.worker import Worker, _phase_timer

    spec = get_model_spec(
        "model_zoo", "mnist.mnist_functional_api.custom_model"
    )
    stamps = Stamps()
    spec.callbacks = list(spec.callbacks or []) + [stamps]
    reader = TFRecordDataReader(mnist_data[0])
    tm = TaskManager(
        training_shards=create_shards_from_ranges(
            reader.create_shards(),
            records_per_task=STEPS_PER_TASK * MINIBATCH,
        ),
        num_epochs=1,
    )
    worker = Worker(
        worker_id=0,
        master_client=InProcessMasterClient(MasterServicer(tm)),
        data_reader=reader,
        spec=spec,
        minibatch_size=MINIBATCH,
    )
    # Order the head of every task by events, not by the clock: the
    # task's SECOND read stays open until its step 0 is inside `compute`.
    # A loop that held the first batch back for the second's read would
    # never get there.
    dispatched = {}      # task_id -> step 0's program is being enqueued
    reads = {}           # task_id -> bulk reads begun
    held = []            # task_ids whose second read waited, and went on
    read_bulk = reader.read_records_bulk
    train_step = worker._owner.trainer.train_step

    def ordered_read(task):
        reads[task.task_id] = reads.get(task.task_id, 0) + 1
        if reads[task.task_id] == 2:
            event = dispatched.setdefault(task.task_id, threading.Event())
            assert event.wait(60.0), "step 0 waits for the second read"
            held.append(task.task_id)
        return read_bulk(task)

    def marked_train_step(*args):
        task_id, step = _phase_timer.marks()
        if step == 0:
            dispatched.setdefault(task_id, threading.Event()).set()
        return train_step(*args)

    reader.read_records_bulk = ordered_read
    worker._owner.trainer.train_step = marked_train_step
    trace_dir = str(tmp_path_factory.mktemp("spans_trace"))
    already = len(_phase_timer.spans())
    t0 = time.perf_counter()
    jax.profiler.start_trace(trace_dir)
    try:
        assert worker.run()
    finally:
        jax.profiler.stop_trace()
    assert tm.counters.finished == TASKS
    spans = [s for s in _phase_timer.spans()[already:] if s.start >= t0]
    return {
        "spans": spans,
        "ends": stamps.ends,
        "loop": threading.get_native_id(),
        "trace_dir": trace_dir,
        "rate": worker.step_rate.steps_per_sec,
        "held": held,
    }


def test_every_train_step_has_its_wait_and_its_dispatch(job):
    by = {}
    for span in job["spans"]:
        if span.name in ("data_wait", "compute") and span.step is not None:
            by.setdefault(span.name, []).append(span)
    for name in ("data_wait", "compute"):
        spans = by[name]
        assert all(s.thread == job["loop"] for s in spans)
        tasks = []
        for s in spans:
            if s.task_id not in tasks:
                tasks.append(s.task_id)
        assert len(tasks) == TASKS
        # task by task in the order leased, and in each the steps count up
        assert [(s.task_id, s.step) for s in spans] == [
            (task_id, step)
            for task_id in tasks for step in range(STEPS_PER_TASK)
        ]


def test_a_task_reads_a_batch_a_step_and_feeds_the_first_at_once(job):
    """One `read` span a step, marked with it; and step 0 is enqueued
    (`compute` begins) before the task's second read ends: the first
    batch is not held back for the next one's read."""
    assert len(job["held"]) == TASKS == len(set(job["held"]))
    for task_id in job["held"]:
        mine = [s for s in job["spans"] if s.task_id == task_id]
        reads = sorted(
            (s for s in mine if s.name == "read"), key=lambda s: s.start
        )
        assert [s.step for s in reads] == list(range(STEPS_PER_TASK))
        assert all(s.thread != job["loop"] for s in reads)
        # each batch is packed before the next is read
        packs = sorted(
            (s for s in mine if s.name == "pack"), key=lambda s: s.start
        )
        assert [s.step for s in packs] == list(range(STEPS_PER_TASK))
        for read, pack, after in zip(reads, packs, reads[1:] + [None]):
            assert read.end <= pack.start
            assert after is None or pack.end <= after.start
        (first,) = (
            s for s in mine if s.name == "compute" and s.step == 0
        )
        assert first.start < reads[1].end


def test_loop_spans_and_producer_spans_come_from_their_threads(job):
    names = {}
    for span in job["spans"]:
        names.setdefault(span.name, set()).add(span.thread)
    for name in ("get_task", "data_wait", "h2d_stage", "compute",
                 "task_sync", "report"):
        assert names[name] == {job["loop"]}, name
    for name in ("read", "pack"):
        assert names[name] and job["loop"] not in names[name], name
    # a producer that was ahead blocked on ITS thread, never on the loop's
    assert job["loop"] not in names.get("queue_full", set())
    syncs = [s for s in job["spans"] if s.name == "task_sync"]
    assert len(syncs) == TASKS and all(s.step is None for s in syncs)
    assert len({s.task_id for s in syncs}) == TASKS
    # every producer span belongs to a task and a step of it
    for span in job["spans"]:
        if span.name in ("read", "pack", "queue_full"):
            assert span.task_id is not None
            assert 0 <= span.step <= STEPS_PER_TASK


def test_the_loop_threads_spans_tile_the_time_between_task_ends(job):
    lo, hi = job["ends"][0], job["ends"][-1]
    assert len(job["ends"]) == TASKS and hi > lo
    covered, edge = 0.0, lo
    for span in sorted(
        (s for s in job["spans"] if s.thread == job["loop"]),
        key=lambda s: s.start,
    ):
        start, end = max(span.start, edge), min(span.end, hi)
        if end > start:
            covered += end - start
            edge = end
    assert covered / (hi - lo) >= 0.95, covered / (hi - lo)


def test_the_synchronised_rate_reads_the_task_sync_ends(job):
    syncs = [s for s in job["spans"] if s.name == "task_sync"]
    want = STEPS_PER_TASK / (syncs[-1].end - syncs[-2].end)
    assert job["rate"] == pytest.approx(want)


def test_the_same_regions_lie_on_the_traces_host_plane(job):
    from jax.profiler import ProfileData

    (path,) = glob.glob(
        job["trace_dir"] + "/plugins/profile/*/*.xplane.pb"
    )
    planes = {p.name: p for p in ProfileData.from_file(path).planes}
    traced = []
    for name, plane in planes.items():
        for line in plane.lines:
            for event in line.events:
                if event.name.startswith("edl:"):
                    assert name == "/host:CPU"
                    traced.append(event)
    assert {e.name for e in traced} >= {
        "edl:" + n for n in ("get_task", "data_wait", "read", "pack",
                             "h2d_stage", "compute", "task_sync", "report")
    }
    # one clock: each thread's events in the ring's order, each as long
    # as the ring says within 1 ms (the annotation encloses the stamps)
    # (a compile's stages are laid back from jax's events by `add()`:
    # they were never open, so they have no annotation)
    ring = sorted(
        (s for s in job["spans"] if s.name not in COMPILE_PHASES),
        key=lambda s: s.start,
    )
    traced.sort(key=lambda e: e.start_ns)
    assert [e.name for e in traced] == ["edl:" + s.name for s in ring]
    offset = traced[0].start_ns * 1e-9 - ring[0].start
    for event, span in zip(traced, ring):
        assert event.duration_ns * 1e-9 == pytest.approx(
            span.end - span.start, abs=1e-3
        )
        assert event.start_ns * 1e-9 - span.start == pytest.approx(
            offset, abs=1e-3
        )
