"""Step-phase profiling, Chrome-trace export, and straggler detection.

Unit tests cover the PhaseTimer (attribution, flush windows, telemetry
shapes), labeled registry histograms, event-log rotation, the task
manager's straggler math, and the trace exporter's summary arithmetic on
a synthetic log.  The e2e test runs an in-process master + worker (the
Local-mode pattern from test_telemetry.py) with an event log configured
and asserts `elasticdl trace --chrome` emits valid Chrome trace JSON in
which every completed task is a duration slice on its worker's track —
and that /metrics exposes `worker_step_phase_seconds` for every
phase after a real run.
"""

import json
import time

import pytest

from elasticdl_tpu.common import events
from elasticdl_tpu.common import metrics as metrics_lib
from elasticdl_tpu.common.profiler import STEP_PHASES, PhaseTimer


# ---------------------------------------------------------------------------
# PhaseTimer
# ---------------------------------------------------------------------------


def test_phase_timer_attribution_and_shapes():
    timer = PhaseTimer(flush_every=1000)
    with timer.phase("compute"):
        pass
    timer.add("data_wait", 0.25)
    timer.add("data_wait", 0.75)
    timer.add("not_a_phase", 5.0)   # unknown: ignored, never raises
    timer.add("pack", -1.0)         # clamped to 0
    timer.step_done()

    snap = timer.snapshot()
    assert set(snap) == set(STEP_PHASES)
    assert snap["data_wait"]["total_s"] == pytest.approx(1.0)
    assert snap["data_wait"]["mean_s"] == pytest.approx(1.0)  # 1 step
    assert 0.0 < snap["data_wait"]["share"] <= 1.0
    assert timer.steps == 1

    milli = timer.totals_milli()
    assert milli["data_wait"] == 1000
    assert all(isinstance(v, int) for v in milli.values())


def test_phase_timer_flush_windows_emit_span_events(tmp_path):
    log = str(tmp_path / "events.jsonl")
    events.configure(log, role="worker", worker_id=3)
    try:
        timer = PhaseTimer(flush_every=2)
        for _ in range(3):
            timer.add("compute", 0.5)
            timer.step_done()
        timer.flush()          # partial window (1 step) must not be lost
        timer.flush()          # empty window: no event
    finally:
        events.configure(None)
    recorded = [
        e for e in events.read_events(log)
        if e["event"] == events.STEP_PHASES
    ]
    assert [e["steps"] for e in recorded] == [2, 1]
    assert recorded[0]["phases"]["compute"] == pytest.approx(1.0)
    assert recorded[1]["phases"]["compute"] == pytest.approx(0.5)
    assert all(e["worker_id"] == 3 for e in recorded)


def test_phase_timer_feeds_labeled_histogram():
    registry = metrics_lib.MetricsRegistry()
    hist = registry.histogram(
        "worker_step_phase_seconds", "phase time", labelnames=("phase",)
    )
    timer = PhaseTimer(histogram=hist)
    timer.add("compute", 0.01)
    timer.add("report", 0.02)
    assert hist.labels(phase="compute").count == 1
    assert hist.labels(phase="report").count == 1
    text = metrics_lib.render_text([registry])
    assert 'worker_step_phase_seconds_count{phase="compute"}' in text
    snap = registry.snapshot()
    assert snap['worker_step_phase_seconds_count{phase="compute"}'] == 1.0


def test_worker_scaffolding_without_init_has_no_phase_timer():
    # tests build Worker/Trainer/TaskDataService via __new__ (no
    # __init__): phase hooks must be class-level defaults, not
    # instance state.
    from elasticdl_tpu.worker.task_data_service import TaskDataService
    from elasticdl_tpu.worker.trainer import Trainer

    assert Trainer.__new__(Trainer).phase_timer is None
    assert TaskDataService.__new__(TaskDataService).phase_timer is None


# ---------------------------------------------------------------------------
# Event-log rotation
# ---------------------------------------------------------------------------


def test_event_log_rotates_and_reads_in_order(tmp_path):
    log = str(tmp_path / "events.jsonl")
    events.configure(log, role="master", max_bytes=400)
    try:
        for step in range(20):
            events.emit(events.CHECKPOINT_SAVED, step=step)
    finally:
        events.configure(None)
    import os

    assert os.path.exists(events.rotated_path(log))
    recorded = events.read_events(log)
    steps = [e["step"] for e in recorded]
    # one rolled generation: the newest events form a contiguous,
    # in-order tail ending at the last emit (older generations age out
    # — the cap exists precisely so soaks can't grow the log unboundedly)
    assert steps == list(range(steps[0], 20))
    assert len(steps) >= 5  # at least one generation retained
    assert os.path.getsize(log) <= 400 + 200  # capped, not unbounded


# ---------------------------------------------------------------------------
# Straggler detection (task manager)
# ---------------------------------------------------------------------------


def _run_fleet(tm, rounds, durations_by_worker):
    """Lease + report `rounds` training tasks per worker, back-dating
    each lease so the master observes the given duration."""
    from elasticdl_tpu.master.task_manager import _DoingEntry

    for _ in range(rounds):
        for wid, duration in durations_by_worker.items():
            task = tm.get(wid)
            assert task is not None
            tm._doing[task.task_id] = _DoingEntry(
                worker_id=wid, task=task,
                lease_start=time.time() - duration,
            )
            tm.report(task.task_id, success=True, worker_id=wid,
                      records=1)


def _make_tm(n_shards=64, **kwargs):
    from elasticdl_tpu.master.task_manager import TaskManager
    from elasticdl_tpu.proto import elasticdl_pb2 as pb

    shards = [
        pb.Shard(name="d", start=i, end=i + 1) for i in range(n_shards)
    ]
    return TaskManager(training_shards=shards, num_epochs=1, **kwargs)


def test_straggler_flagged_and_cleared(tmp_path):
    log = str(tmp_path / "events.jsonl")
    events.configure(log, role="master")
    try:
        tm = _make_tm(
            straggler_multiple=2.0, straggler_min_tasks=3
        )
        _run_fleet(tm, 2, {0: 0.01, 1: 0.01, 2: 0.5})
        # below min_tasks: nobody flagged yet
        assert tm.snapshot()["stragglers"] == []
        _run_fleet(tm, 2, {0: 0.01, 1: 0.01, 2: 0.5})
        assert tm.snapshot()["stragglers"] == [2]
        stats = tm.straggler_snapshot()
        assert stats[2]["straggler"] is True
        assert stats[0]["straggler"] is False
        assert stats[2]["mean_task_s"] > stats[0]["mean_task_s"]
        assert (
            tm.counters.registry.value("master_straggler_workers_count")
            == 1.0
        )
        # the flag transition emitted exactly one span event
        flags = [
            e for e in events.read_events(log)
            if e["event"] == events.STRAGGLER_DETECTED
        ]
        assert len(flags) == 1
        assert flags[0]["worker_id"] == 2
        assert flags[0]["ratio"] >= 2.0
        # a recovered (dead) worker stops skewing the fleet
        tm.recover_tasks(2)
        assert tm.snapshot()["stragglers"] == []
        assert (
            tm.counters.registry.value("master_straggler_workers_count")
            == 0.0
        )
    finally:
        events.configure(None)


def test_straggler_detection_disabled_and_single_worker():
    tm = _make_tm(straggler_multiple=0.0, straggler_min_tasks=1)
    _run_fleet(tm, 4, {0: 0.01, 1: 1.0})
    assert tm.snapshot()["stragglers"] == []  # multiple=0 disables

    tm = _make_tm(straggler_multiple=2.0, straggler_min_tasks=1)
    _run_fleet(tm, 4, {0: 1.0})
    assert tm.snapshot()["stragglers"] == []  # no peer, no baseline


# ---------------------------------------------------------------------------
# Trace exporter on a synthetic log
# ---------------------------------------------------------------------------


def _synthetic_log(tmp_path):
    """Two completed tasks (worker 0 fast, worker 1 slow), one in-flight
    task, phase flushes, a straggler flag, and a recovery."""
    log = str(tmp_path / "events.jsonl")
    t0 = 1000.0
    lines = []

    def ev(ts, event, role, **fields):
        rec = {"ts": ts, "role": role, "pid": 1, "event": event}
        rec.update(fields)
        lines.append(json.dumps(rec))

    for task_id, wid, dur in ((1, 0, 1.0), (2, 1, 4.0)):
        ev(t0, events.TASK_DISPATCHED, "master", task_id=task_id,
           worker_id=wid)
        ev(t0 + 0.1, events.TASK_CLAIMED, "worker", task_id=task_id,
           worker_id=wid)
        ev(t0 + 0.1 + dur, events.TASK_TRAINED, "worker",
           task_id=task_id, worker_id=wid, records=64)
        ev(t0 + 0.2 + dur, events.TASK_REPORTED, "master",
           task_id=task_id, worker_id=wid, success=True)
    ev(t0 + 1.0, events.TASK_DISPATCHED, "master", task_id=3,
       worker_id=0)  # in flight: no slice, no duration
    ev(t0 + 2.0, events.STEP_PHASES, "worker", worker_id=0,
       phases={"compute": 0.6, "data_wait": 0.2}, steps=10)
    ev(t0 + 3.0, events.STEP_PHASES, "worker", worker_id=1,
       phases={"compute": 0.9, "data_wait": 0.3}, steps=10)
    ev(t0 + 4.0, events.STRAGGLER_DETECTED, "master", worker_id=1,
       mean_task_s=4.0, median_task_s=1.0, ratio=4.0)
    ev(t0 + 6.0, events.RECOVERY_DONE, "master", duration_s=1.5)
    with open(log, "w") as fh:
        fh.write("\n".join(lines) + "\n")
    return log


def test_chrome_trace_from_synthetic_log(tmp_path):
    from elasticdl_tpu.client.trace import build_chrome_trace, task_durations

    evts = events.read_events(_synthetic_log(tmp_path))
    durations = task_durations(evts)
    assert [(t, w) for t, w, _ in durations] == [(1, 0), (2, 1)]
    assert durations[0][2] == pytest.approx(1.2)
    assert durations[1][2] == pytest.approx(4.2)

    doc = build_chrome_trace(evts)
    trace_events = doc["traceEvents"]
    # every completed task is a complete ("X") slice on its worker track
    slices = {
        e["name"]: e for e in trace_events
        if e.get("ph") == "X" and e.get("cat") == "task"
        and e["name"].startswith("task ")
    }
    assert set(slices) == {"task 1", "task 2"}
    assert slices["task 1"]["tid"] == 0
    assert slices["task 2"]["tid"] == 1
    assert slices["task 2"]["dur"] == pytest.approx(4.2e6)
    # timestamps are normalized to the log start
    assert slices["task 1"]["ts"] == pytest.approx(0.0)
    # nested lifecycle segments exist for each completed task
    segs = [
        e["name"] for e in trace_events
        if e.get("ph") == "X" and e["name"] in
        ("claim_wait", "train", "report_wait")
    ]
    assert segs.count("train") == 2
    # instants + the recovery outage slice survive the conversion
    names = {e["name"] for e in trace_events}
    assert {"step_phases", "straggler_detected",
            "elastic recovery"} <= names
    recovery = next(
        e for e in trace_events if e["name"] == "elastic recovery"
    )
    assert recovery["dur"] == pytest.approx(1.5e6)
    # the document is valid JSON all the way down
    json.loads(json.dumps(doc))


def test_trace_summary_math(tmp_path):
    from elasticdl_tpu.client.trace import summarize

    evts = events.read_events(_synthetic_log(tmp_path))
    text = summarize(evts, slowest_k=1)
    assert "tasks completed: 2" in text
    # slowest task is task 2 on the slow worker
    assert "task 2 (worker 1): 4.200s" in text
    # aggregate phase breakdown: compute dominates (1.5s of 2.0s = 75%)
    assert "step phases (20 steps):" in text
    assert "75.0%" in text
    # straggler flag is surfaced with its ratio
    assert "worker 1: 4.000s/task vs fleet median 1.000s (4.0x)" in text


def test_trace_cli_requires_events(tmp_path):
    from elasticdl_tpu.client.main import main

    missing = str(tmp_path / "nope.jsonl")
    assert main(["trace", missing]) == 1


# ---------------------------------------------------------------------------
# e2e: in-process run -> trace export + phase metrics
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def mnist_data(tmp_path_factory):
    from model_zoo.mnist.data import write_dataset

    root = tmp_path_factory.mktemp("mnist_profiling")
    return write_dataset(str(root), n_train=128, n_val=64)


@pytest.fixture(scope="module")
def spec():
    from elasticdl_tpu.common.model_handler import get_model_spec

    return get_model_spec(
        "model_zoo", "mnist.mnist_functional_api.custom_model"
    )


def test_trace_e2e_cluster_run(mnist_data, spec, tmp_path):
    from elasticdl_tpu.client.main import main
    from elasticdl_tpu.data.reader import TFRecordDataReader
    from elasticdl_tpu.master.servicer import MasterServicer
    from elasticdl_tpu.master.task_manager import (
        TaskManager,
        create_shards_from_ranges,
    )
    from elasticdl_tpu.proto.service import InProcessMasterClient
    from elasticdl_tpu.worker.worker import Worker

    train_dir, _val_dir = mnist_data
    log = str(tmp_path / "events.jsonl")
    events.configure(log, role="master")
    try:
        reader = TFRecordDataReader(train_dir)
        tm = TaskManager(
            training_shards=create_shards_from_ranges(
                reader.create_shards(), records_per_task=64
            ),
            num_epochs=1,
        )
        servicer = MasterServicer(tm)
        worker = Worker(
            worker_id=0,
            master_client=InProcessMasterClient(servicer),
            data_reader=reader,
            spec=spec,
            minibatch_size=32,
        )
        assert worker.run()
        finished = tm.counters.finished
        assert finished >= 2
    finally:
        events.configure(None)

    # acceptance: /metrics exposes worker_step_phase_seconds for every
    # phase after a real run
    text = metrics_lib.render_text([metrics_lib.default_registry()])
    for phase in STEP_PHASES:
        assert (
            f'worker_step_phase_seconds_count{{phase="{phase}"}}' in text
        ), phase

    # acceptance: the trace CLI writes valid Chrome JSON with every
    # completed task as a duration slice on its worker's track
    out = str(tmp_path / "trace.json")
    assert main(["trace", log, "--chrome", out]) == 0
    with open(out) as fh:
        doc = json.load(fh)
    recorded = events.read_events(log)
    reported = {
        e["task_id"] for e in recorded
        if e["event"] == events.TASK_REPORTED and e.get("success")
    }
    assert len(reported) == finished
    task_slices = {
        e["name"]: e for e in doc["traceEvents"]
        if e.get("ph") == "X" and e.get("cat") == "task"
        and e["name"].startswith("task ")
    }
    for task_id in reported:
        slice_ = task_slices[f"task {task_id}"]
        assert slice_["dur"] > 0
        assert slice_["tid"] == 0  # the lone worker's track
    # the run's phase flushes made it into the trace as instants
    assert any(
        e["name"] == "step_phases" for e in doc["traceEvents"]
    )

    # telemetry piggyback carried cumulative per-phase milliseconds
    telemetry = servicer.worker_telemetry()
    assert any(
        key.startswith("phase_") and key.endswith("_ms")
        for key in telemetry[0]
    )
