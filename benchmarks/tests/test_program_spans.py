"""The `program_spans` reader: its rules on hand-made spans, the window,
and the six metric files that use it."""

from collections import namedtuple

import pytest

from benchmarks import manifest
from benchmarks.readers import program_spans

CELL = "deepfm-criteo-kaggle.train-stream"
Span = namedtuple(
    "Span", "name start end thread task_id step parent attrs"
)
LOOP, PRODUCER = 1, 2


def span(name, start, end, thread=LOOP, task_id=0, step=None):
    return Span(name, start, end, thread, task_id, step, None, None)


def two_tasks():
    """Two 10 s tasks of three steps in a window [100, 120], and spans of
    the tasks before and after it."""
    spans = [span("data_wait", 95.0, 99.0, step=0),       # before
             span("read", 95.0, 99.5, PRODUCER, step=0)]
    for task, t0 in enumerate((100.0, 110.0)):
        spans += [
            span("get_task", t0, t0 + 0.1, task_id=task),
            span("read", t0 + 0.1, t0 + 0.4, PRODUCER, task, 0),
            # the head: 0.4 s in task 0, 0.6 s in task 1
            span("data_wait", t0 + 0.1, t0 + 0.5 + 0.2 * task,
                 task_id=task, step=0),
            span("data_wait", t0 + 3.0, t0 + 3.002, task_id=task, step=1),
            span("data_wait", t0 + 6.0, t0 + 6.004, task_id=task, step=2),
            # the get that finds the task's end belongs to no step
            span("data_wait", t0 + 8.0, t0 + 8.5, task_id=task),
            span("queue_full", t0 + 1.0, t0 + 6.0, PRODUCER, task, 2),
            span("compute", t0 + 0.7, t0 + 3.0, task_id=task, step=0),
            span("compute", t0 + 3.002, t0 + 6.0, task_id=task, step=1),
            span("compute", t0 + 6.004, t0 + 8.0, task_id=task, step=2),
            span("task_sync", t0 + 8.5, t0 + 9.5 + 0.2 * task,
                 task_id=task),
            span("report", t0 + 9.7, t0 + 9.9, task_id=task),
        ]
    spans += [span("data_wait", 120.5, 125.0, step=0),    # after
              span("task_sync", 128.0, 129.0)]
    return spans


CONTEXT = {"stamps": [(100.0, 0), (110.0, 300), (120.0, 300)]}


def params_of(metric: str) -> dict:
    cell = manifest.resolve_cell(manifest.load_manifest(), CELL)
    spec = manifest.load_layer_metric(cell, metric)
    assert spec["reader"] == "program_spans"
    assert manifest.import_by_name("readers", spec["reader"]) is program_spans
    return spec["params"]


@pytest.mark.parametrize("metric, want", [
    ("task_head_wait_ms", 500.0),            # (0.4 + 0.6) / 2
    ("steady_data_wait_ms", 3.0),            # (2 + 4 + 2 + 4) / 4 ms
    ("task_sync_ms", 1100.0),                # (1.0 + 1.2) / 2
    ("read_ms_per_task", 300.0),
    ("producer_blocked_share", 50.0),        # 2 x 5 s of 20 s
    # uncovered on the loop thread, a task: 0.5-0.7 (task 0 only),
    # 9.5-9.7 (task 0; 9.7-9.7 in task 1), 9.9-10.0: 0.5 + 0.1 of 20 s
    ("loop_unattributed_share", 3.0),
])
def test_each_metric_files_rule_on_hand_made_spans(monkeypatch, metric, want):
    monkeypatch.setattr(program_spans, "ring", two_tasks)
    assert program_spans.read(params_of(metric), CONTEXT) == pytest.approx(
        want
    )


def test_each_new_metric_is_in_the_manifest_for_its_cell():
    cell = manifest.resolve_cell(manifest.load_manifest(), CELL)
    entries = {m["name"]: m for m in cell.per_layer}
    for metric, layer in [
        ("task_head_wait_ms", "worker loop"),
        ("steady_data_wait_ms", "worker loop"),
        ("task_sync_ms", "worker loop"),
        ("loop_unattributed_share", "worker loop"),
        ("read_ms_per_task", "input pipeline"),
        ("producer_blocked_share", "input pipeline"),
    ]:
        entry, spec = entries[metric], manifest.load_layer_metric(
            cell, metric
        )
        assert entry["source"] == "program_span"
        assert entry["moves"] == spec["moves"] == "train_examples_per_s"
        assert entry["layer"] == spec["layer"] == layer
        assert entry["unit"] == spec["unit"]


def test_spans_outside_the_window_are_left_out(monkeypatch):
    monkeypatch.setattr(program_spans, "ring", two_tasks)
    head = params_of("task_head_wait_ms")
    # a window of the second task alone sees only its own head
    context = {"stamps": [(110.0, 0), (120.0, 300)]}
    assert program_spans.read(head, context) == pytest.approx(600.0)
    # and a window that holds no task end to task end holds nothing
    assert program_spans.read(head, {"stamps": [(100.0, 0)]}) is None
    assert program_spans.read(head, {}) is None
    far = {"stamps": [(200.0, 0), (210.0, 300)]}
    for metric in ("task_head_wait_ms", "producer_blocked_share",
                   "loop_unattributed_share"):
        assert program_spans.read(params_of(metric), far) is None


def test_a_span_that_never_occurred_reads_as_nothing(monkeypatch):
    def without(*names):
        return lambda: [s for s in two_tasks() if s.name not in names]

    monkeypatch.setattr(program_spans, "ring", without("task_sync", "read"))
    for metric in ("task_sync_ms", "loop_unattributed_share",
                   "read_ms_per_task"):
        assert program_spans.read(params_of(metric), CONTEXT) is None
    assert program_spans.read(
        params_of("task_head_wait_ms"), CONTEXT
    ) == pytest.approx(500.0)
    # a producer that never blocked was blocked 0% of the window: the
    # metric's file says so (`absent`), the reader has no such default
    monkeypatch.setattr(program_spans, "ring", without("queue_full"))
    blocked = params_of("producer_blocked_share")
    assert blocked["absent"] == 0.0
    assert program_spans.read(blocked, CONTEXT) == 0.0
    steady = dict(params_of("steady_data_wait_ms"), absent=0.0)
    monkeypatch.setattr(program_spans, "ring", without("data_wait"))
    assert program_spans.read(steady, CONTEXT) is None    # a mean of none


def test_a_program_without_the_ring_reads_as_nothing(monkeypatch):
    from elasticdl_tpu.worker import worker

    class OlderPhaseTimer:
        def snapshot(self):
            return {}

    monkeypatch.setattr(worker, "_phase_timer", OlderPhaseTimer())
    assert program_spans.ring() is None
    for metric in ("task_head_wait_ms", "producer_blocked_share"):
        assert program_spans.read(params_of(metric), CONTEXT) is None


def test_the_ring_is_the_programs_own(monkeypatch):
    from elasticdl_tpu.common.profiler import PhaseTimer
    from elasticdl_tpu.worker import worker

    timer = PhaseTimer()
    monkeypatch.setattr(worker, "_phase_timer", timer)
    timer.mark(task_id=4, step=0)
    with timer.phase("data_wait"):
        pass
    (only,) = program_spans.ring()
    assert (only.name, only.task_id, only.step) == ("data_wait", 4, 0)
    context = {"stamps": [(only.start - 1.0, 0), (only.end + 1.0, 8)]}
    value = program_spans.read(params_of("task_head_wait_ms"), context)
    assert value == pytest.approx(1e3 * (only.end - only.start))


def test_an_unknown_stat_is_an_error(monkeypatch):
    monkeypatch.setattr(program_spans, "ring", two_tasks)
    with pytest.raises(ValueError):
        program_spans.read({"span": "read", "stat": "nope"}, CONTEXT)
