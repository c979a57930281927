"""The `startup_spans` reader: every rule of its metric files on hand-made
spans, the cut at the window's opening, a program without the record, and
the nine files against the manifest."""

from collections import namedtuple

import pytest

from benchmarks import manifest
from benchmarks.readers import program_spans, startup_spans

CELL = "deepfm-criteo-kaggle.train-stream"
Span = namedtuple(
    "Span", "name start end thread task_id step parent attrs"
)
MAIN, LOOP, PRODUCER = 1, 2, 3
STEP, INIT, NONE = "worker_train_step", "worker_init_state", "(unregistered)"
NINE = {
    "setup_boot_s": 10.0,
    # job_setup 4 + worker_setup 0.5 + restore 1
    "setup_job_s": 5.5,
    "setup_init_state_s": 10.0,
    # the step compiled twice (a plan the compiler refused): 12 + 2, 4 + 1
    "setup_step_trace_s": 19.0,
    "setup_step_xla_s": 12.0,
    # the loop thread's 85.5 s less init_state 10, restore 1, the step's
    # compiles 31, and an eager compile's 0.4 + 0.5 outside init_state
    "setup_warmup_run_s": 42.6,
    # hits: init, the step's first; misses: the step's second, two eager
    "setup_cache_hit_share": 40.0,
    # 0.5 inside init_state, 0.4 in the first task, 0.5 up to the opening
    "setup_unregistered_compile_s": 1.4,
    # no span from 26 to 27 and from 60 to 61: 2 s of 100
    "setup_unattributed_share": 2.0,
}


def span(name, start, end, thread=LOOP, parent=None, **attrs):
    return Span(name, start, end, thread, None, None, parent, attrs or None)


def compiled(program, start, trace, lower, xla, cache, parent):
    """One compile's three stages from `start`, one after the other."""
    a, b, c = start + trace, start + trace + lower, start + trace + lower + xla
    return [
        span("compile_trace", start, a, parent=parent, program=program),
        span("compile_lower", a, b, parent=parent, program=program),
        span("compile_xla", b, c, parent=parent, program=program,
             cache=cache),
    ]


def a_start():
    """A process begun at 0 whose window opens at 100."""
    return [
        span("boot", 0.0, 10.0, MAIN),
        span("job_setup", 10.0, 14.0, MAIN),
        span("worker_setup", 14.0, 14.5, MAIN),
        span("get_task", 14.5, 14.6),
        span("read", 14.6, 14.9, PRODUCER),
        span("data_wait", 14.6, 15.0),
        span("init_state", 15.0, 25.0),
        *compiled(INIT, 15.0, 2.0, 1.0, 3.0, "hit", "init_state"),
        span("compile_xla", 22.0, 22.5, parent="init_state", program=NONE,
             cache="miss"),
        span("restore", 25.0, 26.0),
        span("compute", 27.0, 60.0),
        *compiled(STEP, 27.0, 12.0, 2.0, 5.0, "hit", "compute"),
        *compiled(STEP, 46.0, 4.0, 1.0, 7.0, "miss", "compute"),
        span("task_sync", 61.0, 70.0),
        span("compile_lower", 70.0, 70.4, parent="report", program=NONE),
        span("report", 70.0, 71.0),
        span("get_task", 71.0, 71.1),
        span("compute", 71.1, 99.5),
        # straddles the opening: cut there
        span("compile_xla", 99.5, 100.5, parent="task_sync", program=NONE,
             cache="miss"),
        span("task_sync", 99.5, 101.0),
        # begun after the opening: no part of set-up
        span("compile_xla", 102.0, 150.0, program=STEP, cache="miss"),
        span("init_state", 103.0, 104.0),
    ]


CONTEXT = {"stamps": [(100.0, 0), (110.0, 300), (120.0, 300)]}


def spec_of(metric: str) -> dict:
    cell = manifest.resolve_cell(manifest.load_manifest(), CELL)
    spec = manifest.load_layer_metric(cell, metric)
    assert spec["reader"] == "startup_spans"
    assert manifest.import_by_name("readers", spec["reader"]) is startup_spans
    return spec


@pytest.mark.parametrize("metric, want", sorted(NINE.items()))
def test_each_metric_files_rule_on_hand_made_spans(monkeypatch, metric, want):
    monkeypatch.setattr(program_spans, "ring", a_start)
    got = startup_spans.read(spec_of(metric)["params"], CONTEXT)
    assert got == pytest.approx(want)


def test_the_six_parts_and_the_other_compiles_add_up_to_setup(monkeypatch):
    monkeypatch.setattr(program_spans, "ring", a_start)
    parts = sum(
        startup_spans.read(spec_of(name)["params"], CONTEXT)
        for name in NINE if name.endswith("_s")
        and name != "setup_unregistered_compile_s"
    )
    # what is left: the compiles of other programs outside init_state
    assert 100.0 - parts == pytest.approx(0.4 + 0.5)


@pytest.mark.parametrize("metric", sorted(NINE))
def test_a_program_without_the_record_reads_nothing(monkeypatch, metric):
    params = spec_of(metric)["params"]
    older = [s for s in a_start() if s.name in (
        "get_task", "data_wait", "compute", "task_sync", "report", "read",
    )]
    for ring in (lambda: older, lambda: None, lambda: []):
        monkeypatch.setattr(program_spans, "ring", ring)
        assert startup_spans.read(params, CONTEXT) is None
    monkeypatch.setattr(program_spans, "ring", a_start)
    assert startup_spans.read(params, {}) is None


def test_a_record_with_no_compile_reads_zero_not_nothing(monkeypatch):
    monkeypatch.setattr(program_spans, "ring", lambda: [
        s for s in a_start() if not s.name.startswith("compile_")
    ])
    for metric in ("setup_step_trace_s", "setup_step_xla_s",
                   "setup_unregistered_compile_s", "setup_cache_hit_share"):
        assert startup_spans.read(spec_of(metric)["params"], CONTEXT) == 0.0


def test_an_unknown_stat_is_an_error(monkeypatch):
    monkeypatch.setattr(program_spans, "ring", a_start)
    with pytest.raises(ValueError, match="unknown stat"):
        startup_spans.read(
            {"stat": "no_such", "from": "get_task"}, CONTEXT
        )


def test_the_nine_are_in_the_manifest_for_every_cell():
    bench = manifest.load_manifest()
    cells = [w["name"] for w in bench["workloads"]]
    entries = {m["name"]: m for m in bench["per_layer"]}
    # appended: the last nine, after everything that was there
    assert [m["name"] for m in bench["per_layer"][-9:]] == [
        "setup_boot_s", "setup_job_s", "setup_init_state_s",
        "setup_step_trace_s", "setup_step_xla_s", "setup_warmup_run_s",
        "setup_cache_hit_share", "setup_unregistered_compile_s",
        "setup_unattributed_share",
    ]
    for name in NINE:
        entry, spec = entries[name], spec_of(name)
        assert entry["moves"] == spec["moves"] == "setup_s"
        assert entry["workloads"] == cells
        assert entry["layer"] == spec["layer"]
        assert entry["unit"] == spec["unit"] == (
            "s" if name.endswith("_s") else "%"
        )
        assert entry["source"] in ("program_span", "program_counter")
    assert not [
        m["name"] for m in bench["per_layer"][:-9] if m["moves"] == "setup_s"
    ]
