"""Set-up by phase: the start-up spans and every compile's stages.

Start-up (`profiler.STARTUP_PHASES`) and a compile's stages
(`profiler.COMPILE_PHASES`) are `Span` records of the process's one
`PhaseTimer`, kept where the ring's turnover cannot drop them; the stage
seconds also live in the `ProgramRegistry` ledger, joined to a registered
program by the thread that dispatches it (`common/programs.py`, "Compile
stages").  The events they are read from are jax's own: one test finds
each fired by the installed jax, so that a jax which renames one fails here
and no metric silently reads 0.
"""

import json
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from elasticdl_tpu.common import metrics as metrics_lib
from elasticdl_tpu.common import profiler, programs
from elasticdl_tpu.common.profiler import (
    COMPILE_PHASES,
    STARTUP_PHASES,
    STEP_PHASES,
    PhaseTimer,
)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
STEPS_PER_TASK = 4
MINIBATCH = 16
TASKS = 3
NEW_VOCABULARY = set(STARTUP_PHASES) | set(COMPILE_PHASES)


def own_registry():
    return programs.ProgramRegistry(metrics=metrics_lib.MetricsRegistry())


def stage_spans(program: str) -> list:
    return [
        s for s in profiler.process_phase_timer().spans()
        if s.name in COMPILE_PHASES
        and (s.attrs or {}).get("program") == program
    ]


# ---------------------------------------------------------------------------
# the recorder
# ---------------------------------------------------------------------------


def test_the_vocabularies_are_apart():
    assert set(STARTUP_PHASES) == {
        "boot", "job_setup", "init_state", "restore", "worker_setup",
    }
    assert set(COMPILE_PHASES) == {
        "compile_trace", "compile_lower", "compile_xla",
    }
    assert not NEW_VOCABULARY & set(STEP_PHASES)


def test_the_processs_start_lies_before_this_modules_import():
    start = profiler.process_start()
    assert start == profiler.process_start()       # mapped once
    assert start <= profiler._IMPORTED_AT
    # the interpreter and the imports took a while, not a day
    assert 0.0 < time.perf_counter() - start < 86400.0


def test_begin_startup_records_boot_once_and_opens_job_setup():
    timer = PhaseTimer()
    entered = time.perf_counter()
    timer.begin_startup(entered)
    timer.startup("worker_setup")
    timer.begin_startup(time.perf_counter())   # a second job of the process
    timer.startup(None)
    spans = timer.spans()
    assert [s.name for s in spans] == [
        "boot", "job_setup", "worker_setup", "job_setup",
    ]
    boot, job, setup, _ = spans
    assert (boot.start, boot.end) == (profiler.process_start(), entered)
    assert job.start == entered and job.end == setup.start
    timer.startup(None)                        # nothing open: nothing said
    assert len(timer.spans()) == 4


def test_a_startup_phase_belongs_to_the_thread_that_opened_it():
    timer = PhaseTimer()
    timer.startup("worker_setup")
    closer = threading.Thread(target=timer.startup, args=(None,))
    closer.start()
    closer.join(timeout=10)
    (span,) = timer.spans()
    assert span.name == "worker_setup"
    assert span.thread == threading.get_native_id()


def test_startup_spans_survive_a_ring_filled_past_its_length():
    timer = PhaseTimer(ring=16)
    timer.begin_startup(time.perf_counter())
    with timer.phase("init_state"):
        timer.add("compile_xla", 0.25, program="p", cache="hit")
    timer.startup(None)
    for step in range(50):
        timer.mark(step=step)
        with timer.phase("compute"):
            pass
    spans = timer.spans()
    assert [s.name for s in spans[:4]] == [
        "boot", "compile_xla", "init_state", "job_setup",
    ]
    assert len(spans) == 4 + 16
    assert {s.name for s in spans[4:]} == {"compute"}
    assert spans[1].parent == "init_state"
    assert spans[1].attrs == {"program": "p", "cache": "hit"}


def test_past_the_kept_records_the_new_vocabulary_shares_the_ring(
        monkeypatch):
    monkeypatch.setattr(profiler, "STARTUP_SPAN_RECORDS", 2)
    timer = PhaseTimer(ring=4)
    for _ in range(8):
        timer.add("compile_trace", 0.001, program="p")
    assert len(timer.spans()) == 2 + 4


def test_startup_totals_feed_their_gauge_and_nothing_of_the_steps():
    registry = metrics_lib.MetricsRegistry()
    gauge = registry.gauge(
        "worker_startup_phase_seconds", "", labelnames=("phase",)
    )
    histogram = registry.histogram(
        "worker_step_phase_seconds", "", labelnames=("phase",)
    )
    timer = PhaseTimer(histogram=histogram, startup_gauge=gauge)
    timer.add("init_state", 2.0)
    timer.add("init_state", 1.0)                # a re-init after a remesh
    timer.add("compile_xla", 5.0, program="p", cache="miss")
    assert gauge.labels(phase="init_state").value() == pytest.approx(3.0)
    # the stages have counters of their own (common/programs.py)
    assert set(gauge.child_values()) == {("init_state",)}
    assert histogram.count == 0
    assert set(timer.snapshot()) == set(STEP_PHASES)
    assert set(timer.totals_milli()) == set(STEP_PHASES)
    timer.step_done()
    timer.flush()                               # no `step_phases` field


def test_the_processs_timer_exposes_every_startup_phase():
    profiler.process_phase_timer()
    children = {
        key[0] for key in next(
            f for f in metrics_lib.default_registry().families()
            if f.name == "worker_startup_phase_seconds"
        ).child_values()
    }
    assert children == set(STARTUP_PHASES)


# ---------------------------------------------------------------------------
# a compile's stages
# ---------------------------------------------------------------------------


def test_a_first_dispatch_records_the_three_stages_a_second_none():
    import jax.numpy as jnp

    registry = own_registry()
    prog = programs.registered_jit(
        "stages_p", lambda x: jnp.sin(x) @ x, registry=registry
    )
    timer = profiler.process_phase_timer()
    x = np.ones((5, 5), np.float32)
    with timer.phase("compute"):
        prog(x)
    spans = stage_spans("stages_p")
    assert [s.name for s in spans] == list(COMPILE_PHASES)
    trace, lower, xla = spans
    # one after the other inside the span the thread was in
    assert trace.start < trace.end <= lower.start < lower.end <= xla.start
    assert all(s.parent == "compute" for s in spans)
    assert all(s.thread == threading.get_native_id() for s in spans)
    assert xla.attrs["cache"] in ("hit", "miss", "off")
    # the outermost trace alone: `sin` and `matmul` fired their own first
    assert trace.end - trace.start < xla.end - trace.start
    rec = registry.ledger()["stages_p"]
    for span, key in zip(spans, ("trace", "lower", "xla")):
        assert rec[key + "_seconds"] == pytest.approx(
            span.end - span.start, abs=1e-5
        )
    assert rec["compiles"] == 1
    assert rec["cache_hits"] + rec["cache_misses"] == (
        xla.attrs["cache"] != "off"
    )
    # the stages lie inside the dispatch's wall, which has more in it
    assert rec["compile_seconds_total"] >= sum(
        rec[k + "_seconds"] for k in ("trace", "lower", "xla")
    ) - 1e-3
    prog(x)
    assert len(stage_spans("stages_p")) == 3
    assert registry.ledger()["stages_p"]["compiles"] == 1


def test_the_stage_counters_carry_the_programs_name():
    metrics = metrics_lib.MetricsRegistry()
    registry = programs.ProgramRegistry(metrics=metrics)
    prog = programs.registered_jit(
        "stages_c", lambda x: x * 3 + 1, registry=registry
    )
    prog(np.ones((7,), np.float32))
    rec = registry.ledger()["stages_c"]
    for stage in programs.STAGES:
        assert metrics.value(
            "worker_program_compile_stage_seconds_total",
            program="stages_c", stage=stage,
        ) == pytest.approx(rec[stage + "_seconds"], abs=1e-5)
    assert metrics.value("worker_program_cache_requests_total") == (
        rec["cache_hits"] + rec["cache_misses"]
    )
    # nothing of a registered program's is counted as unregistered
    assert metrics.value("worker_unregistered_compiles_total") == 0


def test_an_ahead_of_time_compile_is_the_programs_too():
    registry = own_registry()
    prog = programs.registered_jit(
        "stages_aot", lambda x: x - 2, registry=registry
    )
    assert prog.cost_for(np.ones((9,), np.float32)) is not None
    assert [s.name for s in stage_spans("stages_aot")] == list(
        COMPILE_PHASES
    )
    rec = registry.ledger()["stages_aot"]
    assert rec["compiles"] == 1 and rec["xla_seconds"] > 0.0


def test_the_event_carries_the_stages(tmp_path):
    from elasticdl_tpu.common import events

    path = str(tmp_path / "events.jsonl")
    events.configure(path, role="test")
    try:
        prog = programs.registered_jit(
            "stages_e", lambda x: x * x, registry=own_registry()
        )
        prog(np.ones((11,), np.float32))
    finally:
        events.configure("", role="test")
    (event,) = [
        e for e in events.read_events(path)
        if e["event"] == events.PROGRAM_COMPILED
    ]
    (xla,) = [s for s in stage_spans("stages_e") if s.name == "compile_xla"]
    assert event["program"] == "stages_e"
    assert event["xla_seconds"] == pytest.approx(
        xla.end - xla.start, abs=1e-3
    )
    assert event["cache"] == xla.attrs["cache"]
    assert {"trace_seconds", "lower_seconds"} <= set(event)


def test_an_eager_compile_is_unregistered_and_in_no_programs_record():
    import jax.numpy as jnp

    default = programs.default_program_registry()
    programs.install_compile_listeners()
    counters = metrics_lib.default_registry()
    count = counters.value("worker_unregistered_compiles_total")
    seconds = counters.value("worker_unregistered_compile_seconds_total")
    ledger = default.ledger()
    before = len(stage_spans(programs.UNREGISTERED))
    # shapes nothing else in the suite uses: these compile
    float(jnp.sum(jnp.full((3, 13, 17), 1.25) * 2.5))
    assert counters.value("worker_unregistered_compiles_total") > count
    assert counters.value(
        "worker_unregistered_compile_seconds_total"
    ) > seconds
    assert default.ledger() == ledger
    new = stage_spans(programs.UNREGISTERED)[before:]
    assert {s.name for s in new} == set(COMPILE_PHASES)
    assert counters.value(
        "worker_unregistered_compile_seconds_total"
    ) - seconds == pytest.approx(
        sum(s.end - s.start for s in new), abs=1e-4
    )


def test_the_listeners_register_once_however_many_registries(monkeypatch):
    from jax import monitoring

    asked = []
    monkeypatch.setattr(
        monitoring, "register_event_duration_secs_listener",
        lambda fn: asked.append(("duration", fn)),
    )
    monkeypatch.setattr(
        monitoring, "register_event_listener",
        lambda fn: asked.append(("event", fn)),
    )
    # as in a process that has made no program yet
    monkeypatch.setattr(programs, "_listeners_installed", False)
    for n in range(3):
        programs.registered_jit(
            f"stages_r{n}", lambda x: x, registry=own_registry()
        )
    programs.install_compile_listeners()
    assert asked == [
        ("duration", programs._on_duration), ("event", programs._on_event),
    ]


def test_forensics_keeps_no_wall_time_and_no_cache_state():
    registry = own_registry()
    programs.registered_jit(
        "stages_f", lambda x: x + 5, registry=registry
    )(np.ones((3,), np.float32))
    rec = registry.forensics()["ledger"]["stages_f"]
    assert set(rec) == {
        "compiles", "signatures", "budget", "storms",
        "flops_per_execution", "bytes_per_execution", "avals",
    }


def test_the_programs_table_prints_the_stages_and_the_cache():
    from elasticdl_tpu.client.programs import render_programs

    summary = {"ledger": {"worker_train_step": {
        "compiles": 2, "signatures": 1, "compile_seconds_p50": 40.0,
        "compile_seconds_p99": 41.0, "trace_seconds": 24.5,
        "lower_seconds": 3.25, "xla_seconds": 52.125,
        "cache_hits": 1, "cache_misses": 1,
    }}}
    head, row = render_programs(summary).splitlines()[1:3]
    assert head.split()[5:12] == [
        "c_p50", "c_p99", "trace", "lower", "xla", "cache", "h/m",
    ]
    assert row.split()[5:11] == [
        "40.000s", "41.000s", "24.500s", "3.250s", "52.125s", "1/1",
    ]


# ---------------------------------------------------------------------------
# jax's events, and the persistent cache's answers (a process of its own:
# `jax.clear_caches()` here would cost the suite's other tests theirs)
# ---------------------------------------------------------------------------

CACHE_SCRIPT = """
import json, sys
sys.path.insert(0, {root!r})
import jax, jax.numpy as jnp
import numpy as np
from jax import monitoring
jax.config.update("jax_compilation_cache_dir", {cache!r})
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
from elasticdl_tpu.common import metrics, profiler, programs
fired = set()
monitoring.register_event_listener(lambda event, **kw: fired.add(event))
monitoring.register_event_duration_secs_listener(
    lambda event, seconds, **kw: fired.add(event))
registry = programs.ProgramRegistry(metrics=metrics.MetricsRegistry())
prog = programs.registered_jit(
    "cached", lambda x: jnp.tanh(x) @ x, registry=registry)
x = np.ones((6, 6), np.float32)
prog(x)
jax.clear_caches()
prog(x)
print(json.dumps({{
    "fired": sorted(fired),
    "xla": [s.attrs for s in profiler.process_phase_timer().spans()
            if s.name == "compile_xla"
            and s.attrs["program"] == "cached"],
    "ledger": registry.ledger()["cached"],
}}))
"""


@pytest.fixture(scope="module")
def two_compiles(tmp_path_factory):
    """The same program compiled twice in a fresh process with an empty
    persistent cache: what jax fired and what the record holds."""
    cache = str(tmp_path_factory.mktemp("stage_cache"))
    out = subprocess.run(
        [sys.executable, "-c", CACHE_SCRIPT.format(root=ROOT, cache=cache)],
        capture_output=True, text=True, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert out.returncode == 0, out.stderr[-2000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("event", programs.COMPILE_EVENTS)
def test_the_installed_jax_fires_each_event_the_listeners_read(
        two_compiles, event):
    assert event in two_compiles["fired"]


def test_a_second_compile_reads_hit_where_the_first_read_miss(two_compiles):
    first, second = two_compiles["xla"]
    assert first["cache"] == "miss" and "retrieval_s" not in first
    assert second["cache"] == "hit" and second["retrieval_s"] > 0.0
    ledger = two_compiles["ledger"]
    assert (ledger["cache_hits"], ledger["cache_misses"]) == (1, 1)
    assert ledger["compiles"] == 2 and ledger["signatures"] == 1


# ---------------------------------------------------------------------------
# a Local job through the CLI
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def job(tmp_path_factory):
    """`elasticdl train`, Local, TASKS tasks of MNIST in this process."""
    from elasticdl_tpu.client.main import main
    from model_zoo.mnist.data import write_dataset

    root = tmp_path_factory.mktemp("mnist_startup")
    train, _ = write_dataset(
        str(root), n_train=TASKS * STEPS_PER_TASK * MINIBATCH, n_val=16
    )
    entered = time.perf_counter()
    rc = main([
        "train", "--model_zoo", "model_zoo",
        "--model_def", "mnist.mnist_functional_api.custom_model",
        "--training_data", train, "--distribution_strategy", "Local",
        "--num_epochs", "1", "--minibatch_size", str(MINIBATCH),
        "--records_per_task", str(STEPS_PER_TASK * MINIBATCH),
    ])
    assert rc == 0
    spans = [
        s for s in profiler.process_phase_timer().spans()
        if s.start >= entered
    ]
    return {"entered": entered, "spans": spans}


def test_the_startup_phases_tile_entry_to_the_first_get_task(job):
    spans = job["spans"]
    first_task = min(
        (s for s in spans if s.name == "get_task"), key=lambda s: s.start
    )
    (job_setup,) = [s for s in spans if s.name == "job_setup"]
    (worker_setup,) = [s for s in spans if s.name == "worker_setup"]
    # ordered, one after the other, none over another
    assert job["entered"] <= job_setup.start < job_setup.end
    assert job_setup.end == worker_setup.start < worker_setup.end
    assert worker_setup.end <= first_task.start
    whole = first_task.start - job["entered"]
    covered = (job_setup.end - job_setup.start) + (
        worker_setup.end - worker_setup.start
    )
    assert covered / whole >= 0.95, (covered, whole)
    # the main thread's, though the loop thread closed the second
    assert job_setup.thread == worker_setup.thread != first_task.thread


def test_the_state_is_made_in_the_first_task_on_the_loop_thread(job):
    spans = job["spans"]
    (init,) = [s for s in spans if s.name == "init_state"]
    first_task = min(
        (s for s in spans if s.name == "get_task"), key=lambda s: s.start
    )
    assert init.thread == first_task.thread and init.start > first_task.end
    assert not [s for s in spans if s.name == "restore"]   # no saver
    inside = [
        s for s in spans if s.parent == "init_state"
        and (s.attrs or {}).get("program") == "worker_init_state"
    ]
    assert [s.name for s in inside] == list(COMPILE_PHASES)
    assert all(init.start <= s.start and s.end <= init.end for s in inside)
    # the step compiles in step 0's `compute` of the first task
    step = [
        s for s in spans
        if (s.attrs or {}).get("program") == "worker_train_step"
    ]
    assert [s.name for s in step] == list(COMPILE_PHASES)
    assert all(s.parent == "compute" and s.step == 0 for s in step)
    assert all(s.start > init.end for s in step)


def test_a_steady_step_records_no_span_of_the_new_vocabulary(job):
    spans = job["spans"]
    # the job's tasks: the loop's (the main thread may carry an earlier
    # test's mark into its own eager compiles)
    tasks = sorted({s.task_id for s in spans if s.name == "compute"})
    assert len(tasks) == TASKS
    steady = [s for s in spans if s.task_id in tasks[1:]]
    assert len([s for s in steady if s.name == "compute"]) == (
        (TASKS - 1) * STEPS_PER_TASK
    )
    assert not [s for s in steady if s.name in NEW_VOCABULARY]


def test_the_jobs_ledger_holds_the_steps_stages(job):
    rec = programs.default_program_registry().ledger()["worker_train_step"]
    assert rec["trace_seconds"] > 0.0 and rec["xla_seconds"] > 0.0
    assert rec["cache_hits"] + rec["cache_misses"] >= 1
