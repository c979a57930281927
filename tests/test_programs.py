"""Program observatory acceptance (docs/OBSERVABILITY.md "Program
observatory"): deterministic compile telemetry under a fake clock,
signature/retrace counting, thread-safe concurrent first compiles, the
bucket-missing-engine recompile-storm drill capturing exactly one
byte-stable incident bundle, the prewarm-compiles-<=-buckets regression
guard, and the `elasticdl programs`/`top`/`trace` surfaces."""

import json
import os
import threading

import jax
import numpy as np
import pytest

from elasticdl_tpu.common import events
from elasticdl_tpu.common import metrics as metrics_lib
from elasticdl_tpu.common import programs
from elasticdl_tpu.common.flight import FlightRecorder


class FakeClock:
    """Monotonic fake: every read returns the current time and advances
    by `dt`, so compile wall seconds replay exactly."""

    def __init__(self, start=0.0, dt=1.0):
        self.t = float(start)
        self.dt = float(dt)

    def __call__(self):
        now = self.t
        self.t += self.dt
        return now


def _registry(clock=None):
    return programs.ProgramRegistry(
        clock=clock or FakeClock(),
        metrics=metrics_lib.MetricsRegistry(),
    )


@pytest.fixture(autouse=True)
def _clean_events():
    yield
    events.configure(None)


# ---- registry semantics --------------------------------------------------


def test_compile_histogram_is_deterministic_under_fake_clock():
    registry = _registry(FakeClock(dt=1.0))
    prog = programs.registered_jit(
        "p", lambda x: x + 1, registry=registry
    )
    prog(np.ones((2,), np.float32))
    prog(np.ones((3,), np.float32))
    rec = registry.ledger()["p"]
    assert rec["compiles"] == 2
    assert rec["signatures"] == 2
    # each dispatch brackets its compile with exactly one clock tick
    assert rec["compile_seconds_total"] == 2.0
    assert rec["compile_seconds_p50"] == 1.0
    assert rec["compile_seconds_p99"] == 1.0


def test_signature_cache_hit_is_not_a_retrace():
    registry = _registry()
    prog = programs.registered_jit(
        "p", lambda x: x * 2, registry=registry
    )
    seen = []
    events.add_observer(seen.append)
    try:
        prog(np.ones((2,), np.float32))
        prog(np.ones((3,), np.float32))
        prog(np.ones((2,), np.float32))  # cache hit
    finally:
        events.remove_observer(seen.append)
    rec = registry.ledger()["p"]
    assert rec["compiles"] == 2
    assert rec["signatures"] == 2
    compiled = [
        e for e in seen if e.get("event") == events.PROGRAM_COMPILED
    ]
    assert len(compiled) == 2
    assert all(e["program"] == "p" for e in compiled)


def test_nested_trace_is_not_counted_as_compile():
    registry = _registry()
    prog = programs.registered_jit(
        "inner", lambda x: x * 2, registry=registry
    )
    outer = jax.jit(lambda x: prog(x) + 1)
    out = outer(np.ones((2,), np.float32))
    np.testing.assert_allclose(np.asarray(out), 3.0)
    # the inner program inlined under the outer trace: no compile of
    # its own was observed (tracer args bypass the hook slot)
    assert registry.ledger()["inner"]["compiles"] == 0


def test_concurrent_first_compiles_are_counted_exactly_once_each():
    registry = _registry()
    prog = programs.registered_jit(
        "p", lambda x: (x * x).sum(), registry=registry
    )
    barrier = threading.Barrier(4)
    errors = []

    def call(rows):
        try:
            barrier.wait(timeout=30)
            prog(np.ones((rows, 3), np.float32))
        except Exception as exc:  # pragma: no cover - failure detail
            errors.append(exc)

    threads = [
        threading.Thread(target=call, args=(rows,))
        for rows in (2, 3, 4, 5)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
    assert not errors
    rec = registry.ledger()["p"]
    assert rec["signatures"] == 4
    assert rec["compiles"] == 4


def test_cost_for_harvests_cost_model_into_ledger():
    registry = _registry()
    prog = programs.registered_jit(
        "p", lambda x: x @ x.T, registry=registry
    )
    cost = prog.cost_for(np.ones((8, 8), np.float32))
    rec = registry.ledger()["p"]
    if cost:  # single-process CPU can AOT-compile
        assert rec["flops_per_execution"] > 0
        assert "float32[8,8]" in rec["avals"]
        # the same signature dispatched afterwards is a cache hit on
        # jax's side but the AOT compile was already recorded
        assert rec["compiles"] == 1
    else:  # degraded path: no crash, empty cost
        assert rec["flops_per_execution"] == 0.0


def test_storm_fires_once_per_program_and_names_the_churn():
    registry = _registry(FakeClock(dt=0.001))
    hooks = []
    registry.set_on_storm(hooks.append)
    prog = programs.registered_jit(
        "s", lambda x: x + 1, registry=registry, signature_budget=1
    )
    for rows in (2, 3, 4, 5):
        prog(np.ones((rows,), np.float32))
    rec = registry.ledger()["s"]
    assert rec["storms"] == 1  # dedup: one storm per program instance
    assert rec["budget"] == 1
    assert hooks == [{"program": "s", "signatures": 2, "budget": 1}]


def test_forensics_is_clock_free():
    registry = _registry()
    prog = programs.registered_jit(
        "p", lambda x: x + 1, registry=registry
    )
    prog(np.ones((2,), np.float32))
    forensics = registry.forensics()
    rec = forensics["ledger"]["p"]
    assert not any(k.startswith("compile_seconds") for k in rec)
    assert rec["compiles"] == 1


def test_default_registry_is_a_process_singleton():
    assert (
        programs.default_program_registry()
        is programs.default_program_registry()
    )


# ---- the serving-engine storm drill --------------------------------------

MODEL_DEF = "mnist.mnist_functional_api.custom_model"
FEATURE_SPEC = {"features": {"shape": [784], "dtype": "float32"}}


@pytest.fixture(scope="module")
def spec():
    from elasticdl_tpu.common.model_handler import get_model_spec

    return get_model_spec("model_zoo", MODEL_DEF)


@pytest.fixture(scope="module")
def variables(spec):
    x = np.random.RandomState(0).rand(2, 784).astype(np.float32)
    return dict(spec.model.init(jax.random.PRNGKey(0), x))


def _fresh_engine(monkeypatch, spec, variables, registry, **kwargs):
    from elasticdl_tpu.serving.engine import ServingEngine

    monkeypatch.setattr(
        programs, "default_program_registry", lambda: registry
    )
    return ServingEngine(
        spec.model, dict(variables), step=7,
        feature_spec=FEATURE_SPEC, buckets=(2, 8), **kwargs
    )


def test_prewarm_compiles_at_most_one_program_per_bucket(
    monkeypatch, spec, variables
):
    registry = _registry()
    engine = _fresh_engine(monkeypatch, spec, variables, registry)
    # back-compat surface: the engine's own counter still answers, and
    # it agrees with the observatory ledger
    assert engine.compile_count == len(engine.buckets)
    rec = registry.ledger()["serving_forward"]
    assert rec["compiles"] <= len(engine.buckets)
    assert rec["signatures"] == len(engine.buckets)
    assert rec["budget"] == len(engine.buckets)
    # padded traffic stays inside the warm buckets: no retrace, no storm
    x = np.random.RandomState(1).rand(8, 784).astype(np.float32)
    for rows in (1, 2, 3, 5, 8):
        engine.predict({"features": x[:rows]}, rows)
    rec = registry.ledger()["serving_forward"]
    assert rec["signatures"] == len(engine.buckets)
    assert rec["storms"] == 0


def test_bucket_missing_engine_captures_one_byte_stable_storm_bundle(
    monkeypatch, tmp_path, spec, variables
):
    """The ISSUE-20 acceptance drill: an engine that stopped padding to
    its buckets retraces per request size, blows the bucket-count
    signature budget, and the flight recorder captures exactly ONE
    recompile_storm bundle naming the program and its signature churn —
    byte-identical across two identical runs."""

    def run(subdir):
        registry = _registry(FakeClock(dt=0.001))
        recorder = FlightRecorder(
            incident_dir=str(tmp_path / subdir),
            program_registry=registry,
        )
        engine = _fresh_engine(
            monkeypatch, spec, variables, registry, pad_to_bucket=False
        )
        x = np.random.RandomState(1).rand(8, 784).astype(np.float32)
        for rows in (1, 3, 5, 7):  # none of these is a bucket
            engine.predict({"features": x[:rows]}, rows)
        recorder.close()
        bundles = sorted(os.listdir(tmp_path / subdir))
        assert bundles == ["incident-0001-recompile_storm"]
        bundle = tmp_path / subdir / bundles[0]
        manifest = json.loads((bundle / "manifest.json").read_text())
        evidence = manifest["evidence"]
        assert manifest["trigger"] == "recompile_storm"
        assert evidence["program"] == "serving_forward"
        assert evidence["budget"] == 2
        assert evidence["signatures"] > 2
        ledger = json.loads(
            (bundle / "programs.json").read_text()
        )["ledger"]
        assert ledger["serving_forward"]["storms"] == 1
        return {
            name: (bundle / name).read_bytes()
            for name in sorted(os.listdir(bundle))
        }

    assert run("a") == run("b")


# ---- surfaces: /varz, `elasticdl programs`, `top`, `trace` ---------------


def test_varz_json_carries_the_programs_summary():
    from elasticdl_tpu.common.telemetry import TelemetryServer

    server = TelemetryServer(
        registries=[metrics_lib.MetricsRegistry()], role="test"
    )
    doc = json.loads(server.varz_json())
    assert "programs" in doc
    assert "ledger" in doc["programs"]
    # cost over the roof is not a utilisation: no ratio rides here
    assert not {"mfu", "hbm_utilization", "bytes_per_sec"} & set(
        doc["programs"]
    )


def test_render_programs_table():
    from elasticdl_tpu.client.programs import render_programs

    registry = _registry()
    prog = programs.registered_jit(
        "worker_train_step", lambda x: x + 1, registry=registry,
        signature_budget=4,
    )
    prog(np.ones((2,), np.float32))
    out = render_programs(registry.summary())
    assert "1 programs, 1 compiles, 1 signatures, 0 storms" in out
    assert "worker_train_step" in out
    assert "float32[2]" in out
    assert "mfu" not in out and "bytes/s" not in out
    assert "(no programs registered" in render_programs({})


def test_top_renders_the_programs_line():
    from elasticdl_tpu.client.top import render

    frame = render({"programs": {
        "programs": 2, "compiles_total": 5, "signatures_total": 3,
        "storms_total": 1, "ledger": {},
    }})
    assert "programs: n=2 compiles=5 sigs=3 storms=1\n" in frame + "\n"
    # an empty observatory stays off the frame
    assert "programs:" not in render({})


def test_trace_renders_programs_track_and_compile_summary():
    from elasticdl_tpu.client.trace import build_chrome_trace, summarize

    evts = [
        {"ts": 10.0, "pid": 1, "event": events.PROGRAM_COMPILED,
         "program": "worker_train_step", "signature": "abc",
         "seconds": 2.5, "flops": 1e9, "bytes": 1e8, "signatures": 1},
        {"ts": 12.0, "pid": 1, "event": events.PROGRAM_COMPILED,
         "program": "serving_forward", "signature": "def",
         "seconds": 0.5, "flops": 1e6, "bytes": 1e5, "signatures": 3},
        {"ts": 12.5, "pid": 1, "event": events.RECOMPILE_STORM,
         "program": "serving_forward", "signatures": 3, "budget": 2},
    ]
    trace = build_chrome_trace(evts)
    trace_events = trace["traceEvents"]
    track = [
        e for e in trace_events
        if e.get("ph") == "M" and e.get("name") == "process_name"
        and e.get("args", {}).get("name") == "programs"
    ]
    assert len(track) == 1
    slices = [
        e for e in trace_events
        if e.get("ph") == "X" and e.get("cat") == "compile"
    ]
    assert {s["name"] for s in slices} == {
        "compile worker_train_step", "compile serving_forward"
    }
    by_name = {s["name"]: s for s in slices}
    assert by_name["compile worker_train_step"]["dur"] == 2.5e6
    instants = [
        e for e in trace_events
        if e.get("ph") == "i" and "recompile storm" in e.get("name", "")
    ]
    assert len(instants) == 1
    assert instants[0]["args"]["budget"] == 2

    text = summarize(evts)
    assert "xla compiles: 2 across 2 programs" in text
    assert "STORMS=1" in text

