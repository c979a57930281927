"""The seeded chaos driver of the online loop, for
`tests/test_online_pipeline.py::test_chaos_replay_is_byte_identical`
(docs/ONLINE.md "Determinism under chaos")."""

import json
import tempfile

import numpy as np

from elasticdl_tpu.common import events as events_lib
from elasticdl_tpu.common import faults
from elasticdl_tpu.common.faults import FaultRegistry, FaultSpec
from elasticdl_tpu.common.model_handler import get_model_spec
from elasticdl_tpu.online import OnlineConfig, OnlinePipeline
from elasticdl_tpu.proto import serving_pb2 as spb
from elasticdl_tpu.serving.server import make_predict_request
from model_zoo.clickstream import ctr_mlp


def lineage_reconciliation(records):
    """Reconcile the per-window phase decompositions against the
    measured ingest->first-serve times (docs/OBSERVABILITY.md "Window
    lineage"): over completed, non-dropped windows, the p99 of
    sum(phases) must sit within 5% of the p99 of the measured e2e —
    the contract that the decomposition accounts for ALL the staleness,
    not an approximation of it."""
    done = [
        r for r in records
        if r.get("complete") and not r.get("dropped")
    ]
    if not done:
        return {
            "windows": 0, "phase_sum_p99_s": 0.0, "e2e_p99_s": 0.0,
            "delta_pct": 0.0, "within_5pct": True,
            "max_abs_delta_s": 0.0,
        }
    sums = np.array([sum(r["phases"].values()) for r in done])
    e2e = np.array([r["e2e_s"] for r in done])
    p99_sum = float(np.percentile(sums, 99))
    p99_e2e = float(np.percentile(e2e, 99))
    delta_pct = (
        abs(p99_sum - p99_e2e) / p99_e2e * 100.0 if p99_e2e else 0.0
    )
    return {
        "windows": len(done),
        "phase_sum_p99_s": round(p99_sum, 6),
        "e2e_p99_s": round(p99_e2e, 6),
        "delta_pct": round(delta_pct, 3),
        "within_5pct": delta_pct <= 5.0,
        "max_abs_delta_s": round(
            float(np.max(np.abs(sums - e2e))), 6
        ),
    }


def online_chaos_run(seed: int):
    """One seeded chaos pass of the online loop under a FAKE clock and a
    strictly sequential driver: a stream stall (`stream.poll`), a lost
    window re-arm (`task.rearm`), a rejected hot-reload
    (`serving.reload`), a deferred shard move (`store.shard_handoff`),
    a mid-run replica kill, TWO trainer-worker kills (the second retries
    the deferred shard move), and a master restart landed while a window
    is mid-flight WITH its reader buffers wiped — the survivors must
    replay those windows from the deterministic source, and the lineage
    must keep their ORIGINAL ingest attribution.  Returns
    (canonical_text, summary): the text concatenates the fault trace,
    the fleet manager's and SLO evaluator's clock-free decision lists,
    the normalized span-event stream (window_span lineage stamps
    included), and the completed window-lineage decompositions —
    byte-identical across same-seed runs (the acceptance bar of
    docs/ONLINE.md).  The exactly-once claim is checked in summary:
    zero lost windows, zero duplicate shard reports; the lineage claim
    too: phase sums reconcile with measured e2e within 5%, replayed
    windows keep pre-restart ingest stamps."""
    clk = [1_000_000.0]

    def clock():
        clk[0] += 0.125
        return clk[0]

    # Explicit (still seed-stamped) schedule: every fault is one the
    # driver is guaranteed to reach, so `all_fired()` holds and the
    # trace compares byte-for-byte (the chaos-soak discipline).
    registry = faults.install(FaultRegistry(
        schedule=[
            FaultSpec(faults.POINT_STREAM_POLL, 2, "raise"),
            FaultSpec(faults.POINT_TASK_REARM, 3, "raise"),
            FaultSpec(faults.POINT_SERVING_RELOAD, 2, "raise"),
            # first handoff attempt (trainer 2's shard) defers; the
            # second kill's evacuation retries and completes it
            FaultSpec(faults.POINT_STORE_SHARD_HANDOFF, 1, "raise"),
        ],
        seed=seed,
    ))
    keep = ("window", "tasks", "records", "step",
            "shard", "from_worker", "to_worker",
            "window_id", "phase", "reason", "at_unix_s", "ingest_unix_s")
    norm_events = []

    def observe(record):
        norm_events.append({
            "event": record.get("event"),
            **{k: record[k] for k in keep if k in record},
        })

    events_lib.add_observer(observe)
    rng = np.random.RandomState(seed)
    failed = 0
    restart_at = None
    try:
        spec = get_model_spec(
            "model_zoo", "clickstream.ctr_mlp.custom_model"
        )
        with tempfile.TemporaryDirectory() as tmp:
            pipe = OnlinePipeline(
                tmp, spec,
                OnlineConfig(
                    seed=seed, window_records=64, records_per_poll=64,
                    records_per_task=16, checkpoint_every_windows=2,
                    replicas=2, workers=3, num_shards=4,
                ),
                clock=clock,
            )
            for i in range(12):
                if i == 7:
                    # leave the tick's window mid-flight (1 of its 4
                    # shards trained), wipe the reader's buffers (full
                    # master-process amnesia), then kill the master
                    # brain: the replacement must re-arm exactly the 3
                    # undone shards from the journal AND replay the
                    # wiped windows from the deterministic source —
                    # their lineage must keep the original ingest stamp
                    pipe.tick(max_train_tasks=1)
                    wiped = pipe.drop_window_buffers()
                    restart_at = clk[0]
                    restored = pipe.restart_master()
                    faults.note(
                        "master.restart",
                        "windows=%d tasks=%d buffers_wiped=%d" % (
                            restored["windows_restored"],
                            restored["tasks_rearmed"],
                            wiped,
                        ),
                    )
                else:
                    pipe.tick()
                if i == 3:
                    pipe.kill_replica(1)
                    faults.note("replica.kill", "replica=1")
                if i == 4:
                    info = pipe.kill_worker(2)
                    faults.note(
                        "trainer.kill",
                        "worker=2 handoffs=%d" % info["handoffs"],
                    )
                if i == 9:
                    info = pipe.kill_worker(1)
                    faults.note(
                        "trainer.kill",
                        "worker=1 handoffs=%d" % info["handoffs"],
                    )
                for _ in range(2):
                    x = ctr_mlp.encode(
                        rng.randint(0, 512, 2), rng.randint(0, 128, 2)
                    )
                    try:
                        resp = pipe.predict(make_predict_request(x))
                        if resp.code != spb.SERVING_OK:
                            failed += 1
                    except Exception:
                        failed += 1
            # drain the restart's re-armed remainder before snapshotting
            pipe.tick()
            snap = pipe.snapshot()
            lineage_records = pipe.lineage.records()
            # open windows too: a replayed window still blocked in
            # reload_wait must already carry its original ingest stamp
            all_lineage = lineage_records + pipe.lineage.open_decompositions()
            pipe.shutdown()
    finally:
        events_lib.remove_observer(observe)
        faults.uninstall()

    canonical = json.dumps({
        "fault_trace": registry.trace_text(),
        "fleet_decisions": snap["serving_fleet"]["decisions"],
        "slo_decisions": snap["slo"]["decisions"],
        "events": norm_events,
        "lineage": lineage_records,
    }, sort_keys=True)
    summary = {
        "all_faults_fired": registry.all_fired(),
        "failed_requests": failed,
        "rearm_faults": snap["online"]["rearm_faults"],
        "poll_faults": snap["stream"]["poll_faults"],
        "last_reload_step": snap["online"]["last_reload_step"],
        "windows_trained": snap["windows_trained"],
        "handoffs": snap["online"]["handoffs"],
        "pending_handoffs": snap["online"]["pending_handoffs"],
        "handoff_faults": snap["store"]["handoff_faults"],
        "windows_released": snap["online"]["windows_released"],
        "windows_lost": snap["online"]["windows_lost"],
        "duplicate_reports": snap["online"]["duplicate_reports"],
        "master_restarts": snap["online"]["master_restarts"],
        "alive_trainers": snap["online"]["alive_trainers"],
        "replayed_windows": snap["stream"]["replayed_windows"],
        # ---- window lineage (docs/OBSERVABILITY.md "Window lineage") --
        "lineage_windows": snap["lineage"]["windows_traced"],
        "lineage_replayed": sum(
            1 for r in all_lineage if r.get("replayed")
        ),
        "lineage_dominant_phase": snap["lineage"]["dominant_phase"],
        "lineage_reconcile": lineage_reconciliation(lineage_records),
        # replayed windows must keep their PRE-restart ingest stamp —
        # replay re-buffers records, it never re-bases attribution
        "replayed_original_ingest": (
            restart_at is not None
            and any(r.get("replayed") for r in all_lineage)
            and all(
                r.get("ingest_unix_s") is not None
                and float(r["ingest_unix_s"]) < restart_at
                for r in all_lineage if r.get("replayed")
            )
        ),
    }
    return canonical, summary
