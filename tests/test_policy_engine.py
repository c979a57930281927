"""Policy engine (master/policy.py): eviction dwell/budget/cooldown,
backlog scale-up with hysteresis, data_wait scale-down, fault-point
behavior — and the ISSUE 6 acceptance scenario: a seeded, in-process,
fake-clock chaos run where an injected slowdown + one kill provably
trigger eviction and scale-up, recovery is measured on the recovery
clock, and the policy_decision sequence is byte-stable across same-seed
runs."""

import json

import pytest

from elasticdl_tpu.common import events, faults
from elasticdl_tpu.common.constants import PodStatus
from elasticdl_tpu.common.k8s_client import FakeK8sClient
from elasticdl_tpu.master.pod_manager import PodManager
from elasticdl_tpu.master.policy import PolicyConfig, PolicyEngine
from elasticdl_tpu.master.recovery import RecoveryClock
from elasticdl_tpu.master.task_manager import TaskManager
from elasticdl_tpu.proto import elasticdl_pb2 as pb


@pytest.fixture(autouse=True)
def _clean_globals():
    yield
    faults.uninstall()
    events.configure(None)


class FakeClock:
    def __init__(self, start=1000.0):
        self.t = start

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


class StubTaskManager:
    """Just the two snapshots the engine consumes, fully scriptable."""

    def __init__(self):
        self.todo = 0
        self.stragglers = {}

    def snapshot(self):
        return {"todo": self.todo}

    def straggler_snapshot(self):
        return dict(self.stragglers)

    def recover_tasks(self, worker_id):
        self.stragglers.pop(worker_id, None)
        return 0


def make_pods(num_workers, wpg=1, tm=None, recovery_clock=None):
    k8s = FakeK8sClient()
    manager = PodManager(
        k8s,
        task_manager=tm,
        job_name="poltest",
        num_workers=num_workers,
        workers_per_group=wpg,
        recovery_clock=recovery_clock,
    )
    manager.start()
    return manager, k8s


# ---- eviction ----------------------------------------------------------


def test_evict_waits_out_dwell_then_restarts_group():
    clk = FakeClock()
    tm = StubTaskManager()
    pods, _ = make_pods(4, wpg=2, tm=tm)
    engine = PolicyEngine(
        tm, pods,
        PolicyConfig(min_workers=2, max_workers=4, workers_per_group=2,
                     straggler_dwell_s=30.0, eviction_budget=1),
        clock=clk,
    )
    tm.stragglers = {1: {"straggler": True, "flagged_for_s": 10.0,
                         "mean_task_s": 5.0}}
    assert engine.tick() is None  # dwell not met
    tm.stragglers[1]["flagged_for_s"] = 31.0
    decision = engine.tick()
    assert decision["action"] == "evict"
    assert decision["reason"] == "straggler"
    assert decision["worker_id"] == 1
    assert pods.snapshot()["evictions"] == 1
    # group-aware: worker 1's whole slice (workers 0 and 1) was
    # replaced by fresh ids in the SAME group, fleet back at strength
    alive = pods.alive_workers()
    assert len(alive) == 4
    assert 0 not in alive and 1 not in alive
    replaced = [w for w in alive if w not in (2, 3)]
    assert len(replaced) == 2
    assert pods._group_of[replaced[0]] == pods._group_of[replaced[1]]
    # budget exhausted: a second dwelled flag is not acted on
    tm.stragglers = {2: {"straggler": True, "flagged_for_s": 100.0,
                         "mean_task_s": 5.0}}
    assert engine.tick() is None


def test_evict_cooldown_spaces_evictions():
    clk = FakeClock()
    tm = StubTaskManager()
    pods, _ = make_pods(3, tm=tm)
    engine = PolicyEngine(
        tm, pods,
        PolicyConfig(min_workers=1, max_workers=3,
                     straggler_dwell_s=10.0, eviction_budget=2,
                     eviction_cooldown_s=500.0),
        clock=clk,
    )
    tm.stragglers = {
        0: {"straggler": True, "flagged_for_s": 50.0},
        1: {"straggler": True, "flagged_for_s": 50.0},
    }
    assert engine.tick()["worker_id"] == 0
    tm.recover_tasks(0)
    assert engine.tick() is None  # cooldown holds
    clk.advance(501.0)
    assert engine.tick()["worker_id"] == 1


# ---- autoscaling -------------------------------------------------------


def test_scale_up_on_backlog_with_hysteresis_and_ceiling():
    clk = FakeClock()
    tm = StubTaskManager()
    tm.todo = 40
    pods, _ = make_pods(2, tm=tm)
    engine = PolicyEngine(
        tm, pods,
        PolicyConfig(min_workers=2, max_workers=6,
                     backlog_per_worker=4.0, backlog_ticks=2,
                     scale_step=2, scale_hold_ticks=1),
        clock=clk,
    )
    assert engine.tick() is None             # streak 1
    decision = engine.tick()                 # streak 2 -> act
    assert decision["action"] == "scale_up"
    assert decision["reason"] == "backlog"
    assert decision["launched"] == 2
    assert len(pods.alive_workers()) == 4
    assert engine.tick() is None             # hold tick
    decision = engine.tick()                 # streak re-built
    assert decision["action"] == "scale_up"
    assert len(pods.alive_workers()) == 6    # ceiling
    assert engine.tick() is None
    assert engine.tick() is None             # no room left
    assert len(pods.alive_workers()) == 6


def test_scale_up_aligns_to_whole_groups():
    clk = FakeClock()
    tm = StubTaskManager()
    tm.todo = 100
    pods, _ = make_pods(2, wpg=2, tm=tm)
    engine = PolicyEngine(
        tm, pods,
        PolicyConfig(min_workers=2, max_workers=6, workers_per_group=2,
                     backlog_per_worker=1.0, backlog_ticks=1,
                     scale_step=1, scale_hold_ticks=0),
        clock=clk,
    )
    decision = engine.tick()
    assert decision["requested"] == 2        # 1 rounded up to one group
    new = [w for w in pods.alive_workers() if w not in (0, 1)]
    assert len(new) == 2
    assert pods._group_of[new[0]] == pods._group_of[new[1]]


def test_scale_down_on_data_wait_prefers_stragglers():
    clk = FakeClock()
    tm = StubTaskManager()
    tm.todo = 0
    pods, _ = make_pods(4, tm=tm)
    telemetry = {}
    engine = PolicyEngine(
        tm, pods,
        PolicyConfig(min_workers=2, max_workers=4,
                     backlog_per_worker=1e9,
                     data_wait_share=0.5, data_wait_ticks=2,
                     scale_step=1, scale_hold_ticks=0),
        telemetry_fn=lambda: telemetry,
        clock=clk,
    )

    def starve():
        entry = telemetry.setdefault(
            0, {"phase_data_wait_ms": 0.0, "phase_compute_ms": 0.0}
        )
        entry["phase_data_wait_ms"] += 800.0
        entry["phase_compute_ms"] += 200.0

    starve()
    assert engine.tick() is None             # streak 1
    starve()
    decision = engine.tick()                 # streak 2 -> act
    assert decision["action"] == "scale_down"
    assert decision["reason"] == "data_wait"
    assert decision["removed"] == [3]        # newest, nobody flagged
    assert pods.alive_workers() == [0, 1, 2]
    # a flagged straggler becomes the preferred victim
    tm.stragglers = {0: {"straggler": True, "flagged_for_s": 0.0}}
    starve()
    assert engine.tick() is None
    starve()
    assert engine.tick()["removed"] == [0]
    assert pods.alive_workers() == [1, 2]
    # at the floor: starved or not, no further shrink
    starve()
    starve()
    assert engine.tick() is None
    assert engine.tick() is None
    assert pods.alive_workers() == [1, 2]


@pytest.mark.parametrize("producer_ms, device_ms, want", [
    # the loop waits 800 of its 1000 ms whatever the producer thread did
    # meanwhile: its phases overlap the loop's and are not loop time
    ({"read": 5000.0, "pack": 700.0, "queue_full": 0.0}, 0.0, 0.8),
    # a device-bound worker: the producer sits blocked (queue_full) and
    # the loop waits behind the device (task_sync), not on the data
    ({"read": 100.0, "pack": 100.0, "queue_full": 9000.0}, 3000.0, 0.2),
])
def test_data_wait_share_is_of_the_loops_time(producer_ms, device_ms, want):
    tm = StubTaskManager()
    pods, _ = make_pods(2, tm=tm)
    telemetry = {0: {"phase_data_wait_ms": 0.0}}
    engine = PolicyEngine(
        tm, pods,
        PolicyConfig(min_workers=1, max_workers=2, backlog_per_worker=1e9),
        telemetry_fn=lambda: telemetry,
        clock=FakeClock(),
    )
    engine.tick()
    telemetry[0] = {
        "phase_data_wait_ms": 800.0, "phase_compute_ms": 150.0,
        "phase_h2d_stage_ms": 50.0, "phase_task_sync_ms": device_ms,
        **{f"phase_{k}_ms": v for k, v in producer_ms.items()},
    }
    engine.tick()
    assert engine._last_data_wait_ratio == pytest.approx(want)


def test_no_data_wait_signal_without_step_progress():
    clk = FakeClock()
    tm = StubTaskManager()
    pods, _ = make_pods(3, tm=tm)
    telemetry = {0: {"phase_data_wait_ms": 900.0,
                     "phase_compute_ms": 100.0}}
    engine = PolicyEngine(
        tm, pods,
        PolicyConfig(min_workers=1, max_workers=3,
                     backlog_per_worker=1e9,
                     data_wait_share=0.5, data_wait_ticks=2,
                     scale_hold_ticks=0),
        telemetry_fn=lambda: telemetry,
        clock=clk,
    )
    engine.tick()  # first window: real signal, streak 1 of 2
    # counters frozen after that: zero delta resets the streak, so the
    # stale cumulative ratio alone can never trigger a shrink
    assert engine.tick() is None
    assert engine.tick() is None
    assert len(pods.alive_workers()) == 3


# ---- fault point + lifecycle -------------------------------------------


def test_injected_tick_fault_skips_the_tick():
    clk = FakeClock()
    tm = StubTaskManager()
    tm.stragglers = {0: {"straggler": True, "flagged_for_s": 100.0}}
    pods, _ = make_pods(2, tm=tm)
    engine = PolicyEngine(
        tm, pods,
        PolicyConfig(min_workers=1, max_workers=2,
                     straggler_dwell_s=1.0, eviction_budget=1),
        clock=clk,
    )
    faults.install(faults.FaultRegistry(
        [faults.FaultSpec(faults.POINT_POLICY_TICK, 0, "raise")]
    ))
    assert engine.tick() is None
    assert engine.metrics_registry.value(
        "master_policy_skipped_ticks_total"
    ) == 1.0
    assert engine.decisions == []
    # the next tick proceeds and acts
    assert engine.tick()["action"] == "evict"


def test_interval_zero_disables_background_loop():
    tm = StubTaskManager()
    pods, _ = make_pods(1, tm=tm)
    engine = PolicyEngine(tm, pods, PolicyConfig(interval_s=0.0))
    assert engine.start() is False
    engine.stop()  # no-op, must not raise


# ---- the acceptance scenario -------------------------------------------

SEED = 2026
SLOW_WORKER = 2
KILLED_WORKER = 1


def _chaos_run(event_log):
    """One fully in-process, single-threaded chaos run under a fake
    clock: 3 workers, worker 2 runs tasks 10x slow (the injected
    slowdown), worker 1 is killed mid-job, the fault plan wedges one
    policy tick and fails one pod launch mid-scale.  Returns everything
    the assertions and the byte-stability comparison need."""
    events.configure(event_log, role="master")
    reg = faults.install(faults.FaultRegistry(
        [
            faults.FaultSpec(faults.POINT_POLICY_TICK, 2, "raise"),
            # hits 0-2 are the initial fleet; hit 3 is the first
            # policy-driven scale_up launch -> apiserver error mid-scale
            faults.FaultSpec(faults.POINT_POD_CREATE, 3, "raise"),
        ],
        seed=SEED,
    ))
    clk = FakeClock()
    shards = [pb.Shard(name="d", start=i, end=i + 1) for i in range(160)]
    tm = TaskManager(
        training_shards=shards, num_epochs=1,
        straggler_multiple=2.0, straggler_min_tasks=3, clock=clk,
    )
    recovery = RecoveryClock(clock=clk)
    k8s = FakeK8sClient()
    pods = PodManager(
        k8s,
        task_manager=tm,
        job_name="chaos",
        num_workers=3,
        relaunch_on_worker_failure=3,
        recovery_clock=recovery,
    )
    pods.start()
    engine = PolicyEngine(
        tm, pods,
        PolicyConfig(
            min_workers=2, max_workers=5,
            straggler_dwell_s=20.0, eviction_budget=1,
            eviction_cooldown_s=100.0,
            backlog_per_worker=3.0, backlog_ticks=2,
            scale_step=1, scale_hold_ticks=1,
        ),
        clock=clk,
    )

    def work_round():
        """Each alive worker leases one task, 'runs' it on the fake
        clock (10x for the slowdown victim), and reports — the
        servicer's mark_progress on success included."""
        for wid in list(pods.alive_workers()):
            task = tm.get(wid)
            assert task is not None
            clk.advance(10.0 if wid == SLOW_WORKER else 1.0)
            assert tm.report(task.task_id, success=True, worker_id=wid,
                             records=1)
            recovery.mark_progress()

    finished_at_kill = None
    for rnd in range(1, 11):
        work_round()
        if rnd == 4:
            reg.note("kill", f"worker-{KILLED_WORKER}")
            finished_at_kill = tm.counters.finished
            k8s.emit(f"chaos-worker-{KILLED_WORKER}", PodStatus.FAILED,
                     exit_code=1)
        engine.tick()

    events.configure(None)
    return {
        "engine": engine,
        "pods": pods,
        "tm": tm,
        "recovery": recovery,
        "registry": reg,
        "finished_at_kill": finished_at_kill,
        "decisions_json": json.dumps(engine.decisions, sort_keys=True),
        "events": events.read_events(event_log),
    }


def _policy_event_projection(evts):
    """policy_decision span events minus the run-variant fields."""
    return json.dumps(
        [
            {k: v for k, v in e.items() if k not in ("ts", "pid")}
            for e in evts
            if e.get("event") == "policy_decision"
        ],
        sort_keys=True,
    )


def test_chaos_policy_scenario(tmp_path):
    run = _chaos_run(str(tmp_path / "run_a.jsonl"))
    engine, pods, recovery = run["engine"], run["pods"], run["recovery"]
    actions = [d["action"] for d in engine.decisions]

    # the flagged straggler was evicted, exactly once, past its dwell
    evicts = [d for d in engine.decisions if d["action"] == "evict"]
    assert len(evicts) == 1
    assert evicts[0]["worker_id"] == SLOW_WORKER
    assert evicts[0]["reason"] == "straggler"
    assert evicts[0]["flagged_for_s"] >= 20.0
    assert pods.snapshot()["evictions"] == 1
    assert SLOW_WORKER not in pods.alive_workers()
    # and its flag is gone: the replacement runs at fleet pace
    assert run["tm"].snapshot()["stragglers"] == []

    # backlog drove scale-up; the injected mid-scale apiserver error was
    # absorbed (one launch failure, no phantom, a later launch made it)
    scale_ups = [d for d in engine.decisions if d["action"] == "scale_up"]
    assert scale_ups, actions
    assert any(d["launched"] >= 1 for d in scale_ups)
    assert any(d["launched"] == 0 for d in scale_ups)  # the absorbed one
    assert pods.snapshot()["launch_failures"] == 1

    # the injected policy.tick wedge skipped exactly one tick
    assert engine.metrics_registry.value(
        "master_policy_skipped_ticks_total"
    ) == 1.0

    # recovery-clock-measured restoration: both outages (the kill and
    # the eviction) closed, on the fake clock, within a round's worth of
    # work — throughput provably resumed
    rsnap = recovery.snapshot()
    assert rsnap["pending"] is False
    assert rsnap["recoveries"] >= 2
    assert all(d < 30.0 for d in rsnap["recovery_durations_s"])
    # and tasks kept finishing after the kill + eviction
    assert run["tm"].counters.finished > run["finished_at_kill"] + 10

    # the full fault plan fired (precondition for trace comparison)
    assert run["registry"].all_fired(), run["registry"].unfired()

    # policy decisions carry the closed-vocabulary fields, every one
    for d in engine.decisions:
        assert d["action"] in events.POLICY_ACTIONS
        assert d["reason"] in events.POLICY_REASONS


def test_chaos_policy_scenario_is_byte_stable(tmp_path):
    run_a = _chaos_run(str(tmp_path / "a.jsonl"))
    trace_a = run_a["registry"].trace_text()
    run_b = _chaos_run(str(tmp_path / "b.jsonl"))
    trace_b = run_b["registry"].trace_text()

    assert run_a["decisions_json"] == run_b["decisions_json"]
    assert _policy_event_projection(run_a["events"]) == \
        _policy_event_projection(run_b["events"])
    # the span stream actually carried the decisions
    assert '"action": "evict"' in _policy_event_projection(run_a["events"])
    assert trace_a == trace_b
