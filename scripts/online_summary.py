"""Online-loop smoke summary for CI.

Runs a short real-clock pass of the continuous-learning pipeline —
stream -> perpetual task queue -> train -> checkpoint -> hot-reload
behind live predicts (docs/ONLINE.md) — and prints two
machine-readable lines:

    ONLINE_SUMMARY train_eps=<e> qps=<q> staleness_p99_s=<s> burn=<b> \
        windows_armed=<a> windows_lost=<l> handoffs=<h> \
        freshness_budget_worst_phase=<p> lineage_windows=<n>
    TRAFFIC_SUMMARY offered_qps=<q> shed_ratio=<r> scale_actions=<n> \
        failed_requests=<f> fleet=<k>

`scripts/run_tests.sh` emits them next to STORE_SUMMARY /
TIER1_SUMMARY so CI can watch the online loop's sustained throughput,
train-to-serve staleness drift, the window-ledger health (armed/lost
counts plus shard handoffs — lost must stay 0; see docs/ONLINE.md
exactly-once accounting), and the serving control loop (the seeded
traffic generator's spike against the autoscaling fleet,
docs/SERVING.md "Autoscaling & backpressure").  A few seconds on
CPU: two windows, two in-process replicas, sequential predicts on the
driver thread.

tests/test_online_pipeline.py asserts on `smoke_summary()` (and
tests/test_traffic.py on `traffic_summary()`) directly, so the printed
numbers and the tested behaviour cannot diverge.
"""

from __future__ import annotations

import os
import sys
import tempfile
import time

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)

WINDOWS = 2
PREDICTS_PER_TICK = 2
SEED = 0x5EED


def smoke_summary(windows: int = WINDOWS,
                  predicts_per_tick: int = PREDICTS_PER_TICK,
                  seed: int = SEED) -> dict:
    """Drive `windows` stream windows through the online loop under a
    real clock, predicting against the live fleet between ticks.
    Returns the dict behind the ONLINE_SUMMARY line."""
    import numpy as np

    from elasticdl_tpu.common.model_handler import get_model_spec
    from elasticdl_tpu.online import OnlineConfig, OnlinePipeline
    from elasticdl_tpu.proto import serving_pb2 as spb
    from elasticdl_tpu.serving.server import make_predict_request
    from model_zoo.clickstream import ctr_mlp

    spec = get_model_spec(
        os.path.join(_ROOT, "model_zoo"),
        "clickstream.ctr_mlp.custom_model",
    )
    cfg = OnlineConfig(
        seed=seed, window_records=64, records_per_poll=64,
        records_per_task=16, checkpoint_every_windows=1, replicas=2,
    )
    rng = np.random.RandomState(seed)
    served = failed = 0
    with tempfile.TemporaryDirectory() as tmp:
        pipe = OnlinePipeline(tmp, spec, cfg)
        t0 = time.perf_counter()
        ticks = 0
        while pipe._windows_trained < windows and ticks < windows * 4:
            pipe.tick()
            ticks += 1
            for _ in range(predicts_per_tick):
                x = ctr_mlp.encode(
                    rng.randint(0, cfg.source_users, 2),
                    rng.randint(0, cfg.source_items, 2),
                )
                try:
                    resp = pipe.predict(make_predict_request(x))
                    ok = resp.code == spb.SERVING_OK
                except Exception:
                    ok = False
                if ok:
                    served += 1
                else:
                    failed += 1
        elapsed = time.perf_counter() - t0
        staleness = pipe.freshness.quantiles()
        snap = pipe.snapshot()
        pipe.shutdown()
    return {
        "train_eps": snap["examples_trained"] / elapsed,
        "qps": served / elapsed,
        "staleness_p99_s": staleness["staleness_p99_s"],
        "burn": snap["max_burn"],
        "failed_requests": failed,
        "windows_trained": snap["windows_trained"],
        "last_reload_step": snap["online"]["last_reload_step"],
        "windows_armed": snap["online"]["windows_armed"],
        "windows_lost": snap["online"]["windows_lost"],
        "handoffs": snap["online"]["handoffs"],
        # Per-window lineage (docs/OBSERVABILITY.md "Window lineage"):
        # which freshness phase dominated the traced windows, and how
        # many windows the tracer closed end-to-end.
        "freshness_budget_worst_phase": (
            snap["lineage"]["dominant_phase"] or "-"
        ),
        "lineage_windows": snap["lineage"]["windows_traced"],
    }


def traffic_summary(ticks: int = 10, seed: int = SEED,
                    capacity_per_tick: int = 6) -> dict:
    """Drive the seeded spike profile through an autoscaling fleet for
    `ticks` generator ticks.  Returns the dict behind the
    TRAFFIC_SUMMARY line.

    Each replica sits behind a per-tick capacity gate (the overload
    model): the in-process
    engine answers everything a sequential driver offers, so without a
    declared capacity the spike sheds nothing and the control loop
    under test never has to act."""
    import numpy as np

    from elasticdl_tpu.common.model_handler import get_model_spec
    from elasticdl_tpu.online import OnlineConfig, OnlinePipeline
    from elasticdl_tpu.proto import serving_pb2 as spb
    from elasticdl_tpu.traffic import (
        TrafficConfig,
        TrafficGenerator,
        router_request_fn,
    )
    from model_zoo.clickstream import ctr_mlp

    class _CapacityGate:
        def __init__(self, inner):
            self._inner = inner
            self.used = 0

        def reset(self):
            self.used = 0

        def predict(self, request, timeout=None):
            if self.used >= capacity_per_tick:
                response = spb.PredictResponse()
                response.code = spb.SERVING_OVERLOADED
                response.error = "per-tick capacity exhausted"
                return response
            self.used += 1
            return self._inner.predict(request, timeout=timeout)

        def health(self, request, timeout=None):
            return self._inner.health(request, timeout=timeout)

    gates = {}

    def client_wrapper(rid, inner):
        gates[rid] = _CapacityGate(inner)
        return gates[rid]

    spec = get_model_spec(
        os.path.join(_ROOT, "model_zoo"),
        "clickstream.ctr_mlp.custom_model",
    )
    cfg = OnlineConfig(
        seed=seed, window_records=64, records_per_poll=64,
        records_per_task=16, checkpoint_every_windows=1, replicas=1,
        max_serving_replicas=3, serving_up_ticks=1,
        serving_down_ticks=2, serving_scale_hold_ticks=1,
    )
    with tempfile.TemporaryDirectory() as tmp:
        pipe = OnlinePipeline(tmp, spec, cfg, client_wrapper=client_wrapper)

        def encode_fn(rows, payload_seed):
            rng = np.random.RandomState(payload_seed % (2 ** 31))
            return ctr_mlp.encode(
                rng.randint(0, cfg.source_users, rows),
                rng.randint(0, cfg.source_items, rows),
            )

        gen = TrafficGenerator(
            router_request_fn(pipe.router, encode_fn),
            TrafficConfig(
                profile="spike", base_qps=4.0, clients=2, seed=seed,
                spike_at_tick=3, spike_ticks=2, spike_factor=5.0,
            ),
        )
        for _ in range(ticks):
            for gate in gates.values():
                gate.reset()
            gen.tick()
            pipe.tick()
        traffic = gen.snapshot()
        snap = pipe.snapshot()
        pipe.shutdown()
    policy = snap["serving_policy"] or {}
    return {
        "offered_qps": traffic["offered_qps"],
        "shed_ratio": traffic["shed_ratio"],
        "scale_actions": len(policy.get("decisions", [])),
        "failed_requests": traffic["failed"],
        "fleet": policy.get("live_replicas",
                            len(snap["serving_fleet"]["replicas"])),
    }


def main() -> int:
    summary = smoke_summary()
    print(
        "ONLINE_SUMMARY train_eps={eps:.1f} qps={qps:.1f} "
        "staleness_p99_s={stale:.4f} burn={burn:.3f} "
        "windows_armed={armed} windows_lost={lost} "
        "handoffs={handoffs} "
        "freshness_budget_worst_phase={phase} "
        "lineage_windows={lineage}".format(
            eps=summary["train_eps"],
            qps=summary["qps"],
            stale=summary["staleness_p99_s"],
            burn=summary["burn"],
            armed=summary["windows_armed"],
            lost=summary["windows_lost"],
            handoffs=summary["handoffs"],
            phase=summary["freshness_budget_worst_phase"],
            lineage=summary["lineage_windows"],
        )
    )
    traffic = traffic_summary()
    print(
        "TRAFFIC_SUMMARY offered_qps={qps:.1f} shed_ratio={shed:.4f} "
        "scale_actions={actions} failed_requests={failed} "
        "fleet={fleet}".format(
            qps=traffic["offered_qps"],
            shed=traffic["shed_ratio"],
            actions=traffic["scale_actions"],
            failed=traffic["failed_requests"],
            fleet=traffic["fleet"],
        )
    )
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
