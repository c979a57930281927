import os

import pytest

from benchmarks import trace_reduce
from benchmarks.readers import trace_ops

RECORDED = os.path.join(os.path.dirname(__file__), "data",
                        "small.xplane.pb")


def test_union_counts_overlap_once():
    merged = trace_reduce.union_intervals(
        [(0.0, 1.0), (0.5, 2.0), (3.0, 4.0), (3.2, 3.5)]
    )
    assert merged == [(0.0, 2.0), (3.0, 4.0)]
    assert trace_reduce.gaps_between(merged, -1.0, 5.0) == [
        (-1.0, 0.0), (2.0, 3.0), (4.0, 5.0)
    ]
    assert trace_reduce.clip(merged, 1.5, 3.5) == [(1.5, 2.0), (3.0, 3.5)]


def test_synthetic_trace_busy_idle_and_attribution():
    raw = {
        "devices": {
            "/device:TPU:0": [
                ("%a = f32[8]{0} fusion(", 1.0, 2.0),
                ("%b = f32[8]{0} fusion(", 1.5, 2.5),   # overlaps a
                ("%a = f32[8]{0} fusion(", 4.0, 5.0),
            ],
            "/device:TPU:1": [("%a = f32[8]{0} fusion(", 1.0, 2.0)],
        },
        "spans": [
            ("bench:window", 0.0, 6.0),
            ("bench:task", 0.0, 6.0),
            ("bench:train_batch", 2.4, 4.1),   # innermost over 2.5..4.0
        ],
    }
    out = trace_reduce.reduce_trace(raw)
    assert out["window_s"] == pytest.approx(6.0)
    # device 0 busy 1..2.5 and 4..5 = 2.5 s; device 1 busy 1 s; mean 1.75
    assert out["busy_s"] == pytest.approx(1.75)
    assert out["op_seconds"]["%a = f32[8]{0} fusion("] == pytest.approx(2.0)
    gaps = dict(out["breakdown"]["idle_gaps"])
    assert gaps["bench:train_batch"] == pytest.approx(1.5)
    assert gaps["bench:task"] == pytest.approx(2.0)     # 0..1 and 5..6
    assert out["breakdown"]["device_ops"][0] == ["a f32[8] fusion", 2.0]
    with pytest.raises(ValueError):
        trace_reduce.reduce_trace({"devices": {}, "spans": []})


def test_recorded_chip_trace():
    """Three steps of matmul + scatter-add with 20 ms sleeps between,
    recorded on a v5e by tests/data/record_trace.py."""
    raw = trace_reduce.read_xplane(RECORDED)
    assert list(raw["devices"]) == ["/device:TPU:0"]
    names = {name for name, _, _ in raw["spans"]}
    assert {"bench:window", "bench:step", "bench:sleep"} <= names
    out = trace_reduce.reduce_trace(raw)
    # the window holds three 20 ms sleeps and three sub-millisecond steps
    assert 0.060 < out["window_s"] < 0.080
    assert 0 < out["busy_s"] < 0.001
    assert out["busy_s"] <= sum(out["op_seconds"].values()) + 1e-9
    gaps = dict(out["breakdown"]["idle_gaps"])
    assert gaps["bench:sleep"] > 0.055          # idle is the sleeping
    assert len(out["breakdown"]["device_ops"]) <= 10
    # per-step division and the shape rule, as the layer metrics use them
    cell = type("Cell", (), {"config": {"rows": 65536}})()
    context = {"trace": out, "trace_steps": 3, "cell": cell}
    scatter = trace_ops.read(
        {"stat": "ops_ms_per_step",
         "include": [r"^%\S+ = f32\[{rows},16\]"]}, context,
    )
    busy = trace_ops.read({"stat": "busy_ms_per_step"}, context)
    assert 0 < scatter < busy == pytest.approx(1e3 * out["busy_s"] / 3)
    idle = trace_ops.read({"stat": "idle_share_pct"}, context)
    assert 98.0 < idle < 100.0
    assert trace_ops.read(
        {"stat": "ops_ms_per_step", "include": ["no-such-op"]}, context
    ) is None


def test_short_name():
    text = ("%fusion.3 = f32[33554432,16]{0,1:T(8,128)} fusion(f32[1]{0} "
            "%p), kind=kLoop")
    assert trace_reduce.short_name(text) == (
        "fusion.3 f32[33554432,16] fusion"
    )
    assert trace_reduce.short_name("jit_step(1)") == "jit_step(1)"
