"""From a profiler trace (`.xplane.pb`) to numbers.

Generalised from `scripts/profile_bert.py`'s `analyze` (listed in PERF.md
for a later PR to delete there): device busy time is the UNION of the
intervals in which an operation ran, not their sum; every idle gap is
charged to the host span the benchmark had open at the time; per-op
seconds are kept by name for the `trace_ops` reader.

`read_xplane` needs jaxlib's `ProfileData`; everything below it works on
plain tuples, so the tests run it on hand-made intervals too.
"""

from __future__ import annotations

import glob
import os

DEVICE_PLANE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
# start-to-done spans of asynchronous operations (copies, collectives);
# they overlap the operations of OPS_LINE and never count as busy time
ASYNC_LINE = "Async XLA Ops"
HOST_PLANE = "/host:CPU"
SPAN_PREFIX = "bench:"
WINDOW_SPAN = "bench:window"


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(
        os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")
    ))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def read_xplane(path: str) -> dict:
    """{"devices": {plane: [(name, start_s, end_s)]}, "spans": [...]}:
    device operations of every TPU plane and the benchmark's own host
    spans, all on the trace's one clock, in seconds."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices, asynchronous, spans = {}, {}, []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PLANE_PREFIX):
            for line in plane.lines:
                table = {OPS_LINE: devices, ASYNC_LINE: asynchronous}.get(
                    line.name
                )
                if table is None:
                    continue
                table.setdefault(plane.name, []).extend(
                    (e.name, e.start_ns * 1e-9,
                     (e.start_ns + e.duration_ns) * 1e-9)
                    for e in line.events
                )
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                spans.extend(
                    (e.name, e.start_ns * 1e-9,
                     (e.start_ns + e.duration_ns) * 1e-9)
                    for e in line.events
                    if e.name.startswith(SPAN_PREFIX)
                )
    return {"devices": devices, "async": asynchronous, "spans": spans}


def short_name(hlo_text: str, limit: int = 96) -> str:
    """`%fusion.3 = f32[8,16]{...} fusion(...)` -> `fusion.3 f32[8,16]
    fusion`: the instruction, its result shape without layouts, its
    opcode.  Anything else passes through, cut to `limit`."""
    import re

    match = re.match(r"%(\S+) = (.*?) ([\w-]+)\(", hlo_text)
    if not match:
        return hlo_text[:limit]
    name, shape, opcode = match.groups()
    shape = re.sub(r"\{[^{}]*\}", "", shape)
    return f"{name} {shape} {opcode}"[:limit]


def union_intervals(intervals):
    """Sorted, merged [(start, end)] of possibly overlapping intervals."""
    merged = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            if end > merged[-1][1]:
                merged[-1] = (merged[-1][0], end)
        else:
            merged.append((start, end))
    return merged


def clip(intervals, lo: float, hi: float):
    return [
        (max(s, lo), min(e, hi)) for s, e in intervals
        if min(e, hi) > max(s, lo)
    ]


def gaps_between(busy, lo: float, hi: float):
    """Idle intervals of [lo, hi] given merged busy intervals."""
    gaps, cursor = [], lo
    for start, end in busy:
        if start > cursor:
            gaps.append((cursor, start))
        cursor = max(cursor, end)
    if hi > cursor:
        gaps.append((cursor, hi))
    return gaps


def attribute_gap(gap, spans) -> str:
    """The innermost (shortest) benchmark span open over most of the
    gap; `no_span` when none was."""
    start, end = gap
    best, best_key = "no_span", None
    for name, s, e in spans:
        overlap = min(e, end) - max(s, start)
        if overlap <= 0 or name == WINDOW_SPAN:
            continue
        covers = overlap >= 0.5 * (end - start)
        key = (covers, -(e - s) if covers else overlap)
        if best_key is None or key > best_key:
            best, best_key = name, key
    return best


def reduce_trace(raw: dict, top: int = 10) -> dict:
    """Busy and idle seconds, per-op seconds and the longest idle gaps by
    host span.  The window is the `bench:window` span when the trace has
    one, else first operation to last.  `busy_s` is averaged over the
    devices; per-op seconds and gaps are those of the first device."""
    devices = {k: v for k, v in sorted(raw["devices"].items()) if v}
    if not devices:
        raise ValueError("the trace holds no device operation")
    window = [(s, e) for name, s, e in raw["spans"] if name == WINDOW_SPAN]
    if window:
        lo = min(s for s, _ in window)
        hi = max(e for _, e in window)
    else:
        lo = min(s for ops in devices.values() for _, s, _ in ops)
        hi = max(e for ops in devices.values() for _, _, e in ops)
    busy_by_device = {}
    for plane, ops in devices.items():
        busy = clip(union_intervals((s, e) for _, s, e in ops), lo, hi)
        busy_by_device[plane] = busy
    first = next(iter(devices))
    def seconds_by_name(events):
        table = {}
        for name, s, e in events:
            for cs, ce in clip([(s, e)], lo, hi):
                table[name] = table.get(name, 0.0) + (ce - cs)
        return table

    op_seconds = seconds_by_name(devices[first])
    async_seconds = seconds_by_name(raw.get("async", {}).get(first, []))
    gap_seconds = {}
    for gap in gaps_between(busy_by_device[first], lo, hi):
        name = attribute_gap(gap, raw["spans"])
        gap_seconds[name] = gap_seconds.get(name, 0.0) + (gap[1] - gap[0])
    busy_s = sum(
        sum(e - s for s, e in busy) for busy in busy_by_device.values()
    ) / len(busy_by_device)

    def ranked(table):
        return [
            [short_name(name), seconds] for name, seconds in
            sorted(table.items(), key=lambda kv: -kv[1])[:top]
        ]

    return {
        "window_s": hi - lo,
        "busy_s": busy_s,
        "op_seconds": op_seconds,
        "async_op_seconds": async_seconds,
        "breakdown": {
            "device_ops": ranked(op_seconds),
            "idle_gaps": ranked(gap_seconds),
        },
    }


def reduce_trace_dir(trace_dir: str) -> dict:
    return reduce_trace(read_xplane(find_xplane(trace_dir)))
