"""The program's own spans (`common/profiler.PhaseTimer.spans()`): every
timed region of the worker loop and of the prefetch producer, with its
start and end on `time.perf_counter()` (the clock of the driver's task
stamps), its thread, its task and its step (index of the batch in the
task).  Kept are the spans that lie inside the window, first task end to
last; the rule that reduces them is the metric file's `params`:

    span        the region's name (`data_wait`, `task_sync`, `read`, ...)
    step_min,   optional bounds on `step`; with either given, a span that
    step_max    belongs to no step is left out
    stat        mean_ms            mean length of the kept spans
                ms_per_task        their summed length over the window's tasks
                window_share_pct   their summed length over the window
                uncovered_share_pct  share of the window in which the thread
                                   that recorded `span` was inside NO span
    absent      what a sum reads when the window holds spans but none of
                this name (default: nothing).  A mean of no spans is nothing.

A program that keeps no spans (one older than the ring) reads as nothing.
"""


def ring():
    """The program's span records, oldest first, or None if it keeps none."""
    try:
        from elasticdl_tpu.worker.worker import _phase_timer
    except ImportError:
        return None
    spans = getattr(_phase_timer, "spans", None)
    return spans() if callable(spans) else None


def _kept(spans, params: dict) -> list:
    lo, hi = params.get("step_min"), params.get("step_max")
    kept = []
    for span in spans:
        if span.name != params["span"]:
            continue
        if lo is not None or hi is not None:
            if span.step is None:
                continue
            if lo is not None and span.step < lo:
                continue
            if hi is not None and span.step > hi:
                continue
        kept.append(span)
    return kept


def _uncovered_s(spans, thread: int, lo: float, hi: float) -> float:
    """Seconds of [lo, hi] in which `thread` was inside none of `spans`."""
    covered, edge = 0.0, lo
    for span in sorted(
        (s for s in spans if s.thread == thread), key=lambda s: s.start
    ):
        start, end = max(span.start, edge), min(span.end, hi)
        if end > start:
            covered += end - start
            edge = end
    return (hi - lo) - covered


def read(params: dict, context: dict):
    stamps = context.get("stamps")
    if not stamps or len(stamps) < 2:
        return None
    spans = ring()
    if not spans:
        return None
    lo, hi = stamps[0][0], stamps[-1][0]
    inside = [s for s in spans if s.start >= lo and s.end <= hi]
    kept = _kept(inside, params)
    stat = params["stat"]
    if not kept:
        return params.get("absent") if inside and stat != "mean_ms" else None
    seconds = sum(s.end - s.start for s in kept)
    if stat == "mean_ms":
        return 1e3 * seconds / len(kept)
    if stat == "ms_per_task":
        return 1e3 * seconds / (len(stamps) - 1)
    if stat == "window_share_pct":
        return 100.0 * seconds / (hi - lo)
    if stat == "uncovered_share_pct":
        # a span that straddles an edge of the window covers its part
        touching = [s for s in spans if s.end > lo and s.start < hi]
        return 100.0 * _uncovered_s(
            touching, kept[-1].thread, lo, hi
        ) / (hi - lo)
    raise ValueError(f"program_spans: unknown stat {stat!r}")
