"""The arithmetic behind every end-to-end number.  Pure Python on plain
lists, so the tests check it on synthetic stamps and a later PR cannot
change what a metric means without editing this file."""

from __future__ import annotations

import statistics


def task_readings(stamps, drop_first: int = 0):
    """`stamps`: [(t_end_seconds, records)] of consecutive task ends, the
    first being the end of the last warm-up task, where the window opens
    (records ignored).  One reading a whole task: records / (this end -
    the previous end), so the gap between tasks (report, lease, first
    read) is inside the reading.  `drop_first` readings can be left out
    of a per-layer statistic; the end-to-end rate drops none."""
    readings = []
    for (t_prev, _), (t_end, records) in zip(stamps, stamps[1:]):
        if t_end <= t_prev:
            raise ValueError("task stamps must increase")
        readings.append((records, t_end - t_prev))
    return readings[drop_first:]


def window_task_rate(stamps) -> float:
    """THE end-to-end rate: all records of all whole tasks of the window
    over all its time, from the stamp that opens it to the last task end.
    A stall, a flush or a pause anywhere in the window costs what it
    lasted; both ends are synchronised stamps, so no partial step or
    task is counted and nothing is quantised."""
    readings = task_readings(stamps)
    if not readings:
        raise ValueError("no whole task inside the window")
    return sum(r for r, _ in readings) / sum(s for _, s in readings)


def median_task_rate(stamps, drop_first: int = 1) -> float:
    """Per-layer: median of the per-task rates, the first task left out.
    It is the worker loop's steady pace: a stalled task moves it by one
    rank, so read beside `window_task_rate` it says whether a loss is a
    slower loop or a stall."""
    readings = task_readings(stamps, drop_first)
    if not readings:
        raise ValueError("no whole task past the dropped ones")
    return statistics.median(r / s for r, s in readings)
