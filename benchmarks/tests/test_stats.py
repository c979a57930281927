import pytest

from benchmarks import stats


def stamps(durations, records=1000, t0=100.0):
    """Task-end stamps: the window opens at t0, then one task each."""
    out, t = [(t0, 0)], t0
    for d in durations:
        t += d
        out.append((t, records))
    return out


def test_the_rate_is_all_records_over_all_the_window():
    # every whole task counts, the slow first one too
    s = stamps([5.0, 2.0, 2.0, 2.0, 2.0])
    assert stats.window_task_rate(s) == pytest.approx(5000 / 13.0)
    # the per-layer median leaves the first task out
    assert stats.median_task_rate(s) == pytest.approx(500.0)
    assert len(stats.task_readings(s, drop_first=1)) == 4
    assert len(stats.task_readings(s)) == 5


def test_a_stalled_task_costs_the_rate_what_it_lasted():
    steady = stamps([2.0] * 11)
    stalled = stamps([2.0] * 5 + [6.0] + [2.0] * 5)
    assert stats.window_task_rate(steady) == pytest.approx(500.0)
    assert stats.window_task_rate(stalled) == pytest.approx(
        11000 / 26.0
    )
    # the median beside it says the loop itself is no slower
    assert stats.median_task_rate(stalled) == pytest.approx(
        stats.median_task_rate(steady)
    )


def test_readings_are_whole_tasks_only():
    # records / (this end - the previous end): the gap between tasks is
    # inside the reading, and there is no partial task to count
    s = [(0.0, 0), (1.0, 10), (3.0, 10), (4.0, 10)]
    assert stats.task_readings(s, drop_first=0) == [
        (10, 1.0), (10, 2.0), (10, 1.0)
    ]
    with pytest.raises(ValueError):
        stats.median_task_rate([(0.0, 0), (1.0, 10)])   # only the dropped
    with pytest.raises(ValueError):
        stats.window_task_rate([(0.0, 0)])              # no whole task
    with pytest.raises(ValueError):
        stats.task_readings([(1.0, 0), (1.0, 10)])
