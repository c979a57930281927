"""The train driver's task-window state machine on fakes: no jax."""

import threading
import time

import pytest

from benchmarks.drivers import train
from benchmarks.manifest import BenchmarkError

TRAFFIC = {"records_per_task": 16, "minibatch_size": 4,
           "warmup_tasks_after_compile": 1, "trace_tasks": 2}


class FakeWorker:
    def __init__(self):
        self.losses = list(range(100))
        self.stopped = False

    def drain_and_stop(self):
        self.stopped = True


class FakeMaster:
    aborted = None

    def _on_job_abort(self, reason):
        self.aborted = reason


@pytest.fixture
def window(monkeypatch):
    counters = {"compiles": 0, "steps": 0, "failed": 0}
    monkeypatch.setattr(train, "compiles_so_far",
                        lambda: counters["compiles"])
    monkeypatch.setattr(
        train, "registry_value",
        lambda name, **labels: counters["failed"] if labels
        else counters["steps"],
    )
    monkeypatch.setattr(train, "phase_totals", lambda: {"data_wait": 0.0})
    monkeypatch.setattr(train, "live_bytes", lambda: [0])
    w = train.TaskWindow(seconds=0.05, trace=False, traffic=TRAFFIC,
                         trace_dir="unused")
    w.worker, w.master = FakeWorker(), FakeMaster()
    w.counters = counters
    return w


def end_task(window, records=16):
    window.on_task_start(None)
    window.counters["steps"] += 4
    window.on_task_end(None, records)


def test_warm_up_then_window_then_close_at_a_task_boundary(window):
    end_task(window)                      # nothing compiled yet: warm-up
    assert window.phase == train.WARMUP and not window.stamps
    window.counters["compiles"] = 1
    end_task(window)                      # the task that compiled
    assert window.phase == train.WARMUP
    end_task(window)                      # one whole task after it
    assert window.phase == train.WINDOW
    assert len(window.stamps) == 1 and window.stamps[0][1] == 0
    end_task(window)
    assert window.phase == train.WINDOW and len(window.stamps) == 2
    assert len(window.losses) == 4        # the task's four steps
    time.sleep(0.06)
    end_task(window)                      # first boundary past --seconds
    assert window.phase == train.DONE
    assert window.worker.stopped and window.master.aborted
    assert len(window.stamps) == 3 and len(window.gaps) == 2
    assert window.at_close["steps"] - window.at_open["steps"] == 8


def test_watchdog_ends_a_job_whose_task_failed(window):
    thread = threading.Thread(target=window.watch, args=(60.0,))
    thread.start()
    window.counters["failed"] = 1
    thread.join(timeout=5)
    assert not thread.is_alive()
    assert isinstance(window.error, BenchmarkError)
    assert "task failed" in str(window.error)
    assert window.worker.stopped and "benchmark" in window.master.aborted


def test_watchdog_ends_a_warm_up_that_never_finishes(window):
    thread = threading.Thread(target=window.watch, args=(0.2,))
    thread.start()
    thread.join(timeout=5)
    assert not thread.is_alive()
    assert "warm-up took more than" in str(window.error)
