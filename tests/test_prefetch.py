"""prefetch_batches: background host pipeline (read+parse) overlapping
the consumer's device work — order-preserving, exception-transparent,
and abandonment-safe."""

import threading
import time

import pytest

from elasticdl_tpu.worker.task_data_service import prefetch_batches


def test_order_preserved():
    assert list(prefetch_batches(iter(range(100)))) == list(range(100))


def test_exception_propagates():
    def gen():
        yield 1
        yield 2
        raise ValueError("reader died")

    out = []
    with pytest.raises(ValueError, match="reader died"):
        for item in prefetch_batches(gen()):
            out.append(item)
    assert out == [1, 2]


def test_abandonment_stops_producer():
    produced = []

    def gen():
        for i in range(1000):
            produced.append(i)
            yield i

    before = set(threading.enumerate())
    it = prefetch_batches(gen(), depth=2)
    assert next(it) == 0
    producer_threads = [
        t for t in threading.enumerate() if t not in before
    ]
    assert len(producer_threads) == 1
    it.close()  # consumer walks away mid-stream
    count_at_close = len(produced)
    # the SPECIFIC producer thread must exit (not merely be a daemon):
    # a producer wedged on a full queue would hold the reader forever
    producer_threads[0].join(timeout=5.0)
    assert not producer_threads[0].is_alive()
    # and it stopped producing: at most the in-flight buffer after close
    assert len(produced) <= count_at_close + 3


def test_overlap_actually_happens():
    """Producer runs ahead while the consumer is slow: with depth=2 the
    producer should have items ready the moment the consumer asks."""
    timestamps = []

    def gen():
        for i in range(5):
            timestamps.append(("produced", i, time.perf_counter()))
            yield i

    consumed = []
    for item in prefetch_batches(gen(), depth=2):
        time.sleep(0.05)  # slow consumer (the "device step")
        consumed.append((item, time.perf_counter()))
    # by the time the consumer finished item 0, the producer had already
    # produced items beyond it (ran ahead into the buffer)
    produced_before_first_consume = [
        i for kind, i, ts in timestamps if ts < consumed[0][1]
    ]
    assert len(produced_before_first_consume) >= 2


def test_device_stage_runs_on_consumer_thread_and_preserves_order():
    """The staging hook (double-buffered H2D overlap) must run on the
    CONSUMER's thread — the single-device-thread rule
    (scripts/check_host_device_boundary.py) — and must not reorder or
    drop items."""
    consumer = threading.current_thread()
    staged_on = []

    def stage(item):
        staged_on.append(threading.current_thread())
        return ("staged", item)

    out = list(prefetch_batches(iter(range(20)), device_stage=stage))
    assert out == [("staged", i) for i in range(20)]
    assert set(staged_on) == {consumer}


@pytest.mark.parametrize("producer", ["ahead", "behind"])
def test_device_stage_runs_ahead_of_consumption(producer):
    """The staging rule (device_depth=1).  Producer AHEAD: the hook
    stages item N+1 while the consumer holds item N, so at the moment
    the FIRST item is yielded the second is already staged (the double
    buffer).  Producer BEHIND: a staged item is never held back for the
    next one's read, so the first item is yielded while the second has
    not been produced, and the rest follow in order once it is."""
    staged = []
    produced = []
    in_queue = threading.Event()   # the queue holds item 1
    release = threading.Event()    # the reader may go past item 0

    def stage(item):
        if producer == "ahead" and item == 0:
            assert in_queue.wait(5.0)
        staged.append(item)
        return item

    def gen():
        for i in range(5):
            if producer == "behind" and i == 1:
                assert release.wait(5.0)
            produced.append(i)
            yield i
            if i == 1:
                # the producer asks for item 2 once item 1 is queued
                in_queue.set()

    it = prefetch_batches(gen(), device_stage=stage, device_depth=1)
    first = next(it)
    assert first == 0
    if producer == "ahead":
        assert staged == [0, 1]  # second transfer already issued
    else:
        assert staged == [0] and produced == [0]
        release.set()
    assert list(it) == [1, 2, 3, 4]
    assert staged == [0, 1, 2, 3, 4]


def test_device_stage_error_propagates_and_stops_producer():
    """A transfer failure (bad shapes, device OOM) must surface to the
    consumer as the original exception — not wedge the pipeline — and
    the producer thread must exit."""
    produced = []

    def gen():
        for i in range(1000):
            produced.append(i)
            yield i

    def stage(item):
        if item == 3:
            raise RuntimeError("transfer failed")
        return item

    before = set(threading.enumerate())
    out = []
    with pytest.raises(RuntimeError, match="transfer failed"):
        for item in prefetch_batches(gen(), device_stage=stage):
            out.append(item)
    assert out == [0, 1, 2]
    for t in threading.enumerate():
        if t not in before:
            t.join(timeout=5.0)
            assert not t.is_alive()


def test_reader_error_propagates_through_staged_pipeline():
    """Reader-side failure with staging active: items staged before the
    failure still arrive, then the reader's exception surfaces."""
    def gen():
        yield 1
        yield 2
        raise ValueError("reader died")

    out = []
    with pytest.raises(ValueError, match="reader died"):
        for item in prefetch_batches(gen(), device_stage=lambda x: x):
            out.append(item)
    assert out == [1, 2]
