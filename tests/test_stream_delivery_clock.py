"""A stream times each item from the thread that offers it to the thread that
takes it (`ObjectRefStream.offer` / `next`, `Runtime.stream_delivery`).

  * a consumer that comes late reads its lateness as the item's wait, whether
    it is parked in `next()` or asks with `timeout=0` after `on_ready`;
  * `stream_delivery()` reads a stream the same open as closed, groups by the
    producing task's name, and says how many items are waiting;
  * a stream whose consumer let go of it retires once its producer ends, and
    what it held is dropped, not waiting;
  * an item's way takes no process-wide lock and writes no new attribute of
    the runtime;
  * one `stream.deliver` span a stream, in the producing task's trace.
"""

import gc
import queue
import threading
import time

import pytest

import ray_tpu
from ray_tpu._private.runtime import Runtime
from ray_tpu._private.streaming import _SENTINEL
from ray_tpu.util import tracing


def _wait_until(condition, timeout=30.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if condition():
            return
        time.sleep(0.01)
    raise AssertionError("condition never held")


def _take_blocking(stream):
    return stream.next()


def _take_on_ready(stream):
    """As `serve/handle.py` does: wait for the producer's callback, then ask
    with timeout=0."""
    woken = threading.Event()
    stream.on_ready(woken.set)
    assert woken.wait(timeout=30)
    return stream.next(timeout=0)


@pytest.mark.parametrize(
    "take", (_take_blocking, _take_on_ready), ids=("blocking", "on_ready")
)
def test_a_late_consumer_reads_its_lateness_as_the_items_wait(
    ray_start_regular, take
):
    @ray_tpu.remote(num_returns="streaming")
    def gen():
        yield 1
        yield 2

    handle = gen.remote()
    stream = handle._stream
    _wait_until(lambda: stream.items_offered == 2)
    time.sleep(0.05)
    first = take(stream)
    assert ray_tpu.get(first) == 1
    assert stream.items_taken == 1
    assert stream.wait_s >= 0.05 and stream.wait_max_s >= 0.05
    after_first = stream.wait_s
    second = take(stream)
    assert ray_tpu.get(second) == 2
    # The second item waited as long and a little longer.
    assert stream.wait_s >= 2 * 0.05 and stream.wait_s > after_first
    assert stream.wait_max_s >= stream.wait_s / 2
    assert stream.next() is _SENTINEL
    assert (stream.items_offered, stream.items_taken) == (2, 2)


def test_an_item_taken_at_once_waits_next_to_nothing(ray_start_regular):
    gate = queue.Queue()

    @ray_tpu.remote
    class Producer:
        @ray_tpu.method(num_returns="streaming")
        def stream(self):
            for _ in range(3):
                yield gate.get(timeout=30)

    handle = Producer.remote().stream.remote()
    got = []
    reader = threading.Thread(
        target=lambda: got.extend(ray_tpu.get(ref) for ref in handle)
    )
    reader.start()  # parked in next() before anything is offered
    for i in range(3):
        time.sleep(0.02)
        gate.put(i)
    reader.join(timeout=30)
    assert not reader.is_alive() and got == [0, 1, 2]
    group = ray_start_regular.stream_delivery()["Producer.stream"]
    assert group["items_taken"] == 3
    # Each was taken by a thread already waiting: no 20 ms in any wait.
    assert group["wait_max_s"] < 0.02


def test_delivery_reads_a_stream_the_same_open_as_closed(ray_start_regular):
    """Requests of the long-answer cell outlive a window, so a total over
    closed streams only would miss them: live and retired are one sum."""
    runtime = ray_start_regular
    gate = queue.Queue()

    @ray_tpu.remote
    class Producer:
        @ray_tpu.method(num_returns="streaming")
        def tokens(self, n):
            for i in range(n):
                yield gate.get(timeout=30)

        @ray_tpu.method(num_returns="streaming")
        def other(self):
            yield "x"

    producer = Producer.options(max_concurrency=4).remote()
    assert runtime.stream_delivery() == {}
    handle = producer.tokens.remote(5)
    for i in range(3):
        gate.put(i)
    _wait_until(lambda: handle._stream.items_offered == 3)
    assert ray_tpu.get(next(handle)) == 0
    open_now = runtime.stream_delivery()
    assert set(open_now) == {"Producer.tokens"}
    group = open_now["Producer.tokens"]
    assert group["streams"] == 1
    assert (group["items_offered"], group["items_taken"]) == (3, 1)
    assert group["items_offered"] - group["items_taken"] == len(
        handle._stream._items
    )
    assert group["wait_s"] == handle._stream.wait_s > 0.0
    assert handle._task_id in runtime._streams
    # A second producer method is a group of its own.
    assert [ray_tpu.get(ref) for ref in producer.other.remote()] == ["x"]
    assert runtime.stream_delivery()["Producer.other"]["items_taken"] == 1
    # Drain and close: the stream leaves `_streams`, and the group reads
    # what the open stream read plus what was taken since.
    gate.put(3)
    gate.put(4)
    assert [ray_tpu.get(ref) for ref in handle] == [1, 2, 3, 4]
    live_wait = handle._stream.wait_s
    _wait_until(lambda: handle._task_id not in runtime._streams)
    closed = runtime.stream_delivery()["Producer.tokens"]
    assert closed == {
        "streams": 1,
        "items_offered": 5,
        "items_taken": 5,
        "items_dropped": 0,
        "wait_s": live_wait,
        "wait_max_s": handle._stream.wait_max_s,
    }
    assert closed["wait_s"] >= group["wait_s"]
    # A second stream of the same method adds to the same group.
    for i in range(2):
        gate.put(i)
    assert len(list(producer.tokens.remote(2))) == 2
    _wait_until(lambda: not runtime._streams)
    again = runtime.stream_delivery()["Producer.tokens"]
    assert (again["streams"], again["items_taken"]) == (2, 7)


def test_a_stream_nobody_reads_any_more_retires_with_its_producer(
    ray_start_regular,
):
    runtime = ray_start_regular
    gate = queue.Queue()

    @ray_tpu.remote
    class Producer:
        @ray_tpu.method(num_returns="streaming")
        def tokens(self):
            for i in range(4):
                yield i
            gate.get(timeout=30)

    handle = Producer.remote().tokens.remote()
    assert ray_tpu.get(next(handle)) == 0
    stream, task_id = handle._stream, handle._task_id
    _wait_until(lambda: stream.items_offered == 4)
    del handle
    gc.collect()
    # Abandoned with its producer still running: listed, its three items
    # dropped and not waiting.
    group = runtime.stream_delivery()["Producer.tokens"]
    assert task_id in runtime._streams
    assert (group["items_offered"], group["items_taken"]) == (4, 1)
    assert group["items_dropped"] == 3
    gate.put(None)
    _wait_until(lambda: task_id not in runtime._streams)
    assert runtime.stream_delivery()["Producer.tokens"] == {
        **group, "wait_s": stream.wait_s, "wait_max_s": stream.wait_max_s,
    }


def test_an_items_way_takes_no_shared_lock_and_writes_no_new_attribute(
    ray_start_regular, monkeypatch
):
    """PR 36 took three process-wide locks out of an item's way; the clock
    puts none back. With the runtime's lock, the reference counter's and the
    store's all held by this thread, items are still reported, timed, taken
    and read, and the only attributes of the runtime an item writes are the
    two counts it wrote before."""
    runtime = ray_start_regular
    gate = queue.Queue()
    items = 50

    @ray_tpu.remote
    class Producer:
        @ray_tpu.method(num_returns="streaming")
        def tokens(self):
            yield "first"
            assert gate.get(timeout=30) == "go"
            for i in range(items):
                yield {"token_id": i}
            assert gate.get(timeout=30) == "end"

    handle = Producer.remote().tokens.remote()
    assert ray_tpu.get(next(handle)) == "first"  # the task runs, lockless now
    received = []

    def consume():
        for _ in range(items):
            received.append(ray_tpu.get(next(handle))["token_id"])

    written = set()
    plain_setattr = Runtime.__setattr__

    def recording_setattr(self, name, value):
        written.add(name)
        plain_setattr(self, name, value)

    consumer = threading.Thread(target=consume)
    locks = (runtime._lock, runtime.refcount._lock, runtime.store._lock)
    for lock in locks:
        assert lock.acquire(timeout=30)
    try:
        monkeypatch.setattr(Runtime, "__setattr__", recording_setattr)
        consumer.start()
        gate.put("go")
        consumer.join(timeout=30)
        monkeypatch.undo()
        finished_under_the_locks = not consumer.is_alive()
        stream = handle._stream
        counted = (stream.items_offered, stream.items_taken)
    finally:
        for lock in reversed(locks):
            lock.release()
    gate.put("end")
    consumer.join(timeout=30)
    assert finished_under_the_locks
    assert received == list(range(items))
    assert counted == (items + 1, items + 1)
    assert written <= {"stream_items_reported", "stream_items_inline"}
    assert stream.wait_s > 0.0
    assert list(handle) == []


def test_one_deliver_span_a_stream_in_the_producers_trace(ray_start_regular):
    @ray_tpu.remote(num_returns="streaming")
    def gen(n):
        for i in range(n):
            yield i

    with tracing.span("caller") as root:
        handle = gen.remote(3)
        task_span = tracing.task_span_id(handle._task_id)
        time.sleep(0.03)
        assert [ray_tpu.get(ref) for ref in handle] == [0, 1, 2]
    _wait_until(lambda: not ray_start_regular._streams)
    rows = tracing.traces(trace_id=root.trace_id)
    (deliver,) = [r for r in rows if r["name"] == "stream.deliver"]
    assert deliver["parent_span_id"] == task_span
    attributes = deliver["attributes"]
    assert attributes["producer"] == "gen" or attributes["producer"].endswith(
        ".gen"
    )
    assert attributes["items"] == 3
    assert attributes["wait_s"] >= attributes["wait_max_s"] > 0.0
    assert deliver["start_s"] <= deliver["end_s"]
    # None a token: a second stream adds one span, not three.
    with tracing.span("caller") as root:
        assert len(list(gen.remote(5))) == 5
    _wait_until(lambda: not ray_start_regular._streams)
    rows = tracing.traces(trace_id=root.trace_id)
    assert len([r for r in rows if r["name"] == "stream.deliver"]) == 1
