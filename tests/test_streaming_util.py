"""Streaming generators + util extras (ActorPool, Queue, multiprocessing Pool).

Reference test models: python/ray/tests/test_streaming_generator.py,
test_actor_pool.py, test_queue.py, util/multiprocessing tests.
"""

import time

import pytest

import ray_tpu
from ray_tpu.util.actor_pool import ActorPool
from ray_tpu.util.multiprocessing import Pool
from ray_tpu.util.queue import Empty, Full, Queue


# ---------------- streaming generators ----------------


def test_streaming_generator_basic(ray_start_regular):
    @ray_tpu.remote(num_returns="streaming")
    def gen(n):
        for i in range(n):
            yield i * 10

    out = [ray_tpu.get(ref) for ref in gen.remote(5)]
    assert out == [0, 10, 20, 30, 40]


def test_streaming_generator_incremental(ray_start_regular):
    """Consumer sees early items while the producer is still running."""

    @ray_tpu.remote(num_returns="streaming")
    def slow_gen():
        for i in range(3):
            yield i
            time.sleep(0.3)

    start = time.monotonic()
    it = iter(gen_obj := slow_gen.remote())
    first = ray_tpu.get(next(it))
    first_latency = time.monotonic() - start
    assert first == 0
    # Got item 0 well before the full ~0.9s run completes.
    assert first_latency < 0.6
    rest = [ray_tpu.get(r) for r in it]
    assert rest == [1, 2]


def test_streaming_generator_error_mid_stream(ray_start_regular):
    @ray_tpu.remote(num_returns="streaming")
    def bad_gen():
        yield 1
        yield 2
        raise RuntimeError("boom")

    refs = list(bad_gen.remote())
    assert ray_tpu.get(refs[0]) == 1
    assert ray_tpu.get(refs[1]) == 2
    with pytest.raises(Exception, match="boom"):
        ray_tpu.get(refs[2])


def test_streaming_generator_on_actor(ray_start_regular):
    @ray_tpu.remote
    class Gen:
        @ray_tpu.method(num_returns="streaming")
        def produce(self, n):
            for i in range(n):
                yield i + 100

    g = Gen.remote()
    out = [ray_tpu.get(r) for r in g.produce.remote(3)]
    assert out == [100, 101, 102]


def test_streaming_generator_empty(ray_start_regular):
    @ray_tpu.remote(num_returns="streaming")
    def empty():
        if False:
            yield 1

    assert list(empty.remote()) == []


# ---------------- ActorPool ----------------


def test_actor_pool_map_ordered(ray_start_regular):
    @ray_tpu.remote
    class Worker:
        def double(self, x):
            return 2 * x

    pool = ActorPool([Worker.remote() for _ in range(2)])
    out = list(pool.map(lambda a, v: a.double.remote(v), range(8)))
    assert out == [0, 2, 4, 6, 8, 10, 12, 14]


def test_actor_pool_map_unordered(ray_start_regular):
    @ray_tpu.remote
    class Worker:
        def work(self, x):
            time.sleep(0.01 * (x % 3))
            return x

    pool = ActorPool([Worker.remote() for _ in range(3)])
    out = list(pool.map_unordered(lambda a, v: a.work.remote(v), range(9)))
    assert sorted(out) == list(range(9))


def test_actor_pool_submit_get_next(ray_start_regular):
    @ray_tpu.remote
    class Worker:
        def echo(self, x):
            return x

    pool = ActorPool([Worker.remote()])
    pool.submit(lambda a, v: a.echo.remote(v), "a")
    pool.submit(lambda a, v: a.echo.remote(v), "b")
    assert pool.get_next() == "a"
    assert pool.get_next() == "b"
    assert not pool.has_next()


# ---------------- Queue ----------------


def test_queue_fifo(ray_start_regular):
    q = Queue()
    for i in range(5):
        q.put(i)
    assert q.qsize() == 5
    assert [q.get() for _ in range(5)] == [0, 1, 2, 3, 4]
    assert q.empty()


def test_queue_maxsize_and_timeouts(ray_start_regular):
    q = Queue(maxsize=2)
    q.put(1)
    q.put(2)
    assert q.full()
    with pytest.raises(Full):
        q.put(3, block=False)
    with pytest.raises(Full):
        q.put(3, timeout=0.1)
    assert q.get() == 1
    q.put(3)
    with pytest.raises(Empty):
        Queue().get(timeout=0.1)


def test_queue_batch_ops(ray_start_regular):
    q = Queue()
    q.put_nowait_batch([1, 2, 3])
    assert q.get_nowait_batch(2) == [1, 2]
    with pytest.raises(Empty):
        q.get_nowait_batch(5)


def test_queue_producer_consumer_threads(ray_start_regular):
    import threading

    q = Queue(maxsize=4)
    results = []

    def producer():
        for i in range(20):
            q.put(i)

    def consumer():
        for _ in range(20):
            results.append(q.get(timeout=10))

    tp = threading.Thread(target=producer)
    tc = threading.Thread(target=consumer)
    tp.start(); tc.start()
    tp.join(timeout=30); tc.join(timeout=30)
    assert results == list(range(20))


# ---------------- multiprocessing Pool ----------------


def _square(x):
    return x * x


def test_pool_map(ray_start_regular):
    with Pool(2) as pool:
        assert pool.map(_square, range(6)) == [0, 1, 4, 9, 16, 25]


def test_pool_apply_and_async(ray_start_regular):
    with Pool(2) as pool:
        assert pool.apply(_square, (3,)) == 9
        res = pool.apply_async(_square, (4,))
        assert res.get(timeout=10) == 16


def test_pool_starmap_imap(ray_start_regular):
    def add(a, b):
        return a + b

    with Pool(2) as pool:
        assert pool.starmap(add, [(1, 2), (3, 4)]) == [3, 7]
        assert list(pool.imap(_square, range(4), chunksize=2)) == [0, 1, 4, 9]
        assert sorted(pool.imap_unordered(_square, range(4), chunksize=1)) == [
            0,
            1,
            4,
            9,
        ]


def test_streaming_generator_on_async_actor(ray_start_regular):
    """Regression: streaming methods on async actors must drive the generator."""

    @ray_tpu.remote
    class AsyncGen:
        async def ping(self):
            return "pong"

        @ray_tpu.method(num_returns="streaming")
        async def produce(self, n):
            for i in range(n):
                yield i * 2

        @ray_tpu.method(num_returns="streaming")
        def produce_sync(self, n):
            for i in range(n):
                yield i + 1

    a = AsyncGen.remote()
    assert ray_tpu.get(a.ping.remote()) == "pong"
    assert [ray_tpu.get(r) for r in a.produce.remote(3)] == [0, 2, 4]
    assert [ray_tpu.get(r) for r in a.produce_sync.remote(3)] == [1, 2, 3]


def test_actor_pool_timeout_is_retryable(ray_start_regular):
    @ray_tpu.remote
    class Slow:
        def work(self):
            time.sleep(0.5)
            return "done"

    pool = ActorPool([Slow.remote()])
    pool.submit(lambda a, v: a.work.remote(), None)
    with pytest.raises(TimeoutError):
        pool.get_next(timeout=0.05)
    # State unchanged: retry succeeds and the actor returns to the pool.
    assert pool.get_next(timeout=10) == "done"
    assert pool.has_free()


def test_actor_pool_task_error_returns_actor(ray_start_regular):
    @ray_tpu.remote
    class Flaky:
        def work(self, fail):
            if fail:
                raise ValueError("nope")
            return "ok"

    pool = ActorPool([Flaky.remote()])
    pool.submit(lambda a, v: a.work.remote(v), True)
    with pytest.raises(Exception, match="nope"):
        pool.get_next()
    pool.submit(lambda a, v: a.work.remote(v), False)
    assert pool.get_next() == "ok"


def test_streaming_generator_killed_actor_does_not_hang(ray_start_regular):
    """Killing the actor while a streaming task is queued/running must finish
    the stream with ActorDiedError, not hang the reader (regression: every
    _finalize path now closes the stream)."""
    import time

    import ray_tpu
    from ray_tpu.exceptions import ActorDiedError

    @ray_tpu.remote
    class Gen:
        def slow_stream(self):
            for i in range(100):
                time.sleep(0.05)
                yield i

    actor = Gen.options(max_restarts=0).remote()
    gen = actor.slow_stream.options(num_returns="streaming").remote()
    # Let the generator start, then kill mid-stream.
    time.sleep(0.2)
    ray_tpu.kill(actor)
    with pytest.raises(ActorDiedError):
        for _ in range(200):
            ray_tpu.get(next(gen), timeout=10.0)


# -- util.iter parallel iterators -----------------------------------------


def test_parallel_iterator_transforms(ray_start_regular):
    from ray_tpu.util import iter as par_iter

    it = (
        par_iter.from_range(20, num_shards=4)
        .for_each(lambda x: x * 2)
        .filter(lambda x: x % 4 == 0)
    )
    out = sorted(it.gather_sync())
    assert out == sorted(x * 2 for x in range(20) if (x * 2) % 4 == 0)


def test_parallel_iterator_batch_flatten(ray_start_regular):
    from ray_tpu.util import iter as par_iter

    batched = par_iter.from_items(list(range(10)), num_shards=2).batch(3)
    batches = list(batched.gather_sync())
    assert all(len(b) <= 3 for b in batches)
    flat = sorted(
        par_iter.from_items([[1, 2], [3], [4, 5]], num_shards=2)
        .flatten()
        .gather_sync()
    )
    assert flat == [1, 2, 3, 4, 5]


def test_parallel_iterator_async_and_take(ray_start_regular):
    from ray_tpu.util import iter as par_iter

    it = par_iter.from_range(100, num_shards=4).for_each(lambda x: x + 1)
    assert sorted(it.gather_async()) == list(range(1, 101))
    assert len(par_iter.from_range(50, num_shards=2).take(7)) == 7
    assert par_iter.from_range(13, num_shards=3).count() == 13


def test_parallel_iterator_from_iterators(ray_start_regular):
    from ray_tpu.util import iter as par_iter

    def make_gen(start):
        def gen():
            for i in range(3):
                yield start + i

        return gen

    it = par_iter.from_iterators([make_gen(0), make_gen(100)])
    assert sorted(it.gather_sync()) == [0, 1, 2, 100, 101, 102]


# -- small stream items travel with their refs ------------------------------
# (Runtime.report_stream_item: neither the reference counter nor the store
# hears of such an item unless its ref escapes.)


def _tables_hold(runtime, ref) -> bool:
    """Whether the store or the reference counter has an entry for the id
    (read through `_id`: taking `ref.id` is itself an escape)."""
    return bool(_held(runtime, lambda oid: oid == ref._id))


def _held(runtime, mine) -> list:
    """The ids among `mine` (a predicate on an id) that the reference
    counter or the store still has an entry for.

    What a test made, and not "the tables are empty": a worker that ran
    Serve's files before still has their routers' and controllers' threads
    (`router-*`, `serve-reconcile`; 26 and 4 of them in the driver's run),
    which long-poll whichever runtime is current, so a dozen of their refs
    come and go in both tables for as long as the process lives. One
    collection there takes 0.11 s; it was never the collector."""
    with runtime.refcount._lock:
        counted = [oid for oid in runtime.refcount._refs if mine(oid)]
    with runtime.store._lock:
        stored = [oid for oid in runtime.store._entries if mine(oid)]
    return counted + stored


def _wait_until(predicate, timeout_s=10.0):
    deadline = time.monotonic() + timeout_s
    while not predicate():
        assert time.monotonic() < deadline, "condition not reached"
        time.sleep(0.02)


def _small_items(n=3):
    @ray_tpu.remote(num_returns="streaming")
    def gen(n):
        for i in range(n):
            yield {"i": i, "payload": [i, i]}

    return list(gen.remote(n))


def _read_by_get(ref):
    return ray_tpu.get(ref)


def _read_by_await(ref):
    import asyncio

    async def read():
        return await ref

    return asyncio.run(read())


def _read_by_future(ref):
    fut = ref.future()
    assert fut.done()  # filled by the caller's own thread
    return fut.result()


def _read_in_a_mixed_list(ref):
    sealed = ray_tpu.put("sealed")
    assert ray_tpu.get([sealed, ref, sealed])[::2] == ["sealed", "sealed"]
    return ray_tpu.get([ref, sealed])[0]


def _read_after_wait(ref):
    sealed = ray_tpu.put("sealed")
    ready, rest = ray_tpu.wait([ref, sealed], num_returns=2, timeout=5)
    assert ready == [ref, sealed] and rest == []
    ready, rest = ray_tpu.wait([sealed, ref], num_returns=1, timeout=5)
    assert len(ready) == 1 and len(rest) == 1
    ready, rest = ray_tpu.wait([ref], num_returns=1, timeout=0)
    assert ready == [ref] and rest == []
    return ray_tpu.get(ready[0])


@pytest.mark.parametrize(
    "read",
    [
        _read_by_get,
        _read_by_await,
        _read_by_future,
        _read_in_a_mixed_list,
        _read_after_wait,
    ],
)
def test_small_stream_item_is_read_from_its_ref(ray_start_regular, read):
    runtime = ray_start_regular
    refs = _small_items(3)
    assert runtime.stream_items_reported == 3
    assert runtime.stream_items_inline == 3
    assert read(refs[1]) == {"i": 1, "payload": [1, 1]}
    assert runtime.stream_items_promoted == 0
    assert not any(_tables_hold(runtime, ref) for ref in refs)


def test_small_stream_item_two_gets_do_not_alias(ray_start_regular):
    (ref,) = _small_items(1)
    first, second = ray_tpu.get(ref), ray_tpu.get(ref)
    assert first == second == {"i": 0, "payload": [0, 0]}
    assert first is not second
    assert first["payload"] is not second["payload"]
    first["payload"].append("mutated")
    assert ray_tpu.get([ref])[0] == {"i": 0, "payload": [0, 0]}


def _escape_as_task_argument(ref):
    @ray_tpu.remote
    def receive(item):
        return item

    return ray_tpu.get(receive.remote(ref))


def _escape_as_task_return(ref):
    @ray_tpu.remote
    def pass_on(box):
        return box[0]  # the ref itself, sealed into the task's return object

    return ray_tpu.get(ray_tpu.get(pass_on.remote([ref])))


def _escape_inside_a_put(ref):
    outer = ray_tpu.put({"inner": ref})
    return ray_tpu.get(ray_tpu.get(outer)["inner"])


def _escape_by_pickle(ref):
    import pickle

    return ray_tpu.get(pickle.loads(pickle.dumps(ref)))


def _escape_by_copy(ref):
    import copy

    # A copy does not share the carried value: it goes through __reduce__,
    # so it promotes, and both handles are counted.
    twin, deep = copy.copy(ref), copy.deepcopy(ref)
    assert twin == ref and deep == ref and twin is not ref
    assert ray_tpu.get(deep) == ray_tpu.get(ref)
    return ray_tpu.get(twin)


def _escape_by_its_id(ref):
    from ray_tpu._private.runtime import get_runtime

    return get_runtime().store.get(ref.id, timeout=5)


@pytest.mark.parametrize(
    "escape",
    [
        _escape_as_task_argument,
        _escape_as_task_return,
        _escape_inside_a_put,
        _escape_by_pickle,
        _escape_by_copy,
        _escape_by_its_id,
    ],
)
def test_small_stream_item_is_promoted_once_when_it_escapes(
    ray_start_regular, escape
):
    import gc

    runtime = ray_start_regular
    refs = _small_items(3)
    ref = refs[1]
    assert escape(ref) == {"i": 1, "payload": [1, 1]}
    assert runtime.stream_items_promoted == 1
    # From then on an ordinary object: sealed, counted, readable as before.
    assert runtime.store.contains(ref._id)
    assert runtime.refcount.counts(ref._id)[0] >= 1
    assert ray_tpu.get(ref) == {"i": 1, "payload": [1, 1]}
    assert escape(ref) == {"i": 1, "payload": [1, 1]}
    assert runtime.stream_items_promoted == 1
    assert not _tables_hold(runtime, refs[0])
    assert not _tables_hold(runtime, refs[2])
    # ... and collected like one when its handles die: one collection for
    # what the escape left in cycles, then nothing is held for the stream's
    # ids (the task that took the ref gives its count back on its own
    # thread, a moment after its result is readable).
    oid, made = ref._id, ref.task_id()
    del ref, refs
    gc.collect()
    _wait_until(lambda: not _held(runtime, lambda o: o.task_id == made))
    assert runtime.refcount.counts(oid) == (0, 0)
    assert not runtime.store.contains(oid)


def test_small_stream_items_leave_nothing_behind(ray_start_regular):
    """1,000 items consumed and dropped, some freed, some never read: the
    reference counter's and the store's tables end up with nothing of the
    stream's: no item, not its completion object, no output set."""
    import gc

    from ray_tpu.exceptions import ObjectFreedError

    runtime = ray_start_regular

    @ray_tpu.remote(num_returns="streaming")
    def gen(n):
        for i in range(n):
            yield i

    stream = gen.remote(1000)
    total = 0
    for i, ref in enumerate(stream):
        if i % 100 == 7:
            # Freed before any read: the id goes to the store, which forgets
            # the value; the entry goes when the handle does.
            runtime.store.free([ref.id])
            with pytest.raises(ObjectFreedError):
                ray_tpu.get(ref)
        elif i % 2:
            total += ray_tpu.get(ref)
        # else: dies unread
    made = ref.task_id()
    del ref
    assert total == sum(i for i in range(1000) if i % 2 and i % 100 != 7)
    assert runtime.stream_items_inline == 1000
    assert runtime.stream_items_promoted == 10
    assert _held(runtime, lambda o: o.task_id == made)  # the completion object
    del stream  # its handle
    gc.collect()
    _wait_until(lambda: not _held(runtime, lambda o: o.task_id == made))
    assert made not in runtime.refcount._task_outputs


def test_error_and_large_stream_items_are_sealed(ray_start_regular):
    """What does not travel with its ref goes the way every object goes: an
    error (it must surface at its item), and a value over
    `max_direct_call_object_size`."""
    runtime = ray_start_regular
    limit = runtime.config.max_direct_call_object_size

    @ray_tpu.remote(num_returns="streaming")
    def gen():
        yield b"x" * (limit // 2)
        yield b"y" * (limit + 1)
        raise RuntimeError("boom")

    small, large, failed = list(gen.remote())
    assert runtime.stream_items_reported == 3
    assert runtime.stream_items_inline == 1
    assert not _tables_hold(runtime, small)
    assert runtime.store.contains(large._id) and runtime.store.contains(failed._id)
    assert runtime.refcount.counts(large._id) == (1, 0)
    assert ray_tpu.get(small) == b"x" * (limit // 2)
    assert ray_tpu.get(large) == b"y" * (limit + 1)
    with pytest.raises(Exception, match="boom"):
        ray_tpu.get(failed)
    assert runtime.stream_items_promoted == 0


def test_stream_item_holding_a_ref_keeps_it_alive(ray_start_regular):
    """A carried value pins the refs pickled inside it, as a store entry
    pins its nested refs."""
    import gc

    runtime = ray_start_regular

    @ray_tpu.remote(num_returns="streaming")
    def gen():
        yield {"inner": ray_tpu.put("kept")}

    (ref,) = list(gen.remote())
    gc.collect()
    assert not _tables_hold(runtime, ref)
    inner = ray_tpu.get(ref)["inner"].id
    assert runtime.refcount.counts(inner)[0] >= 1
    assert ray_tpu.get(ray_tpu.get(ref)["inner"]) == "kept"
    del ref
    gc.collect()
    _wait_until(lambda: not _held(runtime, lambda o: o == inner))


def test_concurrent_streams_stay_off_the_shared_tables(ray_start_regular):
    """The convoy's regression test, on counts and not on time. 32 streams
    of 200 small items from one actor whose producer thread stays busy,
    each consumed by a thread of its own: at no sample does the reference
    counter or the store hold an item's id (every thread of the process
    used to queue on those two locks, seven trips an item), and the
    counter's per-task output sets do not grow with the items."""
    import queue
    import sys
    import threading

    runtime = ray_start_regular
    streams, items = 32, 200

    @ray_tpu.remote
    class Producer:
        def __init__(self, streams, items):
            self._queues = [queue.Queue() for _ in range(streams)]
            self._items = items
            threading.Thread(target=self._produce, daemon=True).start()

        def _produce(self):
            for i in range(self._items):
                sum(range(2000))  # the interpreter stays busy between items
                for q in self._queues:
                    q.put(i)

        @ray_tpu.method(num_returns="streaming")
        def stream(self, lane):
            for _ in range(self._items):
                yield {"token_id": self._queues[lane].get(timeout=30)}

    producer = Producer.options(max_concurrency=streams + 1).remote(streams, items)
    gens = [producer.stream.remote(lane) for lane in range(streams)]
    tasks = {gen._task_id for gen in gens}
    completions = {gen._completion_ref._id for gen in gens}
    received = [[] for _ in range(streams)]

    def consume(lane):
        for ref in gens[lane]:
            received[lane].append(ray_tpu.get(ref)["token_id"])

    seen = {"samples": 0, "item_ids": 0, "largest_output_set": 0}
    done = threading.Event()

    def sample():
        while not done.is_set():
            with runtime.refcount._lock:
                counted = list(runtime.refcount._refs)
                outputs = [
                    len(runtime.refcount._task_outputs.get(t, ())) for t in tasks
                ]
            with runtime.store._lock:
                stored = list(runtime.store._entries)
            seen["item_ids"] += sum(
                1
                for oid in counted + stored
                if oid.task_id in tasks and oid not in completions
            )
            seen["largest_output_set"] = max(seen["largest_output_set"], *outputs)
            seen["samples"] += 1
            time.sleep(0.005)

    sampler = threading.Thread(target=sample)
    consumers = [
        threading.Thread(target=consume, args=(lane,)) for lane in range(streams)
    ]
    # Threads change places often: an update of the plain-int counters that
    # could be lost would be, and the exact counts below would miss it.
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-4)
    try:
        sampler.start()
        for thread in consumers:
            thread.start()
        for thread in consumers:
            thread.join(timeout=120)
        done.set()
        sampler.join(timeout=30)
    finally:
        sys.setswitchinterval(interval)
    assert not any(thread.is_alive() for thread in consumers + [sampler])
    assert all(lane == list(range(items)) for lane in received)
    assert runtime.stream_items_reported == streams * items
    assert runtime.stream_items_inline == streams * items
    assert runtime.stream_items_promoted == 0
    assert seen["samples"] > 5
    assert seen["item_ids"] == 0
    assert seen["largest_output_set"] <= 1  # the completion object alone
