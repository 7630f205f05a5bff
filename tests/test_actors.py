"""Actor tests (reference scope: python/ray/tests/test_actor.py,
test_actor_failures.py, test_async_actor)."""

import time

import pytest

import ray_tpu
from ray_tpu.exceptions import ActorDiedError


@ray_tpu.remote
class Counter:
    def __init__(self, start=0):
        self.n = start

    def inc(self, by=1):
        self.n += by
        return self.n

    def read(self):
        return self.n


def test_actor_basic(ray_start_regular):
    c = Counter.remote()
    assert ray_tpu.get(c.inc.remote()) == 1
    assert ray_tpu.get(c.inc.remote(5)) == 6
    assert ray_tpu.get(c.read.remote()) == 6


def test_actor_constructor_args(ray_start_regular):
    c = Counter.remote(100)
    assert ray_tpu.get(c.read.remote()) == 100


def test_actor_ordering(ray_start_regular):
    c = Counter.remote()
    refs = [c.inc.remote() for _ in range(50)]
    assert ray_tpu.get(refs) == list(range(1, 51))


def test_actor_method_exception(ray_start_regular):
    @ray_tpu.remote
    class Bad:
        def fail(self):
            raise KeyError("oops")

        def ok(self):
            return "fine"

    b = Bad.remote()
    with pytest.raises(KeyError):
        ray_tpu.get(b.fail.remote())
    # Actor stays alive after method exceptions.
    assert ray_tpu.get(b.ok.remote()) == "fine"


def test_actor_constructor_failure(ray_start_regular):
    @ray_tpu.remote
    class Broken:
        def __init__(self):
            raise RuntimeError("ctor boom")

        def ping(self):
            return "pong"

    b = Broken.remote()
    with pytest.raises((RuntimeError, ActorDiedError)):
        ray_tpu.get(b.ping.remote(), timeout=10)


def test_kill_actor(ray_start_regular):
    c = Counter.remote()
    assert ray_tpu.get(c.inc.remote()) == 1
    ray_tpu.kill(c)
    time.sleep(0.2)
    with pytest.raises(ActorDiedError):
        ray_tpu.get(c.inc.remote(), timeout=10)


def test_named_actor(ray_start_regular):
    Counter.options(name="global_counter").remote(7)
    handle = ray_tpu.get_actor("global_counter")
    assert ray_tpu.get(handle.read.remote()) == 7


def test_named_actor_duplicate_rejected(ray_start_regular):
    Counter.options(name="dup").remote()
    with pytest.raises(ValueError):
        Counter.options(name="dup").remote()


def test_get_if_exists(ray_start_regular):
    a = Counter.options(name="gie", get_if_exists=True).remote(1)
    ray_tpu.get(a.inc.remote())
    b = Counter.options(name="gie", get_if_exists=True).remote(1)
    # Same actor: counter state shared.
    assert ray_tpu.get(b.read.remote()) == 2


def test_actor_handle_passing(ray_start_regular):
    @ray_tpu.remote
    def use_actor(handle):
        return ray_tpu.get(handle.inc.remote(10))

    c = Counter.remote()
    assert ray_tpu.get(use_actor.remote(c)) == 10


def test_async_actor(ray_start_regular):
    @ray_tpu.remote
    class AsyncWorker:
        async def process(self, x):
            import asyncio

            await asyncio.sleep(0.05)
            return x * 2

    w = AsyncWorker.options(max_concurrency=8).remote()
    start = time.monotonic()
    refs = [w.process.remote(i) for i in range(8)]
    values = ray_tpu.get(refs, timeout=10)
    elapsed = time.monotonic() - start
    assert sorted(values) == [i * 2 for i in range(8)]
    # 8 concurrent 50ms sleeps must overlap (well under 8*0.05=0.4s serial).
    assert elapsed < 0.35


def test_an_actor_whose_only_async_def_is_a_generator_is_async(
    ray_start_regular,
):
    """Run threaded, its stream's one item would be the async generator
    object; it runs on a loop, where concurrent streams interleave."""

    @ray_tpu.remote
    class Ticker:
        async def ticks(self, n):
            import asyncio

            for i in range(n):
                await asyncio.sleep(0.02)
                yield i

    t = Ticker.options(max_concurrency=4).remote()
    start = time.monotonic()
    streams = [
        t.ticks.options(num_returns="streaming").remote(5) for _ in range(4)
    ]
    got = [[ray_tpu.get(ref) for ref in stream] for stream in streams]
    assert got == [[0, 1, 2, 3, 4]] * 4
    # Four streams of five 20 ms ticks side by side, not end to end (0.4 s).
    assert time.monotonic() - start < 0.35


def test_threaded_actor_concurrency(ray_start_regular):
    @ray_tpu.remote
    class Slow:
        def work(self):
            time.sleep(0.1)
            return 1

    s = Slow.options(max_concurrency=4).remote()
    start = time.monotonic()
    ray_tpu.get([s.work.remote() for _ in range(4)], timeout=10)
    assert time.monotonic() - start < 0.35


def test_actor_restart_on_kill(ray_start_regular):
    @ray_tpu.remote(max_restarts=1)
    class Phoenix:
        def __init__(self):
            self.n = 0

        def bump(self):
            self.n += 1
            return self.n

    p = Phoenix.remote()
    assert ray_tpu.get(p.bump.remote()) == 1
    ray_tpu.kill(p, no_restart=False)
    time.sleep(0.5)
    # Restarted: state reset, still serving.
    assert ray_tpu.get(p.bump.remote(), timeout=10) == 1


def test_actor_ordering_with_deferred_deps(ray_start_regular):
    """A call whose args are still pending must not be overtaken by later
    dep-free calls (sequential submit queue semantics)."""

    @ray_tpu.remote
    def slow_value():
        time.sleep(0.4)
        return 99

    @ray_tpu.remote
    class Box:
        def __init__(self):
            self.v = 0

        def set(self, v):
            self.v = v

        def read(self):
            return self.v

    b = Box.remote()
    b.set.remote(slow_value.remote())
    # Submitted after set(): must observe set()'s effect.
    assert ray_tpu.get(b.read.remote(), timeout=10) == 99
