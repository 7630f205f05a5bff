"""Process-isolated worker tests (RAY_TPU_ISOLATION=process).

Covers the failure semantics only a real OS process boundary can provide
(reference: python/ray/tests/test_actor_failures.py, test_failure*.py run
against real worker processes): crashing workers don't kill the driver,
fate-sharing, retries on worker death, and serialization across the boundary.
"""

from __future__ import annotations

import os
import time

import numpy as np
import pytest

import ray_tpu
from ray_tpu.exceptions import ActorDiedError, WorkerCrashedError


@pytest.fixture(scope="module")
def proc_runtime():
    runtime = ray_tpu.init(
        num_cpus=8, num_tpus=1, _system_config={"isolation": "process"}
    )
    yield runtime
    ray_tpu.shutdown()


def test_task_runs_in_separate_process(proc_runtime):
    @ray_tpu.remote
    def worker_pid():
        return os.getpid()

    pid = ray_tpu.get(worker_pid.remote())
    assert pid != os.getpid()


def test_actor_crash_does_not_kill_driver(proc_runtime):
    @ray_tpu.remote
    class Bomb:
        def boom(self):
            os._exit(1)

        def ping(self):
            return "pong"

    bomb = Bomb.remote()
    assert ray_tpu.get(bomb.ping.remote()) == "pong"
    with pytest.raises(ActorDiedError):
        ray_tpu.get(bomb.boom.remote())
    # Driver is alive and can keep scheduling work.
    @ray_tpu.remote
    def alive():
        return 1

    assert ray_tpu.get(alive.remote()) == 1


def test_task_crash_is_retried_then_surfaces(proc_runtime, tmp_path):
    marker = tmp_path / "attempt"

    @ray_tpu.remote(max_retries=2)
    def crash_once(path):
        if not os.path.exists(path):
            open(path, "w").write("x")
            os._exit(1)
        return "recovered"

    assert ray_tpu.get(crash_once.remote(str(marker))) == "recovered"

    @ray_tpu.remote(max_retries=1)
    def always_crashes():
        os._exit(1)

    with pytest.raises(WorkerCrashedError):
        ray_tpu.get(always_crashes.remote())


def test_actor_restart_resets_state(proc_runtime):
    @ray_tpu.remote(max_restarts=1)
    class Phoenix:
        def __init__(self):
            self.n = 0

        def bump(self):
            self.n += 1
            return self.n

        def die(self):
            os._exit(1)

    ph = Phoenix.remote()
    assert ray_tpu.get(ph.bump.remote()) == 1
    assert ray_tpu.get(ph.bump.remote()) == 2
    with pytest.raises(ActorDiedError):
        ray_tpu.get(ph.die.remote())
    deadline = time.monotonic() + 10
    while time.monotonic() < deadline:
        try:
            assert ray_tpu.get(ph.bump.remote()) == 1  # fresh instance
            break
        except ActorDiedError:
            time.sleep(0.1)
    else:
        pytest.fail("actor never restarted")


def test_mutation_cannot_cross_the_boundary(proc_runtime):
    ref = ray_tpu.put({"xs": [1, 2, 3]})

    @ray_tpu.remote
    def mutate(d):
        d["xs"].append(99)
        return len(d["xs"])

    assert ray_tpu.get(mutate.remote(ref)) == 4
    assert ray_tpu.get(ref) == {"xs": [1, 2, 3]}
    local = ray_tpu.get(ref)
    local["xs"].clear()
    assert ray_tpu.get(ref) == {"xs": [1, 2, 3]}


def test_nested_submission_from_worker(proc_runtime):
    @ray_tpu.remote
    def inner(x):
        return x * 2

    @ray_tpu.remote
    def outer(x):
        return ray_tpu.get(inner.remote(x)) + 1

    assert ray_tpu.get(outer.remote(10)) == 21


def test_worker_put_get_and_wait(proc_runtime):
    @ray_tpu.remote
    def round_trip():
        ref = ray_tpu.put(np.arange(10))
        ready, _ = ray_tpu.wait([ref], num_returns=1, timeout=5)
        assert ready
        return int(ray_tpu.get(ref).sum())

    assert ray_tpu.get(round_trip.remote()) == 45


def test_streaming_generator_across_process(proc_runtime):
    @ray_tpu.remote
    def gen(n):
        for i in range(n):
            yield i * i

    items = [
        ray_tpu.get(r) for r in gen.options(num_returns="streaming").remote(5)
    ]
    assert items == [0, 1, 4, 9, 16]


def test_stream_consumed_in_a_worker_process_is_not_inline(proc_runtime):
    """A small item travels with its ref only where the consumer reads the
    ref in the owner's process. A task in a worker process gets its items by
    id over the wire, so the owner seals them as it always did; the driver's
    own stream (produced in a worker, consumed here) carries them."""
    runtime = proc_runtime

    @ray_tpu.remote
    def gen(n):
        for i in range(n):
            yield i * i

    @ray_tpu.remote
    def consume(n):
        stream = gen.options(num_returns="streaming").remote(n)
        return [ray_tpu.get(ref) for ref in stream]

    @ray_tpu.remote
    def consume_one(item):
        return item

    reported, inline = runtime.stream_items_reported, runtime.stream_items_inline
    assert ray_tpu.get(consume.remote(6)) == [0, 1, 4, 9, 16, 25]
    assert runtime.stream_items_reported == reported + 6
    assert runtime.stream_items_inline == inline
    assert runtime.stream_items_promoted == 0

    refs = list(gen.options(num_returns="streaming").remote(4))
    assert runtime.stream_items_inline == inline + 4
    assert not any(runtime.store.contains(ref._id) for ref in refs)
    assert ray_tpu.get(refs) == [0, 1, 4, 9]
    # Handed on to a worker process, a carried item becomes an object.
    assert ray_tpu.get(consume_one.remote(refs[2])) == 4
    assert runtime.stream_items_promoted == 1
    assert runtime.store.contains(refs[2]._id)


def test_large_object_via_shared_memory(proc_runtime):
    @ray_tpu.remote
    def produce():
        return np.ones(500_000, dtype=np.float64)  # ~4MB -> shm path

    @ray_tpu.remote
    def consume(arr):
        return float(arr.sum())

    ref = produce.remote()
    assert ray_tpu.get(consume.remote(ref)) == 500_000.0
    assert float(ray_tpu.get(ref).sum()) == 500_000.0


def test_named_actor_lookup_from_task(proc_runtime):
    @ray_tpu.remote
    class Registry:
        def who(self):
            return "registry"

    Registry.options(name="proc_registry").remote()

    @ray_tpu.remote
    def lookup():
        handle = ray_tpu.get_actor("proc_registry")
        return ray_tpu.get(handle.who.remote())

    assert ray_tpu.get(lookup.remote()) == "registry"


def test_async_actor_in_process(proc_runtime):
    @ray_tpu.remote
    class AsyncWorker:
        async def work(self, x):
            import asyncio

            await asyncio.sleep(0.01)
            return x + 1

    actor = AsyncWorker.remote()
    assert ray_tpu.get([actor.work.remote(i) for i in range(4)]) == [1, 2, 3, 4]


def test_async_generator_only_actor_in_process(proc_runtime):
    @ray_tpu.remote
    class Ticker:
        async def ticks(self, n):
            for i in range(n):
                yield i

    actor = Ticker.remote()
    stream = actor.ticks.options(num_returns="streaming").remote(3)
    assert [ray_tpu.get(ref) for ref in stream] == [0, 1, 2]


def test_threaded_actor_concurrency(proc_runtime):
    @ray_tpu.remote(max_concurrency=4)
    class Threaded:
        def ready(self):
            return True

        def slow(self):
            time.sleep(0.3)
            return 1

    actor = Threaded.remote()
    ray_tpu.get(actor.ready.remote())  # constructor + process spawn done
    start = time.monotonic()
    ray_tpu.get([actor.slow.remote() for _ in range(4)])
    assert time.monotonic() - start < 1.0  # 4 x 0.3s sequential would be 1.2s


def test_exceptions_carry_cause_type(proc_runtime):
    @ray_tpu.remote
    def raises():
        raise ValueError("bad value")

    with pytest.raises(ValueError, match="bad value"):
        ray_tpu.get(raises.remote())


def test_unpicklable_argument_fails_cleanly(proc_runtime):
    import threading

    @ray_tpu.remote
    def takes(x):
        return x

    with pytest.raises(Exception):
        ray_tpu.get(takes.remote(threading.Lock()))

    # The scheduler survives the serialization failure.
    assert ray_tpu.get(takes.remote(5)) == 5


@pytest.mark.parametrize("kind", ["task", "actor"])
def test_tpu_grant_on_cpu_worker_is_an_error(proc_runtime, kind):
    """Process workers start on the CPU jax platform (the driver owns the
    chip). Work that was granted TPU chips must fail there with the reason,
    not compute on the host without a word."""

    def platform():
        return os.environ.get("JAX_PLATFORMS")

    if kind == "task":
        submit = ray_tpu.remote(num_tpus=1)(platform).remote
    else:

        @ray_tpu.remote(num_tpus=1)
        class Engine:
            def platform(self):
                return platform()

        engine = Engine.remote()
        submit = engine.platform.remote
        # A call that races the failing constructor only learns that the
        # actor is gone; every later one is told why.
        with pytest.raises(Exception):
            ray_tpu.get(submit(), timeout=60)
    with pytest.raises(Exception, match="granted 1 TPU chip"):
        ray_tpu.get(submit(), timeout=60)

    # Without a TPU grant the same worker pool runs, on the CPU platform.
    assert ray_tpu.get(ray_tpu.remote(platform).remote()) == "cpu"
