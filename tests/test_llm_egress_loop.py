"""A step's tokens leave the step thread in one hand-over, and the engine
actor serves every stream from one event loop.

  * a step that commits N tokens to N live streams is one
    `call_soon_threadsafe` (`egress_handoffs`) that carries N tokens
    (`egress_handoff_tokens`);
  * 64 concurrent streams each get their own tokens, in order, equal to the
    depth-0 engine's;
  * the engine actor's threads do not grow with its requests, live or
    waiting for a lane (192 submitted on 8 lanes);
  * the deadline, the idle time-out, a closed stream, a poison request, the
    wedge's broadcast and `shutdown` each end their streams as they did when a
    request held a thread.
"""

import asyncio
import threading
import time

import pytest

import jax.numpy as jnp

import ray_tpu
from ray_tpu._private import fault_injection as fi
from ray_tpu.exceptions import PoisonRequestError
from ray_tpu.llm import EngineConfig, LLMEngine
from ray_tpu.llm import engine as engine_mod
from ray_tpu.llm.engine import _STREAM_END, LLMServer
from ray_tpu.models.gpt import GPTConfig

TINY = GPTConfig(
    vocab_size=128,
    num_layers=2,
    num_heads=4,
    embed_dim=64,
    max_seq_len=128,
    dtype=jnp.float32,
    attention_impl="reference",
)
BASE = dict(block_size=8, num_blocks=160, max_blocks_per_seq=16)


def _prompt(i):
    return [1 + i % 97, 2 + i % 89, 3 + i % 83, 5, 7]


@pytest.fixture(autouse=True)
def _no_leftover_faults():
    fi.clear()
    yield
    fi.clear()


@pytest.fixture
def server():
    made = LLMServer(
        TINY, EngineConfig(max_decode_slots=4, **BASE), seed=0, warmup=False
    )
    yield made
    asyncio.run(made.shutdown())


async def _collect(stream):
    return [token async for token in stream]


def _pool_is_whole(server):
    allocator = server._engine.allocator
    return allocator.num_allocated == 0


def test_a_step_that_feeds_n_streams_is_one_handover_of_n_tokens(
    server, monkeypatch
):
    batches = []
    deliver = engine_mod._deliver

    def recording(batch):
        batches.append(list(batch))
        deliver(batch)

    monkeypatch.setattr(engine_mod, "_deliver", recording)
    lanes, new = 4, 24

    async def run():
        before = await server.metrics()
        answers = await asyncio.gather(
            *(
                _collect(server.generate_stream(_prompt(i), max_new_tokens=new))
                for i in range(lanes)
            )
        )
        return before, answers, await server.metrics()

    before, answers, after = asyncio.run(run())
    assert [len(a) for a in answers] == [new] * lanes
    tokens = [
        [state for state, item, _ in batch if item is not _STREAM_END]
        for batch in batches
    ]
    # Every hand-over is one call, whatever it carried.
    assert after["egress_handoffs"] - before["egress_handoffs"] == len(batches)
    assert sum(map(len, tokens)) == lanes * new
    assert (
        after["egress_handoff_tokens"] - before["egress_handoff_tokens"]
        == lanes * new
    )
    # With every lane decoding, a commit's hand-over carries a token of each
    # stream: all but the steps in which the lanes filled, whose first tokens
    # leave at the step's end.
    whole = [states for states in tokens if len(states) == lanes]
    assert len(whole) >= new - 2 * lanes
    assert all(len(set(map(id, states))) == lanes for states in whole)
    assert len(batches) <= new + 2 * lanes


def test_64_streams_get_their_own_tokens_in_order():
    ecfg = dict(max_decode_slots=8, **BASE)
    prompts = [_prompt(i) for i in range(64)]
    want = LLMEngine(
        TINY, EngineConfig(async_scheduling=False, **ecfg), seed=0
    ).generate(prompts, max_new_tokens=12)
    server = LLMServer(TINY, EngineConfig(**ecfg), seed=0, warmup=False)

    async def run():
        try:
            return await asyncio.gather(
                *(
                    _collect(server.generate_stream(p, max_new_tokens=12))
                    for p in prompts
                )
            )
        finally:
            await server.shutdown()

    got = asyncio.run(run())
    assert got == want
    assert len({tuple(answer) for answer in got}) > 1
    assert _pool_is_whole(server)


def test_the_actors_threads_do_not_grow_with_its_requests(ray_start_regular):
    """192 requests on 8 lanes through the actor's public API: they are
    inside the engine together, 8 on a lane and the others waiting for one,
    and the process runs no more threads for them than the server's pool
    has."""
    # Each sequence of a decode step takes 2 ms: a request stays long
    # enough for all of them to be inside at once.
    fi.inject(
        "llm.decode.seq", action="delay", delay_s=0.002, every=1, times=None
    )
    engine = (
        ray_tpu.remote(LLMServer)
        .options(max_concurrency=1000)
        .remote(
            TINY, EngineConfig(max_decode_slots=8, **BASE), None, 0,
            warmup=False,
        )
    )
    ray_tpu.get(engine.generate.remote(_prompt(0), 2))
    before = threading.active_count()
    refs = [engine.generate.remote(_prompt(i), 6) for i in range(192)]
    inside = most_threads = 0
    deadline = time.monotonic() + 60.0
    while inside < 128 and time.monotonic() < deadline:
        inside = max(inside, ray_tpu.get(engine.num_pending.remote()))
        most_threads = max(most_threads, threading.active_count())
        time.sleep(0.005)
    assert inside >= 128
    answers = ray_tpu.get(refs, timeout=120.0)
    assert all(len(a["token_ids"]) == 6 for a in answers)
    # The pool's 16 and some of the runtime's own under load, not 192.
    assert most_threads - before <= 48, most_threads - before
    stats = ray_tpu.get(engine.metrics.remote())
    assert stats["kv_pool_allocated"] == 0
    ray_tpu.get(engine.shutdown.remote())


def test_the_deadline_ends_a_stream_with_what_it_had(server):
    fi.inject(
        "llm.decode.seq", action="delay", delay_s=0.05, every=1, times=None
    )

    async def run():
        got = []
        with pytest.raises(TimeoutError, match="exceeded its 1.0s deadline"):
            async for token in server.generate_stream(
                _prompt(1), max_new_tokens=100, timeout_s=1.0
            ):
                got.append(token)
        with pytest.raises(TimeoutError):
            await server.generate(_prompt(2), max_new_tokens=100, timeout_s=0.1)
        return got

    got = asyncio.run(run())
    assert 1 <= len(got) < 100
    assert not server._requests
    assert _pool_is_whole(server)


def test_the_idle_timeout_is_the_gap_and_arms_no_timer_without_one(
    server, monkeypatch
):
    async def run():
        loop = asyncio.get_running_loop()
        timers = []
        call_later = loop.call_later

        def counting(delay, callback, *args):
            timers.append(delay)
            return call_later(delay, callback, *args)

        monkeypatch.setattr(loop, "call_later", counting)
        # No idle time-out: the deadline's timer, and none a token.
        assert len(await _collect(
            server.generate_stream(_prompt(3), max_new_tokens=10)
        )) == 10
        assert timers == [120.0]
        fi.inject(
            "llm.decode.seq", action="delay", delay_s=0.2, every=1, times=None
        )
        with pytest.raises(TimeoutError, match="produced no token for 0.05s"):
            await _collect(
                server.generate_stream(
                    _prompt(4), max_new_tokens=10, stream_idle_timeout_s=0.05
                )
            )

    asyncio.run(run())
    assert not server._requests
    assert _pool_is_whole(server)


def test_a_closed_stream_aborts_its_request_and_returns_its_blocks(server):
    async def run():
        stream = server.generate_stream(_prompt(5), max_new_tokens=100)
        first = [await stream.__anext__(), await stream.__anext__()]
        assert server._engine.allocator.num_allocated > 0
        await stream.aclose()
        return first, await server.metrics()

    first, stats = asyncio.run(run())
    assert len(first) == 2
    assert not server._requests
    assert stats["kv_pool_allocated"] == 0
    assert stats["num_running"] == 0


def test_a_poison_request_ends_its_stream_and_no_other(server):
    fi.inject(
        "llm.decode.seq",
        match="poison-me",
        exc_factory=lambda: RuntimeError("cosmic ray in decode"),
    )

    async def run():
        good = asyncio.ensure_future(
            _collect(server.generate_stream(_prompt(6), max_new_tokens=12))
        )
        got = []
        with pytest.raises(PoisonRequestError):
            async for token in server.generate_stream(
                _prompt(7), max_new_tokens=12, request_id="poison-me"
            ):
                got.append(token)
        return got, await good, await server.dead_letters()

    got, good, letters = asyncio.run(run())
    # The token its prefill gave came out before the error did.
    assert len(got) >= 1
    assert len(good) == 12
    assert [letter["request_id"] for letter in letters] == ["poison-me"]
    assert server.check_health() is True
    assert _pool_is_whole(server)


def test_the_wedge_reaches_every_stream_behind_its_tokens():
    ecfg = EngineConfig(
        max_decode_slots=4, max_consecutive_step_failures=2, **BASE
    )
    fi.inject(
        "llm.decode.seq", action="delay", delay_s=0.01, every=1, times=None
    )
    server = LLMServer(TINY, ecfg, seed=0, warmup=False)

    async def one(i, got):
        try:
            async for token in server.generate_stream(
                _prompt(i), max_new_tokens=100
            ):
                got.append(token)
        except fi.InjectedFault as exc:
            return exc

    async def run():
        got = [[], [], []]
        tasks = [asyncio.ensure_future(one(i, got[i])) for i in range(3)]
        while not all(got):
            await asyncio.sleep(0.01)
        # Every step fails from here: one retry, then the wedge.
        fi.inject("llm.step", times=None, message="engine meltdown")
        ends = await asyncio.gather(*tasks)
        with pytest.raises(RuntimeError, match="not running"):
            await server.generate([1, 2], max_new_tokens=1)
        return got, ends

    got, ends = asyncio.run(run())
    assert all(isinstance(end, fi.InjectedFault) for end in ends)
    assert all(1 <= len(g) < 100 for g in got)
    assert server.check_health() is False


def test_shutdown_ends_every_stream(server):
    fi.inject(
        "llm.decode.seq", action="delay", delay_s=0.02, every=1, times=None
    )

    async def one(i, got):
        try:
            async for token in server.generate_stream(
                _prompt(i), max_new_tokens=100
            ):
                got.append(token)
        except RuntimeError as exc:
            return exc

    async def run():
        got = [[] for _ in range(6)]  # four on a lane, two waiting for one
        tasks = [asyncio.ensure_future(one(i, got[i])) for i in range(6)]
        # All six inside, and the four that got a lane (whichever) streaming.
        while len(server._requests) < 6 or sum(map(bool, got)) < 4:
            assert not any(task.done() for task in tasks)
            await asyncio.sleep(0.01)
        await server.shutdown()
        return got, await asyncio.gather(*tasks)

    got, ends = asyncio.run(run())
    assert all("shut down with requests in flight" in str(end) for end in ends)
    assert all(len(g) < 100 for g in got)
    assert not server._requests


@pytest.mark.filterwarnings(
    # The stream of a loop that is gone cannot be closed on it.
    "ignore::pytest.PytestUnraisableExceptionWarning"
)
def test_a_closed_loop_stops_the_step_thread_without_a_traceback(capfd):
    """A step thread that outlives the loop its requests were submitted on
    (the actor died under it) drops what it cannot hand over and stops."""
    server = LLMServer(
        TINY, EngineConfig(max_decode_slots=4, **BASE), seed=0, warmup=False
    )
    fi.inject(
        "llm.decode.seq", action="delay", delay_s=0.02, every=1, times=None
    )
    loop = asyncio.new_event_loop()
    stream = server.generate_stream(_prompt(8), max_new_tokens=50)
    assert isinstance(loop.run_until_complete(stream.__anext__()), int)
    loop.close()
    server._thread.join(timeout=30.0)
    assert not server._thread.is_alive()
    assert server.check_health() is False
    assert "Traceback" not in capfd.readouterr().err
