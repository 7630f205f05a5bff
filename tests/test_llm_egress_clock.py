"""`LLMServer` times the hand-over it owns (commit -> the thread that streams
the request) and reports the hand-overs after it (`Runtime.stream_delivery`).

  * a `generate_stream` consumer that comes late reads its lateness in
    `egress_handoff_s`; `egress_backlog_tokens` counts committed and untaken
    tokens and is back at 0 once the stream is drained; live and retired
    requests are one total;
  * the blocking `generate` is counted too;
  * with `instrument=False` an item's stamp is 0.0 and the fields read 0;
  * under Serve a token changes thread three times: `llm.request` carries
    `handoff_s`, each stream leaves one `stream.deliver` span in the request's
    trace, and `metrics()` splits the streams' wait by hop.
"""

import asyncio
import time

import pytest

import jax.numpy as jnp

import ray_tpu
from ray_tpu._private import fault_injection as fi
from ray_tpu.llm import EngineConfig
from ray_tpu.llm.engine import _STREAM_END, LLMServer
from ray_tpu.models.gpt import GPTConfig
from ray_tpu.util import tracing
from llm_in_process import in_process

TINY = GPTConfig(
    vocab_size=128,
    num_layers=2,
    num_heads=4,
    embed_dim=64,
    max_seq_len=128,
    dtype=jnp.float32,
    attention_impl="reference",
)
BASE = dict(
    block_size=8, num_blocks=64, max_decode_slots=4, max_blocks_per_seq=16
)
ECFG_SERVE = EngineConfig(
    block_size=4,
    num_blocks=12,
    max_decode_slots=4,
    max_blocks_per_seq=8,
    prefill_buckets=(8, 32),
)
EGRESS_KEYS = (
    "egress_handoff_s",
    "egress_handoff_tokens",
    "engine_stream_wait_s",
    "engine_stream_items_taken",
    "stream_wait_s",
    "stream_items_taken",
    "egress_backlog_tokens",
    "stall_steps",
)
PROMPT = [5, 9, 11, 3, 7]


def _wait_idle(server, timeout=60.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if server.num_pending() == 0:
            return
        time.sleep(0.01)
    raise AssertionError("engine never drained")


@pytest.fixture
def server():
    made = in_process(
        LLMServer(TINY, EngineConfig(**BASE), seed=0, warmup=False)
    )
    yield made
    made.shutdown()


def test_a_late_stream_consumer_reads_its_lateness_and_the_backlog(server):
    before = server.metrics()
    assert all(before[key] == 0 for key in EGRESS_KEYS)
    stream = server.generate_stream(list(PROMPT), max_new_tokens=6)
    first = next(stream)
    _wait_idle(server)  # every token is committed; one was taken
    time.sleep(0.1)
    live = server.metrics()
    assert live["egress_backlog_tokens"] == 5
    assert live["egress_handoff_tokens"] == 1
    rest = list(stream)
    assert len([first] + rest) == 6
    after = server.metrics()
    # Five tokens each waited out the sleep.
    assert after["egress_handoff_s"] >= 5 * 0.1
    assert after["egress_handoff_tokens"] == 6
    assert after["egress_backlog_tokens"] == 0
    # Retired with its request: the total stays, and the next request adds.
    assert not server._requests
    assert list(server.generate_stream(list(PROMPT), max_new_tokens=3))
    again = server.metrics()
    assert again["egress_handoff_tokens"] == 9
    assert again["egress_handoff_s"] > after["egress_handoff_s"]


def test_a_request_open_at_both_snapshots_is_in_the_windows_difference(server):
    stream = server.generate_stream(list(PROMPT), max_new_tokens=8)
    taken = [next(stream), next(stream)]
    opened = server.metrics()
    taken += [next(stream), next(stream), next(stream)]
    closed = server.metrics()  # the request is still live
    assert server._requests
    assert (
        closed["egress_handoff_tokens"] - opened["egress_handoff_tokens"] == 3
    )
    assert closed["egress_handoff_s"] > opened["egress_handoff_s"]
    assert len(taken + list(stream)) == 8


def test_the_blocking_call_is_counted_where_it_drains_its_queue(server):
    result = server.generate(list(PROMPT), max_new_tokens=5)
    assert len(result["token_ids"]) == 5
    assert all(isinstance(token, int) for token in result["token_ids"])
    stats = server.metrics()
    assert stats["egress_handoff_tokens"] == 5
    assert stats["egress_handoff_s"] > 0.0
    assert stats["egress_backlog_tokens"] == 0


def test_instrument_off_queues_bare_tokens_and_reads_zero():
    raw = LLMServer(
        TINY, EngineConfig(instrument=False, **BASE), seed=0, warmup=False
    )
    server = in_process(raw)
    try:

        async def filed():
            rid, state, expiry = await raw._admit(
                True, list(PROMPT), 4, None, None, None
            )
            while not state.ended:
                await state.wait()
            await raw._release(rid, expiry)
            return state

        state = asyncio.run(filed())
        filed_items = list(state.items)
        assert filed_items[-1] == (_STREAM_END, 0.0)
        assert len(filed_items) == 5
        assert all(
            isinstance(token, int) and stamp == 0.0
            for token, stamp in filed_items[:-1]
        )
        assert (state.stamped, state.offered) == (False, 0)
        assert not server._requests
        assert len(list(server.generate_stream(list(PROMPT), 4))) == 4
        assert len(server.generate(list(PROMPT), 4)["token_ids"]) == 4
        stats = server.metrics()
        assert all(stats[key] == 0 for key in EGRESS_KEYS)
    finally:
        server.shutdown()


def test_the_request_span_carries_its_handoff(server):
    """The span closes where the last token is committed, so it carries the
    hand-overs of the tokens taken until then: a decode step is held to
    30 ms, the consumer sleeps 100 ms after the first token, and the three
    tokens it then finds had waited 70, 40 and 10 ms."""
    fi.inject(
        "llm.decode.seq", action="delay", delay_s=0.03, every=1, times=None
    )
    try:
        with tracing.span("caller") as root:
            stream = server.generate_stream(list(PROMPT), max_new_tokens=6)
            tokens = [next(stream)]
            time.sleep(0.1)
            tokens += list(stream)
    finally:
        fi.clear()
    assert len(tokens) == 6
    rows = tracing.traces(trace_id=root.trace_id)
    (request,) = [r for r in rows if r["name"] == "llm.request"]
    attributes = request["attributes"]
    assert attributes["handoff_s"] >= attributes["handoff_max_s"] >= 0.02
    assert attributes["handoff_s"] <= server.metrics()["egress_handoff_s"]


@pytest.fixture
def serve_ray():
    runtime = ray_tpu.init(num_cpus=8)
    yield runtime
    from ray_tpu import serve

    serve.shutdown()
    ray_tpu.shutdown()


def test_under_serve_each_hop_has_its_wait_and_its_span(serve_ray):
    from ray_tpu import serve
    from ray_tpu.llm.serve import build_app

    handle = serve.run(
        build_app(TINY, ECFG_SERVE, engine_name="egress", num_replicas=1),
        name="llmegress",
    )
    engine = ray_tpu.get_actor("llm_engine:egress")
    before = ray_tpu.get(engine.metrics.remote())
    # A decode commit sleeps 10 ms a sequence: on a loaded machine the six
    # steps of this tiny model could otherwise all commit before the
    # actor's loop got one turn at the interpreter, and the span's
    # `handoff_s` (over the tokens taken by the last commit) would read 0.
    slow = fi.inject(
        "llm.decode.seq", action="delay", delay_s=0.01, every=1, times=None
    )
    try:
        with tracing.span("client") as root:
            stream = handle.options(stream=True).remote(
                {"prompt_ids": list(PROMPT), "max_new_tokens": 6, "stream": True}
            )
            tokens = []
            for item in stream:
                tokens.append(item["token_id"])
                time.sleep(0.02)  # the last hop's consumer is the late one
    finally:
        fi.remove(slow)
    assert len(tokens) == 6
    deadline = time.monotonic() + 30
    while serve_ray._streams and time.monotonic() < deadline:
        time.sleep(0.01)
    after = ray_tpu.get(engine.metrics.remote())
    window = {key: after[key] - before[key] for key in EGRESS_KEYS}
    assert window["egress_handoff_tokens"] == 6
    assert window["egress_handoff_s"] > 0.0
    # Hop 1: the engine's own stream, read by the replica's thread.
    assert window["engine_stream_items_taken"] == 6
    assert window["engine_stream_wait_s"] > 0.0
    # Every stream of the process: that hop and replica -> caller.
    assert window["stream_items_taken"] == 12
    replica_wait = window["stream_wait_s"] - window["engine_stream_wait_s"]
    assert replica_wait >= 5 * 0.02 * 0.5  # the sleeping consumer's hop
    assert after["egress_backlog_tokens"] == 0
    groups = serve_ray.stream_delivery()
    assert groups["LLMServer.generate_stream"]["items_taken"] >= 6
    assert len(groups) >= 2

    rows = tracing.traces(trace_id=root.trace_id)
    (request,) = [r for r in rows if r["name"] == "llm.request"]
    assert request["attributes"]["handoff_s"] > 0.0
    delivers = [r for r in rows if r["name"] == "stream.deliver"]
    assert len(delivers) == 2  # one a hop, none a token
    assert {d["attributes"]["items"] for d in delivers} == {6}
    assert "LLMServer.generate_stream" in {
        d["attributes"]["producer"] for d in delivers
    }
    span_ids = {r["span_id"] for r in rows}
    assert all(d["parent_span_id"] in span_ids for d in delivers)
