"""Overload control plane: bounded admission + end-to-end deadlines.

The engine must stay well-behaved past its saturation point instead of
queueing toward collapse. These tests pin the two mechanisms:

  * bounded admission — `max_queue_len` / `max_queue_tokens` cap the
    prefill backlog; an over-cap submission fails fast with a typed,
    retryable EngineOverloadedError carrying a retry-after hint, and
    every rejection leaves the same three traces a dead letter does
    (shed ring, counter, flight-recorder shed record);
  * deadline enforcement is RESOURCE-TRUE — a request whose monotonic
    deadline passed while queued is dropped before schedule_prefills can
    feed it to a prefill program (prefill_tokens stays 0); one expiring
    mid-decode is aborted within one step with its KV (and, under
    speculation=draft, mirror) blocks reclaimed, at BOTH pipeline depths —
    including between dispatch and deferred commit under
    async_scheduling, where _commit_head's inactive-skip must drop the
    in-flight orphan token;
  * survivors are untouched: requests sharing the batch with a shed,
    expired, or aborted neighbour finish token-identical to reference.
"""

import time

import numpy as np
import pytest

import jax.numpy as jnp

from ray_tpu.exceptions import EngineOverloadedError
from ray_tpu.llm import EngineConfig, LLMEngine, LLMServer
from ray_tpu.llm.scheduler import FINISH_EXPIRED
from ray_tpu.models.gpt import GPT, GPTConfig
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
DRAFT = GPTConfig(
    vocab_size=128,
    num_layers=1,
    num_heads=2,
    embed_dim=16,
    max_seq_len=128,
    dtype=jnp.float32,
    attention_impl="reference",
)

BASE = dict(
    block_size=8, num_blocks=64, max_decode_slots=4, max_blocks_per_seq=8
)


def reference_greedy(model, params, prompt, n_tokens, pad_to=64):
    toks = list(prompt)
    out = []
    for _ in range(n_tokens):
        padded = np.zeros((1, pad_to), np.int32)
        padded[0, : len(toks)] = toks
        logits = model.apply(params, jnp.asarray(padded))
        t = int(jnp.argmax(logits[0, len(toks) - 1]))
        out.append(t)
        toks.append(t)
    return out


def random_prompts(lengths, vocab=128, seed=0):
    rng = np.random.RandomState(seed)
    return [list(map(int, rng.randint(0, vocab, size=n))) for n in lengths]


# ---------------- bounded admission ----------------


def test_bounded_admission_sheds_typed_and_audited():
    """Over max_queue_len: typed retryable rejection with a retry-after
    hint; the shed lands in the ring, the counter, and the flight record;
    the accepted requests are untouched and finish token-identical."""
    eng = LLMEngine(TINY, EngineConfig(max_queue_len=2, **BASE), seed=0)
    model = GPT(TINY)
    prompts = random_prompts((5, 6, 7))
    streams = [[], []]
    for p, s in zip(prompts[:2], streams):
        eng.add_request(p, max_new_tokens=4, on_token=s.append)
    with pytest.raises(EngineOverloadedError) as ei:
        eng.add_request(prompts[2], max_new_tokens=4, request_id="shed-me")
    err = ei.value
    assert "max_queue_len" in err.reason
    assert err.queue_len == 2
    assert 0.0 < err.retry_after_s <= 2.0
    sheds = eng.shed_requests()
    assert [s["request_id"] for s in sheds] == ["shed-me"]
    assert sheds[0]["queue_len"] == 2
    assert sheds[0]["retry_after_s"] == err.retry_after_s
    fr = eng.flight_recorder.snapshot()["sheds"]
    assert [s["request_id"] for s in fr] == ["shed-me"]
    assert not eng.scheduler.is_active("shed-me")
    while eng.has_work():
        eng.step()
    for p, s in zip(prompts[:2], streams):
        assert s == reference_greedy(model, eng.runner.params, p, 4)
    st = eng.stats()
    assert st["shed_requests"] == 1
    assert st["expired_requests"] == 0
    assert st["max_queue_len"] == 2
    assert eng.allocator.num_allocated == 0


def test_bounded_admission_token_cap():
    """max_queue_tokens caps the queued PROMPT tokens: a submission that
    would push the backlog over is shed, a smaller one still fits."""
    eng = LLMEngine(TINY, EngineConfig(max_queue_tokens=16, **BASE), seed=0)
    eng.add_request(random_prompts((10,))[0], max_new_tokens=2)
    with pytest.raises(EngineOverloadedError, match="max_queue_tokens"):
        eng.add_request(random_prompts((10,), seed=1)[0], max_new_tokens=2)
    eng.add_request(random_prompts((6,), seed=2)[0], max_new_tokens=2)
    while eng.has_work():
        eng.step()
    st = eng.stats()
    assert st["shed_requests"] == 1
    assert st["max_queue_tokens"] == 16
    assert eng.allocator.num_allocated == 0


def test_dead_on_arrival_is_never_admitted():
    """A deadline that passed in transit is rejected at submission —
    before any queue state, prefill program, or block allocation."""
    eng = LLMEngine(TINY, EngineConfig(**BASE), seed=0)
    with pytest.raises(TimeoutError, match="past its deadline"):
        eng.add_request(
            random_prompts((5,))[0],
            max_new_tokens=4,
            request_id="doa",
            deadline_s=time.monotonic() - 0.5,
        )
    assert not eng.scheduler.is_active("doa")
    assert not eng.has_work()
    assert eng.allocator.num_allocated == 0
    sheds = eng.shed_requests()
    assert [s["reason"] for s in sheds] == ["expired_at_submit"]
    st = eng.stats()
    assert st["shed_requests"] == 1
    assert st["expired_requests"] == 0
    assert st["prefill_tokens"] == 0


# ---------------- deadline expiry: resource truth ----------------


@pytest.mark.parametrize("async_mode", [False, True])
def test_queued_expiry_never_runs_prefill(async_mode):
    """A request whose deadline passes while QUEUED is dropped by the
    per-step sweep before schedule_prefills sees it: zero prefill tokens,
    zero blocks, finish_reason=expired delivered through on_finish."""
    eng = LLMEngine(
        TINY, EngineConfig(async_scheduling=async_mode, **BASE), seed=0
    )
    finished = []
    rid = eng.add_request(
        random_prompts((7,))[0],
        max_new_tokens=8,
        request_id="late",
        on_finish=finished.append,
        deadline_s=time.monotonic() + 0.01,
    )
    time.sleep(0.03)  # the deadline passes before any step runs
    assert eng.has_work()
    while eng.has_work():
        eng.step()
    assert not eng.scheduler.is_active(rid)
    assert finished and finished[0].finish_reason == FINISH_EXPIRED
    st = eng.stats()
    assert st["prefill_tokens"] == 0  # resource truth: no prefill ran
    assert st["expired_requests"] == 1
    assert st["shed_requests"] == 0
    assert eng.allocator.num_allocated == 0
    expiries = eng.flight_recorder.snapshot()["expiries"]
    assert len(expiries) == 1
    assert expiries[0]["request_id"] == "late"
    assert expiries[0]["phase"] == "queued"
    assert expiries[0]["tokens_generated"] == 0


@pytest.mark.parametrize("async_mode", [False, True])
def test_mid_decode_expiry_frees_blocks_within_one_step(async_mode):
    """A DECODING request crossing its deadline is aborted by the very
    next step's sweep — blocks back to zero immediately, not after a
    drain — and its delivered prefix plus an undisturbed neighbour are
    token-identical to reference. Parametrized over both pipeline depths: under
    async_scheduling the sweep runs between dispatch and deferred commit,
    so _commit_head's inactive-skip must drop the orphan token."""
    eng = LLMEngine(
        TINY, EngineConfig(async_scheduling=async_mode, **BASE), seed=0
    )
    model = GPT(TINY)
    prompts = random_prompts((6, 9))
    doomed, survivor = [], []
    survivor_done = []
    deadline = time.monotonic() + 30.0  # generous: WE decide when to step
    rid = eng.add_request(
        prompts[0],
        max_new_tokens=56,
        request_id="doomed",
        on_token=doomed.append,
        deadline_s=deadline,
    )
    eng.add_request(
        prompts[1],
        max_new_tokens=3,
        on_token=survivor.append,
        on_finish=survivor_done.append,
    )
    # Let the doomed request get well into decode (and the survivor
    # finish) while the deadline is still comfortably in the future.
    while len(doomed) < 5 or not survivor_done:
        eng.step()
    assert eng.scheduler.is_active(rid)
    assert eng.allocator.num_allocated > 0
    # Monkeypatch-free deadline crossing: rewrite the sequence's own
    # deadline to the past (the sweep reads seq.request.deadline_s), so
    # the test never sleeps against the wall clock.
    eng.scheduler._active[rid].request.deadline_s = time.monotonic() - 0.01
    eng.step()  # the sweep at the top of THIS step must drop it
    assert not eng.scheduler.is_active(rid)
    assert eng.allocator.num_allocated == 0  # freed within that one step
    while eng.has_work():  # drain any in-flight async record
        eng.step()
    st = eng.stats()
    assert st["inflight_steps"] == 0
    assert st["expired_requests"] == 1
    assert eng.allocator.num_allocated == 0
    expiries = eng.flight_recorder.snapshot()["expiries"]
    assert [e["phase"] for e in expiries] == ["running"]
    assert expiries[0]["tokens_generated"] >= 5
    # Token identity: the doomed prefix and the survivor match reference
    # greedy exactly — expiry never corrupted either stream.
    assert doomed == reference_greedy(
        model, eng.runner.params, prompts[0], len(doomed)
    )
    assert survivor == reference_greedy(
        model, eng.runner.params, prompts[1], 3
    )


def test_async_abort_between_dispatch_and_commit_drops_orphan():
    """Satellite: an abort landing while a decode step is dispatched but
    not yet committed (async steady state pipelines one deep) reclaims
    the blocks and the in-flight orphan token never reaches the stream;
    the survivor is token-identical to reference."""
    eng = LLMEngine(
        TINY, EngineConfig(async_scheduling=True, **BASE), seed=0
    )
    model = GPT(TINY)
    prompts = random_prompts((6, 9))
    doomed, survivor = [], []
    rid = eng.add_request(
        prompts[0],
        max_new_tokens=48,
        request_id="doomed",
        on_token=doomed.append,
    )
    eng.add_request(prompts[1], max_new_tokens=10, on_token=survivor.append)
    while len(doomed) < 3:
        eng.step()
    assert eng.stats()["inflight_steps"] >= 1  # commit still deferred
    assert eng.abort(rid)
    assert eng.allocator.num_allocated > 0  # survivor still decoding
    while eng.has_work():
        eng.step()
    st = eng.stats()
    assert st["inflight_steps"] == 0
    assert st["kv_pool_allocated"] == 0
    assert eng.allocator.num_allocated == 0
    assert survivor == reference_greedy(
        model, eng.runner.params, prompts[1], 10
    )
    # Committed tokens only — never the orphan from the in-flight record.
    assert doomed == reference_greedy(
        model, eng.runner.params, prompts[0], len(doomed)
    )


def test_async_draft_abort_releases_mirror_blocks():
    """Satellite: abort under async_scheduling + speculation=draft
    releases the KV blocks AND the draft-mirror blocks (speculation is a
    pipeline-flush boundary, so the teardown runs through the same
    deferred-commit machinery); the surviving request's stream is
    token-identical to reference."""
    eng = LLMEngine(
        TINY,
        EngineConfig(
            async_scheduling=True,
            speculation="draft",
            draft_model_config=DRAFT,
            **BASE,
        ),
        seed=0,
    )
    model = GPT(TINY)
    prompts = random_prompts((6, 9))
    doomed, survivor = [], []
    rid = eng.add_request(
        prompts[0],
        max_new_tokens=48,
        request_id="doomed",
        on_token=doomed.append,
    )
    eng.add_request(prompts[1], max_new_tokens=10, on_token=survivor.append)
    while len(doomed) < 3:
        eng.step()
    assert eng.stats()["spec_draft_pool_allocated"] > 0
    assert eng.abort(rid)
    while eng.has_work():
        eng.step()
    st = eng.stats()
    assert st["inflight_steps"] == 0
    assert st["kv_pool_allocated"] == 0
    assert st["spec_draft_pool_allocated"] == 0
    assert eng.allocator.num_allocated == 0
    assert survivor == reference_greedy(
        model, eng.runner.params, prompts[1], 10
    )
    # The aborted stream's delivered prefix was committed tokens only —
    # never the orphan from the in-flight record.
    assert doomed == reference_greedy(
        model, eng.runner.params, prompts[0], len(doomed)
    )


@pytest.mark.parametrize("async_mode", [False, True])
def test_expiry_under_draft_releases_mirror_blocks(async_mode):
    """Deadline expiry (not abort) with speculation=draft: mirror blocks
    are reclaimed through the same finish teardown at both depths."""
    eng = LLMEngine(
        TINY,
        EngineConfig(
            async_scheduling=async_mode,
            speculation="draft",
            draft_model_config=DRAFT,
            **BASE,
        ),
        seed=0,
    )
    doomed = []
    rid = eng.add_request(
        random_prompts((6,))[0],
        max_new_tokens=48,
        request_id="late",
        on_token=doomed.append,
        deadline_s=time.monotonic() + 30.0,
    )
    while len(doomed) < 3:
        eng.step()
    assert eng.stats()["spec_draft_pool_allocated"] > 0
    eng.scheduler._active[rid].request.deadline_s = time.monotonic() - 0.01
    eng.step()
    assert not eng.scheduler.is_active(rid)
    while eng.has_work():
        eng.step()
    st = eng.stats()
    assert st["expired_requests"] == 1
    assert st["spec_draft_pool_allocated"] == 0
    assert st["kv_pool_allocated"] == 0
    assert eng.allocator.num_allocated == 0


# ---------------- server boundary: timeout_s split ----------------


def test_server_deadline_expiry_raises_timeout():
    """LLMServer.generate: timeout_s becomes the engine-side deadline;
    when the ENGINE enforces it (dead on arrival here — the deadline is
    already spent at submit), the caller sees TimeoutError, and nothing
    was admitted."""
    server = in_process(
        LLMServer(TINY, EngineConfig(**BASE), seed=0, warmup=False)
    )
    try:
        with pytest.raises(TimeoutError, match="deadline"):
            server.generate(
                random_prompts((5,))[0], max_new_tokens=4, timeout_s=0.0
            )
        st = server.metrics()
        assert st["shed_requests"] == 1
        assert st["prefill_tokens"] == 0
    finally:
        server.shutdown()


def test_server_stream_idle_timeout_is_separate_knob():
    """Satellite: the old per-token-gap meaning of timeout_s lives in
    stream_idle_timeout_s now; a healthy stream with a tight idle bound
    but a loose deadline completes, token-identical."""
    server = in_process(
        LLMServer(TINY, EngineConfig(**BASE), seed=0, warmup=False)
    )
    model = GPT(TINY)
    try:
        prompt = random_prompts((7,))[0]
        got = list(
            server.generate_stream(
                prompt,
                max_new_tokens=5,
                timeout_s=60.0,
                stream_idle_timeout_s=10.0,
            )
        )
        assert got == reference_greedy(
            model, server._engine.runner.params, prompt, 5
        )
        assert server.metrics()["expired_requests"] == 0
    finally:
        server.shutdown()
