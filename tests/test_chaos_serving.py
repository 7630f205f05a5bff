"""Chaos tests for the fault-tolerant serving stack.

Deterministic fault injection (ray_tpu._private.fault_injection) drives
three failure layers:

  * engine — a poisoned request fails alone (dead-letter, KV release) while
    every other in-flight generation completes token-identically; K
    consecutive failing steps wedge the engine and broadcast to all waiters;
  * router — requests landing on dead replicas fail over with exponential
    backoff, an excluded-replica set, and a typed error on budget
    exhaustion; streaming LLM requests resume mid-stream on another replica
    with a contiguous, token-identical greedy stream;
  * harness — the injection points themselves count hits deterministically.

Every test seeds the model identically (seed=0), so greedy outputs have an
exact unbatched ground truth to compare against.
"""

import threading
import time

import pytest

import jax.numpy as jnp
import numpy as np

import ray_tpu
from ray_tpu._private import fault_injection as fi
from ray_tpu.exceptions import (
    ActorDiedError,
    EngineOverloadedError,
    FleetOverloadedError,
    PoisonRequestError,
    ReplicaUnavailableRetryExhausted,
)
from ray_tpu.llm import EngineConfig, LLMEngine, LLMServer
from ray_tpu.models.gpt import GPT, GPTConfig
from llm_in_process import in_process

pytestmark = pytest.mark.chaos

TINY = GPTConfig(
    vocab_size=128,
    num_layers=2,
    num_heads=4,
    embed_dim=64,
    max_seq_len=128,
    dtype=jnp.float32,
    attention_impl="reference",
)

ECFG = EngineConfig(
    block_size=8, num_blocks=64, max_decode_slots=4, max_blocks_per_seq=8
)

# Serve-path tests pay the engine actor's init-time warmup (it compiles
# every bucket); two buckets keep each test well inside the tier-1 budget.
ECFG_SERVE = EngineConfig(
    block_size=8,
    num_blocks=64,
    max_decode_slots=4,
    max_blocks_per_seq=8,
    prefill_buckets=(8, 32),
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


@pytest.fixture(autouse=True)
def _no_leftover_faults():
    fi.clear()
    yield
    fi.clear()


# ---------------- engine layer: poison-request isolation ----------------


def _concurrent_generates(server, jobs):
    """Run several server.generate calls concurrently; returns
    {request_id: result-or-exception}."""
    results = {}

    def run(rid, prompt, n):
        try:
            results[rid] = server.generate(
                prompt, max_new_tokens=n, request_id=rid, timeout_s=60.0
            )
        except BaseException as exc:  # noqa: BLE001
            results[rid] = exc

    threads = [
        threading.Thread(target=run, args=job, daemon=True) for job in jobs
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=90)
    return results


def test_poisoned_prefill_fails_only_that_request():
    """Acceptance: a poisoned request (injected step exception during its
    prefill) is failed in isolation — other in-flight generations finish
    token-identical to the unbatched reference, the replica stays healthy,
    and the dead letter shows up in metrics()/dead_letters()."""
    prompts = random_prompts((5, 11, 3), seed=2)
    n_new = 8
    fi.inject(
        "llm.prefill",
        match="poison-me",
        exc_factory=lambda: RuntimeError("cosmic ray in prefill"),
    )
    server = in_process(LLMServer(TINY, ECFG, seed=0, warmup=False))
    jobs = [(f"ok-{i}", p, n_new) for i, p in enumerate(prompts)]
    jobs.append(("poison-me", random_prompts((9,), seed=3)[0], n_new))
    results = _concurrent_generates(server, jobs)

    # The culprit got the typed error; nobody else did.
    poisoned = results["poison-me"]
    assert isinstance(poisoned, PoisonRequestError)
    assert poisoned.request_id == "poison-me"
    assert "cosmic ray" in repr(poisoned.cause)
    model = GPT(TINY)
    params = server._engine.runner.params
    for i, p in enumerate(prompts):
        out = results[f"ok-{i}"]
        assert not isinstance(out, BaseException), out
        assert out["token_ids"] == reference_greedy(model, params, p, n_new)

    # Replica stays healthy; the dead letter is visible.
    assert server.check_health() is True
    stats = server.metrics()
    assert stats["num_dead_letters"] == 1
    assert stats["wedged"] is False
    letters = server.dead_letters()
    assert len(letters) == 1
    assert letters[0]["request_id"] == "poison-me"
    assert "cosmic ray" in letters[0]["error"]
    assert letters[0]["prompt_len"] == 9
    # Its KV blocks were released with it.
    assert server._engine.allocator.num_allocated == 0

    # The engine keeps serving new work afterwards.
    out = server.generate(prompts[0], max_new_tokens=4, timeout_s=60.0)
    assert out["token_ids"] == reference_greedy(model, params, prompts[0], 4)
    server.shutdown()


def test_poisoned_decode_fails_only_that_request():
    """A fault in one sequence's decode section dead-letters that request
    mid-generation; the other requests in the same decode batch continue
    unperturbed (their state only mutates after the risky calls)."""
    prompts = random_prompts((7, 6), seed=4)
    fi.inject(
        "llm.decode.seq",
        match="poison-me",
        nth=3,  # fail on its 3rd decode iteration, mid-stream
        exc_factory=lambda: RuntimeError("decode bitflip"),
    )
    server = in_process(LLMServer(TINY, ECFG, seed=0, warmup=False))
    jobs = [
        ("ok-0", prompts[0], 10),
        ("poison-me", prompts[1], 10),
    ]
    results = _concurrent_generates(server, jobs)
    assert isinstance(results["poison-me"], PoisonRequestError)
    model = GPT(TINY)
    params = server._engine.runner.params
    assert results["ok-0"]["token_ids"] == reference_greedy(
        model, params, prompts[0], 10
    )
    assert server.check_health() is True
    letters = server.dead_letters()
    assert [d["request_id"] for d in letters] == ["poison-me"]
    assert letters[0]["tokens_generated"] >= 1  # died mid-generation
    server.shutdown()


def test_poison_in_multi_prefill_step_requeues_innocent_admits():
    """With max_prefills_per_step > 1, a poisoned prefill must not leave
    the OTHER sequences admitted in the same step decoding from K/V that
    was never computed: they are requeued recompute-style and finish
    token-identical after the culprit is failed."""
    ecfg = EngineConfig(
        block_size=8,
        num_blocks=64,
        max_decode_slots=4,
        max_blocks_per_seq=8,
        max_prefills_per_step=4,
    )
    fi.inject(
        "llm.prefill",
        match="poison-me",
        exc_factory=lambda: RuntimeError("poisoned first admit"),
    )
    eng = LLMEngine(TINY, ecfg, seed=0)
    prompts = random_prompts((6, 9), seed=10)
    tokens = []
    eng.add_request(prompts[0], max_new_tokens=6, request_id="poison-me")
    eng.add_request(
        prompts[1], max_new_tokens=6, request_id="ok", on_token=tokens.append
    )
    with pytest.raises(RuntimeError, match="poisoned first admit"):
        eng.step()  # both admitted; the first one's prefill raises
    assert eng.culprit_for(RuntimeError()) == "poison-me"  # via _current_rid
    assert eng.fail_request("poison-me", RuntimeError("poisoned first admit"))
    while eng.has_work():
        eng.step()
    want = reference_greedy(GPT(TINY), eng.runner.params, prompts[1], 6)
    assert tokens == want
    assert eng.allocator.num_allocated == 0
    assert [d["request_id"] for d in eng.dead_letters()] == ["poison-me"]


def test_engine_wedges_after_k_consecutive_failing_steps():
    """Satellite + tentpole: unattributable step failures retry, but K
    consecutive failures wedge the engine — the error reaches EVERY
    concurrent generate/generate_stream waiter, check_health() flips false,
    and _submit raises afterwards."""
    ecfg = EngineConfig(
        block_size=8,
        num_blocks=64,
        max_decode_slots=4,
        max_blocks_per_seq=8,
        max_consecutive_step_failures=2,
    )
    # Steps 1-2 succeed (tokens flow), then every step fails
    # unattributably: step 3 retries, step 4 wedges (K=2).
    fi.inject("llm.step", nth=3, times=None, message="engine meltdown")
    server = in_process(LLMServer(TINY, ecfg, seed=0, warmup=False))
    prompts = random_prompts((5, 7), seed=5)

    stream_tokens = []
    stream_error = []

    def run_stream():
        try:
            for tok in server.generate_stream(
                prompts[1], max_new_tokens=16, timeout_s=60.0
            ):
                stream_tokens.append(tok)
        except BaseException as exc:  # noqa: BLE001
            stream_error.append(exc)

    stream_thread = threading.Thread(target=run_stream, daemon=True)
    stream_thread.start()
    results = _concurrent_generates(server, [("g0", prompts[0], 16)])
    stream_thread.join(timeout=90)

    # Both waiters saw the broadcast error (not a timeout, not a hang).
    assert isinstance(results["g0"], fi.InjectedFault)
    assert stream_error and isinstance(stream_error[0], fi.InjectedFault)
    assert server.check_health() is False
    assert server.metrics()["wedged"] is True
    # New submissions fail fast after the crash.
    with pytest.raises(RuntimeError, match="not running"):
        server.generate([1, 2], max_new_tokens=1)


def test_unattributable_failure_below_threshold_recovers():
    """A transient unattributable step failure (fails twice, then stops) is
    retried in place: no dead letters, no wedge, token-identical output."""
    fi.inject("llm.step", nth=2, times=2, message="transient glitch")
    server = in_process(LLMServer(TINY, ECFG, seed=0, warmup=False))
    prompt = random_prompts((6,), seed=6)[0]
    out = server.generate(prompt, max_new_tokens=8, timeout_s=60.0)
    model = GPT(TINY)
    want = reference_greedy(model, server._engine.runner.params, prompt, 8)
    assert out["token_ids"] == want
    assert server.check_health() is True
    assert server.metrics()["num_dead_letters"] == 0
    server.shutdown()


def test_verify_fault_dead_letters_only_culprit_releases_draft_blocks():
    """Speculative decoding: an injected failure at the engine.verify
    site (the per-sequence commit of a verify step) dead-letters ONLY the
    culpable request — with its target KV blocks AND its draft-model
    mirror blocks released — while every other in-flight generation
    finishes token-identical to the unbatched reference and the KV pools
    end exactly as they started."""
    draft_cfg = GPTConfig(
        vocab_size=128, num_layers=1, num_heads=4, embed_dim=64,
        max_seq_len=128, dtype=jnp.float32, attention_impl="reference",
    )
    ecfg = EngineConfig(
        block_size=8, num_blocks=64, max_decode_slots=4,
        max_blocks_per_seq=8, speculation="draft",
        draft_model_config=draft_cfg,
    )
    fi.inject(
        "engine.verify",
        match="poison-me",
        exc_factory=lambda: RuntimeError("verify bitflip"),
    )
    server = in_process(LLMServer(TINY, ecfg, seed=0, warmup=False))
    prompts = random_prompts((7, 6), seed=4)
    jobs = [
        ("ok-0", prompts[0], 10),
        ("ok-1", prompts[1], 10),
        ("poison-me", [3, 4, 5] * 4, 10),  # repetitive: speculation engages
    ]
    results = _concurrent_generates(server, jobs)
    poisoned = results["poison-me"]
    assert isinstance(poisoned, PoisonRequestError)
    assert "verify bitflip" in repr(poisoned.cause)
    model = GPT(TINY)
    params = server._engine.runner.params
    for rid, prompt in (("ok-0", prompts[0]), ("ok-1", prompts[1])):
        out = results[rid]
        assert not isinstance(out, BaseException), out
        assert out["token_ids"] == reference_greedy(model, params, prompt, 10)
    assert server.check_health() is True
    letters = server.dead_letters()
    assert [d["request_id"] for d in letters] == ["poison-me"]
    # The step that died really was a verify step with proposals in it.
    assert server._engine.stats()["spec_verify_steps"] > 0
    # Pool-size invariants: every target KV block and every draft mirror
    # block went back with its request — the pools are exactly as big as
    # at boot, so repeated poisonings can never shrink serving capacity.
    assert server._engine.allocator.num_allocated == 0
    assert server._engine._spec.allocator.num_allocated == 0
    assert server._engine._spec._state == {}
    # The engine keeps speculating for new work afterwards.
    out = server.generate([3, 4, 5] * 4, max_new_tokens=6, timeout_s=60.0)
    assert out["token_ids"] == reference_greedy(
        model, params, [3, 4, 5] * 4, 6
    )
    server.shutdown()


def test_poisoned_chunk_dead_letters_only_culprit_releases_all_blocks():
    """Chunked prefill: an injected failure at the engine.prefill_chunk
    site MID-chunk-stream (the request's 2nd chunk, with a whole prompt's
    worth of blocks already held and K/V partially scattered) dead-letters
    ONLY the culprit — all of its blocks (allocated up front at admission)
    are released in one abort, the draft mirror pool ends at boot size —
    while concurrent generations finish token-identical and the engine
    keeps chunking new work."""
    draft_cfg = GPTConfig(
        vocab_size=128, num_layers=1, num_heads=4, embed_dim=64,
        max_seq_len=128, dtype=jnp.float32, attention_impl="reference",
    )
    ecfg = EngineConfig(
        block_size=8, num_blocks=64, max_decode_slots=4,
        max_blocks_per_seq=8, speculation="draft",
        draft_model_config=draft_cfg,
        max_prefill_tokens_per_step=16,
    )
    fi.inject(
        "engine.prefill_chunk",
        match="poison-me",
        nth=2,  # fail on its SECOND chunk: mid-prompt, blocks held
        exc_factory=lambda: RuntimeError("cosmic ray mid-chunk"),
    )
    server = in_process(LLMServer(TINY, ecfg, seed=0, warmup=False))
    prompts = random_prompts((7, 6), seed=4)
    poison_prompt = random_prompts((40,), seed=12)[0]  # 3 chunks of 16
    jobs = [
        ("ok-0", prompts[0], 10),
        ("ok-1", [3, 4, 5] * 4, 10),  # repetitive: speculation engages
        ("poison-me", poison_prompt, 10),
    ]
    results = _concurrent_generates(server, jobs)
    poisoned = results["poison-me"]
    assert isinstance(poisoned, PoisonRequestError)
    assert "mid-chunk" in repr(poisoned.cause)
    model = GPT(TINY)
    params = server._engine.runner.params
    for rid, prompt in (("ok-0", prompts[0]), ("ok-1", [3, 4, 5] * 4)):
        out = results[rid]
        assert not isinstance(out, BaseException), out
        assert out["token_ids"] == reference_greedy(model, params, prompt, 10)
    assert server.check_health() is True
    letters = server.dead_letters()
    assert [d["request_id"] for d in letters] == ["poison-me"]
    assert letters[0]["tokens_generated"] == 0  # died before its 1st token
    # Pool invariants: the culprit's WHOLE block table (admission
    # allocates for the full prompt; chunk 1 had already scattered into
    # it) went back, and the draft mirror pool is exactly at boot size.
    assert server._engine.allocator.num_allocated == 0
    assert server._engine._spec.allocator.num_allocated == 0
    assert server._engine._spec._state == {}
    # The engine keeps chunking new long prompts afterwards.
    out = server.generate(poison_prompt, max_new_tokens=4, timeout_s=60.0)
    assert out["token_ids"] == reference_greedy(
        model, params, poison_prompt, 4
    )
    assert server._engine.stats()["chunked_prefill_requests"] >= 1
    server.shutdown()


# ---------------- router layer: failover + resume ----------------


@pytest.fixture
def serve_ray():
    runtime = ray_tpu.init(num_cpus=8)
    yield runtime
    from ray_tpu import serve

    serve.shutdown()
    ray_tpu.shutdown()


def test_unary_failover_retries_on_another_replica(serve_ray):
    """A replica failing with ActorDiedError on the first dispatch is
    excluded and the request re-dispatched; the caller sees the result,
    not the error."""
    from ray_tpu import serve

    @serve.deployment(num_replicas=2)
    def double(x):
        return x * 2

    handle = serve.run(double.bind(), name="failover-unary")
    spec = fi.inject(
        "replica.handle_request",
        match="double",
        exc_factory=lambda: ActorDiedError(None, "injected replica death"),
    )
    assert handle.remote(21).result(timeout_s=30) == 42
    assert spec.fires == 1  # the failure really happened, and was survived


def test_retry_budget_exhaustion_raises_typed_error_with_backoff(serve_ray):
    """Acceptance: when every dispatch fails, the router backs off with
    full jitter between attempts and, after the configured budget,
    surfaces ReplicaUnavailableRetryExhausted — not a raw ActorDiedError.
    The jitter seed makes the delay sequence deterministic: the expected
    sleeps are recomputed here from the same seeded RNG."""
    import random

    from ray_tpu import serve

    @serve.deployment
    def echo(x):
        return x

    handle = serve.run(echo.bind(), name="failover-budget")
    assert handle.remote(1).result(timeout_s=30) == 1  # sanity: app works

    backoff = 0.05
    seed = 1234
    spec = fi.inject(
        "actor.submit",
        match="ReplicaActor.handle_request",
        times=None,
        exc_factory=lambda: ActorDiedError(None, "injected submit failure"),
    )
    tuned = handle.options(
        retry_budget=2, backoff_initial_s=backoff, backoff_jitter_seed=seed
    )
    t0 = time.monotonic()
    with pytest.raises(ReplicaUnavailableRetryExhausted) as ei:
        tuned.remote(2)
    elapsed = time.monotonic() - t0
    assert ei.value.attempts == 3  # initial + 2 retries
    assert isinstance(ei.value.last_error, ActorDiedError)
    assert spec.fires == 3
    # Full-jitter backoff: each delay is uniform over [0, initial * 2^k].
    # The router's RNG is private and seeded, so the exact draws are
    # reproducible — the attempts slept at least their sum.
    rng = random.Random(seed)
    expected = rng.uniform(0.0, backoff) + rng.uniform(0.0, 2 * backoff)
    assert elapsed >= expected
    fi.clear()
    # The deployment still serves once the faults stop.
    assert tuned.remote(3).result(timeout_s=30) == 3


def test_overload_shed_redispatches_once_to_other_replica(serve_ray):
    """An EngineOverloadedError from one replica is treated like a drain:
    redispatch to the other replica (budget-exempt, no backoff ladder)
    and the caller sees the result, never the shed."""
    from ray_tpu import serve

    @serve.deployment(num_replicas=2)
    def work(x):
        return x + 1

    handle = serve.run(work.bind(), name="overload-failover")
    spec = fi.inject(
        "replica.handle_request",
        match="work",
        times=1,
        exc_factory=lambda: EngineOverloadedError(
            engine="e0",
            reason="queue_len 8 >= max_queue_len 8",
            queue_len=8,
            retry_after_s=0.01,
        ),
    )
    assert handle.remote(1).result(timeout_s=30) == 2
    assert spec.fires == 1  # the shed really happened, and was survived


def test_fleet_overloaded_typed_rejection_with_retry_hint(serve_ray):
    """When EVERY replica sheds, the router gives up after one attempt
    per live replica and surfaces FleetOverloadedError carrying the
    retry-after hint — fast typed rejection, not retry-budget burn."""
    from ray_tpu import serve

    @serve.deployment(num_replicas=2)
    def busy(x):
        return x

    handle = serve.run(busy.bind(), name="overload-fleet")
    assert handle.remote(0).result(timeout_s=30) == 0  # sanity: app works
    spec = fi.inject(
        "replica.handle_request",
        match="busy",
        times=None,
        exc_factory=lambda: EngineOverloadedError(
            engine="e0",
            reason="queue full",
            queue_len=8,
            retry_after_s=0.2,
        ),
    )
    t0 = time.monotonic()
    with pytest.raises(FleetOverloadedError) as ei:
        handle.remote(1).result(timeout_s=30)
    elapsed = time.monotonic() - t0
    assert ei.value.attempts == 2  # one try per live replica
    assert ei.value.retry_after_s >= 0.2  # the engine's hint rides out
    assert isinstance(ei.value.last_error, EngineOverloadedError)
    assert spec.fires == 2
    # Fast rejection: two dispatches and one short inter-replica pause,
    # never the exponential retry ladder.
    assert elapsed < 5.0
    fi.clear()
    assert handle.remote(3).result(timeout_s=30) == 3  # fleet recovered


def test_single_replica_overload_rejects_immediately(serve_ray):
    """With one live replica there is no 'other replica' to try: the
    first shed becomes FleetOverloadedError with zero backoff sleeps."""
    from ray_tpu import serve

    @serve.deployment(num_replicas=1)
    def solo(x):
        return x

    handle = serve.run(solo.bind(), name="overload-solo")
    assert handle.remote(0).result(timeout_s=30) == 0
    spec = fi.inject(
        "replica.handle_request",
        match="solo",
        times=None,
        exc_factory=lambda: EngineOverloadedError(
            engine="e0", reason="queue full", queue_len=4,
            retry_after_s=0.05,
        ),
    )
    t0 = time.monotonic()
    with pytest.raises(FleetOverloadedError) as ei:
        handle.remote(1).result(timeout_s=30)
    assert ei.value.attempts == 1
    assert spec.fires == 1
    assert time.monotonic() - t0 < 2.0


def _build_llm_app(serve_run, engine_name, app_name, num_replicas=2):
    from ray_tpu.llm.serve import build_app

    return serve_run(
        build_app(
            TINY, ECFG_SERVE, engine_name=engine_name,
            num_replicas=num_replicas
        ),
        name=app_name,
    )


def test_llm_stream_failover_injected_token_identical(serve_ray):
    """Acceptance: a replica dying mid-stream (injected ActorDiedError
    between yields) fails over, resuming on another replica by re-submitting
    prompt + tokens-generated-so-far — the client-visible greedy stream is
    uninterrupted and token-identical to a failure-free run."""
    from ray_tpu import serve
    from ray_tpu.llm.serve import llm_stream_resume

    handle = _build_llm_app(serve.run, "chaos-inj", "llmchaos1")
    prompt = random_prompts((7,), seed=7)[0]
    n_new = 8
    want = reference_greedy(
        GPT(TINY), LLMEngine(TINY, ECFG_SERVE, seed=0).runner.params, prompt, n_new
    )

    spec = fi.inject(
        "replica.stream_item",
        nth=4,  # die after delivering 3 tokens
        exc_factory=lambda: ActorDiedError(None, "injected mid-stream kill"),
    )
    stream = handle.options(
        stream=True, stream_resume_fn=llm_stream_resume
    ).remote({"prompt_ids": prompt, "max_new_tokens": n_new, "stream": True})
    tokens = [d["token_id"] for d in stream]
    assert spec.fires == 1  # the mid-stream death really happened
    assert tokens == want


def test_spec_midstream_replica_kill_stream_resumes_token_identical(
    serve_ray,
):
    """A replica dying mid-stream WHILE the engine is speculating resumes
    on another replica token-identically: the resume re-submits prompt +
    tokens-so-far, the engine rolls any in-flight speculative state back
    with the aborted original, and the client-visible greedy stream stays
    contiguous — speculation must never leak a rejected token into a
    resumed stream."""
    from ray_tpu import serve
    from ray_tpu.llm.serve import build_app, llm_stream_resume

    ecfg = EngineConfig(
        block_size=8, num_blocks=64, max_decode_slots=4,
        max_blocks_per_seq=8, prefill_buckets=(8, 32),
        speculation="ngram",
    )
    handle = serve.run(
        build_app(TINY, ecfg, engine_name="chaos-spec", num_replicas=2),
        name="llmchaos5",
    )
    prompt = [5, 6, 7] * 4  # repetitive: the n-gram proposer engages
    n_new = 9
    want = reference_greedy(
        GPT(TINY), LLMEngine(TINY, ecfg, seed=0).runner.params, prompt, n_new
    )
    spec = fi.inject(
        "replica.stream_item",
        nth=4,  # die after delivering 3 tokens, mid-speculation
        exc_factory=lambda: ActorDiedError(None, "injected mid-spec kill"),
    )
    stream = handle.options(
        stream=True, stream_resume_fn=llm_stream_resume
    ).remote({"prompt_ids": prompt, "max_new_tokens": n_new, "stream": True})
    tokens = [d["token_id"] for d in stream]
    assert spec.fires == 1
    assert tokens == want
    # The engine really speculated around the failover.
    engine = ray_tpu.get_actor("llm_engine:chaos-spec")
    stats = ray_tpu.get(engine.metrics.remote())
    assert stats["speculation"] == "ngram"
    assert stats["spec_verify_steps"] > 0
    assert stats["spec_accepted_tokens"] > 0


def test_midstream_replica_kill_during_chunked_prefill_stream_resumes(
    serve_ray,
):
    """A replica dying while a long prompt is still STREAMING IN as
    chunks (killed at its very first stream item, before any token was
    delivered) stream-resumes on another replica token-identically: the
    resume re-submits the prompt, which re-chunks from scratch under the
    same budget on the survivor."""
    from ray_tpu import serve
    from ray_tpu.llm.serve import build_app, llm_stream_resume

    ecfg = EngineConfig(
        block_size=8, num_blocks=64, max_decode_slots=4,
        max_blocks_per_seq=8, prefill_buckets=(8, 32),
        max_prefill_tokens_per_step=8,
    )
    handle = serve.run(
        build_app(TINY, ecfg, engine_name="chaos-chunk", num_replicas=2),
        name="llmchaos6",
    )
    prompt = random_prompts((26,), seed=13)[0]  # 4 chunks under budget 8
    n_new = 6
    want = reference_greedy(
        GPT(TINY), LLMEngine(TINY, ecfg, seed=0).runner.params, prompt, n_new
    )
    spec = fi.inject(
        "replica.stream_item",
        nth=1,  # die delivering the FIRST token: prefill just chunked in
        exc_factory=lambda: ActorDiedError(None, "injected mid-chunk kill"),
    )
    stream = handle.options(
        stream=True, stream_resume_fn=llm_stream_resume
    ).remote({"prompt_ids": prompt, "max_new_tokens": n_new, "stream": True})
    tokens = [d["token_id"] for d in stream]
    assert spec.fires == 1
    assert tokens == want
    # The prompt really chunked on the serving engine.
    engine = ray_tpu.get_actor("llm_engine:chaos-chunk")
    stats = ray_tpu.get(engine.metrics.remote())
    assert stats["prefill_token_budget"] == 8
    assert stats["chunked_prefill_requests"] >= 1


def test_llm_stream_double_failover_token_identical(serve_ray):
    """Two replica deaths during ONE stream: each resume must fold only the
    tokens delivered since the previous resume (regression: re-folding the
    first batch duplicated prompt context and truncated the budget)."""
    from ray_tpu import serve
    from ray_tpu.llm.serve import llm_stream_resume

    handle = _build_llm_app(serve.run, "chaos-inj2", "llmchaos4")
    prompt = random_prompts((6,), seed=11)[0]
    n_new = 8
    want = reference_greedy(
        GPT(TINY), LLMEngine(TINY, ECFG_SERVE, seed=0).runner.params,
        prompt, n_new,
    )
    # Fires on the 3rd and 6th delivered items: 2 tokens, die, resume,
    # 2 more tokens, die again, resume again, finish.
    spec = fi.inject(
        "replica.stream_item",
        every=3,
        times=2,
        exc_factory=lambda: ActorDiedError(None, "injected double kill"),
    )
    stream = handle.options(
        stream=True, stream_resume_fn=llm_stream_resume
    ).remote({"prompt_ids": prompt, "max_new_tokens": n_new, "stream": True})
    tokens = [d["token_id"] for d in stream]
    assert spec.fires == 2
    assert tokens == want


def test_llm_stream_failover_real_replica_kill_token_identical(serve_ray):
    """Same acceptance via a real ray_tpu.kill of the replica serving the
    stream (≥2 replicas deployed): the router excludes the dead replica,
    resumes on the survivor, and the greedy stream stays token-identical.
    The resumed prefill mostly hits the prefix cache (PR 2), so failover
    costs roughly one tail prefill."""
    from ray_tpu import serve
    from ray_tpu.llm.serve import llm_stream_resume
    from ray_tpu.serve._private.controller import get_or_create_controller

    handle = _build_llm_app(serve.run, "chaos-kill", "llmchaos2")
    prompt = random_prompts((9,), seed=8)[0]
    # Long enough that the stream cannot have run to its end on the first
    # replica between the third token's delivery and the kill: at pipeline
    # depth 1 a 10-token answer sometimes had, and nothing failed over.
    n_new = 24
    want = reference_greedy(
        GPT(TINY), LLMEngine(TINY, ECFG_SERVE, seed=0).runner.params, prompt, n_new
    )

    gen = handle.options(
        stream=True, stream_resume_fn=llm_stream_resume
    ).remote({"prompt_ids": prompt, "max_new_tokens": n_new, "stream": True})
    it = iter(gen)
    received = [next(it)["token_id"] for _ in range(3)]
    serving_tag = gen.replica_tag
    assert serving_tag is not None
    _, replicas = ray_tpu.get(
        get_or_create_controller().get_replica_snapshot.remote(
            "llmchaos2", "LLMIngress"
        )
    )
    ray_tpu.kill(replicas[serving_tag])
    received += [d["token_id"] for d in it]
    assert received == want
    # Failover really moved the stream to a different replica.
    assert gen.replica_tag != serving_tag


def test_poisoned_request_isolated_through_serve_path(serve_ray):
    """End-to-end: a poisoned request through the Serve ingress fails with
    a typed error while a concurrent request completes token-identically,
    and the dead letter is visible through the ingress metrics API."""
    from ray_tpu import serve

    handle = _build_llm_app(serve.run, "chaos-poison", "llmchaos3", 1)
    prompts = random_prompts((5, 6), seed=9)
    want = reference_greedy(
        GPT(TINY), LLMEngine(TINY, ECFG_SERVE, seed=0).runner.params, prompts[0], 6
    )
    fi.inject(
        "llm.prefill",
        match="poison-via-serve",
        exc_factory=lambda: RuntimeError("poisoned via serve"),
    )
    ok = handle.remote({"prompt_ids": prompts[0], "max_new_tokens": 6})
    bad = handle.remote(
        {
            "prompt_ids": prompts[1],
            "max_new_tokens": 6,
            "request_id": "poison-via-serve",
        }
    )
    with pytest.raises(PoisonRequestError):
        bad.result(timeout_s=60)
    assert ok.result(timeout_s=60)["token_ids"] == want
    letters = handle.dead_letters.remote().result(timeout_s=30)
    assert [d["request_id"] for d in letters] == ["poison-via-serve"]
    stats = handle.metrics.remote().result(timeout_s=30)
    assert stats["num_dead_letters"] == 1
    assert stats["wedged"] is False


# ---------------- pipeline depth 1 (async_scheduling, PR 17) ----------------


def test_async_poisoned_decode_attributes_one_step_late():
    """A poisoned decode sequence surfaces at COMMIT: at pipeline depth
    1 (async_scheduling) one step after its program was dispatched, at
    depth 0 in the same step. The failure must be attributed to the
    DISPATCH step (failure_step() == current step - 1, vs == current
    step at depth 0), dead-letter only the culprit with that step index,
    leave the innocent batchmate token-identical, and return the pools
    to boot size. At both depths the record stays at the head of the
    pipeline with its commit pointer on the culprit's slot, and the next
    step resumes it: nothing is decoded twice, nothing is emitted for
    the dead-lettered slot."""
    prompts = random_prompts((7, 6), seed=4)
    attributed = {}
    for mode in (False, True):
        fi.clear()
        fi.inject(
            "llm.decode.seq",
            match="poison-me",
            nth=3,  # 3rd decode commit for that sequence, mid-stream
            exc_factory=lambda: RuntimeError("decode bitflip"),
        )
        ecfg = EngineConfig(
            block_size=8, num_blocks=64, max_decode_slots=4,
            max_blocks_per_seq=8, async_scheduling=mode,
        )
        eng = LLMEngine(TINY, ecfg, seed=0)
        boot_free = eng.allocator.num_free
        ok_tokens = []
        eng.add_request(
            prompts[0], max_new_tokens=10, request_id="ok-0",
            on_token=ok_tokens.append,
        )
        eng.add_request(
            prompts[1], max_new_tokens=10, request_id="poison-me"
        )
        with pytest.raises(RuntimeError, match="decode bitflip"):
            while eng.has_work():
                eng.step()
        attributed[mode] = (eng.failure_step(), eng._steps)
        assert eng.culprit_for(RuntimeError()) == "poison-me"
        if not mode:
            # The survivor's slot committed, the culprit's did not, and
            # the record waits for the retry.
            (record,) = eng._inflight
            assert record.rids == ["ok-0", "poison-me"]
            assert record.commit_idx == 1
        assert eng.fail_request(
            "poison-me", RuntimeError("decode bitflip")
        )
        if not mode:
            dispatches = eng.stats()["decode_dispatches"]
            emitted = len(ok_tokens)
            eng.step()
            # The retry popped the record without a token for the dead
            # slot (the survivor had its own already), then decoded the
            # survivor once: one program, one token.
            assert record not in eng._inflight and record.commit_idx == 2
            assert eng.stats()["decode_dispatches"] == dispatches + 1
            assert len(ok_tokens) == emitted + 1
        while eng.has_work():
            eng.step()
        want = reference_greedy(
            GPT(TINY), eng.runner.params, prompts[0], 10
        )
        assert ok_tokens == want, f"async={mode}: survivor diverged"
        assert eng.allocator.num_free == boot_free
        letters = eng.dead_letters()
        assert [d["request_id"] for d in letters] == ["poison-me"]
        assert letters[0]["step"] == attributed[mode][0]
    # Sync attributes to the step that raised; async to the step that
    # DISPATCHED the poisoned program — exactly one earlier.
    fail_sync, steps_sync = attributed[False]
    fail_async, steps_async = attributed[True]
    assert fail_sync == steps_sync
    assert fail_async == steps_async - 1


def test_async_midstream_replica_kill_stream_resumes_token_identical(
    serve_ray,
):
    """A replica dying mid-stream while its engine runs the ASYNC step
    loop (a chained decode in flight at the moment of death) resumes on
    another replica token-identically: the in-flight overshoot dies with
    the replica, the resume re-submits prompt + delivered tokens, and the
    client-visible greedy stream stays contiguous."""
    from ray_tpu import serve
    from ray_tpu.llm.serve import build_app, llm_stream_resume

    ecfg = EngineConfig(
        block_size=8, num_blocks=64, max_decode_slots=4,
        max_blocks_per_seq=8, prefill_buckets=(8, 32),
        async_scheduling=True,
    )
    handle = serve.run(
        build_app(TINY, ecfg, engine_name="chaos-async", num_replicas=2),
        name="llmchaos7",
    )
    prompt = random_prompts((7,), seed=7)[0]
    n_new = 8
    want = reference_greedy(
        GPT(TINY), LLMEngine(TINY, ecfg, seed=0).runner.params, prompt, n_new
    )
    spec = fi.inject(
        "replica.stream_item",
        nth=4,  # die after delivering 3 tokens: decode pipeline is hot
        exc_factory=lambda: ActorDiedError(None, "injected async kill"),
    )
    stream = handle.options(
        stream=True, stream_resume_fn=llm_stream_resume
    ).remote({"prompt_ids": prompt, "max_new_tokens": n_new, "stream": True})
    tokens = [d["token_id"] for d in stream]
    assert spec.fires == 1
    assert tokens == want
    # The surviving engine really served async (and drained cleanly).
    engine = ray_tpu.get_actor("llm_engine:chaos-async")
    stats = ray_tpu.get(engine.metrics.remote())
    assert stats["async_scheduling"] is True
    assert stats["inflight_steps"] == 0
