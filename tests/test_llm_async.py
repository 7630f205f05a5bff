"""The step loop at pipeline depth 1 (EngineConfig.async_scheduling)
against depth 0.

Depth 1 defers each decode's commit to the next step: while step N's
program runs on device, the host plans and dispatches step N+1 by chaining
decode's
`next_tokens` device array straight into the next step's `tokens` input
(positions/context_lens advance +1 deterministically) and fetching values
one step behind via `copy_to_host_async`. These tests pin the contract:

  * greedy outputs are TOKEN-IDENTICAL async on vs off — base case and
    across the full feature matrix (prefix cache + CoW, chunked prefill,
    preempt-resume under a tight pool, int8 KV, ngram + draft speculation,
    the pallas kernel in interpret mode, tp=2, KV fabric);
  * EOS / max-token finishes are detected one step late but the overshoot
    token NEVER reaches the client — proven with a prompt, found from a
    fixed seed, whose greedy stream first emits its EOS mid-stream;
  * the steady decode path allocates NO fresh host input buffers per step
    (preallocated, reused, asserted by allocation count) in either mode;
  * per-step dispatch/commit timestamps land in the flight record, whose
    host_exposed_s is the step's share of stats() host_exposed_total_s;
  * async ON is the default (PR 31); async_scheduling=False leaves sync
    records free of async keys and counts nothing;
  * stats() counts the chained dispatches and the flushes by cause, and
    each cause is reached by a test that provokes it alone.
"""

import numpy as np
import pytest

import jax.numpy as jnp

import ray_tpu
from ray_tpu.llm import EngineConfig, KVFabricConfig, LLMEngine
from ray_tpu.llm.engine import FLUSH_CAUSES
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
# One layer for tp=2 / draft / fabric cells: semantics are per-block and
# the smaller compile bill keeps the matrix inside the tier-1 budget.
TINY1 = GPTConfig(
    vocab_size=64,
    num_layers=1,
    num_heads=4,
    embed_dim=32,
    max_seq_len=128,
    dtype=jnp.float32,
    attention_impl="reference",
)
DRAFT1 = GPTConfig(
    vocab_size=64,
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


def run_modes(model_cfg, prompts, n_new, repeat=False, **overrides):
    """Generate with async_scheduling off and on; returns (sync, async,
    async_engine). The async engine must fully drain its pipeline."""
    outs = {}
    engines = {}
    for mode in (False, True):
        eng = LLMEngine(
            model_cfg,
            EngineConfig(async_scheduling=mode, **overrides),
            seed=0,
        )
        outs[mode] = eng.generate(prompts, max_new_tokens=n_new)
        if repeat:  # cached-path pass: prefix hits + CoW shapes live
            again = eng.generate(prompts, max_new_tokens=n_new)
            assert again == outs[mode], "cached repeat diverged"
        engines[mode] = eng
    eng = engines[True]
    assert eng.stats()["async_scheduling"] is True
    assert eng.stats()["inflight_steps"] == 0, "pipeline not drained"
    assert eng.allocator.num_allocated == 0
    return outs[False], outs[True], eng


# ---------------- token identity ----------------


def test_async_greedy_matches_sync_and_reference():
    """Base acceptance: mixed prompt/output lengths, async on vs off vs
    the unbatched ground truth — and the async run really pipelined
    (chained dispatches in the flight record)."""
    prompts = random_prompts((5, 11, 3, 17), seed=2)
    sync, async_, eng = run_modes(TINY, prompts, 8, **BASE)
    assert async_ == sync
    model = GPT(TINY)
    for prompt, out in zip(prompts, async_):
        assert out == reference_greedy(model, eng.runner.params, prompt, 8)
    steps = eng.flight_recorder.snapshot()["steps"]
    chained = [s for s in steps if s.get("chained")]
    assert len(chained) >= 4, "depth 1 never chained a dispatch"
    assert all(s["loop"] == "async" for s in chained)


MATRIX = {
    "prefix_cow": dict(TINY=True, repeat=True),
    "chunked": dict(
        TINY=True, repeat=True, max_prefill_tokens_per_step=8,
        prefill_buckets=(8, 32),
    ),
    "int8": dict(TINY=True, kv_cache_dtype="int8"),
    "spec_ngram": dict(
        TINY=True, speculation="ngram", num_speculative_tokens=3
    ),
    "spec_draft": dict(speculation="draft", num_speculative_tokens=3),
    "tp2": dict(tensor_parallel_size=2),
}


@pytest.mark.parametrize("feature", sorted(MATRIX))
def test_async_identity_feature_matrix(feature):
    """Async on/off token identity across the feature matrix. Spec modes
    flush the pipeline every step (the proposer reads committed tokens),
    so at depth 1 they exercise the dispatch that is committed in its own
    step rather than chaining."""
    kw = dict(MATRIX[feature])
    two_layer = kw.pop("TINY", False)
    repeat = kw.pop("repeat", False)
    if two_layer:
        model_cfg, base = TINY, dict(BASE)
        prompts = random_prompts((9, 8, 5), seed=6)
    else:
        model_cfg, base = TINY1, dict(
            block_size=4, num_blocks=64, max_decode_slots=4,
            max_blocks_per_seq=16,
        )
        prompts = random_prompts((9, 8, 5), vocab=64, seed=6)
    if kw.get("speculation") == "draft":
        kw["draft_model_config"] = DRAFT1
    sync, async_, _ = run_modes(
        model_cfg, prompts, 6, repeat=repeat, **base, **kw
    )
    assert async_ == sync, f"{feature}: async changed tokens"


def test_async_identity_under_preemption_pressure():
    """A pool far too small for the working set forces preempt-resume;
    depth 1 must flush before any step that preempts (a preempted
    sequence's blocks cannot be freed with a dispatch in flight) and the
    recompute path stays token-identical."""
    kw = dict(
        block_size=4, num_blocks=10, max_decode_slots=4,
        max_blocks_per_seq=8,
    )
    prompts = random_prompts((6, 7, 5, 6), seed=1)
    sync, async_, eng = run_modes(TINY, prompts, 12, **kw)
    assert async_ == sync
    assert eng.stats()["preemptions"] > 0, "pool never pressured"
    model = GPT(TINY)
    for prompt, out in zip(prompts, async_):
        assert out == reference_greedy(model, eng.runner.params, prompt, 12)


def test_async_identity_pallas_interpret():
    """The chained device tokens feed the same jitted decode program, so
    the fused pallas kernel (interpret mode on CPU) must be oblivious to
    who produced its token input."""
    kw = dict(
        block_size=8, num_blocks=64, max_decode_slots=4, max_blocks_per_seq=4
    )
    prompts = random_prompts((5, 11), seed=31)
    outs = {}
    for mode in (False, True):
        eng = LLMEngine(
            TINY,
            EngineConfig(attn_impl="pallas", async_scheduling=mode, **kw),
            seed=0,
        )
        outs[mode] = eng.generate(prompts, max_new_tokens=4)
        assert eng.stats()["attn_impl"] == "pallas"
    assert outs[True] == outs[False]


def test_async_identity_kv_fabric():
    """The host-DRAM spill tier hooks (note_filled_blocks at commit,
    restore as a flush boundary) see only committed state; fabric on must
    not perturb the async stream."""
    runtime = ray_tpu.init(num_cpus=4)
    try:
        prompts = random_prompts((9, 8, 5), vocab=64, seed=6)
        base = dict(
            block_size=4, num_blocks=16, max_decode_slots=4,
            max_blocks_per_seq=8, prefill_buckets=(8, 32),
        )
        outs = {}
        for mode in (False, True):
            eng = LLMEngine(
                TINY1,
                EngineConfig(
                    async_scheduling=mode,
                    kv_fabric=KVFabricConfig(
                        name=f"async-{mode}", byte_budget=8 << 20
                    ),
                    **base,
                ),
                seed=0,
            )
            first = eng.generate(prompts, max_new_tokens=6)
            again = eng.generate(prompts, max_new_tokens=6)
            assert first == again
            outs[mode] = first
        assert outs[True] == outs[False]
    finally:
        ray_tpu.shutdown()


# ---------------- EOS overshoot ----------------


def find_late_eos(n_new=12, min_index=3, draws=64):
    """A prompt whose greedy stream has a token that first appears at an
    index >= min_index (and not at the very end, so a token exists past
    it to overshoot into): (prompt, reference stream, index). Searched
    from a fixed seed rather than pinned, because the stream is the
    argmax of random weights and any change to the model's numerics
    moves it."""
    eng = LLMEngine(TINY, EngineConfig(**BASE), seed=0)
    rng = np.random.RandomState(17)
    for _ in range(draws):
        prompt = list(map(int, rng.randint(0, TINY.vocab_size, size=6)))
        want = eng.generate([prompt], max_new_tokens=n_new)[0]
        for k in range(min_index, n_new - 1):
            if want[k] not in want[:k]:
                return prompt, want, k
    pytest.fail(
        f"no prompt in {draws} draws whose greedy stream first emits a "
        f"token at an index >= {min_index}: the EOS fixture cannot be built"
    )


def test_async_eos_overshoot_never_emitted():
    """EOS finishes are detected one step late under async_scheduling:
    when the commit of step N sees the EOS, the chained step N+1 has
    already run on device. That overshoot token must never reach the
    client: the stream ends with the EOS, exactly as the reference does
    when cut there, at both depths."""
    prompt, want, k = find_late_eos()
    eos = want[k]
    for mode in (False, True):
        eng = LLMEngine(
            TINY, EngineConfig(async_scheduling=mode, **BASE), seed=0
        )
        stream = []
        free = eng.allocator.num_free
        eng.add_request(
            prompt, max_new_tokens=12, eos_id=eos, on_token=stream.append
        )
        while eng.has_work():
            eng.step()
        assert stream == want[: k + 1], (mode, stream)
        assert eng.allocator.num_free == free
        if mode:
            steps = eng.flight_recorder.snapshot()["steps"]
            # The finish really rode the pipeline: chained dispatches
            # happened, and the drain after the EOS commit skipped the
            # overshoot token (a commit entry with zero tokens).
            assert any(s.get("chained") for s in steps)
            drained = [
                c
                for s in steps
                for c in s.get("commits", ())
                if c["tokens"] == 0
            ]
            assert drained, "overshoot step was never drained"


def test_async_max_tokens_overshoot_not_emitted():
    """Same one-step-late finish for the max_new_tokens limit: the
    chained dispatch past the last requested token is skipped at commit
    and the stream length is exact."""
    prompts = random_prompts((7, 5), seed=9)
    sync, async_, _ = run_modes(TINY, prompts, 3, **BASE)
    assert async_ == sync
    assert all(len(o) == 3 for o in async_)


# ---------------- buffer reuse (satellite: preallocated inputs) ----------------


@pytest.mark.parametrize("mode", (False, True))
def test_steady_decode_allocates_no_fresh_host_buffers(mode):
    """The engine's per-step decode inputs (tokens/positions/
    block_tables/context_lens) are preallocated at engine init and
    reused: steady decode steps make ZERO np.zeros allocations at either
    depth, and the buffer objects themselves are stable across steps.
    What this does not count: `GPTRunner.decode` makes one small host
    copy of each input at dispatch (the program must not alias a buffer
    the next step refills)."""
    eng = LLMEngine(
        TINY, EngineConfig(async_scheduling=mode, **BASE), seed=0
    )
    for p in random_prompts((5, 9), seed=12):
        eng.add_request(p, max_new_tokens=16)
    eng.step()
    eng.step()  # both admitted; loop is now pure decode
    bufs = (
        id(eng._dec_tokens), id(eng._dec_positions),
        id(eng._dec_block_tables), id(eng._dec_context_lens),
    )
    calls = []
    real_zeros = np.zeros
    np.zeros = lambda *a, **kw: (calls.append(a), real_zeros(*a, **kw))[1]
    try:
        for _ in range(6):
            eng.step()
    finally:
        np.zeros = real_zeros
    assert calls == [], f"steady decode allocated host buffers: {calls}"
    assert bufs == (
        id(eng._dec_tokens), id(eng._dec_positions),
        id(eng._dec_block_tables), id(eng._dec_context_lens),
    )
    while eng.has_work():
        eng.step()


# ---------------- host_exposed + flight record ----------------


def test_host_exposed_and_flight_record_surfaces():
    """Per-step dispatch/commit timestamps in the flight record, and one
    account of host time: a step record's host_exposed_s is its share of
    stats() host_exposed_total_s (the ring's sum is the window's
    difference), sync steps record a positive share, and nothing of the
    old decode-to-decode gap is left in stats(), in a step record or in
    the metric registry. (That a chained dispatch samples 0 is
    test_llm_phase_clock's, on a pure decode loop: a chunk's synchronous
    fetch leaves the device idle before the next chained dispatch.)"""
    from ray_tpu.util import metrics

    exposed = {}
    for mode in (False, True):
        eng = LLMEngine(
            TINY, EngineConfig(async_scheduling=mode, **BASE), seed=0
        )
        before = eng.stats()
        eng.generate(random_prompts((5, 9), seed=3), max_new_tokens=8)
        stats = eng.stats()
        ring = eng.flight_recorder.snapshot()["steps"]
        assert len(ring) == stats["steps"] - before["steps"]
        exposed[mode] = (
            stats["host_exposed_total_s"] - before["host_exposed_total_s"]
        )
        assert exposed[mode] > 0.0
        # Each record is rounded to the microsecond.
        assert sum(s["host_exposed_s"] for s in ring) == pytest.approx(
            exposed[mode], abs=1e-6 * len(ring)
        )
        assert not [key for key in stats if "host_gap" in key]
        assert not [key for s in ring for key in s if "host_gap" in key]
        with pytest.raises(KeyError):
            metrics.histogram_snapshot("llm_engine_step_host_gap_seconds")
        steps = [s for s in ring if s.get("commits")]
        assert steps
        for s in steps:
            # Every step that committed also dispatched a decode batch;
            # only an async drain-only step (commits the in-flight tail
            # without queueing new work) legitimately has none.
            if not s["batch_size"]:
                assert s.get("loop") == "async" and not s.get("chained")
            for c in s["commits"]:
                assert c["dispatch_step"] <= s["step"]
                assert "time" in c and "tokens" in c
        if mode:
            assert any(s.get("chained") for s in steps)
        else:
            assert all("loop" not in s for s in steps)
            # Depth 0: every decode dispatch after the first follows a
            # fetch, with the device idle in between.
            assert all(s["host_exposed_s"] > 0.0 for s in steps[1:])
    # Sync leaves the device idle before every decode dispatch; async's
    # chained dispatches sample 0, so its total comes in below on the
    # same workload.
    assert exposed[True] < exposed[False]


def test_dashboard_percentiles_are_the_request_histograms():
    """The dashboard panel's percentile helper reads the four request
    histograms and nothing else (null-safe before any observation)."""
    from ray_tpu.dashboard.head import _llm_latency_percentiles

    eng = LLMEngine(
        TINY, EngineConfig(async_scheduling=True, **BASE), seed=0
    )
    eng.generate(random_prompts((6,), seed=4), max_new_tokens=6)
    out = _llm_latency_percentiles(eng.stats()["engine_id"])
    assert list(out) == ["ttft_s", "tpot_s", "queue_s", "e2e_s"]
    assert all(series["p50"] is not None for series in out.values())
    assert _llm_latency_percentiles("no-such-engine") == dict.fromkeys(
        out, {"p50": None, "p99": None}
    )


def test_async_off_is_default_and_records_unchanged():
    """async_scheduling defaults ON (PR 31): a default engine reports
    depth 1 and its flight records carry the async keys; an engine built
    with async_scheduling=False carries none and counts no chained
    dispatch and no flush."""
    assert EngineConfig(**BASE).async_scheduling is True
    eng = LLMEngine(TINY, EngineConfig(**BASE), seed=0)
    eng.generate(random_prompts((5,), seed=5), max_new_tokens=4)
    stats = eng.stats()
    assert stats["async_scheduling"] is True
    assert stats["inflight_steps"] == 0
    steps = eng.flight_recorder.snapshot()["steps"]
    assert all(s["loop"] == "async" and "chained" in s for s in steps)
    assert stats["chained_decode_dispatches"] > 0

    off = LLMEngine(TINY, EngineConfig(async_scheduling=False, **BASE), seed=0)
    off.generate(random_prompts((5,), seed=5), max_new_tokens=4)
    stats = off.stats()
    assert stats["async_scheduling"] is False
    assert stats["inflight_steps"] == 0
    for s in off.flight_recorder.snapshot()["steps"]:
        assert "chained" not in s and "loop" not in s
    assert stats["chained_decode_dispatches"] == 0
    assert stats["pipeline_flushes"] == 0
    assert not any(stats["pipeline_flushes_by_cause"].values())


# ---------------- how often depth 1 engages, and why it does not ----------------


def flushes(eng) -> dict:
    return dict(eng.stats()["pipeline_flushes_by_cause"])


def only(cause: str, n: int) -> dict:
    """The counts by cause with `n` under `cause` and nothing elsewhere."""
    assert cause in FLUSH_CAUSES
    return {c: (n if c == cause else 0) for c in FLUSH_CAUSES}


def assert_only(eng, before: dict, cause: str, n: int = 1) -> None:
    """Since `before`, `cause` was counted `n` times and no other was."""
    after = flushes(eng)
    assert {c: after[c] - before[c] for c in after} == only(cause, n)
    stats = eng.stats()
    assert stats["pipeline_flushes"] == sum(after.values())
    assert stats["chained_decode_dispatches"] <= stats["decode_dispatches"]


def steady(n_streams: int, max_new_tokens: int = 40):
    """An engine at depth 1 whose streams are all decoding and chained."""
    eng = LLMEngine(TINY, EngineConfig(**BASE), seed=0)
    rids = [
        eng.add_request(p, max_new_tokens=max_new_tokens)
        for p in random_prompts((5, 9, 7)[:n_streams], seed=12)
    ]
    for _ in range(n_streams + 2):
        eng.step()  # one admission a step, then the first chained steps
    assert eng.flight_recorder.snapshot()["steps"][-1]["chained"]
    return eng, rids


def drain(eng) -> None:
    while eng.has_work():
        eng.step()
    assert eng.stats()["inflight_steps"] == 0
    assert eng.allocator.num_allocated == 0


def test_steady_batch_chains_every_dispatch_after_the_first():
    """A batch of unchanging composition: the first decode dispatch is made
    from host tokens, every later one from the in-flight record's, and no
    flush is counted until the stream ends (one, `left`: the drain)."""
    eng = LLMEngine(TINY, EngineConfig(**BASE), seed=0)
    eng.add_request(random_prompts((6,), seed=4)[0], max_new_tokens=20)
    eng.step()  # prefill + the first decode, from committed state
    base = eng.stats()
    assert base["decode_dispatches"] == 1
    assert base["chained_decode_dispatches"] == 0
    for _ in range(10):
        eng.step()
    stats = eng.stats()
    assert stats["decode_dispatches"] == 11
    assert stats["chained_decode_dispatches"] == 10
    assert stats["pipeline_flushes"] == 0
    drain(eng)
    stats = eng.stats()
    # 19 decodes emit tokens 2..20, the 20th is the overshoot; all but the
    # first chained; the one flush drains the overshoot's record.
    assert stats["decode_dispatches"] == 20
    assert stats["chained_decode_dispatches"] == 19
    assert flushes(eng) == only("left", 1)
    assert stats["chained_decode_dispatches"] <= stats["decode_dispatches"]


def test_flush_cause_left_on_finish_and_on_abort():
    eng, rids = steady(2)
    before = flushes(eng)
    assert eng.abort(rids[0])
    eng.step()  # the in-flight batch lost a member: flush, re-plan
    assert_only(eng, before, "left")
    eng.step()
    assert eng.flight_recorder.snapshot()["steps"][-1]["chained"]
    # A finish: the survivor runs out its budget, the drain flushes once.
    before = flushes(eng)
    drain(eng)
    assert_only(eng, before, "left")


def test_flush_cause_joined_when_a_prompt_joins():
    """A prompt that joins a chained batch chains with it (PR 48): its
    chunk is left unread behind the chained decode, the next chained
    dispatch takes its first token from the device, and no flush is
    counted. `joined` is left for a join that cannot keep the record's
    lanes (tests/test_llm_chunk_handoff.py provokes it). Under speculation
    nothing chains, with a join as without one: every decode commits in
    its own step and counts `speculation`, the chunk is read at once and
    `joined` stays 0, as it did before."""
    eng, _ = steady(1)
    before = flushes(eng)
    chained = eng.stats()["chained_decode_dispatches"]
    eng.add_request(random_prompts((7,), seed=13)[0], max_new_tokens=30)
    eng.step()  # chains (the prompt is still waiting), then prefills it
    assert flushes(eng) == before
    assert eng.stats()["first_tokens_on_device"] == 1  # the first stream's
    eng.step()  # the decode batch is one wider than the record in flight
    assert flushes(eng) == before
    record = eng.flight_recorder.snapshot()["steps"][-1]
    assert record["chained"] and record["batch_size"] == 2
    stats = eng.stats()
    assert stats["chained_decode_dispatches"] == chained + 2
    assert stats["first_tokens_on_device"] == stats["prompts_prefilled"] == 2
    eng.step()
    assert eng.flight_recorder.snapshot()["steps"][-1]["chained"]
    drain(eng)

    spec = LLMEngine(
        TINY,
        EngineConfig(speculation="ngram", num_speculative_tokens=3, **BASE),
        seed=0,
    )
    spec.add_request([3, 4, 5, 3, 4, 5, 3, 4], max_new_tokens=12)
    for _ in range(3):
        spec.step()
    spec.add_request(random_prompts((7,), seed=13)[0], max_new_tokens=6)
    drain(spec)
    stats = spec.stats()
    assert stats["chained_decode_dispatches"] == 0
    assert stats["prompts_prefilled"] == 2
    assert stats["first_tokens_on_device"] == 0
    assert flushes(spec) == only("speculation", stats["pipeline_flushes"])


def test_flush_cause_lookahead_under_block_pressure():
    """A pool with no block to spare for the look-ahead: the chain is
    refused without preempting, the step flushes and schedules normally."""
    kw = dict(
        block_size=4, num_blocks=6, max_decode_slots=2, max_blocks_per_seq=8
    )
    eng = LLMEngine(TINY, EngineConfig(**kw), seed=0)
    # 5 usable blocks. Two prompts of 6 hold 2 blocks each; the first to
    # cross into its third block takes the last free one, and the other's
    # look-ahead is then refused.
    for p in random_prompts((6, 6), seed=14):
        eng.add_request(p, max_new_tokens=8)
    seen = 0
    while eng.has_work():
        before = flushes(eng)
        eng.step()
        after = flushes(eng)
        if after["lookahead"] > before["lookahead"]:
            seen += 1
            assert_only(eng, before, "lookahead")  # once, and no other
    assert seen, flushes(eng)
    assert eng.stats()["inflight_steps"] == 0
    assert eng.allocator.num_allocated == 0


def test_flush_cause_speculation_commits_every_decode_at_once():
    eng = LLMEngine(
        TINY,
        EngineConfig(speculation="ngram", num_speculative_tokens=3, **BASE),
        seed=0,
    )
    eng.generate([[3, 4, 5, 3, 4, 5, 3, 4]], max_new_tokens=10)
    stats = eng.stats()
    assert stats["async_scheduling"] is True
    assert stats["chained_decode_dispatches"] == 0
    assert stats["pipeline_flushes"] > 0
    assert flushes(eng) == only("speculation", stats["pipeline_flushes"])


def test_flush_cause_retry_when_a_commit_failed_midway():
    """A poisoned sequence stops the commit of the in-flight record; the
    retried step finds it already fetched and flushes (`retry`)."""
    from ray_tpu._private import fault_injection as fi

    eng, rids = steady(2)
    before = flushes(eng)
    spec = fi.inject("llm.decode.seq", match=rids[1])
    try:
        with pytest.raises(fi.InjectedFault):
            eng.step()  # chained, then the head's commit raises on slot 1
    finally:
        fi.remove(spec)
    assert eng.stats()["inflight_steps"] == 2
    eng.step()  # the retry: nothing chains, both records commit
    assert_only(eng, before, "retry")
    drain(eng)


# ---------------- the server's lock between steps ----------------


def test_server_lock_serves_a_waiter_before_the_thread_that_released_it():
    """LLMServer's step thread releases the lock between steps and takes
    it again at once; at depth 1 it no longer blocks on the device inside
    a step either. A thread already waiting (a submission, an abort) must
    get the lock before the step thread's next turn: with a plain Lock it
    waits out many turns, which is decode slots standing empty."""
    import threading
    import time

    from ray_tpu.llm.engine import LLMServer, _HandoffLock

    lock = _HandoffLock()
    cond = threading.Condition(lock)  # what LLMServer builds on it
    turns = []
    stop = threading.Event()

    def step_thread():
        while not stop.is_set():
            with cond:
                pass  # `with self._work:` — nothing to wait for
            with lock:  # `with self._lock:` — the step
                turns.append(time.perf_counter())
                time.sleep(0.002)

    thread = threading.Thread(target=step_thread, daemon=True)
    thread.start()
    try:
        waited = []
        for _ in range(20):
            time.sleep(0.003)  # arrive somewhere inside a step
            asked = time.perf_counter()
            with cond:
                got = time.perf_counter()
                cond.notify_all()
            waited.append(sum(1 for t in turns if asked < t < got))
    finally:
        stop.set()
        thread.join(timeout=5)
    # At most the step in progress ends, and never a whole further one
    # starts, between asking and getting.
    assert max(waited) == 0, waited
    assert not lock._is_owned()
    with lock:
        assert lock._is_owned()
    server = in_process(
        LLMServer(TINY, EngineConfig(**BASE), seed=0, warmup=False)
    )
    try:
        assert isinstance(server._lock, _HandoffLock)
    finally:
        server.shutdown()
