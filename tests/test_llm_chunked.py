"""Chunked prefill: the per-step prompt-token budget
(EngineConfig.max_prefill_tokens_per_step) that splits long prompts into
block-aligned chunks fed through the existing partial-prefill buckets,
interleaved with the decode batch.

The acceptance oracle everywhere: greedy outputs are token-identical with
the budget set vs unset, across full/partial prefill, prefix-cache hits,
copy-on-write, recompute-preemption resume, speculation on/off (both
proposers), both attention implementations, and the int8 KV cache —
chunking is purely a latency-shaping scheduler change. What a chunk does
to the token gap on the chip is the benchmark's to read (PERF.md,
`token_gap_p90_ms` in the chat cell); here the tests pin the
mechanics: budget respected per step, monotonic chunk progress, decode
never starved, backlog observable, warmup covering every reachable
program.
"""

import numpy as np
import pytest

import jax.numpy as jnp

from ray_tpu.llm import (
    BlockAllocator,
    EngineConfig,
    LLMEngine,
    LLMServer,
    Request,
    Scheduler,
    Sequence,
)
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


def ecfg(budget, **kw):
    base = dict(
        block_size=8, num_blocks=64, max_decode_slots=4, max_blocks_per_seq=8
    )
    base.update(kw)
    return EngineConfig(max_prefill_tokens_per_step=budget, **base)


# ---------------- config knob ----------------


def test_budget_knob_validation_and_resolution():
    # Default is auto: a block-aligned quarter of max_model_len.
    assert EngineConfig().max_prefill_tokens_per_step == -1
    assert ecfg(-1).prefill_token_budget == 16  # 64 // 4
    # 0 / None turn chunking off entirely.
    assert ecfg(0).prefill_token_budget is None
    assert ecfg(None).prefill_token_budget is None
    # Explicit budgets must be block-aligned.
    assert ecfg(24).prefill_token_budget == 24
    with pytest.raises(ValueError, match="multiple of block_size"):
        ecfg(12)
    with pytest.raises(ValueError, match="-1 \\(auto\\)"):
        ecfg(-2)
    # Auto never resolves below one block even for tiny caches.
    tiny = EngineConfig(block_size=8, num_blocks=4, max_blocks_per_seq=2)
    assert tiny.prefill_token_budget == 8


def test_chunk_widths_are_reachable_bucket_subset():
    # Budget 16 → chunks feed at most 16 tokens → only buckets ≤ 16.
    cfg = ecfg(16)
    assert cfg.buckets() == (8, 16, 32, 64)
    assert cfg.chunk_widths() == (8, 16)
    # A budget between buckets reaches the bucket it pads into.
    assert ecfg(24).chunk_widths() == (8, 16, 32)
    # Off, or a budget >= the largest bucket: the whole table.
    assert ecfg(0).chunk_widths() == (8, 16, 32, 64)
    assert ecfg(64).chunk_widths() == (8, 16, 32, 64)
    # A budget above the largest (custom) bucket can't restrict anything.
    wide = EngineConfig(
        block_size=8, max_blocks_per_seq=16, prefill_buckets=(8, 16),
        max_prefill_tokens_per_step=32,
    )
    assert wide.chunk_widths() == (8, 16)


# ---------------- scheduler chunk state machine ----------------


def test_scheduler_chunk_plan_budget_and_alignment():
    alloc = BlockAllocator(num_blocks=64, block_size=8)
    sched = Scheduler(alloc, max_decode_slots=4, max_blocks_per_seq=8)
    a = Sequence(Request("a", list(range(40)), 4))
    b = Sequence(Request("b", list(range(20)), 4))
    sched.add(a)
    sched.add(b)
    sched.schedule_prefills(max_prefills=4)
    assert a.prefilling and b.prefilling
    assert sched.prefill_backlog_tokens() == 60
    # Budget 24 over (40, 20): oldest first — a gets 24, b nothing.
    plans = sched.schedule_prefill_chunks(24)
    assert [(s.request.request_id, t) for s, t in plans] == [("a", 24)]
    a.num_cached += 24
    assert sched.prefill_backlog_tokens() == 36
    # Next step: a's final 16, then b gets the block-aligned remainder 8.
    plans = sched.schedule_prefill_chunks(24)
    assert [(s.request.request_id, t) for s, t in plans] == [
        ("a", 16), ("b", 8),
    ]
    a.num_cached += 16
    b.num_cached += 8
    assert not a.prefilling
    # Decode batch excludes the still-prefilling b; a decodes.
    a.generated.append(1)  # the final chunk's token
    assert sched.schedule_decode() == [a]
    # b finishes in one more chunk; None budget = whole remainder.
    plans = sched.schedule_prefill_chunks(None)
    assert [(s.request.request_id, t) for s, t in plans] == [("b", 12)]
    b.num_cached += 12
    assert sched.prefill_backlog_tokens() == 0


def test_scheduler_chunk_plan_monotonic_progress_on_tiny_budget():
    alloc = BlockAllocator(num_blocks=64, block_size=8)
    sched = Scheduler(alloc, max_decode_slots=4, max_blocks_per_seq=8)
    seq = Sequence(Request("long", list(range(60)), 4))
    sched.add(seq)
    sched.schedule_prefills(max_prefills=1)
    fed = []
    while seq.prefilling:
        plans = sched.schedule_prefill_chunks(8)
        assert plans, "budget >= block_size must always make progress"
        (s, take), = plans
        assert take > 0
        fed.append(take)
        s.num_cached += take
    assert sum(fed) == 60
    assert all(t == 8 for t in fed[:-1])  # non-final chunks block-aligned


# ---------------- token identity: the acceptance oracle ----------------


def run_engine(budget, prompts, max_new=8, seed=0, **kw):
    eng = LLMEngine(TINY, ecfg(budget, **kw), seed=seed)
    out = eng.generate(prompts, max_new_tokens=max_new)
    return out, eng


def test_greedy_identical_chunked_vs_unchunked_and_ground_truth():
    """Budget on vs off vs the unbatched reference loop, over prompts
    spanning sub-budget, exactly-budget, and multi-chunk lengths."""
    prompts = random_prompts((3, 16, 23, 40, 55), seed=2)
    off, eng_off = run_engine(0, prompts)
    on, eng_on = run_engine(16, prompts)
    assert on == off
    assert eng_on.stats()["chunked_prefill_requests"] >= 3  # 23, 40, 55
    assert eng_off.stats()["chunked_prefill_requests"] == 0
    model = GPT(TINY)
    for p, toks in zip(prompts, on):
        assert toks == reference_greedy(model, eng_on.runner.params, p, 8)


def test_chunked_identical_with_prefix_cache_hits_and_cow():
    """Prefix-cache composition: chunking only ever splits the UNCACHED
    tail. A repeated long prompt admits with its prefix shared and chunks
    just the remainder; an exactly-repeated prompt takes the CoW path
    (a 1-token final chunk). Outputs identical to chunking off."""
    long_p = random_prompts((48,), seed=3)[0]
    first = [long_p, long_p[:32] + random_prompts((8,), seed=4)[0]]
    outs = {}
    for budget in (0, 16):
        eng = LLMEngine(TINY, ecfg(budget), seed=0)
        # Round 1 fills the cache; round 2 repeats the long prompt once
        # it is fully cached (the CoW path: a 1-token final chunk).
        outs[budget] = (
            eng.generate(first, max_new_tokens=8),
            eng.generate([long_p], max_new_tokens=8),
        )
    assert outs[16] == outs[0]
    stats = eng.stats()  # the chunked engine, from the loop's last round
    assert stats["prefix_cache_hit_tokens"] > 0
    assert stats["cow_blocks"] >= 1  # the exact repeat went CoW
    assert stats["chunked_prefill_requests"] >= 1  # the cold 48-token run


def test_chunked_identical_across_preempt_resume():
    """Recompute-preemption composition: a preempted request's resume
    re-chunks prompt+generated under the same budget, token-identically."""
    kw = dict(num_blocks=10, max_decode_slots=4, block_size=4,
              max_blocks_per_seq=8)
    prompts = random_prompts((6, 7, 5, 6), seed=1)
    off, eng_off = run_engine(0, prompts, max_new=12, **kw)
    on, eng_on = run_engine(8, prompts, max_new=12, **kw)
    assert eng_on.stats()["num_preemptions"] > 0  # pressure really engaged
    assert on == off


def test_chunked_identical_with_speculation_both_proposers():
    """Speculation composition: chunking must not perturb the verify
    path — greedy outputs identical spec on/off with chunking enabled,
    for both proposers (ngram and draft)."""
    draft_cfg = GPTConfig(
        vocab_size=128, num_layers=1, num_heads=4, embed_dim=64,
        max_seq_len=128, dtype=jnp.float32, attention_impl="reference",
    )
    # Repetitive prompts so proposers engage; one long enough to chunk.
    prompts = [[5, 6, 7] * 12, [9, 2] * 6, random_prompts((40,), seed=5)[0]]
    want, _ = run_engine(0, prompts)
    for spec_kw in (
        {"speculation": "ngram"},
        {"speculation": "draft", "draft_model_config": draft_cfg},
    ):
        got, eng = run_engine(16, prompts, **spec_kw)
        assert got == want, f"{spec_kw['speculation']} + chunking diverged"
        assert eng.stats()["spec_verify_steps"] > 0
        assert eng.stats()["chunked_prefill_requests"] >= 1


def test_chunked_identical_pallas_and_int8():
    """Hot-path composition: the chunk dispatches ride the same bucketed
    programs, so the pallas kernel (interpret mode on CPU) and the int8
    KV cache stay token-identical chunked vs not, like-for-like."""
    prompts = random_prompts((9, 26), seed=6)
    for kw in ({"attn_impl": "pallas"}, {"kv_cache_dtype": "int8"}):
        off, _ = run_engine(0, prompts, max_new=4, **kw)
        on, eng = run_engine(16, prompts, max_new=4, **kw)
        assert on == off, f"{kw} diverged under chunking"
        assert eng.stats()["chunked_prefill_requests"] >= 1


def test_verify_steps_interleave_with_inflight_chunks():
    """Chunked prefill × speculation, the mixed-step shape: while a long
    prompt streams in as chunks, an already-decoding repetitive request
    keeps taking VERIFY steps in the same engine iterations — the flight
    recorder shows prefill+verify steps, and the verify path's multi-token
    commits proceed under an in-flight chunk stream."""
    eng = LLMEngine(TINY, ecfg(8, speculation="ngram"), seed=0)
    rep_tokens = []
    eng.add_request(
        [5, 6, 7] * 6, max_new_tokens=16, on_token=rep_tokens.append
    )
    # Let the repetitive request reach steady speculation first.
    while eng.stats()["spec_verify_steps"] < 1:
        eng.step()
    eng.add_request(random_prompts((40,), seed=14)[0], max_new_tokens=4)
    while eng.has_work():
        eng.step()
    steps = eng.flight_recorder.snapshot()["steps"]
    mixed = [s for s in steps if s["phase"] == "prefill+verify"]
    assert mixed, [s["phase"] for s in steps]
    # A mixed step really carried both: a chunk within budget AND a
    # speculative commit for the decode-ready request.
    assert all(0 < s["tokens_in"] <= 8 for s in mixed)
    assert all(s["speculation"]["emitted"] >= 1 for s in mixed)
    # Both requests finished whole: chunking never starved the verifier.
    assert len(rep_tokens) == 16


# ---------------- budget + interleaving mechanics ----------------


def test_budget_respected_and_decode_interleaves():
    """The tentpole behavior, pinned from flight-recorder step records: no
    step feeds more prompt tokens than the budget, and while a long prompt
    streams in, already-decoding requests keep advancing one token per
    step (mixed prefill+decode steps) — decode is never starved."""
    eng = LLMEngine(TINY, ecfg(16), seed=0)
    short_tokens = []
    eng.add_request(
        random_prompts((5,), seed=7)[0], max_new_tokens=12,
        on_token=short_tokens.append,
    )
    eng.step()  # the short request is admitted and decoding
    progress = [len(short_tokens)]
    eng.add_request(random_prompts((55,), seed=8)[0], max_new_tokens=4)
    while eng.has_work():
        eng.step()
        progress.append(len(short_tokens))
    steps = eng.flight_recorder.snapshot()["steps"]
    assert all(s["tokens_in"] <= 16 for s in steps)
    mixed = [s for s in steps if s["phase"] == "prefill+decode"]
    assert mixed, "chunks must interleave with the decode batch"
    # One decode token per step for the short request while chunks ran
    # (until it finished): monotonic, no stalls.
    chunk_steps = [s for s in steps if s["num_prefills"]]
    assert len(chunk_steps) >= 4  # 55 tokens / 16-token budget
    for before, after in zip(progress, progress[1:]):
        if before < 12:
            assert after == before + 1
    # Chunk records carry their index and finality, in order.
    chunks = [p for s in steps for p in s["prefills"]
              if p["tokens"] > 0 and s["num_prefills"]]
    long_chunks = [c for c in chunks if c["chunk"] > 0 or not c["final"]]
    assert [c["chunk"] for c in long_chunks] == list(range(len(long_chunks)))
    assert [c["final"] for c in long_chunks[:-1]] == [False] * (
        len(long_chunks) - 1
    )
    assert long_chunks[-1]["final"]


def test_prefill_backlog_gauge_and_stats():
    from ray_tpu.util import metrics

    eng = LLMEngine(TINY, ecfg(8), seed=0)
    eng.add_request(random_prompts((40,), seed=9)[0], max_new_tokens=2)
    eng.add_request(random_prompts((20,), seed=10)[0], max_new_tokens=2)
    backlogs = []
    while eng.has_work():
        backlogs.append(eng.step()["prefill_backlog_tokens"])
    # The backlog drains monotonically at <= budget per step and ends dry.
    assert backlogs[0] > 0
    assert all(b2 <= b1 for b1, b2 in zip(backlogs, backlogs[1:]))
    assert all(b1 - b2 <= 8 for b1, b2 in zip(backlogs, backlogs[1:]))
    assert backlogs[-1] == 0
    stats = eng.stats()
    assert stats["prefill_token_budget"] == 8
    assert stats["prefill_backlog_tokens"] == 0
    assert stats["prefill_chunk_dispatches"] >= 8  # 60 tokens / 8
    assert "llm_engine_prefill_backlog_tokens" in metrics.prometheus_text()


def test_chunking_off_restores_single_dispatch_prefills():
    eng = LLMEngine(TINY, ecfg(None), seed=0)
    eng.generate([random_prompts((55,), seed=11)[0]], max_new_tokens=2)
    steps = eng.flight_recorder.snapshot()["steps"]
    prefills = [p for s in steps for p in s["prefills"]]
    assert len(prefills) == 1  # one dispatch for the whole 55-token prompt
    assert prefills[0]["tokens"] == 55 and prefills[0]["final"]
    assert eng.stats()["prefill_chunk_dispatches"] == 1
    assert eng.stats()["chunked_prefill_requests"] == 0


# ---------------- warmup: no cold compile under a chunked serve ----------


def test_warmup_without_prefix_caching_still_compiles_chunk_programs():
    """With prefix caching OFF the generate-based warmup never touches the
    partial-prefill family — but chunked continuation chunks dispatch it.
    The chunk warmup pass must cover it so a chunked serve stays compile-
    free (asserted via the jit caches, which the serve must not grow)."""
    cfg = EngineConfig(
        block_size=8, num_blocks=64, max_decode_slots=4,
        max_blocks_per_seq=8, enable_prefix_caching=False,
        max_prefill_tokens_per_step=16,
    )
    server = in_process(LLMServer(TINY, cfg, seed=0, warmup=True))
    programs = {
        (c["program"], c["bucket"])
        for c in server.flight_record()["compile_events"]
    }
    for w in cfg.chunk_widths():
        assert ("chunk_prefill", w) in programs
    runner = server._engine.runner
    jit_fns = (runner._prefill_fn, runner._prefill_suffix_fn,
               runner._decode_fn)
    sizes = [f._cache_size() for f in jit_fns]
    out = server.generate(
        random_prompts((40,), seed=12)[0], max_new_tokens=4, timeout_s=60.0
    )
    assert len(out["token_ids"]) == 4
    assert [f._cache_size() for f in jit_fns] == sizes
    server.shutdown()
