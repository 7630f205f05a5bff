"""Speculative decoding (ray_tpu.llm.spec): proposers + k-token verify.

The acceptance bar is the repo's idiom: greedy outputs must be
token-identical with speculation on vs off — across full/partial prefill,
copy-on-write, preemption-resume, and both paged-attention
implementations — because verification compares proposals against the
target model's own argmax and rolls back everything that disagrees.
Proposers only change speed, never output.
"""

import numpy as np
import pytest

import jax.numpy as jnp

from ray_tpu.llm import (
    EngineConfig,
    LLMEngine,
    LLMServer,
    NgramProposer,
    Request,
    Scheduler,
    Sequence,
    build_proposer,
)
from ray_tpu.llm.cache import BlockAllocator
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
    num_heads=4,
    embed_dim=64,
    max_seq_len=128,
    dtype=jnp.float32,
    attention_impl="reference",
)

KW = dict(
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


def spec_cfg(mode, **overrides):
    kw = dict(KW, speculation=mode, **overrides)
    if mode == "draft":
        kw.setdefault("draft_model_config", DRAFT)
    return EngineConfig(**kw)


# ---------------- config validation (fail-fast) ----------------


def test_config_speculation_knob_validation():
    with pytest.raises(ValueError, match="speculation"):
        EngineConfig(speculation="medusa")
    with pytest.raises(ValueError, match="num_speculative_tokens"):
        EngineConfig(speculation="ngram", num_speculative_tokens=0)
    # k must leave room for at least one committed token in the cache.
    with pytest.raises(ValueError, match="max_model_len"):
        EngineConfig(
            block_size=8, max_blocks_per_seq=2, speculation="ngram",
            num_speculative_tokens=16,
        )
    with pytest.raises(ValueError, match="ngram_max"):
        EngineConfig(speculation="ngram", ngram_max=1, ngram_min=2)
    with pytest.raises(ValueError, match="ngram_min"):
        EngineConfig(speculation="ngram", ngram_min=0)


def test_config_draft_model_required_iff_draft():
    with pytest.raises(ValueError, match="draft_model_config"):
        EngineConfig(speculation="draft")
    # ...and the mirror: a draft config with any OTHER mode is rejected
    # (a silently-ignored draft model is a misconfiguration).
    with pytest.raises(ValueError, match="draft_model_config"):
        EngineConfig(speculation="ngram", draft_model_config=DRAFT)
    with pytest.raises(ValueError, match="draft_model_config"):
        EngineConfig(draft_model_config=DRAFT)
    assert (
        EngineConfig(
            speculation="draft", draft_model_config=DRAFT
        ).draft_model_config
        is DRAFT
    )


def test_config_speculation_rejects_non_greedy_sampling():
    """Rejection sampling is not implemented: speculation + non-greedy
    must fail fast at config time with a speculation-specific message."""
    with pytest.raises(ValueError, match="greedy sampling"):
        EngineConfig(speculation="ngram", sampling="temperature")
    with pytest.raises(ValueError, match="greedy"):
        EngineConfig(sampling="temperature")


def test_config_verify_buckets():
    ecfg = EngineConfig(speculation="ngram", num_speculative_tokens=4)
    assert ecfg.verify_buckets() == (2, 3, 5)
    assert ecfg.verify_bucket_for(2) == 2
    assert ecfg.verify_bucket_for(4) == 5
    with pytest.raises(ValueError, match="verify"):
        ecfg.verify_bucket_for(6)
    assert EngineConfig().verify_buckets() == ()
    assert EngineConfig(
        speculation="ngram", num_speculative_tokens=1
    ).verify_buckets() == (2,)


# ---------------- n-gram proposer ----------------


def test_ngram_proposer_prompt_lookup():
    p = NgramProposer(ngram_max=3, ngram_min=1)
    # Tail [7, 8, 9] recurs earlier; propose what followed it.
    assert p.match([7, 8, 9, 1, 2, 3, 7, 8, 9], k=3) == [1, 2, 3]
    # Truncated to k.
    assert p.match([7, 8, 9, 1, 2, 3, 7, 8, 9], k=2) == [1, 2]
    # Most recent occurrence wins (recency predicts best).
    assert p.match([5, 1, 9, 9, 5, 2, 9, 9, 5], k=1) == [2]
    # No earlier occurrence of any tail n-gram -> no proposal.
    assert p.match([1, 2, 3, 4, 5], k=4) == []
    # Pure repetition: the deepest overlap match predicts it continuing
    # for the full k (a most-recent-only scan would propose 1 token).
    assert p.match([6, 6, 6, 6, 6, 6, 6, 6], k=3) == [6, 6, 6]
    # Too little history for a full window: best truncated match.
    assert p.match([6, 6, 6, 6], k=3) == [6]
    assert p.match([], k=4) == []
    with pytest.raises(ValueError, match="ngram_min"):
        NgramProposer(ngram_max=0)


def test_build_proposer_dispatch():
    assert build_proposer(EngineConfig()) is None
    ng = build_proposer(EngineConfig(speculation="ngram", ngram_max=5))
    assert isinstance(ng, NgramProposer) and ng.ngram_max == 5
    from ray_tpu.llm.spec.draft import DraftModelProposer

    dr = build_proposer(spec_cfg("draft"), seed=0)
    assert isinstance(dr, DraftModelProposer)
    assert dr.name == "draft"


class _StubReq:
    def __init__(self, rid):
        self.request_id = rid
        self.max_new_tokens = 16
        self.eos_id = None


class _StubSeq:
    def __init__(self, ids, rid="r1"):
        self.prefill_ids = list(ids)
        self.request = _StubReq(rid)
        self.generated = []


def test_draft_proposer_first_contact_chain_crossing_block_boundary():
    """Regression (RTL8xx triage): the draft mirror table is sized for
    the prompt PLUS the proposal chain (_reserve), but the first-contact
    prefill program's block vector holds exactly bucket_for(n) //
    block_size ids. Feeding the whole mirror table made numpy reject
    the scatter ("could not broadcast"), _catch_up swallowed the
    ValueError as a bucket overflow, and speculation was silently
    disabled for every prompt whose chain crossed a block boundary —
    including all block-aligned prompts. The proposer must return a
    full k-chain for both geometries."""
    k = 4
    # Block-aligned prompt: n == block_size, chain spills into block 2.
    dr = build_proposer(spec_cfg("draft"), seed=0)
    props = dr.propose([_StubSeq(range(1, 9))], k)
    assert len(props[0]) == k, (
        "draft proposer produced no chain for a block-aligned prompt"
    )
    # Mid-block prompt whose chain still crosses the boundary (n=7,
    # chain writes reach position 9).
    dr2 = build_proposer(spec_cfg("draft"), seed=0)
    props2 = dr2.propose([_StubSeq(range(1, 8))], k)
    assert len(props2[0]) == k
    # Steady state stays intact: commit the first proposal + a bonus
    # token and re-propose through the partial-prefill path.
    seq = _StubSeq(range(1, 9))
    dr3 = build_proposer(spec_cfg("draft"), seed=0)
    first = dr3.propose([seq], k)[0]
    seq.prefill_ids.extend([first[0], 42])
    seq.generated.extend([first[0], 42])
    again = dr3.propose([seq], k)
    assert len(again[0]) == k


# ---------------- scheduler: reserve + rollback ----------------


def test_scheduler_reserve_speculative_and_rollback():
    alloc = BlockAllocator(num_blocks=6, block_size=4)  # 5 usable
    sched = Scheduler(alloc, max_decode_slots=2, max_blocks_per_seq=4)
    seq = Sequence(Request("r", list(range(6)), max_new_tokens=8))
    sched.add(seq)
    assert sched.schedule_prefills(1) == [seq]
    seq.num_cached = 6  # prefill done: 2 blocks hold 6 tokens
    assert len(seq.block_table) == 2
    # Decode write (pos 6) fits block 2; 4 speculative tokens need
    # coverage through pos 10 -> 3 blocks; pool has 3 left.
    got = sched.reserve_speculative(seq, 4)
    assert got == 4 and len(seq.block_table) == 3
    # Accept 1 proposal + the correction: 8 tokens committed, the
    # speculative tail block is trimmed back to the pool.
    free_before = alloc.num_free
    sched.rollback(seq, 8)
    assert seq.num_cached == 8
    assert len(seq.block_table) == 2
    assert alloc.num_free == free_before + 1


def test_scheduler_reserve_speculative_shrinks_under_pressure():
    alloc = BlockAllocator(num_blocks=4, block_size=4)  # 3 usable
    sched = Scheduler(alloc, max_decode_slots=2, max_blocks_per_seq=4)
    seq = Sequence(Request("r", list(range(4)), max_new_tokens=8))
    sched.add(seq)
    assert sched.schedule_prefills(1) == [seq]
    seq.num_cached = 4
    hog = alloc.allocate(1)  # leave exactly 1 free block
    # 8 speculative tokens would need 2 more blocks; only 1 is free and
    # speculation never preempts -> shrunk to what one block covers.
    got = sched.reserve_speculative(seq, 8)
    assert got == 3  # positions 4..7 in the new block (write at 4 + 3)
    assert len(seq.block_table) == 2
    alloc.free(hog)
    # Length cap: max_blocks_per_seq bounds speculation regardless of pool.
    alloc2 = BlockAllocator(num_blocks=8, block_size=4)
    sched2 = Scheduler(alloc2, max_decode_slots=2, max_blocks_per_seq=4)
    seq2 = Sequence(Request("r2", list(range(14)), max_new_tokens=2))
    sched2.add(seq2)
    assert sched2.schedule_prefills(1) == [seq2]
    seq2.num_cached = 14  # 4 blocks cover the 16-token ceiling
    # Only position 15 is left inside the table: 1 speculative token.
    assert sched2.reserve_speculative(seq2, 8) == 1


# ---------------- engine acceptance: identical on vs off ----------------


def _acceptance_prompts():
    """Mixed workload: random lengths (full prefill), a repeated prompt
    (partial prefill via prefix-cache hit), a repeated 2-full-block prompt
    (CoW), and repetitive prompts the n-gram proposer can actually hit."""
    prompts = random_prompts((5, 11, 16, 3), seed=2)
    prompts.append(list(prompts[1]))  # partial-prefill path
    prompts.append(list(prompts[2]))  # CoW path
    prompts.append([7, 8, 9, 10] * 5)  # repetitive: ngram territory
    prompts.append([3, 4] * 8)
    return prompts


@pytest.mark.parametrize("mode", ["ngram", "draft"])
def test_engine_speculation_token_identical_and_accepts(mode):
    """Acceptance: greedy outputs are token-identical with speculation on
    vs off on the mixed full/partial/CoW workload, the proposer actually
    proposes and gets tokens accepted, every KV block is released, and
    the outputs match the unbatched ground truth."""
    prompts = _acceptance_prompts()
    base = LLMEngine(TINY, EngineConfig(**KW), seed=0)
    want = base.generate(prompts, max_new_tokens=8)
    eng = LLMEngine(TINY, spec_cfg(mode), seed=0)
    got = eng.generate(prompts, max_new_tokens=8)
    assert got == want
    stats = eng.stats()
    assert stats["speculation"] == mode
    assert stats["spec_verify_steps"] > 0
    assert stats["spec_proposed_tokens"] > 0
    assert stats["spec_accepted_tokens"] > 0
    assert 0.0 < stats["spec_acceptance_rate"] <= 1.0
    assert stats["prefix_cache_hit_tokens"] > 0  # partial/CoW paths ran
    assert eng.allocator.num_allocated == 0
    model = GPT(TINY)
    for prompt, out in zip(prompts, want):
        assert out == reference_greedy(model, base.runner.params, prompt, 8)


@pytest.mark.parametrize("mode", ["ngram", "draft"])
def test_engine_speculation_token_identical_under_preemption(mode):
    """A pool far too small for the working set forces recompute
    preemptions mid-speculation; resumes must stay token-identical and
    release the proposer's per-request state with the victim's blocks."""
    kw = dict(
        block_size=4, num_blocks=10, max_decode_slots=4, max_blocks_per_seq=8
    )
    prompts = random_prompts((6, 7, 5), seed=1)
    prompts.append([9, 2] * 3)
    base = LLMEngine(TINY, EngineConfig(**kw), seed=0)
    want = base.generate(prompts, max_new_tokens=12)
    cfg = dict(kw, speculation=mode)
    if mode == "draft":
        cfg["draft_model_config"] = DRAFT
    eng = LLMEngine(TINY, EngineConfig(**cfg), seed=0)
    got = eng.generate(prompts, max_new_tokens=12)
    assert got == want
    assert eng.stats()["num_preemptions"] > 0
    assert eng.allocator.num_allocated == 0
    if mode == "draft":
        assert eng._spec.allocator.num_allocated == 0
        assert eng._spec._state == {}


def test_engine_speculation_token_identical_pallas():
    """Both paged-attention implementations verify identically (CPU runs
    the same Pallas kernel in interpret mode)."""
    kw = dict(
        block_size=8, num_blocks=64, max_decode_slots=4, max_blocks_per_seq=4
    )
    prompts = random_prompts((5, 11), seed=31) + [[7, 8, 9, 10] * 4]
    outs = {}
    for impl in ("reference", "pallas"):
        eng = LLMEngine(
            TINY,
            EngineConfig(**kw, speculation="ngram", attn_impl=impl),
            seed=0,
        )
        outs[impl] = eng.generate(prompts, max_new_tokens=4)
        assert eng.stats()["spec_verify_steps"] > 0
    assert outs["pallas"] == outs["reference"]
    base = LLMEngine(TINY, EngineConfig(**kw), seed=0)
    assert outs["reference"] == base.generate(prompts, max_new_tokens=4)


def test_engine_speculation_eos_and_budget_respected():
    """A verify step never emits past max_new_tokens, and an accepted
    token equal to eos truncates the commit exactly where the plain
    decode loop would have stopped."""
    rep = [11, 12, 13] * 6
    base = LLMEngine(TINY, EngineConfig(**KW), seed=0)
    plain = base.generate([rep], max_new_tokens=10)[0]
    # An eos somewhere strictly inside the output exercises mid-commit
    # truncation (skip index 0: that would finish at the prefill).
    k = next(
        (i for i in range(1, len(plain)) if plain[i] not in plain[:i]), 1
    )
    eos = plain[k]
    want = base.generate([rep], max_new_tokens=10, eos_id=eos)[0]
    eng = LLMEngine(TINY, spec_cfg("ngram"), seed=0)
    assert eng.generate([rep], max_new_tokens=10, eos_id=eos)[0] == want
    # Budget: exactly max_new_tokens even when k would overshoot.
    assert len(eng.generate([rep], max_new_tokens=3)[0]) == 3
    assert eng.generate([rep], max_new_tokens=3)[0] == plain[:3]
    assert eng.allocator.num_allocated == 0


def test_engine_draft_sharing_target_weights_accepts_everything():
    """Self-speculation sanity: a draft with the target's own config and
    params proposes exactly the target argmax, so every proposal must
    survive verification (acceptance rate 1.0) and steps emit k+1
    tokens until the budget tail."""
    base = LLMEngine(TINY, EngineConfig(**KW), seed=0)
    eng = LLMEngine(
        TINY,
        EngineConfig(**KW, speculation="draft", draft_model_config=TINY,
                     num_speculative_tokens=3),
        seed=0,
        draft_params=base.runner.params,
    )
    # Same seed -> eng's target params == base params == draft params.
    prompts = random_prompts((5, 9), seed=4)
    got = eng.generate(prompts, max_new_tokens=8)
    assert got == base.generate(prompts, max_new_tokens=8)
    stats = eng.stats()
    assert stats["spec_acceptance_rate"] == 1.0
    assert stats["spec_tokens_per_verify_step"] > 1.0


def test_engine_speculation_int8_kv_identical_to_plain_int8():
    """Speculation composes with the int8 KV cache: same quantized pools,
    same scales through the verify scatter, outputs identical to the
    non-speculative int8 engine ON THIS PROMPT SET. Like partial prefill,
    verify lanes attend each other's fresh full-precision K/V while
    sequential decode reads them back quantized, so int8 identity is
    int8's usual within-tolerance contract (this test pins it at this
    scale), not a bit-guarantee — see EngineConfig.kv_cache_dtype."""
    base = LLMEngine(
        TINY, EngineConfig(**KW, kv_cache_dtype="int8"), seed=0
    )
    prompts = random_prompts((5, 11), seed=32) + [[5, 6, 7] * 5]
    want = base.generate(prompts, max_new_tokens=4)
    eng = LLMEngine(
        TINY,
        EngineConfig(**KW, kv_cache_dtype="int8", speculation="ngram"),
        seed=0,
    )
    got = eng.generate(prompts, max_new_tokens=4)
    assert got == want
    assert eng.stats()["spec_verify_steps"] > 0


def test_engine_abort_releases_draft_blocks():
    eng = LLMEngine(TINY, spec_cfg("draft"), seed=0)
    rid = eng.add_request([1, 2, 3] * 4, max_new_tokens=16)
    # Two steps, not three: the draft is the target's own seed, every
    # proposal is accepted, and a step commits 1 + 4 tokens after the
    # prefill's one (6, 11, 16), so the third step finishes the request and
    # its finish, not the abort below, would release the mirror.
    for _ in range(2):
        eng.step()
    assert eng.scheduler.is_active(rid)
    assert eng._spec.allocator.num_allocated > 0  # draft mirror is live
    assert eng.abort(rid)
    assert eng.allocator.num_allocated == 0
    assert eng._spec.allocator.num_allocated == 0
    assert eng._spec._state == {}


# ---------------- observability surfacing ----------------


def test_speculation_metrics_and_flight_records_exposed():
    """Acceptance-rate counters/gauge export through the Prometheus
    registry, the phase=verify histogram fires, stats() carries the
    speculation block, and verify steps land in the flight recorder with
    their proposed/accepted counts."""
    eng = LLMEngine(TINY, spec_cfg("ngram"), seed=0)
    eng.generate([[4, 5, 6] * 5], max_new_tokens=8)
    stats = eng.stats()
    assert stats["spec_verify_steps"] > 0
    assert stats["spec_tokens_per_verify_step"] > 1.0
    from ray_tpu.util.metrics import prometheus_text

    text = prometheus_text()
    for name in (
        "llm_engine_spec_proposed_tokens",
        "llm_engine_spec_accepted_tokens",
        "llm_engine_spec_acceptance_rate",
    ):
        assert name in text
    assert 'phase="verify"' in text
    records = eng.flight_recorder.snapshot()["steps"]
    verify_steps = [r for r in records if "speculation" in r]
    assert verify_steps
    rec = verify_steps[-1]["speculation"]
    assert rec["mode"] == "ngram"
    assert rec["proposed"] >= rec["accepted"] >= 0
    assert rec["emitted"] >= 1
    assert "verify" in verify_steps[-1]["phase"]


def test_llm_server_warmup_compiles_verify_buckets():
    """Init-time warmup must compile every verify bucket (and the draft
    model's programs) so the first speculative step under live traffic
    never cold-compiles; compile events carry the blame."""
    server = in_process(LLMServer(
        TINY,
        EngineConfig(
            block_size=8, num_blocks=64, max_decode_slots=4,
            max_blocks_per_seq=8, prefill_buckets=(8, 32),
            speculation="draft", draft_model_config=DRAFT,
        ),
        seed=0,
        warmup=True,
    ))
    events = server.flight_record()["compile_events"]
    programs = {(e["program"], e["bucket"]) for e in events}
    for s_bucket in (2, 3, 5):  # k=4 -> fed widths 2, 3, 5
        assert ("verify", s_bucket) in programs
    assert any(p == "proposer:draft" for p, _ in programs)
    out = server.generate([1, 2, 3] * 4, max_new_tokens=6)
    assert len(out["token_ids"]) == 6
    server.shutdown()
