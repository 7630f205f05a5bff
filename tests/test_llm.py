"""ray_tpu.llm — continuous batching engine over the paged KV cache.

Covers the block allocator invariants, scheduler admission/preemption under
cache pressure, token-identical greedy generation vs an unbatched reference
loop, streaming order under concurrent requests, and the engine-actor /
Serve paths.
"""

import threading
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import ray_tpu
from ray_tpu.llm import (
    BlockAllocator,
    CacheOutOfBlocks,
    EngineConfig,
    LLMEngine,
    LLMServer,
    Request,
    Scheduler,
    Sequence,
    blocks_for_tokens,
    prefix_block_hashes,
)
from ray_tpu.models.gpt import GPT, GPTConfig
from ray_tpu.ops import mha_reference, paged_attention
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
    """Unbatched full-forward generation loop: the numeric ground truth.

    Runs at one fixed padded length so XLA compiles a single program
    (causality makes right-padding inert for the positions that matter)."""
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


# ---------------- block allocator ----------------


def test_allocator_alloc_free_reuse():
    alloc = BlockAllocator(num_blocks=8, block_size=4)
    assert alloc.num_usable == 7  # block 0 reserved
    a = alloc.allocate(3)
    assert len(a) == 3 and 0 not in a
    assert alloc.num_free == 4
    assert alloc.utilization() == pytest.approx(3 / 7)
    alloc.free(a)
    assert alloc.num_free == 7 and alloc.num_allocated == 0
    # LIFO reuse: freed blocks are handed out again first.
    b = alloc.allocate(3)
    assert set(b) == set(a)


def test_allocator_oom_and_double_free():
    alloc = BlockAllocator(num_blocks=4, block_size=4)
    blocks = alloc.allocate(3)
    assert not alloc.can_allocate(1)
    with pytest.raises(CacheOutOfBlocks):
        alloc.allocate(1)
    alloc.free(blocks[:1])
    with pytest.raises(ValueError, match="double free"):
        alloc.free(blocks[:1])
    # Freeing a never-allocated id (incl. the null block) is rejected.
    with pytest.raises(ValueError):
        alloc.free([0])


def test_blocks_for_tokens():
    assert blocks_for_tokens(1, 8) == 1
    assert blocks_for_tokens(8, 8) == 1
    assert blocks_for_tokens(9, 8) == 2


def test_allocator_free_duplicate_ids_is_atomic():
    """A duplicate id anywhere in one free() call must fail before any
    mutation — a bad free cannot leave the allocator half-updated."""
    alloc = BlockAllocator(num_blocks=8, block_size=4)
    a = alloc.allocate(3)
    before = (alloc.num_free, alloc.num_allocated)
    with pytest.raises(ValueError, match="more than once"):
        alloc.free([a[0], a[1], a[0]])
    assert (alloc.num_free, alloc.num_allocated) == before
    alloc.free(a)  # the same blocks still free cleanly afterwards
    assert alloc.num_allocated == 0 and alloc.num_free == 7


def test_allocator_prefix_cache_match_touch_evict():
    """Content-addressed reuse: chain-keyed full blocks are matchable while
    referenced or evictable, revivable via touch, and evicted LRU-first —
    never while refcounted."""
    alloc = BlockAllocator(num_blocks=8, block_size=4)  # 7 usable
    ids = list(range(12))  # 3 full blocks
    hashes = prefix_block_hashes(ids, 4)
    assert len(hashes) == 3
    blocks = alloc.allocate(3)
    for b, h in zip(blocks, hashes):
        assert alloc.register(b, h)
    assert alloc.match_prefix(hashes) == blocks
    # A divergent token stream matches only the shared block-prefix; the
    # chain key makes equal block contents at different depths distinct.
    diverged = prefix_block_hashes(ids[:8] + [7, 7, 7, 7], 4)
    assert alloc.match_prefix(diverged) == blocks[:2]
    assert prefix_block_hashes([9] * 8, 4)[1] != prefix_block_hashes(
        [9] * 4, 4
    )[0]
    alloc.free(blocks)
    # Freed-but-keyed blocks park evictable: content reusable, space
    # reclaimable.
    assert alloc.num_allocated == 0 and alloc.num_evictable == 3
    assert alloc.num_free == 7
    m = alloc.match_prefix(hashes)
    assert m == blocks
    alloc.touch(m)  # revive from the evictable pool
    assert alloc.num_evictable == 0 and alloc.refcount(m[0]) == 1
    alloc.touch([m[0]])  # shared: refcount, not copy
    assert alloc.refcount(m[0]) == 2
    alloc.free(m)
    assert alloc.refcount(m[0]) == 1  # still held by the second ref
    alloc.free([m[0]])
    assert alloc.num_evictable == 3
    # Pressure: the plain free list (4 blocks) is drained first...
    hot = alloc.allocate(4)
    assert alloc.num_evictable == 3
    # ...then evictable blocks are reclaimed in LRU order — blocks[0] held
    # its extra ref longest, so it was freed last and evicts last — and
    # eviction drops their keys; refcounted blocks are never handed out.
    assert alloc.allocate(3) == [blocks[1], blocks[2], blocks[0]]
    assert alloc.num_evictable == 0 and alloc.match_prefix(hashes) == []
    assert alloc.num_evictions == 3
    with pytest.raises(CacheOutOfBlocks):
        alloc.allocate(1)
    assert set(hot) & set(blocks) == set()


def test_allocator_eviction_policy_knobs():
    with pytest.raises(ValueError, match="eviction_policy"):
        BlockAllocator(4, 4, eviction_policy="bogus")
    with pytest.raises(ValueError, match="prefix_eviction_policy"):
        EngineConfig(prefix_eviction_policy="bogus")
    # FIFO evicts by registration order even when a block was recently
    # used; LRU (the default, exercised above) evicts least-recently-freed.
    alloc = BlockAllocator(num_blocks=6, block_size=4, eviction_policy="fifo")
    a = alloc.allocate(2)
    h = prefix_block_hashes(list(range(8)), 4)
    alloc.register(a[0], h[0])
    alloc.register(a[1], h[1])
    alloc.free(a)
    alloc.touch([a[0]])  # re-use a[0]: LRU would now evict a[1] first
    alloc.free([a[0]])
    alloc.allocate(3)  # drain the plain free list
    assert alloc.allocate(1) == [a[0]]  # FIFO: first registered goes first


def test_engine_config_buckets():
    ecfg = EngineConfig(block_size=8, max_blocks_per_seq=8)
    assert ecfg.max_model_len == 64
    assert ecfg.buckets() == (8, 16, 32, 64)
    assert ecfg.bucket_for(3) == 8
    assert ecfg.bucket_for(17) == 32
    with pytest.raises(ValueError, match="exceeds max_model_len"):
        ecfg.bucket_for(65)
    with pytest.raises(ValueError, match="multiple of block_size"):
        EngineConfig(block_size=8, prefill_buckets=(12,))


# ---------------- scheduler ----------------


def _seq(prompt_len, max_new=4, rid=None):
    rid = rid or f"r{prompt_len}-{time.monotonic_ns()}"
    return Sequence(Request(rid, list(range(prompt_len)), max_new))


def test_scheduler_admission_respects_slots_and_cache():
    alloc = BlockAllocator(num_blocks=5, block_size=4)  # 4 usable
    sched = Scheduler(alloc, max_decode_slots=2, max_blocks_per_seq=4)
    s1, s2, s3 = _seq(8), _seq(4), _seq(4)
    for s in (s1, s2, s3):
        sched.add(s)
    admitted = sched.schedule_prefills(max_prefills=8)
    # s1 takes 2 blocks, s2 takes 1; s3 is slot-blocked (2 slots).
    assert admitted == [s1, s2]
    assert len(alloc._allocated) == 3
    sched.finish(s2, "length")
    # Slot freed; s3 admitted with the cache's remaining room.
    assert sched.schedule_prefills(max_prefills=8) == [s3]


def test_scheduler_preempts_youngest_under_pressure():
    alloc = BlockAllocator(num_blocks=4, block_size=4)  # 3 usable
    sched = Scheduler(alloc, max_decode_slots=2, max_blocks_per_seq=4)
    old, young = _seq(4, rid="old"), _seq(4, rid="young")
    sched.add(old)
    sched.add(young)
    assert sched.schedule_prefills(8) == [old, young]
    old.num_cached = 4  # both need a 2nd block next decode; 1 block free
    young.num_cached = 4
    survivors = sched.schedule_decode()
    assert survivors == [old]
    assert young.num_preemptions == 1 and young.num_cached == 0
    assert sched.waiting[0] is young  # resumes at the front of the queue


def test_scheduler_preempted_seq_folds_generated_into_prompt():
    seq = _seq(3)
    seq.generated = [7, 9]
    assert seq.prefill_ids == [0, 1, 2, 7, 9]
    assert seq.last_token == 9


# ---------------- paged attention op ----------------


def _stored(pool, layer=1):
    """A per-layer [N, bs, H, D] pool as the ops take it: [L, N, bs, H*D],
    here at `layer` of two, the other layer loud."""
    flat = pool.reshape(pool.shape[0], pool.shape[1], -1)
    layers = [jnp.full_like(flat, 1e3)] * 2
    layers[layer] = flat
    return jnp.stack(layers)


def test_paged_attention_matches_dense():
    rng = np.random.RandomState(0)
    bs, nblocks, nb, h, d = 4, 12, 3, 2, 8
    ctx = 9  # tokens in cache (spans 3 blocks, last partially filled)
    k_cache = jnp.asarray(rng.randn(nblocks, bs, h, d), jnp.float32)
    v_cache = jnp.asarray(rng.randn(nblocks, bs, h, d), jnp.float32)
    q = jnp.asarray(rng.randn(1, 1, h, d), jnp.float32)
    new_k = jnp.asarray(rng.randn(1, 1, h, d), jnp.float32)
    new_v = jnp.asarray(rng.randn(1, 1, h, d), jnp.float32)
    table = jnp.asarray([[5, 2, 7]], jnp.int32)
    out = paged_attention(
        q, _stored(k_cache), _stored(v_cache), table,
        jnp.asarray([ctx], jnp.int32), new_k=new_k, new_v=new_v, layer=1,
    )
    # Dense equivalent: gather the context rows in order + the new token.
    k_seq = k_cache[table[0]].reshape(1, nb * bs, h, d)[:, :ctx]
    v_seq = v_cache[table[0]].reshape(1, nb * bs, h, d)[:, :ctx]
    k_full = jnp.concatenate([k_seq, new_k], axis=1)
    v_full = jnp.concatenate([v_seq, new_v], axis=1)
    want = mha_reference(q, k_full, v_full)  # 1 query over ctx+1 keys
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(want), atol=1e-5
    )


def test_paged_attention_partial_prefill_matches_dense():
    """Multi-token queries (prefix-aware partial prefill): paged attention
    over the cached prefix plus a causal mask among the new tokens must
    equal per-position dense attention over the growing sequence."""
    rng = np.random.RandomState(1)
    bs, nblocks, nb, h, d = 4, 12, 3, 2, 8
    ctx, s_new = 8, 3  # 8 cached prefix tokens (2 blocks), 3 suffix tokens
    k_cache = jnp.asarray(rng.randn(nblocks, bs, h, d), jnp.float32)
    v_cache = jnp.asarray(rng.randn(nblocks, bs, h, d), jnp.float32)
    q = jnp.asarray(rng.randn(1, s_new, h, d), jnp.float32)
    new_k = jnp.asarray(rng.randn(1, s_new, h, d), jnp.float32)
    new_v = jnp.asarray(rng.randn(1, s_new, h, d), jnp.float32)
    table = jnp.asarray([[5, 2, 0]], jnp.int32)  # padded past the prefix
    out = paged_attention(
        q, _stored(k_cache), _stored(v_cache), table,
        jnp.asarray([ctx], jnp.int32), new_k=new_k, new_v=new_v, layer=1,
    )
    k_seq = k_cache[table[0]].reshape(1, nb * bs, h, d)[:, :ctx]
    v_seq = v_cache[table[0]].reshape(1, nb * bs, h, d)[:, :ctx]
    for i in range(s_new):
        k_full = jnp.concatenate([k_seq, new_k[:, : i + 1]], axis=1)
        v_full = jnp.concatenate([v_seq, new_v[:, : i + 1]], axis=1)
        want = mha_reference(q[:, i : i + 1], k_full, v_full)
        np.testing.assert_allclose(
            np.asarray(out[:, i : i + 1]), np.asarray(want), atol=1e-5
        )


# ---------------- engine end-to-end ----------------


@pytest.fixture(scope="module")
def tiny_engine():
    ecfg = EngineConfig(
        block_size=8, num_blocks=64, max_decode_slots=4, max_blocks_per_seq=8
    )
    return LLMEngine(TINY, ecfg, seed=0)


def test_engine_request_validation(tiny_engine):
    with pytest.raises(ValueError, match="non-empty"):
        tiny_engine.add_request([], max_new_tokens=4)
    with pytest.raises(ValueError, match="max_model_len"):
        tiny_engine.add_request([1] * 60, max_new_tokens=8)
    with pytest.raises(ValueError, match="max_new_tokens"):
        tiny_engine.add_request([1], max_new_tokens=0)


def test_engine_rejects_never_admittable_requests():
    """Requests that could never be (re)admitted must fail fast instead of
    spinning the engine loop forever."""
    # Lifetime outgrows the block pool (3 usable blocks = 24 tokens).
    small_pool = LLMEngine(
        TINY,
        EngineConfig(block_size=8, num_blocks=4, max_blocks_per_seq=8),
        seed=0,
    )
    with pytest.raises(ValueError, match="num_blocks"):
        small_pool.add_request([1] * 20, max_new_tokens=10)
    # Preemption-resume prefill (prompt+generated) outgrows custom buckets.
    small_buckets = LLMEngine(
        TINY,
        EngineConfig(
            block_size=8, num_blocks=64, max_blocks_per_seq=16,
            prefill_buckets=(8, 16),
        ),
        seed=0,
    )
    with pytest.raises(ValueError, match="bucket"):
        small_buckets.add_request([1] * 12, max_new_tokens=8)


def test_engine_greedy_matches_reference_loop(tiny_engine):
    """Continuous batching with mixed prompt/output lengths is
    token-identical to unbatched full-forward generation."""
    eng = tiny_engine
    prompts = random_prompts((5, 11, 3, 17, 8, 1), seed=2)
    outs = eng.generate(prompts, max_new_tokens=8)
    model = GPT(TINY)
    for prompt, out in zip(prompts, outs):
        assert out == reference_greedy(model, eng.runner.params, prompt, 8)


def test_engine_eos_stops_generation(tiny_engine):
    eng = tiny_engine
    prompt = random_prompts((9,), seed=3)[0]
    free = eng.allocator.num_free
    out = eng.generate([prompt], max_new_tokens=8)[0]
    # Re-run with eos set to the 3rd generated token: generation must stop
    # there (inclusive) and release every cache block.
    # Pick the first token value that has not appeared before it, so the
    # stop point is unambiguous (k > 0 exercises decode-time eos, k == 0
    # the prefill-emission path).
    k = max(
        (i for i in range(len(out)) if out[i] not in out[:i]), default=0
    )
    eos = out[k]
    out_eos = eng.generate([prompt], max_new_tokens=8, eos_id=eos)[0]
    assert out_eos == out[: k + 1]
    assert eng.allocator.num_free == free


def test_engine_streaming_order_interleaves(tiny_engine):
    """Iteration-level batching produces token i of every active request
    before token i+1 of any (per-request order is trivially preserved;
    cross-request production must interleave, not serialize)."""
    eng = tiny_engine
    prompts = random_prompts((4, 6, 5), seed=4)
    order = []
    for i, p in enumerate(prompts):
        eng.add_request(
            p,
            max_new_tokens=6,
            on_token=lambda t, i=i: order.append(i),
        )
    while eng.has_work():
        eng.step()
    counts = {i: 0 for i in range(len(prompts))}
    progress = []
    for i in order:
        counts[i] += 1
        progress.append(dict(counts))
    assert all(c == 6 for c in counts.values())
    # Interleaved, not serialized: the last-admitted request produces its
    # first token well before the first request finishes...
    first_of_last = order.index(2)
    last_of_first = max(i for i, r in enumerate(order) if r == 0)
    assert first_of_last < last_of_first
    # ...and once every request is active, production skew stays bounded by
    # the admission stagger (1 prefill/step, +1 decode token that step, +1
    # since PR 48: a prompt that finds the batch chained joins the decode
    # dispatched after the one in flight, where a flush took it at once).
    for snap in progress:
        if min(snap.values()) >= 1:
            assert max(snap.values()) - min(snap.values()) <= 4


def test_engine_preemption_recompute_matches_reference():
    """A cache far too small for the working set forces preemption; the
    recompute path must not change any emitted token."""
    ecfg = EngineConfig(
        block_size=4, num_blocks=10, max_decode_slots=4, max_blocks_per_seq=8
    )
    eng = LLMEngine(TINY, ecfg, seed=0)
    prompts = random_prompts((6, 7, 5, 6), seed=1)
    outs = eng.generate(prompts, max_new_tokens=12)
    assert eng.stats()["preemptions"] > 0
    model = GPT(TINY)
    for prompt, out in zip(prompts, outs):
        assert out == reference_greedy(model, eng.runner.params, prompt, 12)
    # All blocks returned once everything finished.
    assert eng.allocator.num_allocated == 0


@pytest.mark.parametrize("depth1", (False, True), ids=("depth0", "depth1"))
def test_engine_abort_releases_blocks(depth1):
    """An abort releases every block at once, at either pipeline depth.
    At depth 1 the aborted request's decode is still in flight: the
    engine has work until one more step has drained that record, which
    emits nothing and takes no block."""
    eng = LLMEngine(
        TINY,
        EngineConfig(
            block_size=8, num_blocks=64, max_decode_slots=4,
            max_blocks_per_seq=8, async_scheduling=depth1,
        ),
        seed=0,
    )
    stream = []
    rid = eng.add_request(
        random_prompts((9,), seed=5)[0], max_new_tokens=8,
        on_token=stream.append,
    )
    eng.step()  # prefill admits it
    assert eng.allocator.num_allocated > 0
    assert eng.abort(rid)
    assert eng.allocator.num_allocated == 0
    emitted = list(stream)
    if depth1:
        assert eng.stats()["inflight_steps"] == 1
        assert eng.has_work()
        eng.step()  # drains the orphaned record
        assert eng.stats()["inflight_steps"] == 0
        assert eng.allocator.num_allocated == 0
    assert stream == emitted  # nothing reaches an aborted request's stream
    assert not eng.has_work()
    assert not eng.abort("nonexistent")


def test_engine_prefix_cache_hit_on_repeated_prompt(tiny_engine):
    """A repeated prompt's full blocks are served from the prefix cache
    (only the tail is recomputed) with identical greedy output, and the
    hit/evictable metric series are exported."""
    eng = tiny_engine
    prompt = random_prompts((20,), seed=11)[0]
    out1 = eng.generate([prompt], max_new_tokens=6)[0]
    hits_before = eng.stats()["prefix_cache_hit_tokens"]
    out2 = eng.generate([prompt], max_new_tokens=6)[0]
    assert out2 == out1
    stats = eng.stats()
    # 20-token prompt = 2 full blocks (16 tokens) cached + 4-token tail.
    assert stats["prefix_cache_hit_tokens"] - hits_before == 16
    assert 0 < stats["prefix_cache_hit_rate"] < 1
    assert stats["evictable_blocks"] > 0  # finished seqs stay cached
    from ray_tpu.util.metrics import prometheus_text

    text = prometheus_text()
    for name in (
        "llm_engine_prefix_cache_hit_tokens",
        "llm_engine_prefix_cache_hit_rate",
        "llm_engine_evictable_blocks",
        "llm_engine_preemptions",
    ):
        assert name in text


def test_engine_abort_waiting_never_admitted_sequence(tiny_engine):
    eng = tiny_engine
    allocated_before = eng.allocator.num_allocated
    rid = eng.add_request(random_prompts((9,), seed=12)[0], max_new_tokens=4)
    assert eng.abort(rid)  # still waiting: no blocks were ever mapped
    assert eng.allocator.num_allocated == allocated_before
    assert not eng.has_work()
    assert not eng.abort(rid)


def test_engine_cow_divergence_on_shared_prefix_block(tiny_engine):
    """Two live sequences share a fully-cached prompt: the second one's
    re-prefill copy-on-writes the last shared block (its final-token K/V
    write would otherwise corrupt the first sequence's cache), then the
    two diverge into private tails."""
    eng = tiny_engine
    prompt = random_prompts((16,), seed=13)[0]  # exactly 2 full blocks
    a_toks, b_toks = [], []
    eng.add_request(prompt, max_new_tokens=8, on_token=a_toks.append)
    eng.step()  # A prefills; its two full blocks are published
    seq_a = eng.scheduler.running[0]
    table_a = list(seq_a.block_table)
    cows_before = eng.scheduler.num_cow_blocks
    eng.add_request(prompt, max_new_tokens=3, on_token=b_toks.append)
    eng.step()  # B admits fully-cached: shares block 0, CoWs block 1
    seq_b = eng.scheduler.running[-1]
    assert seq_b is not seq_a
    assert eng.scheduler.num_cow_blocks == cows_before + 1
    assert seq_b.block_table[0] == table_a[0]  # shared, refcounted
    assert eng.allocator.refcount(table_a[0]) == 2
    assert seq_b.block_table[1] != table_a[1]  # private CoW copy
    while eng.has_work():
        eng.step()
    # B's writes never touched A's blocks: both continuations are the
    # unbatched ground truth (B's is a prefix of A's — same prompt).
    ref = reference_greedy(GPT(TINY), eng.runner.params, prompt, 8)
    assert a_toks == ref
    assert b_toks == ref[:3]


@pytest.mark.parametrize("depth1", (False, True), ids=("depth0", "depth1"))
def test_engine_preempt_resume_hits_prefix_cache_and_matches_uncached(depth1):
    """Acceptance: a mixed prefill/decode/preemption workload is
    token-identical with prefix caching on and off — and with caching on,
    a preempted victim's resume re-prefill hits its own still-cached
    blocks instead of recomputing from token 0, at either pipeline depth.

    The victim's blocks stay cached only until pressure evicts them
    (Scheduler.schedule_decode), and the depth moves the step at which
    each preemption falls: with the 9 usable blocks and the four prompts
    this test had until PR 31, depth 0 resumed one victim of two from the
    cache and depth 1 its one victim from none (at 8 usable blocks it was
    the other way round). These prompts resume from the cache at both."""
    kw = dict(
        block_size=4, num_blocks=9, max_decode_slots=4, max_blocks_per_seq=8,
        async_scheduling=depth1,
    )
    prompts = random_prompts((6, 7, 9, 10), seed=1)
    cached = LLMEngine(
        TINY, EngineConfig(**kw, enable_prefix_caching=True), seed=0
    )
    outs_cached = cached.generate(prompts, max_new_tokens=12)
    stats = cached.stats()
    assert stats["num_preemptions"] > 0
    assert stats["prefix_cache_hit_tokens"] > 0  # resumes reused blocks
    assert cached.allocator.num_allocated == 0
    uncached = LLMEngine(
        TINY, EngineConfig(**kw, enable_prefix_caching=False), seed=0
    )
    outs_uncached = uncached.generate(prompts, max_new_tokens=12)
    assert uncached.stats()["num_preemptions"] > 0
    assert uncached.stats()["prefix_cache_hit_tokens"] == 0
    assert uncached.stats()["evictable_blocks"] == 0
    assert outs_cached == outs_uncached


def test_engine_greedy_identical_pallas_vs_reference():
    """Acceptance: greedy outputs are token-identical with the fused
    Pallas paged-attention kernel on vs off (CPU interpret mode runs the
    same kernel the TPU compiles), across full prefill, partial prefill
    (repeated prompt → prefix-cache hit), CoW, and decode — and both match
    the unbatched full-forward ground truth."""
    # max_blocks_per_seq bounds the kernel grid (nb + 1 sequential steps
    # per batch row): keep the table narrow so the interpret-mode compile
    # stays well under the tier-1 budget.
    kw = dict(
        block_size=8, num_blocks=64, max_decode_slots=4, max_blocks_per_seq=4
    )
    prompts = random_prompts((5, 11, 16), seed=31)
    prompts.append(list(prompts[1]))  # repeat 11-tok: partial-prefill path
    prompts.append(list(prompts[2]))  # repeat 2 full blocks: CoW path
    outs = {}
    for impl in ("reference", "pallas"):
        eng = LLMEngine(TINY, EngineConfig(**kw, attn_impl=impl), seed=0)
        outs[impl] = eng.generate(prompts, max_new_tokens=4)
        assert eng.stats()["attn_impl"] == impl
        assert eng.stats()["prefix_cache_hit_tokens"] > 0
    assert outs["pallas"] == outs["reference"]
    model = GPT(TINY)
    eng = LLMEngine(TINY, EngineConfig(**kw), seed=0)
    for prompt, out in zip(prompts, outs["pallas"]):
        assert out == reference_greedy(model, eng.runner.params, prompt, 4)


def test_engine_int8_kv_cache_matches_reference_argmax():
    """Acceptance: int8 KV (per-token scales, dequant fused into the
    attention op) keeps greedy argmax identical to the full-precision
    engine on the acceptance prompt set, with both attention impls, and
    the pools/scales actually store int8."""
    kw = dict(
        block_size=8, num_blocks=64, max_decode_slots=4, max_blocks_per_seq=4
    )
    prompts = random_prompts((5, 11, 17), seed=32)
    exact = LLMEngine(TINY, EngineConfig(**kw), seed=0)
    want = exact.generate(prompts, max_new_tokens=4)
    for impl in ("reference", "pallas"):
        eng = LLMEngine(
            TINY,
            EngineConfig(**kw, attn_impl=impl, kv_cache_dtype="int8"),
            seed=0,
        )
        assert eng.runner.k_cache.dtype == jnp.int8
        assert eng.runner.k_scale is not None
        # One scale per (token, head): the pools' minor axis is H*D.
        assert eng.runner.k_cache.shape[:3] == eng.runner.k_scale.shape[:3]
        assert eng.runner.k_cache.shape[3] == TINY.embed_dim
        assert eng.runner.k_scale.shape[3] == TINY.num_heads
        got = eng.generate(prompts, max_new_tokens=4)
        assert got == want, f"int8 KV diverged from reference with {impl}"
        assert eng.stats()["kv_cache_dtype"] == "int8"


def test_engine_int8_kv_cow_copies_scales():
    """A copy-on-write block copy on int8 pools must carry the dequant
    scales with the values — a fully-cached repeated prompt (the CoW
    path) stays token-identical to the uncached run."""
    ecfg = EngineConfig(
        block_size=8, num_blocks=32, max_decode_slots=4, max_blocks_per_seq=8,
        kv_cache_dtype="int8",
    )
    eng = LLMEngine(TINY, ecfg, seed=0)
    prompt = random_prompts((16,), seed=33)[0]  # exactly 2 full blocks
    out1 = eng.generate([prompt], max_new_tokens=4)[0]
    cows_before = eng.scheduler.num_cow_blocks
    out2 = eng.generate([prompt], max_new_tokens=4)[0]
    assert eng.scheduler.num_cow_blocks == cows_before + 1
    assert out2 == out1


def test_engine_config_hot_path_knob_validation():
    with pytest.raises(ValueError, match="attn_impl"):
        EngineConfig(attn_impl="cuda")
    with pytest.raises(ValueError, match="kv_cache_dtype"):
        EngineConfig(kv_cache_dtype="fp4")


def test_llm_server_warmup_respects_admission_limits():
    """Regression: init-time warmup must shape its requests to pass the
    engine's own admission validation for any valid config (custom buckets
    smaller than max_model_len used to crash the replica at deploy)."""
    server = in_process(LLMServer(
        TINY,
        EngineConfig(
            block_size=8, num_blocks=64, max_blocks_per_seq=16,
            prefill_buckets=(8, 16),
        ),
        warmup=True,
    ))
    out = server.generate([1, 2, 3], max_new_tokens=4)
    assert len(out["token_ids"]) == 4
    server.shutdown()
    # After shutdown new submissions fail fast, not after a timeout.
    with pytest.raises(RuntimeError, match="not running"):
        server.generate([1], max_new_tokens=1)


# ---------------- engine actor + serve ----------------


@pytest.fixture
def llm_ray():
    runtime = ray_tpu.init(num_cpus=8)
    yield runtime
    from ray_tpu import serve

    serve.shutdown()
    ray_tpu.shutdown()


def test_llm_server_concurrent_requests_match_reference(llm_ray):
    """Acceptance: N concurrent requests with different prompt/output
    lengths through LLMServer are token-identical to the sequential
    unbatched loop."""
    ecfg = EngineConfig(
        block_size=8, num_blocks=64, max_decode_slots=4, max_blocks_per_seq=8
    )
    server = (
        ray_tpu.remote(LLMServer)
        .options(max_concurrency=16)
        .remote(TINY, ecfg, None, 0)
    )
    lengths = (5, 11, 3, 17, 8)
    new_tokens = (4, 8, 6, 3, 7)
    prompts = random_prompts(lengths, seed=6)
    refs = [
        server.generate.remote(p, n) for p, n in zip(prompts, new_tokens)
    ]
    outs = [ray_tpu.get(r) for r in refs]

    # Streaming path sees the same tokens in the same order.
    stream = server.generate_stream.options(num_returns="streaming").remote(
        prompts[0], new_tokens[0]
    )
    assert [ray_tpu.get(r) for r in stream] == outs[0]["token_ids"]

    engine = LLMEngine(TINY, ecfg, seed=0)  # same seed -> same params
    model = GPT(TINY)
    for prompt, n, out in zip(prompts, new_tokens, outs):
        want = reference_greedy(model, engine.runner.params, prompt, n)
        assert out["token_ids"] == want
        assert out["finish_reason"] == "length"

    stats = ray_tpu.get(server.metrics.remote())
    assert stats["decode_tokens"] > 0
    assert ray_tpu.get(server.check_health.remote()) is True
    ray_tpu.get(server.shutdown.remote())


def test_llm_serve_deployment_end_to_end(llm_ray):
    """proxy-path architecture: Serve replica forwards to the shared named
    engine actor; blocking and streaming responses both work."""
    from ray_tpu import serve
    from ray_tpu.llm.serve import build_app

    ecfg = EngineConfig(
        block_size=8, num_blocks=64, max_decode_slots=4, max_blocks_per_seq=8
    )
    handle = serve.run(
        build_app(TINY, ecfg, engine_name="test"), name="llmapp"
    )
    prompt = random_prompts((7,), seed=7)[0]
    res = handle.remote({"prompt_ids": prompt, "max_new_tokens": 5}).result(
        timeout_s=60
    )
    engine = LLMEngine(TINY, EngineConfig(block_size=8, num_blocks=64,
                                          max_decode_slots=4,
                                          max_blocks_per_seq=8), seed=0)
    model = GPT(TINY)
    assert res["token_ids"] == reference_greedy(
        model, engine.runner.params, prompt, 5
    )
    streamed = list(
        handle.options(stream=True).remote(
            {"prompt_ids": prompt, "max_new_tokens": 5, "stream": True}
        )
    )
    assert [d["token_id"] for d in streamed] == res["token_ids"]


def test_llm_serve_deadline_propagates_to_engine(llm_ray):
    """timeout_s rides handle → ingress → engine as an end-to-end
    deadline: a zero budget is rejected at engine admission (typed
    TimeoutError to the caller), never prefilled — and the same app still
    serves requests with a sane budget afterwards."""
    from ray_tpu import serve
    from ray_tpu.llm.serve import build_app

    ecfg = EngineConfig(
        block_size=8, num_blocks=64, max_decode_slots=4, max_blocks_per_seq=8
    )
    handle = serve.run(
        build_app(TINY, ecfg, engine_name="deadline"), name="llmapp-deadline"
    )
    prompt = random_prompts((5,), seed=11)[0]
    with pytest.raises(TimeoutError, match="deadline"):
        handle.remote(
            {"prompt_ids": prompt, "max_new_tokens": 4, "timeout_s": 0.0}
        ).result(timeout_s=60)
    res = handle.remote(
        {"prompt_ids": prompt, "max_new_tokens": 4, "timeout_s": 60.0}
    ).result(timeout_s=60)
    assert len(res["token_ids"]) == 4
    assert res["finish_reason"] == "length"


def test_cow_copy_failure_releases_copy_source_ref():
    """Regression (found by `ray-tpu lint` RTL403 cleared-before-commit):
    a copy-on-write prefill whose device block copy raises must not leak
    the extra ref admission took on the copy source. The engine used to
    clear `pending_copy` BEFORE running the copy, so a poisoned CoW
    request left the shared source block referenced forever — every such
    failure permanently shrank the KV block pool."""
    ecfg = EngineConfig(
        block_size=8, num_blocks=16, max_decode_slots=4, max_blocks_per_seq=8
    )
    eng = LLMEngine(TINY, ecfg, seed=0)
    prompt = random_prompts((16,), seed=21)[0]  # exactly 2 full blocks

    eng.add_request(prompt, max_new_tokens=2)
    while eng.has_work():
        eng.step()
    assert eng.allocator.num_allocated == 0  # all parked evictable / free

    # Same prompt again: fully cached admission takes the CoW path, and
    # the injected failure hits exactly the device copy.
    boom = RuntimeError("injected device copy failure")

    def failing_copy(src, dst):
        raise boom

    original_copy = eng.runner.copy_block
    eng.runner.copy_block = failing_copy
    rid = eng.add_request(prompt, max_new_tokens=2)
    try:
        with pytest.raises(RuntimeError, match="injected device copy"):
            eng.step()
        # The step loop's poison-isolation path: attribute + dead-letter.
        assert eng.culprit_for(boom) == rid
        assert eng.fail_request(rid, boom)
    finally:
        eng.runner.copy_block = original_copy
    # The copy-source ref must be gone: nothing allocated, engine idle.
    assert eng.allocator.num_allocated == 0
    assert not eng.has_work()
    assert eng.dead_letters()[-1]["request_id"] == rid

    # The pool still serves the same request afterwards (no shrinkage).
    tokens = eng.generate([prompt], max_new_tokens=2)[0]
    assert len(tokens) == 2
    assert eng.allocator.num_allocated == 0
