"""Tensor-parallel LLM engine: tp=2 on a CPU host-device mesh must serve
greedy outputs token-identical to tp=1 across the whole feature matrix.

The engine spans a `tp` mesh (EngineConfig.tensor_parallel_size): GPT
weights shard Megatron-style, the paged KV / int8 scale / draft-mirror
pools shard on the HEAD axis, and all five jitted programs run SPMD —
while the block allocator, prefix cache, scheduler, and chunking logic
stay host-global (block ids are shard-invariant). These tests pin:

  * token identity tp=1 vs tp=2 (and vs the unbatched reference) across
    prefix-cache hits, CoW, preempt-resume, chunked prefill, ngram and
    draft speculation, int8 KV, and the pallas kernel in interpret mode;
  * zero per-token host gathers: the flight-recorded per-step
    host_transfer_bytes series is IDENTICAL at tp=1 and tp=2, and the
    pools still carry the head-axis PartitionSpec after serving traffic;
  * per-chip pool bytes = aggregate / tp;
  * fail-fast config validation (indivisible heads for target AND draft,
    more chips than the backend exposes);
  * chaos: a poison step on a tp=2 engine dead-letters only the culprit
    with the sharded target + draft pools back at boot size.

Conftest forces an 8-device virtual CPU backend, so tp=2 exercises the
real mesh machinery (shard_map, NamedSharding, donation) end to end.
"""

import threading

import numpy as np
import pytest

import jax.numpy as jnp

from ray_tpu._private import fault_injection as fi
from ray_tpu.exceptions import PoisonRequestError
from ray_tpu.llm import EngineConfig, LLMEngine, LLMServer
from ray_tpu.models.gpt import GPT, GPTConfig
from llm_in_process import in_process


def random_prompts(lengths, vocab=128, seed=0):
    rng = np.random.RandomState(seed)
    return [list(map(int, rng.randint(0, vocab, size=n))) for n in lengths]


def reference_greedy(model, params, prompt, n_tokens, pad_to=64):
    """Unbatched full-forward generation: the numeric ground truth (one
    fixed padded length so XLA compiles a single program)."""
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

# One layer keeps this suite's XLA-CPU compile bill low — TP semantics
# are per-block (column/row shard + psum + head-sharded scatter repeat
# identically per layer); the multi-layer pool indexing gets its own
# direct-runner parity test below with a 2-layer model.
TINY = GPTConfig(
    vocab_size=64,
    num_layers=1,
    num_heads=4,
    embed_dim=32,
    max_seq_len=128,
    dtype=jnp.float32,
    attention_impl="reference",
)
DRAFT = GPTConfig(
    vocab_size=64,
    num_layers=1,
    num_heads=2,
    embed_dim=16,
    max_seq_len=128,
    dtype=jnp.float32,
    attention_impl="reference",
)
BASE = dict(
    block_size=4, num_blocks=64, max_decode_slots=4, max_blocks_per_seq=16
)
HEAD_SPEC = "PartitionSpec(None, None, None, 'tp')"


@pytest.fixture(autouse=True)
def _clear_faults():
    yield
    fi.clear()


def make_engine(tp: int, **overrides) -> LLMEngine:
    kw = dict(BASE)
    kw.update(overrides)
    return LLMEngine(
        TINY, EngineConfig(tensor_parallel_size=tp, **kw), seed=0
    )


def tp_pair(prompts, n_new: int, **overrides):
    """Generate with tp=1 and tp=2 engines built identically (same seed →
    same weights); returns (outputs_tp1, outputs_tp2, engine_tp2)."""
    e1 = make_engine(1, **overrides)
    e2 = make_engine(2, **overrides)
    o1 = e1.generate(prompts, max_new_tokens=n_new)
    o2 = e2.generate(prompts, max_new_tokens=n_new)
    return o1, o2, e2


# ---------------- token-identity matrix ----------------


def test_tp2_parity_reference_prefix_cow_and_flat_host_bytes():
    """Acceptance, on ONE engine pair (compiles dominate this suite's
    wall time, so the plain-config phases share programs): the tp=2 mesh
    serves token-identical greedy outputs matching the unbatched
    full-forward ground truth; the flight-recorded per-step
    host_transfer_bytes series is IDENTICAL at tp=1 and tp=2 (program
    inputs + sampled tokens only — the in-program no-gather gate is
    test_tp2_decode_program_compiles_zero_all_gathers); a
    repeated workload hits the prefix cache and a fully-cached
    block-aligned prompt takes the CoW path (the copy must carry each
    chip's local head slice) — all token-identical, with the pools still
    head-sharded at the end and the tp=1 path untouched."""
    e1, e2 = make_engine(1), make_engine(2)
    prompts = random_prompts((5, 11, 3, 8), vocab=64, seed=1)
    o1 = e1.generate(prompts, max_new_tokens=8)
    o2 = e2.generate(prompts, max_new_tokens=8)
    assert o1 == o2
    model = GPT(TINY)
    for prompt, out in list(zip(prompts, o2))[:2]:
        assert out == reference_greedy(model, e2.runner.params, prompt, 8)
    # Zero per-token host gathers: identical explicit-transfer series.
    s1 = [
        (s["phase"], s["host_transfer_bytes"])
        for s in e1.flight_recorder.snapshot()["steps"]
    ]
    s2 = [
        (s["phase"], s["host_transfer_bytes"])
        for s in e2.flight_recorder.snapshot()["steps"]
    ]
    assert s1 == s2
    assert any(b > 0 for _, b in s1)
    assert all(
        s["tensor_parallel_size"] == 2
        for s in e2.flight_recorder.snapshot()["steps"]
    )
    # Same prompts again: the second pass must hit the prefix cache.
    assert e1.generate(prompts, max_new_tokens=6) == e2.generate(
        prompts, max_new_tokens=6
    )
    assert e2.stats()["prefix_cache_hit_tokens"] > 0
    # A block-aligned prompt repeated after finishing is cached in FULL:
    # re-admission copy-on-writes the last shared block.
    cow = random_prompts((8,), vocab=64, seed=3)[0]
    assert e1.generate([cow, cow], max_new_tokens=6) == e2.generate(
        [cow, cow], max_new_tokens=6
    )
    assert e2.scheduler.num_cow_blocks > 0
    assert e2.runner.pool_sharding_spec() == HEAD_SPEC
    assert e1.runner.pool_sharding_spec() is None  # tp=1 path untouched


def test_tp2_decode_program_compiles_zero_all_gathers():
    """The compiled tp=2 decode executable must contain NO all-gather:
    the head-sharded layout implies only the per-block psums
    (all-reduce after the row-parallel attn-proj/mlp-out matmuls). The
    host-transfer counters are flat in tp by construction (they count
    the bytes the runner itself feeds/fetches), so THIS is the gate
    that actually catches an in-program gather regression — dropping a
    pool output-sharding constraint makes GSPMD insert an all-gather of
    the pools right here, before any dynamic test notices."""
    e = make_engine(2)
    r = e.runner
    ecfg = e.engine_config
    slots = ecfg.max_decode_slots
    lowered = r._decode_fn.lower(
        r.params,
        *r._pools,
        jnp.zeros((slots,), jnp.int32),
        jnp.zeros((slots,), jnp.int32),
        jnp.zeros((slots, ecfg.max_blocks_per_seq), jnp.int32),
        jnp.zeros((slots,), jnp.int32),
    )
    hlo = lowered.compile().as_text()
    assert "all-gather" not in hlo
    # Positive control that we are reading real SPMD output: the two
    # row-parallel projections' psums must be present as all-reduces.
    assert "all-reduce" in hlo


def test_tp2_parity_preempt_resume():
    """A cache far too small for the working set forces recompute-style
    preemption; resume re-prefills through the sharded programs."""
    prompts = random_prompts((6, 7, 5, 6), vocab=64, seed=4)
    o1, o2, e2 = tp_pair(prompts, 10, num_blocks=10, max_blocks_per_seq=8)
    assert o1 == o2
    assert e2.stats()["preemptions"] > 0
    assert e2.allocator.num_allocated == 0


def test_tp2_parity_chunked_prefill():
    prompts = random_prompts((30, 5, 17), vocab=64, seed=5)
    o1, o2, e2 = tp_pair(prompts, 8, max_prefill_tokens_per_step=8)
    assert o1 == o2
    assert e2.stats()["chunked_prefill_requests"] > 0


def test_tp2_parity_speculation_ngram():
    # Repetitive prompts so the n-gram proposer actually proposes.
    prompts = [[7, 8, 9] * 5, [1, 2] * 8]
    o1, o2, e2 = tp_pair(prompts, 8, speculation="ngram")
    assert o1 == o2
    assert e2.stats()["spec_verify_steps"] > 0


def test_tp2_parity_speculation_draft():
    """The draft model runs through its own GPTRunner with the SAME
    engine config — its mirror pool shards on its own head axis."""
    prompts = random_prompts((6, 9), vocab=64, seed=6)
    o1, o2, e2 = tp_pair(
        prompts, 8, speculation="draft", draft_model_config=DRAFT
    )
    assert o1 == o2
    assert e2.stats()["spec_verify_steps"] > 0
    assert e2._spec.runner.pool_sharding_spec() == HEAD_SPEC
    assert e2.stats()["spec_draft_pool_allocated"] == 0


def test_tp2_parity_int8_kv():
    """int8 pools shard values AND per-token scale tensors on the head
    axis; quantization happens shard-locally at every scatter. Identity
    inherits int8's own argmax-on-the-tested-set contract."""
    prompts = random_prompts((5, 12), vocab=64, seed=7)
    o1, o2, e2 = tp_pair(prompts, 8, kv_cache_dtype="int8")
    assert o1 == o2
    assert e2.runner.k_scale is not None
    assert str(e2.runner.k_scale.sharding.spec) == HEAD_SPEC


def test_tp2_parity_pallas_interpret():
    """The fused kernel head-sliced under shard_map: each instance walks
    the block table over its local heads only (interpret mode on CPU runs
    the same kernel code path the TPU compiles)."""
    prompts = random_prompts((5,), vocab=64, seed=8)
    o1, o2, _ = tp_pair(prompts, 3, attn_impl="pallas")
    assert o1 == o2


def test_tp2_runner_parity_multi_layer():
    """Two-layer direct-runner parity: the per-layer scatter loop indexes
    the head-sharded pools at every layer (layer is an UNSHARDED dim, so
    each write stays shard-local) — one prefill + a few decode steps must
    match tp=1 exactly, and the pools keep their layout."""
    from ray_tpu.llm.model_runner import GPTRunner

    deep = GPTConfig(
        vocab_size=64,
        num_layers=2,
        num_heads=4,
        embed_dim=32,
        max_seq_len=128,
        dtype=jnp.float32,
        attention_impl="reference",
    )
    ecfg = lambda tp: EngineConfig(tensor_parallel_size=tp, **BASE)
    r1 = GPTRunner(deep, ecfg(1), seed=0)
    r2 = GPTRunner(deep, ecfg(2), seed=0)
    prompt = [1, 5, 9, 2, 7]
    assert r1.prefill(prompt, [1, 2]) == r2.prefill(prompt, [1, 2])
    toks = np.zeros(BASE["max_decode_slots"], np.int32)
    pos = np.zeros_like(toks)
    bt = np.zeros((len(toks), BASE["max_blocks_per_seq"]), np.int32)
    cl = np.zeros_like(toks)
    toks[0], pos[0], bt[0, :2], cl[0] = 3, 5, [1, 2], 5
    for _ in range(3):
        o1 = r1.decode(toks, pos, bt, cl)
        o2 = r2.decode(toks.copy(), pos.copy(), bt.copy(), cl.copy())
        assert (o1 == o2).all()
        toks, pos, cl = o1, pos + 1, cl + 1
    assert r2.pool_sharding_spec() == HEAD_SPEC


# ---------------- the stored pool form, tp = 1 and 2 ----------------

DEEP = GPTConfig(
    vocab_size=64,
    num_layers=2,
    num_heads=4,
    embed_dim=32,
    max_seq_len=128,
    dtype=jnp.float32,
    attention_impl="reference",
)


@pytest.mark.parametrize("tp", [1, 2])
@pytest.mark.parametrize("impl", ["reference", "pallas"])
def test_runner_stored_pool_heads_are_lane_groups(impl, tp):
    """The pools are [L, N, bs, H*D]: head h of a token is lanes
    h*D:(h+1)*D of its row, at every layer, whole or split over tp. A
    full prefill writes exactly the K/V the model computes; a CoW copy
    and a spill/restore carry it to other slots; decode and suffix
    prefill through the table read the same tokens from the copies as
    from the originals (two layers, so a layer index other than 0)."""
    from ray_tpu.llm.model_runner import GPTRunner
    from ray_tpu.models.gpt import collect_kv_caches

    ecfg = EngineConfig(tensor_parallel_size=tp, attn_impl=impl, **BASE)
    runner = GPTRunner(DEEP, ecfg, seed=0)
    bs, slots = BASE["block_size"], BASE["max_decode_slots"]
    assert runner.k_cache.shape == (
        DEEP.num_layers, BASE["num_blocks"], bs, DEEP.embed_dim
    )
    prompt = random_prompts((8,), vocab=64, seed=11)[0]  # two full blocks
    first = runner.prefill(prompt, [1, 2])
    _, state = GPT(DEEP).apply(
        runner.params, jnp.asarray([prompt]), return_kv=True,
        mutable=["intermediates"],
    )
    kvs = collect_kv_caches(state["intermediates"], DEEP.num_layers)
    for block, rows in ((1, slice(0, bs)), (2, slice(bs, 2 * bs))):
        payload = runner.extract_block(block)
        for layer, (k, v) in enumerate(kvs):
            for got, want in ((payload["k"], k), (payload["v"], v)):
                np.testing.assert_allclose(
                    got[layer],
                    np.asarray(want[0, rows]).reshape(bs, DEEP.embed_dim),
                    atol=1e-5,
                )
    # Block 1 restored into slot 5, block 2 copied (CoW) into slot 6.
    runner.restore_block(5, runner.extract_block(1))
    runner.copy_block(2, 6)
    assert runner.pool_sharding_spec() == (HEAD_SPEC if tp > 1 else None)

    def decode_through(table):
        toks = np.zeros(slots, np.int32)
        pos, cl = np.zeros_like(toks), np.zeros_like(toks)
        bt = np.zeros((slots, BASE["max_blocks_per_seq"]), np.int32)
        toks[0], pos[0], cl[0], bt[0, :3] = first, 8, 8, table
        return int(runner.decode(toks, pos, bt, cl)[0])

    # The new token's K/V lands in the third block: a fresh one each time.
    assert decode_through([1, 2, 3]) == decode_through([5, 6, 4])
    # Suffix prefill over the cached first block, originals and copies.
    assert runner.prefill_suffix(prompt[bs:], [1, 7], bs) == first
    assert runner.prefill_suffix(prompt[bs:], [5, 8], bs) == first


@pytest.mark.parametrize("tp", [1, 2])
@pytest.mark.parametrize("impl", ["reference", "pallas"])
def test_engine_token_identity_on_the_stored_pool(impl, tp):
    """A two-layer engine through full prefill, decode, a prefix hit
    (suffix prefill) and a fully cached prompt (CoW copy) stays
    token-identical to the unbatched forward, with either attention
    implementation, on one chip's pools and on pools split by heads."""
    engine = LLMEngine(
        DEEP,
        EngineConfig(tensor_parallel_size=tp, attn_impl=impl, **BASE),
        seed=0,
    )
    model = GPT(DEEP)
    shared = random_prompts((8,), vocab=64, seed=12)[0]
    prompts = [shared + [3, 1, 4], shared + [1, 5], shared]
    want = [
        reference_greedy(model, engine.runner.params, p, 5) for p in prompts
    ]
    assert engine.generate(prompts[:1], max_new_tokens=5) == want[:1]
    # The shared blocks are cached now: the next two hit them, the last
    # is cached in full and copies its last block before writing.
    assert engine.generate(prompts[1:], max_new_tokens=5) == want[1:]
    assert engine.stats()["prefix_cache_hit_tokens"] > 0
    assert engine.scheduler.num_cow_blocks > 0


# ---------------- pool bytes ----------------


def test_tp2_pool_bytes_per_shard_is_aggregate_over_tp():
    e2 = make_engine(2)
    stats = e2.stats()
    assert stats["tensor_parallel_size"] == 2
    assert stats["kv_pool_bytes_per_shard"] * 2 == stats["kv_pool_bytes"]
    # The live device arrays agree with the accounting: each chip holds
    # exactly half the pool bytes (K + V).
    per_chip = sum(
        s.data.nbytes for s in e2.runner.k_cache.addressable_shards[:1]
    ) + sum(s.data.nbytes for s in e2.runner.v_cache.addressable_shards[:1])
    assert per_chip == stats["kv_pool_bytes_per_shard"]
    # tp=1 reports the degenerate sharding (aggregate == per-shard).
    s1 = make_engine(1).stats()
    assert s1["kv_pool_bytes_per_shard"] == s1["kv_pool_bytes"]
    assert s1["kv_pool_sharding"] is None


# ---------------- fail-fast validation ----------------


def test_tp_must_divide_target_heads():
    with pytest.raises(ValueError, match="num_heads 4 is not divisible"):
        make_engine(3)


def test_tp_must_divide_draft_heads():
    # Target heads (4) divide tp=4 but the draft's (2) do not — the error
    # must name the draft model so the operator fixes the right config.
    with pytest.raises(ValueError, match="draft model num_heads 2"):
        make_engine(4, speculation="draft", draft_model_config=DRAFT)


def test_tp_exceeding_backend_devices_fails_fast():
    # Conftest pins an 8-device virtual CPU backend. Heads (16) divide
    # tp=16, so the device-count check is the one that must fire.
    wide = GPTConfig(
        vocab_size=64,
        num_layers=1,
        num_heads=16,
        embed_dim=64,
        max_seq_len=128,
        dtype=jnp.float32,
        attention_impl="reference",
    )
    with pytest.raises(ValueError, match="exceeds the 8 device"):
        LLMEngine(
            wide, EngineConfig(tensor_parallel_size=16, **BASE), seed=0
        )


def test_tp_zero_rejected_at_config():
    with pytest.raises(ValueError, match="tensor_parallel_size"):
        EngineConfig(tensor_parallel_size=0)


def test_tp_reference_impl_supported():
    # attn_impl="reference" is explicitly SUPPORTED at tp>1 (the reference
    # op head-slices under the same shard_map) — constructing must work.
    eng = make_engine(2, attn_impl="reference")
    assert eng.runner.attn_impl == "reference"
    assert eng.runner.mesh is not None


# ---------------- chaos: poison isolation on the sharded engine ----------


def test_tp2_poison_dead_letters_only_culprit_pools_at_boot():
    """A poison step on a tp=2 engine (with a sharded draft mirror pool in
    play) dead-letters ONLY the culprit; every pool — target KV and draft
    mirror, both head-sharded — is back at boot size, still sharded."""
    # With speculation on, decode-ready sequences advance through the
    # verify path — poison the per-sequence commit section there.
    fi.inject(
        "engine.verify",
        match="poison-me",
        exc_factory=lambda: RuntimeError("cosmic ray at tp=2"),
    )
    ecfg = EngineConfig(
        tensor_parallel_size=2,
        speculation="draft",
        draft_model_config=DRAFT,
        **BASE,
    )
    server = in_process(LLMServer(TINY, ecfg, seed=0, warmup=False))
    prompts = random_prompts((5, 7), vocab=64, seed=10)
    results = {}

    def run(rid, prompt):
        try:
            results[rid] = server.generate(
                prompt, max_new_tokens=8, request_id=rid, timeout_s=60.0
            )
        except BaseException as exc:  # noqa: BLE001
            results[rid] = exc

    jobs = [(f"ok-{i}", p) for i, p in enumerate(prompts)]
    jobs.append(("poison-me", random_prompts((6,), vocab=64, seed=11)[0]))
    threads = [
        threading.Thread(target=run, args=j, daemon=True) for j in jobs
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=90)

    assert isinstance(results["poison-me"], PoisonRequestError)
    model = GPT(TINY)
    params = server._engine.runner.params
    for i, p in enumerate(prompts):
        out = results[f"ok-{i}"]
        assert not isinstance(out, BaseException), out
        assert out["token_ids"] == reference_greedy(model, params, p, 8)
    assert server.check_health() is True
    stats = server.metrics()
    assert stats["num_dead_letters"] == 1
    assert stats["tensor_parallel_size"] == 2
    # Both sharded pools drained back to boot size...
    assert stats["kv_pool_allocated"] == 0
    assert stats["spec_draft_pool_allocated"] == 0
    # ...and neither lost its head-axis layout in the failure path.
    assert stats["kv_pool_sharding"] == HEAD_SPEC
    assert server._engine._spec.runner.pool_sharding_spec() == HEAD_SPEC
    server.shutdown()
