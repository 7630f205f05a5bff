"""A model with recurrent layers through `ray_tpu.llm`: the engine, the
scheduler's state slots and `HybridRunner`'s programs against the plain
float32 reference's full forward, logits and not tokens, at toy widths on
seeded weights.

Every program run is observed where its logits become tokens
(`_HybridPrograms._sample`), and each observed row is compared with the
reference's logits at that position of the finished sequence: the first
token after a prompt fed in one, two or three chunks, and every decode step
through the paged cache and the state slot.

Tolerance: 2e-8 absolute on logits about 0.003 wide. The programs and the
reference both compute in float32 here and differ in the order of sums
(chunked scan, grouped experts, paged attention); that reads 2e-9 at most.
A state slot left dirty, a chunk that restarts from an empty state or a
lane that reads another's state moves a logit by 1e-5 or more, and the
recurrent state kept in bfloat16 by ten times the tolerance
(tests/test_granite_hybrid_model.py).
"""

import functools
import gc

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.llm import hybrid_runner as hr
from ray_tpu.llm.config import EngineConfig, KVFabricConfig
from ray_tpu.llm.engine import LLMEngine
from ray_tpu.models import granite_hybrid as gh
from ray_tpu.models import granite_hybrid_reference as ref

from hybrid_toy import assert_idle_lanes_keep_their_state, toy_config

TOLERANCE = 2e-8
PAD = 96  # the reference runs every sequence at one padded length
CFG = toy_config()


@pytest.fixture(scope="module", autouse=True)
def _leave_a_small_heap():
    """What this file traced goes when it is done: the worker that ran it
    runs other files after, and some of them time a full `gc.collect()`."""
    yield
    _reference.cache_clear()
    jax.clear_caches()
    gc.collect()


@pytest.fixture(scope="module")
def params():
    return gh.init_params(CFG, 11)


@functools.lru_cache(maxsize=None)
def _reference():
    return jax.jit(functools.partial(ref.forward, CFG))


def reference_logits(params, tokens):
    padded = np.zeros((PAD,), np.int32)
    padded[: len(tokens)] = tokens
    return np.asarray(_reference()(params, jnp.asarray(padded)))[: len(tokens)]


@pytest.fixture
def observed(monkeypatch):
    """Every logits array a program samples from, in execution order."""
    seen = []

    def sample(self, logits):
        jax.debug.callback(lambda x: seen.append(np.asarray(x)), logits, ordered=True)
        return jnp.argmax(logits, axis=-1)

    monkeypatch.setattr(hr._HybridPrograms, "_sample", sample)
    monkeypatch.setattr(hr, "_PROGRAM_CACHE", {})
    return seen


def engine_config(**changes):
    fields = dict(
        block_size=8, num_blocks=64, max_decode_slots=4, max_blocks_per_seq=12,
        prefill_buckets=(16, 32, 64), max_prefill_tokens_per_step=16,
        attn_impl="reference",
    )
    fields.update(changes)
    return EngineConfig(**fields)


def serve(params, observed, prompts, new_tokens, **changes):
    """Run `prompts` to completion; returns the engine, the generated
    tokens and, for each request, {row: observed logits} over the rows of
    its finished sequence that a program sampled from."""
    del observed[:]
    engine = LLMEngine(CFG, engine_config(**changes), params=params)
    runner, metas = engine.runner, []
    prefill, suffix, decode = runner.prefill, runner.prefill_suffix, runner.decode

    def on_prefill(token_ids, block_ids, slot):
        metas.append([(engine._current_rid, None, len(token_ids) - 1)])
        return prefill(token_ids, block_ids, slot)

    def on_suffix(token_ids, block_ids, offset, slot):
        metas.append([(engine._current_rid, None, offset + len(token_ids) - 1)])
        return suffix(token_ids, block_ids, offset, slot)

    def on_decode(tokens, positions, block_tables, context_lens):
        lanes = {
            s.state_slot: s.request.request_id
            for s in engine.scheduler.running if not s.prefilling
        }
        metas.append([
            (lanes[lane], lane, int(positions[lane]))
            for lane in np.flatnonzero(context_lens)
        ])
        return decode(tokens, positions, block_tables, context_lens)

    runner.prefill, runner.prefill_suffix, runner.decode = on_prefill, on_suffix, on_decode
    outputs = []
    rids = []
    for i, prompt in enumerate(prompts):
        tokens = []
        rids.append(engine.add_request(
            list(prompt), max_new_tokens=new_tokens, request_id=f"r{i}",
            on_token=tokens.append,
        ))
        outputs.append(tokens)
    while engine.has_work():
        engine.step()
    jax.effects_barrier()
    assert len(metas) == len(observed)
    rows = {rid: {} for rid in rids}
    for meta, logits in zip(metas, observed):
        for rid, lane, row in meta:
            rows[rid].setdefault(row, []).append(logits if lane is None else logits[lane])
    return engine, outputs, rows


def assert_matches_reference(params, prompts, outputs, rows):
    worst = 0.0
    for i, (prompt, answer) in enumerate(zip(prompts, outputs)):
        full = list(prompt) + list(answer)
        want = reference_logits(params, full)
        seen = rows[f"r{i}"]
        # every sampled position of the answer was observed
        assert set(range(len(prompt) - 1, len(full) - 1)) <= set(seen)
        for row, observations in seen.items():
            if row >= len(full):
                continue  # depth 1's one token past the stop: never emitted
            for got in observations:
                worst = max(worst, float(np.abs(got - want[row]).max()))
    assert worst < TOLERANCE, worst


def prompts_of(*lengths, seed=0):
    rng = np.random.RandomState(seed)
    return [list(rng.randint(1, 512, n)) for n in lengths]


DEPTHS = pytest.mark.parametrize("depth", [0, 1], ids=["depth0", "depth1"])


# (c) prefill then decode through cache and state: a prompt fed in one, two
# and three chunks (budget 16 a step).
@DEPTHS
@pytest.mark.parametrize("length", [9, 30, 40], ids=["1chunk", "2chunks", "3chunks"])
def test_chunked_prefill_then_decode(params, observed, depth, length):
    prompts = prompts_of(length, seed=length)
    engine, outputs, rows = serve(
        params, observed, prompts, 6, async_scheduling=bool(depth)
    )
    assert engine.stats()["prefill_chunk_dispatches"] == -(-length // 16)
    assert_matches_reference(params, prompts, outputs, rows)


@DEPTHS
def test_a_slot_reused_by_a_second_sequence_starts_empty(params, observed, depth):
    prompts = prompts_of(20, 13, 33, 7, 26, seed=1)
    engine, outputs, rows = serve(
        params, observed, prompts, 5, max_decode_slots=2,
        async_scheduling=bool(depth),
    )
    stats = engine.stats()
    assert stats["state_slots"] == 2 and stats["state_slot_resets"] == 5
    assert stats["state_slots_in_use"] == 0
    assert_matches_reference(params, prompts, outputs, rows)


@DEPTHS
def test_a_request_alone_and_among_others(params, observed, depth):
    mine, others = prompts_of(27, seed=2), prompts_of(11, 35, 19, seed=3)
    _, alone_out, alone = serve(params, observed, mine, 6, async_scheduling=bool(depth))
    alone = {row: got[0].copy() for row, got in alone["r0"].items()}
    prompts = others[:1] + mine + others[1:]
    _, outputs, rows = serve(params, observed, prompts, 6, async_scheduling=bool(depth))
    assert outputs[1] == alone_out[0]
    for row, got in rows["r1"].items():
        if row in alone:
            assert float(np.abs(got[0] - alone[row]).max()) < TOLERANCE
    assert_matches_reference(params, prompts, outputs, rows)


def test_a_decode_step_leaves_an_idle_lanes_state_alone(params):
    runner = LLMEngine(CFG, engine_config(), params=params).runner
    assert_idle_lanes_keep_their_state(runner, 2 * CFG.layer_types.count(gh.MAMBA))


@DEPTHS
def test_a_preempted_sequence_is_prefilled_again(params, observed, depth):
    prompts = prompts_of(20, 21, seed=4)
    engine, outputs, rows = serve(
        params, observed, prompts, 24, num_blocks=9, max_decode_slots=2,
        async_scheduling=bool(depth),
    )
    stats = engine.stats()
    assert stats["num_preemptions"] > 0
    assert stats["state_slot_resets"] == 2 + stats["num_preemptions"]
    assert all(len(out) == 24 for out in outputs)
    assert_matches_reference(params, prompts, outputs, rows)


def test_depth_1_gives_depth_0s_tokens(params, observed):
    prompts = prompts_of(18, 40, 5, 29, 12, 33, seed=5)
    _, sync, _ = serve(params, observed, prompts, 8, async_scheduling=False)
    engine, chained, _ = serve(params, observed, prompts, 8, async_scheduling=True)
    assert chained == sync
    assert engine.stats()["chained_decode_dispatches"] > 0


def test_the_paged_kernel_serves_the_same_logits(params, observed):
    """attn_impl="pallas": the grouped-query paged kernel (interpreted) in
    the decode and chunk programs."""
    prompts = prompts_of(21, 38, seed=6)
    _, outputs, rows = serve(params, observed, prompts, 4, attn_impl="pallas")
    assert_matches_reference(params, prompts, outputs, rows)


# (e) what such a model refuses at construction, and the prefix cache.
@pytest.mark.parametrize("changes", [
    dict(speculation="ngram"),
    dict(kv_fabric=KVFabricConfig(name="hybrid-test")),
    dict(kv_cache_dtype="int8"),
    dict(tensor_parallel_size=2),
], ids=["speculation", "kv_fabric", "int8", "tensor_parallel"])
def test_refused_at_construction(params, changes):
    with pytest.raises(ValueError, match="recurrent"):
        LLMEngine(CFG, engine_config(**changes), params=params)


def test_no_prefix_hit_on_a_model_with_recurrent_layers(params, observed):
    prompt = prompts_of(40, seed=7)
    engine, outputs, _ = serve(params, observed, prompt * 3, 3, max_decode_slots=1)
    stats = engine.stats()
    assert outputs[0] == outputs[1] == outputs[2]
    assert stats["prefix_caching"] is False and stats["recurrent_state"] is True
    assert stats["prefix_cache_hit_tokens"] == 0
    assert stats["prefill_tokens"] == 3 * 40
    assert engine.allocator.num_evictable == 0  # nothing hashed, nothing kept


# (f) the counters.
@DEPTHS
def test_counters(params, observed, depth):
    prompts = prompts_of(18, 40, 5, 29, seed=8)
    engine, outputs, rows = serve(params, observed, prompts, 7, async_scheduling=bool(depth))
    stats = engine.stats()
    lane_steps = sum(len(got) for seen in rows.values() for got in seen.values())
    lane_steps -= stats["prefill_chunk_dispatches"]  # rows a chunk sampled
    routed = stats["decode_expert_assignments"] + stats["decode_expert_assignments_absent"]
    assert routed == CFG.num_experts_per_tok * CFG.num_layers * lane_steps
    if not depth:
        assert lane_steps == stats["decode_tokens"]
    assert stats["decode_state_bytes"] == 2 * stats["state_slot_bytes"] * lane_steps
    assert stats["state_slot_bytes"] == CFG.mamba_layers * (
        8 * 16 * 16 * 4 + 3 * CFG.conv_dim * 4
    )
    assert stats["prefill_scan_tokens"] == stats["prefill_tokens"] == 18 + 40 + 5 + 29
    assert 0 < stats["prefill_expert_assignments"] <= 2 * 4 * 92
    assert stats["decode_experts_touched"] <= stats["decode_expert_assignments"]
    assert stats["decode_expert_load_max"] <= stats["decode_expert_assignments"]
    assert stats["state_slots"] == 4 and stats["state_slots_in_use"] == 0
    assert stats["recurrent_shape"]["num_layers"] == 3
    assert stats["expert_shape"]["experts_held"] == 4
    assert stats["attention_shape"] == {
        "num_layers": 1, "num_heads": 2, "head_dim": 16, "kv_itemsize": 4,
        "num_query_heads": 4,
        # The widest chunk is one q tile of 16; a cached head's two query
        # heads are the rows of one product.
        "prefill_q_tile": 16, "prefill_rows_per_product": 32,
        # A decode walk's compute block is the whole 96-token table.
        "decode_tile_tokens": 96, "decode_bytes_in_flight": 24576,
    }


def test_op_scopes_name_every_part(params):
    engine = LLMEngine(CFG, engine_config(), params=params)
    report = engine.runner.device_report()
    decode = set(report["op_scopes"]["jit__decode_step"].values())
    assert {"llm.mixer.mamba.update", "llm.mixer.mamba.proj", "llm.mixer.attention",
            "llm.moe.router", "llm.moe.routed", "llm.moe.shared", "llm.head"} <= decode
    chunk = set(report["op_scopes"]["jit__prefill_suffix_step"].values())
    assert "llm.mixer.mamba.scan" in chunk and "llm.mixer.mamba.update" not in chunk
    assert set(report["op_scopes"]) == {
        "jit__decode_step", "jit__prefill_step", "jit__prefill_suffix_step"
    }


def test_scopes_of_reads_the_innermost_scope():
    text = '''
  %fusion.3 = f32[4]{0} fusion(%a), kind=kLoop, metadata={op_name="jit(_decode_step)/jit(main)/llm.mixer.attention/llm.head/dot_general"}
  ROOT %add.1 = f32[4]{0} add(%a, %b), metadata={op_name="jit(_decode_step)/jit(main)/add"}
  %copy.2 = f32[4]{0} copy(%a), metadata={op_name="jit(_decode_step)/llm.moe.routed/mul" source_file="x.py"}
'''
    assert hr.scopes_of(text) == {"fusion.3": "llm.head", "copy.2": "llm.moe.routed"}


def test_a_calls_tables_are_no_tuple_another_thread_can_break(monkeypatch):
    """`tuple(<generator>)` resizes the tuple it built, which CPython refuses
    while anything else refers to it, and `gc.get_objects()` refers to
    everything: called while a table's transfer has released the interpreter
    (the benchmark's harness does, in another thread), it made the step raise
    `SystemError`. Here the transfer itself takes the references."""
    held = []

    class Spying:
        int32 = np.int32

        @staticmethod
        def asarray(table, dtype):
            held.append(gc.get_objects())
            return np.asarray(table, dtype)

    monkeypatch.setattr(hr, "jnp", Spying)
    tables = (np.arange(4, dtype=np.int32), np.arange(8, dtype=np.int32))
    got = hr.HybridRunner._on_device(tables)
    assert isinstance(got, tuple) and len(held) == 2
    assert [t.tolist() for t in got] == [t.tolist() for t in tables]
    with pytest.raises(SystemError):
        tuple(Spying.asarray(t, np.int32) for t in tables)
