"""Solar-Open2 through `ray_tpu.llm`: the first model whose decode program
uses the recurrent-kind interface AND carries the routing counts, through the
engine, the scheduler's state slots and `HybridRunner`'s programs (which know
of KDA only what `solar_open2.recurrent_kinds` declares), against the plain
float32 reference's full forward, logits and not tokens, at toy widths on
seeded weights (`tests/test_llm_hybrid.py`'s way: every program is observed
where its logits become tokens).

Tolerance: 2e-5 absolute on logits about 0.15 wide. The programs and the
reference both compute in float32 and differ in the order of sums (grouped
experts, paged attention) and in the chunk's solve; that reads under 3e-6.
A state slot left dirty, a chunk that restarts from an empty state or a
dropped convolution tail moves a logit by 1e-3 or more
(tests/test_solar_open2_model.py).
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
from ray_tpu.models import solar_open2 as so
from ray_tpu.models import solar_open2_reference as ref

from hybrid_toy import assert_idle_lanes_keep_their_state
from solar_open2_toy import toy_config

TOLERANCE = 2e-5
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
    return so.init_params(CFG, 11)


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
    outputs, rids = [], []
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


# Prefill then decode through cache and state: a prompt fed in one, two and
# three chunks (budget 16 a step; the delta rule's chunk is 8).
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
def test_a_slot_reused_without_being_cleared_starts_empty(params, observed, depth):
    prompts = prompts_of(20, 13, 33, 7, 26, seed=1)
    engine, outputs, rows = serve(
        params, observed, prompts, 5, max_decode_slots=2,
        async_scheduling=bool(depth),
    )
    stats = engine.stats()
    assert stats["state_slots"] == 2 and stats["state_slot_resets"] == 5
    assert stats["state_slots_in_use"] == 0
    # Nothing cleared them: the last sequences' states are still there.
    assert all(float(jnp.abs(pool).max()) > 0 for pool in engine.runner.state[1])
    assert_matches_reference(params, prompts, outputs, rows)


@DEPTHS
def test_lanes_join_and_leave_around_a_request(params, observed, depth):
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
    assert_idle_lanes_keep_their_state(runner, 2 * CFG.layer_types.count(so.KDA))


def test_a_preempted_sequence_is_prefilled_again(params, observed):
    prompts = prompts_of(20, 21, seed=4)
    engine, outputs, rows = serve(
        params, observed, prompts, 24, num_blocks=9, max_decode_slots=2,
    )
    stats = engine.stats()
    assert stats["num_preemptions"] > 0
    assert stats["state_slot_resets"] == 2 + stats["num_preemptions"]
    assert_matches_reference(params, prompts, outputs, rows)


def test_the_paged_kernel_serves_the_same_logits(params, observed):
    """attn_impl="pallas": the paged kernel (interpreted) at two query
    heads a cached head, in the decode and chunk programs."""
    prompts = prompts_of(21, 38, seed=6)
    _, outputs, rows = serve(params, observed, prompts, 4, attn_impl="pallas")
    assert_matches_reference(params, prompts, outputs, rows)


# What a model with recurrent layers refuses at construction, in the words
# granite's is refused in.
@pytest.mark.parametrize("changes", [
    dict(speculation="ngram"),
    dict(kv_fabric=KVFabricConfig(name="solar-open2-test")),
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


@DEPTHS
def test_counters_of_recurrent_layers_and_routed_experts(params, observed, depth):
    prompts = prompts_of(18, 40, 5, 29, seed=8)
    engine, outputs, rows = serve(params, observed, prompts, 7, async_scheduling=bool(depth))
    stats = engine.stats()
    lane_steps = sum(len(got) for seen in rows.values() for got in seen.values())
    lane_steps -= stats["prefill_chunk_dispatches"]  # rows a chunk sampled
    if not depth:
        assert lane_steps == stats["decode_tokens"]
    # From the declared shapes: a float32 state and a tail of three positions.
    assert stats["state_slot_bytes"] == 3 * (4 * 8 * 8 * 4 + 3 * CFG.conv_dim * 4)
    assert stats["state_pool_bytes"] == 4 * stats["state_slot_bytes"]
    assert stats["decode_state_bytes"] == 2 * stats["state_slot_bytes"] * lane_steps
    assert stats["prefill_scan_tokens"] == stats["prefill_tokens"] == 18 + 40 + 5 + 29
    assert stats["state_slots"] == 4 and stats["state_slots_in_use"] == 0
    assert stats["recurrent_shape"] == {
        "num_layers": 3, "num_heads": 4, "key_dim": 8, "value_dim": 8,
        "decay_width": 8, "conv_width": 4, "conv_dim": 96, "chunk_size": 8,
        "state_itemsize": 4, "conv_itemsize": 4,
    }
    # By class name, as Laguna's: the readers of `llm.mixer.attention.full`.
    assert stats["attention_shape"] == {"full": {
        "num_layers": 2, "num_heads": 2, "head_dim": 16, "kv_itemsize": 4,
        "num_query_heads": 4, "prefill_q_tile": 16, "prefill_rows_per_product": 32,
        "decode_tile_tokens": 96, "decode_bytes_in_flight": 24576,
    }}
    # Every layer routes: the decode result carries the counts behind the
    # lanes' tokens, and each decoding lane chose 3 experts in 5 layers.
    assert engine.runner._tail == len(hr.DECODE_COUNTS)
    routed = stats["decode_expert_assignments"] + stats["decode_expert_assignments_absent"]
    assert routed == CFG.num_experts_per_tok * CFG.num_hidden_layers * lane_steps
    assert 0 < stats["decode_expert_assignments"] < routed
    assert 0 < stats["prefill_expert_assignments"] <= 3 * 5 * 92
    assert stats["prefill_expert_assignments"] <= stats["prefill_expert_rows_walked"]
    assert 0 < stats["decode_experts_touched"] <= stats["decode_expert_assignments"]
    assert stats["decode_context_tokens"] > 0
    assert stats["expert_shape"] == {
        "num_layers": 5, "num_experts": 8, "experts_held": 4,
        "experts_per_token": 3, "hidden_size": 64, "expert_width": 32,
        "weight_itemsize": 4,
    }
    assert stats["layer_mixers"] == {"gqa": ["gqa"], "kda": ["kda"]}


def test_op_scopes_name_every_part(params):
    """The new scopes are in a lowered decode and chunk program, and no
    operation under an `llm.` scope is one the model does not declare."""
    engine = LLMEngine(CFG, engine_config(), params=params)
    report = engine.runner.device_report()
    decode = set(report["op_scopes"]["jit__decode_step"].values())
    assert {"llm.mixer.kda.update", "llm.mixer.kda.proj", "llm.mixer.attention.proj",
            "llm.mixer.attention.full", "llm.moe.router", "llm.moe.routed",
            "llm.moe.shared", "llm.head"} <= decode
    assert "llm.mixer.kda.scan" not in decode
    chunk = set(report["op_scopes"]["jit__prefill_suffix_step"].values())
    assert {"llm.mixer.kda.scan", "llm.mixer.kda.proj", "llm.moe.routed"} <= chunk
    assert "llm.mixer.kda.update" not in chunk
    assert set(so.SCOPES) >= {s for s in decode | chunk if s.startswith("llm.")}


def test_stats_carry_what_the_benchmarks_readers_take(params, observed):
    """The keys `benchmark/layer_metrics/` reads for this model (kda_*, moe_*,
    expert_load_max_over_mean, full_attn_roofline, head_roofline), under the
    names the other models publish them by, and the flight record's rounds."""
    engine, _, _ = serve(params, observed, prompts_of(20, 33, seed=9), 5)
    stats = engine.stats()
    assert {"num_layers", "num_heads", "key_dim", "value_dim", "decay_width", "conv_width",
            "conv_dim", "chunk_size", "state_itemsize", "conv_itemsize"} == set(
        stats["recurrent_shape"])
    assert {"num_layers", "num_experts", "experts_held", "experts_per_token",
            "hidden_size", "expert_width", "weight_itemsize"} == set(stats["expert_shape"])
    assert {"num_layers", "num_heads", "head_dim", "kv_itemsize", "num_query_heads"} <= set(
        stats["attention_shape"]["full"])
    assert set(stats["head_shape"]) == {"vocab_size", "hidden_size", "weight_itemsize"}
    for counter in ("decode_state_bytes", "prefill_scan_tokens", "decode_experts_touched",
                    "decode_expert_assignments", "decode_expert_assignments_absent",
                    "decode_expert_load_max", "prefill_expert_assignments",
                    "prefill_expert_rows_walked", "decode_context_tokens",
                    "decode_dispatches", "prefill_chunk_dispatches"):
        assert stats[counter] > 0, counter
