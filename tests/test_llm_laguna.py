"""A model with sliding-window layers through `ray_tpu.llm`: the engine, the
scheduler's two block classes and `HybridRunner`'s programs against the plain
float32 reference's full forward, logits and not tokens, at toy widths on
seeded weights (tests/laguna_toy.py: a window of 12, shorter than every
context here).

Every program run is observed where its logits become tokens
(`_HybridPrograms._sample`), and each observed row is compared with the
reference's logits at that position of the finished sequence: the first
token after a prompt fed in one, two or three chunks, and every decode step
through both pools.

Tolerance: 2e-6 absolute on logits about 0.16 wide. The programs and the
reference both compute in float32 here and differ in the order of sums
(grouped experts, paged attention) and in the rotation's angles (float32 in
both, of float32 and float64 frequencies); that reads 4e-7 at most. A window
block freed a step early, a window table read at the wrong entry, a chunk
that rotates from position 0 again or a lane that reads another's blocks
moves a logit by 1e-3 or more.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.llm import hybrid_runner as hr
from ray_tpu.llm.config import EngineConfig, KVFabricConfig
from ray_tpu.llm.engine import LLMEngine
from ray_tpu.models import laguna as lg
from ray_tpu.models import laguna_reference as ref

from laguna_toy import WINDOW, toy_config

TOLERANCE = 2e-6
PAD = 96  # the reference runs every sequence at one padded length
CFG = toy_config()
BS = 8
STEADY = -(-WINDOW // BS) + 2  # window blocks a lane holds at most between steps


@pytest.fixture(scope="module")
def params():
    return lg.init_params(CFG, 11)


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
        block_size=BS, num_blocks=64, max_decode_slots=4, max_blocks_per_seq=12,
        prefill_buckets=(16, 32, 64), max_prefill_tokens_per_step=16,
        attn_impl="reference",
    )
    fields.update(changes)
    return EngineConfig(**fields)


def serve(params, observed, prompts, new_tokens, **changes):
    """Run `prompts` to completion; returns the engine, the generated
    tokens, for each request {row: observed logits} over the rows of its
    finished sequence that a program sampled from, and the most window
    blocks in use after any step."""
    del observed[:]
    engine = LLMEngine(CFG, engine_config(**changes), params=params)
    runner, metas = engine.runner, []
    prefill, suffix, decode = runner.prefill, runner.prefill_suffix, runner.decode

    def on_prefill(token_ids, block_ids, window_ids):
        metas.append([(engine._current_rid, None, len(token_ids) - 1)])
        return prefill(token_ids, block_ids, window_ids=window_ids)

    def on_suffix(token_ids, block_ids, offset, window_ids):
        metas.append([(engine._current_rid, None, offset + len(token_ids) - 1)])
        return suffix(token_ids, block_ids, offset, window_ids=window_ids)

    def on_decode(tokens, positions, block_tables, context_lens, window_tables):
        decoding = [s for s in engine.scheduler.running if not s.prefilling]
        live = np.flatnonzero(context_lens)
        assert len(live) == len(decoding)
        metas.append([
            (seq.request.request_id, int(lane), int(positions[lane]))
            for lane, seq in zip(live, decoding)
        ])
        return decode(tokens, positions, block_tables, context_lens,
                      window_tables=window_tables)

    runner.prefill, runner.prefill_suffix, runner.decode = on_prefill, on_suffix, on_decode
    outputs, rids = [], []
    for i, prompt in enumerate(prompts):
        tokens = []
        rids.append(engine.add_request(
            list(prompt), max_new_tokens=new_tokens, request_id=f"r{i}",
            on_token=tokens.append,
        ))
        outputs.append(tokens)
    most = 0
    while engine.has_work():
        engine.step()
        most = max(most, engine.scheduler.window.allocator.num_allocated)
    jax.effects_barrier()
    assert len(metas) == len(observed)
    rows = {rid: {} for rid in rids}
    for meta, logits in zip(metas, observed):
        for rid, lane, row in meta:
            rows[rid].setdefault(row, []).append(logits if lane is None else logits[lane])
    return engine, outputs, rows, most


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


# Prefill in chunks, then decode through both pools: a prompt fed in one,
# two and three chunks (budget 16 a step), shorter and longer than the window.
@DEPTHS
@pytest.mark.parametrize("length", [9, 30, 40], ids=["1chunk", "2chunks", "3chunks"])
def test_chunked_prefill_then_decode(params, observed, depth, length):
    prompts = prompts_of(length, seed=length)
    engine, outputs, rows, _ = serve(
        params, observed, prompts, 20, async_scheduling=bool(depth)
    )
    stats = engine.stats()
    assert stats["prefill_chunk_dispatches"] == -(-length // 16)
    assert stats["window_blocks_freed"] > 0  # the context passed the window
    assert_matches_reference(params, prompts, outputs, rows)


@DEPTHS
def test_a_request_alone_and_among_others(params, observed, depth):
    mine, others = prompts_of(27, seed=2), prompts_of(11, 35, 19, seed=3)
    _, alone_out, alone, _ = serve(params, observed, mine, 16, async_scheduling=bool(depth))
    alone = {row: got[0].copy() for row, got in alone["r0"].items()}
    prompts = others[:1] + mine + others[1:]
    _, outputs, rows, _ = serve(params, observed, prompts, 16, async_scheduling=bool(depth))
    assert outputs[1] == alone_out[0]
    for row, got in rows["r1"].items():
        if row in alone:
            assert float(np.abs(got[0] - alone[row]).max()) < TOLERANCE
    assert_matches_reference(params, prompts, outputs, rows)


@DEPTHS
def test_a_preempted_sequence_is_prefilled_again(params, observed, depth):
    prompts = prompts_of(20, 21, seed=4)
    engine, outputs, rows, _ = serve(
        params, observed, prompts, 24, num_blocks=9, max_decode_slots=2,
        async_scheduling=bool(depth),
    )
    stats = engine.stats()
    assert stats["num_preemptions"] > 0
    assert all(len(out) == 24 for out in outputs)
    assert_matches_reference(params, prompts, outputs, rows)
    classes = stats["cache_classes"]
    assert classes["full"]["blocks_in_use"] == classes["window"]["blocks_in_use"] == 0


def test_depth_1_gives_depth_0s_tokens(params, observed):
    prompts = prompts_of(18, 40, 5, 29, 12, 33, seed=5)
    _, sync, _, _ = serve(params, observed, prompts, 12, async_scheduling=False)
    engine, chained, _, _ = serve(params, observed, prompts, 12, async_scheduling=True)
    assert chained == sync
    assert engine.stats()["chained_decode_dispatches"] > 0


def test_the_paged_kernel_serves_the_same_logits(params, observed):
    """attn_impl="pallas": the paged kernel (interpreted) with its window
    bound in the decode and chunk programs, 6 and 4 query heads over 2."""
    prompts = prompts_of(21, 38, seed=6)
    _, outputs, rows, _ = serve(params, observed, prompts, 18, attn_impl="pallas")
    assert_matches_reference(params, prompts, outputs, rows)


def test_window_blocks_stay_bounded_while_contexts_grow(params, observed):
    """Four lanes, contexts growing to 90 tokens (seven windows): the window
    class never holds more than lanes x (window / bs + 2) + the chunk in
    flight, and the running sequences hold several times less cache than one
    class for every layer would."""
    prompts = prompts_of(40, 33, 48, 25, 37, 44, seed=9)
    engine, outputs, rows, most = serve(params, observed, prompts, 46)
    stats = engine.stats()
    lanes, chunk_blocks = 4, 16 // BS
    assert 0 < most <= lanes * STEADY + chunk_blocks
    assert stats["cache_classes"]["window"]["blocks"] == lanes * STEADY + chunk_blocks + 1
    assert max(len(p) + len(o) for p, o in zip(prompts, outputs)) >= 90
    full, win = 2, 3  # layers of each class
    saving = (full + win) * stats["held_tokens_full"] / (
        full * stats["held_tokens_full"] + win * stats["held_tokens_window"]
    )
    assert saving > 1.5
    assert_matches_reference(params, prompts, outputs, rows)


# What such a model refuses at construction, and the prefix cache.
@pytest.mark.parametrize("changes", [
    dict(speculation="ngram"),
    dict(kv_fabric=KVFabricConfig(name="laguna-test")),
    dict(kv_cache_dtype="int8"),
    dict(tensor_parallel_size=2),
    dict(enable_prefix_caching=True),  # asked for; the default (None) is served without
], ids=["speculation", "kv_fabric", "int8", "tensor_parallel", "prefix_caching"])
def test_refused_at_construction(params, changes):
    with pytest.raises(ValueError, match="sliding-window"):
        LLMEngine(CFG, engine_config(**changes), params=params)


def test_no_prefix_hit_on_a_model_with_a_window_class(params, observed):
    prompt = prompts_of(40, seed=7)
    engine, outputs, _, _ = serve(params, observed, prompt * 3, 3, max_decode_slots=1)
    stats = engine.stats()
    assert outputs[0] == outputs[1] == outputs[2]
    assert stats["prefix_caching"] is False and stats["recurrent_state"] is False
    assert stats["prefix_cache_hit_tokens"] == 0
    assert stats["prefill_tokens"] == 3 * 40
    assert engine.allocator.num_evictable == 0  # nothing hashed, nothing kept


@DEPTHS
def test_counters(params, observed, depth):
    prompts = prompts_of(18, 40, 5, 29, seed=8)
    engine, outputs, rows, _ = serve(params, observed, prompts, 7, async_scheduling=bool(depth))
    stats = engine.stats()
    lane_steps = sum(len(got) for seen in rows.values() for got in seen.values())
    lane_steps -= stats["prefill_chunk_dispatches"]  # rows a chunk sampled
    routed = stats["decode_expert_assignments"] + stats["decode_expert_assignments_absent"]
    sparse = CFG.mlp_layer_types.count(lg.SPARSE)
    assert routed == CFG.num_experts_per_tok * sparse * lane_steps
    if not depth:
        assert lane_steps == stats["decode_tokens"]
    assert stats["decode_window_tokens"] <= stats["decode_context_tokens"]
    assert stats["decode_window_tokens"] <= WINDOW * lane_steps
    assert stats["prefill_tokens"] == 18 + 40 + 5 + 29
    assert stats["prefill_window_pairs"] == sum(
        min(p + 1, WINDOW) for n in (18, 40, 5, 29) for p in range(n)
    )
    assert 0 < stats["prefill_expert_assignments"] <= 3 * sparse * 92
    assert stats["decode_experts_touched"] <= stats["decode_expert_assignments"]
    assert stats["expert_shape"] == {
        "num_layers": sparse, "num_experts": 8, "experts_held": 4,
        "experts_per_token": 3, "hidden_size": 64, "expert_width": 32,
        "weight_itemsize": 4,
    }
    assert stats["attention_shape"] == {
        # The widest chunk is one q tile of 16; a cached head's two or
        # three query heads are the rows of one product.
        "full": {"num_layers": 2, "num_heads": 2, "head_dim": 16,
                 "kv_itemsize": 4, "num_query_heads": 4,
                 "prefill_q_tile": 16, "prefill_rows_per_product": 32,
                 # A decode walk's compute block is the whole 96-token
                 # table, K and V of 2 heads of 16 in float32.
                 "decode_tile_tokens": 96, "decode_bytes_in_flight": 24576},
        "window": {"num_layers": 3, "num_heads": 2, "head_dim": 16,
                   "kv_itemsize": 4, "num_query_heads": 6, "horizon": WINDOW,
                   "prefill_q_tile": 16, "prefill_rows_per_product": 48,
                   "decode_tile_tokens": 96, "decode_bytes_in_flight": 24576},
    }
    classes = stats["cache_classes"]
    assert classes["full"] == {
        "layers": 2, "horizon": None, "blocks": 63, "blocks_in_use": 0,
        "bytes_per_token": 2 * 2 * 2 * 16 * 4,
    }
    assert classes["window"]["horizon"] == WINDOW and classes["window"]["layers"] == 3
    assert stats["held_tokens_window"] < stats["held_tokens_full"]
    assert "state_slots" not in stats and "recurrent_shape" not in stats


def test_op_scopes_name_every_part(params):
    engine = LLMEngine(CFG, engine_config(), params=params)
    report = engine.runner.device_report()
    wanted = set(lg.SCOPES)
    for program in ("jit__decode_step", "jit__prefill_step", "jit__prefill_suffix_step"):
        assert wanted <= set(report["op_scopes"][program].values()), program
    assert set(report["op_scopes"]) == {
        "jit__decode_step", "jit__prefill_step", "jit__prefill_suffix_step"
    }


def test_built_by_its_configurations_type(params):
    from ray_tpu.llm.model_runner import build_runner

    runner = build_runner(CFG, engine_config(), params=params)
    assert type(runner) is hr.HybridRunner and runner.model is lg
    assert [k.shape[0] for k in runner.k_cache] == [2, 3]
    assert runner.k_cache[1].shape[1] == engine_config().window_class_blocks(WINDOW)
