"""Two block classes under one manager (`cache.WindowBlocks` beside the full
class's `BlockAllocator`, both driven by `Scheduler`): what the window class
holds and when it lets go, and that admission, growth, preemption and
release ask both classes and succeed or fail together. Host bookkeeping
only, but for the last test, which serves the toy Laguna with the null block
of both pools filled with garbage.
"""

import random

import numpy as np
import pytest

from ray_tpu.llm.cache import (
    NULL_BLOCK,
    BlockAllocator,
    CacheClass,
    WindowBlocks,
    window_class_of,
)
from ray_tpu.llm.config import EngineConfig
from ray_tpu.llm.scheduler import Request, Scheduler, Sequence


def visible_blocks(next_position, horizon, block_size, upto):
    """Blocks (by index) holding a position that some query at
    `next_position` or later can see, among positions 0 .. upto."""
    lowest = max(next_position - horizon + 1, 0)
    return {p // block_size for p in range(lowest, upto + 1)}


@pytest.mark.parametrize("horizon,block_size", [(12, 8), (16, 8), (32, 16), (5, 4), (1, 4), (512, 16)])
def test_window_blocks_are_freed_exactly_when_no_later_query_sees_them(horizon, block_size):
    """A sequence grows a token at a time: after every commit the table
    holds exactly the blocks a later query can still see (plus the one being
    written), the rest are the null block, and never one block fewer."""
    window = WindowBlocks(64, block_size, horizon)
    table, first = [], 0
    length = 3 * horizon + 5 * block_size
    for n in range(length):
        window.extend(table, n)  # the token at position n is written
        first = window.advance(table, first, n + 1)  # and committed
        live = {i for i, b in enumerate(table) if b != NULL_BLOCK}
        seen = visible_blocks(n + 1, horizon, block_size, n)
        # Never earlier: what a later query sees is held. Exactly then: what
        # is held beyond that is the block the next token is written into.
        assert seen <= live, (n, live, seen)
        assert live - seen <= {(n + 1) // block_size}, (n, live, seen)
        assert first == min(live, default=len(table))
        assert window.allocator.num_allocated == len(live) <= window.steady_blocks
        assert window.held_tokens(first, n + 1) == n + 1 - first * block_size
    assert window.num_freed == len(table) - len(live) > 0
    window.release(table, first)
    assert window.allocator.num_allocated == 0


def test_a_chunk_holds_the_window_and_itself_until_it_commits():
    window = WindowBlocks(64, 8, 12)
    table, first = [], 0
    window.extend(table, 39)  # a first chunk of 40 tokens
    assert window.allocator.num_allocated == 5
    first = window.advance(table, first, 40)
    assert first == (40 - 12 + 1) // 8 == 3 and table[:3] == [NULL_BLOCK] * 3
    window.extend(table, 40 + 24 - 1)  # the next chunk, 24 tokens at offset 40
    # The 12 tokens before the boundary are still held beside the chunk's.
    assert window.allocator.num_allocated == 8 - 3
    first = window.advance(table, first, 64)
    assert first == (64 - 12 + 1) // 8 == 6


def test_derived_size_never_refuses_a_lane():
    """Random sequences through every lane, chunked prefill and decode with
    one step of lookahead: in use never passes lanes x (horizon / bs + 2) +
    the chunk in flight, which is what `blocks_needed` provides."""
    lanes, horizon, bs, chunk = 6, 512, 16, 3584  # the cell's, at 6 lanes
    window = WindowBlocks(WindowBlocks.blocks_needed(lanes, horizon, bs, chunk), bs, horizon)
    bound = lanes * (horizon // bs + 2) + chunk // bs + 1
    assert window.allocator.num_usable == bound
    rng = random.Random(0)
    seqs = [{"table": [], "first": 0, "n": 0, "prompt": rng.randrange(1, 12288),
             "total": 0} for _ in range(lanes)]
    for s in seqs:
        s["total"] = s["prompt"] + rng.randrange(1, 2048)
    peak = longest = 0
    for _ in range(6000):
        for s in seqs:
            if s["n"] < s["prompt"]:  # one chunk, committed before the next
                take = min(chunk, s["prompt"] - s["n"])
                window.extend(s["table"], s["n"] + take - 1)
                peak = max(peak, window.allocator.num_allocated)
                s["n"] += take
                s["first"] = window.advance(s["table"], s["first"], s["n"])
        for s in seqs:
            if s["n"] >= s["prompt"]:  # decode: this step's write and the next's
                window.extend(s["table"], s["n"] + 1)
        peak = max(peak, window.allocator.num_allocated)
        for s in seqs:
            if s["n"] >= s["prompt"]:
                s["n"] += 1
                s["first"] = window.advance(s["table"], s["first"], s["n"])
                if s["n"] >= s["total"]:
                    window.release(s["table"], s["first"])
                    longest = max(longest, s["n"])
                    s.update(table=[], first=0, n=0, prompt=rng.randrange(1, 12288))
                    s["total"] = s["prompt"] + rng.randrange(1, 2048)
    assert 0 < peak <= bound
    # Contexts grew to thousands of tokens; the class held a window a lane.
    assert longest > 8000
    assert peak <= lanes * (horizon // bs + 2) + chunk // bs


def scheduler(full_blocks=64, window_blocks=32, horizon=12, bs=8, lanes=4):
    return Scheduler(
        BlockAllocator(full_blocks, bs, enable_prefix_caching=False), lanes, 32,
        window=WindowBlocks(window_blocks, bs, horizon),
    )


def sequence(n, rid):
    return Sequence(Request(rid, list(range(n)), 8))


def test_both_classes_are_admitted_or_neither():
    # The window class is short: nothing is taken from the full class.
    sched = scheduler(window_blocks=3)  # 2 usable < steady 4
    sched.add(sequence(20, "a"))
    assert sched.schedule_prefills(4) == []
    assert sched.allocator.num_allocated == 0 and sched.window.allocator.num_allocated == 0
    # The full class is short: nothing is taken from the window class.
    sched = scheduler(full_blocks=3)
    sched.add(sequence(40, "b"))
    assert sched.schedule_prefills(4) == []
    assert sched.allocator.num_allocated == 0 and sched.window.allocator.num_allocated == 0
    # Both have room: the full class gives the prompt's blocks at once, the
    # window class the chunk's when it is dispatched.
    sched = scheduler()
    seq = sequence(40, "c")
    sched.add(seq)
    assert sched.schedule_prefills(4) == [seq]
    assert len(seq.block_table) == 5 and seq.window_table == []
    assert sched.reserve_chunk(seq, 16)
    assert len(seq.window_table) == 2


def test_growth_asks_both_classes_and_release_returns_every_block():
    sched = scheduler(window_blocks=8)  # 7 usable
    a, b = sequence(16, "a"), sequence(16, "b")
    for seq in (a, b):
        sched.add(seq)
    assert sched.schedule_prefills(4) == [a, b]
    for seq in (a, b):
        assert sched.reserve_chunk(seq, 16)
        seq.num_cached = 16
        sched.advance_window(seq)
        seq.generated.append(1)
    # Both decode: each needs block 2 in both classes. The window class has
    # 7 - 2 x 2 = 3 free.
    assert sched.schedule_decode() == [a, b]
    assert len(a.window_table) == len(a.block_table) == 3
    # Exhaust the window class: the next growth preempts the youngest.
    for seq in (a, b):
        seq.num_cached = 24
        sched.advance_window(seq)
    hog = sched.window.allocator.allocate(sched.window.allocator.num_free)
    decoding = sched.schedule_decode()
    assert b not in decoding and sched.num_preemptions >= 1
    assert b.block_table == [] and b.window_table == [] and b.window_first == 0
    # The lookahead is all or nothing over both classes.
    if a in decoding:
        before = (sched.allocator.num_allocated, sched.window.allocator.num_allocated)
        a.num_cached = 31
        sched.advance_window(a)
        if not sched.reserve_decode_lookahead([a]):
            assert before[0] == sched.allocator.num_allocated
    sched.window.allocator.free(hog)
    for seq in list(sched.running):
        sched.finish(seq, "length")
    sched.abort("b")
    assert sched.allocator.num_allocated == 0
    assert sched.window.allocator.num_allocated == 0


def test_a_chunk_the_window_class_cannot_hold_goes_back_to_the_queue():
    sched = scheduler(window_blocks=6)  # 5 usable
    seq = sequence(64, "a")
    sched.add(seq)
    assert sched.schedule_prefills(4) == [seq]
    assert not sched.reserve_chunk(seq, 64)  # 8 blocks asked of 5
    assert not seq.is_running and sched.waiting[0] is seq
    assert sched.allocator.num_allocated == 0 and sched.window.allocator.num_allocated == 0


def test_the_engine_derives_the_window_class_from_the_model():
    from laguna_toy import WINDOW, toy_config

    cfg = toy_config()
    assert window_class_of(cfg) == CacheClass("window", 3, WINDOW)
    assert window_class_of(object()) is None
    ecfg = EngineConfig(block_size=8, num_blocks=64, max_decode_slots=4,
                        max_blocks_per_seq=12, prefill_buckets=(16, 32, 64),
                        max_prefill_tokens_per_step=16)
    # lanes x (ceil(12 / 8) + 2) + the 16-token chunk's 2 + 1, and the null block.
    assert ecfg.window_class_blocks(WINDOW) == 1 + 4 * 4 + 2 + 1
    whole = EngineConfig(block_size=8, num_blocks=64, max_decode_slots=4,
                         max_blocks_per_seq=12, prefill_buckets=(16, 32, 64),
                         max_prefill_tokens_per_step=0)
    assert whole.window_class_blocks(WINDOW) == 1 + 4 * 4 + 8 + 1  # a whole prompt


@pytest.mark.parametrize("impl", ["reference", "pallas"])
def test_the_null_entry_is_never_read(impl):
    """Serve the toy model with block 0 of every pool full of garbage (as
    idle lanes' scatters leave it, only larger): tokens and counters are
    those of a clean run. Freed window entries point there."""
    import jax.numpy as jnp

    from laguna_toy import toy_config
    from ray_tpu.llm.engine import LLMEngine
    from ray_tpu.models import laguna as lg

    cfg = toy_config()
    params = lg.init_params(cfg, 5)
    ecfg = EngineConfig(block_size=8, num_blocks=64, max_decode_slots=4,
                        max_blocks_per_seq=12, prefill_buckets=(16, 32, 64),
                        max_prefill_tokens_per_step=16, attn_impl=impl)
    rng = np.random.default_rng(1)
    prompts = [list(map(int, rng.integers(0, 512, size=n))) for n in (37, 5, 50)]

    def serve(garbage):
        engine = LLMEngine(cfg, ecfg, params=params)
        if garbage:
            runner = engine.runner
            runner.k_cache = tuple(k.at[:, 0].set(3e4) for k in runner.k_cache)
            runner.v_cache = tuple(v.at[:, 0].set(-3e4) for v in runner.v_cache)
        out = engine.generate(prompts, max_new_tokens=24)
        assert engine.stats()["window_blocks_freed"] > 0
        return out

    assert serve(True) == serve(False)
