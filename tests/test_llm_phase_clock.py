"""The engine's one clock (llm.observability.StepPhaseClock / CompileClock).

  * the phases partition the step loop's wall time, at both depths, and every
    flight record's phases sum to its duration;
  * a prefill chunk that runs between two decode batches adds its device time
    to `wait` and nothing to `host_exposed` — the fault of the old host gap,
    which counts it, pinned with a runner whose chunk program sleeps;
  * a chained async dispatch samples 0 exposed;
  * with instrument=False the decode loop reads no clock at all;
  * a jax.profiler session holds `llm.step*` annotations on a host plane;
  * set-up is on the same footing: warm-up and JAX's compile events in
    stats(), and the split on every warm-up round of the flight record;
  * around `prepare` the step thread's own CPU clock beside the wall's:
    prepare's CPU seconds never pass its wall seconds, are the thread's
    `thread_time` over its prepare stretches and no other phase's, and
    the clock is read nowhere else; threads that spin on the interpreter
    beside it grow `step_prepare_offcpu_s` and not `step_prepare_cpu_s`; a
    block in `wait` is not prepare's;
  * a step that holds the thread over 0.25 s outside `wait` is counted in
    `stall_steps` and kept in the flight record's `stalls`; a long `wait`
    is not.
"""

import gc
import os
import re
import sys
import threading
import time
import types

import pytest

import jax
import jax.numpy as jnp

from ray_tpu.llm import EngineConfig, LLMEngine
from ray_tpu.llm import engine as engine_module
from ray_tpu.llm import observability as observability_module
from ray_tpu.llm.engine import LLMServer
from ray_tpu.llm.observability import (
    STALL_SECONDS,
    STEP_PHASES,
    StepPhaseClock,
)
from ray_tpu.models.gpt import GPTConfig
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
BASE = dict(
    block_size=8, num_blocks=64, max_decode_slots=4, max_blocks_per_seq=16
)
PHASE_KEYS = tuple(f"step_{phase}_s" for phase in STEP_PHASES)


def window(before: dict, after: dict, *keys) -> dict:
    return {key: after[key] - before[key] for key in keys}


def run_to_idle(eng) -> float:
    """Step until nothing is live; the wall seconds from the first step's
    entry to the last step's return, give or take the loop itself."""
    t0 = time.perf_counter()
    while eng.has_work():
        eng.step()
    return time.perf_counter() - t0


@pytest.mark.parametrize("mode", (False, True), ids=("sync", "async"))
def test_phases_partition_the_wall_time(mode):
    eng = LLMEngine(TINY, EngineConfig(async_scheduling=mode, **BASE), seed=0)
    prompts = ([5, 9, 11, 3, 7], [8, 2, 4, 6, 1, 3, 9, 9, 2, 5, 7])
    eng.generate(list(prompts), max_new_tokens=3)  # compiles every shape
    before = eng.stats()
    for prompt in prompts:
        eng.add_request(list(prompt), max_new_tokens=60)
    wall = run_to_idle(eng)
    after = eng.stats()
    assert after["steps"] - before["steps"] >= 50
    spent = window(before, after, *PHASE_KEYS)
    assert all(seconds >= 0.0 for seconds in spent.values())
    assert sum(spent.values()) == pytest.approx(wall, rel=0.01)
    # Every step dispatched a program, and the loop was live in between.
    assert (
        after["dispatch_steps"] - before["dispatch_steps"]
        >= after["steps"] - before["steps"] - 2
    )
    assert spent["step_between_s"] > 0.0
    assert spent["step_prepare_s"] > 0.0 and spent["step_commit_s"] > 0.0
    # Each record's phases sum to its duration (both rounded to the µs).
    records = eng.flight_recorder.snapshot()["steps"]
    assert len(records) >= 50
    for record in records:
        assert set(record["phases"]) == set(STEP_PHASES) - {"between"}
        assert sum(record["phases"].values()) == pytest.approx(
            record["duration_s"], abs=1e-5
        )
    if not mode:
        # Depth 0: the device has nothing queued whenever the host
        # is not waiting, so exposed is the sum of the non-wait phases
        # (less the first dispatch after idle, which has no sample).
        exposed = after["host_exposed_total_s"] - before["host_exposed_total_s"]
        non_wait = sum(spent.values()) - spent["step_wait_s"]
        assert exposed <= non_wait
        assert exposed == pytest.approx(non_wait, rel=0.1)


@pytest.mark.parametrize("mode", (False, True), ids=("sync", "async"))
def test_prefill_chunk_between_decodes_is_wait_not_exposed(mode):
    """One stream decoding, a second prompt arrives: its chunk program
    runs between two decode batches (at depth 1, behind the chained
    decode of its own step). The device is busy for the chunk's whole
    run, so that time is `wait` and not `host_exposed`, in the window's
    total and in the step's own record; at depth 1 the chained dispatch
    beat the fetch and the step's exposed reads 0."""
    chunk_s = 0.5  # far above a loaded host's own work in one step
    eng = LLMEngine(
        TINY, EngineConfig(async_scheduling=mode, **BASE), seed=0
    )
    late = [9, 4, 7, 1, 8, 2, 6, 3, 5]
    eng.generate([[5, 9, 11, 3, 7], late], max_new_tokens=3)  # compile
    eng.allocator.reset_prefix_cache()

    in_prefill = []
    notify = eng.runner._dispatched

    def dispatched_then_runs():
        notify()
        if in_prefill:
            time.sleep(chunk_s)  # the device runs the chunk; the host waits

    def slow(program):
        def run(*args, **kwargs):
            in_prefill.append(True)
            try:
                return program(*args, **kwargs)
            finally:
                in_prefill.pop()

        return run

    eng.runner._dispatched = dispatched_then_runs
    eng.runner.prefill = slow(eng.runner.prefill)
    eng.runner.prefill_suffix = slow(eng.runner.prefill_suffix)

    eng.add_request([5, 9, 11, 3, 7], max_new_tokens=40)
    for _ in range(4):
        eng.step()  # prefilled and decoding
    eng.add_request(late, max_new_tokens=4)
    keys = PHASE_KEYS + ("host_exposed_total_s",)
    before = eng.stats()
    dispatches, samples = eng._clock.dispatches, eng._clock.exposed_samples
    eng.step()  # the chunk, then the decode batch
    delta = window(before, eng.stats(), *keys)
    record = eng.flight_recorder.snapshot()["steps"][-1]
    assert record["phase"] == "prefill+decode"
    assert eng._clock.dispatches - dispatches == 2
    assert eng._clock.exposed_samples - samples == 2
    assert delta["step_wait_s"] >= chunk_s
    assert record["phases"]["wait"] >= chunk_s
    assert delta["host_exposed_total_s"] < chunk_s / 2
    assert record["host_exposed_s"] == pytest.approx(
        delta["host_exposed_total_s"], abs=1e-6
    )
    if mode:
        assert record["chained"]
        assert record["host_exposed_s"] == 0.0
    while eng.has_work():
        eng.step()


def test_chained_async_dispatch_samples_zero_exposed():
    eng = LLMEngine(TINY, EngineConfig(async_scheduling=True, **BASE), seed=0)
    eng.generate([[5, 9, 11, 3, 7]], max_new_tokens=3)
    eng.add_request([5, 9, 11, 3, 7], max_new_tokens=30)
    chained = 0
    while eng.has_work():
        before = eng.stats()
        samples = eng._clock.exposed_samples
        eng.step()
        record = eng.flight_recorder.snapshot()["steps"][-1]
        if record["chained"]:
            chained += 1
            delta = window(before, eng.stats(), "host_exposed_total_s")
            assert eng._clock.exposed_samples - samples == 1
            assert delta["host_exposed_total_s"] == 0.0
            assert record["host_exposed_s"] == 0.0
    assert chained >= 10
    assert eng.stats()["inflight_steps"] == 0


@pytest.mark.parametrize("mode", (False, True), ids=("sync", "async"))
def test_instrument_off_reads_no_clock_in_the_decode_loop(mode, monkeypatch):
    """As the no-allocation test does for numpy: with instrument=False the
    steady decode loop makes no perf_counter call, in the engine or in the
    clock's module, and the counters stay where they were."""
    eng = LLMEngine(
        TINY,
        EngineConfig(async_scheduling=mode, instrument=False, **BASE),
        seed=0,
    )
    for prompt in ([5, 9, 11, 3, 7], [8, 2, 4, 6, 1, 3, 9]):
        eng.add_request(prompt, max_new_tokens=16)
    eng.step()
    eng.step()  # both admitted; the loop is now pure decode
    reads = []

    def counted():
        reads.append(1)
        return time.perf_counter()

    counting = types.SimpleNamespace(
        perf_counter=counted, time=time.time, monotonic=time.monotonic
    )
    monkeypatch.setattr(engine_module, "time", counting)
    monkeypatch.setattr(observability_module, "time", counting)
    for _ in range(6):
        eng.step()
    assert reads == []
    monkeypatch.undo()
    while eng.has_work():
        eng.step()
    stats = eng.stats()
    assert all(stats[key] == 0.0 for key in PHASE_KEYS)
    assert eng._clock.exposed_samples == 0 and eng._clock.dispatches == 0
    assert stats["host_exposed_total_s"] == 0.0
    assert stats["dispatch_steps"] == 0
    # No listener is registered for an uninstrumented engine, so stats()
    # has no jax_* totals to show.
    assert eng._compile_clock is None
    assert not [key for key in stats if key.startswith("jax_")]
    # What the kernel was asked to read is counted all the same.
    assert stats["decode_context_tokens"] > stats["decode_dispatches"] > 0


@pytest.mark.parametrize("mode", (False, True), ids=("sync", "async"))
def test_decode_work_counters_follow_the_batches(mode):
    eng = LLMEngine(
        TINY, EngineConfig(async_scheduling=mode, **BASE), seed=0
    )
    eng.add_request([5, 9, 11, 3, 7], max_new_tokens=5)
    while eng.has_work():
        eng.step()
    stats = eng.stats()
    # The prefill emits token 1; decodes 2..5 read contexts 5, 6, 7, 8.
    # Depth 1 sees the length stop one step late: one dispatch more, past
    # the last token, reads a context of 9 and commits nothing.
    overshoot = 1 if mode else 0
    assert stats["decode_dispatches"] == 4 + overshoot
    assert stats["chained_decode_dispatches"] == (4 if mode else 0)
    # One token a sequence a decode dispatch: the batch width is the
    # existing decode_tokens over the dispatches.
    assert stats["decode_tokens"] == 4
    assert stats["decode_context_tokens"] == 5 + 6 + 7 + 8 + 9 * overshoot
    assert stats["attention_shape"] == {
        "num_layers": 2, "num_heads": 4, "head_dim": 16, "kv_itemsize": 4,
        # The widest warmed chunk (32 tokens) is one q tile, and a head
        # with a cached head of its own is one product's rows.
        "prefill_q_tile": 32, "prefill_rows_per_product": 32,
        # Heads narrower than a lane tile: XLA gathers the blocks, and the
        # decode walk keeps its 128-token compute block.
        "decode_tile_tokens": 128, "decode_bytes_in_flight": 65536,
    }


def test_profiler_session_holds_step_annotations_on_a_host_plane(tmp_path):
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "benchmark"))
    try:
        from lib import xplane
    finally:
        sys.path.pop(0)
    from jax.profiler import ProfileData

    eng = LLMEngine(TINY, EngineConfig(**BASE), seed=0)
    eng.generate([[5, 9, 11, 3, 7]], max_new_tokens=3)
    trace_dir = str(tmp_path / "trace")
    xplane.start_trace(trace_dir)
    deadline = time.monotonic() + 1.0
    while time.monotonic() < deadline:
        if not eng.has_work():
            eng.add_request([5, 9, 11, 3, 7], max_new_tokens=100)
        eng.step()
    xplane.stop_trace()
    while eng.has_work():
        eng.step()
    path = xplane.newest_xplane(trace_dir)
    assert path is not None
    host = re.compile(r"^/host:CPU$")
    threads = [
        line.name
        for plane in ProfileData.from_file(path).planes
        if host.match(plane.name)
        for line in plane.lines
    ]
    events = [
        e for e in xplane.load_events(path, planes=host, lines=threads)
        if e[2].startswith("llm.step")
    ]
    by_name = {}
    for _, _, name, start, dur in events:
        by_name.setdefault(name, []).append((start, start + dur))
    assert set(by_name) == {
        "llm.step", "llm.step.schedule", "llm.step.prepare",
        "llm.step.wait", "llm.step.commit",
    }
    assert len({e[1] for e in events}) == 1  # one thread steps the engine
    steps = sorted(by_name["llm.step"])
    assert len(steps) >= 10
    # Every phase lies inside a step's annotation, and in a synchronous
    # decode step prepare ends where wait begins and wait where commit does.
    for name in ("prepare", "wait", "commit"):
        for start, end in by_name["llm.step." + name]:
            assert any(s <= start and end <= e for s, e in steps)
    ordered = sorted(
        (start, name) for name, spans in by_name.items()
        if name != "llm.step" for start, _ in spans
    )
    order = [name.rsplit(".", 1)[1] for _, name in ordered]
    assert "schedule prepare wait commit" in " ".join(order)


def test_setup_is_on_the_clock_and_rounds_carry_the_split():
    ecfg = EngineConfig(
        block_size=8, num_blocks=64, max_blocks_per_seq=16,
        prefill_buckets=(8, 16),
    )
    # Whatever an earlier file of this worker compiled is forgotten first:
    # the same toy programs found in JAX's own caches reach no compile
    # step, and the split below would read 0.0 for it (seen at PR 41, when
    # longer files moved which worker runs this one after which).
    jax.clear_caches()
    before = observability_module.compile_clock().totals()
    t0 = time.perf_counter()
    server = in_process(LLMServer(TINY, ecfg, warmup=True))
    wall = time.perf_counter() - t0
    try:
        stats = server.metrics()
        rounds = server.flight_record(0)["compile_events"]
    finally:
        server.shutdown()
    assert 0.0 < stats["warmup_s"] < wall
    grew = {
        key: stats["jax_" + key] - value for key, value in before.items()
    }
    assert grew["trace_lower_s"] > 0.0
    assert grew["compile_step_s"] > 0.0
    # The CPU backend is left uncached: nothing is written.
    assert grew["cache_misses"] == 0
    assert rounds
    for entry in rounds:
        assert set(before) <= set(entry)
        assert entry["trace_lower_s"] + entry["compile_step_s"] <= (
            entry["compile_s"] + 1e-3
        )
    # The rounds' compile seconds are what warm-up saw, to the rounding.
    for key in ("trace_lower_s", "compile_step_s"):
        assert sum(e[key] for e in rounds) <= grew[key] + 1e-3
    assert sum(e["compile_step_s"] for e in rounds) > 0.0


def test_interval_union_counts_nested_spans_once():
    """JAX reports a traced function after the functions traced inside it;
    the union charges the outer one only for what the inner ones left."""
    union = observability_module._IntervalUnion()
    union.add(1.0, 2.0)
    union.add(3.0, 4.0)  # disjoint
    assert union.total == pytest.approx(2.0)
    union.add(0.5, 5.0)  # contains both
    assert union.total == pytest.approx(4.5)
    union.add(4.5, 6.0)  # overlaps the end
    assert union.total == pytest.approx(5.5)
    for i in range(100):  # many inner spans, then their outer one
        union.add(10.0 + i * 0.01, 10.005 + i * 0.01)
    union.add(9.0, 12.0)
    assert union.total == pytest.approx(8.5)
    assert len(union._tail) == 2  # merged: the first stretch and this one


def test_train_phases_are_annotated_on_a_host_plane(tmp_path):
    from jax.profiler import ProfileData

    from ray_tpu.train.observability import StepProfiler

    profiler = StepProfiler(rank=0, world_size=1)
    jax.profiler.start_trace(str(tmp_path))
    try:
        for _ in range(3):
            with profiler.phase("data_wait"):
                batch = jnp.ones((8, 8))
            with profiler.phase("compute"):
                jax.block_until_ready(batch @ batch)
    finally:
        jax.profiler.stop_trace()
    found = sorted(tmp_path.glob("plugins/profile/*/*.xplane.pb"))
    assert found
    names = [
        event.name
        for plane in ProfileData.from_file(str(found[-1])).planes
        if plane.name.startswith("/host:")
        for line in plane.lines
        for event in line.events
        if event.name.startswith("train.")
    ]
    assert names.count("train.compute") == 3
    assert names.count("train.data_wait") == 3
    record = profiler.end_round()
    assert record["phases"]["compute"] > 0.0


@pytest.mark.parametrize("mode", (False, True), ids=("sync", "async"))
def test_dispatch_outside_a_step_leaves_the_clock_alone(mode):
    """Warm-up (and device_report) drive the runner directly, with the hook
    installed: nothing may start a phase that no step will close."""
    eng = LLMEngine(
        TINY, EngineConfig(async_scheduling=mode, **BASE), seed=0
    )
    eng.runner.prefill([1, 2, 3], [1])
    time.sleep(0.1)  # would be charged to `wait` had the hook opened it
    t0 = time.perf_counter()
    eng.generate([[5, 9, 11]], max_new_tokens=2)
    wall = time.perf_counter() - t0
    stats = eng.stats()
    # The request's prefill and decode, and at depth 1 the chained decode
    # past its last token.
    assert eng._clock.dispatches == (3 if mode else 2)
    assert sum(stats[key] for key in PHASE_KEYS) <= wall


def test_a_step_that_raises_is_closed_by_the_next_entry():
    """The step body stays in `step()`'s own frame (a frame between warm-up
    and a program costs set-up seconds), so no `finally` closes the clock: the
    next step's entry does, and the partition still holds."""
    eng = LLMEngine(TINY, EngineConfig(**BASE), seed=0)
    eng.generate([[5, 9, 11, 3, 7]], max_new_tokens=3)
    eng.add_request([5, 9, 11, 3, 7], max_new_tokens=8)
    before = eng.stats()  # idle: no phase is open
    t0 = time.perf_counter()
    eng.step()
    decode = eng.runner.decode

    def broken(*args):
        eng.runner.decode = decode
        raise RuntimeError("device fell over")

    eng.runner.decode = broken
    with pytest.raises(RuntimeError):
        eng.step()
    assert eng._clock._step_annotation is not None  # left open
    run_to_idle(eng)
    wall = time.perf_counter() - t0
    assert eng._clock._step_annotation is None
    spent = window(before, eng.stats(), *PHASE_KEYS)
    assert all(seconds >= 0.0 for seconds in spent.values())
    assert sum(spent.values()) <= wall
    assert eng.stats()["inflight_steps"] == 0


@pytest.mark.parametrize("mode", (False, True), ids=("sync", "async"))
def test_prepares_cpu_seconds_stay_under_its_wall_seconds(mode, monkeypatch):
    eng = LLMEngine(TINY, EngineConfig(async_scheduling=mode, **BASE), seed=0)
    prompts = ([5, 9, 11, 3, 7], [8, 2, 4, 6, 1, 3, 9, 9, 2, 5, 7])
    eng.generate(list(prompts), max_new_tokens=3)  # compiles every shape
    before = eng.stats()
    for prompt in prompts:
        eng.add_request(list(prompt), max_new_tokens=60)
    # The CPU clock is a system call, and a dear one on a sandboxed host:
    # it is read where prepare opens and where it closes, nowhere else.
    reads = []
    clock = eng._clock

    def counted():
        reads.append(clock._phase)
        return time.thread_time()

    monkeypatch.setattr(
        observability_module,
        "time",
        types.SimpleNamespace(
            perf_counter=time.perf_counter,
            time=time.time,
            thread_time=counted,
        ),
    )
    dispatches = clock.dispatches
    cpu0 = time.thread_time()
    run_to_idle(eng)
    thread_cpu = time.thread_time() - cpu0
    monkeypatch.undo()
    after = eng.stats()
    steps = after["steps"] - before["steps"]
    wall = window(before, after, *PHASE_KEYS)
    cpu = after["step_prepare_cpu_s"] - before["step_prepare_cpu_s"]
    # The two clocks are read one after the other at a boundary, and what
    # runs between the readings (a young collection, at worst) is prepare's
    # CPU and the next phase's wall.
    assert 0.0 < cpu <= wall["step_prepare_s"] + 1e-3
    assert cpu <= thread_cpu
    # Read in pairs: once as prepare opens (from another phase), once as
    # it closes; a step has one prepare stretch a program it dispatches.
    opened = [phase for phase in reads if phase != "prepare"]
    closed = [phase for phase in reads if phase == "prepare"]
    assert steps - 2 <= len(opened) == len(closed)
    assert len(closed) <= clock.dispatches - dispatches + 2
    offcpu = after["step_prepare_offcpu_s"] - before["step_prepare_offcpu_s"]
    assert offcpu == pytest.approx(wall["step_prepare_s"] - cpu, abs=1e-9)
    assert [key for key in after if key.endswith("_cpu_s")] == [
        "step_prepare_cpu_s"
    ]
    assert after["stall_steps"] == before["stall_steps"]
    for record in eng.flight_recorder.snapshot()["steps"]:
        assert (
            0.0
            <= record["prepare_cpu_s"]
            <= record["phases"]["prepare"] + 1e-3
        )


def _prepare_stretch(clock: StepPhaseClock, loops: int) -> None:
    """One step of fixed interpreter work in `prepare` and a block in
    `wait`, as a decode step has them."""
    clock.enter_step(0)
    clock.switch("prepare")
    total = 0
    for i in range(loops):
        total += i * i
    clock.dispatched()
    time.sleep(0.05)
    clock.ready()
    clock.exit_step(live=False)


def test_threads_that_spin_beside_it_grow_prepares_offcpu_not_its_cpu():
    """The step thread's wait for the interpreter, told from its own work:
    the same loop costs the same CPU seconds alone and beside three threads
    that never let go of the interpreter, and the wall seconds it loses to
    them are `step_prepare_offcpu_s`. The block in `wait` is in neither."""
    loops = 400_000
    alone = StepPhaseClock()
    _prepare_stretch(alone, loops)
    stop = threading.Event()

    def spin():
        while not stop.is_set():
            sum(range(1000))

    spinners = [threading.Thread(target=spin) for _ in range(3)]
    for thread in spinners:
        thread.start()
    crowded = StepPhaseClock()
    try:
        _prepare_stretch(crowded, loops)
    finally:
        stop.set()
        for thread in spinners:
            thread.join(timeout=30)
    assert not any(thread.is_alive() for thread in spinners)
    quiet, busy = alone.stats(), crowded.stats()
    assert busy["step_prepare_cpu_s"] < 2.0 * quiet["step_prepare_cpu_s"] + 0.01
    assert busy["step_prepare_offcpu_s"] > 3.0 * quiet["step_prepare_offcpu_s"]
    assert busy["step_prepare_offcpu_s"] > 0.5 * busy["step_prepare_cpu_s"]
    for stats in (quiet, busy):
        # The sleep is `wait`'s, and not prepare's.
        assert stats["step_wait_s"] >= 0.05
        assert stats["step_prepare_offcpu_s"] == pytest.approx(
            stats["step_prepare_s"] - stats["step_prepare_cpu_s"]
        )
    assert quiet["step_prepare_offcpu_s"] < 0.05 <= quiet["step_wait_s"]


def _primed_engine():
    """An engine one prefill into a request, decode steps from here, with
    the stalls its first steps may have had (a step that compiles a program
    is one) out of the record."""
    eng = LLMEngine(TINY, EngineConfig(async_scheduling=False, **BASE), seed=0)
    eng.generate([[5, 9, 11, 3, 7]], max_new_tokens=3)
    eng.add_request([5, 9, 11, 3, 7], max_new_tokens=6)
    eng.step()
    eng._clock.stall_steps = 0
    eng.flight_recorder.stalls.clear()
    return eng


def test_a_step_held_outside_wait_is_a_stall_with_its_phases():
    eng = _primed_engine()
    decode = eng.runner.decode
    hold = STALL_SECONDS + 0.1

    def slow_prepare(*args, **kwargs):
        eng.runner.decode = decode
        time.sleep(hold)  # before the dispatch: prepare's
        return decode(*args, **kwargs)

    assert eng.stats()["stall_steps"] == 0
    stalled_step = eng.stats()["steps"]
    eng.runner.decode = slow_prepare
    gc.disable()  # no collection of its own accord in the stalled step
    try:
        eng.step()
    finally:
        gc.enable()
    run_to_idle(eng)
    assert eng.stats()["stall_steps"] == 1
    (stall,) = eng.flight_recorder.snapshot()["stalls"]
    assert stall["step"] == stalled_step
    assert stall["batch_size"] == 1
    assert set(stall["phases"]) == set(STEP_PHASES)
    assert stall["phases"]["prepare"] >= hold
    assert stall["held_s"] == pytest.approx(
        sum(stall["phases"].values()) - stall["phases"]["wait"], abs=1e-5
    )
    # It slept: the thread was off the CPU, and no collection explains it.
    assert stall["prepare_cpu_s"] < 0.5 * stall["phases"]["prepare"]
    assert stall["full_collection"] is False


def test_a_stall_says_whether_a_full_collection_ran_in_it():
    eng = _primed_engine()
    decode = eng.runner.decode

    def collecting_prepare(*args, **kwargs):
        eng.runner.decode = decode
        gc.collect()
        time.sleep(STALL_SECONDS + 0.05)
        return decode(*args, **kwargs)

    eng.runner.decode = collecting_prepare
    run_to_idle(eng)
    (stall,) = eng.flight_recorder.snapshot()["stalls"]
    assert stall["full_collection"] is True


def test_a_long_wait_is_not_a_stall():
    eng = _primed_engine()
    dispatched = eng.runner.on_dispatched

    def slow_device():
        dispatched()
        time.sleep(STALL_SECONDS + 0.1)  # after the dispatch: wait's

    before = eng.stats()
    eng.runner.on_dispatched = slow_device
    eng.step()
    eng.runner.on_dispatched = dispatched
    run_to_idle(eng)
    after = eng.stats()
    assert after["step_wait_s"] - before["step_wait_s"] >= STALL_SECONDS + 0.1
    assert after["stall_steps"] == 0
    assert eng.flight_recorder.snapshot()["stalls"] == []
