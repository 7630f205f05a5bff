"""Core runtime microbenchmarks.

A fresh TPU-native re-implementation of the reference's microbenchmark matrix
(reference: python/ray/_private/ray_perf.py:93 main(); recorded numbers in
release/release_logs/2.2.0/microbenchmark.json, mirrored in BASELINE.md).
Each benchmark prints one JSON line:

    {"benchmark": ..., "value": ..., "unit": "ops/s"|"GB/s",
     "baseline": <reference m5-class number>, "vs_baseline": ratio}

Run:  python benchmarks/microbenchmark.py [--filter substr] [--json-out PATH]
Environment: RAY_TPU_ISOLATION=process exercises the process-worker path.
"""

from __future__ import annotations

import argparse
import json
import os
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

import ray_tpu

# Reference numbers from BASELINE.md (m5.16xlarge-class node, Ray 2.2.0).
BASELINES = {
    "single_client_tasks_sync": 1294,
    "single_client_tasks_async": 10905,
    "multi_client_tasks_async": 32133,
    "1_1_actor_calls_sync": 2182,
    "1_1_actor_calls_async": 5770,
    "1_1_actor_calls_concurrent": 4668,
    "1_n_actor_calls_async": 11646,
    "n_n_actor_calls_async": 35152,
    "n_n_actor_calls_with_arg_async": 2832,
    "1_1_async_actor_calls_sync": 1479,
    "1_1_async_actor_calls_async": 2746,
    "n_n_async_actor_calls_async": 28666,
    "single_client_put_calls": 5893,
    "single_client_get_calls": 5877,
    "multi_client_put_calls": 11141,
    "single_client_put_gigabytes": 19.2,
    "multi_client_put_gigabytes": 38.4,
    "single_client_tasks_and_get_batch": 11.2,
    "placement_group_create_removal": 1016,
}

RESULTS: list[dict] = []


def report(name: str, value: float, unit: str = "ops/s") -> None:
    baseline = BASELINES.get(name)
    row = {
        "benchmark": name,
        "value": round(value, 2),
        "unit": unit,
        "baseline": baseline,
        "vs_baseline": round(value / baseline, 3) if baseline else None,
    }
    RESULTS.append(row)
    print(json.dumps(row), flush=True)


def timeit(fn, n_per_call: int = 1, min_seconds: float = 2.0) -> float:
    """ops/s of fn(), warmed up once, run until min_seconds elapse."""
    fn()  # warmup
    calls = 0
    start = time.perf_counter()
    while True:
        fn()
        calls += 1
        elapsed = time.perf_counter() - start
        if elapsed >= min_seconds:
            return calls * n_per_call / elapsed


# -- definitions -------------------------------------------------------------


@ray_tpu.remote
def tiny():
    return b"ok"


@ray_tpu.remote
class Sink:
    def sink(self, *args):
        return b"ok"


@ray_tpu.remote
class AsyncSink:
    async def sink(self, *args):
        return b"ok"


def bench_tasks_sync():
    report(
        "single_client_tasks_sync",
        timeit(lambda: ray_tpu.get(tiny.remote())),
    )


def bench_tasks_async():
    def batch():
        ray_tpu.get([tiny.remote() for _ in range(1000)])

    report("single_client_tasks_async", timeit(batch, n_per_call=1000))


def bench_multi_client_tasks_async(n_clients: int = 8):
    pool = ThreadPoolExecutor(max_workers=n_clients)

    def batch():
        futs = [
            pool.submit(lambda: ray_tpu.get([tiny.remote() for _ in range(500)]))
            for _ in range(n_clients)
        ]
        for f in futs:
            f.result()

    report(
        "multi_client_tasks_async", timeit(batch, n_per_call=500 * n_clients)
    )
    pool.shutdown()


def bench_actor_calls(name: str, actor_cls, n_actors: int, n_clients: int,
                      sync: bool, with_arg: bool = False,
                      options: dict | None = None):
    actors = [
        (actor_cls.options(**options) if options else actor_cls).remote()
        for _ in range(n_actors)
    ]
    ray_tpu.get([a.sink.remote() for a in actors])  # ready
    arg = ray_tpu.put(np.zeros(100 * 1024, dtype=np.uint8)) if with_arg else None

    if sync:
        def run():
            for _ in range(100):
                ray_tpu.get(actors[0].sink.remote())

        report(name, timeit(run, n_per_call=100))
    elif n_clients == 1:
        def run():
            refs = []
            for _ in range(200):
                for a in actors:
                    refs.append(a.sink.remote(arg) if with_arg else a.sink.remote())
            ray_tpu.get(refs)

        report(name, timeit(run, n_per_call=200 * n_actors))
    else:
        pool = ThreadPoolExecutor(max_workers=n_clients)

        def client(a):
            refs = [
                (a.sink.remote(arg) if with_arg else a.sink.remote())
                for _ in range(200)
            ]
            ray_tpu.get(refs)

        def run():
            futs = [pool.submit(client, a) for a in actors for _ in (0,)]
            for f in futs:
                f.result()

        report(name, timeit(run, n_per_call=200 * n_actors))
        pool.shutdown()
    for a in actors:
        ray_tpu.kill(a)


def bench_puts_and_gets():
    payload = np.zeros(10 * 1024, dtype=np.uint8)  # 10KB, matches reference

    def put_loop():
        for _ in range(100):
            ray_tpu.put(payload)

    report("single_client_put_calls", timeit(put_loop, n_per_call=100))

    ref = ray_tpu.put(payload)

    def get_loop():
        for _ in range(100):
            ray_tpu.get(ref)

    report("single_client_get_calls", timeit(get_loop, n_per_call=100))

    pool = ThreadPoolExecutor(max_workers=8)

    def multi_put():
        futs = [pool.submit(put_loop) for _ in range(8)]
        for f in futs:
            f.result()

    report("multi_client_put_calls", timeit(multi_put, n_per_call=800))
    pool.shutdown()


def bench_put_gigabytes():
    chunk = np.random.randint(0, 256, size=(1 << 30) // 8, dtype=np.uint8)  # 128MB

    def put_gb():
        refs = [ray_tpu.put(chunk) for _ in range(8)]  # 1 GiB total
        del refs

    gb_per_call = 1.0
    value = timeit(put_gb, min_seconds=4.0)
    report("single_client_put_gigabytes", value * gb_per_call, unit="GB/s")

    pool = ThreadPoolExecutor(max_workers=4)

    def multi_put_gb():
        futs = [
            pool.submit(lambda: [ray_tpu.put(chunk) for _ in range(2)])
            for _ in range(4)
        ]
        for f in futs:
            f.result()

    value = timeit(multi_put_gb, min_seconds=4.0)
    report("multi_client_put_gigabytes", value * gb_per_call, unit="GB/s")
    pool.shutdown()


def bench_tasks_and_get_batch():
    @ray_tpu.remote
    def small_value():
        return b"ok"

    def run():
        submitted = [small_value.remote() for _ in range(1000)]
        ray_tpu.get(submitted)

    report("single_client_tasks_and_get_batch", timeit(run, min_seconds=2.0))


def bench_placement_groups():
    from ray_tpu.util.placement_group import placement_group, remove_placement_group

    def cycle():
        for _ in range(10):
            pg = placement_group([{"CPU": 0.01}], strategy="PACK")
            pg.ready(timeout=5)
            remove_placement_group(pg)

    report("placement_group_create_removal", timeit(cycle, n_per_call=10))


def bench_train_ingestion():
    """Feed-the-TPU layer (SURVEY §7 hard-part 3): a synthetic train loop
    consumes image-shaped batches while doing fixed per-batch compute. The
    prefetch on/off delta shows fetch/format overlapping the step; the
    on-row approaching the compute-only bound means ingest is NOT the
    bottleneck."""
    import numpy as np

    import ray_tpu.data as rdata

    n_rows, batch = 2048, 128
    weights = np.random.randn(12288, 256).astype(np.float32)

    def make_ds():
        return rdata.range_tensor(
            n_rows, shape=(64, 64, 3), parallelism=8
        ).map_batches(
            lambda b: {"x": b["data"].astype(np.float32).reshape(len(b["data"]), -1)}
        )

    def step(b):
        # ~fixed "train" compute per batch.
        return float(np.dot(b["x"], weights).sum())

    def epoch(prefetch: int) -> float:
        ds = make_ds()
        t0 = time.perf_counter()
        n = 0
        for b in ds.iter_batches(
            batch_size=batch, prefetch_batches=prefetch, drop_last=True
        ):
            step(b)
            n += 1
        return n / (time.perf_counter() - t0)

    epoch(0)  # warm the plan/executor paths
    off = sum(epoch(0) for _ in range(3)) / 3
    on = sum(epoch(2) for _ in range(3)) / 3
    report("train_ingestion_prefetch_off", off, unit="batches/s")
    report("train_ingestion_prefetch_on", on, unit="batches/s")
    report("train_ingestion_overlap_gain", on / off, unit="x")


def bench_training_observability():
    """Cost of the training observability plane on the report loop: the
    same multi-worker JaxTrainer.fit with TrainConfig.instrument on
    (per-round phase records, train.* spans, train_* histograms, straggler
    scan) vs compiled out. All instrumentation work happens once per round
    — never per batch or per step call — and must stay under 5% of a
    small-but-realistic round.

    Methodology: each round holds a fixed device-bound step stand-in (the
    host blocks ~8 ms, as it does on block_until_ready for a real step) so
    the plane's host-side cost shows directly; per-fit round time is the
    MEDIAN inter-report gap (robust to GC/scheduler pauses); on/off fits
    alternate in PAIRS and the overhead is the median paired ratio, so the
    box's throughput drift cancels instead of masquerading as overhead
    (CPU-compute rounds here are bimodal by 2x from thread placement alone,
    drowning a sub-1% signal)."""
    import statistics

    from ray_tpu import train
    from ray_tpu.train import JaxTrainer, ScalingConfig, TrainConfig

    ROUNDS = 60

    def loop(config):
        import time as _t

        for i in range(config["rounds"]):
            _t.sleep(0.008)  # device-bound step: host waits on the chip
            train.report({"i": i})

    def run(instrument: bool) -> float:
        trainer = JaxTrainer(
            loop,
            train_loop_config={"rounds": ROUNDS},
            scaling_config=ScalingConfig(num_workers=2, cpus_per_worker=1),
            train_config=TrainConfig(instrument=instrument),
        )
        stamps: list[float] = []
        trainer.add_result_callback(lambda m: stamps.append(time.perf_counter()))
        result = trainer.fit()
        assert result.error is None, result.error
        assert len(stamps) == ROUNDS
        gaps = sorted(b - a for a, b in zip(stamps, stamps[1:]))
        return gaps[len(gaps) // 2]

    run(True)
    run(False)  # warm actor/backend paths for both modes
    ons, offs, ratios = [], [], []
    for _ in range(3):
        on = run(True)
        off = run(False)
        ons.append(on)
        offs.append(off)
        ratios.append(on / off)
    overhead = statistics.median(ratios) - 1.0
    # Median paired values, consistent with the median-of-ratios overhead
    # (the last pair alone can carry a GC/scheduler outlier).
    report(
        "training_observability_round_ms_on",
        1e3 * statistics.median(ons),
        unit="ms/round",
    )
    report(
        "training_observability_round_ms_off",
        1e3 * statistics.median(offs),
        unit="ms/round",
    )
    report("training_observability_overhead_pct", 100 * overhead, unit="%")
    assert overhead < 0.05, (
        f"training observability overhead {overhead:.1%} exceeds the 5% budget"
    )


def bench_serving_decode():
    """ray_tpu.llm continuous batching vs static (gang-scheduled) batching.

    Same engine, same jitted programs, same varied-length workload; the only
    difference is admission policy. Static batching admits a full gang of
    max_decode_slots requests and waits for the LONGEST one before admitting
    the next gang, so slots idle as short requests finish; continuous
    batching refills slots every iteration. Reported tokens/sec is decode
    throughput; occupancy is active-slots / total-slot-steps.
    """
    import jax.numpy as jnp

    from ray_tpu.llm import EngineConfig, LLMEngine
    from ray_tpu.models.gpt import GPTConfig

    cfg = GPTConfig(
        vocab_size=512, num_layers=2, num_heads=4, embed_dim=128,
        max_seq_len=256, dtype=jnp.float32, attention_impl="reference",
    )
    ecfg = EngineConfig(
        block_size=8, num_blocks=128, max_decode_slots=8, max_blocks_per_seq=8
    )
    rng = np.random.RandomState(0)
    n_requests = 24
    prompts = [
        list(map(int, rng.randint(0, 512, size=rng.randint(4, 25))))
        for _ in range(n_requests)
    ]
    budgets = [int(rng.randint(4, 33)) for _ in range(n_requests)]

    engine = LLMEngine(cfg, ecfg, seed=0)
    # Warm every compiled program: each prefill bucket plus the decode step.
    for n in (5, 9, 17, 33):
        engine.generate([[1] * n], max_new_tokens=2)

    def run(gang_size: int | None) -> tuple[float, float]:
        """gang_size=None → continuous admission; otherwise admit gangs of
        that size and drain each fully before the next (gang_size=1 is
        one-request-at-a-time generation)."""
        produced = []

        def admit(p, b):
            tokens = []
            engine.add_request(p, max_new_tokens=b, on_token=tokens.append)
            produced.append(tokens)

        t0 = time.perf_counter()
        slot_steps = active_steps = 0
        pending = list(zip(prompts, budgets))
        while pending or engine.has_work():
            if gang_size is None:
                while pending and len(engine.scheduler.waiting) < ecfg.max_decode_slots:
                    admit(*pending.pop(0))
            elif not engine.has_work():
                for p, b in pending[:gang_size]:
                    admit(p, b)
                del pending[:gang_size]
            stats = engine.step()
            slot_steps += ecfg.max_decode_slots
            active_steps += stats["num_decoding"]
        wall = time.perf_counter() - t0
        total = sum(len(v) for v in produced)
        assert total == sum(budgets)
        return total / wall, active_steps / max(slot_steps, 1)

    seq_tps, seq_occ = run(gang_size=1)
    static_tps, static_occ = run(gang_size=ecfg.max_decode_slots)
    cont_tps, cont_occ = run(gang_size=None)
    report("serving_decode_sequential_tokens_per_s", seq_tps, unit="tokens/s")
    report("serving_decode_sequential_occupancy", seq_occ, unit="frac")
    report("serving_decode_static_tokens_per_s", static_tps, unit="tokens/s")
    report("serving_decode_static_occupancy", static_occ, unit="frac")
    report("serving_decode_continuous_tokens_per_s", cont_tps, unit="tokens/s")
    report("serving_decode_continuous_occupancy", cont_occ, unit="frac")
    report("serving_decode_vs_static_speedup", cont_tps / static_tps, unit="x")
    report("serving_decode_vs_sequential_speedup", cont_tps / seq_tps, unit="x")


def bench_serving_async_step():
    """Async double-buffered step loop (EngineConfig.async_scheduling) vs
    the synchronous dispatch-then-read loop, same engine shape, same
    varied-length workload.

    The claim the async loop makes is a HOST-GAP claim, not a CPU
    tokens/sec claim: chaining decode's on-device next_tokens into the
    next dispatch (values fetched one step behind via copy_to_host_async)
    removes the host's read-plan-dispatch window from between device
    programs. That window is what the flight-recorded per-step host_gap_s
    series measures, so the p50 reduction is asserted on ANY backend —
    chained dispatches record exactly 0 — while the tokens/sec rows are
    backend-labeled per the PR 7 convention (on CPU the "device" is the
    same cores the host plans on, so wall-clock gains are noise-level;
    the throughput claim is TPU-gated). Token identity off vs on is
    asserted unconditionally."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.llm import EngineConfig, LLMEngine
    from ray_tpu.models.gpt import GPTConfig

    cfg = GPTConfig(
        vocab_size=512, num_layers=2, num_heads=4, embed_dim=128,
        max_seq_len=256, dtype=jnp.float32, attention_impl="reference",
    )
    rng = np.random.RandomState(0)
    n_requests = 24
    prompts = [
        list(map(int, rng.randint(0, 512, size=rng.randint(4, 25))))
        for _ in range(n_requests)
    ]
    budgets = [int(rng.randint(8, 33)) for _ in range(n_requests)]

    def run(async_on: bool):
        ecfg = EngineConfig(
            block_size=8, num_blocks=128, max_decode_slots=8,
            max_blocks_per_seq=8, async_scheduling=async_on,
            flight_recorder_capacity=4096,
        )
        engine = LLMEngine(cfg, ecfg, seed=0)
        for n in (5, 9, 17, 33):  # warm every compiled program
            engine.generate([[1] * n], max_new_tokens=2)
        engine.allocator.reset_prefix_cache()
        engine.flight_recorder.steps.clear()
        produced = []

        def admit(p, b):
            tokens = []
            engine.add_request(p, max_new_tokens=b, on_token=tokens.append)
            produced.append(tokens)

        pending = list(zip(prompts, budgets))
        t0 = time.perf_counter()
        while pending or engine.has_work():
            while pending and len(engine.scheduler.waiting) < 8:
                admit(*pending.pop(0))
            engine.step()
        wall = time.perf_counter() - t0
        total = sum(len(v) for v in produced)
        assert total == sum(budgets)
        steps = engine.flight_recorder.snapshot()["steps"]
        gaps = sorted(
            s["host_gap_s"] for s in steps if s.get("host_gap_s") is not None
        )
        chained = sum(1 for s in steps if s.get("chained"))
        dispatches = sum(1 for s in steps if s["batch_size"])
        stats = engine.stats()
        assert stats["inflight_steps"] == 0
        return {
            "tps": total / wall,
            "out": produced,
            "gap_p50": gaps[len(gaps) // 2] if gaps else None,
            "gap_mean": stats["host_gap_mean_s"],
            "chained_frac": chained / max(dispatches, 1),
        }

    on_cpu = jax.devices()[0].platform == "cpu"
    tag = "_cpu" if on_cpu else ""
    off = run(False)
    on = run(True)
    assert on["out"] == off["out"], "async loop changed greedy tokens"
    assert on["gap_p50"] is not None and off["gap_p50"] is not None
    # Chained dispatches pin the gap at 0, so with the loop mostly in
    # steady state the async p50 must land BELOW the sync p50 on any
    # backend — this is the perf claim the PR gates on.
    assert on["gap_p50"] < off["gap_p50"], (
        f"async host-gap p50 {on['gap_p50']} !< sync {off['gap_p50']}"
    )
    assert on["chained_frac"] > 0.5, "async loop rarely chained"
    report(
        f"serving_async_step_off_tokens_per_s{tag}", off["tps"],
        unit="tokens/s",
    )
    report(
        f"serving_async_step_on_tokens_per_s{tag}", on["tps"],
        unit="tokens/s",
    )
    report(
        f"serving_async_step_speedup{tag}", on["tps"] / off["tps"], unit="x"
    )
    report(
        f"serving_async_step_host_gap_p50_off_us{tag}",
        off["gap_p50"] * 1e6,
        unit="us",
    )
    report(
        f"serving_async_step_host_gap_p50_on_us{tag}",
        on["gap_p50"] * 1e6,
        unit="us",
    )
    # Mean-based: the async mean stays nonzero (flush-boundary dispatches
    # still pay a real gap), so the ratio is finite and trackable; the p50
    # rows above show the headline (async p50 is exactly 0 once chaining
    # dominates).
    report(
        f"serving_async_step_host_gap_mean_reduction{tag}",
        off["gap_mean"] / max(on["gap_mean"], 1e-9),
        unit="x",
    )
    # Unlabeled: the chain rate is a property of the loop/workload shape
    # (flush boundaries), not of the backend.
    report(
        "serving_async_step_chained_frac", on["chained_frac"], unit="frac"
    )


def bench_serving_decode_tp():
    """Tensor-parallel serving: one engine spanning a tp=2 mesh vs the
    single-chip tp=1 path, same weights (same seed), same workload.

    CPU rows are parity/plumbing exercise, not the perf claim (per the
    PR 7 convention they are `*_cpu`-labeled): a virtual host-device mesh
    adds shard_map orchestration without any extra FLOPs/chip, so tp=2
    LOSES on CPU by construction — the speedup claim is TPU-gated, where
    tp=2 halves each chip's weight matmuls and KV traffic. What this run
    asserts unconditionally: greedy outputs token-identical tp=1 vs tp=2,
    the per-step explicit host-transfer-bytes series IDENTICAL (zero
    per-token gathers sneaking into the decode loop), and per-chip pool
    bytes exactly aggregate / tp."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.llm import EngineConfig, LLMEngine
    from ray_tpu.models.gpt import GPTConfig

    cfg = GPTConfig(
        vocab_size=512, num_layers=2, num_heads=4, embed_dim=128,
        max_seq_len=256, dtype=jnp.float32, attention_impl="reference",
    )
    if len(jax.devices()) < 2:
        print(
            "# serving_decode_tp skipped: backend exposes "
            f"{len(jax.devices())} device(s), tp=2 needs 2 "
            "(set XLA_FLAGS=--xla_force_host_platform_device_count=2 "
            "for a virtual CPU mesh)"
        )
        return

    rng = np.random.RandomState(0)
    prompts = [
        list(map(int, rng.randint(0, 512, size=rng.randint(4, 25))))
        for _ in range(16)
    ]
    budgets = [int(rng.randint(8, 25)) for _ in range(16)]

    def run(tp: int):
        ecfg = EngineConfig(
            block_size=8, num_blocks=128, max_decode_slots=8,
            max_blocks_per_seq=8, tensor_parallel_size=tp,
        )
        engine = LLMEngine(cfg, ecfg, seed=0)
        for n in (5, 9, 17, 33):  # warm every compiled program
            engine.generate([[1] * n], max_new_tokens=2)
        engine.allocator.reset_prefix_cache()
        produced = []

        def admit(p, b):
            tokens = []
            engine.add_request(p, max_new_tokens=b, on_token=tokens.append)
            produced.append(tokens)

        pending = list(zip(prompts, budgets))
        t0 = time.perf_counter()
        while pending or engine.has_work():
            while pending and len(engine.scheduler.waiting) < 8:
                admit(*pending.pop(0))
            engine.step()
        wall = time.perf_counter() - t0
        total = sum(len(v) for v in produced)
        assert total == sum(budgets)
        steps = engine.flight_recorder.snapshot()["steps"]
        series = [(s["phase"], s["host_transfer_bytes"]) for s in steps]
        stats = engine.stats()
        return total / wall, produced, series, stats

    on_cpu = jax.devices()[0].platform == "cpu"
    tag = "_cpu" if on_cpu else ""
    tp1_tps, tp1_out, tp1_series, _ = run(1)
    tp2_tps, tp2_out, tp2_series, tp2_stats = run(2)
    assert tp1_out == tp2_out, "tp=2 outputs diverged from tp=1"
    # The explicit host<->device byte series must be flat in tp (identical
    # phases, identical bytes, every step) — accounting that the dispatch
    # loop stayed tp-invariant; the in-program no-gather guarantee is the
    # compiled-HLO gate in tests/test_llm_tp.py.
    assert tp1_series == tp2_series, "host-transfer bytes grew under tp=2"
    assert (
        tp2_stats["kv_pool_bytes_per_shard"] * 2
        == tp2_stats["kv_pool_bytes"]
    )
    report(f"serving_decode_tp1_tokens_per_s{tag}", tp1_tps, unit="tokens/s")
    report(f"serving_decode_tp2_tokens_per_s{tag}", tp2_tps, unit="tokens/s")
    report(f"serving_decode_tp2_speedup{tag}", tp2_tps / tp1_tps, unit="x")
    # Unlabeled like serving_kv_int8_capacity_ratio: exactly 1/tp on any
    # backend (asserted above), so there is no CPU-vs-TPU row to keep apart.
    report(
        "serving_decode_tp2_pool_bytes_per_chip_frac",
        tp2_stats["kv_pool_bytes_per_shard"] / tp2_stats["kv_pool_bytes"],
        unit="frac",
    )


def bench_serving_decode_attn_impl():
    """Serving hot path: the fused Pallas paged-attention kernel vs the
    XLA gather+softmax reference on a decode-shaped step (the program the
    engine dispatches every iteration), plus the int8 KV capacity ratio.

    The speedup claim is a TPU claim — the kernel deletes the padded-gather
    materialization and the [B, H, Q, K] logits round trip, which is HBM
    traffic a CPU run can't see; on CPU the kernel executes in Pallas
    interpret mode and loses by construction (the ratio is still reported
    so BENCH_* tracks both backends honestly). Capacity is backend-
    independent: at head_dim 64 int8 pools + per-token bf16 scales hold
    ~1.94x the sequences of bf16 in the same bytes."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops.attention import paged_attention
    from ray_tpu.ops.paged_flash import (
        kv_pool_bytes,
        paged_flash_attention,
    )

    # Engine-shaped inputs come from the profile script's shared fixture
    # (same directory): one source of truth for the table/pool layout the
    # engine compiles, so the BENCH row and the sweep can't drift apart.
    import sys
    from pathlib import Path

    bench_dir = str(Path(__file__).resolve().parent)
    if bench_dir not in sys.path:
        sys.path.insert(0, bench_dir)
    from profile_attn_paged import _build_case, _time_step

    on_cpu = jax.devices()[0].platform == "cpu"
    b, h, d, bs, nb = 8, 4, 64, 8, 8
    ctx = 48
    rng = np.random.RandomState(0)
    dtype = jnp.float32 if on_cpu else jnp.bfloat16
    q, kc, vc, tables, lens, nk, nv, _, _ = _build_case(
        rng, b, 1, ctx, h, d, bs, nb, dtype, int8=False
    )

    def timed(op, **kw):
        fn = jax.jit(
            lambda q, kc, vc, t, l, nk, nv: op(
                q, kc, vc, t, l, new_k=nk, new_v=nv, **kw
            )
        )
        # Shared warmup/loop harness with the sweep script, so BENCH rows
        # and the sweep can never disagree for harness reasons.
        return _time_step(
            fn, q, kc, vc, tables, lens, nk, nv,
            iters=5 if on_cpu else 50,
        )

    # Backend-qualified row names: a CPU run times the kernel in interpret
    # mode, which is a parity exercise, not the perf claim — keep its rows
    # from ever being compared against (or mistaken for) TPU numbers.
    tag = "_cpu_interpret" if on_cpu else ""
    ref_s = timed(paged_attention)
    pal_s = timed(paged_flash_attention)
    report(f"serving_decode_attn_reference_ms{tag}", 1e3 * ref_s, unit="ms")
    report(f"serving_decode_attn_pallas_ms{tag}", 1e3 * pal_s, unit="ms")
    report(f"serving_decode_attn_impl_speedup{tag}", ref_s / pal_s, unit="x")

    q, kc, vc, tables, lens, nk, nv, ks, vs = _build_case(
        np.random.RandomState(0), b, 1, ctx, h, d, bs, nb, dtype, int8=True
    )
    pal8_s = timed(paged_flash_attention, k_scale=ks, v_scale=vs)
    report(
        f"serving_decode_attn_pallas_int8_ms{tag}", 1e3 * pal8_s, unit="ms"
    )
    ratio = kv_pool_bytes(1, bs, h, d, jnp.bfloat16, False) / kv_pool_bytes(
        1, bs, h, d, jnp.int8, True
    )
    report("serving_kv_int8_capacity_ratio", ratio, unit="x")
    assert ratio >= 1.9, (
        f"int8 KV capacity ratio {ratio:.3f} fell below the 1.9x budget"
    )


def bench_serving_speculative():
    """Speculative decoding: tokens/s and acceptance with the n-gram and
    draft proposers vs plain decode, on a repetitive prompt set (quoting /
    boilerplate-style text, where prompt lookup shines) and a
    non-repetitive random set (its worst case). Outputs are asserted
    token-identical to the non-speculative engine in every cell —
    speculation is a pure speed knob.

    The SPEEDUP claim is a TPU claim: speculation trades one decode
    dispatch per token for one wider verify dispatch per several tokens,
    which wins where per-dispatch latency (compile-fixed overhead + HBM
    sweep of the KV pool) dominates — on CPU the verify program's extra
    FLOPs are the same cores doing more math, so CPU rows are labeled and
    the >1x assertion is TPU-gated, like the PR 7 attn rows. Acceptance is
    backend-independent and asserted here: the repetitive set must accept
    more than one proposed token per verify step (each verify step then
    replaces 2+ decode steps). Caveat on the "random" rows: the prompts
    are random but the seed-initialized model's OUTPUT still loops, so
    even that set shows nontrivial acceptance — with a trained model the
    random set is the honest worst case."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.llm import EngineConfig, LLMEngine
    from ray_tpu.models.gpt import GPTConfig

    cfg = GPTConfig(
        vocab_size=512, num_layers=2, num_heads=4, embed_dim=128,
        max_seq_len=256, dtype=jnp.float32, attention_impl="reference",
    )
    draft_cfg = GPTConfig(
        vocab_size=512, num_layers=1, num_heads=4, embed_dim=128,
        max_seq_len=256, dtype=jnp.float32, attention_impl="reference",
    )
    rng = np.random.RandomState(0)
    n_requests = 8
    max_new = 24
    # Repetitive: each prompt loops a short distinct phrase — the shape of
    # boilerplate, quoted context, and list continuation.
    repetitive = []
    for _ in range(n_requests):
        phrase = list(map(int, rng.randint(0, 512, size=6)))
        repetitive.append((phrase * 6)[:32])
    random_set = [
        list(map(int, rng.randint(0, 512, size=32)))
        for _ in range(n_requests)
    ]
    prompt_sets = {"repetitive": repetitive, "random": random_set}

    def make_engine(mode: str) -> "LLMEngine":
        kw = dict(
            block_size=8, num_blocks=128, max_decode_slots=8,
            max_blocks_per_seq=16, speculation=mode,
        )
        if mode == "draft":
            kw["draft_model_config"] = draft_cfg
        return LLMEngine(cfg, EngineConfig(**kw), seed=0)

    def run(engine, prompts) -> tuple[float, list, dict]:
        slots = engine.engine_config.max_decode_slots
        produced = []

        def admit(p):
            tokens = []
            engine.add_request(p, max_new_tokens=max_new, on_token=tokens.append)
            produced.append(tokens)

        t0 = time.perf_counter()
        pending = list(prompts)
        while pending or engine.has_work():
            while pending and len(engine.scheduler.waiting) < slots:
                admit(pending.pop(0))
            engine.step()
        wall = time.perf_counter() - t0
        total = sum(len(v) for v in produced)
        assert total == max_new * len(prompts)
        stats = engine.stats()
        engine.allocator.reset_prefix_cache()
        return total / wall, produced, stats

    on_tpu = jax.devices()[0].platform == "tpu"
    tag = "" if on_tpu else "_cpu"
    for set_name, prompts in prompt_sets.items():
        baseline_tps, want, _ = None, None, None
        for mode in ("off", "ngram", "draft"):
            engine = make_engine(mode)
            run(engine, prompts)  # warm every program incl. verify buckets
            tps, outs, stats = run(engine, prompts)
            if mode == "off":
                baseline_tps, want = tps, outs
                report(
                    f"serving_spec_{set_name}_off_tokens_per_s{tag}",
                    tps, unit="tokens/s",
                )
                continue
            assert outs == want, (
                f"speculation={mode} changed greedy outputs on {set_name}"
            )
            accepted_per_step = stats["spec_accepted_tokens"] / max(
                stats["spec_verify_steps"], 1
            )
            report(
                f"serving_spec_{set_name}_{mode}_tokens_per_s{tag}",
                tps, unit="tokens/s",
            )
            report(
                f"serving_spec_{set_name}_{mode}_accepted_per_verify_step",
                accepted_per_step, unit="tokens",
            )
            report(
                f"serving_spec_{set_name}_{mode}_tokens_per_slot_step",
                stats["mean_occupancy"], unit="tokens",
            )
            report(
                f"serving_spec_{set_name}_{mode}_acceptance_rate",
                stats["spec_acceptance_rate"], unit="frac",
            )
            report(
                f"serving_spec_{set_name}_{mode}_speedup{tag}",
                tps / baseline_tps, unit="x",
            )
            if set_name == "repetitive":
                # Backend-independent claim: on repetition, each verify
                # step commits >1 proposed token (plus the bonus), so it
                # amortizes 2+ decode steps.
                assert accepted_per_step > 1.0, (
                    f"{mode} accepted only {accepted_per_step:.2f} "
                    "tokens/verify step on the repetitive set"
                )
                if on_tpu:
                    assert tps > baseline_tps, (
                        f"{mode} speculation slower than plain decode on "
                        "TPU for the repetitive set"
                    )


def bench_serving_chunked_prefill():
    """Chunked prefill: the latency-shaping claim. One long prompt lands
    on an engine with a steady pool of decoding requests; with chunking
    OFF its whole prefill monopolizes one engine step, so every in-flight
    decode stalls behind it (a decode-TPOT p99 spike the size of the full
    prefill); with a per-step token budget the prompt streams in as
    block-aligned chunks interleaved with the decode batch, so decode
    inter-token latency stays flat and only TTFT of the long prompt
    stretches. Outputs are asserted token-identical both ways — chunking
    is a pure latency-shaping knob.

    The p99 RATIO is asserted on CPU too (a chunk costs a bounded
    fraction of the full prefill on any backend); the absolute TPOT
    numbers are CPU-labeled and the production speedup claim is TPU's,
    like the PR 7/9 rows. The budget invariant — no engine step feeds
    more prompt tokens than configured — is asserted from the flight
    recorder's step records."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.llm import EngineConfig, LLMEngine
    from ray_tpu.models.gpt import GPTConfig

    cfg = GPTConfig(
        vocab_size=512, num_layers=2, num_heads=4, embed_dim=128,
        max_seq_len=512, dtype=jnp.float32, attention_impl="reference",
    )
    rng = np.random.RandomState(0)
    pool_prompts = [
        list(map(int, rng.randint(0, 512, size=12))) for _ in range(7)
    ]
    long_prompt = list(map(int, rng.randint(0, 512, size=448)))
    pool_new, long_new = 48, 8
    budget = 64

    def run(budget_setting):
        ecfg = EngineConfig(
            block_size=16, num_blocks=96, max_decode_slots=8,
            max_blocks_per_seq=32,
            max_prefill_tokens_per_step=budget_setting,
        )
        engine = LLMEngine(cfg, ecfg, seed=0)
        # Warm every program this scenario dispatches (both the chunked
        # and the monolithic shapes), then drop the cached blocks so the
        # measured run prefills cold.
        engine.generate(
            [list(map(int, rng.randint(0, 512, size=448)))] + pool_prompts,
            max_new_tokens=2,
        )
        engine.allocator.reset_prefix_cache()

        pool_tokens = [[] for _ in pool_prompts]
        pool_stamps = [[] for _ in pool_prompts]
        long_tokens = []
        marks = {}

        def pool_cb(i):
            def cb(tok):
                pool_tokens[i].append(tok)
                pool_stamps[i].append(time.perf_counter())
            return cb

        def long_cb(tok):
            if not long_tokens:
                marks["first"] = time.perf_counter()
            long_tokens.append(tok)

        for i, p in enumerate(pool_prompts):
            engine.add_request(p, max_new_tokens=pool_new,
                               on_token=pool_cb(i))
        # Let the pool reach steady-state decode before the long prompt.
        while min(len(t) for t in pool_tokens) < 4:
            engine.step()
        marks["submit"] = time.perf_counter()
        engine.add_request(long_prompt, max_new_tokens=long_new,
                           on_token=long_cb)
        while engine.has_work():
            engine.step()
        # Decode inter-token gaps of the pool AFTER the long prompt
        # arrived — the latency the chunking knob is shaping. The last
        # pre-submission stamp anchors each request's first gap: with
        # chunking off the whole monolithic-prefill stall lands exactly
        # there (between the last token before the long prompt and the
        # first token after), and dropping it would hide the spike the
        # benchmark exists to measure.
        gaps = []
        for stamps in pool_stamps:
            idx = next(
                (i for i, s in enumerate(stamps) if s >= marks["submit"]),
                len(stamps),
            )
            window = stamps[max(idx - 1, 0) :]
            gaps.extend(b - a for a, b in zip(window, window[1:]))
        gaps.sort()
        records = engine.flight_recorder.snapshot()["steps"]
        if budget_setting:
            assert all(r["tokens_in"] <= budget_setting for r in records), (
                "an engine step exceeded the prefill token budget"
            )
        return {
            "outputs": (pool_tokens, long_tokens),
            "tpot_p50": gaps[len(gaps) // 2],
            "tpot_p99": gaps[min(len(gaps) - 1, int(0.99 * len(gaps)))],
            "ttft": marks["first"] - marks["submit"],
        }

    off = run(0)
    on = run(budget)
    assert on["outputs"] == off["outputs"], (
        "chunked prefill changed greedy outputs"
    )
    on_tpu = jax.devices()[0].platform == "tpu"
    tag = "" if on_tpu else "_cpu"
    report(f"serving_chunked_decode_tpot_p50_off{tag}",
           1e3 * off["tpot_p50"], unit="ms")
    report(f"serving_chunked_decode_tpot_p50_on{tag}",
           1e3 * on["tpot_p50"], unit="ms")
    report(f"serving_chunked_decode_tpot_p99_off{tag}",
           1e3 * off["tpot_p99"], unit="ms")
    report(f"serving_chunked_decode_tpot_p99_on{tag}",
           1e3 * on["tpot_p99"], unit="ms")
    report(f"serving_chunked_long_ttft_off{tag}", 1e3 * off["ttft"],
           unit="ms")
    report(f"serving_chunked_long_ttft_on{tag}", 1e3 * on["ttft"],
           unit="ms")
    report("serving_chunked_tpot_p99_ratio_on_vs_off",
           on["tpot_p99"] / off["tpot_p99"], unit="x")
    # Backend-independent claim: the worst decode stall shrinks, because
    # no single step carries more than a budget-sized slice of the long prefill.
    assert on["tpot_p99"] < off["tpot_p99"], (
        f"chunking did not flatten decode TPOT p99: "
        f"{on['tpot_p99']:.4f}s vs {off['tpot_p99']:.4f}s"
    )


def bench_serving_prefix_cache():
    """Automatic prefix caching on a prefix-heavy workload: every request
    shares a 256-token system prompt and appends a distinct 16-token user
    suffix. With caching the shared prefix is computed once and every later
    admission only prefills its suffix (a much smaller bucket), so TTFT
    drops; with caching off every prefill recomputes all 272 tokens.
    Outputs are asserted token-identical between the two engines.
    """
    import jax.numpy as jnp

    from ray_tpu.llm import EngineConfig, LLMEngine
    from ray_tpu.models.gpt import GPTConfig

    cfg = GPTConfig(
        vocab_size=512, num_layers=2, num_heads=4, embed_dim=128,
        max_seq_len=512, dtype=jnp.float32, attention_impl="reference",
    )
    rng = np.random.RandomState(0)
    system = list(map(int, rng.randint(0, 512, size=256)))
    n_requests = 8
    suffixes = [
        list(map(int, rng.randint(0, 512, size=16))) for _ in range(n_requests)
    ]
    prompts = [system + s for s in suffixes]
    max_new = 16

    def run(enable: bool) -> tuple[float, float, list]:
        ecfg = EngineConfig(
            block_size=32, num_blocks=96, max_decode_slots=8,
            max_blocks_per_seq=16, enable_prefix_caching=enable,
        )
        engine = LLMEngine(cfg, ecfg, seed=0)
        # Warm every program this workload compiles — the full-prefill
        # bucket, the partial-prefill bucket a suffix hit lands in, and
        # decode — on a *different* system prompt, then drop the warmup's
        # cached blocks so the measured run starts cold.
        warm_sys = list(map(int, rng.randint(0, 512, size=256)))
        warm = [
            warm_sys + list(map(int, rng.randint(0, 512, size=16)))
            for _ in range(2)
        ]
        engine.generate(warm, max_new_tokens=2)
        engine.allocator.reset_prefix_cache()

        produced = [[] for _ in prompts]
        submit = [0.0] * len(prompts)
        first = [0.0] * len(prompts)

        def on_token(i):
            def cb(_tok):
                if not produced[i]:
                    first[i] = time.perf_counter()
                produced[i].append(_tok)
            return cb

        t0 = time.perf_counter()
        for i, p in enumerate(prompts):
            submit[i] = time.perf_counter()
            engine.add_request(p, max_new_tokens=max_new, on_token=on_token(i))
        while engine.has_work():
            engine.step()
        wall = time.perf_counter() - t0
        total = sum(len(v) for v in produced)
        assert total == max_new * len(prompts)
        ttft = sum(f - s for f, s in zip(first, submit)) / len(prompts)
        return ttft, total / wall, produced

    ttft_off, tps_off, out_off = run(enable=False)
    ttft_on, tps_on, out_on = run(enable=True)
    assert out_on == out_off, "prefix caching changed greedy outputs"
    report("serving_prefix_ttft_uncached", 1e3 * ttft_off, unit="ms")
    report("serving_prefix_ttft_cached", 1e3 * ttft_on, unit="ms")
    report("serving_prefix_ttft_speedup", ttft_off / ttft_on, unit="x")
    report("serving_prefix_tokens_per_s_uncached", tps_off, unit="tokens/s")
    report("serving_prefix_tokens_per_s_cached", tps_on, unit="tokens/s")
    report("serving_prefix_throughput_speedup", tps_on / tps_off, unit="x")


def bench_serving_failover():
    """Cost of a mid-stream replica failover: p50/p99 latency ADDED to a
    streaming LLM request when the replica serving it dies halfway through
    (deterministic fault injection raises ActorDiedError between yields)
    and the router resumes on the second replica via llm_stream_resume.

    The resume re-submits prompt + tokens-received-so-far, so with prefix
    caching the resumed prefill is mostly cache hits — the added latency is
    roughly one retry backoff (50ms default) plus one tail prefill."""
    import jax.numpy as jnp

    from ray_tpu import serve
    from ray_tpu._private import fault_injection as fi
    from ray_tpu.exceptions import ActorDiedError
    from ray_tpu.llm import EngineConfig
    from ray_tpu.llm.serve import build_app, llm_stream_resume
    from ray_tpu.models.gpt import GPTConfig

    cfg = GPTConfig(
        vocab_size=512, num_layers=2, num_heads=4, embed_dim=128,
        max_seq_len=256, dtype=jnp.float32, attention_impl="reference",
    )
    ecfg = EngineConfig(
        block_size=8, num_blocks=128, max_decode_slots=8,
        max_blocks_per_seq=8, prefill_buckets=(16, 64),
    )
    handle = serve.run(
        build_app(cfg, ecfg, engine_name="bench-failover", num_replicas=2),
        name="bench-failover",
    )
    stream_handle = handle.options(
        stream=True, stream_resume_fn=llm_stream_resume
    )
    rng = np.random.RandomState(0)
    n_new = 24
    prompts = [
        list(map(int, rng.randint(0, 512, size=12))) for _ in range(12)
    ]

    def stream_once(prompt) -> float:
        t0 = time.perf_counter()
        tokens = [
            d["token_id"]
            for d in stream_handle.remote(
                {"prompt_ids": prompt, "max_new_tokens": n_new, "stream": True}
            )
        ]
        assert len(tokens) == n_new  # contiguous through any failover
        return time.perf_counter() - t0

    for p in prompts[:2]:  # warm both replicas' paths
        stream_once(p)
    base = sorted(stream_once(p) for p in prompts)
    killed = []
    for p in prompts:
        # Fresh spec per request: die after delivering half the tokens.
        spec = fi.inject(
            "replica.stream_item",
            nth=n_new // 2,
            exc_factory=lambda: ActorDiedError(None, "bench mid-stream kill"),
        )
        try:
            killed.append(stream_once(p))
            assert spec.fires == 1
        finally:
            fi.remove(spec)
    killed.sort()

    def pct(xs, q):
        return xs[min(len(xs) - 1, int(q * len(xs)))]

    base_p50 = pct(base, 0.5)
    added = sorted(k - base_p50 for k in killed)
    report("serving_failover_stream_base_p50", 1e3 * base_p50, unit="ms")
    report("serving_failover_added_latency_p50", 1e3 * pct(added, 0.5), unit="ms")
    report("serving_failover_added_latency_p99", 1e3 * pct(added, 0.99), unit="ms")
    serve.shutdown()


def bench_serving_observability():
    """Cost of the serving observability plane on the decode hot loop:
    the same continuous-batching workload with EngineConfig.instrument on
    (request spans, TTFT/TPOT/queue/e2e/step histograms, flight recorder)
    vs compiled out. Instrumentation records per stretch and per step —
    never per token — so the overhead must stay under 5% of decode
    throughput even on CPU, where a decode step is only ~1 ms."""
    import jax.numpy as jnp

    from ray_tpu.llm import EngineConfig, LLMEngine
    from ray_tpu.models.gpt import GPTConfig

    cfg = GPTConfig(
        vocab_size=512, num_layers=2, num_heads=4, embed_dim=128,
        max_seq_len=256, dtype=jnp.float32, attention_impl="reference",
    )
    rng = np.random.RandomState(0)
    n_requests = 24
    prompts = [
        list(map(int, rng.randint(0, 512, size=rng.randint(4, 25))))
        for _ in range(n_requests)
    ]
    budgets = [int(rng.randint(8, 33)) for _ in range(n_requests)]

    def make_engine(instrument: bool) -> "LLMEngine":
        ecfg = EngineConfig(
            block_size=8, num_blocks=128, max_decode_slots=8,
            max_blocks_per_seq=8, instrument=instrument,
        )
        engine = LLMEngine(cfg, ecfg, seed=0)
        for n in (5, 9, 17, 33):  # warm every compiled program
            engine.generate([[1] * n], max_new_tokens=2)
        engine.allocator.reset_prefix_cache()
        return engine

    def run(engine) -> float:
        slots = engine.engine_config.max_decode_slots
        produced = []

        def admit(p, b):
            tokens = []
            engine.add_request(p, max_new_tokens=b, on_token=tokens.append)
            produced.append(tokens)

        t0 = time.perf_counter()
        pending = list(zip(prompts, budgets))
        while pending or engine.has_work():
            while pending and len(engine.scheduler.waiting) < slots:
                admit(*pending.pop(0))
            engine.step()
        wall = time.perf_counter() - t0
        total = sum(len(v) for v in produced)
        assert total == sum(budgets)
        engine.allocator.reset_prefix_cache()
        return total / wall

    eng_on, eng_off = make_engine(True), make_engine(False)
    # Alternate rounds and take each mode's best, so a one-off GC pause or
    # frequency wobble can't masquerade as instrumentation overhead.
    tps_on = tps_off = 0.0
    for _ in range(3):
        tps_on = max(tps_on, run(eng_on))
        tps_off = max(tps_off, run(eng_off))
    overhead = 1.0 - tps_on / tps_off
    report("serving_observability_tokens_per_s_on", tps_on, unit="tokens/s")
    report("serving_observability_tokens_per_s_off", tps_off, unit="tokens/s")
    report("serving_observability_overhead_pct", 100 * overhead, unit="%")
    assert overhead < 0.05, (
        f"observability overhead {overhead:.1%} exceeds the 5% budget"
    )


ALL = [
    ("single_client_tasks_sync", bench_tasks_sync),
    ("single_client_tasks_async", bench_tasks_async),
    ("multi_client_tasks_async", bench_multi_client_tasks_async),
    (
        "1_1_actor_calls_sync",
        lambda: bench_actor_calls("1_1_actor_calls_sync", Sink, 1, 1, sync=True),
    ),
    (
        "1_1_actor_calls_async",
        lambda: bench_actor_calls("1_1_actor_calls_async", Sink, 1, 1, sync=False),
    ),
    (
        "1_1_actor_calls_concurrent",
        lambda: bench_actor_calls(
            "1_1_actor_calls_concurrent", Sink, 1, 1, sync=False,
            options={"max_concurrency": 16},
        ),
    ),
    (
        "1_n_actor_calls_async",
        lambda: bench_actor_calls("1_n_actor_calls_async", Sink, 8, 1, sync=False),
    ),
    (
        "n_n_actor_calls_async",
        lambda: bench_actor_calls("n_n_actor_calls_async", Sink, 8, 8, sync=False),
    ),
    (
        "n_n_actor_calls_with_arg_async",
        lambda: bench_actor_calls(
            "n_n_actor_calls_with_arg_async", Sink, 8, 8, sync=False, with_arg=True
        ),
    ),
    (
        "1_1_async_actor_calls_sync",
        lambda: bench_actor_calls(
            "1_1_async_actor_calls_sync", AsyncSink, 1, 1, sync=True
        ),
    ),
    (
        "1_1_async_actor_calls_async",
        lambda: bench_actor_calls(
            "1_1_async_actor_calls_async", AsyncSink, 1, 1, sync=False
        ),
    ),
    (
        "n_n_async_actor_calls_async",
        lambda: bench_actor_calls(
            "n_n_async_actor_calls_async", AsyncSink, 8, 8, sync=False
        ),
    ),
    ("put_get_calls", bench_puts_and_gets),
    ("put_gigabytes", bench_put_gigabytes),
    ("tasks_and_get_batch", bench_tasks_and_get_batch),
    ("placement_group_create_removal", bench_placement_groups),
    ("train_ingestion", bench_train_ingestion),
    ("training_observability", bench_training_observability),
    ("serving_decode", bench_serving_decode),
    ("serving_async_step", bench_serving_async_step),
    ("serving_decode_tp", bench_serving_decode_tp),
    ("serving_decode_attn_impl", bench_serving_decode_attn_impl),
    ("serving_speculative", bench_serving_speculative),
    ("serving_chunked_prefill", bench_serving_chunked_prefill),
    ("serving_prefix_cache", bench_serving_prefix_cache),
    ("serving_failover", bench_serving_failover),
    ("serving_observability", bench_serving_observability),
]


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--filter", default="", help="substring filter")
    parser.add_argument("--json-out", default="", help="write results to file")
    args = parser.parse_args()

    ray_tpu.init(num_cpus=16)
    for name, fn in ALL:
        if args.filter and args.filter not in name:
            continue
        fn()
    ray_tpu.shutdown()
    beat = sum(
        1 for r in RESULTS if r["vs_baseline"] is not None and r["vs_baseline"] >= 1.0
    )
    total = sum(1 for r in RESULTS if r["vs_baseline"] is not None)
    # Local memory-bandwidth ceiling for honest GB/s comparisons: the
    # reference numbers come from an m5.16xlarge-class box (64 vCPUs,
    # ~20 GB/s single-stream copy); put-gigabytes is a memcpy at heart and
    # cannot exceed this machine's copy bandwidth, and the multi_client/n_n
    # scaling rows cannot scale past the local core count.
    a = np.ones(1 << 27, dtype=np.uint8)
    b = np.empty_like(a)
    np.copyto(b, a)
    best = 0.0
    for _ in range(3):
        t0 = time.perf_counter()
        np.copyto(b, a)
        best = max(best, a.nbytes / (time.perf_counter() - t0) / 1e9)
    summary = {
        "benchmark": "summary",
        "beats_baseline": beat,
        "compared": total,
        "hardware_cpu_cores": os.cpu_count(),
        "local_memcpy_gbps": round(best, 1),
        "baseline_hardware": "m5.16xlarge-class (64 vCPU)",
    }
    for row in RESULTS:
        if row["unit"] == "GB/s":
            row["pct_of_local_memcpy"] = round(100 * row["value"] / best, 1)
    print(json.dumps(summary))
    if args.json_out:
        with open(args.json_out, "w") as f:
            json.dump(RESULTS + [summary], f, indent=2)


if __name__ == "__main__":
    main()
