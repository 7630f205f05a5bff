"""Fleet observability plane: cross-replica time ledger, merged
histograms, SLO burn-rate monitoring, and Perfetto request timelines.

Acceptance (ISSUE 19): on a seeded loadgen run against 2 replicas the
/api/fleet ledger's components sum to 100% +- 5% of each replica's
measured wall, one sampled request exports a Perfetto-loadable timeline
spanning handle -> replica -> engine with flow events connecting the
actor rows, and the burn-rate monitor flips its gauge above 1.0 during
an overload burst and back below afterwards. The obs_smoke-marked test
is the `make obs-smoke` CI entry point (rides tier-1 — keep it fast).
"""

import json
import urllib.request

import pytest

import jax.numpy as jnp

import ray_tpu
from ray_tpu.llm import EngineConfig
from ray_tpu.loadgen.slo import SLOSpec
from ray_tpu.models.gpt import GPTConfig
from ray_tpu.observability import (
    SLOBurnRateMonitor,
    fleet_snapshot,
    fleet_ledger,
    replica_ledger,
    step_ledger,
)
from ray_tpu.observability.ledger import LEDGER_COLUMNS, REPLICA_COLUMNS
from ray_tpu.serve.config import LLMAutoscalingPolicy
from ray_tpu.util import metrics, tracing
from ray_tpu.util.metrics import (
    BucketMismatchError,
    fraction_over_threshold,
    merge_snapshots,
)

TINY = GPTConfig(
    vocab_size=128,
    num_layers=2,
    num_heads=4,
    embed_dim=64,
    max_seq_len=128,
    dtype=jnp.float32,
    attention_impl="reference",
)

ECFG = EngineConfig(
    block_size=4,
    num_blocks=24,
    max_decode_slots=4,
    max_blocks_per_seq=8,
    prefill_buckets=(8, 32),
)


# ---------------- merge_snapshots (satellite: typed cross-replica merge) ----


def _snap(boundaries, buckets, total=None, count=None):
    return {
        "boundaries": list(boundaries),
        "buckets": list(buckets),
        "sum": sum(buckets) if total is None else total,
        "count": sum(buckets) if count is None else count,
    }


def test_merge_snapshots_sums_known_sets():
    a = _snap([1.0, 2.0], [1, 2, 3], total=4.0, count=6)
    b = _snap([1.0, 2.0], [0, 1, 1], total=2.5, count=2)
    merged = merge_snapshots([a, b])
    assert merged["boundaries"] == [1.0, 2.0]
    assert merged["buckets"] == [1, 3, 4]
    assert merged["sum"] == pytest.approx(6.5)
    assert merged["count"] == 8
    # Single-input merge is the identity.
    solo = merge_snapshots([a])
    assert solo["buckets"] == a["buckets"] and solo["count"] == a["count"]


def test_merge_snapshots_refuses_mismatched_ladders():
    a = _snap([1.0, 2.0], [1, 2, 3])
    b = _snap([1.0, 5.0], [1, 2, 3])
    with pytest.raises(BucketMismatchError):
        merge_snapshots([a, b])
    # Length mismatch between buckets and ladder is the same typed error.
    with pytest.raises(BucketMismatchError):
        merge_snapshots([a, _snap([1.0, 2.0], [1, 2])])
    # BucketMismatchError is a ValueError: existing except ValueError
    # callers degrade instead of crashing.
    assert issubclass(BucketMismatchError, ValueError)
    with pytest.raises(ValueError):
        merge_snapshots([])


def test_fraction_over_threshold_interpolates():
    boundaries = [1.0, 2.0, 4.0]
    buckets = [2, 2, 2, 2]  # 8 samples, 2 in the +Inf overflow
    assert fraction_over_threshold(boundaries, buckets, 2.0) == pytest.approx(
        0.5
    )
    # Threshold mid-bucket: half of the (2, 4] bucket counts as over.
    assert fraction_over_threshold(boundaries, buckets, 3.0) == pytest.approx(
        3 / 8
    )
    # Below the first boundary: half of bucket 0 plus everything above.
    assert fraction_over_threshold(boundaries, buckets, 0.5) == pytest.approx(
        7 / 8
    )
    # Past the last finite boundary: the whole overflow bucket counts
    # (conservative — alert rather than stay silent).
    assert fraction_over_threshold(
        boundaries, buckets, 100.0
    ) == pytest.approx(2 / 8)
    assert fraction_over_threshold(boundaries, [0, 0, 0, 0], 1.0) is None
    with pytest.raises(ValueError):
        fraction_over_threshold(boundaries, [1, 2], 1.0)


# ---------------- time ledger ----------------


def test_step_ledger_partitions_duration_exactly():
    rec = {
        "time": 1000.0,
        "phase": "prefill+decode",
        "duration_s": 0.100,
        "phases": {
            "schedule": 0.015, "prepare": 0.015, "wait": 0.050,
            "commit": 0.010, "other": 0.010,
        },
        "commits": [{"tokens": 4}],
        "host_exposed_s": 0.002,
    }
    led = step_ledger(rec)
    assert led["idle_s"] == 0.0
    # Each column IS the phase the step's clock measured; nothing is
    # derived from stamps of another clock.
    assert led["schedule_s"] == pytest.approx(0.015)
    assert led["prepare_s"] == pytest.approx(0.015)
    assert led["host_wait_s"] == pytest.approx(0.050)
    assert led["commit_s"] == pytest.approx(0.010)
    assert led["other_s"] == pytest.approx(0.010)
    assert sum(led[c] for c in LEDGER_COLUMNS) == pytest.approx(0.100)
    # host_exposed is an OVERLAY (it runs across the non-wait columns),
    # never part of the partition sum.
    assert led["host_exposed_s"] == pytest.approx(0.002)


def test_step_ledger_idle_and_unphased_steps():
    idle = step_ledger(
        {"time": 5.0, "phase": "idle", "duration_s": 0.05, "commits": [],
         "phases": {"schedule": 0.04, "other": 0.01}}
    )
    assert idle["idle_s"] == pytest.approx(0.05)
    assert sum(idle[c] for c in LEDGER_COLUMNS) == pytest.approx(0.05)
    # The phases sum to the duration by measurement; what rounding leaves
    # over, or a record that carries no phases at all, lands in other_s,
    # so the partition stays exact.
    rounded = step_ledger(
        {
            "time": 10.0,
            "phase": "decode",
            "duration_s": 0.010,
            "phases": {"schedule": 0.001, "prepare": 0.002, "wait": 0.006,
                       "commit": 0.0005},
            "commits": [{"tokens": 1}],
        }
    )
    assert sum(rounded[c] for c in LEDGER_COLUMNS) == pytest.approx(0.010)
    assert rounded["other_s"] == pytest.approx(0.0005)
    assert rounded["idle_s"] == 0.0
    bare = step_ledger({"time": 11.0, "phase": "decode", "duration_s": 0.02})
    assert bare["other_s"] == pytest.approx(0.02)
    assert sum(bare[c] for c in LEDGER_COLUMNS) == pytest.approx(0.02)


def test_replica_ledger_covers_wall_and_estimates_mfu():
    t0 = 100.0
    steps = []
    for i in range(2):
        start = t0 + i * 0.2
        steps.append(
            {
                "time": start,
                "phase": "decode",
                "duration_s": 0.1,
                "phases": {"schedule": 0.005, "prepare": 0.005,
                           "wait": 0.07, "commit": 0.01, "other": 0.01},
                "commits": [{"tokens": 4}],
                "host_exposed_s": None,
            }
        )
    led = replica_ledger(steps, model_params=1000, peak_flops_per_s=1e6)
    # Wall span: first step start -> last step end = 0.3s; the 0.1s
    # between the steps is inter-step loop time.
    assert led["wall_s"] == pytest.approx(0.3)
    assert led["columns"]["loop_s"] == pytest.approx(0.1)
    assert led["columns"]["host_wait_s"] == pytest.approx(0.14)
    assert led["ledger_sum_s"] == pytest.approx(0.3)
    assert led["coverage"] == pytest.approx(1.0)
    assert led["committed_tokens"] == 8
    goodput = 8 / 0.3
    assert led["goodput_tokens_per_s"] == pytest.approx(goodput)
    assert led["mfu"] == pytest.approx(2 * 1000 * goodput / 1e6)
    # CPU runs have no peak-FLOPs figure: MFU is unknown, not guessed.
    assert replica_ledger(steps, model_params=1000)["mfu"] is None
    empty = replica_ledger([])
    assert empty["steps"] == 0 and empty["coverage"] is None


def test_fleet_ledger_merges_replicas():
    t0 = 100.0
    step = {
        "time": t0,
        "phase": "decode",
        "duration_s": 0.1,
        "phases": {"schedule": 0.005, "prepare": 0.005, "wait": 0.08,
                   "commit": 0.005, "other": 0.005},
        "commits": [{"tokens": 6}],
    }
    a = replica_ledger([step])
    b = replica_ledger([dict(step, time=t0 + 1.0)])
    fleet = fleet_ledger({"r0": a, "r1": b})
    assert fleet["replicas"] == 2
    assert fleet["committed_tokens"] == 12
    # Replicas run concurrently: fleet goodput is the SUM of per-replica
    # token rates.
    assert fleet["goodput_tokens_per_s"] == pytest.approx(
        a["goodput_tokens_per_s"] + b["goodput_tokens_per_s"]
    )
    assert fleet["min_coverage"] == pytest.approx(1.0)
    assert set(fleet["columns"]) == set(REPLICA_COLUMNS)
    assert fleet["bottlenecks"][0] == "host_wait_s"


# ---------------- SLO burn-rate monitor ----------------

_BOUNDS = [0.001, 0.01, 0.1, 1.0, 10.0]


def _ttft_snap(good, bad):
    """good samples ~50ms (within a 1s SLO), bad ~5s (over it)."""
    buckets = [0, 0, good, 0, bad, 0]
    return {
        "boundaries": list(_BOUNDS),
        "buckets": buckets,
        "sum": 0.05 * good + 5.0 * bad,
        "count": good + bad,
    }


def test_burn_rate_flips_above_one_during_burst_and_recovers():
    spec = SLOSpec.from_bounds("burntest", ttft_p99=1.0)
    state = {"cur": _ttft_snap(0, 0)}
    mon = SLOBurnRateMonitor(
        spec,
        windows=(5.0,),
        source=lambda: {"llm_request_ttft_seconds": dict(state["cur"])},
    )
    assert mon.sample(now=0.0)["5s"] == 0.0  # no traffic burns nothing

    # Overload burst: 90% of the window's samples blow the 1s bound
    # against a 1% error budget -> burn ~90.
    state["cur"] = _ttft_snap(10, 90)
    burst = mon.sample(now=2.0)["5s"]
    assert burst > 1.0
    assert mon.peak_burn(5.0) == pytest.approx(burst)
    assert mon.autoscaler_signal()["slo_burn_rate"] == pytest.approx(burst)
    text = metrics.prometheus_text()
    assert 'llm_slo_burn_rate{slo="burntest",window="5s"}' in text

    # Shedding recovers the fleet: only good samples arrive afterwards,
    # and once the burst ages out of the window the burn drops back
    # below 1.0 (cumulative counters keep the burst forever — the
    # windowed DIFF is what lets the gauge recover).
    state["cur"] = _ttft_snap(110, 90)
    recovered = mon.sample(now=10.0)["5s"]
    assert recovered < 1.0
    assert mon.peak_burn() == pytest.approx(burst)  # peak remembers
    rates = mon.burn_rates()["5s"]
    assert rates["ttft_p99"] == pytest.approx(recovered)


def test_burn_rate_feeds_autoscaler_policy():
    policy = LLMAutoscalingPolicy(
        min_replicas=1, max_replicas=3, target_burn_rate=1.0
    )  # valid as the lone target
    hot = policy.desired_replicas(
        {"slo_burn_rate": 5.0, "window_complete": True}, current=1
    )
    assert hot == 2
    # Burn within margin of the target blocks scale-down.
    hold = policy.desired_replicas(
        {"slo_burn_rate": 0.6, "window_complete": True}, current=2
    )
    assert hold == 2
    cold = policy.desired_replicas(
        {"slo_burn_rate": 0.0, "window_complete": True}, current=2
    )
    assert cold == 1
    with pytest.raises(ValueError):
        LLMAutoscalingPolicy(min_replicas=1, max_replicas=2)
    with pytest.raises(ValueError):
        LLMAutoscalingPolicy(
            min_replicas=1, max_replicas=2, target_burn_rate=-1.0
        )


# ---------------- timeline merging across forked processes ----------------


def test_timeline_fork_isolation_no_span_collisions(tmp_path):
    """Spans emitted from process-isolated workers merge into one
    timeline with no span-id collisions (the per-process PRNG re-seeds
    after fork), and llm.* spans get their own process row in the
    Perfetto export — not just train spans."""
    runtime = ray_tpu.init(
        num_cpus=2, _system_config={"isolation": "process"}
    )
    try:

        @ray_tpu.remote
        def emit(i):
            # llm.-named spans from FORKED workers: each child process
            # mints its own span ids.
            with tracing.span("llm.decode", {"worker": i}):
                with tracing.span("llm.prefill"):
                    pass
            return i

        with tracing.span("client") as root:
            assert sorted(
                ray_tpu.get([emit.remote(i) for i in range(8)])
            ) == list(range(8))

        rows = tracing.traces(trace_id=root.trace_id)
        span_ids = [r["span_id"] for r in rows]
        assert len(span_ids) == len(set(span_ids)), "span-id collision"
        assert sum(r["name"] == "llm.decode" for r in rows) == 8
        assert sum(r["name"] == "llm.prefill" for r in rows) == 8

        out = tmp_path / "request.json"
        trace = ray_tpu.timeline(str(out), trace_id=root.trace_id)
        loaded = json.loads(out.read_text())
        assert loaded["traceEvents"] == trace["traceEvents"]
        names = {
            e["args"]["name"]: e["pid"]
            for e in loaded["traceEvents"]
            if e["ph"] == "M" and e["name"] == "process_name"
        }
        # llm spans land on their own process row, distinct from the
        # driver's and the task rows.
        assert "llm.engine" in names
        assert "driver" in names
        llm_slices = [
            e
            for e in loaded["traceEvents"]
            if e["ph"] == "X" and e["pid"] == names["llm.engine"]
        ]
        assert len(llm_slices) == 16
    finally:
        ray_tpu.shutdown()


# ---------------- obs-smoke: the end-to-end acceptance run ----------------


def _get_json(url):
    with urllib.request.urlopen(url, timeout=10) as resp:
        return json.loads(resp.read().decode())


@pytest.mark.obs_smoke
def test_obs_smoke_fleet_ledger_and_perfetto_export(tmp_path):
    """make obs-smoke: seeded short loadgen against 2 ingress replicas
    with per-replica engines. Asserts (1) every active replica's ledger
    columns sum to 100% +- 5% of its measured wall span, (2) /api/fleet
    serves the same view over HTTP with merged fleet histograms, (3) one
    sampled request's Perfetto export is valid Chrome-trace JSON with
    handle/replica/engine process rows stitched by flow events, and
    (4) the live burn monitor sees an impossible SLO burning (>1.0) and
    a loose one not."""
    from ray_tpu import serve
    from ray_tpu.llm.serve import build_app
    from ray_tpu.loadgen.slo import IMPOSSIBLE_SLO, LOOSE_SLO

    runtime = ray_tpu.init(
        num_cpus=8,
        _system_config={"include_dashboard": True, "dashboard_port": 0},
    )
    try:
        handle = serve.run(
            build_app(
                TINY,
                ECFG,
                engine_name="fleetobs",
                num_replicas=2,
                engine_per_replica=True,
            ),
            name="fleetobs",
        )
        monitors = {
            s.name: SLOBurnRateMonitor(s, windows=(5.0, 60.0))
            for s in (LOOSE_SLO, IMPOSSIBLE_SLO)
        }
        for mon in monitors.values():
            mon.sample()  # baseline before traffic

        import numpy as np

        rng = np.random.RandomState(19)
        prompts = [
            list(map(int, rng.randint(0, 128, size=n)))
            for n in rng.randint(4, 12, size=14)
        ]
        # Concurrent wave so the router spreads load across replicas.
        refs = [
            handle.remote({"prompt_ids": p, "max_new_tokens": 6})
            for p in prompts
        ]
        for r in refs:
            assert len(r.result(timeout_s=120)["token_ids"]) == 6
        # One SAMPLED request under a handle-side span: the Perfetto
        # export stitches its cross-actor path.
        with tracing.span("serve.handle.request") as root:
            res = handle.remote(
                {"prompt_ids": prompts[0], "max_new_tokens": 4}
            )
            assert len(res.result(timeout_s=120)["token_ids"]) == 4
        burns = {name: mon.sample() for name, mon in monitors.items()}

        # ---- (1) the fleet ledger sums to ~100% of measured wall ----
        snap = fleet_snapshot(runtime, steps_limit=512)
        replicas = snap["replicas"]
        assert len(replicas) == 2, sorted(replicas)
        active = 0
        for name, row in replicas.items():
            assert "error" not in row, (name, row)
            ledger = row["ledger"]
            if not ledger["steps"]:
                continue
            active += 1
            assert 0.95 <= ledger["coverage"] <= 1.05, (name, ledger)
            assert set(ledger["fractions"]) == set(REPLICA_COLUMNS)
            assert row["model_params"] and row["model_params"] > 0
        assert active >= 1
        fleet = snap["fleet"]
        assert fleet["committed_tokens"] > 0
        assert fleet["goodput_tokens_per_s"] > 0
        assert 0.95 <= fleet["min_coverage"] <= 1.05
        # Merged request histograms carry every request exactly once.
        ttft = snap["histograms"]["llm_request_ttft_seconds"]
        assert ttft["count"] >= len(prompts) + 1
        assert snap["percentiles"]["llm_request_ttft_seconds"]["p99"] > 0

        # ---- (2) the dashboard serves the same view ----
        base = runtime.dashboard.url
        api = _get_json(f"{base}/api/fleet")
        assert set(api["replicas"]) == set(replicas)
        with urllib.request.urlopen(base, timeout=10) as resp:
            page = resp.read().decode()
        assert "Fleet ledger" in page

        # ---- (3) Perfetto export of the sampled request ----
        out = tmp_path / "request_timeline.json"
        ray_tpu.timeline(str(out), trace_id=root.trace_id)
        trace = json.loads(out.read_text())  # valid Chrome-trace JSON
        events = trace["traceEvents"]
        rows_by_label = {
            e["args"]["name"]: e["pid"]
            for e in events
            if e["ph"] == "M" and e["name"] == "process_name"
        }
        # handle -> ingress replica -> engine, each its own process row.
        assert "serve.handle" in rows_by_label, sorted(rows_by_label)
        assert "serve.replica" in rows_by_label, sorted(rows_by_label)
        assert "llm.engine" in rows_by_label, sorted(rows_by_label)
        llm_names = {
            e["name"]
            for e in events
            if e["ph"] == "X" and e["pid"] == rows_by_label["llm.engine"]
        }
        assert "llm.request" in llm_names
        # Flow events stitch the cross-actor span ids: every source
        # arrow has its finish half, and at least one crosses rows.
        starts = [e for e in events if e["ph"] == "s"]
        finishes = {e["id"]: e for e in events if e["ph"] == "f"}
        assert starts and finishes
        crossed = 0
        for s in starts:
            f = finishes.get(s["id"])
            assert f is not None, f"unpaired flow {s['id']}"
            if f["pid"] != s["pid"]:
                crossed += 1
        assert crossed > 0

        # ---- (4) live burn pair discriminates ----
        for mon in monitors.values():
            mon.stop()
        assert monitors["impossible"].peak_burn() > 1.0
        assert monitors["loose"].peak_burn() < 1.0
        assert burns["impossible"]["5s"] > 1.0 or (
            monitors["impossible"].peak_burn() > 1.0
        )
    finally:
        from ray_tpu import serve

        serve.shutdown()
        ray_tpu.shutdown()
