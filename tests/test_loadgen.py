"""ray_tpu.loadgen — open-loop traffic harness with SLO gating.

Covers seeded determinism (byte-identical schedules — the property that
makes a loadgen run a bench record), arrival-process shapes, the SLO
gate's pass/fail discrimination, the serve-path smoke cell (real
router → replica → engine traffic with the engine-histogram
cross-check), poison isolation through the harness, and the mid-stream
disconnect abort path (KV + draft pools back at boot size).
"""

import time

import pytest

import jax.numpy as jnp

import ray_tpu
from ray_tpu.llm import EngineConfig, LLMServer
from ray_tpu.loadgen import (
    IMPOSSIBLE_SLO,
    LOOSE_SLO,
    ArrivalSpec,
    ScenarioSpec,
    SLOSpec,
    arrival_times,
    build_report,
    evaluate_slo,
    format_report,
    generate_requests,
    schedule_fingerprint,
)
from ray_tpu.loadgen.driver import LoadRunResult, RequestSample
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


# ---------------- scenarios ----------------


def test_scenario_schedule_is_byte_identical_across_runs():
    """Same scenario seed ⇒ byte-identical request list (ids, prompts,
    kinds, disconnect points); a different seed ⇒ a different one."""
    spec = ScenarioSpec.for_engine(
        64, 64, 128, name="mixed", num_requests=48, seed=7
    )
    a = generate_requests(spec)
    b = generate_requests(spec)
    assert schedule_fingerprint(a) == schedule_fingerprint(b)
    other = generate_requests(
        ScenarioSpec.for_engine(
            64, 64, 128, name="mixed", num_requests=48, seed=8
        )
    )
    assert schedule_fingerprint(a) != schedule_fingerprint(other)


def test_scenario_requests_respect_engine_admission_bounds():
    """Every generated request must pass the engine's admission checks:
    prompt + max_new within max_model_len AND lifetime within the largest
    prefill bucket (for_engine derives the caps)."""
    ecfg = EngineConfig(block_size=8, num_blocks=96, max_blocks_per_seq=8)
    spec = ScenarioSpec.for_engine(
        ecfg.max_model_len, ecfg.buckets()[-1], 128,
        name="mixed", num_requests=64, seed=3,
    )
    for req in generate_requests(spec):
        total = len(req.prompt_ids) + req.max_new_tokens
        assert total <= ecfg.max_model_len
        assert total - 1 <= ecfg.buckets()[-1]
        assert len(req.prompt_ids) >= 1 and req.max_new_tokens >= 1


def test_multiturn_sessions_share_growing_prefixes():
    """Turn t's full prompt is a strict prefix of the same session's turn
    t+1 prompt (the prefix-cache / CoW exercise the scenario exists for)."""
    spec = ScenarioSpec.for_engine(
        64, 64, 128, name="multiturn", num_requests=16, seed=1
    )
    by_session = {}
    for req in generate_requests(spec):
        by_session.setdefault(req.session_id, []).append(req)
    assert len(by_session) > 1
    checked = 0
    for reqs in by_session.values():
        for a, b in zip(reqs, reqs[1:]):
            if b.turn == 0:
                continue  # session restarted after outgrowing the context
            assert b.prompt_ids[: len(a.prompt_ids)] == a.prompt_ids
            assert len(b.prompt_ids) > len(a.prompt_ids)
            checked += 1
    assert checked > 0


def test_scenario_kinds_and_unknown_name():
    spec = ScenarioSpec.for_engine(
        64, 64, 128, name="disconnect", num_requests=8, seed=0
    )
    for req in generate_requests(spec):
        assert req.kind == "disconnect"
        assert 1 <= req.disconnect_after < req.max_new_tokens
    with pytest.raises(ValueError, match="unknown scenario"):
        generate_requests(
            ScenarioSpec.for_engine(
                64, 64, 128, name="nope", num_requests=4
            )
        )
    # The output budget floor is validated up front (a disconnect must be
    # able to land mid-stream), so for_engine's admission guarantee holds
    # for every generator.
    with pytest.raises(ValueError, match="max_new_tokens"):
        ScenarioSpec(max_new_tokens=3)


# ---------------- arrivals ----------------


def test_arrival_processes_deterministic_and_monotonic():
    for process in ("poisson", "uniform", "onoff", "ramp"):
        spec = ArrivalSpec(
            process=process, rate=8.0, seed=5, off_rate_fraction=0.2
        )
        ts = arrival_times(spec, 64)
        assert len(ts) == 64
        assert ts == sorted(ts)
        assert ts == arrival_times(spec, 64)
    assert arrival_times(ArrivalSpec(rate=4.0), 0) == []


def test_onoff_arrivals_respect_phase_rates():
    """With off_rate_fraction=0 every arrival lands inside an on-window —
    the bursty shape is real, not an average."""
    spec = ArrivalSpec(
        process="onoff", rate=50.0, seed=2, on_s=1.0, off_s=1.0,
        off_rate_fraction=0.0,
    )
    for t in arrival_times(spec, 100):
        assert t % 2.0 < 1.0, f"arrival at {t} inside an off window"


def test_uniform_and_ramp_rates():
    ts = arrival_times(ArrivalSpec(process="uniform", rate=10.0), 11)
    assert ts[-1] == pytest.approx(1.0)
    # Ramp sweeps the gap downward on average: the second half of a
    # 4 → 40/s ramp must be denser than the first half.
    ts = arrival_times(
        ArrivalSpec(process="ramp", rate=4.0, ramp_to_rate=40.0, seed=3),
        200,
    )
    first_half = ts[99] - ts[0]
    second_half = ts[199] - ts[100]
    assert second_half < first_half


def test_arrival_spec_validation():
    with pytest.raises(ValueError, match="unknown arrival process"):
        ArrivalSpec(process="burst")
    with pytest.raises(ValueError, match="rate"):
        ArrivalSpec(rate=0.0)


# ---------------- SLO gate (no server needed) ----------------


def _fake_result(n_ok=20, n_err=2, ttft=0.01, tpot=0.002):
    samples = []
    for i in range(n_ok):
        samples.append(
            RequestSample(
                request_id=f"ok-{i}", kind="normal", scenario="longtail",
                session_id=None, scheduled_s=i * 0.1, sent_s=i * 0.1,
                ttft_s=ttft, tpot_s=tpot, e2e_s=ttft + 10 * tpot,
                num_tokens=10,
            )
        )
    for i in range(n_err):
        samples.append(
            RequestSample(
                request_id=f"bad-{i}", kind="poison", scenario="poison",
                session_id=None, scheduled_s=i * 0.1, sent_s=i * 0.1,
                error="PoisonRequestError",
            )
        )
    return LoadRunResult(
        samples=samples,
        offered_duration_s=n_ok * 0.1,
        wall_duration_s=n_ok * 0.1 + 0.05,
        offered_rate=(n_ok + n_err) / (n_ok * 0.1),
    )


def test_slo_gate_discriminates_loose_vs_impossible():
    report = build_report(_fake_result())
    loose = evaluate_slo(LOOSE_SLO, report)
    impossible = evaluate_slo(IMPOSSIBLE_SLO, report)
    assert loose["passed"] is True
    assert impossible["passed"] is False
    failed = {c["rule"] for c in impossible["checks"] if not c["passed"]}
    assert "ttft_p99" in failed and "error_rate" in failed


def test_slo_report_counts_errors_not_latency_samples():
    """Errored requests appear in error_rate and the errors map, never in
    the latency populations."""
    report = build_report(_fake_result(n_ok=10, n_err=5))
    assert report["num_errors"] == 5
    assert report["errors"] == {"PoisonRequestError": 5}
    assert report["error_rate"] == pytest.approx(5 / 15)
    assert report["sample_counts"]["ttft_s"] == 10
    assert report["sample_counts"]["tpot_s"] == 10
    # A tight error-rate bound fails on the same report a latency-only
    # spec passes: errors gate independently of latency.
    latency_only = SLOSpec.from_bounds("lat", ttft_p99=1.0)
    errors_too = SLOSpec.from_bounds("err", ttft_p99=1.0, error_rate=0.1)
    assert evaluate_slo(latency_only, report)["passed"] is True
    assert evaluate_slo(errors_too, report)["passed"] is False


def test_report_splits_sheds_from_failures():
    """Overload sheds (any *OverloadedError class, including the
    TaskError(EngineOverloadedError) dynamic name an actor-crossing shed
    arrives as) are counted apart from real failures, with their own
    rejection-latency percentiles; error_rate stays the union for
    back-compat with recorded trajectories."""
    result = _fake_result(n_ok=10, n_err=1)  # one real failure (poison)
    for i, (cls, lat) in enumerate(
        [
            ("TaskError(EngineOverloadedError)", 0.002),
            ("EngineOverloadedError", 0.004),
            ("FleetOverloadedError", 0.006),
        ]
    ):
        result.samples.append(
            RequestSample(
                request_id=f"shed-{i}", kind="normal", scenario="longtail",
                session_id=None, scheduled_s=1.0, sent_s=1.0,
                error=cls, error_latency_s=lat,
            )
        )
    report = build_report(result)
    assert report["num_shed"] == 3
    assert report["num_failures"] == 1
    assert report["num_errors"] == 4  # the union, unchanged
    assert report["shed_rate"] == pytest.approx(3 / 14)
    assert report["failure_rate"] == pytest.approx(1 / 14)
    assert report["error_rate"] == pytest.approx(4 / 14)
    # Rejection latency percentiles come from error_latency_s (e2e_s is
    # deliberately unset on errors so it can't carry the number).
    assert report["shed_latency_s"]["p50"] == pytest.approx(0.004)
    assert report["shed_latency_s"]["p99"] <= 0.006
    # Sheds never become latency samples for the accepted populations.
    assert report["sample_counts"]["ttft_s"] == 10
    line = format_report(report)
    assert "shed=3" in line and "failed=1" in line


def test_slo_no_samples_fails_not_passes():
    """An SLO cannot be demonstrated by a run that produced no samples."""
    empty = LoadRunResult(
        samples=[], offered_duration_s=0.0, wall_duration_s=0.0,
        offered_rate=0.0,
    )
    verdict = evaluate_slo(
        SLOSpec.from_bounds("x", ttft_p99=10.0), build_report(empty)
    )
    assert verdict["passed"] is False


def test_slo_spec_parsing_and_validation():
    spec = SLOSpec.from_bounds(
        "svc", ttft_p99=0.5, tpot_p50=0.01, error_rate=0.05
    )
    assert {r.label for r in spec.rules} == {"ttft_p99", "tpot_p50"}
    assert spec.max_error_rate == 0.05
    # p100 is a legal bound (SLORule accepts (0, 100]).
    assert SLOSpec.from_bounds("max", e2e_p100=60.0).rules[0].percentile == 100.0
    with pytest.raises(ValueError, match="unknown SLO bound"):
        SLOSpec.from_bounds("bad", queue_p99=1.0)
    with pytest.raises(ValueError, match="max_seconds"):
        SLOSpec.from_bounds("bad", ttft_p99=0.0)


# ---------------- serve-path smoke + chaos ----------------


@pytest.fixture
def loadgen_ray():
    runtime = ray_tpu.init(num_cpus=8)
    yield runtime
    from ray_tpu import serve

    serve.shutdown()
    ray_tpu.shutdown()


def test_loadgen_smoke_real_serve_path(loadgen_ray):
    """Acceptance smoke: an open-loop seeded run drives the real
    router → LLMIngress replica → engine-actor path, produces latency
    percentiles that agree with the engine's own llm_request_* histograms
    within one bucket, passes the loose SLO while failing the impossible
    one IN THE SAME RUN, and leaves the KV pool drained."""
    from ray_tpu.loadgen.sweep import run_cell

    cell = run_cell("base", {}, False, rate=8.0, num_requests=20, seed=0)
    if not cell["cross_check"]["agreed"]:
        # The cross-check exists to catch systematic disagreement (a broken
        # clock or sample population), which reproduces on a fresh run. A
        # one-off scheduler hiccup on a loaded single-core box can push a
        # single tail quantile past the one-bucket tolerance; retry once so
        # only reproducible disagreement fails the gate.
        cell = run_cell("base", {}, False, rate=8.0, num_requests=20, seed=0)
    report = cell["report"]
    assert report["requests"] == 20
    assert report["completed"] > 0
    assert report["sample_counts"]["ttft_s"] > 0
    assert report["percentiles"]["ttft_s"]["p99"] is not None
    # Mixed scenario includes poisons: they must land as errors.
    assert report["num_errors"] >= 1
    assert "PoisonRequestError" in report["errors"]
    assert cell["slo"]["loose"]["passed"] is True
    assert cell["slo"]["impossible"]["passed"] is False
    assert cell["cross_check"]["agreed"] is True
    for q in ("p50", "p99"):
        assert cell["cross_check"]["ttft_s"][q]["agree"]
    assert cell["engine"]["kv_pool_allocated"] == 0
    assert cell["engine"]["dead_letters"] == report["num_errors"]


@pytest.mark.chaos
def test_poison_scenario_dead_letters_only_poisons(loadgen_ray):
    """Chaos: in a longtail+poison mix, the engine dead-letters exactly
    the poisoned requests — every non-poison completes, and the SLO
    report counts poisons as errors, not latency samples."""
    from ray_tpu import serve
    from ray_tpu.llm.serve import build_app
    from ray_tpu.loadgen.driver import run_open_loop

    ecfg = EngineConfig(block_size=8, num_blocks=96, max_blocks_per_seq=8)
    spec = ScenarioSpec.for_engine(
        ecfg.max_model_len, ecfg.buckets()[-1], 128,
        name="mixed", num_requests=14, seed=11,
        mix=(("longtail", 0.5), ("poison", 0.5)),
    )
    requests = generate_requests(spec)
    n_poison = sum(1 for r in requests if r.kind == "poison")
    assert 0 < n_poison < len(requests)
    handle = serve.run(
        build_app(TINY, ecfg, engine_name="lg-poison"), name="lgpoison"
    )
    offsets = arrival_times(ArrivalSpec(rate=10.0, seed=11), len(requests))
    result = run_open_loop(handle, requests, offsets, timeout_s=30.0)
    report = build_report(result)
    assert report["errors"] == {"PoisonRequestError": n_poison}
    assert report["completed"] == len(requests) - n_poison
    assert report["sample_counts"]["tpot_s"] <= report["completed"]
    by_id = {s.request_id: s for s in result.samples}
    for req in requests:
        if req.kind == "poison":
            assert by_id[req.request_id].error == "PoisonRequestError"
            assert by_id[req.request_id].e2e_s is None
        else:
            assert by_id[req.request_id].error is None
    stats = handle.options(method_name="metrics").remote().result(
        timeout_s=30.0
    )
    assert stats["num_dead_letters"] == n_poison
    assert stats["kv_pool_allocated"] == 0


# ---------------- mid-stream disconnect abort path ----------------


def test_stream_close_aborts_engine_request_direct():
    """Regression (satellite): closing a token_stream consumer before
    exhaustion must propagate an abort — N disconnected streams leave the
    KV pool at boot size, without the engine generating the rest of
    max_new_tokens for nobody."""
    ecfg = EngineConfig(
        block_size=8, num_blocks=64, max_decode_slots=4, max_blocks_per_seq=8
    )
    server = in_process(LLMServer(TINY, ecfg, warmup=False))
    engine = server._engine
    assert engine.allocator.num_allocated == 0  # boot size
    for i in range(5):
        gen = server.generate_stream(
            [1 + i, 2, 3, 4, 5, 6, 7], max_new_tokens=40
        )
        assert next(gen) is not None
        assert next(gen) is not None
        gen.close()  # GeneratorExit at the yield → abort in the finally
        deadline = time.monotonic() + 5.0
        while engine.scheduler.has_work() and time.monotonic() < deadline:
            time.sleep(0.01)
        assert engine.allocator.num_allocated == 0
    # 5 x 40 = 200 tokens were nominally on order; the aborts must have
    # cut nearly all of them.
    assert engine.stats()["decode_tokens"] < 60
    server.shutdown()


def test_stream_close_releases_draft_mirror_blocks():
    """Same abort path with speculation=draft: the proposer's mirror pool
    must drain with the target pool."""
    draft_cfg = GPTConfig(
        vocab_size=128, num_layers=1, num_heads=2, embed_dim=32,
        max_seq_len=128, dtype=jnp.float32, attention_impl="reference",
    )
    ecfg = EngineConfig(
        block_size=8, num_blocks=64, max_decode_slots=4,
        max_blocks_per_seq=8, speculation="draft",
        draft_model_config=draft_cfg,
    )
    server = in_process(LLMServer(TINY, ecfg, warmup=False))
    engine = server._engine
    for i in range(3):
        gen = server.generate_stream([1 + i, 2, 3, 4, 5], max_new_tokens=30)
        next(gen)
        next(gen)
        gen.close()
        deadline = time.monotonic() + 5.0
        while engine.scheduler.has_work() and time.monotonic() < deadline:
            time.sleep(0.01)
    assert engine.allocator.num_allocated == 0
    assert engine._spec.allocator.num_allocated == 0
    assert engine.stats()["spec_draft_pool_allocated"] == 0
    server.shutdown()


@pytest.mark.chaos
def test_serve_path_disconnects_leave_pool_at_boot(loadgen_ray):
    """The full client-disconnect path: handle stream → cancel →
    replica token_stream closed → engine abort. After N disconnected
    streams the KV pool is back at boot size and the engine did NOT run
    the disconnected generations to completion."""
    from ray_tpu import serve
    from ray_tpu.llm.serve import build_app

    ecfg = EngineConfig(
        block_size=8, num_blocks=64, max_decode_slots=4, max_blocks_per_seq=8
    )
    handle = serve.run(
        build_app(TINY, ecfg, engine_name="lg-disc"), name="lgdisc"
    )
    metrics = handle.options(method_name="metrics")
    assert metrics.remote().result(timeout_s=60.0)["kv_pool_allocated"] == 0
    n_streams, max_new = 6, 40
    for i in range(n_streams):
        gen = handle.options(stream=True).remote(
            {
                "prompt_ids": [1 + i, 2, 3, 4, 5, 6, 7],
                "max_new_tokens": max_new,
                "stream": True,
            }
        )
        it = iter(gen)
        assert "token_id" in next(it)
        assert "token_id" in next(it)
        gen.cancel()  # what the proxy does on client disconnect
    deadline = time.monotonic() + 15.0
    while time.monotonic() < deadline:
        stats = metrics.remote().result(timeout_s=30.0)
        if stats["num_running"] == 0 and stats["queue_depth"] == 0:
            break
        time.sleep(0.1)
    assert stats["kv_pool_allocated"] == 0
    # Abandoned work was cut short: without the abort these streams would
    # decode ~n_streams * max_new tokens.
    assert stats["decode_tokens"] < n_streams * max_new // 2


# ---------------- CLI report round trip ----------------


def test_loadgen_cli_report_roundtrip(tmp_path, capsys):
    from ray_tpu.loadgen.sweep import main

    record = {
        "record": "BENCH_SERVE_test",
        "cells": [
            {
                "config": "base",
                "rate": 4.0,
                "cpu_parity_only": False,
                "report": build_report(_fake_result()),
                "slo": {
                    "loose": evaluate_slo(
                        LOOSE_SLO, build_report(_fake_result())
                    )
                },
            }
        ],
        "gate_problems": [],
    }
    path = tmp_path / "rec.json"
    import json

    path.write_text(json.dumps(record))
    assert main(["report", str(path)]) == 0
    out = capsys.readouterr().out
    assert "base @ 4/s" in out
    assert "SLO loose: PASS" in out


# ---------------- driver: scheduled events + token recording ----------------


class _StubStreamHandle:
    """Handle-shaped stub: every request streams three fixed token dicts.
    Lets the event/token-recording plumbing be tested without a serve
    stack."""

    def __init__(self):
        self.resume_fns = []

    def options(self, **opts):
        self.resume_fns.append(opts.get("stream_resume_fn"))
        return self

    def remote(self, request):
        return iter(
            {"token_id": t} for t in (7, 8, 9)
        )


def test_run_open_loop_events_resume_fn_and_token_recording():
    """ScheduledEvents fire at their offsets with outcomes recorded on the
    result (an event exception is data, not a run failure); the
    stream_resume_fn threads through to every dispatch; record_tokens
    captures the exact delivered ids per sample."""
    from ray_tpu.loadgen import ScheduledEvent, run_open_loop
    from ray_tpu.llm.serve import llm_stream_resume

    spec = ScenarioSpec(
        name="repetitive", num_requests=3, seed=0, max_new_tokens=4
    )
    requests = generate_requests(spec)
    offsets = [0.0, 0.02, 0.04]
    fired = []

    def boom():
        raise RuntimeError("chaos hook failed")

    events = [
        ScheduledEvent(offset_s=0.01, name="ok", fn=lambda: fired.append(1)),
        ScheduledEvent(offset_s=0.03, name="boom", fn=boom),
    ]
    handle = _StubStreamHandle()
    result = run_open_loop(
        handle,
        requests,
        offsets,
        timeout_s=5.0,
        settle_timeout_s=10.0,
        events=events,
        stream_resume_fn=llm_stream_resume,
        record_tokens=True,
    )
    assert fired == [1]
    ok, boom_ev = result.events
    assert ok.fired_s is not None and ok.error is None
    assert boom_ev.fired_s is not None
    assert "chaos hook failed" in boom_ev.error
    # Events ride the serialized result.
    d = result.to_dict()
    assert [e["name"] for e in d["events"]] == ["ok", "boom"]
    # Every dispatch carried the resume fn; every sample captured tokens.
    assert handle.resume_fns == [llm_stream_resume] * 3
    for s in result.samples:
        assert s.token_ids == [7, 8, 9]
        assert s.num_tokens == 3
    # Without record_tokens the field stays None (no memory cost).
    result2 = run_open_loop(
        _StubStreamHandle(), requests, offsets, timeout_s=5.0,
        settle_timeout_s=10.0,
    )
    assert all(s.token_ids is None for s in result2.samples)
