"""End-to-end serving observability: request lifecycle traces, TTFT/TPOT
latency histograms, the engine flight recorder, and the dashboard LLM
panel.

Acceptance (ISSUE 4): a single streamed request produces ONE connected
trace — ingress → replica → queue/prefill/decode phases, with
preempt-resume and an injected failover retry as child/sibling spans —
retrievable via tracing.traces(); the TTFT and time-per-output-token
histograms appear in the dashboard /metrics with counts matching requests
served.
"""

import json
import re
import time
import urllib.request

import pytest

import jax.numpy as jnp
import numpy as np

import ray_tpu
from ray_tpu._private import fault_injection as fi
from ray_tpu.exceptions import ActorDiedError, ReplicaUnavailableRetryExhausted
from ray_tpu.llm import EngineConfig, LLMEngine, LLMServer
from ray_tpu.models.gpt import GPT, GPTConfig
from ray_tpu.util import metrics, tracing
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

# Small pool: a handful of concurrent sequences overflow it, forcing
# recompute-style preemption (same shape as the test_llm preemption tests).
ECFG_PRESSURE = EngineConfig(
    block_size=4, num_blocks=10, max_decode_slots=4, max_blocks_per_seq=8
)

# Serve-path engines pay init-time warmup; two buckets keep it fast.
ECFG_SERVE = EngineConfig(
    block_size=4,
    num_blocks=12,
    max_decode_slots=4,
    max_blocks_per_seq=8,
    prefill_buckets=(8, 32),
)


def reference_greedy(model, params, prompt, n_tokens, pad_to=64):
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


@pytest.fixture(autouse=True)
def _no_leftover_faults():
    fi.clear()
    yield
    fi.clear()


def _span_index(rows):
    by_name = {}
    for r in rows:
        by_name.setdefault(r["name"], []).append(r)
    return by_name


# ---------------- engine-level tracing ----------------


def test_engine_request_trace_connected_with_preempt_resume():
    """Every request yields a connected trace under the ambient span:
    llm.request root, one llm.queue per admission wait, one llm.prefill per
    (re-)prefill, decode stretches, and llm.preempt markers — a preempted
    request shows the full preempt → queue → partial-prefill → decode
    resume cycle."""
    eng = LLMEngine(TINY, ECFG_PRESSURE, seed=0)
    prompts = random_prompts((6, 7, 5, 6), seed=1)
    with tracing.span("driver-batch") as root:
        eng.generate(prompts, max_new_tokens=12)
    assert eng.stats()["num_preemptions"] > 0
    rows = tracing.traces(trace_id=root.trace_id)
    by_name = _span_index(rows)
    reqs = by_name["llm.request"]
    assert len(reqs) == len(prompts)
    # Roots hang off the ambient driver span; every phase span hangs off
    # its request root; nothing dangles.
    assert all(r["parent_span_id"] == root.span_id for r in reqs)
    span_ids = {r["span_id"] for r in rows}
    for r in rows:
        assert r["parent_span_id"] is None or r["parent_span_id"] in span_ids
    n_preempts = len(by_name.get("llm.preempt", ()))
    assert n_preempts == eng.stats()["num_preemptions"]
    # One queue wait per admission (initial + every resume). Chunked
    # prefill may split one admission over several llm.prefill spans, but
    # exactly ONE of them per admission is final (produces the token).
    assert len(by_name["llm.queue"]) == len(prompts) + n_preempts
    finals = [
        s for s in by_name["llm.prefill"] if s["attributes"]["final"]
    ]
    assert len(finals) == len(prompts) + n_preempts
    assert len(by_name["llm.prefill"]) >= len(finals)
    # Resume prefills hit the victim's still-cached blocks (partial kind).
    kinds = {s["attributes"]["kind"] for s in by_name["llm.prefill"]}
    assert "full" in kinds and "partial" in kinds
    # Decode stretches carry token counts; a preempted request has > 1.
    preempted_roots = [
        r for r in reqs if r["attributes"]["preemptions"] > 0
    ]
    assert preempted_roots
    for req in preempted_roots:
        stretches = [
            s
            for s in by_name["llm.decode"]
            if s["parent_span_id"] == req["span_id"]
        ]
        assert len(stretches) >= 2
    # All requests closed cleanly.
    assert all(r["attributes"]["status"] == "ok" for r in reqs)
    assert all(r["attributes"]["finish_reason"] == "length" for r in reqs)
    assert all(r["attributes"]["ttft_s"] > 0 for r in reqs)


def test_dead_lettered_request_closes_span_with_error():
    """Poison isolation (PR 3) closes the culprit's request span with error
    status + the step exception, and records the failure in the flight
    recorder with action=dead_letter."""
    fi.inject(
        "llm.prefill",
        match="poison-me",
        exc_factory=lambda: RuntimeError("cosmic ray in prefill"),
    )
    server = in_process(LLMServer(TINY, ECFG_PRESSURE, seed=0, warmup=False))
    with tracing.span("poison-root") as root:
        with pytest.raises(Exception):
            server.generate(
                random_prompts((6,), seed=2)[0],
                max_new_tokens=4,
                request_id="poison-me",
                timeout_s=60.0,
            )
    rows = tracing.traces(trace_id=root.trace_id)
    req = next(r for r in rows if r["name"] == "llm.request")
    assert req["attributes"]["status"] == "error"
    assert req["attributes"]["finish_reason"] == "error"
    assert "cosmic ray" in req["attributes"]["error"]
    failures = server.flight_record()["failures"]
    assert failures and failures[-1]["action"] == "dead_letter"
    assert failures[-1]["request_id"] == "poison-me"
    server.shutdown()


def test_wedged_engine_closes_inflight_traces_with_error():
    """A wedged engine (K consecutive unattributable step failures) must
    close every in-flight request's root span with error status — not
    strand already-emitted phase spans under a root that never gets
    written, during the very incident the trace explains."""
    ecfg = EngineConfig(
        block_size=8, num_blocks=64, max_decode_slots=4,
        max_blocks_per_seq=8, max_consecutive_step_failures=2,
    )
    # Steps 1-2 succeed (the request prefillls and decodes), then every
    # step fails unattributably: step 3 retries, step 4 wedges.
    fi.inject("llm.step", nth=3, times=None, message="engine meltdown")
    server = in_process(LLMServer(TINY, ecfg, seed=0, warmup=False))
    with tracing.span("wedge-root") as root:
        with pytest.raises(Exception):
            server.generate(
                random_prompts((6,), seed=6)[0],
                max_new_tokens=16,
                timeout_s=60.0,
            )
    assert server.metrics()["wedged"] is True
    rows = tracing.traces(trace_id=root.trace_id)
    req = next(r for r in rows if r["name"] == "llm.request")
    assert req["attributes"]["status"] == "error"
    assert "meltdown" in req["attributes"]["error"]
    span_ids = {r["span_id"] for r in rows}
    for r in rows:
        assert r["parent_span_id"] is None or r["parent_span_id"] in span_ids


def test_instrument_off_compiles_out_spans_and_histograms():
    ecfg = EngineConfig(
        block_size=8, num_blocks=64, max_decode_slots=4,
        max_blocks_per_seq=8, instrument=False,
    )
    eng = LLMEngine(TINY, ecfg, seed=0)
    with tracing.span("uninstrumented") as root:
        eng.generate(random_prompts((6,), seed=3), max_new_tokens=4)
    rows = tracing.traces(trace_id=root.trace_id)
    assert not any(r["name"].startswith("llm.") for r in rows)
    assert eng.flight_recorder.snapshot()["steps"] == []
    text = metrics.prometheus_text()
    assert "llm_request_ttft_seconds_count" not in text
    # The coarse engine counters still export.
    assert "llm_engine_generated_tokens" in text


# ---------------- latency histograms ----------------


def test_request_latency_histogram_counts_match_requests_served():
    eng = LLMEngine(TINY, ECFG_PRESSURE, seed=0)
    prompts = random_prompts((6, 7, 5), seed=4)
    eng.generate(prompts, max_new_tokens=6)
    engine_tag = eng.stats()["engine_id"]
    text = metrics.prometheus_text()

    def count_of(name):
        m = re.search(
            rf'{name}_count{{engine="{engine_tag}"}} (\d+)', text
        )
        assert m, f"{name} missing from exposition"
        return int(m.group(1))

    assert count_of("llm_request_ttft_seconds") == len(prompts)
    assert count_of("llm_request_e2e_seconds") == len(prompts)
    # Multi-token requests all report a time-per-output-token sample.
    assert count_of("llm_request_time_per_output_token_seconds") == len(
        prompts
    )
    # One queue sample per admission (>= one per request; preemption adds).
    assert count_of("llm_request_queue_time_seconds") >= len(prompts)
    # Step histogram carries per-phase series with cumulative le buckets,
    # tagged with the resolved paged-attention implementation so the
    # dashboards can attribute kernel speedups per phase. Full prefill
    # never dispatches on the knob, so its series is tagged "n/a".
    impl = eng.stats()["attn_impl"]
    assert re.search(
        rf'llm_engine_step_seconds_bucket{{attn_impl="{impl}",'
        rf'chunk="n/a",engine="{engine_tag}",le="\+Inf",'
        rf'phase="decode"}} \d+',
        text,
    )
    assert re.search(
        rf'llm_engine_step_seconds_count{{attn_impl="n/a",'
        rf'chunk="final",engine="{engine_tag}",phase="prefill"}} \d+',
        text,
    )


# ---------------- flight recorder ----------------


def test_flight_recorder_step_records_and_warmup_compile_events():
    server = in_process(LLMServer(TINY, ECFG_SERVE, seed=0, warmup=True))
    record = server.flight_record()
    # Warmup charged each program/bucket with its cold-compile seconds.
    # Under the default chunked-prefill budget only the chunk-reachable
    # widths exist (ECFG_SERVE: budget 8 of max_model_len 32 → width 8;
    # the 32 bucket can never dispatch, so warming it would be waste),
    # and every (width × program) pair gets a chunk_prefill blame entry.
    widths = ECFG_SERVE.chunk_widths()
    assert widths == (8,)
    programs = {(c["program"], c["bucket"]) for c in record["compile_events"]}
    assert ("prefill", 8) in programs
    assert ("prefill", 32) not in programs  # unreachable under the budget
    assert any(p == "partial_prefill" for p, _ in programs)
    assert any(p == "cow" for p, _ in programs)
    for w in widths:
        assert ("chunk_prefill", w) in programs
    assert all(c["compile_s"] > 0 for c in record["compile_events"])

    # Zero cold compiles during a chunked serve: warmup already compiled
    # every program the chunked path can dispatch, so serving a prompt
    # that chunks (9 tokens under a budget of 8) adds no jit cache entry.
    runner = server._engine.runner
    jit_fns = (
        runner._prefill_fn, runner._prefill_suffix_fn, runner._decode_fn,
        runner._copy_block_fn,
    )
    cache_sizes = [f._cache_size() for f in jit_fns]
    out = server.generate(
        random_prompts((9,), seed=5)[0], max_new_tokens=4, timeout_s=60.0
    )
    assert len(out["token_ids"]) == 4
    assert [f._cache_size() for f in jit_fns] == cache_sizes
    steps = server.flight_record(steps_limit=8)["steps"]
    assert 0 < len(steps) <= 8
    prefill_steps = [s for s in steps if s["num_prefills"]]
    assert prefill_steps, steps
    # The 9-token prompt streamed in as an 8-token chunk plus a 1-token
    # final chunk, each within the budget, each in the width-8 bucket.
    chunks = [p for s in prefill_steps for p in s["prefills"]]
    assert [c["tokens"] for c in chunks] == [8, 1]
    assert [c["final"] for c in chunks] == [False, True]
    assert all(c["bucket"] == 8 for c in chunks)
    for s in prefill_steps:
        assert s["phase"].startswith("prefill")
        assert s["tokens_in"] <= s["prefill_budget"]
        assert s["duration_s"] > 0
    decode_steps = [s for s in steps if "decode" in s["phase"]]
    assert decode_steps and all(s["batch_size"] >= 1 for s in decode_steps)
    # The ring is bounded by config; a 0 limit means zero records.
    assert len(server.flight_record()["steps"]) <= (
        ECFG_SERVE.flight_recorder_capacity
    )
    assert server.flight_record(steps_limit=0)["steps"] == []
    # Warmup generations are not requests: no latency samples, no spans.
    engine_tag = server.metrics()["engine_id"]
    text = metrics.prometheus_text()
    m = re.search(
        rf'llm_request_ttft_seconds_count{{engine="{engine_tag}"}} (\d+)',
        text,
    )
    assert m and int(m.group(1)) == 1  # just the one real request above
    server.shutdown()


# ---------------- serve path: the acceptance trace ----------------


@pytest.fixture
def serve_ray():
    runtime = ray_tpu.init(
        num_cpus=8,
        _system_config={"include_dashboard": True, "dashboard_port": 0},
    )
    yield runtime
    from ray_tpu import serve

    serve.shutdown()
    ray_tpu.shutdown()


def _wait_engine_idle(engine_name, timeout=60.0):
    handle = ray_tpu.get_actor(f"llm_engine:{engine_name}")
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if ray_tpu.get(handle.num_pending.remote()) == 0:
            return handle
        time.sleep(0.05)
    raise TimeoutError("engine never drained")


def test_streamed_request_yields_one_connected_trace(serve_ray):
    """ISSUE 4 acceptance: one streamed request through the Serve path —
    preempted and resumed under cache pressure, killed mid-stream and
    failed over to a retry dispatch — produces ONE connected trace:
    client span → replica stream → llm.request with queue/prefill/decode/
    preempt children, the failover retry as a sibling span under the
    client, and the resumed llm.request beneath it."""
    from ray_tpu import serve
    from ray_tpu.llm.serve import build_app, llm_stream_resume

    handle = serve.run(
        build_app(TINY, ECFG_SERVE, engine_name="obs", num_replicas=2),
        name="llmobs",
    )
    prompt = random_prompts((7,), seed=7)[0]
    n_new = 12
    want = reference_greedy(
        GPT(TINY), LLMEngine(TINY, ECFG_SERVE, seed=0).runner.params,
        prompt, n_new,
    )
    engine = ray_tpu.get_actor("llm_engine:obs")
    # Cache pressure: background generations keep the 11-block pool
    # oversubscribed, so the traced stream (youngest arrival) gets
    # preempted and resumed at least once. Each bg sequence grows to 8
    # blocks (its max_blocks_per_seq cap), so a 3-request wave holds 24
    # blocks against the 11-block pool while it lives.
    #
    # Two races have made this the tier-1 flake historically, both closed
    # by construction below rather than by tuning token counts:
    #  * the FIRST metrics poll can return seconds late (it queues behind
    #    cold compiles / a loaded box), by which time the wave already
    #    drained — the loop then RESUBMITS a wave on observing an idle
    #    engine; once polls are warm (~ms cadence) a fresh 3 x 24-token
    #    wave is observed for dozens of polls before it can drain;
    #  * pressure can be observed at the wave's TAIL and drain before the
    #    traced stream is admitted — so after observing it we TOP UP with
    #    one more wave, queued behind the live one, spanning the traced
    #    stream's admission with ≥ 24 further decode steps of pressure.
    bg_prompts = random_prompts((6, 6, 5), seed=8)
    bg = [engine.generate.remote(p, 24) for p in bg_prompts]
    # The traced stream must be the YOUNGEST arrival (the scheduler preempts
    # youngest-first), so wait until the background load is in the engine.
    deadline = time.monotonic() + 30
    while time.monotonic() < deadline:
        stats = ray_tpu.get(engine.metrics.remote())
        if stats["num_running"] + stats["queue_depth"] >= 3:
            break
        if stats["num_running"] + stats["queue_depth"] == 0:
            bg += [engine.generate.remote(p, 24) for p in bg_prompts]
        time.sleep(0.02)
    else:
        raise AssertionError("background pressure never observed")
    # Top-up wave: still older than the traced stream (submitted next),
    # still pressure when the live wave drains.
    bg += [engine.generate.remote(p, 24) for p in bg_prompts]
    # Replica dies after delivering 4 tokens: the router re-dispatches with
    # the delivered tokens folded into the prompt (llm_stream_resume).
    spec = fi.inject(
        "replica.stream_item",
        nth=5,
        exc_factory=lambda: ActorDiedError(None, "injected mid-stream kill"),
    )
    with tracing.span("client") as root:
        stream = handle.options(
            stream=True, stream_resume_fn=llm_stream_resume
        ).remote(
            {"prompt_ids": prompt, "max_new_tokens": n_new, "stream": True}
        )
        tokens = [d["token_id"] for d in stream]
    assert spec.fires == 1
    assert tokens == want  # contiguous + token-identical through failover
    for ref in bg:
        ray_tpu.get(ref)
    # The original (orphaned) engine request may still be draining; its
    # spans close when it finishes.
    _wait_engine_idle("obs")

    rows = tracing.traces(trace_id=root.trace_id)
    by_name = _span_index(rows)
    span_ids = {r["span_id"] for r in rows}
    # Connected: every span in the trace parents onto another trace span
    # (the client root is the only parentless one).
    orphans = [
        r["name"]
        for r in rows
        if r["parent_span_id"] is not None
        and r["parent_span_id"] not in span_ids
    ]
    assert orphans == [], orphans
    # Ingress → replica: the replica-side stream spans and their task spans.
    assert len(by_name["serve.replica.stream"]) == 2  # original + resumed
    # The failover retry rides the SAME trace as a sibling under the
    # client span, and the re-dispatched replica task nests beneath it.
    (retry,) = by_name["serve.retry"]
    assert retry["parent_span_id"] == root.span_id
    assert retry["attributes"]["attempt"] == 1
    retry_children = [
        r for r in rows if r["parent_span_id"] == retry["span_id"]
    ]
    assert retry_children, "re-dispatched task did not nest under the retry"
    # Two llm.request roots: the orphaned original and the resumed tail.
    reqs = by_name["llm.request"]
    assert len(reqs) == 2
    assert all(r["attributes"]["status"] == "ok" for r in reqs)
    resumed = max(reqs, key=lambda r: r["attributes"]["prompt_tokens"])
    assert resumed["attributes"]["prompt_tokens"] == len(prompt) + 4
    # The orphaned original no longer drains to completion: the dying
    # replica's token_stream closed before exhaustion, which propagates
    # an engine abort (the mid-stream disconnect path), so its root span
    # records an aborted finish instead of running out max_new_tokens.
    orphan = min(reqs, key=lambda r: r["attributes"]["prompt_tokens"])
    assert orphan["attributes"]["finish_reason"] == "aborted"
    # Queue → prefill → decode phases present for each request root.
    for req in reqs:
        children = {
            r["name"] for r in rows if r["parent_span_id"] == req["span_id"]
        }
        assert {"llm.queue", "llm.prefill", "llm.decode"} <= children
    # The traced request was preempted and resumed inside the trace.
    assert by_name.get("llm.preempt"), "no preemption in the traced request"
    preempted = [r for r in reqs if r["attributes"]["preemptions"] > 0]
    assert preempted, [r["attributes"] for r in reqs]


def test_router_failover_metrics_counters(serve_ray):
    """PR 3 shipped failover with no metrics: retries, exclusions, stream
    resumes, and budget exhaustion now export as deployment-tagged
    counters."""
    from ray_tpu import serve

    @serve.deployment(num_replicas=2)
    def echo(x):
        return x

    handle = serve.run(echo.bind(), name="obs-failover")
    assert handle.remote(1).result(timeout_s=30) == 1

    fi.inject(
        "replica.handle_request",
        match="echo",
        exc_factory=lambda: ActorDiedError(None, "injected death"),
    )
    assert handle.remote(2).result(timeout_s=30) == 2
    text = metrics.prometheus_text()
    assert 'serve_router_retry_dispatches{deployment="echo"} 1.0' in text
    assert 'serve_router_excluded_replicas{deployment="echo"} 1.0' in text

    fi.clear()
    fi.inject(
        "actor.submit",
        match="ReplicaActor.handle_request",
        times=None,
        exc_factory=lambda: ActorDiedError(None, "injected submit failure"),
    )
    tuned = handle.options(retry_budget=1, backoff_initial_s=0.01)
    with pytest.raises(ReplicaUnavailableRetryExhausted):
        tuned.remote(3)
    text = metrics.prometheus_text()
    assert 'serve_router_retry_exhausted{deployment="echo"} 1.0' in text


def test_stream_resume_counter_increments(serve_ray):
    from ray_tpu import serve
    from ray_tpu.llm.serve import build_app, llm_stream_resume

    handle = serve.run(
        build_app(TINY, ECFG_SERVE, engine_name="obs-resume", num_replicas=2),
        name="llmobsresume",
    )
    prompt = random_prompts((5,), seed=9)[0]
    fi.inject(
        "replica.stream_item",
        nth=3,
        exc_factory=lambda: ActorDiedError(None, "kill for resume count"),
    )
    stream = handle.options(
        stream=True, stream_resume_fn=llm_stream_resume
    ).remote({"prompt_ids": prompt, "max_new_tokens": 6, "stream": True})
    assert len(list(stream)) == 6
    text = metrics.prometheus_text()
    assert (
        'serve_router_stream_resumes{deployment="LLMIngress"} 1.0' in text
    )


def test_streamed_tokens_travel_inline_and_the_engine_counts_them(serve_ray):
    """A streamed generation's tokens are small items of two streaming
    generators (engine actor -> replica -> caller), so every one travels
    with its ref: LLMServer copies the runtime's two counts into `metrics()`
    and `observability_snapshot()["metrics"]`, beside `wedged`, where the
    benchmark's `stream_inline_share` reads them."""
    from ray_tpu import serve
    from ray_tpu.llm.serve import build_app

    handle = serve.run(
        build_app(TINY, ECFG_SERVE, engine_name="obs-inline", num_replicas=1),
        name="llmobsinline",
    )
    engine = ray_tpu.get_actor("llm_engine:obs-inline")
    before = ray_tpu.get(engine.metrics.remote())
    prompt = random_prompts((5,), seed=11)[0]
    stream = handle.options(stream=True).remote(
        {"prompt_ids": prompt, "max_new_tokens": 6, "stream": True}
    )
    assert len(list(stream)) == 6
    stats = ray_tpu.get(engine.metrics.remote())
    snapshot = ray_tpu.get(engine.observability_snapshot.remote())["metrics"]
    for view in (stats, snapshot):
        assert view["wedged"] is False
        assert view["stream_items_inline"] == view["stream_items_reported"] > 0
    # Six tokens, two hops each.
    assert (
        stats["stream_items_reported"] - before["stream_items_reported"] >= 12
    )
    assert stats["stream_items_inline"] == serve_ray.stream_items_inline
    assert serve_ray.stream_items_promoted == 0


# ---------------- dashboard ----------------


def _get_json(url):
    with urllib.request.urlopen(url, timeout=10) as resp:
        return json.loads(resp.read().decode())


def test_dashboard_llm_panel_and_metrics_scrape(serve_ray):
    """/api/llm renders engine stats + flight recorder + dead letters per
    named engine; /metrics serves the request histograms with counts
    matching requests served and refreshes LLM gauges at scrape time."""
    from ray_tpu import serve
    from ray_tpu.llm.serve import build_app

    runtime = serve_ray
    base = runtime.dashboard.url
    handle = serve.run(
        build_app(TINY, ECFG_SERVE, engine_name="dash", num_replicas=1),
        name="llmdash",
    )
    prompts = random_prompts((5, 9), seed=10)
    for p in prompts:
        res = handle.remote({"prompt_ids": p, "max_new_tokens": 4})
        assert len(res.result(timeout_s=60)["token_ids"]) == 4

    rows = _get_json(f"{base}/api/llm?steps=16")
    row = next(r for r in rows if r["name"] == "llm_engine:dash")
    assert "error" not in row, row
    assert row["metrics"]["decode_tokens"] > 0
    assert row["metrics"]["wedged"] is False
    assert row["dead_letters"] == []
    assert row["flight_record"]["compile_events"]
    assert 0 < len(row["flight_record"]["steps"]) <= 16
    engine_tag = row["metrics"]["engine_id"]

    with urllib.request.urlopen(f"{base}/metrics", timeout=10) as resp:
        text = resp.read().decode()
    # Request-latency histograms: counts match the requests served exactly
    # (warmup generations are NOT requests — instrumentation is suppressed
    # during warmup so compile stalls can't masquerade as latency samples).
    m = re.search(
        rf'llm_request_ttft_seconds_count{{engine="{engine_tag}"}} (\d+)',
        text,
    )
    assert m and int(m.group(1)) == len(prompts)
    m = re.search(
        rf'llm_request_time_per_output_token_seconds_count'
        rf'{{engine="{engine_tag}"}} (\d+)',
        text,
    )
    assert m and int(m.group(1)) == len(prompts)
    # Scrape-time freshness: the idle engine's gauges and dead-letter count
    # were just re-sampled head-side.
    assert f'llm_engine_dead_letters{{engine="{engine_tag}"}} 0.0' in text
    assert f'llm_engine_wedged{{engine="{engine_tag}"}} 0.0' in text
    assert re.search(
        rf'llm_engine_queue_depth{{engine="{engine_tag}"}} 0\.0', text
    )
    # The panel survives in the HTML page too.
    with urllib.request.urlopen(base, timeout=10) as resp:
        page = resp.read().decode()
    assert "LLM engines" in page
