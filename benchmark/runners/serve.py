"""Runner for configurations served by `ray_tpu.llm` behind Serve.

The process that owns the chip deploys the engine the way a user does,
`serve.run(llm.serve.build_app(...))` plus the HTTP proxy, and starts the
load generator (`lib/loadgen.py`) as a child that never imports JAX and talks
HTTP to the proxy. Set-up is everything up to the window's opening: device,
weights, the engine's own warm-up of the cell's programs, the schedule's
priming requests and the lead-in.
"""

from __future__ import annotations

import gc
import json
import os
import random
import subprocess
import sys
import time

from lib import device, serving_metrics, traffic, xplane
from lib.reference import ServingReference
from lib.stats import histogram_window

APP = "bench"
TRACE_SECONDS = 5.0
REFERENCE_SAMPLE = 8  # the longest completed request and seven drawn from the seed
LAG_WARNING_MS = 20.0
HISTOGRAMS = (
    "llm_request_ttft_seconds",
    "llm_request_queue_time_seconds",
    "llm_request_time_per_output_token_seconds",
)


def model_config(fields: dict):
    import jax.numpy as jnp

    from ray_tpu.models.gpt import GPTConfig

    fields = dict(fields)
    fields["dtype"] = getattr(jnp, fields["dtype"])
    return GPTConfig(**fields)


def make_params(cfg, seed: int):
    """Weights on the device in one jitted call from the seed, in the types
    the normal path holds them in (float32 parameters, `cfg.dtype` compute)."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.gpt import GPT

    probe = jnp.zeros((1, 16), jnp.int32)
    return jax.jit(GPT(cfg).init)(jax.random.PRNGKey(seed), probe)


class Deployment:
    """One engine behind Serve and the proxy, as a user deploys it."""

    def __init__(self, cfg, ecfg, params, serve_options: dict):
        import ray_tpu
        from ray_tpu import serve
        from ray_tpu.llm.serve import build_app
        from ray_tpu.serve._private.http_proxy import start_proxy

        ray_tpu.init()
        serve.run(
            build_app(cfg, ecfg, params=params, engine_name=APP, **serve_options),
            name=APP,
            _blocking_timeout_s=1100.0,
        )
        self._ray = ray_tpu
        self.ecfg = ecfg
        self.engine = ray_tpu.get_actor(f"llm_engine:{APP}")
        # The ingress reports healthy while the engine still warms its
        # programs; its first answer marks the end of warm-up (PR 21).
        self.boot = self.call("metrics", timeout=1100.0)
        host, port = start_proxy("127.0.0.1", 0, 600.0)
        self.url = f"http://{host}:{port}/{APP}"

    def call(self, method: str, *args, timeout: float = 120.0):
        return self._ray.get(getattr(self.engine, method).remote(*args), timeout=timeout)

    def close(self) -> None:
        from ray_tpu import serve

        serve.shutdown()
        try:
            self.call("shutdown", timeout=60.0)
            self._ray.kill(self.engine)
        finally:
            self._ray.shutdown()


def _sleep_until(when: float) -> None:
    delay = when - time.monotonic()
    if delay > 0:
        time.sleep(delay)


def measure(ctx, deployment: Deployment, mix: dict, vocab: int, tag: str,
            seed: int = None):
    """One lead-in and window of `mix` against the deployment, its token ids
    drawn from `seed` (the run's own where none is given). Returns what
    the per-layer readers dig into (the client's log reduced, the engine's
    counters and histograms over the window and, with `ctx.trace`, the
    reduced device trace of its middle seconds, `lib/xplane.TracedWindow`), the schedule, and the
    records of the requests that completed inside the window."""
    ecfg = deployment.ecfg
    seed = ctx.seed if seed is None else seed
    schedule = traffic.generate(mix, seed, ctx.seconds, vocab, ecfg.max_model_len)
    out = os.path.join(ctx.out_dir, tag)
    os.makedirs(out, exist_ok=True)
    schedule_path = os.path.join(out, "schedule.json")
    log_path = os.path.join(out, "requests.json")
    with open(schedule_path, "w") as f:
        json.dump(schedule, f)
    ctx.emit("schedule", tag=tag, **traffic.summary(schedule))

    child = subprocess.Popen(
        [
            sys.executable, os.path.join(ctx.bench_dir, "lib", "loadgen.py"),
            "--schedule", schedule_path, "--url", deployment.url,
            "--log", log_path, "--run", f"{tag}-{seed}",
        ],
        stdout=subprocess.PIPE, text=True,
    )
    try:
        clock = json.loads(child.stdout.readline() or "{}")
        if clock.get("event") != "clock":
            raise RuntimeError(f"load generator gave no clock line: {clock}")
        _sleep_until(clock["open"])
        before = deployment.call("observability_snapshot", 0)
        compiles_before = ctx.compiles.count
        compile_step_s = ctx.compiles.seconds
        cache_hits, cache_misses = ctx.compiles.cache_hits, ctx.compiles.cache_misses
        gc_before = [g["collections"] for g in gc.get_stats()]
        traced = None
        if ctx.trace:
            length = min(TRACE_SECONDS, ctx.seconds / 3.0)
            _sleep_until(clock["open"] + (ctx.seconds - length) / 2.0)
            window = xplane.TracedWindow(os.path.join(out, "trace"))
            window.open()
            time.sleep(length)
            traced = (window.trace_dir, window.close(), window.profiled_s)
        _sleep_until(clock["close"])
        after = deployment.call("observability_snapshot", 0)
        compiles_in_window = ctx.compiles.count - compiles_before
        # The engine's step thread shares this interpreter: collections by
        # generation inside the window. Not `gc.get_objects()`: walking the
        # heap beside a stepping engine dead-lettered a request (PERF.md 7.13l).
        client_gc = {
            "gc_collections_in_window": [
                g["collections"] - b for g, b in zip(gc.get_stats(), gc_before)
            ],
        }
        # Before the reference runs on this chip: a process's peak never falls.
        memory_peak = device.memory_peak_bytes(ctx.chips)
        child.wait(timeout=120.0)
    finally:
        if child.poll() is None:
            child.kill()
            child.wait()
    if child.returncode != 0:
        raise RuntimeError(f"load generator exited with {child.returncode}")
    with open(log_path) as f:
        log = json.load(f)

    client = {**serving_metrics.reduce_log(log), **client_gc}
    complete = client.pop("complete")
    engine_window = {
        key: after["metrics"][key] - value
        for key, value in before["metrics"].items()
        if isinstance(value, (int, float)) and not isinstance(value, bool)
        and isinstance(after["metrics"].get(key), (int, float))
    }
    # Which phase of the step a stall fell in: the step clock's totals.
    client["step_phases_in_window_s"] = {
        key: round(value, 3) for key, value in engine_window.items()
        if key.startswith("step_") and key.endswith("_s")
    }
    collected = {
        "window_open": clock["open"],
        # Seconds in XLA's compile step (compilations cold, reads of cached
        # executables warm), programs read from the cache and programs
        # compiled and written to it, from process start to the window's opening: the harness's own
        # count of JAX's events, not the program's.
        "compile_step_s": compile_step_s,
        "cache_hits": cache_hits,
        "cache_misses": cache_misses,
        "memory_peak_bytes": memory_peak,
        "client": client,
        "engine_before": before["metrics"],
        "engine_after": after["metrics"],
        "engine_window": engine_window,
        "histograms": {
            name: histogram_window(before["histograms"][name], after["histograms"][name])
            for name in HISTOGRAMS
        },
        "engine_config": {"max_decode_slots": ecfg.max_decode_slots},
        # JAX's own count, and the flight recorder's of warm-up rounds.
        "compiles_in_window": compiles_in_window
        + len(after["flight_record"]["compile_events"])
        - len(before["flight_record"]["compile_events"]),
        "trace": None,
    }
    if traced is not None:
        collected["trace"] = xplane.reduce_trace(*traced, keep=ctx.keep_trace)
    return collected, schedule, complete


def check_outputs(ctx, cfg, params, schedule: dict, complete: list,
                  limits: dict) -> dict:
    """The longest completed request and a seeded sample of the others,
    teacher-forced against the float32 reference once the window has closed
    and the deployment is gone. `worst_gap` is the widest gap by which a
    served token's logit lies below the reference's best, `mean_gap` the mean
    of that gap over the sample's tokens (nought where the served token is
    the reference's own). A traced run also prints how far bfloat16 alone
    moves a logit; `--control` puts the int8 control's gaps on the same
    prompts and tokens, and the sample's with one served token altered,
    through the same comparison."""
    tolerance = limits["logit_tolerance"]
    prompts = {r["id"]: r["prompt_ids"] for r in schedule["requests"]}
    ordered = sorted(complete, key=lambda r: r["id"])
    longest = max(ordered, key=lambda r: len(prompts[r["id"]]) + len(r["token_ids"]),
                  default=None)
    others = [r for r in ordered if r is not longest]
    chosen = ([] if longest is None else [longest]) + random.Random(
        repr(("sample", ctx.seed))
    ).sample(others, min(REFERENCE_SAMPLE - 1, len(others)))
    if ctx.reference_seed != ctx.seed:
        params = make_params(cfg, ctx.reference_seed)
    t0 = time.monotonic()
    reference = ServingReference(cfg, params, cfg.max_seq_len)
    verdicts = {
        r["id"]: reference.judge(
            prompts[r["id"]], r["token_ids"], tolerance, noise=ctx.trace and i == 0
        )
        for i, r in enumerate(chosen)
    }

    def pooled(readings) -> dict:
        """Tokens, widest and mean gap over `readings`; no gap where one of
        them has none (no finite logits) or nothing completed."""
        tokens = sum(v.get("tokens", 0) for v in readings)
        if not tokens or any("gap_sum" not in v for v in readings):
            return {"tokens": tokens, "worst_gap": None, "mean_gap": None}
        return {
            "tokens": tokens,
            "worst_gap": max(v["worst_gap"] for v in readings),
            "mean_gap": sum(v["gap_sum"] for v in readings) / tokens,
        }

    out = {
        "checked": len(verdicts), **pooled(list(verdicts.values())),
        "logit_tolerance": tolerance,
        "mean_gap_limit": limits["mean_gap_limit"],
        "reference_s": time.monotonic() - t0,
        "verdicts": verdicts,
    }
    out["ok"] = within_limits(out, limits)
    if ctx.control:
        # The control and a planted fault through the same comparison: each
        # has to come out not correct (PERF.md section 2).
        out["control"] = pooled(
            [reference.control_gaps(prompts[r["id"]], r["token_ids"]) for r in chosen]
        )
        out["control"]["ok"] = within_limits(out["control"], limits)
        if longest is not None:
            altered = list(longest["token_ids"])
            altered[len(altered) // 2] = (altered[len(altered) // 2] + 1) % cfg.vocab_size
            judged = reference.judge(prompts[longest["id"]], altered, tolerance)
            out["altered_token"] = pooled(
                [judged] + [v for i, v in verdicts.items() if i != longest["id"]]
            )
            out["altered_token"]["ok"] = within_limits(out["altered_token"], limits)
    return out


def within_limits(gaps: dict, limits: dict) -> bool:
    """The comparison that decides `correct`, the same for the served tokens,
    for the control and for a planted fault: both gaps read, the widest under
    the configuration's tolerance and the mean under its limit."""
    return (
        gaps["mean_gap"] is not None
        and gaps["worst_gap"] < limits["logit_tolerance"]
        and gaps["mean_gap"] < limits["mean_gap_limit"]
    )


def misses_of_a_warm_run(cache_hits: int, cache_misses: int) -> int:
    """`cache_misses` in a warm run, 0 in a cold one. A run is warm when it
    read more programs from the cache than it compiled and wrote: what the run
    itself observes, whatever other cells or configurations left in the
    checkout's cache before it."""
    return cache_misses if cache_hits > cache_misses else 0


def run(ctx) -> dict:
    from ray_tpu.llm.config import EngineConfig

    config = ctx.config
    sized = config["rehearsal"] if ctx.rehearse else config
    cfg = model_config(sized["model"])
    engine_fields = dict(sized["engine"])
    engine_fields["prefill_buckets"] = tuple(engine_fields["prefill_buckets"])
    ecfg = EngineConfig(**engine_fields, tensor_parallel_size=ctx.chips)
    # Prompts draw from the published vocabulary, not from the padding rows.
    vocab = cfg.vocab_size if ctx.rehearse else config["published"]["vocab_size"]
    mix = ctx.traffic
    if ctx.rehearse:
        real = config["engine"]["block_size"] * config["engine"]["max_blocks_per_seq"]
        mix = traffic.scaled(mix, ecfg.max_model_len / real)

    params = make_params(cfg, ctx.seed)
    entries_before = device.cache_entries()
    t0, cpu0, compile0 = time.monotonic(), time.process_time(), ctx.compiles.seconds
    deployment = Deployment(cfg, ecfg, params, config.get("serve", {}))
    try:
        boot = deployment.boot
        rounds = deployment.call("flight_record", 0)["compile_events"]
        # Warm-up is most of set-up and took 112-117 s in some processes and
        # 128-135 s in others (PR 22): its rounds, the process's CPU time and
        # the time inside XLA's compile step (cache reads) say where.
        ctx.emit(
            "deployed",
            warmup_s=time.monotonic() - t0,
            warmup_cpu_s=time.process_time() - cpu0,
            warmup_backend_compile_s=ctx.compiles.seconds - compile0,
            # program, bucket, the round's seconds, of which tracing and
            # lowering, of which the compile step (the cache's read, warm)
            warmup_rounds_s=[
                [r["program"], r["bucket"], r["compile_s"], r.get("trace_lower_s"),
                 r.get("compile_step_s")]
                for r in rounds
            ],
            attn_impl=boot["attn_impl"],
            programs_warmed=len(rounds),
            compiles_so_far=ctx.compiles.count,
            cache_entries_before=entries_before,
            cache_entries_after=device.cache_entries(),
            kv_pool_bytes=boot["kv_pool_bytes"],
            model_params=boot["model_params"],
            prefill_token_budget=boot["prefill_token_budget"],
        )
        if not ctx.rehearse and boot["attn_impl"] != "pallas":
            raise RuntimeError(f"engine resolved attn_impl {boot['attn_impl']!r}")
        if ctx.sweep:
            # Finding the knee: the same deployment under one rate (open
            # loop) or client count (closed loop) after another.
            for i, value in enumerate(ctx.sweep):
                swept = dict(mix)
                if mix["loop"] == "open":
                    swept["arrivals"] = {**mix["arrivals"], "rate_per_s": value}
                else:
                    swept["clients"] = int(value)
                # Other token ids at every rate: the prompts of the last rate
                # would otherwise all sit in the prefix cache.
                collected, _, _ = measure(
                    ctx, deployment, swept, vocab, f"sweep-{value}", seed=ctx.seed + i
                )
                ctx.emit(
                    "sweep", value=value, **collected["client"],
                    queue_depth_at_close=collected["engine_after"]["queue_depth"],
                    running_at_close=collected["engine_after"]["num_running"],
                    preemptions=collected["engine_window"]["num_preemptions"],
                    compiles_in_window=collected["compiles_in_window"],
                    prefix_hit_tokens=collected["engine_window"]["prefix_cache_hit_tokens"],
                    prefill_tokens=collected["engine_window"]["prefill_tokens"],
                    memory_peak_bytes=collected["memory_peak_bytes"],
                )
            return {"sweep": True}

        collected, schedule, complete = measure(
            ctx, deployment, mix, vocab, f"seed{ctx.seed}-trace{int(ctx.trace)}"
        )
        if ctx.trace:
            # Compiles the decode program again (a cache hit, but traced anew),
            # so only where the per-layer metrics are wanted.
            collected["device_report"] = deployment.call("device_report", timeout=900.0)
            ctx.emit("device_report", **collected["device_report"])
        dead = deployment.call("dead_letters")
    finally:
        deployment.close()
    # The reference runs last: the window has closed, the peak has been read
    # and the engine with its cache is gone.
    verdict = check_outputs(
        ctx, cfg, params, schedule, complete, config["correctness"],
    )

    client = collected["client"]
    ctx.emit("client", **client)
    ctx.emit("reference", **verdict)
    must_fail = {k: verdict[k] for k in ("control", "altered_token") if k in verdict}
    if client["generator_lag_p99_ms"] is not None and client["generator_lag_p99_ms"] > LAG_WARNING_MS:
        ctx.emit("warning", what="generator lag p99 over 20 ms",
                 generator_lag_p99_ms=client["generator_lag_p99_ms"])
    problems = []
    if collected["compiles_in_window"]:
        problems.append(f"{collected['compiles_in_window']} compilations inside the window")
    if client["callers_that_ran_dry"]:
        problems.append(f"callers ran out of requests: {client['callers_that_ran_dry']}")
    if dead or collected["engine_after"]["wedged"]:
        problems.append(f"dead letters {dead}")
    # Only a run that finds no cache may compile: one that read most of its
    # programs and still wrote one warmed a shape the first run did not.
    warm_misses = 0 if ctx.rehearse else misses_of_a_warm_run(
        collected["cache_hits"], collected["cache_misses"]
    )
    if warm_misses:
        problems.append(f"{warm_misses} programs compiled in a warm run")
    compared = {
        "worst_logit_gap": [verdict["worst_gap"], verdict["logit_tolerance"]],
        "mean_logit_gap": [verdict["mean_gap"], verdict["mean_gap_limit"]],
        "compiles_in_window": [collected["compiles_in_window"], 0],
        "callers_ran_dry": [len(client["callers_that_ran_dry"]), 0],
        "dead_letters": [len(dead), 0],
        "warm_cache_misses": [warm_misses, 0],
    }
    return {
        "correct": verdict["ok"] and not problems,
        "problems": problems,
        "passed_that_must_fail": sorted(k for k, v in must_fail.items() if v["ok"]),
        "compared": compared,
        # `setup_s` leaves out the compile step, whose cache reads take 3-4 s
        # in some processes and 17-20 s in others (PERF.md section 2).
        "setup_excluded_s": collected["compile_step_s"],
        "attempted": client["attempted"],
        "failed": client["failed"],
        "window_open": collected["window_open"],
        "end_to_end": {
            "ttft_p90_ms": client["ttft_p90_ms"],
            "itl_p50_ms": client["itl_p50_ms"],
            "completed_tokens_per_s": client["completed_tokens_per_s"],
        },
        "collected": collected,
    }
