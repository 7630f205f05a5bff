"""Runner for `laguna` configurations (poolside Laguna) served by
`ray_tpu.llm` behind Serve: `runners/serve.py`'s deployment, window and
rules, with this family's model configuration, seeded parameters and plain
reference (`lib/reference_laguna.py`), and `runners/serve_hybrid.py`'s table
of device seconds by part of a layer.

The model is imported first thing, so that a checkout of the program which
lacks it fails at once, before a device or a deployment exists.
"""

from __future__ import annotations

import gc
import json
import os
import random
import time

from ray_tpu.models import laguna  # noqa: F401  (fails fast on a parent without it)

from lib import device, traffic
from lib.reference_laguna import LagunaServingReference, sizes
from runners.serve import (
    LAG_WARNING_MS,
    REFERENCE_SAMPLE,
    Deployment,
    measure,
    misses_of_a_warm_run,
    within_limits,
)
from runners.serve_hybrid import _pooled, scope_table

# The engine's counters of the window, as the `laguna` line carries them.
WINDOW_COUNTERS = (
    "decode_dispatches", "decode_tokens", "prefill_tokens",
    "prefill_chunk_dispatches", "decode_context_tokens", "decode_window_tokens",
    "held_tokens_full", "held_tokens_window", "window_blocks_freed",
    "prefill_window_pairs", "decode_expert_assignments",
    "decode_expert_assignments_absent", "decode_experts_touched",
    "decode_expert_load_max", "prefill_expert_assignments", "num_preemptions",
    "prefix_cache_hit_tokens",
)


TOTALS = (
    "steps", "decode_dispatches", "decode_tokens", "prefill_tokens",
    "prefill_chunk_dispatches", "step_wait_s", "step_prepare_s",
    "step_commit_s", "step_schedule_s", "step_between_s", "queue_depth",
    "chained_decode_dispatches", "pipeline_flushes", "host_exposed_total_s",
)


def model_config(fields: dict):
    import jax.numpy as jnp

    fields = dict(fields)
    for key in ("dtype", "param_dtype"):
        fields[key] = getattr(jnp, fields[key])
    return laguna.LagunaConfig(**fields)


def make_params(cfg, seed: int):
    """Weights on the device from the seed by the program's own init, leaf
    by leaf in bfloat16 (a float32 tree does not fit)."""
    return laguna.init_params(cfg, seed)


def completed_in_window(ctx, tag: str) -> list:
    """The load generator's records of the requests whose last token came
    inside the window, whenever they were due. `lib/serving_metrics.py`
    counts a request to the window it was due in, which fits answers that
    take a fraction of it; here an answer is a thousand decode steps and a
    caller's next request queues behind 47 others, so no request is both due
    and complete inside 45 s, while some two dozen that were due before it
    complete there. Those are what the window served, and what is checked."""
    with open(os.path.join(ctx.out_dir, tag, "requests.json")) as f:
        log = json.load(f)
    return [
        r for r in log["records"]
        if r["phase"] == "run" and r["status"] == "ok" and r["token_times"]
        and log["open"] <= r["token_times"][-1] <= log["close"]
    ]


def check_outputs(ctx, cfg, fields: dict, params, schedule: dict, complete: list,
                  limits: dict) -> dict:
    """As `runners/serve.check_outputs`: the longest completed request and
    seven drawn from the seed, teacher-forced through the float32 reference
    once the deployment is gone (`params` None: the weights of
    `--reference-seed`, made here). A traced or `--control` run also reads, on
    the longest request, the picks of the reference with a window 16 longer,
    with bfloat16 scores and with a float16 gate (no limit of their own:
    they say what the comparison would notice)."""
    tolerance = limits["logit_tolerance"]
    prompts = {r["id"]: r["prompt_ids"] for r in schedule["requests"]}
    ordered = sorted(complete, key=lambda r: r["id"])
    longest = max(ordered, key=lambda r: len(prompts[r["id"]]) + len(r["token_ids"]),
                  default=None)
    others = [r for r in ordered if r is not longest]
    chosen = ([] if longest is None else [longest]) + random.Random(
        repr(("sample", ctx.seed))
    ).sample(others, min(REFERENCE_SAMPLE - 1, len(others)))
    if params is None:
        params = make_params(cfg, ctx.reference_seed)
    t0 = time.monotonic()
    block = 16 if ctx.rehearse else 2048
    reference = LagunaServingReference(
        sizes(fields), params, pad_to=block, query_block=block
    )
    verdicts = {
        r["id"]: reference.judge(
            prompts[r["id"]], r["token_ids"], tolerance,
            noise=(ctx.trace or ctx.control) and i == 0,
        )
        for i, r in enumerate(chosen)
    }
    out = {
        "checked": len(verdicts), **_pooled(list(verdicts.values())),
        "logit_tolerance": tolerance,
        "mean_gap_limit": limits["mean_gap_limit"],
        "longest_context": max((v.get("context", 0) for v in verdicts.values()), default=0),
        "verdicts": verdicts,
    }
    out["ok"] = within_limits(out, limits)
    if ctx.control:
        out["control"] = _pooled(
            [reference.control_gaps(prompts[r["id"]], r["token_ids"]) for r in chosen]
        )
        out["control"]["ok"] = within_limits(out["control"], limits)
        if longest is not None:
            altered = list(longest["token_ids"])
            altered[len(altered) // 2] = (altered[len(altered) // 2] + 1) % cfg.vocab_size
            judged = reference.judge(prompts[longest["id"]], altered, tolerance)
            out["altered_token"] = _pooled(
                [judged] + [v for i, v in verdicts.items() if i != longest["id"]]
            )
            out["altered_token"]["ok"] = within_limits(out["altered_token"], limits)
    out["reference_s"] = time.monotonic() - t0
    return out


def run(ctx) -> dict:
    from ray_tpu.llm.config import EngineConfig

    config = ctx.config
    sized = config["rehearsal"] if ctx.rehearse else config
    cfg = model_config(sized["model"])
    engine_fields = dict(sized["engine"])
    engine_fields["prefill_buckets"] = tuple(engine_fields["prefill_buckets"])
    ecfg = EngineConfig(**engine_fields, tensor_parallel_size=ctx.chips)
    vocab = cfg.vocab_size  # the published vocabulary, whole
    mix = ctx.traffic
    if ctx.rehearse:
        real = config["engine"]["block_size"] * config["engine"]["max_blocks_per_seq"]
        mix = traffic.scaled(mix, ecfg.max_model_len / real)
        mix["clients"] = min(mix["clients"], 2 * ecfg.max_decode_slots)
        # The toy model answers in microseconds: queues long enough that
        # no caller runs dry before the window closes.
        mix["requests_per_client"] *= 64

    params = make_params(cfg, ctx.seed)
    entries_before = device.cache_entries()
    t0, cpu0, compile0 = time.monotonic(), time.process_time(), ctx.compiles.seconds
    deployment = Deployment(cfg, ecfg, params, config.get("serve", {}))
    try:
        boot = deployment.boot
        rounds = deployment.call("flight_record", 0)["compile_events"]
        ctx.emit(
            "deployed",
            warmup_s=time.monotonic() - t0,
            warmup_cpu_s=time.process_time() - cpu0,
            warmup_backend_compile_s=ctx.compiles.seconds - compile0,
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
            cache_classes=boot["cache_classes"],
            attention_shape=boot["attention_shape"],
            model_params=boot["model_params"],
            weight_bytes=boot["weight_bytes"],
            prefill_token_budget=boot["prefill_token_budget"],
            prefix_caching=boot["prefix_caching"],
            memory_after_warmup=device.memory_stats(ctx.chips),
        )
        if not ctx.rehearse and boot["attn_impl"] != "pallas":
            raise RuntimeError(f"engine resolved attn_impl {boot['attn_impl']!r}")
        if ctx.sweep:
            # The closed loop under one caller count after another (other
            # token ids at each), to see where the lanes fill.
            for i, value in enumerate(ctx.sweep):
                swept = {**mix, "clients": int(value)}
                collected, _, _ = measure(
                    ctx, deployment, swept, vocab, f"sweep-{value}", seed=ctx.seed + i
                )
                window = collected["engine_window"]
                ctx.emit(
                    "sweep", value=value, **collected["client"],
                    queue_depth_at_close=collected["engine_after"]["queue_depth"],
                    running_at_close=collected["engine_after"]["num_running"],
                    preemptions=window["num_preemptions"],
                    compiles_in_window=collected["compiles_in_window"],
                    prefill_tokens=window["prefill_tokens"],
                    decode_tokens=window["decode_tokens"],
                    decode_dispatches=window["decode_dispatches"],
                    mean_occupancy=window["decode_tokens"]
                    / max(window["decode_dispatches"] * ecfg.max_decode_slots, 1),
                    held_tokens_full=window["held_tokens_full"],
                    held_tokens_window=window["held_tokens_window"],
                    memory_peak_bytes=collected["memory_peak_bytes"],
                )
            return {"sweep": True}

        tag = f"seed{ctx.seed}-trace{int(ctx.trace)}"
        collected, schedule, _ = measure(ctx, deployment, mix, vocab, tag)
        complete = completed_in_window(ctx, tag)
        if ctx.trace:
            collected["device_report"] = deployment.call("device_report", timeout=900.0)
            report = dict(collected["device_report"])
            scopes = report.pop("op_scopes", {})
            ctx.emit("device_report", **report,
                     op_scopes_named={k: len(v) for k, v in scopes.items()})
            ctx.emit("scope_seconds", **scope_table(collected))
        dead = deployment.call("dead_letters")
    finally:
        deployment.close()
    # The engine's pools go with the deployment; the weights stay for the
    # reference, which runs last.
    del deployment
    gc.collect()
    if ctx.reference_seed != ctx.seed:
        # Two trees of 9.7 GB do not fit a chip: the served one goes before
        # `check_outputs` makes the other seed's. Its buffers are deleted,
        # not just let go: the closed deployment's actors and the task
        # records that carried the tree to them still refer to it (chip
        # run, PR 35, call G: 176 MB free after dropping the name).
        import jax

        for leaf in jax.tree_util.tree_leaves(params):
            leaf.delete()
        params = None
    verdict = check_outputs(
        ctx, cfg, sized["model"], params, schedule, complete, config["correctness"],
    )

    client = collected["client"]
    # Complete by the close, whenever due (completed_in_window), or failed.
    client["completed_in_window"] = len(complete)
    client["attempted"] = len(complete) + client["failed"]
    ctx.emit("client", **client)
    ctx.emit("reference", **verdict)
    window = collected["engine_window"]
    ctx.emit(
        "laguna", **{key: window.get(key) for key in WINDOW_COUNTERS},
        cache_classes_at_close=collected["engine_after"].get("cache_classes"),
        running_at_open=collected["engine_before"].get("num_running"),
        running_at_close=collected["engine_after"].get("num_running"),
        # Since the engine started, warm-up's few steps included: what the
        # window's differences are taken from.
        totals_at_open={k: collected["engine_before"].get(k) for k in TOTALS},
        totals_at_close={k: collected["engine_after"].get(k) for k in TOTALS},
        pipeline_flushes_by_cause=collected["engine_after"].get("pipeline_flushes_by_cause"),
    )
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
