"""Runner for `granitemoehybrid` configurations served by `ray_tpu.llm`
behind Serve: `runners/serve.py`'s deployment, window and rules, with this
family's model configuration, seeded parameters and plain reference.

The model is imported first thing, so that a checkout of the program which
lacks it fails at once, before a device or a deployment exists.
"""

from __future__ import annotations

import gc
import random
import time

from ray_tpu.models import granite_hybrid  # noqa: F401  (fails fast on a parent without it)

from lib import device, traffic
from lib.reference_granite_hybrid import HybridServingReference, sizes
from runners.serve import (
    LAG_WARNING_MS,
    REFERENCE_SAMPLE,
    Deployment,
    measure,
    misses_of_a_warm_run,
    within_limits,
)


def model_config(fields: dict):
    import jax.numpy as jnp

    fields = dict(fields)
    for key in ("dtype", "param_dtype"):
        fields[key] = getattr(jnp, fields[key])
    for key in ("layer_types", "experts_held"):
        fields[key] = tuple(fields[key])
    return granite_hybrid.GraniteHybridConfig(**fields)


def make_params(cfg, seed: int):
    """Weights on the device from the seed by the program's own init, leaf
    by leaf in bfloat16 (a float32 tree does not fit)."""
    return granite_hybrid.init_params(cfg, seed)


def _pooled(readings) -> dict:
    """Tokens, widest and mean gap over `readings`; no gap where one of them
    has none (no finite logits) or nothing completed."""
    tokens = sum(v.get("tokens", 0) for v in readings)
    if not tokens or any("gap_sum" not in v for v in readings):
        return {"tokens": tokens, "worst_gap": None, "mean_gap": None}
    return {
        "tokens": tokens,
        "worst_gap": max(v["worst_gap"] for v in readings),
        "mean_gap": sum(v["gap_sum"] for v in readings) / tokens,
    }


def check_outputs(ctx, cfg, fields: dict, params, schedule: dict, complete: list,
                  limits: dict) -> dict:
    """As `runners/serve.check_outputs`: the longest completed request and
    seven drawn from the seed, teacher-forced through the float32 reference
    once the deployment is gone. A traced or `--control` run also reads the
    longest request with the reference's recurrent state rounded to bfloat16
    at every position (no limit: it says whether the comparison would notice
    a state kept one precision down)."""
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
    reference = HybridServingReference(
        sizes(fields), params, pad_to=16 if ctx.rehearse else 1024
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


def scope_table(collected: dict) -> dict:
    """The traced seconds of each program by the part of a layer its
    operations were traced under (`device_report()["op_scopes"]`), what is
    under none as `other`: where the cell's device time goes."""
    scopes = collected["device_report"].get("op_scopes", {})
    table: dict = {}
    ops = []
    for name, seconds in (collected["trace"] or {"op_seconds": {}})["op_seconds"].items():
        module, _, op = name.partition("/")
        scope = scopes.get(module, {}).get(op.split(" ")[0], "other")
        row = table.setdefault(module, {})
        row[scope] = row.get(scope, 0.0) + seconds
        ops.append([name, scope, seconds])
    return {
        "by_program": {
            module: dict(sorted(row.items(), key=lambda kv: -kv[1]))
            for module, row in table.items()
        },
        "largest_ops": sorted(ops, key=lambda o: -o[2])[:48],
    }


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
        mix["clients"] = min(mix["clients"], 3 * ecfg.max_decode_slots)
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
            state_pool_bytes=boot["state_pool_bytes"],
            state_slots=boot["state_slots"],
            model_params=boot["model_params"],
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
                    memory_peak_bytes=collected["memory_peak_bytes"],
                )
            return {"sweep": True}

        collected, schedule, complete = measure(
            ctx, deployment, mix, vocab, f"seed{ctx.seed}-trace{int(ctx.trace)}"
        )
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
    verdict = check_outputs(
        ctx, cfg, sized["model"], params, schedule, complete, config["correctness"],
    )

    client = collected["client"]
    ctx.emit("client", **client)
    ctx.emit("reference", **verdict)
    window = collected["engine_window"]
    ctx.emit("hybrid", **{
        key: window.get(key) for key in (
            "decode_dispatches", "decode_tokens", "prefill_tokens",
            "prefill_chunk_dispatches", "decode_state_bytes",
            "decode_expert_assignments", "decode_expert_assignments_absent",
            "decode_experts_touched", "decode_expert_load_max",
            "prefill_expert_assignments", "prefill_scan_tokens",
            "state_slot_resets", "num_preemptions", "prefix_cache_hit_tokens",
        )
    })
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
