"""Runner for `solar_open2` configurations (Upstage Solar-Open2) served by
`ray_tpu.llm` behind Serve: `runners/serve.py`'s deployment, window and
rules, with this family's model configuration, seeded parameters and plain
reference (`lib/reference_solar_open2.py`), `runners/serve_hybrid.py`'s table
of device seconds by part of a layer, `runners/serve_olmo_hybrid.py`'s list
of what no scope names and `runners/serve_laguna.py`'s rule for which
requests a window served. What `runners/serve_falcon_h1.py` is to
`runners/serve_olmo_hybrid.py`: the same run with another model's names, and
the routing's counters on its line.

The model is imported first thing, so that a checkout of the program which
lacks it fails at once, before a device or a deployment exists.
"""

from __future__ import annotations

import gc
import random
import time

from ray_tpu.models import solar_open2  # noqa: F401  (fails fast on a parent without it)

from lib import device, traffic
from lib.reference_solar_open2 import CONTROLS, MUST_FAIL, SolarOpen2ServingReference, sizes
from runners.serve import (
    LAG_WARNING_MS,
    REFERENCE_SAMPLE,
    Deployment,
    measure,
    misses_of_a_warm_run,
    within_limits,
)
from runners.serve_hybrid import _pooled, scope_table
from runners.serve_laguna import TOTALS, completed_in_window
from runners.serve_olmo_hybrid import WINDOW_COUNTERS, unscoped_ops

# The routing's counters of the window, beside the recurrent layers'.
ROUTING_COUNTERS = (
    "decode_expert_assignments", "decode_expert_assignments_absent",
    "decode_experts_touched", "decode_expert_load_max",
    "prefill_expert_assignments", "prefill_expert_rows_walked",
)


def model_config(fields: dict):
    import jax.numpy as jnp

    fields = dict(fields)
    for key in ("dtype", "param_dtype"):
        fields[key] = getattr(jnp, fields[key])
    return solar_open2.SolarOpen2Config(**fields)


def make_params(cfg, seed: int):
    """Weights on the device from the seed by the program's own init, leaf
    by leaf in bfloat16 (a float32 tree does not fit)."""
    return solar_open2.init_params(cfg, seed)


def check_outputs(ctx, cfg, fields: dict, params, schedule: dict, complete: list,
                  limits: dict) -> dict:
    """As `runners/serve.check_outputs`: the longest completed request and
    seven drawn from the seed, teacher-forced through the float32 reference
    once the deployment is gone (`params` None: the weights of
    `--reference-seed`, made here). A `--control` run also puts every control
    of `lib/reference_solar_open2.CONTROLS` and the sample with one served
    token altered through the same comparison."""
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
    reference = SolarOpen2ServingReference(
        sizes(fields), params, pad_to=16 if ctx.rehearse else 2048
    )
    verdicts = {
        r["id"]: reference.judge(prompts[r["id"]], r["token_ids"], tolerance)
        for r in chosen
    }
    out = {
        "checked": len(verdicts), **_pooled(list(verdicts.values())),
        "logit_tolerance": tolerance,
        "mean_gap_limit": limits["mean_gap_limit"],
        "longest_context": max(
            (len(prompts[r["id"]]) + len(r["token_ids"]) for r in chosen), default=0
        ),
        "verdicts": verdicts,
    }
    out["ok"] = within_limits(out, limits)
    if ctx.control:
        readings = [
            reference.control_gaps(prompts[r["id"]], r["token_ids"], tuple(CONTROLS))
            for r in chosen
        ]
        out["controls"] = {}
        for name in CONTROLS:
            pooled = _pooled([reading[name] for reading in readings])
            pooled["logit_move"] = max((r[name]["logit_move"] for r in readings), default=None)
            pooled["ok"] = within_limits(pooled, limits)
            out["controls"][name] = pooled
        if longest is not None:
            altered = list(longest["token_ids"])
            altered[len(altered) // 2] = (altered[len(altered) // 2] + 1) % cfg.vocab_size
            judged = reference.judge(prompts[longest["id"]], altered, tolerance)
            pooled = _pooled(
                [judged] + [v for i, v in verdicts.items() if i != longest["id"]]
            )
            pooled["ok"] = within_limits(pooled, limits)
            out["controls"]["altered_token"] = pooled
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
    vocab = cfg.vocab_size  # the slice of the vocabulary this chip holds
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
            state_pool_bytes=boot["state_pool_bytes"],
            state_slots=boot["state_slots"],
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
                    decode_context_tokens=window["decode_context_tokens"],
                    mean_occupancy=window["decode_tokens"]
                    / max(window["decode_dispatches"] * ecfg.max_decode_slots, 1),
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
            # What no scope of the model names (norms on the sub-layers'
            # outputs, residual sums, the embedding, K/V scatters, sampling).
            ctx.emit("unscoped_seconds", largest_ops=unscoped_ops(collected)[:24])
        dead = deployment.call("dead_letters")
    finally:
        deployment.close()
    # The engine's pools go with the deployment; the weights stay for the
    # reference, which runs last.
    del deployment
    gc.collect()
    if ctx.reference_seed != ctx.seed:
        # Two trees of 6.6 GB and the reference's float32 layer do not fit a chip: the served one goes before
        # `check_outputs` makes the other seed's, its buffers deleted and not
        # just let go (`runners/serve_laguna.py` says why).
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
    controls = verdict.pop("controls", {})
    ctx.emit("reference", **verdict)
    for name, reading in controls.items():
        ctx.emit("control", control=name, correct=reading["ok"],
                 must_fail=name in MUST_FAIL or name == "altered_token", **reading)
    window = collected["engine_window"]
    ctx.emit(
        "solar_open2",
        **{key: window.get(key) for key in WINDOW_COUNTERS + ROUTING_COUNTERS},
        kv_blocks_in_use_at_open=collected["engine_before"].get("kv_pool_allocated"),
        kv_blocks_in_use_at_close=collected["engine_after"].get("kv_pool_allocated"),
        running_at_open=collected["engine_before"].get("num_running"),
        running_at_close=collected["engine_after"].get("num_running"),
        totals_at_open={k: collected["engine_before"].get(k) for k in TOTALS},
        totals_at_close={k: collected["engine_after"].get(k) for k in TOTALS},
        pipeline_flushes_by_cause=collected["engine_after"].get("pipeline_flushes_by_cause"),
    )
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
    if window["num_preemptions"]:
        # A preempted sequence of a recurrent model is prefilled again from
        # its first token: the cell is sized so that none is.
        problems.append(f"{window['num_preemptions']} preemptions in the window")
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
        "preemptions": [window["num_preemptions"], 0],
        "warm_cache_misses": [warm_misses, 0],
    }
    return {
        "correct": verdict["ok"] and not problems,
        "problems": problems,
        # The named faults and the altered token; the other controls are
        # reported, seen or not (`lib/reference_solar_open2.py`).
        "passed_that_must_fail": sorted(
            k for k, v in controls.items()
            if v["ok"] and (k in MUST_FAIL or k == "altered_token")
        ),
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
