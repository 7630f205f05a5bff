"""Runner for the `mellum` configuration trained through `JaxTrainer`.

`runners/train.py`'s loop and window rule for a model that is a plain tree
with routed experts (that file cannot be edited, so its frame is repeated
here: PERF.md section 7). The loop is the user's side of Ray Train:
`train.prepare_params`, `train.prepare_batch`, `train.prepare_step`,
`train.report`; the step is the model's own (`mellum.train_step`: adamw over
float32 masters, the routing's counts beside the loss), and a report carries
the counts of the steps since the last one.

Set-up is everything up to the first timed step, and `setup_s` leaves out
the seconds inside XLA's compile step up to then, as the serving runners do
(`benchmark/README.md`): compilations in a cold run, reads of cached
executables in a warm one. The float32 reference (`lib/reference_mellum.py`)
takes the warm-up steps again after the window, at the published widths, on
the chip.
"""

from __future__ import annotations

import os
import re
import statistics
import time

import numpy as np

from lib import device, mellum_costs, traffic, xplane
from lib.peaks import peaks_for
from lib.reference import adam_momentum
from lib.reference_mellum import INT8, relative_distance, sizes, training_reference_step

TRACE_SECONDS = 5.0
SCALARS = ("held", "absent", "touched", "load_max")


def _sum_counts(counts: list) -> dict:
    """The routing's counts of several steps, summed; `load` an expert."""
    out = {k: int(sum(int(c[k]) for c in counts)) for k in SCALARS}
    out["load"] = [int(v) for v in np.sum([np.asarray(c["load"]) for c in counts], axis=0)]
    return out


def run(ctx) -> dict:
    import jax.numpy as jnp

    import ray_tpu
    from ray_tpu.models import mellum
    from ray_tpu.train import JaxTrainer, ScalingConfig

    config = ctx.config
    mix = dict(ctx.traffic)
    model_fields = dict(config["model"])
    if ctx.rehearse:
        model_fields = dict(config["rehearsal"]["model"])
        mix.update(config["rehearsal"]["traffic"])
    types = {k: getattr(jnp, model_fields[k]) for k in ("dtype", "param_dtype")}
    cfg = mellum.MellumConfig(**{**model_fields, **types})
    reference_cfg = sizes(model_fields)
    batch, seq = mix["sequences_per_step"], mix["tokens_per_sequence"]
    trace_dir = os.path.join(ctx.out_dir, f"seed{ctx.seed}-trace")
    seconds, seed, reference_seed = ctx.seconds, ctx.seed, ctx.reference_seed
    want_trace, compiles, chips, control = ctx.trace, ctx.compiles, ctx.chips, ctx.control
    learning_rate = config["trainer"]["learning_rate"]

    def train_loop(_config):
        import jax
        import optax

        from ray_tpu import train
        from ray_tpu.train.observability import current_profiler

        t_loop = time.monotonic()
        batches = traffic.step_batches(mix, seed, cfg.rows_held)
        warmup = [next(batches) for _ in range(mix["warmup_steps"])]
        params = train.prepare_params(mellum.init_params(cfg, seed))
        tx = optax.adamw(learning_rate)
        opt_state = jax.block_until_ready(jax.jit(tx.init)(params))
        init_s = time.monotonic() - t_loop

        jit_step = train.prepare_step(mellum.train_step(cfg, tx), donate_argnums=(0, 1))

        def dispatch(tokens):
            nonlocal params, opt_state
            params, opt_state, loss, counts = jit_step(
                params, opt_state, train.prepare_batch(tokens)
            )
            return loss, counts

        # Set-up: the compile and the warm-up steps, each read back. What the
        # optimizer has averaged of their gradients is kept for the check, on
        # the device as `runners/train.py` keeps it (2.38 GB of the window's
        # peak are this copy): fetched here, to the host, it took 3 to 8 s of
        # a set-up of 25, by the run (calls 4 and 9).
        warmup_losses, warmup_took, compile_before = [], [], compiles.seconds
        for tokens in warmup:
            t0 = time.monotonic()
            warmup_losses.append(float(dispatch(tokens)[0]))
            warmup_took.append(time.monotonic() - t0)
            if len(warmup_took) == 1:
                first_compile_s = compiles.seconds - compile_before
        t_copy = time.monotonic()
        momentum = jax.block_until_ready(
            jax.tree_util.tree_map(jnp.copy, adam_momentum(opt_state))
        )
        momentum_copy_s = time.monotonic() - t_copy
        later = statistics.median(warmup_took[1:]) if len(warmup_took) > 1 else 0.0
        setup_split = {
            "init_s": init_s,
            "trace_lower_s": max(warmup_took[0] - first_compile_s - later, 0.0),
            "compile_step_s": compiles.seconds,
            "warmup_steps_s": sum(warmup_took) - warmup_took[0] + later,
            "momentum_copy_s": momentum_copy_s,
        }

        profiler = current_profiler()
        rounds_before = len(profiler.records) if profiler is not None else 0
        compiles_before = compiles.count
        trace_len = min(TRACE_SECONDS, seconds / 3.0)
        trace_from = (seconds - trace_len) / 2.0
        traced, window, before_trace = None, None, None
        # The loop reads a loss back only where it reports one, as a user's
        # does: whatever else closes a step is the program's own doing.
        closed, losses, window_counts, pending, iterator_wait = [], [], [], [], 0.0
        excluded_s = compiles.seconds
        t_open = time.monotonic()
        setup_split["loop_to_open_s"] = t_open - t_loop  # the rest: process start to the loop
        while True:
            t_next = time.monotonic()
            tokens = next(batches)
            iterator_wait += time.monotonic() - t_next
            pending.append(dispatch(tokens))
            if len(pending) < mix["report_every"]:
                continue
            fetched = jax.device_get(pending)
            now = time.monotonic() - t_open
            if now > seconds:
                break  # these steps ended outside the window and do not count
            losses += [float(loss) for loss, _ in fetched]
            experts = _sum_counts([counts for _, counts in fetched])
            window_counts.append(experts)
            pending = []
            closed.append((len(losses), now))
            train.report({"step": len(losses), "loss": losses[-1], "experts": experts})
            if want_trace and traced is None:
                if window is None and now >= trace_from:
                    before_trace = closed[-1]
                    window = xplane.TracedWindow(trace_dir)
                    window.open()
                elif window is not None and time.monotonic() - window.opened >= trace_len:
                    traced = window.close()
        rounds = list(profiler.records)[rounds_before:] if profiler is not None else []
        compiles_in_window = compiles.count - compiles_before
        platforms = sorted(
            {d.platform for leaf in jax.tree_util.tree_leaves(params) for d in leaf.devices()}
        )
        # What the window held, before the reference puts its own on the chip.
        memory_peak = device.memory_peak_bytes(chips)
        report = train.step_device_report(
            jit_step, params, opt_state, train.prepare_batch(warmup[0])
        )

        # Outside the window: the float32 reference takes the warm-up steps
        # again, on the same batches, from the weights of `reference_seed`,
        # and needs the chip's memory to itself.
        momentum = jax.device_get(momentum)
        params = opt_state = pending = fetched = None

        def reference_steps(dtype=None, window_delta=0):
            weights = mellum.init_params(cfg, reference_seed)
            state = jax.jit(tx.init)(weights)
            take = training_reference_step(reference_cfg, tx, dtype, window_delta)
            taken = []
            for tokens in warmup:
                weights, state, loss = take(weights, state, jnp.asarray(tokens))
                taken.append(float(loss))
            return taken, jax.device_get(adam_momentum(state))

        t_reference = time.monotonic()
        reference_losses, reference_momentum = reference_steps()
        reference_s = time.monotonic() - t_reference
        gradient_distance = relative_distance(reference_cfg, momentum, reference_momentum)
        gradient_noise = None
        if want_trace:
            # How far the same dense steps in the training type move the
            # gradients: what the configuration's tolerance is derived from.
            _, noisy = reference_steps(cfg.dtype)
            gradient_noise = relative_distance(reference_cfg, noisy, reference_momentum)
        variants = {}
        if control:
            # What the comparison has to notice: the reference one precision
            # below the training type, and the float32 reference with the
            # sliding layers' window one key longer, sixteen keys longer.
            for name, change in (
                ("int8", {"dtype": INT8}), ("window_plus_1", {"window_delta": 1}),
                ("window_plus_16", {"window_delta": 16}),
            ):
                off_losses, off = reference_steps(**change)
                variants[name] = {
                    "gradient_distance": relative_distance(reference_cfg, off, reference_momentum),
                    "loss_distance": max(
                        abs(a - b) for a, b in zip(off_losses, reference_losses)
                    ),
                }
        train.report(
            {
                "bench": {
                    "t_open": t_open, "closed": closed, "losses": losses,
                    "warmup_losses": warmup_losses,
                    "reference_losses": reference_losses,
                    "gradient_distance": gradient_distance,
                    "gradient_noise": gradient_noise, "reference_s": reference_s,
                    "variants": variants,
                    "rounds": rounds, "iterator_wait_s": iterator_wait,
                    "traced_window_s": traced, "before_trace": before_trace,
                    "traced_profile_s": window.profiled_s if window else None,
                    "compiles_in_window": compiles_in_window,
                    "param_platforms": platforms, "experts": _sum_counts(window_counts),
                    "setup_split": setup_split, "excluded_s": excluded_s,
                    "memory_peak_bytes": memory_peak, "device_report": report,
                }
            }
        )

    ray_tpu.init()
    try:
        result = JaxTrainer(
            train_loop,
            train_loop_config={},
            scaling_config=ScalingConfig(
                num_workers=config["trainer"]["num_workers"],
                chips_per_worker=0 if ctx.rehearse else ctx.chips,
            ),
        ).fit()
    finally:
        ray_tpu.shutdown()
    if result.error is not None:
        raise result.error
    bench = result.metrics_history[-1]["bench"]
    run_report = result.train_report or {}

    closed, losses, experts = bench["closed"], bench["losses"], bench["experts"]
    tokens_per_step = batch * seq
    # Over the time the closed steps took, not over the nominal window: the
    # steps that straddle the close would otherwise quantise the rate.
    steps, took = closed[-1]
    tokens_per_s = steps * tokens_per_step / took / ctx.chips
    if bench["before_trace"]:
        n, until = bench["before_trace"]
        untraced_tokens_per_s = n * tokens_per_step / until / ctx.chips
    else:
        untraced_tokens_per_s = tokens_per_s
    correctness = config["correctness"]
    loss_distances = [
        abs(a - b) for a, b in zip(bench["warmup_losses"], bench["reference_losses"])
    ]
    # One tolerance for the gradients as a whole and for the worst single
    # kind of weight matrix, so a fault in one kind is not averaged away.
    gradient_distance = max(
        bench["gradient_distance"]["all"], bench["gradient_distance"]["worst_matrix"]
    )
    # And one for the attention's matrices alone, which read a third of
    # what the routed kinds do and are where a mask or a rotation shows.
    attention_distance = bench["gradient_distance"]["worst_attention"]
    finite = all(loss == loss and abs(loss) != float("inf") for loss in losses)
    tenth = max(1, len(losses) // 10)
    falling = sum(losses[-tenth:]) / tenth < sum(losses[:tenth]) / tenth
    on_device = bench["param_platforms"] == [ctx.device["platform"]]
    # Every choice of every token in every layer is counted, held or not.
    assignments = steps * tokens_per_step * cfg.num_experts_per_tok * cfg.num_layers
    counted = experts["held"] + experts["absent"] == assignments
    thirds = [closed[len(closed) * k // 3 - 1] for k in (1, 2, 3)] if len(closed) >= 3 else []
    by_third = [
        (n - n0) * tokens_per_step / (t - t0) / ctx.chips
        for (n0, t0), (n, t) in zip([(0, 0.0)] + thirds, thirds)
    ]
    if bench["variants"]:
        ctx.emit("control", variants=bench["variants"],
                 loss_tolerance=correctness["loss_tolerance"],
                 gradient_tolerance=correctness["gradient_tolerance"],
                 attention_gradient_tolerance=correctness["attention_gradient_tolerance"])
    report = bench["device_report"]
    scopes = report.pop("op_scopes", {})
    ctx.emit("device_report", **report,
             op_scopes_named={k: len(v) for k, v in scopes.items()})
    report["op_scopes"] = scopes
    ctx.emit(
        "train", steps=steps, tokens_per_step=tokens_per_step, step_mean_s=took / steps,
        tokens_per_s_by_third=by_third, reference_s=bench["reference_s"],
        warmup_losses=bench["warmup_losses"], reference_losses=bench["reference_losses"],
        loss_distances=loss_distances, loss_tolerance=correctness["loss_tolerance"],
        gradient_distance=bench["gradient_distance"],
        gradient_tolerance=correctness["gradient_tolerance"],
        attention_gradient_tolerance=correctness["attention_gradient_tolerance"],
        bf16_gradient_noise=bench["gradient_noise"],
        loss_first_tenth=sum(losses[:tenth]) / tenth,
        loss_last_tenth=sum(losses[-tenth:]) / tenth,
        finite=finite, falling=falling, param_platforms=bench["param_platforms"],
        compiles_in_window=bench["compiles_in_window"],
        untraced_tokens_per_s=untraced_tokens_per_s,
        experts=experts, assignments_expected=assignments,
        run_report_experts=run_report.get("experts"),
        parameters=mellum.num_params(mellum.param_shapes(cfg)),
        **bench["setup_split"],
    )
    held_per_token = experts["held"] / (steps * tokens_per_step)
    collected = {
        "window_open": bench["t_open"],
        "train": {
            "tokens_per_s": tokens_per_s,
            "untraced_tokens_per_s": untraced_tokens_per_s,
            "flops_per_token": mellum_costs.train_flops_per_token(
                model_fields, seq, held_per_token
            ),
            "rounds": bench["rounds"],
            "iterator_wait_s": bench["iterator_wait_s"],
            "model": model_fields, "steps": steps, "experts": experts,
            "sequences_per_step": batch, "tokens_per_sequence": seq,
        },
        "device_report": report,
        "peaks": None if ctx.rehearse else peaks_for(ctx.device["kind"]),
        "compiles_in_window": bench["compiles_in_window"],
        "memory_peak_bytes": bench["memory_peak_bytes"],
        "trace": None,
    }
    if bench["traced_window_s"]:
        collected["trace"] = xplane.reduce_trace(
            trace_dir, bench["traced_window_s"], bench["traced_profile_s"],
            keep=ctx.keep_trace,
        )
    if collected["trace"]:
        # A step by part: device ms a traced step under each of the model's
        # scopes, forward and backward, and what no scope names.
        traced_steps = mellum_costs.traced_steps(collected) or 1
        by_part = {
            scope: (mellum_costs.step_scope_seconds(collected, f"^{re.escape(scope)}$") or 0.0)
            * 1e3 / traced_steps
            for scope in sorted(set(scopes.get("jit_step", {}).values()))
        }
        step_ms = sum(
            spent for name, spent in collected["trace"]["op_seconds"].items()
            if name.startswith("jit_step/")
        ) * 1e3 / traced_steps
        ctx.emit("step_by_part", traced_steps=traced_steps, device_ms_a_step=step_ms,
                 ms_a_step_by_scope=by_part, unscoped_ms=step_ms - sum(by_part.values()))
    problems = []
    if bench["compiles_in_window"]:
        problems.append(f"{bench['compiles_in_window']} compilations inside the window")
    if not on_device:
        problems.append(f"parameters on {bench['param_platforms']}")
    if not counted:
        problems.append(
            f"{experts['held']} held + {experts['absent']} absent assignments, "
            f"{assignments} made"
        )
    agrees = (
        max(loss_distances) < correctness["loss_tolerance"]
        and gradient_distance < correctness["gradient_tolerance"]
        and attention_distance < correctness["attention_gradient_tolerance"]
    )
    return {
        # Not `falling`, which `runners/train.py` asks of GPT-2: this model's
        # seeded head is narrow, its loss starts at the entropy of targets
        # drawn at random, and there is nothing below that to fall to.
        "correct": agrees and finite and not problems,
        "problems": problems,
        "compared": {
            "loss_distance": [max(loss_distances), correctness["loss_tolerance"]],
            "gradient_distance": [gradient_distance, correctness["gradient_tolerance"]],
            "attention_gradient_distance": [
                attention_distance, correctness["attention_gradient_tolerance"]
            ],
            "compiles_in_window": [bench["compiles_in_window"], 0],
        },
        "attempted": steps,
        "failed": 0 if finite else sum(1 for loss in losses if loss != loss),
        "window_open": bench["t_open"],
        "setup_excluded_s": bench["excluded_s"],
        "end_to_end": {"train_tokens_per_s": tokens_per_s},
        "collected": collected,
    }
