"""Runner for configurations trained through `JaxTrainer`.

The loop below is the user's side of Ray Train, as `chip_smoke.py` and the
README write it: `train.prepare_params`, `train.prepare_batch`,
`train.prepare_step`, `train.report`. `JaxTrainer(...).fit()` runs it in the
worker group's one worker, which under the default thread-isolated runtime
lives in this process and holds the chip. Set-up is everything up to the
first timed step: device, weights, optimizer state, the compile of the step
and the warm-up steps. The reference runs after the window.
"""

from __future__ import annotations

import os
import time

from lib import flops, traffic, xplane
from lib.peaks import peaks_for
from lib.reference import adam_momentum, relative_distance, training_reference_step

TRACE_SECONDS = 5.0
REFERENCE_ROWS = 2  # sequences per slice of the float32 reference's gradient


def run(ctx) -> dict:
    import jax.numpy as jnp

    import ray_tpu
    from ray_tpu.models.gpt import GPTConfig
    from ray_tpu.train import JaxTrainer, ScalingConfig

    config = ctx.config
    mix = dict(ctx.traffic)
    model_fields = dict(config["model"])
    if ctx.rehearse:
        model_fields = dict(config["rehearsal"]["model"])
        mix.update(config["rehearsal"]["traffic"])
    model_fields["dtype"] = getattr(jnp, model_fields["dtype"])
    cfg = GPTConfig(**model_fields)
    vocab = cfg.vocab_size if ctx.rehearse else config["published"]["vocab_size"]
    batch, seq = mix["sequences_per_step"], mix["tokens_per_sequence"]
    trace_dir = os.path.join(ctx.out_dir, f"seed{ctx.seed}-trace")
    seconds, seed, reference_seed = ctx.seconds, ctx.seed, ctx.reference_seed
    want_trace, compiles = ctx.trace, ctx.compiles
    learning_rate = config["trainer"]["learning_rate"]

    def train_loop(_config):
        import flax.linen as nn
        import jax
        import optax

        from ray_tpu import train
        from ray_tpu.models.gpt import GPT, cross_entropy_loss
        from ray_tpu.train.observability import current_profiler

        model = GPT(cfg)
        batches = traffic.step_batches(mix, seed, vocab)
        warmup = [next(batches) for _ in range(mix["warmup_steps"])]

        def init(seed):
            weights = jax.jit(model.init)(jax.random.PRNGKey(seed), jnp.asarray(warmup[0][:1]))
            return nn.meta.unbox(weights)

        params = train.prepare_params(init(seed))
        tx = optax.adamw(learning_rate)
        opt_state = jax.jit(tx.init)(params)

        def step(params, opt_state, tokens):
            def loss_fn(p):
                logits = model.apply(p, tokens)
                return cross_entropy_loss(logits[:, :-1], tokens[:, 1:])

            loss, grads = jax.value_and_grad(loss_fn)(params)
            updates, opt_state = tx.update(grads, opt_state, params)
            return optax.apply_updates(params, updates), opt_state, loss

        jit_step = train.prepare_step(step, donate_argnums=(0, 1))

        def dispatch(tokens):
            nonlocal params, opt_state
            params, opt_state, loss = jit_step(params, opt_state, train.prepare_batch(tokens))
            return loss

        # Set-up: the compile and the warm-up steps, each read back. What the
        # optimizer has averaged of their gradients is kept for the check.
        warmup_losses = [float(dispatch(tokens)) for tokens in warmup]
        momentum = jax.block_until_ready(
            jax.tree_util.tree_map(jnp.copy, adam_momentum(opt_state))
        )

        profiler = current_profiler()
        rounds_before = len(profiler.records) if profiler is not None else 0
        compiles_before = compiles.count
        trace_len = min(TRACE_SECONDS, seconds / 3.0)
        trace_from = (seconds - trace_len) / 2.0
        traced, window, before_trace = None, None, None
        # The loop reads a loss back only where it reports one, as a user's
        # does: whatever else closes a step is the program's own doing.
        closed, losses, pending, iterator_wait = [], [], [], 0.0
        t_open = time.monotonic()
        while True:
            t_next = time.monotonic()
            tokens = next(batches)
            iterator_wait += time.monotonic() - t_next
            pending.append(dispatch(tokens))
            if len(pending) < mix["report_every"]:
                continue
            values = [float(v) for v in jax.device_get(pending)]
            now = time.monotonic() - t_open
            if now > seconds:
                break  # these steps ended outside the window and do not count
            losses += values
            pending = []
            closed.append((len(losses), now))
            train.report({"step": len(losses), "loss": values[-1]})
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

        # Outside the window: the float32 reference takes the warm-up steps
        # again, on the same batches, from the weights of `reference_seed`.
        params = opt_state = pending = None

        def reference_steps(dtype=None):
            weights = init(reference_seed)
            state = jax.jit(tx.init)(weights)
            take = training_reference_step(cfg, tx, REFERENCE_ROWS, dtype)
            taken = []
            for tokens in warmup:
                weights, state, loss = take(weights, state, jnp.asarray(tokens))
                taken.append(float(loss))
            return taken, adam_momentum(state)

        t_reference = time.monotonic()
        reference_losses, reference_momentum = reference_steps()
        reference_s = time.monotonic() - t_reference
        gradient_distance = relative_distance(momentum, reference_momentum)
        gradient_noise = None
        if want_trace:
            # How far the same dense steps in the training type move the
            # gradients: what the configuration's tolerance is derived from.
            _, noisy = reference_steps(cfg.dtype)
            gradient_noise = relative_distance(noisy, reference_momentum)
        train.report(
            {
                "bench": {
                    "t_open": t_open, "closed": closed, "losses": losses,
                    "warmup_losses": warmup_losses,
                    "reference_losses": reference_losses,
                    "gradient_distance": gradient_distance,
                    "gradient_noise": gradient_noise, "reference_s": reference_s,
                    "rounds": rounds, "iterator_wait_s": iterator_wait,
                    "traced_window_s": traced, "before_trace": before_trace,
                    "traced_profile_s": window.profiled_s if window else None,
                    "compiles_in_window": compiles_in_window,
                    "param_platforms": platforms,
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

    closed, losses = bench["closed"], bench["losses"]
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
    finite = all(loss == loss and abs(loss) != float("inf") for loss in losses)
    tenth = max(1, len(losses) // 10)
    falling = sum(losses[-tenth:]) / tenth < sum(losses[:tenth]) / tenth
    on_device = bench["param_platforms"] == [ctx.device["platform"]]
    # Where in the window a slow stretch fell: the rate of each third.
    thirds = [closed[len(closed) * k // 3 - 1] for k in (1, 2, 3)] if len(closed) >= 3 else []
    by_third = [
        (n - n0) * tokens_per_step / (t - t0) / ctx.chips
        for (n0, t0), (n, t) in zip([(0, 0.0)] + thirds, thirds)
    ]
    ctx.emit(
        "train", steps=steps, tokens_per_step=tokens_per_step, step_mean_s=took / steps,
        tokens_per_s_by_third=by_third, reference_s=bench["reference_s"],
        warmup_losses=bench["warmup_losses"], reference_losses=bench["reference_losses"],
        loss_distances=loss_distances, loss_tolerance=correctness["loss_tolerance"],
        gradient_distance=bench["gradient_distance"],
        gradient_tolerance=correctness["gradient_tolerance"],
        bf16_gradient_noise=bench["gradient_noise"],
        loss_first_tenth=sum(losses[:tenth]) / tenth,
        loss_last_tenth=sum(losses[-tenth:]) / tenth,
        finite=finite, falling=falling, param_platforms=bench["param_platforms"],
        compiles_in_window=bench["compiles_in_window"],
        untraced_tokens_per_s=untraced_tokens_per_s,
    )
    model_flops = flops.gpt_train_flops_per_token(
        cfg.num_layers, cfg.embed_dim, cfg.vocab_size, seq, cfg.mlp_ratio
    )
    collected = {
        "window_open": bench["t_open"],
        "train": {
            "tokens_per_s": tokens_per_s,
            "untraced_tokens_per_s": untraced_tokens_per_s,
            "flops_per_token": model_flops,
            "rounds": bench["rounds"],
            "iterator_wait_s": bench["iterator_wait_s"],
        },
        "peaks": None if ctx.rehearse else peaks_for(ctx.device["kind"]),
        "compiles_in_window": bench["compiles_in_window"],
        "trace": None,
    }
    if bench["traced_window_s"]:
        collected["trace"] = xplane.reduce_trace(
            trace_dir, bench["traced_window_s"], bench["traced_profile_s"],
            keep=ctx.keep_trace,
        )
    problems = []
    if bench["compiles_in_window"]:
        problems.append(f"{bench['compiles_in_window']} compilations inside the window")
    if not on_device:
        problems.append(f"parameters on {bench['param_platforms']}")
    agrees = (
        max(loss_distances) < correctness["loss_tolerance"]
        and gradient_distance < correctness["gradient_tolerance"]
    )
    return {
        "correct": agrees and finite and falling and not problems,
        "problems": problems,
        "compared": {
            "loss_distance": [max(loss_distances), correctness["loss_tolerance"]],
            "gradient_distance": [gradient_distance, correctness["gradient_tolerance"]],
            "compiles_in_window": [bench["compiles_in_window"], 0],
        },
        "attempted": steps,
        "failed": 0 if finite else sum(1 for loss in losses if loss != loss),
        "window_open": bench["t_open"],
        "end_to_end": {"train_tokens_per_s": tokens_per_s},
        "collected": collected,
    }
