"""Headline benchmarks: the BASELINE.json north-star configs.

Prints one JSON line per config; the LAST line is the headline ResNet-50
number (same metric/format as round 1, so driver history stays comparable):

  1. gpt2_125m_train_tokens_per_sec_per_chip  (config #5: LM, flash attention)
  2. ppo_env_steps_per_sec                    (config #3: RLlib PPO)
  3. resnet50_train_images_per_sec_per_chip   (config #2: the headline)

Every line names the device it ran on (`platform`, `device_kind`,
`device_count`). The measuring path needs a TPU: with no chip the script
exits non-zero, unless the CPU was asked for with `JAX_PLATFORMS=cpu` —
then it is a rehearsal of the control flow at toy shapes: every metric is
named `cpu_rehearsal/...` and says `"rehearsal": true`, and its values are
no device metric. Each config runs
once; one that fails prints an error line and makes the exit code 1.

The reference publishes no TPU numbers; its stated goal is GPU-parity
throughput (BASELINE.md "Targets"), so `vs_baseline` compares against
A100-class single-accelerator marks: 1500 img/s (ResNet-50 bf16),
150k tokens/s (GPT-2 125M at ~40% MFU), and 10k env-steps/s (PPO CartPole
with a handful of CPU sampling workers).

MFU context printed with the ResNet line: `measured_matmul_tflops` is a
bf16 matmul probe on THIS device and `pct_of_measured_peak` positions the
training step against it (ROADMAP S4: the probe is not a ceiling).
"""

from __future__ import annotations

import functools
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import optax

from ray_tpu._private.jax_setup import cpu_requested, ensure_compile_cache

GPU_PARITY_IMG_S_PER_CHIP = 1500.0
GPU_PARITY_TOK_S_PER_CHIP = 150_000.0
PARITY_PPO_ENV_STEPS_S = 10_000.0


def _emit(line: dict) -> None:
    device = jax.devices()[0]
    line.update(
        platform=device.platform,
        device_kind=device.device_kind,
        device_count=len(jax.devices()),
    )
    if device.platform == "cpu" and "value" in line:
        # No CPU number goes out under a device metric's name.
        line["metric"] = "cpu_rehearsal/" + line["metric"]
        line["rehearsal"] = True
    print(json.dumps(line), flush=True)


def _sync(x) -> None:
    # block_until_ready is the sync: a 20-matmul chain timed 185.5 TFLOP/s
    # with it and 184.2 with a device->host read of the result (v5e, PR 21).
    jax.block_until_ready(x)


def bench_gpt2(on_tpu: bool) -> None:
    """Config #5: GPT-2 125M LM training, tokens/sec/chip."""
    from ray_tpu.models import GPT, cross_entropy_loss, gpt2_125m

    devices = jax.devices()
    n_chips = len(devices)
    if on_tpu:
        # 24 seqs/chip: measured MXU sweet spot on v5e (8 underfills the
        # [S,E]x[E,V] head matmul; 32 thrashes HBM with the f32 grads of
        # the multi-GB bf16 logits).
        B, S, warmup, timed = 24 * n_chips, 1024, 3, 20
        cfg = gpt2_125m(attention_impl="flash", dtype=jnp.bfloat16)
    else:
        B, S, warmup, timed = 2, 128, 1, 2
        cfg = gpt2_125m(
            attention_impl="reference",
            dtype=jnp.float32,
            num_layers=2,
            max_seq_len=128,
            vocab_size=1024,
        )
    model = GPT(cfg)
    key = jax.random.PRNGKey(0)
    tokens = jax.random.randint(key, (B, S), 0, cfg.vocab_size)
    params = jax.jit(model.init)(key, tokens)
    tx = optax.adamw(3e-4)
    opt_state = jax.jit(tx.init)(params)

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def step(params, opt_state, tokens):
        def loss_fn(p):
            logits = model.apply(p, tokens)
            return cross_entropy_loss(logits[:, :-1], tokens[:, 1:])

        loss, grads = jax.value_and_grad(loss_fn)(params)
        updates, opt_state = tx.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    for _ in range(warmup):
        params, opt_state, loss = step(params, opt_state, tokens)
    _sync(loss)
    t0 = time.perf_counter()
    for _ in range(timed):
        params, opt_state, loss = step(params, opt_state, tokens)
    _sync(loss)
    dt = time.perf_counter() - t0
    tok_s_chip = B * S * timed / dt / n_chips
    _emit(
        {
            "metric": "gpt2_125m_train_tokens_per_sec_per_chip",
            "value": round(tok_s_chip, 1),
            "unit": "tokens/sec/chip",
            "vs_baseline": round(tok_s_chip / GPU_PARITY_TOK_S_PER_CHIP, 4),
        }
    )


def bench_ppo(on_tpu: bool) -> None:
    """Config #3: RLlib PPO sampling+training throughput, env-steps/sec.

    Envs + policy inference on host CPU threads; the learner's whole
    epochs x minibatches SGD runs as one jitted scan on the accelerator."""
    import ray_tpu
    from ray_tpu.rllib.algorithms.ppo import PPOConfig

    # Logical CPUs: runner actors each request 1 CPU and this box may have a
    # single physical core (threads timeshare it regardless).
    ray_tpu.init(num_cpus=max(8, os.cpu_count() or 1), ignore_reinit_error=True)
    if on_tpu:
        # One runner with many natively-vectorized sub-envs: on a
        # single-core sampling host extra runner actors only add context
        # switching; the fused numpy env + numpy policy fast path make one
        # big vector the fastest sampler. The runner overlaps with the TPU
        # learner (PPO.training_step re-arms sampling before the update).
        runners, envs, frag, train_bs, iters = 1, 128, 64, 8192, 5
    else:
        runners, envs, frag, train_bs, iters = 2, 4, 32, 256, 2
    config = (
        PPOConfig()
        .environment("CartPole-v1")
        .env_runners(
            num_env_runners=runners,
            num_envs_per_env_runner=envs,
            rollout_fragment_length=frag,
        )
        .training(train_batch_size=train_bs, minibatch_size=256, num_epochs=4)
    )
    algo = config.build()
    algo.train()  # compile + warmup
    steps0 = algo._env_steps_total
    t0 = time.perf_counter()
    for _ in range(iters):
        algo.train()
    dt = time.perf_counter() - t0
    env_steps_s = (algo._env_steps_total - steps0) / dt
    algo.cleanup()  # join learner machinery BEFORE runtime teardown
    import ray_tpu as _rt

    _rt.shutdown()
    _emit(
        {
            "metric": "ppo_env_steps_per_sec",
            "value": round(env_steps_s, 1),
            "unit": "env_steps/sec",
            "vs_baseline": round(env_steps_s / PARITY_PPO_ENV_STEPS_S, 4),
        }
    )


def bench_impala(on_tpu: bool) -> None:
    """Config #3's second half: IMPALA async throughput on the Atari-class
    MinAtar-Breakout env (image observations [10,10,4]) — the architecture
    built for sampling/learning overlap, measured as env-steps consumed by
    the learner per second."""
    import ray_tpu
    from ray_tpu.rllib.algorithms.impala import IMPALAConfig

    ray_tpu.init(num_cpus=max(8, os.cpu_count() or 1), ignore_reinit_error=True)
    if on_tpu:
        # 256 sub-envs: the fused numpy env steps all of them in one
        # vector op, so doubling the vector over 128 costs ~nothing on the
        # sampling thread while halving per-step Python overhead (measured
        # 10.6k -> 17.8k env-steps/s on v5e + 1-core host).
        # 10 timed iterations: the 1-core sampling host's throughput
        # fluctuates with outside load; a longer window averages the dips.
        runners, envs, frag, train_bs, iters = 1, 256, 64, 4096, 10
    else:
        runners, envs, frag, train_bs, iters = 2, 4, 16, 128, 2
    config = (
        IMPALAConfig()
        .environment("MinAtar-Breakout")
        .env_runners(
            num_env_runners=runners,
            num_envs_per_env_runner=envs,
            rollout_fragment_length=frag,
        )
        .training(train_batch_size=train_bs)
    )
    algo = config.build()
    algo.train()  # compile + pipeline fill
    steps0 = algo._env_steps_total
    t0 = time.perf_counter()
    for _ in range(iters):
        algo.train()
    dt = time.perf_counter() - t0
    env_steps_s = (algo._env_steps_total - steps0) / dt
    algo.cleanup()  # join the learner thread BEFORE runtime teardown
    import ray_tpu as _rt

    _rt.shutdown()
    _emit(
        {
            "metric": "impala_env_steps_per_sec",
            "value": round(env_steps_s, 1),
            "unit": "env_steps/sec",
            "vs_baseline": round(env_steps_s / PARITY_PPO_ENV_STEPS_S, 4),
        }
    )


def _measure_matmul_tflops() -> float:
    n = 8192
    a = jax.random.normal(jax.random.PRNGKey(0), (n, n), jnp.bfloat16)
    f = jax.jit(lambda x: x @ x)
    b = f(a)
    _sync(b)
    t0 = time.perf_counter()
    for _ in range(10):
        b = f(b)
    _sync(b)
    return 10 * 2 * n**3 / (time.perf_counter() - t0) / 1e12


def bench_resnet(on_tpu: bool) -> None:
    """Config #2 (headline): ResNet-50 training, images/sec/chip.

    Runs the full jitted train step (fwd + bwd + SGD-momentum update, donated
    buffers) on synthetic ImageNet-shaped data sharded over ALL local chips
    via a dp mesh, bf16 compute, averaged over timed steps after warmup."""
    from ray_tpu.models import ResNet50
    from ray_tpu.parallel import MeshSpec, batch_sharding, replicated

    devices = jax.devices()
    n_chips = len(devices)
    if on_tpu:
        per_chip_batch, image_hw, warmup, timed = 256, 224, 5, 20
        model = ResNet50(num_classes=1000, dtype=jnp.bfloat16)
    else:
        # CPU smoke path: tiny CIFAR-style shapes so XLA compile stays short.
        per_chip_batch, image_hw, warmup, timed = 8, 32, 1, 3
        model = ResNet50(num_classes=1000, dtype=jnp.bfloat16, small_inputs=True)
    batch = per_chip_batch * n_chips

    mesh = MeshSpec(dp=-1).build(devices)
    data_shard = batch_sharding(mesh)
    repl = replicated(mesh)
    key = jax.random.PRNGKey(0)

    # Generate data and params INSIDE jit with explicit out_shardings: nothing
    # is ever materialized on one device, and it works on multi-host slices
    # where host data can't be device_put onto non-addressable devices.
    @functools.partial(jax.jit, out_shardings=(data_shard, data_shard))
    def make_data(key):
        images = jax.random.normal(key, (batch, image_hw, image_hw, 3), jnp.bfloat16)
        labels = jax.random.randint(key, (batch,), 0, 1000)
        return images, labels

    images, labels = make_data(key)

    @functools.partial(jax.jit, out_shardings=repl)
    def make_params(key):
        probe = jnp.zeros((1, image_hw, image_hw, 3), jnp.bfloat16)
        return model.init(key, probe, train=False)

    params = make_params(key)

    tx = optax.sgd(0.1, momentum=0.9)
    opt_state = jax.jit(tx.init)(params)

    def step(params, opt_state, images, labels):
        def loss_fn(p):
            logits = model.apply(p, images, train=True)
            return optax.softmax_cross_entropy_with_integer_labels(
                logits.astype(jnp.float32), labels
            ).mean()

        loss, grads = jax.value_and_grad(loss_fn)(params)
        updates, opt_state = tx.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    step = jax.jit(step, donate_argnums=(0, 1))

    for _ in range(warmup):
        params, opt_state, loss = step(params, opt_state, images, labels)
    _sync(loss)

    t0 = time.perf_counter()
    for _ in range(timed):
        params, opt_state, loss = step(params, opt_state, images, labels)
    _sync(loss)
    dt = time.perf_counter() - t0

    img_s_per_chip = batch * timed / dt / n_chips
    line = {
        "metric": "resnet50_train_images_per_sec_per_chip",
        "value": round(img_s_per_chip, 2),
        "unit": "images/sec/chip",
        "vs_baseline": round(img_s_per_chip / GPU_PARITY_IMG_S_PER_CHIP, 4),
    }
    if on_tpu:
        # ResNet-50 fwd+bwd ~= 3 x 4.1 GFLOP/img, set beside a bf16
        # matmul probe on this device.
        matmul_tflops = _measure_matmul_tflops()
        train_tflops = img_s_per_chip * 3 * 4.1e9 / 1e12
        line["train_tflops"] = round(train_tflops, 1)
        line["measured_matmul_tflops"] = round(matmul_tflops, 1)
        line["pct_of_measured_peak"] = round(100 * train_tflops / matmul_tflops, 1)
    _emit(line)


def main() -> int:
    device = jax.devices()[0]
    on_tpu = device.platform == "tpu"
    if not on_tpu and not (device.platform == "cpu" and cpu_requested()):
        _emit(
            {
                "metric": "device",
                "error": "no TPU found; a CPU rehearsal must be asked for "
                "with JAX_PLATFORMS=cpu",
            }
        )
        return 2
    ensure_compile_cache()
    failed = 0
    for bench in (bench_gpt2, bench_ppo, bench_impala, bench_resnet):
        try:
            bench(on_tpu)
        except Exception as exc:  # one config failing must not hide the rest
            failed += 1
            _emit({"metric": bench.__name__, "error": repr(exc)[:300]})
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
