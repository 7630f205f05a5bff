"""The quickest proof that ray_tpu still starts on the chip.

    python chip_smoke.py                  one TPU chip: device, serve, train
    python chip_smoke.py --chips 4        four chips: tensor-parallel serving
                                          against one chip, and nothing else
    python chip_smoke.py --cpu-rehearsal  toy sizes on the CPU backend: finds
                                          wrong paths, never prints a result

One process drives everything through the entry points a user calls
(`ray_tpu.init`, `serve.run(llm.serve.build_app(...))` + the HTTP proxy,
`JaxTrainer(...).fit()`): the default runtime is thread-isolated, so driver,
Serve controller, proxy, replicas, engine and train worker all live in the
process that owns the chip. Every phase prints one JSON line; a phase that
fails raises and the exit code is non-zero. Only a run on a TPU ends with

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

Serving is `gpt2_760m` at full width (1280 wide, 20 heads of 64, 24 layers,
vocabulary 50,304; random weights from --seed) with engine options at their
defaults where they select behaviour and a deployment's geometry. The pool
is 1,024 blocks of 16 tokens: the compiled decode program needs temp space
4.3x the pool (ROADMAP S6d), so beside 2.15 GB of float32 weights 1,024
blocks take 12.8 GB of the chip's 15.75 GB (arguments + temp, chip run,
PR 21) and 2,048 are refused by the compiler. Every output is compared with
an unbatched greedy `model.apply` loop in float32 over the XLA reference
attention: tokens equal, or at the first differing position the two
candidates' reference logits lie within LOGIT_TOLERANCE of each other —
the bf16 kernel and the reference round differently, so a near tie may
flip; anything else fails.
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import glob
import importlib.metadata
import json
import os
import shutil
import sys
import threading
import time
import urllib.request

# Reference-logit gap under which a differing greedy token counts as a
# rounding flip. The same dense forward in bf16 moves a next-token logit
# of the seeded 760M model by up to 0.044 against float32 (the serve phase
# prints it as `bf16_logit_noise`), so two candidates can swap across a
# gap of twice that. Flips seen on the chip had gaps of 0.011-0.038 (PR 21).
LOGIT_TOLERANCE = 0.1

_HERE = os.path.dirname(os.path.abspath(__file__))
_TAG: dict = {}  # on every line of a rehearsal: {"rehearsal": true, "platform": "cpu"}


def emit(phase: str, **fields) -> None:
    if _TAG:
        # A rehearsal's clock times the CPU backend and the interpreter:
        # no time or rate leaves it under a device metric's name.
        fields = {
            key: "not measured" if key.endswith(("_s", "_per_s")) else value
            for key, value in fields.items()
        }
    print(json.dumps({"phase": phase, **_TAG, **fields}), flush=True)


def check(condition: bool, message: str) -> None:
    if not condition:
        raise AssertionError(message)


def cache_entries(cache_dir) -> int:
    if not cache_dir or not os.path.isdir(cache_dir):
        return 0
    return len(os.listdir(cache_dir))


# ----------------------------------------------------------------- device


def device_phase(args) -> dict:
    import jax
    import jaxlib

    import ray_tpu
    from ray_tpu._private.jax_setup import ensure_compile_cache
    from ray_tpu._private.native_store import native_store_available

    devices = jax.devices()
    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }
    if args.cpu_rehearsal:
        check(device["platform"] == "cpu", f"rehearsal is for the CPU: {device}")
        _TAG.update(rehearsal=True, platform="cpu")
    elif device["platform"] != "tpu":
        print(f"chip_smoke: no TPU, JAX found {device}", file=sys.stderr)
        sys.exit(2)
    check(
        device["count"] >= args.chips,
        f"--chips {args.chips} needs that many devices, JAX reports {device}",
    )
    cache_dir = ensure_compile_cache()
    entries = cache_entries(cache_dir)
    store_lib = os.path.join(_HERE, "src", "build", "libtpustore.so")
    store_prebuilt = os.path.exists(store_lib)
    ray_tpu.init()
    tpus = int(ray_tpu.cluster_resources().get("TPU", 0))
    if not args.cpu_rehearsal:
        check(
            tpus == device["count"],
            f"cluster_resources() counts {tpus} TPU, JAX {device['count']}",
        )
    store_loaded = native_store_available()
    emit(
        "device",
        **device,
        pid=os.getpid(),
        jax=jax.__version__,
        jaxlib=jaxlib.__version__,
        libtpu=importlib.metadata.version("libtpu"),
        jax_platforms=os.environ.get("JAX_PLATFORMS"),
        compile_cache_dir=cache_dir,
        compile_cache_entries=entries,
        cluster_tpus=tpus,
        dev_nodes=sorted(glob.glob("/dev/accel*") + glob.glob("/dev/vfio/*")),
        native_store_loaded=store_loaded,
        native_store_built_by_this_run=store_loaded and not store_prebuilt,
        toolchain={tool: shutil.which(tool) for tool in ("make", "g++")},
    )
    return {**device, "cache_dir": cache_dir, "cache_entries": entries}


# ------------------------------------------------------------------ serve


def serving_setup(args):
    """(model name, its config, engine geometry, prompts, new tokens per
    request)."""
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.models.gpt import GPTConfig, gpt2_760m

    if args.cpu_rehearsal:
        model = "toy"
        cfg = GPTConfig(
            vocab_size=512, num_layers=2, num_heads=4, embed_dim=64,
            max_seq_len=128, dtype=jnp.bfloat16,
        )
        # The kernel in interpret mode: "auto" picks the reference off-TPU.
        geometry = dict(
            block_size=16, num_blocks=64, max_blocks_per_seq=8,
            max_decode_slots=8, attn_impl="pallas",
        )
        lengths = dict(short=12, chunked=48, repeat=40, stream=24, short2=20)
        new_tokens = 6
    else:
        model = "gpt2_760m"
        cfg = gpt2_760m(dtype=jnp.bfloat16)
        geometry = dict(
            block_size=16, num_blocks=1024, max_blocks_per_seq=64,
            max_decode_slots=8,
        )
        lengths = dict(short=12, chunked=300, repeat=80, stream=40, short2=20)
        new_tokens = 16
    rng = np.random.RandomState(args.seed)
    prompts = {
        name: [int(t) for t in rng.randint(1, cfg.vocab_size, size=n)]
        for name, n in lengths.items()
    }
    # Sent once on its own first, so its blocks are cached when it comes
    # again among the concurrent five.
    prompts["repeat_first"] = prompts["repeat"]
    return model, cfg, geometry, prompts, new_tokens


def make_params(cfg, seed: int):
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.gpt import GPT

    probe = jnp.zeros((1, 16), jnp.int32)
    return jax.jit(GPT(cfg).init)(jax.random.PRNGKey(seed), probe)


def post(url: str, body: dict, timeout: float = 600.0):
    request = urllib.request.Request(
        url,
        data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"},
    )
    return urllib.request.urlopen(request, timeout=timeout)


def serve_prompts(name, cfg, ecfg, params, prompts, new_tokens) -> dict:
    """Deploy one engine behind Serve and the HTTP proxy, send the prompts,
    check the engine's own account of what happened, tear it down.
    Returns outputs, engine metrics, the device report and timings."""
    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.llm.serve import build_app
    from ray_tpu.serve._private.http_proxy import start_proxy

    t0 = time.monotonic()
    serve.run(
        build_app(cfg, ecfg, params=params, engine_name=name),
        name=name,
        _blocking_timeout_s=900.0,
    )
    engine = ray_tpu.get_actor(f"llm_engine:{name}")
    # The ingress reports healthy while the engine actor is still warming
    # its programs; its first answer marks the end of warmup.
    boot = ray_tpu.get(engine.metrics.remote(), timeout=1100.0)
    warmup_s = time.monotonic() - t0
    compiles = ray_tpu.get(engine.flight_record.remote(0))["compile_events"]
    host, port = start_proxy("127.0.0.1", 0, 600.0)
    url = f"http://{host}:{port}/{name}"

    outputs: dict = {}
    timings: dict = {}
    errors: list = []

    def blocking(key):
        body = {"prompt_ids": prompts[key], "max_new_tokens": new_tokens}
        with post(url, body) as response:
            outputs[key] = json.loads(response.read())["result"]["token_ids"]

    def streaming(key):
        body = {
            "prompt_ids": prompts[key], "max_new_tokens": new_tokens,
            "stream": True,
        }
        start = time.monotonic()
        tokens = []
        with post(url + "?stream=1", body) as response:
            for line in response:
                if line.strip():
                    tokens.append(json.loads(line)["result"]["token_id"])
                    timings.setdefault("ttft_s", time.monotonic() - start)
        outputs[key] = tokens

    def guarded(fn, key):
        try:
            fn(key)
        except BaseException as exc:  # noqa: BLE001 — reported below
            errors.append(f"{key}: {exc!r}")

    try:
        blocking("repeat_first")
        threads = [
            threading.Thread(target=guarded, args=(fn, key), daemon=True)
            for key, fn in (
                ("short", blocking), ("chunked", blocking),
                ("repeat", blocking), ("stream", streaming),
                ("short2", blocking),
            )
        ]
        start = time.monotonic()
        for thread in threads:
            thread.start()
        for thread in threads:
            thread.join(timeout=900.0)
        batch_s = time.monotonic() - start
        check(not any(t.is_alive() for t in threads), "a request never returned")
        check(not errors, f"requests failed: {errors}")
        metrics = ray_tpu.get(engine.metrics.remote(), timeout=60.0)
        report = ray_tpu.get(engine.device_report.remote(), timeout=600.0)
        dead = ray_tpu.get(engine.dead_letters.remote(), timeout=60.0)
    finally:
        serve.shutdown()  # proxy, replicas, controller
        ray_tpu.get(engine.shutdown.remote(), timeout=60.0)
        ray_tpu.kill(engine)

    for key, tokens in outputs.items():
        check(len(tokens) == new_tokens, f"{key}: {len(tokens)} tokens")
    check(metrics["attn_impl"] == "pallas", f"attn_impl {metrics['attn_impl']}")
    check(not dead and not metrics["wedged"], f"dead letters {dead}")
    check(metrics["prefix_cache_hit_tokens"] > 0, "no prefix-cache hit")
    check(metrics["chunked_prefill_requests"] > 0, "no chunked prefill")
    check(
        metrics["kv_pool_allocated"] == boot["kv_pool_allocated"] == 0,
        f"KV blocks still allocated after drain: {metrics['kv_pool_allocated']}",
    )
    concurrent = len(outputs) - 1
    return {
        "outputs": outputs,
        "metrics": metrics,
        "report": report,
        "warmup_s": warmup_s,
        "compile_events_s": round(sum(e["compile_s"] for e in compiles), 3),
        "programs_warmed": len(compiles),
        "ttft_s": timings["ttft_s"],
        "tokens_per_s": concurrent * new_tokens / batch_s,
    }


class Reference:
    """The plain reference: the same weights through `model.apply` in
    float32 with the XLA reference attention, one sequence at a time."""

    def __init__(self, cfg, params, prompts, new_tokens: int):
        import jax
        import jax.numpy as jnp

        from ray_tpu.models.gpt import GPT

        # One padded length, so one program: the longest sequence a judged
        # loop reaches, rounded up to 128.
        longest = max(len(p) for p in prompts.values()) + new_tokens
        self.max_len = -(-longest // 128) * 128
        model = GPT(
            dataclasses.replace(
                cfg, dtype=jnp.float32, attention_impl="reference"
            )
        )
        noisy = GPT(dataclasses.replace(cfg, attention_impl="reference"))

        def logits_at(apply_model, params, tokens, n):
            with jax.default_matmul_precision("highest"):
                logits = apply_model.apply(params, tokens)
            return logits[0, n - 1].astype(jnp.float32)

        self._params = params
        self._logits = jax.jit(lambda p, t, n: logits_at(model, p, t, n))
        self._noisy = jax.jit(lambda p, t, n: logits_at(noisy, p, t, n))

    def _padded(self, tokens):
        import numpy as np

        padded = np.zeros((1, self.max_len), np.int32)
        padded[0, : len(tokens)] = tokens
        return padded

    def logits(self, tokens):
        import numpy as np

        out = np.asarray(
            self._logits(self._params, self._padded(tokens), len(tokens))
        )
        check(bool(np.isfinite(out).all()), "reference logits not finite")
        return out

    def bf16_noise(self, tokens) -> float:
        """Largest move of a next-token logit when the same dense forward
        runs in the model's dtype instead of float32."""
        import numpy as np

        noisy = np.asarray(
            self._noisy(self._params, self._padded(tokens), len(tokens))
        )
        return float(np.abs(noisy - self.logits(tokens)).max())

    def judge(self, prompt, got) -> dict:
        """Greedy loop against `got`: equal, or the gap between the two
        candidates' reference logits where they first differ."""
        tokens = list(prompt)
        for position, token in enumerate(got):
            logits = self.logits(tokens)
            want = int(logits.argmax())
            if want != token:
                gap = float(logits[want] - logits[token])
                return {
                    "equal": False, "first_diff": position,
                    "logit_gap": gap, "ok": gap < LOGIT_TOLERANCE,
                }
            tokens.append(want)
        return {"equal": True, "ok": True}


def placement_checks(result: dict, platform: str, chips: int) -> dict:
    """Weights and pools sit where the engine says: on `chips` distinct
    devices of the platform, each holding its shard's bytes of the pool."""
    report, metrics = result["report"], result["metrics"]
    for key in ("param_bytes_by_device", "pool_bytes_by_device"):
        devices = sorted(report[key])
        check(
            len(devices) == chips
            and all(d.startswith(platform + ":") for d in devices),
            f"{key} on {devices}, expected {chips} {platform} device(s)",
        )
    pool = report["pool_bytes_by_device"]
    check(
        set(pool.values()) == {metrics["kv_pool_bytes_per_shard"]},
        f"pool bytes per device {pool} != kv_pool_bytes_per_shard "
        f"{metrics['kv_pool_bytes_per_shard']}",
    )
    pool_bytes = sum(pool.values())
    return {
        "devices": sorted(pool),
        "param_bytes_by_device": report["param_bytes_by_device"],
        "kv_pool_bytes": pool_bytes,
        "kv_pool_bytes_per_device": pool_bytes // chips,
        "decode_temp_bytes": report["decode_temp_bytes"],
        "decode_argument_bytes": report["decode_argument_bytes"],
        "decode_temp_over_pool": round(
            report["decode_temp_bytes"] / (pool_bytes // chips), 3
        ),
        "decode_kernels": report["decode_kernels"],
        "decode_collectives": report["decode_collectives"],
    }


def serve_phase(args, device: dict) -> None:
    from ray_tpu.llm.config import EngineConfig

    model, cfg, geometry, prompts, new_tokens = serving_setup(args)
    ecfg = EngineConfig(**geometry)
    params = make_params(cfg, args.seed)
    entries_before = cache_entries(device["cache_dir"])
    result = serve_prompts("smoke", cfg, ecfg, params, prompts, new_tokens)
    metrics = result["metrics"]
    placement = placement_checks(result, device["platform"], 1)

    reference = Reference(cfg, params, prompts, new_tokens)
    verdicts = {
        key: reference.judge(prompts[key], tokens)
        for key, tokens in result["outputs"].items()
    }
    noise = reference.bf16_noise(prompts["stream"])
    emit(
        "serve",
        ok=all(v["ok"] for v in verdicts.values()),
        model=model,
        engine=geometry,
        attn_impl=metrics["attn_impl"],
        requests=len(verdicts),
        prefix_cache_hit_tokens=metrics["prefix_cache_hit_tokens"],
        chunked_prefill_requests=metrics["chunked_prefill_requests"],
        prefill_token_budget=metrics["prefill_token_budget"],
        dead_letters=metrics["num_dead_letters"],
        wedged=metrics["wedged"],
        kv_pool_allocated_after_drain=metrics["kv_pool_allocated"],
        reference=verdicts,
        logit_tolerance=LOGIT_TOLERANCE,
        bf16_logit_noise=noise,
        **placement,
    )
    emit(
        "serve_first_measured",
        warmup_s=round(result["warmup_s"], 3),
        warmup_compile_events_s=result["compile_events_s"],
        programs_warmed=result["programs_warmed"],
        compile_cache_cold=device["cache_entries"] == 0,
        compile_cache_entries_before=entries_before,
        compile_cache_entries_after=cache_entries(device["cache_dir"]),
        ttft_s=round(result["ttft_s"], 4),
        tokens_per_s=round(result["tokens_per_s"], 2),
        concurrent_requests=len(verdicts) - 1,
    )
    check(all(v["ok"] for v in verdicts.values()), f"reference: {verdicts}")


# ------------------------------------------------------------------ train


def train_phase(args, device: dict) -> None:
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.gpt import GPTConfig, gpt2_125m
    from ray_tpu.train import JaxTrainer, ScalingConfig

    if args.cpu_rehearsal:
        cfg = GPTConfig(
            vocab_size=512, num_layers=2, num_heads=4, embed_dim=64,
            max_seq_len=128, dtype=jnp.bfloat16, attention_impl="flash",
        )
        batch, seq, chips = 2, 128, 0
    else:
        # The per-chip batch bench.py measures with.
        cfg = gpt2_125m(attention_impl="flash", dtype=jnp.bfloat16)
        batch, seq, chips = 24, 1024, 1
    steps = 6

    def train_loop(config):
        import flax.linen as nn
        import jax
        import optax

        from ray_tpu import train
        from ray_tpu.models.gpt import GPT, cross_entropy_loss

        model = GPT(cfg)
        key = jax.random.PRNGKey(config["seed"])
        tokens = jax.random.randint(key, (batch, seq), 0, cfg.vocab_size)
        params = train.prepare_params(
            nn.meta.unbox(jax.jit(model.init)(key, tokens))
        )
        tokens = train.prepare_batch(tokens)
        tx = optax.adamw(3e-4)
        opt_state = jax.jit(tx.init)(params)

        def step(params, opt_state, tokens):
            def loss_fn(p):
                logits = model.apply(p, tokens)
                return cross_entropy_loss(logits[:, :-1], tokens[:, 1:])

            loss, grads = jax.value_and_grad(loss_fn)(params)
            updates, opt_state = tx.update(grads, opt_state, params)
            return optax.apply_updates(params, updates), opt_state, loss

        jit_step = train.prepare_step(step, donate_argnums=(0, 1))
        for i in range(steps):
            t0 = time.monotonic()
            params, opt_state, loss = jit_step(params, opt_state, tokens)
            loss = float(loss)
            platforms = sorted(
                {
                    d.platform
                    for leaf in jax.tree_util.tree_leaves(params)
                    for d in leaf.devices()
                }
            )
            train.report(
                {
                    "step": i, "loss": loss, "platforms": platforms,
                    "step_s": time.monotonic() - t0,
                }
            )

    memory = jax.devices()[0].memory_stats() or {}
    t0 = time.monotonic()
    result = JaxTrainer(
        train_loop,
        train_loop_config={"seed": args.seed},
        scaling_config=ScalingConfig(num_workers=1, chips_per_worker=chips),
    ).fit()
    if result.error is not None:
        raise result.error
    history = result.metrics_history
    losses = [m["loss"] for m in history]
    ok = (
        len(losses) == steps
        and all(loss == loss and abs(loss) != float("inf") for loss in losses)
        and losses[-1] < losses[0]
        and history[-1]["platforms"] == [device["platform"]]
    )
    emit(
        "train",
        ok=ok,
        model="gpt2_125m" if not args.cpu_rehearsal else "toy",
        attention_impl=cfg.attention_impl,
        batch=batch,
        seq=seq,
        losses=[round(loss, 4) for loss in losses],
        param_platforms=history[-1]["platforms"],
        first_step_s=round(history[0]["step_s"], 3),
        last_step_s=round(history[-1]["step_s"], 4),
        fit_s=round(time.monotonic() - t0, 3),
        hbm_bytes_in_use_before=memory.get("bytes_in_use"),
    )
    check(ok, f"train: losses {losses}, params on {history[-1]['platforms']}")


# ------------------------------------------------------------ four chips


def tensor_parallel_phase(args, device: dict) -> None:
    """tp = 4 against tp = 1 in one process: same prompts, same weights,
    same rule. The reference is asked only where the two disagree."""
    from ray_tpu.llm.config import EngineConfig

    model, cfg, geometry, prompts, new_tokens = serving_setup(args)
    params = make_params(cfg, args.seed)
    results = {}
    for tp in (args.chips, 1):
        ecfg = EngineConfig(**geometry, tensor_parallel_size=tp)
        results[tp] = serve_prompts(
            f"smoke-tp{tp}", cfg, ecfg, params, prompts, new_tokens
        )
        gc.collect()
    sharded, single = results[args.chips], results[1]
    placement = placement_checks(sharded, device["platform"], args.chips)
    placement_checks(single, device["platform"], 1)
    check(
        sharded["metrics"]["kv_pool_sharding"] is not None
        and "tp" in sharded["metrics"]["kv_pool_sharding"],
        f"pools not head-sharded: {sharded['metrics']['kv_pool_sharding']}",
    )

    reference = Reference(cfg, params, prompts, new_tokens)
    verdicts = {}
    for key, got in sharded["outputs"].items():
        want = single["outputs"][key]
        if got == want:
            verdicts[key] = {"equal": True, "ok": True}
            continue
        position = next(i for i, (a, b) in enumerate(zip(got, want)) if a != b)
        logits = reference.logits(prompts[key] + want[:position])
        gap = abs(float(logits[want[position]] - logits[got[position]]))
        verdicts[key] = {
            "equal": False, "first_diff": position, "logit_gap": gap,
            "ok": gap < LOGIT_TOLERANCE,
        }
    ok = all(v["ok"] for v in verdicts.values())
    emit(
        "tensor_parallel",
        ok=ok,
        model=model,
        tensor_parallel_size=args.chips,
        engine=geometry,
        tp_vs_single=verdicts,
        logit_tolerance=LOGIT_TOLERANCE,
        kv_pool_sharding=sharded["metrics"]["kv_pool_sharding"],
        kv_pool_bytes_per_shard=sharded["metrics"]["kv_pool_bytes_per_shard"],
        **placement,
    )
    emit(
        "tensor_parallel_first_measured",
        **{
            f"tp{tp}_{key}": round(r[key], 4)
            for tp, r in results.items()
            for key in ("warmup_s", "programs_warmed", "ttft_s", "tokens_per_s")
        },
        compile_cache_entries_after=cache_entries(device["cache_dir"]),
    )
    check(ok, f"tp={args.chips} disagrees with tp=1: {verdicts}")


# ------------------------------------------------------------------- main


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--chips", type=int, choices=(1, 4), default=1)
    parser.add_argument("--cpu-rehearsal", action="store_true")
    parser.add_argument("--seed", type=int, default=0)
    args = parser.parse_args(argv)
    if args.cpu_rehearsal:
        # Before jax is imported: the CPU backend, with as many virtual
        # devices as the path under rehearsal has chips.
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={args.chips}"
        ).strip()

    import ray_tpu

    device = device_phase(args)
    try:
        if args.chips > 1:
            tensor_parallel_phase(args, device)
        else:
            serve_phase(args, device)
            gc.collect()
            train_phase(args, device)
    finally:
        from ray_tpu import serve

        serve.shutdown()
        ray_tpu.shutdown()
    if args.cpu_rehearsal:
        emit("rehearsal_done", note="a CPU rehearsal proves no chip run")
        return 0
    print(
        json.dumps(
            {
                "ok": True,
                "device": {
                    "platform": device["platform"],
                    "kind": device["kind"],
                    "count": device["count"],
                },
            }
        ),
        flush=True,
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
