"""Differential GPT-2 profiling: every measurement is a FULL train step with
one factor changed, so the per-call dispatch overhead cancels in the
subtraction. Run from the repository root."""
import functools
import time

import jax
import jax.numpy as jnp
import optax

from ray_tpu.models import GPT, cross_entropy_loss, gpt2_125m

B, S = 24, 1024


def measure(name, cfg, opt="adamw", head=True, iters=10, warmup=3):
    model = GPT(cfg)
    key = jax.random.PRNGKey(0)
    tokens = jax.random.randint(key, (B, S), 0, cfg.vocab_size)
    params = jax.jit(model.init)(key, tokens)
    tx = optax.adamw(3e-4) if opt == "adamw" else optax.sgd(0.1)

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def step(params, opt_state, tokens):
        def loss_fn(p):
            out = model.apply(p, tokens)
            if head:
                return cross_entropy_loss(out[:, :-1], tokens[:, 1:])
            # headless probe: logits still produced by apply; reduce cheaply
            return out.astype(jnp.float32).mean()

        loss, grads = jax.value_and_grad(loss_fn)(params)
        updates, opt_state = tx.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    opt_state = jax.jit(tx.init)(params)
    p, o = params, opt_state
    for _ in range(warmup):
        p, o, loss = step(p, o, tokens)
    float(loss)
    t0 = time.perf_counter()
    for _ in range(iters):
        p, o, loss = step(p, o, tokens)
    float(loss)
    dt = (time.perf_counter() - t0) / iters
    print(f"{name:44s} {dt*1e3:8.2f} ms  ({B*S/dt:,.0f} tok/s)", flush=True)
    return dt


base = dict(attention_impl="flash", dtype=jnp.bfloat16)
t12 = measure("12L flash adamw (baseline)", gpt2_125m(**base))
t6 = measure("6L flash adamw", gpt2_125m(num_layers=6, **base))
print(f"  -> per-layer fwd+bwd: {(t12-t6)/6*1e3:.2f} ms  (x12 = {(t12-t6)*2*1e3:.1f} ms)")
t12_ref = measure("12L reference-attn adamw", gpt2_125m(attention_impl="reference", dtype=jnp.bfloat16))
print(f"  -> flash vs reference: {(t12-t12_ref)*1e3:+.2f} ms")
t12_sgd = measure("12L flash sgd", gpt2_125m(**base), opt="sgd")
print(f"  -> adamw cost: {(t12-t12_sgd)*1e3:.2f} ms")
t12_nohead = measure("12L flash adamw meanloss", gpt2_125m(**base), head=False)
print(f"  -> CE loss vs mean loss: {(t12-t12_nohead)*1e3:.2f} ms")
# vocab 768 shrinks the head matmul ~65x: isolates head matmul + loss together
t12_smallv = measure("12L flash adamw V=768", gpt2_125m(vocab_size=768, **base))
print(f"  -> head+loss (V=50304 vs 768): {(t12-t12_smallv)*1e3:.2f} ms")
