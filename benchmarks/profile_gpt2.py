"""Profile the GPT-2 125M train step at bench shapes on the real TPU.

Times the full step plus isolated components (attention fwd/bwd, LM head +
loss, optimizer) so the gap to the 150k tokens/s/chip parity mark can be
attributed. Run from the repository root.
"""
import functools
import time

import jax
import jax.numpy as jnp
import optax

from ray_tpu.models import GPT, cross_entropy_loss, gpt2_125m

B, S = 24, 1024


def sync(x):
    leaf = jax.tree_util.tree_leaves(x)[0]
    return float(jnp.sum(leaf.astype(jnp.float32)) if leaf.ndim else leaf)


def timeit(name, fn, *args, iters=10, warmup=3, tokens=B * S):
    for _ in range(warmup):
        out = fn(*args)
    sync(out if not isinstance(out, tuple) else out[-1])
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    sync(out if not isinstance(out, tuple) else out[-1])
    dt = (time.perf_counter() - t0) / iters
    print(f"{name:34s} {dt*1e3:8.2f} ms  ({tokens/dt:,.0f} tok/s)")
    return dt


cfg = gpt2_125m(attention_impl="flash", dtype=jnp.bfloat16)
model = GPT(cfg)
key = jax.random.PRNGKey(0)
tokens = jax.random.randint(key, (B, S), 0, cfg.vocab_size)
params = jax.jit(model.init)(key, tokens)
n_params = sum(x.size for x in jax.tree_util.tree_leaves(params))
print(f"params: {n_params/1e6:.1f}M; dtypes: "
      f"{ {str(x.dtype) for x in jax.tree_util.tree_leaves(params)} }")
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


# full step (non-donated copy cost excluded by reusing outputs)
p, o = params, opt_state
for _ in range(3):
    p, o, loss = step(p, o, tokens)
sync(loss)
t0 = time.perf_counter()
for _ in range(10):
    p, o, loss = step(p, o, tokens)
sync(loss)
dt = (time.perf_counter() - t0) / 10
print(f"{'full train step':34s} {dt*1e3:8.2f} ms  ({B*S/dt:,.0f} tok/s)")

# forward only
fwd = jax.jit(lambda p, t: cross_entropy_loss(model.apply(p, t)[:, :-1], t[:, 1:]))
timeit("fwd only (loss)", fwd, p, tokens)

# fwd+bwd without optimizer
grad_fn = jax.jit(lambda p, t: jax.value_and_grad(
    lambda q: cross_entropy_loss(model.apply(q, t)[:, :-1], t[:, 1:]))(p))
timeit("fwd+bwd (no opt)", grad_fn, p, tokens)

# attention alone at bench shapes: 12 layers worth
from ray_tpu.ops.flash_attention import flash_attention

H, D = cfg.num_heads, cfg.head_dim
q = jax.random.normal(key, (B, S, H, D), jnp.bfloat16)
attn_fwd = jax.jit(lambda q: flash_attention(q, q, q, causal=True))
timeit("flash fwd x1 layer", attn_fwd, q)
attn_grad = jax.jit(jax.grad(lambda q: flash_attention(q, q, q, causal=True)
                             .astype(jnp.float32).sum()))
timeit("flash fwd+bwd x1 layer", attn_grad, q)

# LM head + loss alone (tied embedding): x [B,S,E] -> loss
E, V = cfg.embed_dim, cfg.vocab_size
x = jax.random.normal(key, (B, S, E), jnp.bfloat16)
wte = jax.random.normal(key, (V, E), jnp.float32) * 0.02
def head_loss(wte, x):
    logits = x @ wte.astype(jnp.bfloat16).T
    return cross_entropy_loss(logits[:, :-1], tokens[:, 1:])
head_grad = jax.jit(jax.grad(head_loss, argnums=(0, 1)))
timeit("LM head+loss fwd+bwd", head_grad, wte, x)

# optimizer alone
grads = jax.tree_util.tree_map(jnp.ones_like, p)
opt_only = jax.jit(lambda g, o, p: tx.update(g, o, p))
timeit("adamw update", opt_only, grads, o, p)

# dispatch overhead: tiny jit call
tiny = jax.jit(lambda x: x + 1)
timeit("tiny dispatch", tiny, jnp.zeros((8, 128), jnp.bfloat16))
