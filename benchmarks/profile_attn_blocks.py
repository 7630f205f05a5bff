"""Flash-attention block-size sweep at GPT-2 bench shapes.

12 chained fwd+bwd per dispatch so the per-call overhead is noise."""
import functools
import time

import jax
import jax.numpy as jnp

from ray_tpu.ops.flash_attention import flash_attention

B, S, H, D = 24, 1024, 12, 64
key = jax.random.PRNGKey(0)
x = jax.random.normal(key, (B, S, H, D), jnp.bfloat16)


def run(bq, bk, iters=5):
    @jax.jit
    def chained(x):
        def f(x):
            y = x
            for _ in range(12):
                y = flash_attention(y, y, y, causal=True, block_q=bq, block_k=bk)
            return y.astype(jnp.float32).sum()
        return jax.grad(f)(x)

    g = chained(x)
    float(g[0, 0, 0, 0])
    t0 = time.perf_counter()
    for _ in range(iters):
        g = chained(x)
    float(g[0, 0, 0, 0])
    dt = (time.perf_counter() - t0) / iters
    # FLOPs if nothing were skipped: 2 fwd + 7 bwd matmuls, each 2*S*S*D per bh
    full_tf = 12 * 9 * 2 * S * S * D * B * H / 1e12
    print(f"bq={bq:5d} bk={bk:5d}  {dt*1e3:8.2f} ms   ({full_tf/dt:６.1f} TF/s-equiv)",
          flush=True)
    return dt


for bq, bk in [(1024, 1024), (512, 512), (512, 1024), (1024, 512),
               (256, 256), (256, 512), (512, 256), (128, 128), (256, 1024)]:
    run(bq, bk)
