"""Paged-attention kernel vs XLA reference: serving decode / partial-prefill
sweep over batch x context-length buckets.

For each config the two implementations run the SAME jitted program shape
the engine compiles (fixed block-table width, 0-padded tables, new-token
K/V ride-along) and report per-step wall time plus two bandwidth views:

  * effective HBM GB/s — the bytes the step *needs*: each sequence's live
    context K/V (ctx tokens, int8 + scales when quantized) plus q / new
    K/V / output. This is the number to compare against the chip's HBM
    bandwidth: decode is memory-bound, so the winning implementation is
    the one whose step time approaches needed_bytes / HBM_BW.
  * touched GB — what each implementation actually moves. The reference's
    `k_cache[block_tables]` writes the full padded [B, nb*bs, H, D] gather
    to HBM (then reads it back for the matmul), independent of how short
    each sequence really is. The fused kernel copies only the table
    entries a sequence's context reaches (whole blocks: the context rounded
    up to the block size), HBM→VMEM once each, eight or sixteen blocks a
    128-token compute block, never writing a gathered copy back; what a
    table holds past the context, and a padded slot, cost no copy at all.

Run:  python benchmarks/profile_attn_paged.py [--quick] [--json-out PATH]
      [--impl pallas|reference|both] [--int8] [--tp N]

--tp N runs every config head-sliced over an N-chip tensor-parallel mesh
through the engine's dispatcher (shard_map over the `tp` axis) and asserts
the output matches the single-chip op — the sweep doubles as the parity
oracle for the mesh path. On CPU use the virtual host-device mesh
(XLA_FLAGS=--xla_force_host_platform_device_count=N).

On CPU the kernel runs in Pallas interpret mode — orders of magnitude
slower than compiled, useful only for parity. Timings are meaningful on
TPU; the microbenchmark row `serving_decode_attn_*` tracks the same
comparison in BENCH_* sweeps.
"""

from __future__ import annotations

import argparse
import json
import math
import time

import numpy as np

import jax
import jax.numpy as jnp

from ray_tpu.ops.attention import paged_attention
from ray_tpu.ops.paged_flash import (
    KV_SCALE_DTYPE,
    kv_pool_bytes,
    paged_attention_impl,
    paged_flash_attention,
    quantize_kv,
)

RESULTS: list[dict] = []


def _report(row: dict) -> None:
    RESULTS.append(row)
    print(json.dumps(row), flush=True)


def _build_case(rng, b, s, ctx, h, d, bs, nb, dtype, int8: bool):
    """Engine-shaped inputs: per-row tables 0-padded past ceil(ctx/bs),
    pools in the stored form ([1, N, bs, H*D], scales [1, N, bs, H])."""
    num_blocks = b * nb + 1
    q = jnp.asarray(rng.randn(b, s, h, d), dtype)
    new_k = jnp.asarray(rng.randn(b, s, h, d), dtype)
    new_v = jnp.asarray(rng.randn(b, s, h, d), dtype)
    k_cache = jnp.asarray(rng.randn(num_blocks, bs, h, d), dtype)
    v_cache = jnp.asarray(rng.randn(num_blocks, bs, h, d), dtype)
    tables = np.zeros((b, nb), np.int32)
    used = math.ceil(ctx / bs)
    ids = np.arange(1, num_blocks)
    for i in range(b):
        tables[i, :used] = ids[i * nb : i * nb + used]
    lens = jnp.full((b,), ctx, jnp.int32)
    k_scale = v_scale = None
    if int8:
        k_cache, k_scale = quantize_kv(k_cache)
        v_cache, v_scale = quantize_kv(v_cache)
        k_scale, v_scale = k_scale[None], v_scale[None]
    k_cache = k_cache.reshape(1, num_blocks, bs, h * d)
    v_cache = v_cache.reshape(1, num_blocks, bs, h * d)
    return q, k_cache, v_cache, jnp.asarray(tables), lens, new_k, new_v, \
        k_scale, v_scale


def _time_step(fn, *args, iters: int) -> float:
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters


def run_config(
    *, phase: str, b: int, s: int, ctx: int, h: int, d: int, bs: int,
    nb: int, impls, int8: bool, iters: int, dtype, mesh=None,
) -> None:
    rng = np.random.RandomState(0)
    case = _build_case(rng, b, s, ctx, h, d, bs, nb, dtype, int8)
    q, kc, vc, tables, lens, nk, nv, ks, vs = case
    elem = np.dtype(dtype).itemsize
    kv_elem = 1 if int8 else elem
    scale_b = np.dtype(KV_SCALE_DTYPE).itemsize if int8 else 0
    # Bytes the step NEEDS: live context K+V per sequence + small tensors.
    needed = (
        2 * b * ctx * h * (d * kv_elem + scale_b)
        + 4 * b * s * h * d * elem  # q, new_k, new_v, out
    )
    # Bytes the reference MOVES: the padded pool read (K + V, pool dtype)
    # plus the gathered copy written and read back — int8 pools are
    # dequantized into full-precision q.dtype copies, so the materialized
    # gather is elem-sized regardless of pool dtype.
    ref_touched = (
        2 * b * nb * bs * h * (d * kv_elem + scale_b)
        + 2 * 2 * b * nb * bs * h * d * elem
        + 4 * b * s * h * d * elem  # q, new_k, new_v, out (pool read above)
    )
    # Bytes the kernel COPIES: the K and V blocks each row's context
    # reaches (the context rounded up to whole blocks), read once into
    # VMEM, never written back. (int8 scales come through an XLA gather of
    # the whole table width, written and read back: small beside the pool.)
    pallas_touched = (
        2 * b * math.ceil(ctx / bs) * bs * h * d * kv_elem
        + 3 * 2 * b * nb * bs * h * scale_b
        + 4 * b * s * h * d * elem
    )
    tp = mesh.shape["tp"] if mesh is not None else 1
    for impl in impls:
        op = paged_flash_attention if impl == "pallas" else paged_attention
        if mesh is not None:
            # Tensor-parallel axis: the SAME op head-sliced over the tp
            # mesh via the engine's dispatcher (shard_map, each instance
            # sees h/tp local heads). Outputs must match the single-chip
            # run — the sweep is also the parity oracle for the mesh path.
            fn = jax.jit(
                lambda q, kc, vc, t, l, nk, nv, impl=impl: (
                    paged_attention_impl(
                        q, kc, vc, t, l, new_k=nk, new_v=nv,
                        k_scale=ks, v_scale=vs, impl=impl, mesh=mesh,
                    )
                )
            )
            base = op(
                q, kc, vc, tables, lens, new_k=nk, new_v=nv,
                k_scale=ks, v_scale=vs,
            )
            np.testing.assert_allclose(
                np.asarray(fn(q, kc, vc, tables, lens, nk, nv), np.float32),
                np.asarray(base, np.float32),
                atol=5e-2 if dtype == jnp.bfloat16 else 1e-5,
            )
        else:
            fn = jax.jit(
                lambda q, kc, vc, t, l, nk, nv, op=op: op(
                    q, kc, vc, t, l, new_k=nk, new_v=nv,
                    k_scale=ks, v_scale=vs,
                )
            )
        dt = _time_step(fn, q, kc, vc, tables, lens, nk, nv, iters=iters)
        _report(
            {
                "benchmark": f"paged_attn_{phase}",
                "impl": impl,
                "tp": tp,
                "kv": "int8" if int8 else np.dtype(dtype).name,
                "batch": b,
                "q_len": s,
                "context": ctx,
                "heads": h,
                "head_dim": d,
                "block_size": bs,
                "table_width": nb,
                "step_ms": round(dt * 1e3, 4),
                "effective_hbm_gbps": round(needed / dt / 1e9, 2),
                "touched_gb_per_step": round(
                    (ref_touched if impl == "reference" else pallas_touched)
                    / 1e9, 4
                ),
            }
        )


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--quick", action="store_true", help="tiny CPU-sized sweep")
    p.add_argument("--impl", default="both",
                   choices=("both", "pallas", "reference"))
    p.add_argument("--int8", action="store_true",
                   help="also sweep int8 KV pools")
    p.add_argument("--tp", type=int, default=1,
                   help="tensor-parallel degree: run every config "
                        "head-sliced over a tp mesh (parity-asserted "
                        "against the single-chip op; heads must divide)")
    p.add_argument("--json-out", default="")
    args = p.parse_args()

    mesh = None
    if args.tp > 1:
        from ray_tpu.parallel.mesh import tensor_parallel_mesh

        mesh = tensor_parallel_mesh(args.tp)

    on_cpu = jax.devices()[0].platform == "cpu"
    if on_cpu:
        print("# CPU backend: the kernel runs in interpret mode — parity "
              "only, timings are meaningful on TPU", flush=True)
    impls = ("pallas", "reference") if args.impl == "both" else (args.impl,)

    if args.quick or on_cpu:
        h, d, bs, iters, dtype = 4, 32, 8, 3, jnp.float32
        decode_grid = [(4, 64), (8, 128)]
        prefill_grid = [(2, 16, 64)]
        nb_for = lambda ctx: max(ctx // bs * 2, 8)
    else:
        h, d, bs, iters, dtype = 12, 64, 16, 20, jnp.bfloat16
        decode_grid = [
            (b, ctx) for b in (8, 16, 32) for ctx in (128, 256, 512, 1024)
        ]
        prefill_grid = [(8, 64, 256), (8, 128, 512), (16, 64, 512)]
        nb_for = lambda ctx: 1024 // bs

    quant = (False, True) if args.int8 else (False,)
    for int8 in quant:
        for b, ctx in decode_grid:
            run_config(
                phase="decode", b=b, s=1, ctx=ctx, h=h, d=d, bs=bs,
                nb=nb_for(ctx), impls=impls, int8=int8, iters=iters,
                dtype=dtype, mesh=mesh,
            )
        for b, s, ctx in prefill_grid:
            run_config(
                phase="partial_prefill", b=b, s=s, ctx=ctx, h=h, d=d, bs=bs,
                nb=nb_for(ctx), impls=impls, int8=int8, iters=iters,
                dtype=dtype, mesh=mesh,
            )

    # Capacity: sequences resident in the same pool bytes (the reason int8
    # exists — more sequences in flight = more continuous batching). At the
    # serving shape (head_dim 64, the whole GPT-2 family): values halve and
    # the per-token bf16 scale adds 2 bytes per 64, so ~1.94x sequences fit.
    sh, sd, sbs = 12, 64, 16
    bf16_block = kv_pool_bytes(1, sbs, sh, sd, jnp.bfloat16, with_scales=False)
    int8_block = kv_pool_bytes(1, sbs, sh, sd, jnp.int8, with_scales=True)
    _report(
        {
            "benchmark": "paged_kv_int8_capacity_ratio",
            "value": round(bf16_block / int8_block, 4),
            "unit": "x sequences in the same pool bytes",
            "heads": sh,
            "head_dim": sd,
            "block_size": sbs,
        }
    )
    if args.json_out:
        with open(args.json_out, "w") as f:
            json.dump(RESULTS, f, indent=2)


if __name__ == "__main__":
    main()
