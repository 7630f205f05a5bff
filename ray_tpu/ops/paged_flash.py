"""Fused Pallas paged-attention kernel for the serving hot path.

The XLA-assembled decode path (`ops.paged_attention`) gathers whole pages —
`k_cache[block_tables]` materializes [B, nb*bs, H, D] in HBM every step —
and runs a full-matrix softmax over [B, H, Q, K] logits. This kernel walks
each sequence's block table *inside the pipeline*: the grid is
(batch, q tiles, nb + q tiles) with the kv dimension sequential, and the
k/v BlockSpec index maps read the scalar-prefetched block table, so each
grid step DMAs exactly one [bs, H*D] cache block of one layer into VMEM.
Block gather, QK^T, validity masking, streaming (online) softmax, and the
weighted-V accumulation all happen in one pass; neither the gathered pages
nor the logits ever touch HBM. The final grid step folds in the
not-yet-scattered new tokens' K/V under a causal mask and normalizes —
fully-masked rows (a padded slot with context_len 0 and no new tokens) come
out as exact zeros, matching `finalize_partial`'s l == 0 hygiene.

The pools arrive as the runner stores them, [L, N, bs, H*D]: heads and head
size merged into one lane-dense minor axis, all layers in one array. That
is the form the device keeps row-major, which is what a Mosaic call takes
its operands in — a pool whose minor dimensions are [H, D] = [20, 64] is
kept with the block count minor-most, and every program that handed it to
the kernel converted the whole pool on the way in and back out. The layer
is picked in the index map ((layer, table[b, j], 0, 0)), never sliced out
in XLA, and head h is lanes h*D:(h+1)*D of the block.

Covers both program shapes ray_tpu.llm compiles: decode (S == 1) and
prefix-aware partial prefill (S > 1, the uncached suffix attends the cached
prefix through the table and itself causally). `ops.paged_attention` is the
correctness oracle; interpret mode on CPU runs the same code path in tests.

int8 KV cache rides on top: the cache pools store int8 with per-token,
per-head scales (written by `quantize_kv` at scatter time — per-token
scales are the only granularity a one-token decode scatter can maintain
without requantizing the rest of the block). Dequantization is fused into
the block loop, folded into the score/weight matrices: K's scale multiplies
the [S, bs] score columns after QK^T and V's scale folds into the softmax
weights before PV, so the kernel never materializes a dequantized block.
Scales are stored bfloat16 (math in f32): at block_size=8, head_dim=64 the
pool + scale bytes per token come to ~52% of bf16, so the same HBM holds
~1.9x the sequences.
"""

from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops.attention import (
    NEG_INF,
    dequantize_kv,  # noqa: F401 — canonical home; re-exported via ops
    head_sharded_call,
    paged_attention,
    validate_kv_pools,
    validate_tp_heads,
)
from ray_tpu.ops.flash_attention import _on_cpu

_LANES = 128  # TPU lane width: min trailing dim for scratch tiles
# VMEM one q tile's blocks may take. The compiler's default scoped limit on
# v5e is 16 MiB; the rest is left to the cache blocks and the kernel's own
# temporaries (float32 at "highest" matmul precision took 4.4 MiB of them
# at 20 heads, measured on the chip).
_Q_TILE_VMEM_BYTES = 10 * 1024 * 1024

# Storage dtype for the KV-cache scale tensors. bf16 keeps the scale
# overhead at 2 bytes per (token, head) — f32 scales at block_size=8 would
# eat the capacity win the int8 pool exists for. All scale MATH is f32;
# quantization divides by the bf16-rounded scale so the round trip is
# consistent with what the kernel will dequantize with.
KV_SCALE_DTYPE = jnp.bfloat16


def quantize_kv(x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Symmetric per-token, per-head int8 quantization of K or V.

    x: [..., H, D] (any leading shape) → (values int8 [..., H, D],
    scales KV_SCALE_DTYPE [..., H]). Scales are amax/127 per (token, head)
    so a single decode token's scatter writes its own scale slot and never
    touches neighbors — the property that makes quantization compatible
    with the paged cache's per-token writes (per-block scales would need
    the whole block requantized on every append).
    """
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=-1)
    scale = jnp.maximum(amax / 127.0, 1e-8).astype(KV_SCALE_DTYPE)
    # Quantize with the *stored* (bf16-rounded) scale; clip because the
    # rounding can shrink the scale by ~0.4%, pushing x/scale past 127.
    q = jnp.clip(
        jnp.round(xf / scale.astype(jnp.float32)[..., None]), -127.0, 127.0
    ).astype(jnp.int8)
    return q, scale


def _online_update(s, h, m_scr, l_scr, acc_scr, p_scale, v_block, out_dtype):
    """One streaming-softmax step for head `h`: fold the score block `s`
    ([tq, block]) and its value rows into the running (m, l, acc) scratch.
    `p_scale` optionally rescales the softmax weights columnwise (int8 V
    dequant folded into P instead of into a [block, D] dequant pass)."""
    m_prev = m_scr[h][:, 0:1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    # Masked lanes hold NEG_INF: exp underflows to exactly 0.
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_new = l_scr[h][:, 0:1] * alpha + jnp.sum(p, axis=1, keepdims=True)
    if p_scale is not None:
        p = p * p_scale
    acc_scr[h] = acc_scr[h] * alpha + jax.lax.dot_general(
        p.astype(out_dtype), v_block, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    m_scr[h] = jnp.broadcast_to(m_new, m_scr[h].shape)
    l_scr[h] = jnp.broadcast_to(l_new, l_scr[h].shape)


def _paged_kernel(
    # scalar prefetch
    tables_ref, lens_ref,
    # inputs
    q_ref, k_ref, v_ref, nk_ref, nv_ref, *rest,
    heads: int, bs: int, nb: int, tq: int, quantized: bool,
):
    """Grid (B, nq, nb + nq): one q tile of `tq` fed tokens per (b, qi).
    Steps j < nb consume cache block table[b, j] (skipped past
    context_lens[b]); steps j >= nb fold new-token tile j - nb in causally
    (skipped above the diagonal); the last step normalizes. Running max /
    sum / accumulator live in VMEM scratch across the sequential kv
    dimension. q / new-token K/V / out blocks are heads-leading
    [1, H, tq, D]: Mosaic tiles the last two dims, so a per-head [tq, D]
    view must not have the head dim between them. Cache blocks are
    [bs, H*D] (scales [bs, H]): head h is a lane slice."""
    if quantized:
        ks_ref, vs_ref, o_ref, m_scr, l_scr, acc_scr = rest
    else:
        o_ref, m_scr, l_scr, acc_scr = rest
        ks_ref = vs_ref = None
    b = pl.program_id(0)
    qi = pl.program_id(1)
    j = pl.program_id(2)
    compute_dtype = q_ref.dtype
    d = q_ref.shape[-1]

    @pl.when(j == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    ctx = lens_ref[b]

    # Blocks entirely past the context contribute nothing: skip their
    # compute (their copies still run, through the null block — the
    # data-dependent skip of the copies defeats the pipeline's prefetch,
    # same trade as ops/flash_attention.py).
    @pl.when((j < nb) & (j * bs < ctx))
    def _cache_block():
        for h in range(heads):
            q = q_ref[0, h]  # [tq, D], prescaled by sm_scale
            k = k_ref[:, h * d:(h + 1) * d]  # [bs, D] (int8 when quantized)
            s = jax.lax.dot_general(
                q, k.astype(compute_dtype), (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )  # [tq, bs]
            p_scale = None
            if quantized:
                # Dequant folded into the score/weight matrices: K's
                # per-token scale multiplies score columns, V's rescales
                # the softmax weights — both [tq, bs] ops, never [bs, D].
                s = s * ks_ref[:, h].astype(jnp.float32)[None, :]
                p_scale = vs_ref[:, h].astype(jnp.float32)[None, :]
            t_ids = j * bs + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(t_ids < ctx, s, NEG_INF)
            _online_update(
                s, h, m_scr, l_scr, acc_scr, p_scale,
                v_ref[:, h * d:(h + 1) * d].astype(compute_dtype),
                compute_dtype,
            )

    t = j - nb  # new-token tile this step would fold in

    @pl.when((t >= 0) & (t <= qi))
    def _new_tokens():
        for h in range(heads):
            q = q_ref[0, h]    # [tq, D]
            nk = nk_ref[0, h]  # [tq, D] — new tokens, never quantized
            s = jax.lax.dot_general(
                q, nk, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )  # [tq, tq]
            rows = qi * tq + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            cols = t * tq + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(rows >= cols, s, NEG_INF)
            _online_update(
                s, h, m_scr, l_scr, acc_scr, None, nv_ref[0, h],
                compute_dtype,
            )

    @pl.when(j == pl.num_programs(2) - 1)
    def _finalize():
        for h in range(heads):
            l = l_scr[h][:, 0:1]
            safe = jnp.where(l == 0.0, 1.0, l)
            # Fully-masked rows (context_len 0 and no valid new token)
            # normalize to exact zeros, not garbage — finalize_partial's
            # l == 0 hygiene.
            o_ref[0, h] = jnp.where(
                l == 0.0, 0.0, acc_scr[h] / safe
            ).astype(o_ref.dtype)


def _q_tile(s_len: int, heads: int, head_dim: int, itemsize: int) -> int:
    """Fed tokens per q tile: the largest tile whose blocks fit
    _Q_TILE_VMEM_BYTES — q, new K, new V and out double-buffered in the
    compute dtype plus the float32 running max, sum and accumulator, every
    [tile, D] slab padded to whole lanes."""
    lanes = -(-head_dim // _LANES) * _LANES
    row_bytes = heads * lanes * (4 * 2 * itemsize + 3 * 4)
    tile = next(
        (t for t in (128, 64, 32) if t * row_bytes <= _Q_TILE_VMEM_BYTES), 16
    )
    return min(s_len, tile)


def resolve_paged_impl(impl: str) -> str:
    """Resolve the `impl` knob to a concrete implementation: 'auto' picks
    the fused kernel only on an actual TPU backend and the XLA reference
    everywhere else (CPU, GPU — the kernel's PrefetchScalarGridSpec and
    compiler params lower for TPU only; CPU gets it via interpret mode
    when forced). The single owner of that policy — the engine (tagging
    metrics/flight records) and the dispatcher below both call this, so
    they can never disagree."""
    if impl not in ("auto", "pallas", "reference"):
        raise ValueError(f"Unknown paged attention impl {impl!r}")
    if impl == "auto":
        return (
            "pallas" if jax.devices()[0].platform == "tpu" else "reference"
        )
    return impl


def paged_flash_attention(
    q: jax.Array,
    k_cache: jax.Array,
    v_cache: jax.Array,
    block_tables: jax.Array,
    context_lens: jax.Array,
    *,
    new_k: jax.Array,
    new_v: jax.Array,
    layer: int = 0,
    sm_scale: Optional[float] = None,
    k_scale: Optional[jax.Array] = None,
    v_scale: Optional[jax.Array] = None,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Fused paged attention over the block-table KV cache (Pallas TPU).

    Same contract as :func:`ray_tpu.ops.paged_attention` — q [B, S, H, D],
    k/v_cache [L, N, bs, H*D] pools read at `layer`, block_tables [B, nb]
    (0-padded), context_lens [B] — except `new_k`/`new_v` are REQUIRED
    (every generation step of ray_tpu.llm carries the new tokens' K/V; a
    cache-only query should use the reference op). S == 1 is decode,
    S > 1 is prefix-aware partial prefill. When the cache pools are int8,
    `k_scale`/`v_scale` [L, N, bs, H] carry the per-token dequant scales
    (see `quantize_kv`).

    Runs in interpret mode on CPU by default so tests exercise the same
    kernel the TPU compiles.
    """
    if new_k is None or new_v is None:
        raise ValueError(
            "paged_flash_attention requires new_k/new_v (the engine always "
            "carries the new tokens' K/V); use ops.paged_attention for "
            "cache-only queries"
        )
    validate_kv_pools(q, k_cache, v_cache, k_scale, v_scale)
    quantized = k_cache.dtype == jnp.int8
    b, s_len, h, d = q.shape
    nb = block_tables.shape[1]
    bs = k_cache.shape[2]
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(d)
    if interpret is None:
        interpret = _on_cpu()
    # Prescale q once outside the kernel (fused into the producing matmul's
    # epilogue by XLA): no per-score-element scale pass inside.
    q = (q.astype(jnp.float32) * sm_scale).astype(q.dtype)

    # The fed tokens are tiled over S so VMEM holds one [H, tq, D] tile of
    # q / new K / new V / out and its statistics, whatever the bucket. A
    # length that is not a whole number of tiles is zero-padded: padded key
    # columns sit above every real row's diagonal, padded q rows are cut.
    tq = _q_tile(s_len, h, d, q.dtype.itemsize)
    nq = -(-s_len // tq)
    pad = nq * tq - s_len

    def heads_leading(x):  # [B, S, H, D] -> [B, H, nq * tq, D]
        x = x.transpose(0, 2, 1, 3)
        return jnp.pad(x, ((0, 0), (0, 0), (0, pad), (0, 0))) if pad else x

    def q_map(bi, qi, j, tables_ref, lens_ref):
        return (bi, 0, qi, 0)

    def new_map(bi, qi, j, tables_ref, lens_ref):
        # New-token tile j - nb, clamped into [0, qi]: the cache walk and
        # the skipped above-diagonal steps re-name a tile that is already
        # resident, so they copy nothing.
        return (bi, 0, jnp.clip(j - nb, 0, qi), 0)

    def block_id(bi, j, tables_ref):
        # Walk the block table: grid step j pipelines cache block
        # table[b, j] into VMEM. The new-token steps (j >= nb) and padded
        # steps read the null block — copied but never unmasked.
        return jnp.where(j < nb, tables_ref[bi, jnp.minimum(j, nb - 1)], 0)

    def kv_map(bi, qi, j, tables_ref, lens_ref):
        # The layer is chosen here, in the copy's address: the pool goes
        # into the call whole and as stored, so XLA neither slices a layer
        # out of it nor converts its layout.
        return (layer, block_id(bi, j, tables_ref), 0, 0)

    in_specs = [
        pl.BlockSpec((1, h, tq, d), q_map),
        pl.BlockSpec((None, None, bs, h * d), kv_map),
        pl.BlockSpec((None, None, bs, h * d), kv_map),
        pl.BlockSpec((1, h, tq, d), new_map),
        pl.BlockSpec((1, h, tq, d), new_map),
    ]
    operands = [
        heads_leading(q), k_cache, v_cache,
        heads_leading(new_k), heads_leading(new_v),
    ]
    if quantized:
        in_specs += [
            pl.BlockSpec((None, None, bs, h), kv_map),
            pl.BlockSpec((None, None, bs, h), kv_map),
        ]
        operands += [k_scale, v_scale]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, nq, nb + nq),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, h, tq, d), q_map),
        scratch_shapes=[
            pltpu.VMEM((h, tq, _LANES), jnp.float32),
            pltpu.VMEM((h, tq, _LANES), jnp.float32),
            pltpu.VMEM((h, tq, d), jnp.float32),
        ],
    )
    kernel = functools.partial(
        _paged_kernel, heads=h, bs=bs, nb=nb, tq=tq, quantized=quantized
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, nq * tq, d), q.dtype),
        # Batch and q tiles parallel; the block-table walk is sequential
        # (online softmax state lives in scratch across kv steps).
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
    )(block_tables, context_lens, *operands)
    return out[:, :, :s_len].transpose(0, 2, 1, 3)


def paged_attention_impl(
    q: jax.Array,
    k_cache: jax.Array,
    v_cache: jax.Array,
    block_tables: jax.Array,
    context_lens: jax.Array,
    *,
    new_k: Optional[jax.Array] = None,
    new_v: Optional[jax.Array] = None,
    layer: int = 0,
    sm_scale: Optional[float] = None,
    k_scale: Optional[jax.Array] = None,
    v_scale: Optional[jax.Array] = None,
    impl: str = "auto",
    mesh=None,
) -> jax.Array:
    """Dispatcher: the fused Pallas kernel on TPU, the XLA reference
    elsewhere (impl='auto'); 'pallas' forces the kernel (interpret mode on
    CPU), 'reference' forces the gather+softmax reference. Both take the
    pools as stored ([L, N, bs, H*D], read at `layer`). A cache-only
    query (new_k=None) is outside the kernel's contract: 'auto' falls back
    to the reference, 'pallas' raises (inside paged_flash_attention).

    `mesh` (a Mesh whose `tp` axis is > 1) runs the chosen implementation
    head-sliced over the tensor-parallel axis via shard_map: each chip's
    instance receives only its local heads' q / new-token K/V / cache and
    scale pool slices (heads are contiguous lane groups of the pools'
    minor axis), so the kernel's per-block DMA touches local-head bytes
    only and the attention output comes back head-sharded with no
    collective (heads never mix inside attention — the psum this layering
    implies happens later, in the attn output projection)."""
    resolved = resolve_paged_impl(impl)
    use_reference = resolved == "reference" or (
        impl == "auto" and new_k is None
    )
    op = paged_attention if use_reference else paged_flash_attention
    if mesh is not None and mesh.shape.get("tp", 1) > 1:
        from jax.sharding import PartitionSpec as P

        from ray_tpu.parallel.sharding import LLM_HEAD_SPEC, LLM_POOL_SPEC

        validate_tp_heads(q.shape[2], mesh.shape["tp"])
        if sm_scale is None:
            sm_scale = 1.0 / math.sqrt(q.shape[-1])
        args = [q, k_cache, v_cache, block_tables, context_lens]
        specs = [LLM_HEAD_SPEC, LLM_POOL_SPEC, LLM_POOL_SPEC, P(), P()]
        if new_k is not None:
            args += [new_k, new_v]
            specs += [LLM_HEAD_SPEC, LLM_HEAD_SPEC]
        if k_scale is not None:
            args += [k_scale, v_scale]
            specs += [LLM_POOL_SPEC, LLM_POOL_SPEC]

        def sharded(q, k_cache, v_cache, block_tables, context_lens,
                    *rest):
            nk = nv = ks = vs = None
            if new_k is not None:
                nk, nv, *rest = rest
            if k_scale is not None:
                ks, vs = rest
            return op(
                q, k_cache, v_cache, block_tables, context_lens,
                new_k=nk, new_v=nv, layer=layer, sm_scale=sm_scale,
                k_scale=ks, v_scale=vs,
            )

        return head_sharded_call(mesh, sharded, args, specs)
    return op(
        q, k_cache, v_cache, block_tables, context_lens,
        new_k=new_k, new_v=new_v, layer=layer, sm_scale=sm_scale,
        k_scale=k_scale, v_scale=v_scale,
    )


def kv_pool_bytes(
    num_blocks: int, block_size: int, heads: int, head_dim: int,
    kv_dtype, with_scales: bool,
) -> int:
    """Total bytes of one K or V pool (+ its scale tensor when int8):
    the honest denominator for capacity-ratio claims."""
    values = (
        num_blocks * block_size * heads * head_dim * np.dtype(kv_dtype).itemsize
    )
    if with_scales:
        values += (
            num_blocks * block_size * heads * np.dtype(KV_SCALE_DTYPE).itemsize
        )
    return values
