"""Reference attention math (pure JAX).

Ground truth for the Pallas kernels and the CPU fallback path. Array layout is
[batch, seq, heads, head_dim] (flax convention) everywhere in the ops package.
The reference framework has no attention ops at all (SURVEY.md §2.4: SP/ring
attention absent upstream) — this subsystem is net-new, designed TPU-first.
"""

from __future__ import annotations

import math
from typing import Optional, Sequence

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def mha_reference(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = False,
    sm_scale: Optional[float] = None,
    bias: Optional[jax.Array] = None,
) -> jax.Array:
    """Full-matrix multi-head attention. q,k,v: [B, S, H, D] → [B, S, H, D]."""
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(q.shape[-1])
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32)
    logits = logits * sm_scale
    if bias is not None:
        logits = logits + bias
    if causal:
        q_len, k_len = logits.shape[-2], logits.shape[-1]
        mask = jnp.tril(jnp.ones((q_len, k_len), dtype=bool), k_len - q_len)
        logits = jnp.where(mask, logits, NEG_INF)
    weights = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", weights.astype(v.dtype), v)
    return out.astype(q.dtype)


def validate_tp_heads(
    num_heads: int, tensor_parallel_size: int, role: str = "model"
) -> None:
    """One shared contract for every tensor-parallel entry point (the
    runner sharding weights/pools, the dispatcher head-slicing the
    kernels): the head count must divide evenly across the tp axis.
    Uneven head sharding either trace-fails deep inside GSPMD or pads —
    both far worse failure modes than this config-time error, and the
    target and draft model must BOTH pass (a draft with an incompatible
    head count would shard its mirror pool differently from the target's,
    breaking the shared block-id geometry)."""
    if tensor_parallel_size > 1 and num_heads % tensor_parallel_size:
        raise ValueError(
            f"{role} num_heads {num_heads} is not divisible by "
            f"tensor_parallel_size {tensor_parallel_size}: attention heads "
            "(and with them the paged KV pools) shard on the head axis, so "
            "every chip must own the same number of heads"
        )


def head_sharded_call(mesh, fn, args, in_specs: Sequence):
    """Run `fn(*args)` SPMD over the mesh's `tp` axis, each argument under
    its PartitionSpec, the result head-sharded.

    Every head-carrying array of the paged-attention signature is one of
    two shapes: q/new_k/new_v [B, S, H, D] put H at dim 2
    (`LLM_HEAD_SPEC`), the stored pools [L, N, bs, H*D] and their scales
    [L, N, bs, H] put the heads on dim 3 (`LLM_POOL_SPEC`: a head is a
    contiguous lane group, so an even split of H*D is a split by heads).
    Inside the shard each kernel instance sees (and DMAs) only its local
    heads' slice of the cache blocks. Block tables and context lengths
    replicate (`P()`): block ids are shard-invariant."""
    from ray_tpu._private.jax_compat import shard_map
    from ray_tpu.parallel.sharding import LLM_HEAD_SPEC

    return shard_map(
        fn, mesh=mesh, in_specs=tuple(in_specs), out_specs=LLM_HEAD_SPEC,
        check_vma=False,
    )(*args)


def head_sharded_attention(
    mesh,
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    impl: str = "auto",
    causal: bool = True,
    sm_scale: Optional[float] = None,
) -> jax.Array:
    """Dense causal attention head-sliced over the mesh's `tp` axis (the
    full-prefill program under tensor parallelism): q/k/v [B, S, H, D]
    arrive head-sharded from the column-parallel qkv projection, each
    shard attends its local heads, and the output stays head-sharded for
    the row-parallel output projection. No collective — heads never mix
    inside attention."""
    from ray_tpu.ops.flash_attention import attention as attention_op
    from ray_tpu.parallel.sharding import LLM_HEAD_SPEC

    validate_tp_heads(q.shape[2], mesh.shape["tp"])
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(q.shape[-1])

    def shard(q, k, v):
        return attention_op(
            q, k, v, causal=causal, sm_scale=sm_scale, impl=impl
        )

    return head_sharded_call(mesh, shard, (q, k, v), (LLM_HEAD_SPEC,) * 3)


def validate_kv_pools(
    q, k_cache, v_cache, k_scale, v_scale, num_kv_heads: Optional[int] = None
) -> None:
    """One shared contract for both paged-attention implementations, so
    impl='auto' can never accept inputs on one backend that the other
    rejects: the pools are the stored form [L, N, bs, H*D] for q's
    [B, S, H, D] and share a dtype, int8 pools require BOTH dequant
    scales ([L, N, bs, H]), and scales require int8 pools (silently
    dropping or applying them would diverge). Under grouped-query
    attention H is `num_kv_heads`, the cached heads, a divisor of q's."""
    h, d = q.shape[2:]
    if num_kv_heads is not None and num_kv_heads != h:
        if h % num_kv_heads:
            raise ValueError(
                f"{h} query heads are not a multiple of {num_kv_heads} "
                "cached heads"
            )
        if k_cache.dtype == jnp.int8:
            raise ValueError(
                "int8 pools are not implemented for grouped-query attention"
            )
        h = num_kv_heads
    for name, pool, minor in (
        ("k_cache", k_cache, h * d), ("v_cache", v_cache, h * d),
        ("k_scale", k_scale, h), ("v_scale", v_scale, h),
    ):
        if pool is not None and (pool.ndim != 4 or pool.shape[3] != minor):
            raise ValueError(
                f"{name} has shape {pool.shape}; for {h} heads of {d} the "
                f"stored form is [L, N, bs, {minor}] (heads and head size "
                "merged on the minor axis, every layer in one array)"
            )
    if k_cache.dtype != v_cache.dtype:
        raise ValueError(
            f"k_cache/v_cache dtypes differ ({k_cache.dtype} vs "
            f"{v_cache.dtype}); the pools must share one storage dtype"
        )
    if (k_scale is not None or v_scale is not None) and k_cache.dtype != jnp.int8:
        raise ValueError(
            f"k_scale/v_scale passed with non-int8 cache pools "
            f"({k_cache.dtype}): dequant scales only apply to int8 pools"
        )
    if k_cache.dtype == jnp.int8 and (k_scale is None or v_scale is None):
        raise ValueError("int8 k_cache/v_cache require k_scale/v_scale")


def dequantize_kv(values: jax.Array, scales: jax.Array) -> jax.Array:
    """Inverse of ops.paged_flash.quantize_kv, in f32: values [..., H, D]
    * scales [..., H]. Lives here, next to the shared scale contract, so
    the reference op and the fused kernel's tests share ONE definition of
    the quantization semantics."""
    return values.astype(jnp.float32) * scales.astype(jnp.float32)[..., None]


def paged_attention(
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
    window: Optional[int] = None,
) -> jax.Array:
    """Attention over the paged KV cache through per-sequence block tables.

    The KV cache is paged: `k_cache`/`v_cache` are the pools as the runner
    stores them, [num_layers, num_blocks, block_size, H*D] (heads and head
    size merged on the minor axis), read at `layer`, and each sequence
    owns a list of block ids. Shapes are fully
    static — every sequence gathers `max_blocks_per_seq * block_size` cache
    slots and positions >= its `context_len` are masked, so XLA compiles one
    program regardless of how long each sequence actually is.

    Handles both generation paths of ray_tpu.llm with one program shape:
    decode is S == 1 (one new token per slot); prefix-aware partial prefill
    is S > 1 (the uncached suffix of a prompt whose prefix K/V is already
    resident) — paged attention over the cached prefix, causal among the
    suffix tokens. Queries at suffix offset i attend every cached position
    plus new tokens 0..i.

    q:            [B, S, H, D]  new-token queries per batch slot.
    k_cache:      [L, N, bs, H*D] shared block pool (block 0 of every layer
                  is the null block); H and D are q's.
    block_tables: [B, nb] int32, padded with 0 past each sequence's blocks.
    context_lens: [B] int32 — tokens already written to the cache.
    new_k/new_v:  [B, S, H, D] the new tokens' K/V. They have not been
                  scattered into the cache yet, so they ride along as extra
                  always-gathered slots under a causal (j <= i) mask.
    k_scale/v_scale: [L, N, bs, H] per-token dequant scales for int8 cache
                  pools (ops.paged_flash.quantize_kv); the gathered pages
                  are dequantized in f32 before use, making this op the
                  exact oracle for the fused kernel's int8 path.
    window:       query i of a slot is the token at position
                  context_len + i and sees the keys at positions
                  p - window < j <= p only (None: every j <= p).

    Fully-masked rows (a padded slot with context_len 0 and no new
    tokens) return exact zeros rather than a uniform average of garbage
    gathered through the null block.

    Returns [B, S, H, D].
    """
    b, q_len, h, d = q.shape
    nb = block_tables.shape[1]
    bs = k_cache.shape[2]
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(d)
    # Grouped-query attention: the pools (and new_k / new_v) hold fewer
    # heads than q has, each shared by a group of consecutive query heads.
    h_q = h
    h = new_k.shape[2] if new_k is not None else k_cache.shape[3] // d
    validate_kv_pools(q, k_cache, v_cache, k_scale, v_scale, h)
    # Gather the pages: [B, nb, bs, H*D] -> [B, nb*bs, H, D].
    k_ctx = k_cache[layer, block_tables].reshape(b, nb * bs, h, d)
    v_ctx = v_cache[layer, block_tables].reshape(b, nb * bs, h, d)
    if k_scale is not None:
        k_ctx = dequantize_kv(
            k_ctx, k_scale[layer, block_tables].reshape(b, nb * bs, h)
        ).astype(q.dtype)
    if v_scale is not None:
        v_ctx = dequantize_kv(
            v_ctx, v_scale[layer, block_tables].reshape(b, nb * bs, h)
        ).astype(q.dtype)
    # [B, Q, K] mask: every query sees every valid cached position.
    valid = jnp.broadcast_to(
        (jnp.arange(nb * bs)[None, :] < context_lens[:, None])[:, None, :],
        (b, q_len, nb * bs),
    )
    if window is not None:
        q_pos = context_lens[:, None] + jnp.arange(q_len)[None, :]  # [B, Q]
        valid = valid & (
            jnp.arange(nb * bs)[None, None, :] + window > q_pos[:, :, None]
        )
    if new_k is not None:
        s_new = new_k.shape[1]
        k_ctx = jnp.concatenate([k_ctx, new_k], axis=1)
        v_ctx = jnp.concatenate([v_ctx, new_v], axis=1)
        causal = jnp.tril(jnp.ones((q_len, s_new), dtype=bool), s_new - q_len)
        if window is not None:
            causal = causal & ~jnp.tril(
                jnp.ones((q_len, s_new), dtype=bool), s_new - q_len - window
            )
        valid = jnp.concatenate(
            [valid, jnp.broadcast_to(causal[None], (b, q_len, s_new))], axis=2
        )
    if h != h_q:
        k_ctx = jnp.repeat(k_ctx, h_q // h, axis=2)
        v_ctx = jnp.repeat(v_ctx, h_q // h, axis=2)
    logits = jnp.einsum(
        "bqhd,bkhd->bhqk", q, k_ctx, preferred_element_type=jnp.float32
    )
    logits = logits * sm_scale
    logits = jnp.where(valid[:, None, :, :], logits, NEG_INF)
    weights = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    if new_k is None or new_k.shape[1] < q_len:
        # Softmax over an all-NEG_INF row degrades to uniform weights over
        # whatever the null block holds; masked/empty slots must contribute
        # exact zeros instead (the finalize_partial l == 0 hygiene). With
        # new tokens riding along at s_new >= q_len — every engine step —
        # the causal diagonal guarantees each query at least one valid
        # key, so this pass is statically skipped on the hot path.
        any_valid = jnp.any(valid, axis=-1)  # [B, Q]
        weights = weights * any_valid[:, None, :, None]
    out = jnp.einsum("bhqk,bkhd->bqhd", weights.astype(v_ctx.dtype), v_ctx)
    return out.astype(q.dtype)


def _chunk_attn_partial(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    sm_scale: float,
    mask: Optional[jax.Array],
):
    """One blockwise-attention partial: returns (o_unnorm, m, l) in f32 so
    partials from different KV chunks can be merged with log-sum-exp algebra.

    q: [B, Sq, H, D]; k, v: [B, Sk, H, D]; mask: broadcastable to [B, H, Sq, Sk].
    """
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32)
    logits = logits * sm_scale
    if mask is not None:
        logits = jnp.where(mask, logits, NEG_INF)
    m = jnp.max(logits, axis=-1)  # [B, H, Sq]
    p = jnp.exp(logits - m[..., None])
    if mask is not None:
        p = p * mask  # kill exp(0)=1 rows when everything was masked
    l = jnp.sum(p, axis=-1)  # [B, H, Sq]
    o = jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32))
    return o, m, l


def merge_partials(o1, m1, l1, o2, m2, l2):
    """Merge two blockwise softmax partials (the flash/ring update rule)."""
    m = jnp.maximum(m1, m2)
    a1 = jnp.exp(m1 - m)
    a2 = jnp.exp(m2 - m)
    l = l1 * a1 + l2 * a2
    # o is [B, Sq, H, D]; scales are [B, H, Sq] -> [B, Sq, H, 1]
    s1 = jnp.transpose(a1, (0, 2, 1))[..., None]
    s2 = jnp.transpose(a2, (0, 2, 1))[..., None]
    o = o1 * s1 + o2 * s2
    return o, m, l


def finalize_partial(o, m, l):
    """Normalize an accumulated partial into the final attention output."""
    denom = jnp.where(l == 0.0, 1.0, l)
    scale = jnp.transpose(1.0 / denom, (0, 2, 1))[..., None]
    return o * scale
