"""Jitted prefill / decode step functions over the paged KV cache.

XLA compiles O(1) programs: one decode program (fixed [max_decode_slots]
batch, fixed block-table width), one full-prefill program per power-of-two
bucket, one *partial*-prefill program per bucket (prefix caching: feed only
the uncached suffix at a position offset and attend to the cached prefix
through the block table — paged attention over the prefix, causal over the
suffix), one block-to-block copy (copy-on-write for shared blocks), and —
with speculative decoding on — one batched k-token verify program per fed
width bucket (the partial-prefill shape generalized to [max_decode_slots]
slots with per-slot position offsets, returning the argmax at EVERY fed
position so the engine can accept the longest agreeing proposal prefix).
The cache pools are [L, num_blocks, block_size, H*D] device arrays — heads
and head size merged on one lane-dense minor axis, the form the device
keeps row-major and the paged kernel reads as it is — threaded
functionally through every step with donated buffers, so steps update the
cache in place without host round-trips and no program converts a pool.
Nor a weight: the runner holds its matrices in the compute dtype, rounded
once when it takes them, so a step reads each as it multiplies it.

Serving hot-path knobs (EngineConfig):

  * ``attn_impl`` — the decode / partial-prefill programs read the cache
    either through the fused Pallas kernel (``ops.paged_flash``: the block
    table is walked inside the kernel pipeline, gather + QK^T + masking +
    online softmax + weighted-V in one pass) or the XLA gather+softmax
    reference. "auto" resolves once at construction: pallas on TPU,
    reference elsewhere. Warmup compiles every bucket program with whatever
    was resolved, so the kernel never cold-compiles under live traffic.
  * ``kv_cache_dtype`` — "int8" stores the pools quantized with per-token
    per-head scale tensors [L, N, bs, H] (scales ride every scatter and
    block copy); dequantization is fused into the attention op. ~1.9x the
    sequences fit the same pool bytes.
  * ``tensor_parallel_size`` — > 1 builds a `tp` mesh over the backend
    devices and runs ALL FIVE programs SPMD over it: weights shard
    Megatron-style from the model's logical axis annotations, the cache /
    scale pools shard on their minor axis BY HEADS (a head is a contiguous
    lane group of it, so each chip's kernel instance DMAs only its local
    heads' cache blocks), attention runs head-sliced under shard_map, and the
    donated pool buffers stay sharded through every step (the returned
    pools carry an explicit sharding constraint, so donation aliases
    buffer-for-buffer and nothing ever gathers). Block ids are
    shard-invariant — the allocator/scheduler stay host-global.
"""

from __future__ import annotations

import functools
import re
import threading
from typing import Callable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu._private.jax_setup import ensure_compile_cache, host_cpu_device
from ray_tpu.llm import program_store
from ray_tpu.llm.cache import kv_pool_bytes_sharded
from ray_tpu.llm.config import EngineConfig
from ray_tpu.models.gpt import (
    GPT,
    GPTConfig,
    collect_kv_caches,
    serving_params,
)
from ray_tpu.ops.attention import validate_tp_heads
from ray_tpu.util.device_report import bytes_by_device  # noqa: F401  (hybrid_runner's too)
from ray_tpu.ops.paged_flash import (
    decode_tile,
    KV_SCALE_DTYPE,
    q_tile,
    quantize_kv,
    resolve_paged_impl,
)


class _StepPrograms:
    """The five jitted programs for one (model geometry, block size,
    attention impl, KV dtype, tensor-parallel degree) configuration.

    Shared process-wide through `_step_programs`: jax's compilation cache
    keys on the *callable*, so per-runner bound methods recompile
    everything for every engine instance — a replica restart, a draft
    model, every test engine. One `_StepPrograms` per config makes each
    (program, shapes) pair compile once per process; a same-config runner
    built later warms up through pure cache hits. Entries hold only
    config-derived state (the model *definition*, mesh, pool sharding) —
    never params or pools — so a cached entry costs bytes, not HBM.

    `jit` makes a program of a traced function: `jax.jit`, or beside a
    compile cache `program_store.stored_jit`, whose jits run the programs'
    stored modules and a later PROCESS reads instead of tracing again.
    """

    def __init__(
        self,
        model_config: GPTConfig,
        block_size: int,
        attn_impl: str,
        kv_cache_dtype,
        tensor_parallel_size: int,
        jit: Callable = jax.jit,
    ):
        self.model_config = model_config
        self.block_size = block_size
        self.attn_impl = attn_impl
        self.kv_cache_dtype = kv_cache_dtype
        self.quantized = kv_cache_dtype == jnp.int8
        self.model = GPT(model_config)
        if tensor_parallel_size > 1:
            from ray_tpu.parallel.mesh import tensor_parallel_mesh
            from ray_tpu.parallel.sharding import llm_pool_sharding

            self.mesh = tensor_parallel_mesh(tensor_parallel_size)
            self.pool_sharding = llm_pool_sharding(self.mesh)
        else:
            self.mesh = None
            self.pool_sharding = None
        self.decode_fn = jit(self._decode_step, donate_argnums=(1, 2, 3, 4))
        self.verify_fn = jit(self._verify_step, donate_argnums=(1, 2, 3, 4))
        self.prefill_fn = jit(self._prefill_step, donate_argnums=(1, 2, 3, 4))
        self.prefill_suffix_fn = jit(
            self._prefill_suffix_step, donate_argnums=(1, 2, 3, 4)
        )
        self.copy_block_fn = jit(
            self._copy_block_step, donate_argnums=(0, 1, 2, 3)
        )
        self.restore_block_fn = jit(
            self._restore_block_step, donate_argnums=(0, 1, 2, 3)
        )
        self.join_token_fn = jit(join_token)

    # ---------------- traced helpers ----------------

    def _constrain_pools(self, pools):
        """Pin the returned pools to the head-sharded layout inside every
        jitted program: the constraint makes the donated input buffers and
        the outputs provably alias (same shape, dtype AND sharding), so no
        step can silently reshard — or worse, gather — a pool."""
        if self.pool_sharding is None:
            return pools
        return tuple(
            p
            if p is None
            else jax.lax.with_sharding_constraint(p, self.pool_sharding)
            for p in pools
        )

    def _paged_caches(self, k_cache, v_cache, k_scale, v_scale,
                      block_tables, context_lens):
        return (k_cache, v_cache, block_tables, context_lens, k_scale,
                v_scale)

    def _store_kv(self, new_kv: jax.Array) -> Tuple[jax.Array, Optional[jax.Array]]:
        """New-token K or V [..., H, D] → (pool-dtype values in the stored
        form [..., H*D], per-token scales [..., H] or None). int8 pools
        quantize at scatter time — per-token scales are what a
        single-token decode write can maintain."""
        stored = new_kv.shape[:-2] + (-1,)
        if self.quantized:
            values, scales = quantize_kv(new_kv)
            return values.reshape(stored), scales
        return new_kv.astype(self.kv_cache_dtype).reshape(stored), None

    # ---------------- the five step programs ----------------

    def _prefill_step(
        self, params, k_cache, v_cache, k_scale, v_scale, tokens, blocks,
        true_len,
    ):
        """tokens [1, S_bucket], blocks [S_bucket // bs] (0-padded),
        true_len scalar → (pools, next_token)."""
        cfg = self.model_config
        logits, state = self.model.apply(
            params, tokens, return_kv=True, mutable=["intermediates"],
            paged_mesh=self.mesh,
        )
        kvs = collect_kv_caches(state["intermediates"], cfg.num_layers)
        s = tokens.shape[1]
        paged = (s // self.block_size, self.block_size, -1)
        for layer, (k, v) in enumerate(kvs):
            kq, ks = self._store_kv(k[0])
            vq, vs = self._store_kv(v[0])
            k_cache = k_cache.at[layer, blocks].set(kq.reshape(paged))
            v_cache = v_cache.at[layer, blocks].set(vq.reshape(paged))
            if ks is not None:
                k_scale = k_scale.at[layer, blocks].set(ks.reshape(paged))
                v_scale = v_scale.at[layer, blocks].set(vs.reshape(paged))
        next_token = jnp.argmax(logits[0, true_len - 1, :]).astype(jnp.int32)
        pools = self._constrain_pools((k_cache, v_cache, k_scale, v_scale))
        return pools, next_token

    def _prefill_suffix_step(
        self, params, k_cache, v_cache, k_scale, v_scale, tokens,
        block_table, offset, true_len,
    ):
        """tokens [1, S_bucket] uncached suffix (0-padded), block_table
        [max_blocks_per_seq] the sequence's full table (0-padded), offset
        scalar = cached prefix length, true_len scalar = real suffix length
        → (pools, next_token).

        One program per suffix bucket: the suffix attends to the cached
        prefix through the block table (paged) and to itself causally, and
        its K/V is scattered token-by-token at positions offset..offset+S-1
        (padded lanes land in the null block)."""
        cfg = self.model_config
        sb = tokens.shape[1]
        lane = jnp.arange(sb)
        valid = lane < true_len
        positions = jnp.where(valid, offset + lane, 0)
        logits, state = self.model.apply(
            params,
            tokens,
            positions=positions[None, :],
            paged_caches=self._paged_caches(
                k_cache, v_cache, k_scale, v_scale,
                block_table[None, :], jnp.reshape(offset, (1,)),
            ),
            paged_impl=self.attn_impl,
            paged_mesh=self.mesh,
            mutable=["intermediates"],
        )
        kvs = collect_kv_caches(state["intermediates"], cfg.num_layers)
        bs = self.block_size
        block_ids = jnp.where(valid, block_table[positions // bs], 0)
        offsets = jnp.where(valid, positions % bs, 0)
        for layer, (k, v) in enumerate(kvs):
            kq, ks = self._store_kv(k[0])
            vq, vs = self._store_kv(v[0])
            k_cache = k_cache.at[layer, block_ids, offsets].set(kq)
            v_cache = v_cache.at[layer, block_ids, offsets].set(vq)
            if ks is not None:
                k_scale = k_scale.at[layer, block_ids, offsets].set(ks)
                v_scale = v_scale.at[layer, block_ids, offsets].set(vs)
        next_token = jnp.argmax(logits[0, true_len - 1, :]).astype(jnp.int32)
        pools = self._constrain_pools((k_cache, v_cache, k_scale, v_scale))
        return pools, next_token

    def _restore_block_step(
        self, k_cache, v_cache, k_scale, v_scale, dst, k, v, ks, vs
    ):
        """Write one spilled block's content back into slot `dst` — the KV
        fabric restore path. A scatter of host payloads, not a new model
        program: under tensor parallelism the sharding constraint re-pins
        the pools head-sharded, so a restore can never deshard the cache."""
        k_cache = k_cache.at[:, dst].set(k)
        v_cache = v_cache.at[:, dst].set(v)
        if k_scale is not None:
            k_scale = k_scale.at[:, dst].set(ks)
            v_scale = v_scale.at[:, dst].set(vs)
        return self._constrain_pools((k_cache, v_cache, k_scale, v_scale))

    def _copy_block_step(self, k_cache, v_cache, k_scale, v_scale, src, dst):
        k_cache = k_cache.at[:, dst].set(k_cache[:, src])
        v_cache = v_cache.at[:, dst].set(v_cache[:, src])
        if k_scale is not None:
            # int8 pools: a block copy must carry the dequant scales too,
            # or the CoW copy would be read back at the wrong magnitude.
            k_scale = k_scale.at[:, dst].set(k_scale[:, src])
            v_scale = v_scale.at[:, dst].set(v_scale[:, src])
        return self._constrain_pools((k_cache, v_cache, k_scale, v_scale))

    def _decode_step(
        self, params, k_cache, v_cache, k_scale, v_scale, tokens, positions,
        block_tables, context_lens,
    ):
        """One iteration-level decode over all slots. tokens/positions [B],
        block_tables [B, nb], context_lens [B] → (pools, next_tokens [B])."""
        bs = self.block_size
        b = tokens.shape[0]
        logits, state = self.model.apply(
            params,
            tokens[:, None],
            positions=positions[:, None],
            paged_caches=self._paged_caches(
                k_cache, v_cache, k_scale, v_scale, block_tables, context_lens
            ),
            paged_impl=self.attn_impl,
            paged_mesh=self.mesh,
            mutable=["intermediates"],
        )
        kvs = collect_kv_caches(
            state["intermediates"], self.model_config.num_layers
        )
        # Scatter each slot's new-token K/V at its absolute position. Idle
        # slots carry an all-null block table, so they land in block 0.
        block_ids = block_tables[jnp.arange(b), positions // bs]
        offsets = positions % bs
        for layer, (k, v) in enumerate(kvs):
            kq, ks = self._store_kv(k[:, 0])
            vq, vs = self._store_kv(v[:, 0])
            k_cache = k_cache.at[layer, block_ids, offsets].set(kq)
            v_cache = v_cache.at[layer, block_ids, offsets].set(vq)
            if ks is not None:
                k_scale = k_scale.at[layer, block_ids, offsets].set(ks)
                v_scale = v_scale.at[layer, block_ids, offsets].set(vs)
        next_tokens = jnp.argmax(logits[:, 0, :], axis=-1).astype(jnp.int32)
        pools = self._constrain_pools((k_cache, v_cache, k_scale, v_scale))
        return pools, next_tokens

    def _verify_step(
        self, params, k_cache, v_cache, k_scale, v_scale, tokens,
        block_tables, context_lens, true_lens,
    ):
        """Batched multi-token scoring for speculative decoding. tokens
        [B, S] = each slot's last committed token followed by its proposed
        tokens (0-padded past true_lens[b]); block_tables [B, nb];
        context_lens [B] = committed K/V per slot; true_lens [B] = fed
        tokens per slot (1 + that slot's proposals) → (pools, out [B, S]).

        The batched generalization of the partial-prefill program: slot b's
        fed tokens sit at absolute positions context_lens[b] + lane, attend
        the committed prefix through the block table (paged) and each other
        causally, and their K/V is scattered at those positions — so
        out[b, i], the argmax after consuming fed tokens 0..i, is exactly
        the token the plain decode loop would have produced at that point.
        int8 caveat: lanes attend EACH OTHER through their fresh
        full-precision K/V (new_k/new_v), while sequential decode reads
        the same tokens back quantized — the identical caveat partial
        prefill already carries — so under kv_cache_dtype="int8" the
        equivalence is within quantization tolerance (argmax-identical on
        the tested prompt set, not bit-guaranteed), exactly int8's own
        contract.
        Padded lanes (lane >= true_lens[b]) scatter into the null block and
        their outputs are garbage the engine never reads. The engine
        commits the longest proposal prefix agreeing with `out` and rolls
        the rest back (Scheduler.rollback); rejected lanes' K/V stays
        masked above the rewound context length."""
        cfg = self.model_config
        b, s = tokens.shape
        lane = jnp.arange(s)[None, :]
        valid = lane < true_lens[:, None]  # [B, S]
        positions = jnp.where(valid, context_lens[:, None] + lane, 0)
        logits, state = self.model.apply(
            params,
            tokens,
            positions=positions,
            paged_caches=self._paged_caches(
                k_cache, v_cache, k_scale, v_scale, block_tables,
                context_lens,
            ),
            paged_impl=self.attn_impl,
            paged_mesh=self.mesh,
            mutable=["intermediates"],
        )
        kvs = collect_kv_caches(state["intermediates"], cfg.num_layers)
        bs = self.block_size
        rows = jnp.arange(b)[:, None]
        block_ids = jnp.where(
            valid, block_tables[rows, positions // bs], 0
        )
        offsets = jnp.where(valid, positions % bs, 0)
        for layer, (k, v) in enumerate(kvs):
            kq, ks = self._store_kv(k)
            vq, vs = self._store_kv(v)
            k_cache = k_cache.at[layer, block_ids, offsets].set(kq)
            v_cache = v_cache.at[layer, block_ids, offsets].set(vq)
            if ks is not None:
                k_scale = k_scale.at[layer, block_ids, offsets].set(ks)
                v_scale = v_scale.at[layer, block_ids, offsets].set(vs)
        out = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        pools = self._constrain_pools((k_cache, v_cache, k_scale, v_scale))
        return pools, out


def join_token(tokens, lane, out):
    """A decode's token input with lane `lane` set to the token a chunk
    program sampled (the first value of its output `out`), all of it on
    the device: how a prompt joins a decode batch before the host has read
    its first token. Outside the decode program, which keeps its
    signature; one shape an engine, warmed with the rest. Jitted with a
    table's step programs (`join_token_fn`)."""
    return tokens.at[lane].set(jnp.ravel(out)[0])


def start_host_copy(out: jax.Array) -> None:
    """Begin the device-to-host copy of a program's output, which whoever
    reads it later (`np.asarray`, `int`) then finds under way."""
    try:
        out.copy_to_host_async()
    except (AttributeError, NotImplementedError):  # pragma: no cover
        pass  # backend without async copies: the read blocks


_PROGRAM_CACHE: dict = {}
_PROGRAM_CACHE_LOCK = threading.Lock()


def _step_programs(
    model_config: GPTConfig,
    block_size: int,
    attn_impl: str,
    kv_cache_dtype,
    tensor_parallel_size: int,
    engine_config: EngineConfig,
) -> _StepPrograms:
    """Process-wide config-keyed cache of `_StepPrograms`. The key is
    everything the traced programs close over: the (frozen, hashable)
    model config, the block size (the only EngineConfig field the traced
    bodies read — all other geometry arrives through argument shapes, which
    jax's own cache keys on), the resolved attention impl and pool dtype,
    and the tp degree (the mesh is deterministic given the backend's
    devices, which are fixed for the process). Where the programs' modules
    are stored, the whole engine config besides: it is part of every
    entry's key, so a table is of one. A constructor failure (e.g. tp
    exceeding the device count) propagates without caching."""
    store = program_store.default()
    table = (
        model_config,
        block_size,
        attn_impl,
        np.dtype(kv_cache_dtype).name,
        tensor_parallel_size,
    )
    key = table + ((store.directory, engine_config) if store.directory else ())
    with _PROGRAM_CACHE_LOCK:
        programs = _PROGRAM_CACHE.get(key)
        if programs is None:
            programs = _StepPrograms(
                model_config, block_size, attn_impl, kv_cache_dtype,
                tensor_parallel_size,
                jit=functools.partial(
                    program_store.stored_jit, store=store,
                    table=(table, engine_config),
                ),
            )
            _PROGRAM_CACHE[key] = programs
    return programs


def prefill_tiling(
    ecfg: EngineConfig, heads: int, kv_heads: int, head_dim: int, dtype,
    kv_dtype,
) -> dict:
    """How the paged kernel tiles the widest chunk the engine warms, from
    the function the kernel asks: fed tokens a q tile, and the rows of one
    product (the q tiles of the query heads a cached head serves, stacked;
    one q tile where every query head has its own cached head)."""
    tq = q_tile(
        max(ecfg.chunk_widths()), heads, kv_heads, head_dim,
        np.dtype(dtype).itemsize, ecfg.block_size, ecfg.max_blocks_per_seq,
        np.dtype(kv_dtype).itemsize,
    )
    return {
        "prefill_q_tile": tq,
        "prefill_rows_per_product": heads // kv_heads * tq,
    }


def decode_tiling(
    ecfg: EngineConfig, kv_heads: int, head_dim: int, kv_dtype
) -> dict:
    """How the paged kernel walks a decode lane's context, from the
    function the kernel asks: cached tokens a compute block, and the K and
    V bytes of one, which is what the walk copies ahead of the block it
    folds."""
    itemsize = np.dtype(kv_dtype).itemsize
    _, tile, _ = decode_tile(
        ecfg.block_size, ecfg.max_blocks_per_seq, kv_heads, head_dim, itemsize
    )
    return {
        "decode_tile_tokens": tile,
        "decode_bytes_in_flight": 2 * tile * kv_heads * head_dim * itemsize,
    }


def build_runner(model_config, engine_config: EngineConfig, params=None,
                 seed: int = 0):
    """The runner of `model_config`'s type: the class its `llm_runner`
    names ("module:Class", imported only then), `GPTRunner` for a
    configuration that names none."""
    named = getattr(type(model_config), "llm_runner", None)
    if named is None:
        return GPTRunner(model_config, engine_config, params=params, seed=seed)
    import importlib

    module, _, cls = named.partition(":")
    return getattr(importlib.import_module(module), cls)(
        model_config, engine_config, params=params, seed=seed
    )


class GPTRunner:
    """Owns the params, the paged cache pools, and the compiled steps.

    The params are held as the step programs multiply them: matrices and
    embeddings in `model_config.dtype` (`models.gpt.serving_params`, once,
    here), LayerNorm parameters as they came. `weight_bytes` says how
    much that is; a tree handed in is left to its owner."""

    def __init__(
        self,
        model_config: GPTConfig,
        engine_config: EngineConfig,
        params=None,
        seed: int = 0,
    ):
        if engine_config.max_model_len > model_config.max_seq_len:
            raise ValueError(
                f"cache capacity {engine_config.max_model_len} tokens/seq "
                f"exceeds model max_seq_len {model_config.max_seq_len}"
            )
        self.model_config = model_config
        self.engine_config = engine_config
        # Called where a step program's dispatch call has returned and
        # its results have not been asked for (prefill, suffix, verify,
        # decode): the engine's phase clock ends `prepare` and begins
        # `wait` there.
        self.on_dispatched: Optional[Callable[[], None]] = None
        # Intra-replica tensor parallelism: one mesh with a `tp` axis over
        # the first tensor_parallel_size backend devices; None at tp=1 so
        # the single-chip path stays bit-for-bit unchanged (no device_put,
        # no sharding constraints, no shard_map anywhere below).
        self.tensor_parallel_size = engine_config.tensor_parallel_size
        validate_tp_heads(model_config.num_heads, self.tensor_parallel_size)

        ensure_compile_cache()
        # Resolved once: the jitted programs below bake the choice in.
        self.attn_impl = resolve_paged_impl(engine_config.attn_impl)
        self.kv_cache_dtype = {
            "auto": model_config.dtype,
            "bf16": jnp.bfloat16,
            "int8": jnp.int8,
        }[engine_config.kv_cache_dtype]
        self.quantized = self.kv_cache_dtype == jnp.int8
        # What the pools actually store, in the knob's vocabulary —
        # observability reports this, not the configured string, so
        # "auto" never leaks to dashboards.
        self.kv_cache_dtype_str = {
            jnp.bfloat16: "bf16", jnp.int8: "int8"
        }.get(self.kv_cache_dtype, jnp.dtype(self.kv_cache_dtype).name)

        # The compiled step programs (and the mesh/model/sharding they
        # close over) come from the process-wide config-keyed cache: a
        # same-config runner built later — replica restart, draft model,
        # another test engine — reuses the already-compiled executables.
        self._programs = _step_programs(
            model_config,
            engine_config.block_size,
            self.attn_impl,
            self.kv_cache_dtype,
            self.tensor_parallel_size,
            engine_config,
        )
        self.model = self._programs.model
        self.mesh = self._programs.mesh
        self._pool_sharding = self._programs.pool_sharding
        # serving_params before the placement below and leaf by leaf where
        # each leaf lives, so a tensor-parallel boot shards the rounded
        # bytes from wherever they were: no program takes the tree whole.
        if params is None:
            probe = jnp.zeros((1, engine_config.block_size), jnp.int32)
            # Under tensor parallelism seed-init (and round) on the host
            # CPU: the full tree must never materialize on one accelerator
            # chip (a tp-sharded model may exceed per-chip HBM — the
            # situation tp exists for). llm_shard_params below then
            # device_puts each leaf straight from host memory into its
            # Megatron placement, the same host->shards path a numpy
            # checkpoint takes. On one chip the float32 tree lives there
            # until its rounded copy is whole (1.5x the tree, before the
            # pools exist), then nothing refers to it.
            host = (
                host_cpu_device("seed-initializing tensor-parallel weights")
                if self.mesh is not None
                else None
            )
            with jax.default_device(host):
                params = serving_params(
                    model_config,
                    self.model.init(jax.random.PRNGKey(seed), probe),
                )
        else:
            # A handed tree is rounded in place of residence: numpy leaves
            # (a checkpoint) by numpy on the host, device leaves on their
            # own devices. It is the caller's and is left alive.
            params = serving_params(model_config, params)
        if self.mesh is not None:
            # Megatron-style weight placement from the model's logical axis
            # annotations (parallel.sharding.LLM_TP_RULES): qkv/mlp-in
            # column-parallel, attn-out/mlp-out row-parallel, embeddings
            # and norms replicated. Works on freshly-initialized boxed
            # params and on user checkpoints alike.
            from ray_tpu.parallel.sharding import llm_shard_params

            params = llm_shard_params(self.mesh, params)
        self.params = params
        # Parameter count, once at init (a tree reduce over the weights is
        # too slow for a stats() scrape): feeds the fleet ledger's MFU
        # estimate — decode FLOPs ~= 2 * num_params per generated token.
        leaves = jax.tree_util.tree_leaves(params)
        self.num_params = int(sum(x.size for x in leaves))
        # Bytes of the leaves as held (every shard of them under tensor
        # parallelism): what a decode step reads of the weights.
        self.weight_bytes = int(sum(x.nbytes for x in leaves))
        # Host-transfer accounting: bytes explicitly moved across the
        # host/device boundary by the program dispatches below (token ids,
        # block tables, lengths in; sampled token ids out). The pools and
        # params never appear here — they live donated on the device(s) —
        # so these counters are flat in tensor_parallel_size by
        # construction. They are the accounting half of the no-gather
        # claim; the detection half is pool_sharding_spec() (a desharded
        # pool after traffic) plus the compiled-HLO gate in
        # tests/test_llm_tp.py, which asserts the tp=2 decode executable
        # contains zero all-gather ops (a dropped output-sharding
        # constraint makes GSPMD gather the pools right there).
        self.host_bytes_in = 0
        self.host_bytes_out = 0

        cfg, ecfg = model_config, engine_config
        blocks = (cfg.num_layers, ecfg.num_blocks, ecfg.block_size)
        cache_shape = blocks + (cfg.num_heads * cfg.head_dim,)
        self.k_cache = self._zeros_pool(cache_shape, self.kv_cache_dtype)
        self.v_cache = self._zeros_pool(cache_shape, self.kv_cache_dtype)
        if self.quantized:
            scale_shape = blocks + (cfg.num_heads,)
            self.k_scale = self._zeros_pool(scale_shape, KV_SCALE_DTYPE)
            self.v_scale = self._zeros_pool(scale_shape, KV_SCALE_DTYPE)
        else:
            self.k_scale = None
            self.v_scale = None
        self._decode_fn = self._programs.decode_fn
        self._verify_fn = self._programs.verify_fn
        self._prefill_fn = self._programs.prefill_fn
        self._prefill_suffix_fn = self._programs.prefill_suffix_fn
        self._copy_block_fn = self._programs.copy_block_fn
        self._restore_block_fn = self._programs.restore_block_fn

    # ---------------- pool plumbing ----------------

    def _zeros_pool(self, shape, dtype):
        """Allocate one device pool — under tensor parallelism it is
        assembled shard-by-shard, split by heads on the minor axis, so the full
        pool never materializes on a single chip (a tp-sharded pool may
        exceed per-chip HBM — the very situation tp exists for)."""
        if self._pool_sharding is None:
            return jnp.zeros(shape, dtype)

        def shard_zeros(index):
            shard_shape = tuple(
                len(range(*idx.indices(dim)))
                for idx, dim in zip(index, shape)
            )
            return np.zeros(shard_shape, np.dtype(dtype))

        return jax.make_array_from_callback(
            shape, self._pool_sharding, shard_zeros
        )

    @property
    def _pools(self):
        return (self.k_cache, self.v_cache, self.k_scale, self.v_scale)

    def _set_pools(self, pools) -> None:
        self.k_cache, self.v_cache, self.k_scale, self.v_scale = pools

    def _dispatched(self) -> None:
        if self.on_dispatched is not None:
            self.on_dispatched()

    def _count_transfer(self, arrays_in, out) -> None:
        self.host_bytes_in += sum(int(a.nbytes) for a in arrays_in)
        self.host_bytes_out += int(out.nbytes)

    def host_transfer_bytes(self) -> int:
        """Cumulative explicit host<->device bytes across all program
        dispatches (inputs fed + sampled tokens fetched). Per-step deltas
        land in the flight-recorder step records; the tp parity tests
        assert the series is identical at tensor_parallel_size 1 and 2."""
        return self.host_bytes_in + self.host_bytes_out

    def pool_sharding_spec(self) -> Optional[str]:
        """The live K-pool's PartitionSpec as a string (None at tp=1):
        observability surfaces it, and tests assert it still names the
        head axis after serving traffic — proof no step desharded the
        cache."""
        if self.mesh is None:
            return None
        return str(self.k_cache.sharding.spec)

    def attention_shape(self) -> dict:
        """The K/V pools as the paged kernel reads them, and how it tiles
        a chunk (a chip's own heads under tensor parallelism)."""
        cfg = self.model_config
        local_heads = cfg.num_heads // self.tensor_parallel_size
        return {
            "num_layers": cfg.num_layers,
            "num_heads": cfg.num_heads,
            "head_dim": cfg.head_dim,
            "kv_itemsize": np.dtype(self.kv_cache_dtype).itemsize,
            **prefill_tiling(
                self.engine_config, local_heads, local_heads, cfg.head_dim,
                cfg.dtype, self.kv_cache_dtype,
            ),
            **decode_tiling(
                self.engine_config, local_heads, cfg.head_dim,
                self.kv_cache_dtype,
            ),
        }

    def kv_pool_bytes(self) -> dict:
        """Aggregate and per-shard bytes of both KV pools (+ scale tensors
        when quantized): per-chip HBM is aggregate / tensor_parallel_size
        because the pools shard by heads."""
        cfg, ecfg = self.model_config, self.engine_config
        return kv_pool_bytes_sharded(
            cfg.num_layers,
            ecfg.num_blocks,
            ecfg.block_size,
            cfg.num_heads,
            cfg.head_dim,
            np.dtype(self.kv_cache_dtype).itemsize,
            np.dtype(KV_SCALE_DTYPE).itemsize if self.quantized else None,
            tensor_parallel_size=self.tensor_parallel_size,
        )

    def device_report(self) -> dict:
        """Where the weights and the KV pools live, and what one decode
        step costs the device, read from the arrays and the compiled
        program themselves: bytes per device (`addressable_shards`, so a
        replicated leaf counts on every chip holding it), the decode
        program's `memory_analysis()` (per device under tensor
        parallelism), and the kernels and collectives in its text.
        Compiles the decode program — a persistent-cache hit after warmup."""
        ecfg = self.engine_config
        slots, nb = ecfg.max_decode_slots, ecfg.max_blocks_per_seq

        def i32(*shape):
            return jax.ShapeDtypeStruct(shape, jnp.int32)

        compiled = self._decode_fn.lower(
            self.params, *self._pools, i32(slots), i32(slots),
            i32(slots, nb), i32(slots),
        ).compile()
        memory = compiled.memory_analysis()
        text = compiled.as_text()
        collectives = (
            "all-reduce", "all-gather", "reduce-scatter",
            "collective-permute", "all-to-all",
        )
        return {
            "param_bytes_by_device": bytes_by_device(
                jax.tree_util.tree_leaves(self.params)
            ),
            "pool_bytes_by_device": bytes_by_device(
                p for p in self._pools if p is not None
            ),
            "decode_argument_bytes": int(memory.argument_size_in_bytes),
            "decode_temp_bytes": int(memory.temp_size_in_bytes),
            "decode_kernels": text.count('custom_call_target="tpu_custom_call"'),
            "decode_collectives": {
                op: len(re.findall(rf" {op}(?:-start)?\(", text))
                for op in collectives
            },
        }

    # ---------------- prefill ----------------

    def prefill(
        self, token_ids: Sequence[int], block_ids: Sequence[int]
    ) -> jax.Array:
        """Dispatch one prompt through the model, scattering its K/V into
        the given blocks, WITHOUT waiting for it: returns the
        greedily-sampled next token as the program's output on the device,
        its host copy started. `read_chunk` (or `int`) reads it; `join_token`
        hands it to a decode unread."""
        ecfg = self.engine_config
        n = len(token_ids)
        bucket = ecfg.bucket_for(n)
        nb = bucket // ecfg.block_size
        tokens = np.zeros((1, bucket), np.int32)
        tokens[0, :n] = token_ids
        # Bucket padding beyond the sequence's own blocks scatters into the
        # null block; it is garbage that nothing ever reads unmasked.
        blocks = np.zeros((nb,), np.int32)
        blocks[: len(block_ids)] = block_ids
        pools, next_token = self._prefill_fn(
            self.params,
            *self._pools,
            jnp.asarray(tokens),
            jnp.asarray(blocks),
            jnp.int32(n),
        )
        self._set_pools(pools)
        self._dispatched()
        self._count_transfer((tokens, blocks), next_token)
        start_host_copy(next_token)
        return next_token

    # ---------------- partial prefill (prefix caching) ----------------

    def prefill_suffix(
        self, token_ids: Sequence[int], block_ids: Sequence[int], offset: int
    ) -> jax.Array:
        """Prefix-aware prefill: dispatch only the uncached suffix of a
        prompt whose first `offset` tokens already sit in the paged cache
        (through `block_ids`, the sequence's whole block table), scattering
        the suffix K/V; returns the greedily-sampled next token on the
        device, as `prefill` does."""
        ecfg = self.engine_config
        n = len(token_ids)
        bucket = ecfg.bucket_for(n)
        tokens = np.zeros((1, bucket), np.int32)
        tokens[0, :n] = token_ids
        table = np.zeros((ecfg.max_blocks_per_seq,), np.int32)
        table[: len(block_ids)] = block_ids
        pools, next_token = self._prefill_suffix_fn(
            self.params,
            *self._pools,
            jnp.asarray(tokens),
            jnp.asarray(table),
            jnp.int32(offset),
            jnp.int32(n),
        )
        self._set_pools(pools)
        self._dispatched()
        self._count_transfer((tokens, table), next_token)
        start_host_copy(next_token)
        return next_token

    def read_chunk(self, out: jax.Array) -> int:
        """The token a chunk program sampled (`prefill` / `prefill_suffix`'s
        output), on the host: waits for the program, and raises here if it
        failed."""
        return int(out)

    def join_token(self, tokens, lane: int, out: jax.Array) -> jax.Array:
        """`tokens` (a decode's token input: the host's buffer, or an
        in-flight decode's output) on the device with lane `lane` taken from
        a chunk's output `out`, unread."""
        if not isinstance(tokens, jax.Array):
            self.host_bytes_in += int(tokens.nbytes)
            tokens = jnp.asarray(tokens.copy(), jnp.int32)
        return self._programs.join_token_fn(tokens, np.int32(lane), out)

    def copy_block(self, src: int, dst: int) -> None:
        """Device-copy one block's K/V (and scales) across every layer
        (copy-on-write before a sequence writes into a shared block).
        Under tensor parallelism the copy is shard-local: src and dst
        address the same blocks on every chip, each chip copies its own
        heads' slice (scales included)."""
        self._set_pools(
            self._copy_block_fn(*self._pools, jnp.int32(src), jnp.int32(dst))
        )
        self.host_bytes_in += 8  # two int32 block ids

    # ---------------- KV fabric spill / restore ----------------

    def kv_block_bytes(self) -> int:
        """Bytes of ONE block's payload (K + V values across every layer,
        plus scale tensors when quantized) — what a single fabric entry
        costs, and the floor the fabric byte budget is validated against."""
        cfg, ecfg = self.model_config, self.engine_config
        slots = cfg.num_layers * ecfg.block_size * cfg.num_heads
        nbytes = 2 * slots * cfg.head_dim * np.dtype(self.kv_cache_dtype).itemsize
        if self.quantized:
            nbytes += 2 * slots * np.dtype(KV_SCALE_DTYPE).itemsize
        return nbytes

    def extract_block(self, block: int) -> dict:
        """Read one block's device content to host numpy — the spill half
        of the fabric tier. The payload is pool-dtype values in the stored
        form ([L, bs, H*D], + int8 scales [L, bs, H]), so restore is
        bit-exact; `kv_dtype` stamps the storage format so a mismatched
        engine treats the entry as a miss instead of scattering garbage."""
        payload = {
            "kv_dtype": self.kv_cache_dtype_str,
            "k": np.asarray(self.k_cache[:, block]),
            "v": np.asarray(self.v_cache[:, block]),
        }
        if self.quantized:
            payload["k_scale"] = np.asarray(self.k_scale[:, block])
            payload["v_scale"] = np.asarray(self.v_scale[:, block])
        self.host_bytes_out += sum(
            int(a.nbytes) for a in payload.values() if hasattr(a, "nbytes")
        )
        return payload

    def restore_block(self, block: int, payload: dict) -> None:
        """Write one spilled payload back into slot `block` — the restore
        half. Raises ValueError on a storage-format mismatch (different
        kv_cache_dtype or geometry); the caller must then free the slot
        and treat the chain as a fabric miss."""
        if payload.get("kv_dtype") != self.kv_cache_dtype_str:
            raise ValueError(
                f"fabric payload stored as {payload.get('kv_dtype')!r}, "
                f"pool is {self.kv_cache_dtype_str!r} — engines on one "
                "fabric must share kv_cache_dtype"
            )
        k, v = payload["k"], payload["v"]
        expected = self.k_cache.shape[:1] + self.k_cache.shape[2:]
        if k.shape != expected:
            raise ValueError(
                f"fabric payload block shape {k.shape} does not match "
                f"pool block shape {expected}"
            )
        if self.quantized:
            ks = jnp.asarray(payload["k_scale"])
            vs = jnp.asarray(payload["v_scale"])
        else:
            ks = vs = None
        self._set_pools(
            self._restore_block_fn(
                *self._pools,
                jnp.int32(block),
                jnp.asarray(k),
                jnp.asarray(v),
                ks,
                vs,
            )
        )
        self.host_bytes_in += sum(
            int(a.nbytes) for a in payload.values() if hasattr(a, "nbytes")
        )

    # ---------------- decode / k-token verification ----------------

    def verify(
        self,
        tokens: np.ndarray,
        block_tables: np.ndarray,
        context_lens: np.ndarray,
        true_lens: np.ndarray,
    ) -> np.ndarray:
        """Score up to S-1 proposed tokens per slot in one step (see
        _verify_step). Arrays must already be padded to
        [max_decode_slots, S_bucket] / [max_decode_slots, max_blocks_per_seq]
        / [max_decode_slots]; one program compiles per S bucket
        (EngineConfig.verify_buckets)."""
        pools, out = self._verify_fn(
            self.params,
            *self._pools,
            jnp.asarray(tokens, jnp.int32),
            jnp.asarray(block_tables, jnp.int32),
            jnp.asarray(context_lens, jnp.int32),
            jnp.asarray(true_lens, jnp.int32),
        )
        self._set_pools(pools)
        self._dispatched()
        out = np.asarray(out)
        self._count_transfer(
            (tokens, block_tables, context_lens, true_lens), out
        )
        return out

    def decode(
        self,
        tokens,
        positions: np.ndarray,
        block_tables: np.ndarray,
        context_lens: np.ndarray,
    ) -> jax.Array:
        """Dispatch one batched single-token decode WITHOUT waiting for
        its result; arrays must already be padded to [max_decode_slots] /
        [max_decode_slots, max_blocks_per_seq].

        The sampled tokens stay on device: `tokens` may be the previous
        step's on-device `next_tokens` (token chaining — it is not
        donated, so the caller can still fetch it afterwards), and the
        return value is the device array for THIS step with an async
        device->host copy already started. The caller materializes the
        values with `np.asarray` when it commits them: at once, or one
        step later.

        The host-side numpy inputs are copied on the host first: the
        engine reuses these buffers across steps, and a zero-copy alias
        (the CPU backend makes one) would let next step's buffer fill
        corrupt a still-running program's inputs. Not `jnp.array`: its
        guaranteed copy is a `convert_element_type` program on the device
        for each input, 0.5 ms a step for the four (chip run, PR 30).
        """
        chained = isinstance(tokens, jax.Array)
        pools, next_tokens = self._decode_fn(
            self.params,
            *self._pools,
            tokens if chained else jnp.asarray(tokens.copy(), jnp.int32),
            jnp.asarray(positions.copy(), jnp.int32),
            jnp.asarray(block_tables.copy(), jnp.int32),
            jnp.asarray(context_lens.copy(), jnp.int32),
        )
        self._set_pools(pools)
        self._dispatched()
        start_host_copy(next_tokens)
        # Chained token inputs never cross the host boundary — that is
        # part of the win the transfer counters should show.
        host_in = (positions, block_tables, context_lens)
        if not chained:
            host_in = (tokens,) + host_in
        self._count_transfer(host_in, next_tokens)
        return next_tokens
