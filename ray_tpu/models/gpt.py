"""GPT-2-style decoder-only transformer (flax), TPU-first.

The "GPT-2 125M language modeling" config from BASELINE.json. Every weight
carries *logical* axis names via nn.with_logical_partitioning, so one model
definition serves dp / fsdp / tp / sp by swapping the rules table
(ray_tpu.parallel.sharding) — the design that replaces the reference's
FSDP/DeepSpeed integration wrappers (train/huggingface/accelerate/).

Sequence parallelism: attention goes through ray_tpu.ops (flash kernel on TPU;
ring attention when the caller runs the model under shard_map with the seq dim
sharded on `sp`).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from ray_tpu.ops import attention as attention_op
from ray_tpu.ops.attention import head_sharded_attention
from ray_tpu.ops.flash_attention import flash_attention_packed
from ray_tpu.ops.paged_flash import paged_attention_impl
from ray_tpu.ops.ring_attention import ring_attention


@dataclasses.dataclass(frozen=True)
class GPTConfig:
    vocab_size: int = 50304  # 50257 padded to a multiple of 128 for the MXU
    num_layers: int = 12
    num_heads: int = 12
    embed_dim: int = 768
    mlp_ratio: int = 4
    max_seq_len: int = 1024
    dropout_rate: float = 0.0
    dtype: Any = jnp.bfloat16
    # "flash" (pallas kernel), "reference", or "ring" (requires sp-sharded
    # inputs under shard_map with axis name `sp`).
    attention_impl: str = "flash"
    # MoE: num_experts=0 keeps dense MLPs; otherwise every `moe_every`-th
    # block swaps its MLP for a MoEMlp (experts shard on the ep mesh axis).
    num_experts: int = 0
    moe_every: int = 2
    num_experts_per_tok: int = 2
    moe_capacity_factor: float = 1.25

    @property
    def head_dim(self) -> int:
        return self.embed_dim // self.num_heads


def gpt2_125m(**overrides) -> "GPTConfig":
    return GPTConfig(**overrides)


def gpt2_350m(**overrides) -> "GPTConfig":
    return GPTConfig(num_layers=24, num_heads=16, embed_dim=1024, **overrides)


def gpt2_760m(**overrides) -> "GPTConfig":
    return GPTConfig(num_layers=24, num_heads=20, embed_dim=1280, **overrides)


def _dense(features, logical_axes, dtype, name=None, use_bias=True):
    return nn.Dense(
        features,
        dtype=dtype,
        use_bias=use_bias,
        kernel_init=nn.with_logical_partitioning(
            nn.initializers.normal(stddev=0.02), logical_axes
        ),
        bias_init=nn.with_logical_partitioning(
            nn.initializers.zeros, (logical_axes[-1],)
        ),
        name=name,
    )


class Block(nn.Module):
    config: GPTConfig
    use_moe: bool = False

    @nn.compact
    def __call__(
        self,
        x,
        deterministic: bool = True,
        *,
        return_kv: bool = False,
        paged_state: Optional[tuple] = None,
        paged_layer: int = 0,
        paged_impl: str = "reference",
        paged_mesh: Optional[Any] = None,
    ):
        cfg = self.config
        h = nn.LayerNorm(dtype=cfg.dtype, name="ln_1")(x)
        b, s, _ = h.shape
        qkv = _dense(3 * cfg.embed_dim, ("embed", "heads"), cfg.dtype, name="attn_qkv")(h)
        if return_kv or paged_state is not None:
            # Generation paths (ray_tpu.llm). All need this layer's K/V
            # exposed: prefill sows the prompt's K/V for the engine to
            # scatter into the paged cache; decode (s == 1) and prefix-aware
            # partial prefill (s > 1, uncached suffix only) attend over the
            # cache through the block table — paged over the cached prefix,
            # causal among the fed tokens — and sow the new K/V.
            q, k, v = jnp.split(qkv, 3, axis=-1)
            q = q.reshape(b, s, cfg.num_heads, cfg.head_dim)
            k = k.reshape(b, s, cfg.num_heads, cfg.head_dim)
            v = v.reshape(b, s, cfg.num_heads, cfg.head_dim)
            if paged_state is not None:
                (k_cache, v_cache, block_tables, context_lens,
                 k_scale, v_scale) = paged_state
                # "pallas" runs the fused kernel (walks the block table
                # inside the pipeline, never materializing the gathered
                # pages or the logits — ops/paged_flash.py); "reference"
                # the XLA gather+softmax op. The engine resolves "auto"
                # before tracing, so the choice is compile-time static.
                # The pools go in whole, every layer of them: the op reads
                # this layer's blocks where it addresses them, and a
                # `k_cache[i]` here would be a copy of the layer.
                attn = paged_attention_impl(
                    q, k_cache, v_cache, block_tables, context_lens,
                    new_k=k, new_v=v, layer=paged_layer,
                    k_scale=k_scale, v_scale=v_scale,
                    impl=paged_impl,
                    mesh=paged_mesh,
                )
            else:
                impl = (
                    "reference"
                    if cfg.attention_impl == "ring"
                    else cfg.attention_impl
                )
                if (
                    paged_mesh is not None
                    and paged_mesh.shape.get("tp", 1) > 1
                ):
                    # Full prefill under tensor parallelism: heads are
                    # independent in attention, so the dense causal pass
                    # runs head-sliced over the same tp axis as the paged
                    # programs (the flash kernel can't be auto-partitioned
                    # by GSPMD — each shard runs it over its local heads).
                    attn = head_sharded_attention(
                        paged_mesh, q, k, v, impl=impl
                    )
                else:
                    attn = attention_op(q, k, v, causal=True, impl=impl)
            self.sow("intermediates", "kv_cache", (k, v))
            attn = attn.reshape(b, s, cfg.embed_dim)
        elif cfg.attention_impl == "flash" and s <= 2048:
            # Packed kernel consumes the projection output directly: no
            # split / head reshape / fold transposes in the graph, dqkv
            # comes back packed for the projection's grad matmul.
            attn = flash_attention_packed(qkv, cfg.num_heads, causal=True)
        else:
            q, k, v = jnp.split(qkv, 3, axis=-1)
            q = q.reshape(b, s, cfg.num_heads, cfg.head_dim)
            k = k.reshape(b, s, cfg.num_heads, cfg.head_dim)
            v = v.reshape(b, s, cfg.num_heads, cfg.head_dim)
            if cfg.attention_impl == "ring":
                attn = ring_attention(q, k, v, axis_name="sp", causal=True)
            else:
                attn = attention_op(q, k, v, causal=True, impl=cfg.attention_impl)
            attn = attn.reshape(b, s, cfg.embed_dim)
        attn = _dense(cfg.embed_dim, ("heads", "embed"), cfg.dtype, name="attn_proj")(attn)
        x = x + attn
        h = nn.LayerNorm(dtype=cfg.dtype, name="ln_2")(x)
        if self.use_moe:
            from ray_tpu.models.moe import MoEConfig, MoEMlp

            h, aux = MoEMlp(
                embed_dim=cfg.embed_dim,
                mlp_dim=cfg.mlp_ratio * cfg.embed_dim,
                moe=MoEConfig(
                    num_experts=cfg.num_experts,
                    num_experts_per_tok=cfg.num_experts_per_tok,
                    capacity_factor=cfg.moe_capacity_factor,
                ),
                dtype=cfg.dtype,
                name="moe_mlp",
            )(h)
            # Collected by the train step via mutable=["intermediates"]
            # (collect_moe_losses helper below).
            self.sow("intermediates", "moe_aux", aux)
        else:
            h = _dense(cfg.mlp_ratio * cfg.embed_dim, ("embed", "mlp"), cfg.dtype,
                       name="mlp_in")(h)
            h = nn.gelu(h)
            h = _dense(cfg.embed_dim, ("mlp", "embed"), cfg.dtype, name="mlp_out")(h)
        return x + h


class GPT(nn.Module):
    config: GPTConfig

    @nn.compact
    def __call__(
        self,
        tokens,
        deterministic: bool = True,
        *,
        positions: Optional[jax.Array] = None,
        return_kv: bool = False,
        paged_caches: Optional[tuple] = None,
        paged_impl: str = "reference",
        paged_mesh: Optional[Any] = None,
    ):
        """Forward pass.

        Generation variants for ray_tpu.llm (same parameters, no fork):
          * ``return_kv=True`` (prefill): apply with
            ``mutable=["intermediates"]`` and read each layer's prompt K/V
            back via :func:`collect_kv_caches`.
          * ``paged_caches=(k_cache, v_cache, block_tables, context_lens)``
            or ``(..., k_scale, v_scale)`` (decode and prefix-aware partial
            prefill): k/v_cache are [L, num_blocks, block_size, H*D] paged
            pools, heads and head size merged on the minor axis (int8
            pools carry [L, N, bs, H] scale tensors; pass None scales
            otherwise); tokens is [B, S] (S == 1 for decode, S > 1
            for the uncached suffix of a partially-cached prompt) and
            ``positions`` [B, S] must carry each token's absolute position.
            Attention reads the cached prefix through the block table and
            runs causally over the fed tokens — through the fused Pallas
            kernel when ``paged_impl="pallas"``, the XLA reference
            otherwise; the new K/V is sown for the caller to scatter into
            the cache. ``paged_mesh`` (a Mesh with a tp axis > 1) runs
            every attention head-sliced over the tensor-parallel axis —
            the serving engine passes its intra-replica mesh here so each
            chip's kernel instance only touches its local heads' cache.
        """
        cfg = self.config
        b, s = tokens.shape
        wte = nn.Embed(
            cfg.vocab_size,
            cfg.embed_dim,
            dtype=cfg.dtype,
            embedding_init=nn.with_logical_partitioning(
                nn.initializers.normal(stddev=0.02), ("vocab", "embed")
            ),
            name="wte",
        )
        wpe = nn.Embed(
            cfg.max_seq_len,
            cfg.embed_dim,
            dtype=cfg.dtype,
            embedding_init=nn.with_logical_partitioning(
                nn.initializers.normal(stddev=0.01), (None, "embed")
            ),
            name="wpe",
        )
        if positions is None:
            positions = jnp.arange(s)[None, :]
        x = wte(tokens) + wpe(positions)
        if paged_caches is not None:
            if len(paged_caches) == 4:  # legacy: no scale tensors
                paged_caches = tuple(paged_caches) + (None, None)
        for i in range(cfg.num_layers):
            use_moe = bool(
                cfg.num_experts and (i % cfg.moe_every == cfg.moe_every - 1)
            )
            x = Block(cfg, use_moe=use_moe, name=f"h_{i}")(
                x,
                deterministic=deterministic,
                return_kv=return_kv,
                paged_state=paged_caches,
                paged_layer=i,
                paged_impl=paged_impl,
                paged_mesh=paged_mesh,
            )
        x = nn.LayerNorm(dtype=cfg.dtype, name="ln_f")(x)
        # Tied LM head: logits via the embedding matrix. The matmul runs in
        # the model dtype (bf16 keeps the [S,E]x[E,V] head — ~27% of the
        # model's FLOPs — on the MXU fast path); the loss upcasts to f32
        # where the softmax needs it.
        logits = wte.attend(x)
        return logits


def cross_entropy_loss(logits, targets, mask: Optional[jax.Array] = None):
    """Token-level LM loss. logits [B,S,V], targets [B,S] int.

    Computed as logsumexp(logits) - logits[target] in f32: identical value
    to -log_softmax[target] but HBM-friendlier — XLA fuses the reduction
    instead of materializing a full [B,S,V] f32 log-probability tensor
    (1.6 GB at GPT-2 bench shapes), which dominated the loss's runtime."""
    logits = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    tgt = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    nll = lse - tgt
    if mask is not None:
        return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    return jnp.mean(nll)


def logical_axis_rules(rules_table: dict) -> list[tuple[str, Any]]:
    """Convert a ray_tpu.parallel rules table into flax logical-axis rules
    (for nn.logical_to_mesh_sharding)."""
    return [(name, axis) for name, axis in rules_table.items()]


def collect_kv_caches(
    intermediates: Any, num_layers: int
) -> list[tuple[jax.Array, jax.Array]]:
    """Per-layer (k, v) sown by Blocks under `kv_cache`, in layer order.

    Pair with `model.apply(..., return_kv=True, mutable=["intermediates"])`
    (prefill) or a `paged_caches=` apply (decode / partial prefill): each
    entry is the K/V the layer computed for the *input* tokens —
    [B, S, H, D] of exactly the tokens fed, whose cache writes the caller
    owns ([B, 1, H, D] for a decode step)."""
    out = []
    for i in range(num_layers):
        entry = intermediates[f"h_{i}"]["kv_cache"]
        out.append(entry[0] if isinstance(entry, (tuple, list)) else entry)
    return out


def collect_moe_losses(intermediates: Any) -> jax.Array:
    """Sum MoE aux losses sown by Blocks: run `model.apply(params, tokens,
    mutable=["intermediates"])` and pass the returned collection here.
    Only `moe_aux` entries are summed — other sown diagnostics must never
    leak into the training objective."""

    def collect(node: Any, total: jax.Array) -> jax.Array:
        if isinstance(node, dict):
            for key, sub in node.items():
                if key == "moe_aux":
                    for leaf in jax.tree_util.tree_leaves(sub):
                        total = total + jnp.asarray(leaf, jnp.float32)
                else:
                    total = collect(sub, total)
        return total

    return collect(intermediates, jnp.zeros((), jnp.float32))


# The leaves a forward pass rounds to `cfg.dtype` every time it runs: flax's
# `promote_dtype` casts a Dense's kernel and bias and an Embed's table where
# they are used. By owning module, as (module name, parameter names).
_ROUNDED_EVERY_CALL = {
    "attn_qkv": ("kernel", "bias"),
    "attn_proj": ("kernel", "bias"),
    "mlp_in": ("kernel", "bias"),
    "mlp_out": ("kernel", "bias"),
    "wte": ("embedding",),
    "wpe": ("embedding",),
}


def serving_params(cfg: GPTConfig, params: Any) -> Any:
    """`params` as a server should hold them: the leaves the modules round
    to `cfg.dtype` in every call (`_ROUNDED_EVERY_CALL`) rounded once, here.

    The logits are the same bit for bit: each matmul, the gather and the
    tied head multiply these very values either way, and round-to-nearest
    gives the same bits in a fusion as in a cast of its own. But a jitted
    step takes the parameters as arguments, so XLA cannot hoist the
    rounding out of it, and a decode step over float32 leaves reads twice
    the bytes it multiplies (and writes `wte` rounded, for the gather and
    the head to share). LayerNorm scales and biases stay as they came
    (`_normalize` multiplies them in float32), and so does everything
    under `moe_mlp` (the router scores in float32; models/moe.py). Boxes
    (`nn.LogicallyPartitioned`) are pytree nodes and come back around the
    new leaves, so the sharding rules still find their axis names. Each
    leaf is cast where it lives, a numpy leaf by numpy on the host, so
    nothing moves to a device that was not there, and nothing is donated:
    the caller's tree is the caller's. Where no leaf needs it (`cfg.dtype`
    float32, or a tree that was here before) `params` itself is returned.
    Training keeps its float32 masters and never calls this."""
    dtype = jnp.dtype(cfg.dtype)
    cast_any = False

    def cast(path, leaf):
        nonlocal cast_any
        names = [
            key.key for key in path
            if isinstance(key, jax.tree_util.DictKey)
        ]
        if (
            len(names) < 2
            or "moe_mlp" in names
            or names[-1] not in _ROUNDED_EVERY_CALL.get(names[-2], ())
            or leaf.dtype == dtype
        ):
            return leaf
        cast_any = True
        return leaf.astype(dtype)

    held = jax.tree_util.tree_map_with_path(cast, params)
    return held if cast_any else params
