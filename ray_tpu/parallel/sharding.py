"""Logical-axis sharding rules.

The GSPMD idiom (scaling-book recipe): name every tensor dimension with a
*logical* axis, map logical axes → mesh axes with one rules table per parallelism
strategy, and let XLA insert the collectives. This single table is the
re-design of everything the reference delegates to torch DDP/FSDP/DeepSpeed
(train/torch/train_loop_utils.py:245,329,339 prepare_model): DP/FSDP/TP/SP all
become different rows in the table, not different wrapper classes.
"""

from __future__ import annotations

from typing import Any, Optional, Sequence

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# Logical axis names used by the model zoo (models/).
#   batch      — per-example batch dim
#   seq        — sequence/token dim (sharded under SP)
#   embed      — model/hidden dim
#   mlp        — feed-forward intermediate dim
#   heads      — attention heads dim
#   kv         — per-head dim
#   vocab      — vocabulary dim
#   expert     — MoE expert dim
#   conv_out / conv_in — conv channel dims

RuleTable = dict[str, Any]  # logical axis -> mesh axis | tuple | None

# Pure data parallel: params replicated, batch split over every data-ish axis.
DP_RULES: RuleTable = {
    "batch": ("dp", "fsdp"),
    "seq": None,
    "embed": None,
    "mlp": None,
    "heads": None,
    "kv": None,
    "vocab": None,
    "expert": None,
    "conv_out": None,
    "conv_in": None,
}

# FSDP/ZeRO-3: params sharded over the fsdp axis on their largest dim.
FSDP_RULES: RuleTable = {
    **DP_RULES,
    "embed": "fsdp",
}

# Megatron TP on top of FSDP: hidden-splitting matmuls over tp.
TP_RULES: RuleTable = {
    "batch": ("dp", "fsdp"),
    "seq": None,
    "embed": "fsdp",
    "mlp": "tp",
    "heads": "tp",
    "kv": None,
    "vocab": "tp",
    "expert": None,
    "conv_out": "tp",
    "conv_in": None,
}

# Sequence parallel for long context: activations sharded on seq.
SP_RULES: RuleTable = {
    **TP_RULES,
    "seq": "sp",
}

# MoE: experts over ep.
EP_RULES: RuleTable = {
    **TP_RULES,
    "expert": "ep",
}

STRATEGY_RULES: dict[str, RuleTable] = {
    "dp": DP_RULES,
    "fsdp": FSDP_RULES,
    "tp+fsdp": TP_RULES,
    "sp+fsdp": SP_RULES,
    "ep": EP_RULES,
}


def spec_for(logical_axes: Sequence[Optional[str]], rules: RuleTable) -> P:
    """PartitionSpec for a tensor whose dims carry these logical names."""
    entries = []
    for name in logical_axes:
        if name is None:
            entries.append(None)
        else:
            if name not in rules:
                raise KeyError(f"Unknown logical axis {name!r}")
            entries.append(rules[name])
    # Trailing Nones are implicit.
    while entries and entries[-1] is None:
        entries.pop()
    return P(*entries)


def named_sharding(
    mesh: Mesh, logical_axes: Sequence[Optional[str]], rules: RuleTable
) -> NamedSharding:
    return NamedSharding(mesh, spec_for(logical_axes, rules))


def tree_shardings(mesh: Mesh, logical_tree: Any, rules: RuleTable) -> Any:
    """Map a pytree of logical-axis tuples to NamedShardings."""
    return jax.tree_util.tree_map(
        lambda axes: named_sharding(mesh, axes, rules),
        logical_tree,
        is_leaf=lambda x: isinstance(x, (tuple, list))
        and all(isinstance(e, (str, type(None))) for e in x),
    )


def infer_param_sharding(
    mesh: Mesh, params: Any, rules: RuleTable, min_shard_size: int = 2**16
) -> Any:
    """Heuristic sharding for an unannotated param tree (FSDP-style): shard the
    largest divisible dim of big params over the fsdp axis, replicate the rest.

    Used when a model has no logical-axis annotations (user-supplied flax
    modules) — the analog of torch FSDP auto-wrapping
    (train/torch/train_loop_utils.py:339).
    """
    fsdp_size = mesh.shape.get("fsdp", 1)

    def shard_one(x):
        if fsdp_size == 1 or x.size < min_shard_size:
            return NamedSharding(mesh, P())
        # Pick the largest dim divisible by the fsdp axis.
        best = None
        for i, d in enumerate(x.shape):
            if d % fsdp_size == 0 and (best is None or d > x.shape[best]):
                best = i
        if best is None:
            return NamedSharding(mesh, P())
        entries: list = [None] * x.ndim
        entries[best] = "fsdp"
        while entries and entries[-1] is None:
            entries.pop()
        return NamedSharding(mesh, P(*entries))

    return jax.tree_util.tree_map(shard_one, params)


def batch_sharding(mesh: Mesh) -> NamedSharding:
    """Input-batch sharding: split over all data axes (dp, fsdp)."""
    return NamedSharding(mesh, P(("dp", "fsdp")))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


# ---------------- LLM serving: intra-replica tensor parallelism ----------------

# The serving engine's rules table (ray_tpu.llm with
# EngineConfig.tensor_parallel_size > 1): pure Megatron-style TP over the
# `tp` mesh axis, nothing else. Attention heads and the MLP intermediate
# shard (qkv / mlp-in kernels column-parallel, attn-proj / mlp-out kernels
# row-parallel — each block pays exactly one psum after attn-proj and one
# after mlp-out, inserted by GSPMD); embeddings, layernorms, and the tied
# LM head stay replicated so the per-slot argmax needs no gather. The paged
# KV pools shard by the SAME heads (see llm/model_runner.py), which is
# what makes block ids shard-invariant: every chip holds the same blocks,
# just its own heads' slice of them.
LLM_TP_RULES: RuleTable = {
    **DP_RULES,
    "batch": None,
    "mlp": "tp",
    "heads": "tp",
}

# Queries and new-token K/V [B, S, H, D] put H at dim 2.
LLM_HEAD_SPEC = P(None, None, "tp")
# The stored cache pools [L, N, bs, H*D] and scale pools [L, N, bs, H]
# carry the heads on dim 3. In the pools' merged axis a head is D
# contiguous lanes, so an even split of H*D over tp (H divisible by tp:
# ops.attention.validate_tp_heads) gives each chip its own heads.
LLM_POOL_SPEC = P(None, None, None, "tp")


def llm_pool_sharding(mesh: Mesh) -> NamedSharding:
    """Sharding for the runner's [L, N, bs, H*D] KV pools and
    [L, N, bs, H] int8 scale pools (one spec fits both: the heads are
    dim 3)."""
    return NamedSharding(mesh, LLM_POOL_SPEC)


def llm_shard_params(mesh: Mesh, params: Any) -> Any:
    """Place a GPT param tree onto the serving mesh under LLM_TP_RULES
    (boxed metadata is preserved — flax unboxes at apply time).

    Flax-initialized params carry logical axis names in their
    `nn.LogicallyPartitioned` boxes (models/gpt.py annotates every weight)
    — those drive the specs directly. Plain-array trees (a checkpoint
    saved unboxed) fall back to replication: correct, just not
    memory-sharded, and nothing in the step loop depends on where a
    replicated weight lives."""
    from flax.core import meta

    def put(x):
        if isinstance(x, meta.AxisMetadata):
            sharding = named_sharding(mesh, x.names, LLM_TP_RULES)
            return x.replace_boxed(jax.device_put(x.unbox(), sharding))
        return jax.device_put(x, replicated(mesh))

    return jax.tree_util.tree_map(
        put, params, is_leaf=lambda x: isinstance(x, meta.AxisMetadata)
    )
