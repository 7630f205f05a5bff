"""The parts of a layer that the models served by `ray_tpu.llm.hybrid_runner`
share (`granite_hybrid`, `laguna`): RMS norm, the matrix product in the
compute dtype with float32 accumulation, the gated MLP, the routed experts
beside a shared expert with the routing's counts, the embedding and the
head, and the seeded normal leaf. Pure functions; a model's configuration
is read by attribute.

Not imported by `ray_tpu` or `ray_tpu.models`: import this module by name.
"""

from __future__ import annotations

import functools
from typing import Sequence

import jax
import jax.numpy as jnp

from ray_tpu.ops.grouped_experts import route, routed_dense, routed_grouped


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def normal(key, shape, dtype, std):
    """A normal(std) leaf made on the device in `dtype`."""
    return (std * jax.random.normal(key, shape, jnp.float32)).astype(dtype)


def num_params(params) -> int:
    return int(sum(x.size for x in jax.tree_util.tree_leaves(params)))


def local_of(num_experts: int, experts_held: Sequence[int]) -> jax.Array:
    """[num_experts] int32: an expert's row in the held weights, -1 for an
    expert another chip holds."""
    table = [-1] * num_experts
    for row, expert in enumerate(experts_held):
        table[expert] = row
    return jnp.asarray(table, jnp.int32)


def check_experts_held(held: Sequence[int], num_experts: int) -> None:
    if len(set(held)) != len(held) or not all(0 <= e < num_experts for e in held):
        raise ValueError(f"experts_held {held} of {num_experts}")


def rms_norm(x, weight, eps):
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (y * weight.astype(jnp.float32)).astype(x.dtype)


def matmul(x, w, dtype):
    return jnp.dot(
        x.astype(dtype), w.astype(dtype), preferred_element_type=jnp.float32
    )


def gated_mlp(x, w_in, w_out, dtype):
    """w_out (silu(g) * u), [g, u] = w_in x; w_in [D, 2F], w_out [F, D]."""
    g, u = jnp.split(matmul(x, w_in, dtype), 2, axis=-1)
    return matmul(jax.nn.silu(g) * u, w_out, dtype)


def experts(cfg, p, x, *, grouped: bool, valid=None):
    """routed(x) + shared(x) for x [T, D], float32, and the routing's
    counts over the tokens `valid` marks (all, where None): assignments to
    experts held here and to absent ones, held experts that a token
    reached, and the fullest held expert's load. The router's rule is the
    configuration's (`router_score` and, where it scales the gates,
    `routed_scaling_factor`; `ray_tpu.ops.grouped_experts.route`)."""
    held = cfg.local_of()
    with jax.named_scope("llm.moe.router"):
        ids, gates = route(
            x, p["router"], cfg.num_experts_per_tok, score=cfg.router_score,
            scale=getattr(cfg, "routed_scaling_factor", 1.0),
        )
        if valid is None:
            valid = jnp.ones(x.shape[:1], bool)
        local = jnp.where(valid[:, None], held[ids], -2)
        load = jnp.sum(
            local[..., None] == jnp.arange(len(cfg.experts_held)), axis=(0, 1)
        )
        counts = {
            "held": jnp.sum(local >= 0), "absent": jnp.sum(local == -1),
            "touched": jnp.sum(load > 0), "load_max": jnp.max(load),
        }
    xc = x.astype(cfg.dtype)
    w_in, w_out = p["experts_in"].astype(cfg.dtype), p["experts_out"].astype(cfg.dtype)
    with jax.named_scope("llm.moe.routed"):
        if grouped:
            routed = routed_grouped(xc, ids, gates, held, w_in, w_out, valid)
        else:
            routed = routed_dense(xc, ids, gates, held, w_in, w_out)
    with jax.named_scope("llm.moe.shared"):
        shared = gated_mlp(x, p["shared_in"], p["shared_out"], cfg.dtype)
    return routed + shared, counts


def add_counts(totals, counts):
    """The routing's counts summed over layers (None: no layer yet)."""
    if totals is None:
        return counts
    return {k: totals[k] + v for k, v in counts.items()}


def embed(table, ids, dtype, multiplier=None):
    """Rows `ids` of the embedding table, in `dtype`; times `multiplier`
    in float32 where the model has one."""
    if multiplier is None:
        return table[ids].astype(dtype)
    return (table[ids].astype(jnp.float32) * multiplier).astype(dtype)


def head(h, norm, eps, weight, dtype, *, tied: bool, scaling=None):
    """Logits (float32) of the residual rows h [..., D]: the final norm,
    then the product with `weight`, the embedding table [V, D] where the
    head is `tied` to it, else the head's own [D, V]; divided by `scaling`
    where the model has one."""
    with jax.named_scope("llm.head"):
        x = rms_norm(h, norm, eps).astype(dtype)
        w = weight.astype(dtype)
        logits = jnp.dot(x, w.T if tied else w, preferred_element_type=jnp.float32)
        return logits if scaling is None else logits / scaling
