"""The parts of a layer that the models served by `ray_tpu.llm.hybrid_runner`
share (`granite_hybrid`, `laguna`, `olmo_hybrid`, `falcon_h1`) with the one
that is trained (`mellum`): RMS norm (whole and by group), the L2 norm, QK-norm over a whole projection,
the block with its norms on the sub-layers' outputs, the matrix product in
the compute dtype with float32 accumulation, rotary positions (default and
YaRN frequencies), the gated MLP, the routed experts with the routing's counts, beside a shared expert
where the model has one, the embedding and the head, and the seeded normal
leaf. Pure functions; a model's configuration
is read by attribute.

Not imported by `ray_tpu` or `ray_tpu.models`: import this module by name.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.ops.grouped_experts import (
    route, routed_dense, routed_grouped, rows_walked,
)


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def normal(key, shape, dtype, std):
    """A normal(std) leaf made on the device in `dtype`."""
    return (std * jax.random.normal(key, shape, jnp.float32)).astype(dtype)


def frozen(tree):
    """A nested dict as sorted tuples, so that a configuration that holds
    one (`rope_parameters`) hashes."""
    if isinstance(tree, dict):
        return tuple(sorted((k, frozen(v)) for k, v in tree.items()))
    return tree


def seeded_tree(shapes, seed: int, dtype, std_of=lambda name: 0.02, draws=None):
    """A tree of seeded weights from a tree of shapes (tuples), made leaf by
    leaf on the device in `dtype` (a float32 tree of a serving size does not
    fit a chip beside its bfloat16 copy): ones for a leaf whose name starts
    with "norm", `draws[name](key, shape)` (float32, cast) for a leaf a
    model draws otherwise, else normal(`std_of(name)`), the key folded from
    the leaf's place in the tree."""
    leaves, tree = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=lambda v: isinstance(v, tuple)
    )
    base = jax.random.PRNGKey(seed)
    made = []
    for index, (path, shape) in enumerate(leaves):
        name = path[-1].key
        key = jax.random.fold_in(base, index)
        if name.startswith("norm"):
            made.append(jnp.ones(shape, dtype))
        elif draws and name in draws:
            made.append(draws[name](key, shape).astype(dtype))
        else:
            made.append(normal(key, shape, dtype, std_of(name)))
    return jax.tree_util.tree_unflatten(tree, made)


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


def rms_norm_by_group(x, weight, eps, groups: int):
    """Mamba-2's gated norm where the inner width is normalised a group of
    B and C: x [..., W] in `groups` runs of W / groups channels, each under
    its own mean square; `weight` [W]."""
    x32 = x.astype(jnp.float32).reshape(x.shape[:-1] + (groups, -1))
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (y.reshape(x.shape) * weight.astype(jnp.float32)).astype(x.dtype)


def split_xbc(xbc, heads: int, head_dim: int, groups: int, state: int):
    """What Mamba-2's convolution leaves, [..., H * P + 2 * G * N], cut into
    x [..., H, P] and a group's B and C [..., G, N]."""
    inner, grouped = heads * head_dim, groups * state
    x, b, c = jnp.split(xbc, [inner, inner + grouped], axis=-1)
    lead = xbc.shape[:-1]
    return (
        x.reshape(lead + (heads, head_dim)), b.reshape(lead + (groups, state)),
        c.reshape(lead + (groups, state)),
    )


def l2_norm(x, eps=1e-6):
    """x [..., d] float32 over its last axis' length (flash-linear-attention's
    `l2norm`: the eps is under the root)."""
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + eps)


def qk_norm(q, k, q_weight, k_weight, eps):
    """Olmo 2's QK-norm: an RMS norm over the WHOLE query and key
    projections [..., heads * head size], before they are cut into heads."""
    return rms_norm(q, q_weight, eps), rms_norm(k, k_weight, eps)


def output_norm_block(h, mixer, mlp, norm1, norm2, eps, dtype):
    """Olmo 2's reordered norm, a layer on the residual rows h: the norm is
    on each sub-layer's OUTPUT, `h + norm1(mixer(h))` and then `h +
    norm2(mlp(h))`; the sub-layers read the stream as it is. Sums in
    float32, the stream kept in `dtype`."""
    h = (h.astype(jnp.float32) + rms_norm(mixer(h), norm1, eps)).astype(dtype)
    return (h.astype(jnp.float32) + rms_norm(mlp(h), norm2, eps)).astype(dtype)


def where_live(live, new, old):
    """`new` [B, ...] for the lanes that decode this step (`live` [B]) and
    `old` for the rest: what a recurrent kind's decode returns of an array
    its slots keep."""
    return jnp.where(jnp.expand_dims(live, tuple(range(1, new.ndim))), new, old)


@jax.jit
def inverse_softplus(dt):
    return dt + jnp.log(-jnp.expm1(-dt))


def matmul(x, w, dtype):
    return jnp.dot(
        x.astype(dtype), w.astype(dtype), preferred_element_type=jnp.float32
    )


# ---------------- rotary positions ----------------


def rope_frequencies(rope: Dict[str, Any], rotated: int) -> Tuple[np.ndarray, float]:
    """(inverse frequencies [rotated / 2] float32, the factor cos and sin
    are multiplied by) of one kind of layer. "default": base^(-2i/d).
    "yarn", as Hugging Face's `_compute_yarn_parameters` over the rotated
    dimension d: with f_i = base^(2i/d) and dim(n) = d ln(L / (2 pi n)) /
    (2 ln base) for the original length L, low = floor(dim(beta_fast)),
    high = ceil(dim(beta_slow)) clipped to [0, d - 1], ramp_i =
    clip((i - low) / (high - low), 0, 1): (1 - ramp_i) / f_i + ramp_i /
    (factor f_i)."""
    base = float(rope["rope_theta"])
    f = base ** (np.arange(0, rotated, 2, dtype=np.float64) / rotated)
    kind = rope.get("rope_type", "default")
    if kind == "default":
        return (1.0 / f).astype(np.float32), 1.0
    if kind != "yarn":
        raise ValueError(f"rope_type {kind!r} is not implemented")
    factor, original = float(rope["factor"]), rope["original_max_position_embeddings"]

    def dim(rotations):
        return rotated * math.log(original / (rotations * 2 * math.pi)) / (2 * math.log(base))

    low = max(math.floor(dim(rope["beta_fast"])), 0)
    high = min(math.ceil(dim(rope["beta_slow"])), rotated - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(rotated // 2, dtype=np.float64) - low) / (high - low), 0, 1)
    inv = (1 - ramp) / f + ramp / (factor * f)
    scale = rope.get("attention_factor")
    if scale is None:  # the default of a config that names none
        scale = 0.1 * math.log(factor) + 1.0
    return inv.astype(np.float32), float(scale)


def rotary_tables(rope: Dict[str, Any], rotated: int, positions):
    """cos and sin [..., rotated / 2] float32 at `positions` [...] of one
    kind of layer."""
    inv, scale = rope_frequencies(rope, rotated)
    angles = positions.astype(jnp.float32)[..., None] * jnp.asarray(inv)
    return jnp.cos(angles) * scale, jnp.sin(angles) * scale


def rotate(x, cos, sin):
    """x [..., H, d] float32 with its first 2 * cos.shape[-1] dimensions
    rotated in pairs (i, i + half), the rest passed through."""
    half = cos.shape[-1]
    x1, x2, rest = x[..., :half], x[..., half : 2 * half], x[..., 2 * half :]
    c, s = cos[..., None, :], sin[..., None, :]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s, rest], axis=-1)


# ---------------- the MLPs ----------------


def gated_mlp(x, w_in, w_out, dtype, gate_multiplier=None, out_multiplier=None):
    """w_out (silu(g) * u), [g, u] = w_in x; w_in [D, 2F], w_out [F, D].
    Where the model has them (Falcon-H1's `mlp_multipliers`), g times
    `gate_multiplier` before the silu and the result times
    `out_multiplier`, both on the float32 products."""
    g, u = jnp.split(matmul(x, w_in, dtype), 2, axis=-1)
    if gate_multiplier is not None:
        g = g * gate_multiplier
    out = matmul(jax.nn.silu(g) * u, w_out, dtype)
    return out if out_multiplier is None else out * out_multiplier


def experts(cfg, p, x, *, grouped: bool, valid=None):
    """routed(x) + shared(x) for x [T, D], float32 (routed(x) alone for a
    model without a shared expert: no `shared_in` among the layer's
    parameters), and the routing's
    counts over the tokens `valid` marks (all, where None): assignments to
    experts held here and to absent ones, held experts that a token
    reached, the fullest held expert's load, and every held expert's
    (`load` [held]); where `grouped`, also the sorted rows the grouped
    path visits for them (`walked`, at least `held` and at most `held +
    absent`: `ray_tpu.ops.grouped_experts.rows_walked`). The router's rule is the
    configuration's (`router_score` and, where it scales the gates,
    `routed_scaling_factor`; `ray_tpu.ops.grouped_experts.route`), with the
    layer's selection bias where it has one (`router_bias`)."""
    held = cfg.local_of()
    with jax.named_scope("llm.moe.router"):
        ids, gates = route(
            x, p["router"], cfg.num_experts_per_tok, score=cfg.router_score,
            scale=getattr(cfg, "routed_scaling_factor", 1.0),
            **({"bias": p["router_bias"]} if "router_bias" in p else {}),
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
            "load": load,
        }
        if grouped:
            counts["walked"] = rows_walked(
                counts["held"], ids.size, len(cfg.experts_held) / held.shape[0]
            )
    xc = x.astype(cfg.dtype)
    w_in, w_out = p["experts_in"].astype(cfg.dtype), p["experts_out"].astype(cfg.dtype)
    with jax.named_scope("llm.moe.routed"):
        if grouped:
            routed = routed_grouped(xc, ids, gates, held, w_in, w_out, valid)
        else:
            routed = routed_dense(xc, ids, gates, held, w_in, w_out)
    if "shared_in" not in p:
        return routed, counts
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
