"""IBM Granite 4.0-H (`granitemoehybrid`): Mamba-2 and grouped-query
attention layers without positions, each followed by routed experts plus a
shared expert.

Pure functions over a plain tree of parameters (no flax): the serving
programs in `ray_tpu.llm.hybrid_runner` and the full-sequence `forward`
below run the same layer code and differ only in where a mixer's memory
comes from (a state slot and the paged cache, or nothing). With `h` the
residual stream, `r` the residual multiplier and RMS norms throughout:

    h = wte[ids] * embedding_multiplier
    a layer:  h = h + r * mixer(norm1(h));  x = norm2(h)
              h = h + r * (routed(x) + shared(x))
    logits = norm_f(h) @ wte.T / logits_scaling

The attention mixer has `num_attention_heads` query heads over
`num_key_value_heads` cached heads, scores scaled by `attention_multiplier`
(not 1/sqrt(d)) and no position embedding of any kind. The Mamba-2 mixer is
`[z, xBC, dt] = in_proj(u)`, a causal depthwise convolution over
`mamba_d_conv` positions and SiLU on xBC, the recurrence of `ray_tpu.ops.ssd`
on `[x, B, C] = xBC` with `dt = softplus(dt + dt_bias)`, `A = -exp(A_log)`
and the skip `D * x`, an RMS norm of `y * silu(z)` over the whole inner
width, and `out_proj`. The routed experts follow `ray_tpu.ops.grouped_experts`:
the router scores all `num_local_experts`, and the layer computes the part
of the sum that the experts in `experts_held` give, which is what expert
parallelism asks of one chip.

Parameters are held in `param_dtype` (bfloat16, the checkpoint's own type),
matrix products take `dtype` operands and accumulate in float32, the
recurrent state is float32 and the convolution's tail is `dtype`.

Not imported by `ray_tpu` or `ray_tpu.models`: import this module by name.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable, Dict, Tuple

import jax
import jax.numpy as jnp

from ray_tpu.llm.cache import CacheClass, RecurrentKind
from ray_tpu.models import parts
from ray_tpu.models.parts import (  # noqa: F401  (this module's names for them)
    experts,
    gated_mlp as _gated_mlp,
    inverse_softplus as _inverse_softplus,
    matmul as _matmul,
    normal as _normal,
    num_params,
    rms_norm,
)
from ray_tpu.ops.grouped_experts import route  # noqa: F401  (tests call it here)
from ray_tpu.ops.ssd import ssd_chunked_scan, ssm_decode_update

MAMBA, ATTENTION = "mamba", "attention"
# The parts of a layer a trace's time is split by
# (`ray_tpu.util.device_report.scopes_of`), and the scope of an attention layer's
# projections and of attention alone: one here.
SCOPES = (
    "llm.mixer.mamba.proj", "llm.mixer.mamba.scan", "llm.mixer.mamba.update",
    "llm.mixer.attention", "llm.moe.router", "llm.moe.routed",
    "llm.moe.shared", "llm.head",
)
ATTENTION_SCOPES = {ATTENTION: ("llm.mixer.attention", "llm.mixer.attention")}
# One period of granite-4.0-h-small's `layer_types`.
GRANITE_4_H_PERIOD = (MAMBA,) * 5 + (ATTENTION,) + (MAMBA,) * 4


@dataclasses.dataclass(frozen=True)
class GraniteHybridConfig:
    """Keys as the published config.json names them, plus `experts_held`
    (which of the layer's routed experts this chip holds) and the types."""

    vocab_size: int = 100352
    hidden_size: int = 4096
    layer_types: Tuple[str, ...] = GRANITE_4_H_PERIOD
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    mamba_n_heads: int = 128
    mamba_d_head: int = 64
    mamba_d_state: int = 128
    mamba_n_groups: int = 1
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    mamba_chunk_size: int = 256
    intermediate_size: int = 768
    shared_intermediate_size: int = 1536
    num_local_experts: int = 72
    num_experts_per_tok: int = 10
    experts_held: Tuple[int, ...] = tuple(range(72))
    embedding_multiplier: float = 12.0
    attention_multiplier: float = 0.0078125
    residual_multiplier: float = 0.22
    logits_scaling: float = 16.0
    rms_norm_eps: float = 1e-5
    max_position_embeddings: int = 131072
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.bfloat16

    # What `ray_tpu.llm` reads off a model's configuration: which runner
    # builds its programs, and that some of its layers carry a recurrent
    # state beside the paged cache (so there is no prefix to share, and
    # the features that assume a cache-only model are refused).
    llm_runner = "ray_tpu.llm.hybrid_runner:HybridRunner"
    llm_model = "ray_tpu.models.granite_hybrid"
    recurrent_state = True
    # The router's rule (`ray_tpu.ops.grouped_experts.route`): gates a
    # softmax over the chosen logits.
    router_score = "chosen"

    def __post_init__(self):
        if self.mamba_n_heads % self.mamba_n_groups:
            raise ValueError("mamba heads must divide into the groups of B and C")
        if self.mamba_n_heads * self.mamba_d_head != self.mamba_expand * self.hidden_size:
            raise ValueError("mamba heads x head size must be expand x hidden")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("query heads must be a multiple of cached heads")
        if set(self.layer_types) - {MAMBA, ATTENTION}:
            raise ValueError(f"unknown layer types in {self.layer_types}")
        parts.check_experts_held(self.experts_held, self.num_local_experts)

    # The names the engine knows a model's geometry by.
    @property
    def num_layers(self) -> int:
        return len(self.layer_types)

    @property
    def num_heads(self) -> int:
        return self.num_attention_heads

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def max_seq_len(self) -> int:
        return self.max_position_embeddings

    @property
    def attention_scale(self) -> float:
        return self.attention_multiplier

    @property
    def cache_classes(self) -> Tuple[CacheClass, ...]:
        """One class: the attention layers keep every position."""
        return (CacheClass("full", self.attention_layers, None),)

    def cache_class_of(self, kind: str) -> int:
        return 0

    def heads_of(self, kind: str) -> Tuple[int, ...]:
        return (self.num_attention_heads,)

    @property
    def mamba_layers(self) -> int:
        return sum(kind == MAMBA for kind in self.layer_types)

    @property
    def attention_layers(self) -> int:
        return sum(kind == ATTENTION for kind in self.layer_types)

    @property
    def d_inner(self) -> int:
        return self.mamba_n_heads * self.mamba_d_head

    @property
    def conv_dim(self) -> int:
        return self.d_inner + 2 * self.mamba_n_groups * self.mamba_d_state

    def local_of(self) -> jax.Array:
        """[num_local_experts] int32: an expert's row in the held weights,
        -1 for an expert another chip holds."""
        return parts.local_of(self.num_local_experts, self.experts_held)


def recurrent_shape(cfg: GraniteHybridConfig) -> Dict[str, int]:
    """The Mamba layers' state as `stats()` publishes it."""
    return {
        "num_layers": cfg.mamba_layers,
        "num_heads": cfg.mamba_n_heads,
        "head_dim": cfg.mamba_d_head,
        "state_size": cfg.mamba_d_state,
        "conv_width": cfg.mamba_d_conv,
        "conv_dim": cfg.conv_dim,
        "chunk_size": cfg.mamba_chunk_size,
        "state_itemsize": 4,
        "conv_itemsize": jnp.dtype(cfg.dtype).itemsize,
    }


def recurrent_kinds(cfg: GraniteHybridConfig) -> Dict[str, RecurrentKind]:
    """What a state slot keeps for one Mamba layer, and the layer's two
    functions: the runner makes the pools and calls them."""
    return {
        MAMBA: RecurrentKind(
            arrays=(
                ("conv", (cfg.mamba_d_conv - 1, cfg.conv_dim), cfg.dtype),
                ("ssm", (cfg.mamba_n_heads, cfg.mamba_d_head, cfg.mamba_d_state),
                 jnp.float32),
            ),
            prefill=mamba_prefill, decode=mamba_decode,
            scan_scope="llm.mixer.mamba.scan",
        ),
    }


def expert_shape(cfg: GraniteHybridConfig) -> Dict[str, int]:
    """The routed experts as `stats()` publishes them."""
    return {
        "num_layers": cfg.num_layers,
        "num_experts": cfg.num_local_experts,
        "experts_held": len(cfg.experts_held),
        "experts_per_token": cfg.num_experts_per_tok,
        "hidden_size": cfg.hidden_size,
        "expert_width": cfg.intermediate_size,
    }


# ---------------- parameters ----------------


def _leaf_shapes(cfg: GraniteHybridConfig) -> Dict[str, Any]:
    d, held = cfg.hidden_size, len(cfg.experts_held)
    kv = cfg.num_key_value_heads * cfg.head_dim
    layers = []
    for kind in cfg.layer_types:
        if kind == MAMBA:
            mixer = {
                "in_proj": (d, cfg.d_inner + cfg.conv_dim + cfg.mamba_n_heads),
                "conv_w": (cfg.mamba_d_conv, cfg.conv_dim),
                "conv_b": (cfg.conv_dim,),
                "A_log": (cfg.mamba_n_heads,),
                "D": (cfg.mamba_n_heads,),
                "dt_bias": (cfg.mamba_n_heads,),
                "norm": (cfg.d_inner,),
                "out_proj": (cfg.d_inner, d),
            }
        else:
            mixer = {"q": (d, d), "k": (d, kv), "v": (d, kv), "o": (d, d)}
        layers.append({
            "norm1": (d,), "norm2": (d,), "mixer": mixer,
            "router": (d, cfg.num_local_experts),
            "experts_in": (held, d, 2 * cfg.intermediate_size),
            "experts_out": (held, cfg.intermediate_size, d),
            "shared_in": (d, 2 * cfg.shared_intermediate_size),
            "shared_out": (cfg.shared_intermediate_size, d),
        })
    return {"wte": (cfg.vocab_size, d), "norm_f": (d,), "layers": layers}


def init_params(cfg: GraniteHybridConfig, seed: int) -> Dict[str, Any]:
    """Seeded weights, made leaf by leaf in `param_dtype` (a float32 tree
    of the serving size does not fit a chip): normal(0.02) matrices, ones
    for the norms and `D`, and the Mamba family's published initialisation
    where normal(0.02) would make the state forget within a few tokens:
    `A` uniform in [1, 16], `dt` log-uniform in [0.001, 0.1] behind the
    softplus, the convolution uniform in +-1/sqrt(d_conv). The embedding
    is normal(0.02 / embedding_multiplier), so that what enters the
    residual stream is normal(0.02): at normal(0.02) the multiplied
    embedding is a sixth of the final stream, the tied head puts the input
    token eleven standard deviations above every other logit, and every
    greedy answer is its prompt's last token whatever the layers compute
    (chip run, PR 32)."""
    leaves, tree = jax.tree_util.tree_flatten_with_path(
        _leaf_shapes(cfg), is_leaf=lambda v: isinstance(v, tuple)
    )
    base = jax.random.PRNGKey(seed)
    made = []
    for index, (path, shape) in enumerate(leaves):
        name = path[-1].key
        key = jax.random.fold_in(base, index)
        bound = 1.0 / math.sqrt(cfg.mamba_d_conv)
        if name.startswith("norm") or name == "D":
            leaf = jnp.ones(shape, jnp.float32)
        elif name == "A_log":
            leaf = jnp.log(jax.random.uniform(key, shape, minval=1.0, maxval=16.0))
        elif name == "dt_bias":
            lo, hi = math.log(0.001), math.log(0.1)
            leaf = _inverse_softplus(
                jnp.exp(jax.random.uniform(key, shape, minval=lo, maxval=hi))
            )
        elif name in ("conv_w", "conv_b"):
            leaf = jax.random.uniform(key, shape, minval=-bound, maxval=bound)
        else:
            std = 0.02 / (cfg.embedding_multiplier if name == "wte" else 1.0)
            leaf = _normal(key, shape, cfg.param_dtype, std)
        made.append(leaf.astype(cfg.param_dtype))
    return jax.tree_util.tree_unflatten(tree, made)


# ---------------- the parts of a layer ----------------


def _mamba_split(cfg, p, u):
    """in_proj and the cut into the gate z, the convolution's input xBC
    and the step dt (after its bias and softplus)."""
    with jax.named_scope("llm.mixer.mamba.proj"):
        zxbcdt = _matmul(u, p["in_proj"], cfg.dtype)
    z, xbc, dt = jnp.split(
        zxbcdt, [cfg.d_inner, cfg.d_inner + cfg.conv_dim], axis=-1
    )
    dt = jax.nn.softplus(dt + p["dt_bias"].astype(jnp.float32))
    return z, xbc.astype(cfg.dtype), dt


def _mamba_finish(cfg, p, y, x, z):
    """The skip, the gated norm over the whole inner width, out_proj's
    input: y and x [..., H, P], z [..., d_inner]."""
    y = y + p["D"].astype(jnp.float32)[:, None] * x.astype(jnp.float32)
    y = y.reshape(y.shape[:-2] + (cfg.d_inner,)) * jax.nn.silu(z)
    return rms_norm(y, p["norm"], cfg.rms_norm_eps)


def _xbc_parts(cfg, xbc):
    return parts.split_xbc(
        xbc, cfg.mamba_n_heads, cfg.mamba_d_head, cfg.mamba_n_groups, cfg.mamba_d_state
    )


def mamba_prefill(cfg, p, u, conv_tail, ssm, length):
    """A chunk of one sequence. u [T, D]; conv_tail [d_conv - 1, conv_dim]
    holds xBC of the positions before the chunk (zeros at a sequence's
    start) and ssm [H, P, N] the state there. Returns the mixer's output
    [T, D] and tail and state after token `length` - 1."""
    z, xbc, dt = _mamba_split(cfg, p, u)
    with jax.named_scope("llm.mixer.mamba.scan"):
        taps = cfg.mamba_d_conv
        padded = jnp.concatenate([conv_tail.astype(cfg.dtype), xbc], axis=0)
        w = p["conv_w"].astype(jnp.float32)
        conv = sum(
            padded[i : i + u.shape[0]].astype(jnp.float32) * w[i]
            for i in range(taps)
        ) + p["conv_b"].astype(jnp.float32)
        new_tail = jax.lax.dynamic_slice_in_dim(padded, length, taps - 1, axis=0)
        x, b, c = _xbc_parts(cfg, jax.nn.silu(conv).astype(cfg.dtype))
        y, new_ssm = ssd_chunked_scan(
            x, dt, -jnp.exp(p["A_log"].astype(jnp.float32)), b, c, ssm,
            chunk=cfg.mamba_chunk_size, length=length, dtype=cfg.dtype,
        )
        y = _mamba_finish(cfg, p, y, x, z)
    with jax.named_scope("llm.mixer.mamba.proj"):
        out = _matmul(y, p["out_proj"], cfg.dtype)
    return out, new_tail, new_ssm


def mamba_decode(cfg, p, u, conv_tail, ssm, live):
    """One token for each of a batch of sequences. u [B, D], conv_tail
    [B, d_conv - 1, conv_dim], ssm [B, H, P, N]; a lane that is not `live`
    [B] keeps its tail and state."""
    z, xbc, dt = _mamba_split(cfg, p, u)
    with jax.named_scope("llm.mixer.mamba.update"):
        window = jnp.concatenate(
            [conv_tail.astype(cfg.dtype), xbc[:, None]], axis=1
        )
        conv = jnp.sum(
            window.astype(jnp.float32) * p["conv_w"].astype(jnp.float32), axis=1
        ) + p["conv_b"].astype(jnp.float32)
        x, b, c = _xbc_parts(cfg, jax.nn.silu(conv).astype(cfg.dtype))
        y, new_ssm = ssm_decode_update(
            x, dt, -jnp.exp(p["A_log"].astype(jnp.float32)), b, c, ssm, live
        )
        y = _mamba_finish(cfg, p, y, x, z)
    with jax.named_scope("llm.mixer.mamba.proj"):
        out = _matmul(y, p["out_proj"], cfg.dtype)
    with jax.named_scope("llm.mixer.mamba.update"):
        new_tail = parts.where_live(live, window[:, 1:], conv_tail)
    return out, new_tail, new_ssm


def attention_qkv(cfg, kind, p, u, positions=None):
    """u [..., D] -> q [..., Hq, d], k and v [..., Hkv, d] in `dtype`. This
    model's attention has no positions."""
    def heads(w, n):
        out = _matmul(u, w, cfg.dtype).astype(cfg.dtype)
        return out.reshape(u.shape[:-1] + (n, cfg.head_dim))

    return (
        heads(p["q"], cfg.num_attention_heads),
        heads(p["k"], cfg.num_key_value_heads),
        heads(p["v"], cfg.num_key_value_heads),
    )


def attention_out(cfg, kind, p, u, mixed):
    """The output projection of mixed [..., Hq, d] -> [..., D] float32."""
    return _matmul(mixed.reshape(u.shape[:-1] + (-1,)), p["o"], cfg.dtype)


def causal_attention(cfg, q, k, v):
    """Dense causal grouped-query attention of one sequence: q [T, Hq, d],
    k and v [T, Hkv, d]. The full-sequence forward's, with no cache."""
    group = cfg.num_attention_heads // cfg.num_key_value_heads
    k, v = (jnp.repeat(t, group, axis=1) for t in (k, v))
    scores = jnp.einsum(
        "qhd,khd->hqk", q, k, preferred_element_type=jnp.float32
    ) * cfg.attention_multiplier
    t_len = q.shape[0]
    scores = jnp.where(jnp.tril(jnp.ones((t_len, t_len), bool)), scores, -jnp.inf)
    weights = jax.nn.softmax(scores, axis=-1).astype(cfg.dtype)
    return jnp.einsum("hqk,khd->qhd", weights, v, preferred_element_type=jnp.float32)


def embed(cfg, params, ids):
    return parts.embed(params["wte"], ids, cfg.dtype, cfg.embedding_multiplier)


def head(cfg, params, h):
    """Logits (float32) of the residual rows h [..., D]."""
    return parts.head(
        h, params["norm_f"], cfg.rms_norm_eps, params["wte"], cfg.dtype,
        tied=True, scaling=cfg.logits_scaling,
    )


def run_layers(
    cfg: GraniteHybridConfig, params, h, mixers: Dict[str, Callable], *,
    grouped: bool, valid=None,
):
    """The layer stack over the residual rows h [T, D]. `mixers[kind](i,
    p, u)` is the mixer of the i-th layer of its kind: it owns where the
    layer's memory lives. Returns h and the routing's counts summed over
    the layers."""
    r = cfg.residual_multiplier
    seen = dict.fromkeys(mixers, 0)
    totals = None
    for kind, p in zip(cfg.layer_types, params["layers"]):
        u = rms_norm(h, p["norm1"], cfg.rms_norm_eps)
        mixed = mixers[kind](seen[kind], p["mixer"], u)
        seen[kind] += 1
        h = (h.astype(jnp.float32) + r * mixed).astype(cfg.dtype)
        x = rms_norm(h, p["norm2"], cfg.rms_norm_eps)
        out, counts = experts(cfg, p, x, grouped=grouped, valid=valid)
        h = (h.astype(jnp.float32) + r * out).astype(cfg.dtype)
        totals = parts.add_counts(totals, counts)
    return h, totals


def forward(cfg: GraniteHybridConfig, params, tokens, *, grouped: bool = True):
    """Logits [T, vocab] of one whole sequence `tokens` [T] from an empty
    state and no cache: the chunked scan and the grouped experts as the
    prefill programs run them, dense causal attention."""
    t_len = tokens.shape[0]

    def mamba(_, p, u):
        tail = jnp.zeros((cfg.mamba_d_conv - 1, cfg.conv_dim), cfg.dtype)
        ssm = jnp.zeros(
            (cfg.mamba_n_heads, cfg.mamba_d_head, cfg.mamba_d_state), jnp.float32
        )
        return mamba_prefill(cfg, p, u, tail, ssm, t_len)[0]

    def attend(_, p, u):
        with jax.named_scope("llm.mixer.attention"):
            q, k, v = attention_qkv(cfg, ATTENTION, p, u)
            mixed = causal_attention(cfg, q, k, v).astype(cfg.dtype)
            return attention_out(cfg, ATTENTION, p, u, mixed)

    h, _ = run_layers(
        cfg, params, embed(cfg, params, tokens),
        {MAMBA: mamba, ATTENTION: attend}, grouped=grouped,
    )
    return head(cfg, params, h)
