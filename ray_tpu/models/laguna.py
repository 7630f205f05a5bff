"""poolside Laguna (`laguna`): full-attention and sliding-window layers
mixed, rotary positions of two forms, a head-wise output gate, grouped-query
attention whose query heads differ by layer, a leading dense MLP and then
routed experts beside a shared expert, an untied head.

Pure functions over a plain tree of parameters (no flax), as
`ray_tpu.models.granite_hybrid`: the serving programs in
`ray_tpu.llm.hybrid_runner` and the full-sequence `forward` below run the
same layer code and differ only in where attention finds its keys (the
paged cache of the layer's class, or the sequence itself). With `h` the
residual stream and RMS norms (a learned weight, eps 1e-6) throughout:

    h = wte[ids]
    a layer:  u = norm1(h);  q, k, v = u Wq, u Wk, u Wv
              q, k rotated by the layer's kind (below)
              o = attention(q, k, v) * sigmoid(u Wg)[head];  h = h + o Wo
              x = norm2(h);  h = h + mlp(x)
    logits = norm_f(h) @ lm_head

A layer of `layer_types` "sliding_attention" has the query at position i
see the keys at i - `sliding_window` < j <= i and rotates all of a head's
dimensions (`rope_parameters`: default, base 10,000); a "full_attention"
layer sees every j <= i and rotates the first `partial_rotary_factor` of
them by YaRN's frequencies (Hugging Face's `_compute_yarn_parameters`:
base 500,000, factor 128, original length 8,192, beta 32 and 1), cos and
sin times `attention_factor`. Pairs are (i, i + rotated/2). Layer l has
`num_attention_heads_per_layer[l]` query heads over `num_key_value_heads`
cached ones, scores scaled by head_dim^-0.5; K is cached rotated. The gate
is one scalar a head (`gating: per-head`, the head-wise gate of
arXiv:2505.06708). `mlp_layer_types` "dense" is a gated MLP of
`intermediate_size`; "sparse" is `ray_tpu.models.parts.experts`: the router
scores all `num_experts` by a softmax, a token takes its
`num_experts_per_tok` largest shares, divided by their sum and multiplied
by `moe_routed_scaling_factor`, this chip computes the part of the sum that
the experts in `experts_held` give, and a shared expert of
`shared_expert_intermediate_size` is added with weight 1.

Parameters are held in `param_dtype` (bfloat16), matrix products take
`dtype` operands and accumulate in float32, rotation, gate, router and
softmax are float32.

Not imported by `ray_tpu` or `ray_tpu.models`: import this module by name.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.llm.cache import CacheClass
from ray_tpu.models import parts
from ray_tpu.models.parts import (  # noqa: F401  (names the runner and tests know)
    num_params,
    rope_frequencies,
    rotate,
)

FULL, SLIDING = "full_attention", "sliding_attention"
DENSE, SPARSE = "dense", "sparse"
# One period of Laguna-S-2.1's `layer_types`, and the rotary parameters of
# its two kinds of layer as the published config.json has them.
LAGUNA_PERIOD = (FULL, SLIDING, SLIDING, SLIDING)
LAGUNA_S_ROPE = {
    FULL: {
        "rope_theta": 500000, "rope_type": "yarn", "factor": 128,
        "original_max_position_embeddings": 8192, "beta_slow": 1,
        "beta_fast": 32, "attention_factor": 1.4852030263919618,
        "partial_rotary_factor": 0.5,
    },
    SLIDING: {
        "rope_type": "default", "rope_theta": 10000, "partial_rotary_factor": 1,
    },
}
# The parts of a layer a trace's time is split by
# (`ray_tpu.util.device_report.scopes_of`), and per kind of attention layer the scope of
# its projections (q, k, v, rotation, gate, output) and of attention alone.
SCOPES = (
    "llm.mixer.attention.proj", "llm.mixer.attention.full",
    "llm.mixer.attention.window", "llm.mlp.dense", "llm.moe.router",
    "llm.moe.routed", "llm.moe.shared", "llm.head",
)
ATTENTION_SCOPES = {
    FULL: ("llm.mixer.attention.proj", "llm.mixer.attention.full"),
    SLIDING: ("llm.mixer.attention.proj", "llm.mixer.attention.window"),
}


@dataclasses.dataclass(frozen=True)
class LagunaConfig:
    """Keys as the published config.json names them, plus `experts_held`
    (which of a layer's routed experts this chip holds) and the types.
    `rope_parameters` may be given as the published nested dict; it is kept
    as sorted tuples so that the configuration hashes (`rope`)."""

    vocab_size: int = 100352
    hidden_size: int = 3072
    intermediate_size: int = 12288
    layer_types: Tuple[str, ...] = LAGUNA_PERIOD * 12
    num_attention_heads_per_layer: Tuple[int, ...] = (48, 72, 72, 72) * 12
    mlp_layer_types: Tuple[str, ...] = (DENSE,) + (SPARSE,) * 47
    num_key_value_heads: int = 8
    head_dim: int = 128
    rope_parameters: Any = parts.frozen(LAGUNA_S_ROPE)
    sliding_window: int = 512
    num_experts: int = 256
    num_experts_per_tok: int = 10
    moe_intermediate_size: int = 1024
    shared_expert_intermediate_size: int = 1024
    experts_held: Tuple[int, ...] = tuple(range(256))
    moe_routed_scaling_factor: float = 2.5
    rms_norm_eps: float = 1e-6
    max_position_embeddings: int = 1048576
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.bfloat16

    # What `ray_tpu.llm` reads off a model's configuration: which runner
    # builds its programs from which model module, and the router's rule
    # (`ray_tpu.ops.grouped_experts.route`): a softmax over every expert,
    # the chosen shares renormalised and scaled.
    llm_runner = "ray_tpu.llm.hybrid_runner:HybridRunner"
    llm_model = "ray_tpu.models.laguna"
    recurrent_state = False
    router_score = "all"

    def __post_init__(self):
        if isinstance(self.rope_parameters, dict):
            object.__setattr__(self, "rope_parameters", parts.frozen(self.rope_parameters))
        for name in ("layer_types", "num_attention_heads_per_layer",
                     "mlp_layer_types", "experts_held"):
            object.__setattr__(self, name, tuple(getattr(self, name)))
        n = len(self.layer_types)
        if len(self.num_attention_heads_per_layer) != n or len(self.mlp_layer_types) != n:
            raise ValueError("one head count and one MLP type a layer")
        if set(self.layer_types) - {FULL, SLIDING}:
            raise ValueError(f"unknown layer types in {self.layer_types}")
        if set(self.mlp_layer_types) - {DENSE, SPARSE}:
            raise ValueError(f"unknown MLP types in {self.mlp_layer_types}")
        if any(h % self.num_key_value_heads for h in self.num_attention_heads_per_layer):
            raise ValueError("query heads must be a multiple of cached heads")
        if self.sliding_window < 1:
            raise ValueError("sliding_window must be at least 1")
        for kind in set(self.layer_types):
            rotated = self.rotary_dim(kind)
            if rotated % 2 or not 0 < rotated <= self.head_dim:
                raise ValueError(f"{kind}: {rotated} rotated dimensions of {self.head_dim}")
        parts.check_experts_held(self.experts_held, self.num_experts)

    # The names the engine and the shared parts know a model's geometry by.
    @property
    def num_layers(self) -> int:
        return len(self.layer_types)

    @property
    def num_heads(self) -> int:
        return max(self.num_attention_heads_per_layer)

    @property
    def max_seq_len(self) -> int:
        return self.max_position_embeddings

    @property
    def routed_scaling_factor(self) -> float:
        return self.moe_routed_scaling_factor

    @property
    def attention_scale(self) -> float:
        return self.head_dim ** -0.5

    @property
    def cache_classes(self) -> Tuple[CacheClass, ...]:
        """The cache class of every kind of layer this model has: full
        layers keep every position, sliding layers the last
        `sliding_window`. The full class comes first (it is the one every
        model has), also where the model has no full layer."""
        count = self.layer_types.count
        classes = [CacheClass("full", count(FULL), None)]
        if count(SLIDING):
            classes.append(CacheClass("window", count(SLIDING), self.sliding_window))
        return tuple(classes)

    def cache_class_of(self, kind: str) -> int:
        """Index into `cache_classes` of a layer kind's class."""
        return 0 if kind == FULL else 1

    def heads_of(self, kind: str) -> Tuple[int, ...]:
        """The query head counts the layers of `kind` have."""
        return tuple(sorted({
            h for k, h in zip(self.layer_types, self.num_attention_heads_per_layer)
            if k == kind
        }))

    def rope(self, kind: str) -> Dict[str, Any]:
        return dict(dict(self.rope_parameters)[kind])

    def rotary_dim(self, kind: str) -> int:
        return int(self.head_dim * self.rope(kind).get("partial_rotary_factor", 1))

    def local_of(self) -> jax.Array:
        return parts.local_of(self.num_experts, self.experts_held)


def expert_shape(cfg: LagunaConfig) -> Dict[str, int]:
    """The routed experts as `stats()` publishes them: `num_layers` counts
    the layers that have them."""
    return {
        "num_layers": cfg.mlp_layer_types.count(SPARSE),
        "num_experts": cfg.num_experts,
        "experts_held": len(cfg.experts_held),
        "experts_per_token": cfg.num_experts_per_tok,
        "hidden_size": cfg.hidden_size,
        "expert_width": cfg.moe_intermediate_size,
    }


# ---------------- rotary positions ----------------


def rotary_tables(cfg: LagunaConfig, kind: str, positions):
    """cos and sin [..., rotated / 2] float32 at `positions` [...]."""
    return parts.rotary_tables(cfg.rope(kind), cfg.rotary_dim(kind), positions)


# ---------------- parameters ----------------


def _leaf_shapes(cfg: LagunaConfig) -> Dict[str, Any]:
    d, held, hd = cfg.hidden_size, len(cfg.experts_held), cfg.head_dim
    kv = cfg.num_key_value_heads * hd
    layers = []
    for heads, mlp in zip(cfg.num_attention_heads_per_layer, cfg.mlp_layer_types):
        layer = {
            "norm1": (d,), "norm2": (d,),
            "mixer": {
                "q": (d, heads * hd), "k": (d, kv), "v": (d, kv),
                "g": (d, heads), "o": (heads * hd, d),
            },
        }
        if mlp == DENSE:
            layer.update(
                mlp_in=(d, 2 * cfg.intermediate_size),
                mlp_out=(cfg.intermediate_size, d),
            )
        else:
            layer.update(
                router=(d, cfg.num_experts),
                experts_in=(held, d, 2 * cfg.moe_intermediate_size),
                experts_out=(held, cfg.moe_intermediate_size, d),
                shared_in=(d, 2 * cfg.shared_expert_intermediate_size),
                shared_out=(cfg.shared_expert_intermediate_size, d),
            )
        layers.append(layer)
    return {
        "wte": (cfg.vocab_size, d), "norm_f": (d,), "lm_head": (d, cfg.vocab_size),
        "layers": layers,
    }


def init_params(cfg: LagunaConfig, seed: int) -> Dict[str, Any]:
    """Seeded weights, made leaf by leaf in `param_dtype` (a float32 tree
    of the serving size does not fit a chip): normal(0.02) matrices, ones
    for the norms. The head is untied, so the embedding needs no smaller
    scale to keep the input token from deciding every logit
    (`granite_hybrid.init_params`)."""
    return parts.seeded_tree(_leaf_shapes(cfg), seed, cfg.param_dtype)


# ---------------- the parts of a layer ----------------


def attention_qkv(cfg, kind, p, u, positions):
    """u [..., D] at `positions` [...] -> q [..., Hq, d], k and v
    [..., Hkv, d] in `dtype`, q and k rotated (in float32) by the kind's
    rule."""
    def heads(w):
        return parts.matmul(u, w, cfg.dtype).reshape(u.shape[:-1] + (-1, cfg.head_dim))

    cos, sin = rotary_tables(cfg, kind, positions)
    return (
        rotate(heads(p["q"]), cos, sin).astype(cfg.dtype),
        rotate(heads(p["k"]), cos, sin).astype(cfg.dtype),
        heads(p["v"]).astype(cfg.dtype),
    )


def attention_out(cfg, kind, p, u, mixed):
    """The head-wise gate and the output projection: mixed [..., Hq, d],
    head j times sigmoid(u Wg)[..., j] -> [..., D] float32."""
    gate = jax.nn.sigmoid(parts.matmul(u, p["g"], cfg.dtype))
    gated = (mixed.astype(jnp.float32) * gate[..., None]).astype(cfg.dtype)
    return parts.matmul(gated.reshape(u.shape[:-1] + (-1,)), p["o"], cfg.dtype)


def causal_attention(cfg, kind, q, k, v):
    """Dense causal grouped-query attention of one sequence, inside the
    window on a sliding layer: q [T, Hq, d], k and v [T, Hkv, d]. The
    full-sequence forward's, with no cache."""
    group = q.shape[1] // k.shape[1]
    k, v = (jnp.repeat(t, group, axis=1) for t in (k, v))
    scores = jnp.einsum(
        "qhd,khd->hqk", q, k, preferred_element_type=jnp.float32
    ) * cfg.attention_scale
    t_len = q.shape[0]
    seen = jnp.tril(jnp.ones((t_len, t_len), bool))
    if kind == SLIDING:
        seen = seen & ~jnp.tril(jnp.ones((t_len, t_len), bool), -cfg.sliding_window)
    weights = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1).astype(cfg.dtype)
    return jnp.einsum("hqk,khd->qhd", weights, v, preferred_element_type=jnp.float32)


def embed(cfg, params, ids):
    return parts.embed(params["wte"], ids, cfg.dtype)


def head(cfg, params, h):
    """Logits (float32) of the residual rows h [..., D]; the head is its
    own matrix, not the embedding's."""
    return parts.head(
        h, params["norm_f"], cfg.rms_norm_eps, params["lm_head"], cfg.dtype,
        tied=False,
    )


def run_layers(
    cfg: LagunaConfig, params, h, mixers: Dict[str, Callable], *,
    grouped: bool, valid=None,
):
    """The layer stack over the residual rows h [T, D]. `mixers[kind](i,
    p, u)` is the attention of the i-th layer of its kind: it owns where
    the layer's keys live. Returns h and the routing's counts summed over
    the expert layers."""
    seen = dict.fromkeys(mixers, 0)
    totals: Optional[Dict[str, jax.Array]] = None
    for kind, mlp, p in zip(cfg.layer_types, cfg.mlp_layer_types, params["layers"]):
        u = parts.rms_norm(h, p["norm1"], cfg.rms_norm_eps)
        mixed = mixers[kind](seen[kind], p["mixer"], u)
        seen[kind] += 1
        h = (h.astype(jnp.float32) + mixed).astype(cfg.dtype)
        x = parts.rms_norm(h, p["norm2"], cfg.rms_norm_eps)
        if mlp == DENSE:
            with jax.named_scope("llm.mlp.dense"):
                out = parts.gated_mlp(x, p["mlp_in"], p["mlp_out"], cfg.dtype)
        else:
            out, counts = parts.experts(cfg, p, x, grouped=grouped, valid=valid)
            totals = parts.add_counts(totals, counts)
        h = (h.astype(jnp.float32) + out).astype(cfg.dtype)
    return h, totals


def forward(cfg: LagunaConfig, params, tokens, *, grouped: bool = True):
    """Logits [T, vocab] of one whole sequence `tokens` [T] with no cache:
    the grouped experts as the prefill programs run them, dense causal
    attention under each layer's own mask."""
    positions = jnp.arange(tokens.shape[0])

    def attend(kind):
        def mixer(_, p, u):
            q, k, v = attention_qkv(cfg, kind, p, u, positions)
            mixed = causal_attention(cfg, kind, q, k, v).astype(cfg.dtype)
            return attention_out(cfg, kind, p, u, mixed)
        return mixer

    h, _ = run_layers(
        cfg, params, embed(cfg, params, tokens),
        {kind: attend(kind) for kind in (FULL, SLIDING)}, grouped=grouped,
    )
    return head(cfg, params, h)
