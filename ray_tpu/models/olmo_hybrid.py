"""Ai2 Olmo Hybrid (`olmo_hybrid`): three gated-delta-rule layers to every
full-attention layer, a dense gated MLP in each, Olmo 2's QK-norm and
reordered norm, an untied head.

Pure functions over a plain tree of parameters (no flax), as
`ray_tpu.models.granite_hybrid` and `ray_tpu.models.laguna`: the serving
programs in `ray_tpu.llm.hybrid_runner` and the full-sequence `forward`
below run the same layer code and differ only in where a mixer's memory
comes from (a state slot and the paged cache, or nothing). With `h` the
residual stream and RMS norms (a learned weight, eps 1e-6) throughout, no
bias anywhere:

    h = wte[ids]
    a layer:  h = h + norm1(mixer(h));  h = h + norm2(W2 (silu(W1g h) * W1u h))
    logits = norm_f(h) @ lm_head

A "linear_attention" layer is the gated delta rule (Yang et al., "Gated
Delta Networks", arXiv:2412.06464; the `GatedDeltaNet` layer of
flash-linear-attention, whose argument names the config's keys repeat) over
`linear_num_value_heads` heads of key size `linear_key_head_dim` and value
size `linear_value_head_dim`:

    q, k, v = silu(conv(x Wq)), silu(conv(x Wk)), silu(conv(x Wv))
    q <- q / |q| * key_size^-0.5;  k <- k / |k|        (a head)
    beta = 2 sigmoid(x Wb)     (the 2: `linear_allow_neg_eigval`)
    g = -exp(A_log) softplus(x Wa + dt_bias)           (float32)
    the recurrence of `ray_tpu.ops.gated_delta` on a head's state [K, V]
    y = (rms_norm_V(o) * silu(x Wg)) Wo

where conv is depthwise and causal over `linear_conv_kernel_dim` positions
a channel. A "full_attention" layer has `num_attention_heads` query heads
over `num_key_value_heads` cached ones, q and k each under an RMS norm over
the WHOLE projection before the cut into heads, scores scaled by
head_dim^-0.5, and no positions of any kind (`rope_theta` is null; the
recurrent layers carry order).

Parameters are held in `param_dtype` (bfloat16), matrix products take
`dtype` operands and accumulate in float32, the recurrent state, the decay
and the chunk's solve are float32 and the convolution's tail is `dtype`.

Not imported by `ray_tpu` or `ray_tpu.models`: import this module by name.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Callable, Dict, Tuple

import jax
import jax.numpy as jnp

from ray_tpu.llm.cache import CacheClass, RecurrentKind
from ray_tpu.models import parts
from ray_tpu.models.parts import num_params  # noqa: F401  (the runner's name for it)
from ray_tpu.ops.gated_delta import (
    PACK,
    gated_delta_chunked,
    gated_delta_update,
)

LINEAR, FULL = "linear_attention", "full_attention"
# One period of Olmo-Hybrid-7B's `layer_types`.
OLMO_HYBRID_PERIOD = (LINEAR, LINEAR, LINEAR, FULL)
# The parts of a layer a trace's time is split by
# (`ray_tpu.util.device_report.scopes_of`), and the scope of an attention
# layer's projections and of attention alone (Laguna's names for them).
SCOPES = (
    "llm.mixer.gdn.proj", "llm.mixer.gdn.scan", "llm.mixer.gdn.update",
    "llm.mixer.attention.proj", "llm.mixer.attention.full", "llm.mlp",
    "llm.head",
)
ATTENTION_SCOPES = {FULL: ("llm.mixer.attention.proj", "llm.mixer.attention.full")}
# `stats()["attention_shape"]` by cache class name, as a model with several
# classes has it: what reads `llm.mixer.attention.full` reads the `full` class.
ATTENTION_SHAPE_BY_CLASS = True


@dataclasses.dataclass(frozen=True)
class OlmoHybridConfig:
    """Keys as the published config.json names them, plus `gdn_chunk` (the
    tokens of a chunk the gated delta rule solves at once) and the types."""

    vocab_size: int = 100352
    hidden_size: int = 3840
    intermediate_size: int = 11008
    layer_types: Tuple[str, ...] = OLMO_HYBRID_PERIOD * 8
    num_attention_heads: int = 30
    num_key_value_heads: int = 30
    linear_num_key_heads: int = 30
    linear_num_value_heads: int = 30
    linear_key_head_dim: int = 96
    linear_value_head_dim: int = 192
    linear_conv_kernel_dim: int = 4
    linear_allow_neg_eigval: bool = True
    gdn_chunk: int = 64
    rms_norm_eps: float = 1e-6
    max_position_embeddings: int = 65536
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.bfloat16

    # What `ray_tpu.llm` reads off a model's configuration: which runner
    # builds its programs from which model module, and that some of its
    # layers carry a recurrent state beside the paged cache.
    llm_runner = "ray_tpu.llm.hybrid_runner:HybridRunner"
    llm_model = "ray_tpu.models.olmo_hybrid"
    recurrent_state = True

    def __post_init__(self):
        object.__setattr__(self, "layer_types", tuple(self.layer_types))
        if set(self.layer_types) - {LINEAR, FULL}:
            raise ValueError(f"unknown layer types in {self.layer_types}")
        if self.linear_num_key_heads != self.linear_num_value_heads:
            raise ValueError("fewer key heads than value heads is not implemented")
        if self.linear_num_value_heads % PACK:
            raise ValueError(f"the state is kept {PACK} heads side by side")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("query heads must be a multiple of cached heads")
        if self.hidden_size % self.num_attention_heads:
            raise ValueError("hidden_size must divide into the query heads")

    # The names the engine and the runner know a model's geometry by.
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
        return self.head_dim ** -0.5

    @property
    def cache_classes(self) -> Tuple[CacheClass, ...]:
        """One class: the full-attention layers keep every position."""
        return (CacheClass("full", self.layer_types.count(FULL), None),)

    def cache_class_of(self, kind: str) -> int:
        return 0

    def heads_of(self, kind: str) -> Tuple[int, ...]:
        return (self.num_attention_heads,)

    @property
    def key_dim(self) -> int:
        return self.linear_num_key_heads * self.linear_key_head_dim

    @property
    def value_dim(self) -> int:
        return self.linear_num_value_heads * self.linear_value_head_dim

    @property
    def conv_dim(self) -> int:
        """The channels under the convolution: q, k and v side by side."""
        return 2 * self.key_dim + self.value_dim


def recurrent_shape(cfg: OlmoHybridConfig) -> Dict[str, int]:
    """The gated-delta-rule layers' state as `stats()` publishes it."""
    return {
        "num_layers": cfg.layer_types.count(LINEAR),
        "num_heads": cfg.linear_num_value_heads,
        "key_dim": cfg.linear_key_head_dim,
        "value_dim": cfg.linear_value_head_dim,
        "conv_width": cfg.linear_conv_kernel_dim,
        "conv_dim": cfg.conv_dim,
        "chunk_size": cfg.gdn_chunk,
        "state_itemsize": 4,
        "conv_itemsize": jnp.dtype(cfg.dtype).itemsize,
    }


def recurrent_kinds(cfg: OlmoHybridConfig) -> Dict[str, RecurrentKind]:
    """What a state slot keeps for one gated-delta-rule layer, and the
    layer's two functions: the runner makes the pools and calls them. The
    tail is kept flat ([taps - 1, channels] would pad its three rows to a
    tile of sixteen on the TPU) and the state packed
    (`ray_tpu.ops.gated_delta`)."""
    heads = cfg.linear_num_value_heads
    return {
        LINEAR: RecurrentKind(
            arrays=(
                ("conv", ((cfg.linear_conv_kernel_dim - 1) * cfg.conv_dim,), cfg.dtype),
                ("state", (heads // PACK, cfg.linear_key_head_dim,
                           PACK * cfg.linear_value_head_dim), jnp.float32),
            ),
            prefill=gdn_prefill, decode=gdn_decode,
            scan_scope="llm.mixer.gdn.scan",
        ),
    }


# ---------------- parameters ----------------


def _leaf_shapes(cfg: OlmoHybridConfig) -> Dict[str, Any]:
    d, heads = cfg.hidden_size, cfg.linear_num_value_heads
    kv = cfg.num_key_value_heads * cfg.head_dim
    layers = []
    for kind in cfg.layer_types:
        if kind == LINEAR:
            mixer = {
                "q": (d, cfg.key_dim), "k": (d, cfg.key_dim), "v": (d, cfg.value_dim),
                "b": (d, heads), "a": (d, heads), "g": (d, cfg.value_dim),
                "conv_w": (cfg.linear_conv_kernel_dim, cfg.conv_dim),
                "A_log": (heads,), "dt_bias": (heads,),
                "norm": (cfg.linear_value_head_dim,),
                "o": (cfg.value_dim, d),
            }
        else:
            mixer = {
                "q": (d, d), "k": (d, kv), "v": (d, kv), "o": (d, d),
                "norm_q": (d,), "norm_k": (kv,),
            }
        layers.append({
            "norm1": (d,), "norm2": (d,), "mixer": mixer,
            "mlp_in": (d, 2 * cfg.intermediate_size),
            "mlp_out": (cfg.intermediate_size, d),
        })
    return {
        "wte": (cfg.vocab_size, d), "norm_f": (d,), "lm_head": (d, cfg.vocab_size),
        "layers": layers,
    }


def init_params(cfg: OlmoHybridConfig, seed: int) -> Dict[str, Any]:
    """Seeded weights, made leaf by leaf in `param_dtype` (a float32 tree
    of the serving size does not fit a chip): normal(0.02) matrices, ones
    for the norms, and for the recurrence what normal(0.02) would make
    forget within a token or never: `A` uniform in (0, 16], `dt`
    log-uniform in [0.001, 0.1] behind the softplus, the convolution
    uniform in +-1/sqrt(taps) (`granite_hybrid.init_params`)."""
    leaves, tree = jax.tree_util.tree_flatten_with_path(
        _leaf_shapes(cfg), is_leaf=lambda v: isinstance(v, tuple)
    )
    base = jax.random.PRNGKey(seed)
    bound = 1.0 / math.sqrt(cfg.linear_conv_kernel_dim)
    made = []
    for index, (path, shape) in enumerate(leaves):
        name = path[-1].key
        key = jax.random.fold_in(base, index)
        if name.startswith("norm"):
            leaf = jnp.ones(shape, jnp.float32)
        elif name == "A_log":
            leaf = jnp.log(16.0 * (1.0 - jax.random.uniform(key, shape)))
        elif name == "dt_bias":
            lo, hi = math.log(0.001), math.log(0.1)
            leaf = parts.inverse_softplus(
                jnp.exp(jax.random.uniform(key, shape, minval=lo, maxval=hi))
            )
        elif name == "conv_w":
            leaf = jax.random.uniform(key, shape, minval=-bound, maxval=bound)
        else:
            leaf = parts.normal(key, shape, cfg.param_dtype, 0.02)
        made.append(leaf.astype(cfg.param_dtype))
    return jax.tree_util.tree_unflatten(tree, made)


# ---------------- the parts of a layer ----------------


def _gdn_project(cfg, p, u):
    """The five projections of u [..., D]: q, k and v side by side as the
    convolution takes them (`dtype`), beta and the log-decay g [..., H]
    (float32) and the output gate [..., H * V] (float32)."""
    with jax.named_scope("llm.mixer.gdn.proj"):
        qkv = jnp.concatenate(
            [parts.matmul(u, p[name], cfg.dtype) for name in ("q", "k", "v")], axis=-1
        ).astype(cfg.dtype)
        beta = jax.nn.sigmoid(parts.matmul(u, p["b"], cfg.dtype))
        if cfg.linear_allow_neg_eigval:
            beta = 2.0 * beta
        dt = jax.nn.softplus(
            parts.matmul(u, p["a"], cfg.dtype) + p["dt_bias"].astype(jnp.float32)
        )
        g = -jnp.exp(p["A_log"].astype(jnp.float32)) * dt
        gate = parts.matmul(u, p["g"], cfg.dtype)
    return qkv, beta, g, gate


def _gdn_heads(cfg, conv):
    """silu of the convolution's output [..., conv_dim] float32, cut into
    q and k [..., H, K] (L2-normalised a head, q scaled) and v [..., H, V],
    all in `dtype`."""
    heads = cfg.linear_num_value_heads
    q, k, v = jnp.split(
        jax.nn.silu(conv), [cfg.key_dim, 2 * cfg.key_dim], axis=-1
    )
    q, k, v = (x.reshape(x.shape[:-1] + (heads, -1)) for x in (q, k, v))
    q = parts.l2_norm(q) * cfg.linear_key_head_dim ** -0.5
    return q.astype(cfg.dtype), parts.l2_norm(k).astype(cfg.dtype), v.astype(cfg.dtype)


def _gdn_finish(cfg, p, o, gate):
    """The gated norm a head: o [..., H, V] float32 and the gate
    [..., H * V] -> the output projection's input [..., H * V]."""
    y = parts.rms_norm(o, p["norm"], cfg.rms_norm_eps)
    return (y * jax.nn.silu(gate.reshape(y.shape))).reshape(gate.shape)


def gdn_prefill(cfg, p, u, conv_tail, state, length):
    """A chunk of one sequence. u [T, D]; conv_tail [(taps - 1) * conv_dim]
    holds q, k, v of the positions before the chunk (zeros at a sequence's
    start) and state [H / 2, K, 2 V] the packed state there. Returns the
    mixer's output [T, D] and tail and state after token `length` - 1."""
    qkv, beta, g, gate = _gdn_project(cfg, p, u)
    taps = cfg.linear_conv_kernel_dim
    with jax.named_scope("llm.mixer.gdn.proj"):
        padded = jnp.concatenate(
            [conv_tail.reshape(taps - 1, cfg.conv_dim).astype(cfg.dtype), qkv], axis=0
        )
        w = p["conv_w"].astype(jnp.float32)
        conv = sum(
            padded[i : i + u.shape[0]].astype(jnp.float32) * w[i] for i in range(taps)
        )
        new_tail = jax.lax.dynamic_slice_in_dim(padded, length, taps - 1, axis=0)
        q, k, v = _gdn_heads(cfg, conv)
    with jax.named_scope("llm.mixer.gdn.scan"):
        o, new_state = gated_delta_chunked(
            q, k, v, g, beta, state, length, cfg.gdn_chunk, cfg.dtype
        )
        y = _gdn_finish(cfg, p, o, gate)
    with jax.named_scope("llm.mixer.gdn.proj"):
        out = parts.matmul(y, p["o"], cfg.dtype)
    return out, new_tail.reshape(-1), new_state


def gdn_decode(cfg, p, u, conv_tail, state, live):
    """One token for each of a batch of sequences. u [B, D], conv_tail
    [B, (taps - 1) * conv_dim], state [B, H / 2, K, 2 V]; a lane that is not
    `live` [B] keeps its tail and state."""
    qkv, beta, g, gate = _gdn_project(cfg, p, u)
    taps = cfg.linear_conv_kernel_dim
    with jax.named_scope("llm.mixer.gdn.proj"):
        window = jnp.concatenate(
            [
                conv_tail.reshape(-1, taps - 1, cfg.conv_dim).astype(cfg.dtype),
                qkv[:, None],
            ],
            axis=1,
        )
        conv = jnp.sum(
            window.astype(jnp.float32) * p["conv_w"].astype(jnp.float32), axis=1
        )
        q, k, v = _gdn_heads(cfg, conv)
    with jax.named_scope("llm.mixer.gdn.update"):
        o, new_state = gated_delta_update(q, k, v, g, beta, state, live)
        y = _gdn_finish(cfg, p, o, gate)
        new_tail = parts.where_live(
            live, window[:, 1:].reshape(u.shape[0], -1), conv_tail
        )
    with jax.named_scope("llm.mixer.gdn.proj"):
        out = parts.matmul(y, p["o"], cfg.dtype)
    return out, new_tail, new_state


def attention_qkv(cfg, kind, p, u, positions=None):
    """u [..., D] -> q [..., Hq, d], k and v [..., Hkv, d] in `dtype`, q and
    k under QK-norm. This model's attention has no positions."""
    q, k = parts.qk_norm(
        parts.matmul(u, p["q"], cfg.dtype), parts.matmul(u, p["k"], cfg.dtype),
        p["norm_q"], p["norm_k"], cfg.rms_norm_eps,
    )

    def heads(x):
        return x.astype(cfg.dtype).reshape(u.shape[:-1] + (-1, cfg.head_dim))

    return heads(q), heads(k), heads(parts.matmul(u, p["v"], cfg.dtype))


def attention_out(cfg, kind, p, u, mixed):
    """The output projection of mixed [..., Hq, d] -> [..., D] float32."""
    return parts.matmul(mixed.reshape(u.shape[:-1] + (-1,)), p["o"], cfg.dtype)


def causal_attention(cfg, q, k, v):
    """Dense causal grouped-query attention of one sequence: q [T, Hq, d],
    k and v [T, Hkv, d]. The full-sequence forward's, with no cache."""
    group = q.shape[1] // k.shape[1]
    k, v = (jnp.repeat(t, group, axis=1) for t in (k, v))
    scores = jnp.einsum(
        "qhd,khd->hqk", q, k, preferred_element_type=jnp.float32
    ) * cfg.attention_scale
    t_len = q.shape[0]
    scores = jnp.where(jnp.tril(jnp.ones((t_len, t_len), bool)), scores, -jnp.inf)
    weights = jax.nn.softmax(scores, axis=-1).astype(cfg.dtype)
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


def _mlp(cfg, p, x):
    with jax.named_scope("llm.mlp"):
        return parts.gated_mlp(x, p["mlp_in"], p["mlp_out"], cfg.dtype)


def run_layers(
    cfg: OlmoHybridConfig, params, h, mixers: Dict[str, Callable], *,
    grouped: bool = False, valid=None,
):
    """The layer stack over the residual rows h [T, D]. `mixers[kind](i,
    p, u)` is the mixer of the i-th layer of its kind: it owns where the
    layer's memory lives. Returns h and None: a dense model routes
    nothing and has no counts (`grouped` and `valid` are the routed
    models')."""
    seen = dict.fromkeys(mixers, 0)
    for kind, p in zip(cfg.layer_types, params["layers"]):
        h = parts.output_norm_block(
            h, functools.partial(mixers[kind], seen[kind], p["mixer"]),
            functools.partial(_mlp, cfg, p), p["norm1"], p["norm2"],
            cfg.rms_norm_eps, cfg.dtype,
        )
        seen[kind] += 1
    return h, None


def forward(cfg: OlmoHybridConfig, params, tokens):
    """Logits [T, vocab] of one whole sequence `tokens` [T] from an empty
    state and no cache: the chunked delta rule as the prefill programs run
    it, dense causal attention."""
    t_len = tokens.shape[0]
    arrays = recurrent_kinds(cfg)[LINEAR].arrays

    def linear(_, p, u):
        empty = [jnp.zeros(shape, dtype) for _, shape, dtype in arrays]
        return gdn_prefill(cfg, p, u, *empty, t_len)[0]

    def attend(_, p, u):
        with jax.named_scope("llm.mixer.attention.proj"):
            q, k, v = attention_qkv(cfg, FULL, p, u)
        with jax.named_scope("llm.mixer.attention.full"):
            mixed = causal_attention(cfg, q, k, v).astype(cfg.dtype)
        with jax.named_scope("llm.mixer.attention.proj"):
            return attention_out(cfg, FULL, p, u, mixed)

    h, _ = run_layers(
        cfg, params, embed(cfg, params, tokens), {LINEAR: linear, FULL: attend}
    )
    return head(cfg, params, h)
