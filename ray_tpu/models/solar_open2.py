"""Upstage Solar-Open2 (`solar_open2`): three Kimi-Delta-Attention layers to
every grouped-query attention layer with no positions and an elementwise
output gate, routed experts behind a sigmoid router with a selection bias
beside a shared expert in EVERY layer, pre-norm blocks, an untied head.

Pure functions over a plain tree of parameters (no flax), as
`ray_tpu.models.olmo_hybrid` and `ray_tpu.models.laguna`: the serving
programs in `ray_tpu.llm.hybrid_runner` and the full-sequence `forward`
below run the same layer code and differ only in where a mixer's memory
comes from (a state slot and the paged cache, or nothing). With `h` the
residual stream and RMS norms (a learned weight, eps 1e-5) throughout, no
bias but the output gate's:

    h = wte[ids]
    a layer:  h = h + mixer(norm1(h));  h = h + moe(norm2(h))
    logits = norm_f(h) @ lm_head

Layer i is a "gqa" layer if i is in `gqa_layers` (0, 4, 8, ...), else a
"kda" layer. A "kda" layer is the delta rule with a decay a key channel
(Kimi Delta Attention, arXiv:2510.26692; the `KimiDeltaAttention` layer of
flash-linear-attention) over `kda_num_heads` heads of key and value size
`kda_head_dim`, u the normed input:

    q, k, v = silu(conv(u Wq)), silu(conv(u Wk)), silu(conv(u Wv))
    q <- q / |q| * head_dim^-0.5;  k <- k / |k|        (a head)
    beta = 2 sigmoid(u Wb)     (the 2: `kda_allow_neg_eigval`)
    g = -exp(A_log[h]) softplus((u Fa) Fb + dt_bias)   (float32, [.., H, K])
    the recurrence of `ray_tpu.ops.kda` on a head's state [K, V]
    y = (rms_norm_V(o) * sigmoid((u Ga) Gb + gb)) Wo

where conv is depthwise and causal over `short_conv_kernel_size` positions
a channel and the two low-rank pairs have rank `kda_low_rank`. A "gqa"
layer has `num_attention_heads` query heads over `num_key_value_heads`
cached ones, scores scaled by head_dim^-0.5, no positions of any kind
(`use_rope` false; the recurrent layers carry order) and no QK-norm, its
output times `sigmoid(u Wg)` element by element (`use_gqa_gate`) before the
output projection. The expert MLP is `ray_tpu.models.parts.experts`: the
router scores all `n_routed_experts` by a sigmoid, a token takes the
`num_experts_per_tok` largest of score + `router_bias`, their scores
divided by their sum and multiplied by `routed_scaling_factor`, this chip
computes the part of the sum that the experts in `experts_held` give, and a
shared expert (`n_shared_experts` x `moe_intermediate_size` wide) is added
with weight 1. `models/solar_open2_reference.py` lists what of this the
published config.json does not spell out and is ASSUMED.

Parameters are held in `param_dtype` (bfloat16), matrix products take
`dtype` operands and accumulate in float32, the recurrent state, the decay
and the chunk's solve are float32, the convolution's tail is `dtype`,
gates, router and softmax are float32.

Not imported by `ray_tpu` or `ray_tpu.models`: import this module by name.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ray_tpu.llm.cache import CacheClass, RecurrentKind
from ray_tpu.models import parts
from ray_tpu.models.parts import num_params  # noqa: F401  (the runner's name for it)
from ray_tpu.ops.kda import kda_chunked, kda_update

KDA, GQA = "kda", "gqa"
# The parts of a layer a trace's time is split by
# (`ray_tpu.util.device_report.scopes_of`), and the scope of an attention
# layer's projections (its gate among them) and of attention alone
# (Laguna's names for them).
SCOPES = (
    "llm.mixer.kda.proj", "llm.mixer.kda.scan", "llm.mixer.kda.update",
    "llm.mixer.attention.proj", "llm.mixer.attention.full", "llm.moe.router",
    "llm.moe.routed", "llm.moe.shared", "llm.head",
)
ATTENTION_SCOPES = {GQA: ("llm.mixer.attention.proj", "llm.mixer.attention.full")}
# `stats()["attention_shape"]` by cache class name, as a model with several
# classes has it: what reads `llm.mixer.attention.full` reads the `full` class.
ATTENTION_SHAPE_BY_CLASS = True


@dataclasses.dataclass(frozen=True)
class SolarOpen2Config:
    """Keys as the published config.json names them (`linear_attn_config`'s
    `num_heads`, `head_dim` and `short_conv_kernel_size` as `kda_num_heads`,
    `kda_head_dim` and `short_conv_kernel_size`), plus `kda_low_rank` (the
    rank of the decay's and the output gate's pairs), `kda_chunk` (the tokens
    of a chunk the delta rule solves at once), `experts_held` (which of a
    layer's routed experts this chip holds) and the types."""

    vocab_size: int = 196608
    hidden_size: int = 4096
    num_hidden_layers: int = 48
    gqa_layers: Tuple[int, ...] = tuple(range(0, 48, 4))
    num_attention_heads: int = 64
    num_key_value_heads: int = 8
    head_dim: int = 128
    kda_num_heads: int = 64
    kda_head_dim: int = 128
    short_conv_kernel_size: int = 4
    kda_allow_neg_eigval: bool = True
    kda_low_rank: int = 128
    kda_chunk: int = 64
    n_routed_experts: int = 320
    num_experts_per_tok: int = 8
    moe_intermediate_size: int = 1280
    n_shared_experts: int = 1
    routed_scaling_factor: float = 1.0
    experts_held: Tuple[int, ...] = tuple(range(320))
    rms_norm_eps: float = 1e-5
    max_position_embeddings: int = 1048576
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.bfloat16

    # What `ray_tpu.llm` reads off a model's configuration: which runner
    # builds its programs from which model module, that some of its layers
    # carry a recurrent state beside the paged cache, and the router's rule
    # (`ray_tpu.ops.grouped_experts.route`): a sigmoid of every logit, the
    # choice by score + selection bias, the chosen scores renormalised
    # (`norm_topk_prob`) and scaled.
    llm_runner = "ray_tpu.llm.hybrid_runner:HybridRunner"
    llm_model = "ray_tpu.models.solar_open2"
    recurrent_state = True
    router_score = "sigmoid"

    def __post_init__(self):
        for name in ("gqa_layers", "experts_held"):
            object.__setattr__(self, name, tuple(getattr(self, name)))
        if not all(0 <= i < self.num_hidden_layers for i in self.gqa_layers):
            raise ValueError(f"gqa_layers {self.gqa_layers} of {self.num_hidden_layers} layers")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("query heads must be a multiple of cached heads")
        if self.n_shared_experts != 1:
            raise ValueError("one shared expert is implemented")
        parts.check_experts_held(self.experts_held, self.n_routed_experts)

    # The names the engine, the runner and the shared parts know a model's
    # geometry by.
    @property
    def layer_types(self) -> Tuple[str, ...]:
        return tuple(
            GQA if i in self.gqa_layers else KDA for i in range(self.num_hidden_layers)
        )

    @property
    def num_layers(self) -> int:
        return self.num_hidden_layers

    @property
    def num_heads(self) -> int:
        return self.num_attention_heads

    @property
    def max_seq_len(self) -> int:
        return self.max_position_embeddings

    @property
    def attention_scale(self) -> float:
        return self.head_dim ** -0.5

    @property
    def cache_classes(self) -> Tuple[CacheClass, ...]:
        """One class: the attention layers keep every position."""
        return (CacheClass("full", len(self.gqa_layers), None),)

    def cache_class_of(self, kind: str) -> int:
        return 0

    def heads_of(self, kind: str) -> Tuple[int, ...]:
        return (self.num_attention_heads,)

    @property
    def kda_dim(self) -> int:
        """A KDA layer's q, k, v and gate width: heads x head size."""
        return self.kda_num_heads * self.kda_head_dim

    @property
    def conv_dim(self) -> int:
        """The channels under the convolution: q, k and v side by side."""
        return 3 * self.kda_dim

    def local_of(self) -> jax.Array:
        return parts.local_of(self.n_routed_experts, self.experts_held)


def recurrent_shape(cfg: SolarOpen2Config) -> Dict[str, int]:
    """The KDA layers' state as `stats()` publishes it; `decay_width` is
    the decay's width a head and token (the key size: a decay a channel)."""
    return {
        "num_layers": cfg.layer_types.count(KDA),
        "num_heads": cfg.kda_num_heads,
        "key_dim": cfg.kda_head_dim,
        "value_dim": cfg.kda_head_dim,
        "decay_width": cfg.kda_head_dim,
        "conv_width": cfg.short_conv_kernel_size,
        "conv_dim": cfg.conv_dim,
        "chunk_size": cfg.kda_chunk,
        "state_itemsize": 4,
        "conv_itemsize": jnp.dtype(cfg.dtype).itemsize,
    }


def expert_shape(cfg: SolarOpen2Config) -> Dict[str, int]:
    """The routed experts as `stats()` publishes them: every layer has them."""
    return {
        "num_layers": cfg.num_hidden_layers,
        "num_experts": cfg.n_routed_experts,
        "experts_held": len(cfg.experts_held),
        "experts_per_token": cfg.num_experts_per_tok,
        "hidden_size": cfg.hidden_size,
        "expert_width": cfg.moe_intermediate_size,
    }


def recurrent_kinds(cfg: SolarOpen2Config) -> Dict[str, RecurrentKind]:
    """What a state slot keeps for one KDA layer, and the layer's two
    functions: the runner makes the pools and calls them. The tail is kept
    flat ([taps - 1, channels] would pad its three rows to a tile of sixteen
    on the TPU); a head's [K, V] state is whole tiles as it is."""
    return {
        KDA: RecurrentKind(
            arrays=(
                ("conv", ((cfg.short_conv_kernel_size - 1) * cfg.conv_dim,), cfg.dtype),
                ("state", (cfg.kda_num_heads, cfg.kda_head_dim, cfg.kda_head_dim),
                 jnp.float32),
            ),
            prefill=kda_prefill, decode=kda_decode,
            scan_scope="llm.mixer.kda.scan",
        ),
    }


# ---------------- parameters ----------------


def _leaf_shapes(cfg: SolarOpen2Config) -> Dict[str, Any]:
    d, heads, width, rank = cfg.hidden_size, cfg.kda_num_heads, cfg.kda_dim, cfg.kda_low_rank
    attn = cfg.num_attention_heads * cfg.head_dim
    kv = cfg.num_key_value_heads * cfg.head_dim
    held, f = len(cfg.experts_held), cfg.moe_intermediate_size
    layers = []
    for kind in cfg.layer_types:
        if kind == KDA:
            mixer = {
                "q": (d, width), "k": (d, width), "v": (d, width), "b": (d, heads),
                "fa": (d, rank), "fb": (rank, width), "dt_bias": (width,),
                "A_log": (heads,),
                "ga": (d, rank), "gb": (rank, width), "g_bias": (width,),
                "conv_w": (cfg.short_conv_kernel_size, cfg.conv_dim),
                "norm": (cfg.kda_head_dim,), "o": (width, d),
            }
        else:
            mixer = {
                "q": (d, attn), "k": (d, kv), "v": (d, kv), "g": (d, attn),
                "o": (attn, d),
            }
        layers.append({
            "norm1": (d,), "norm2": (d,), "mixer": mixer,
            "router": (d, cfg.n_routed_experts),
            "router_bias": (cfg.n_routed_experts,),
            "experts_in": (held, d, 2 * f), "experts_out": (held, f, d),
            "shared_in": (d, 2 * f * cfg.n_shared_experts),
            "shared_out": (f * cfg.n_shared_experts, d),
        })
    return {
        "wte": (cfg.vocab_size, d), "norm_f": (d,), "lm_head": (d, cfg.vocab_size),
        "layers": layers,
    }


def init_params(cfg: SolarOpen2Config, seed: int) -> Dict[str, Any]:
    """Seeded weights, made leaf by leaf in `param_dtype` (a float32 tree
    of the serving size does not fit a chip): normal(0.02) matrices and
    biases, ones for the norms, and what normal(0.02) would make invisible
    to a comparison: `A` uniform in (0, 16] a head and `dt` log-uniform in
    [0.001, 0.1] a CHANNEL behind the softplus, so a channel's decay a token
    lies between exp(-1.6) and 1 and differs across the channels of a head;
    the convolution uniform in +-1/sqrt(taps); the selection bias uniform in
    +-0.1, so that it changes choices (the sigmoid's scores are 0.2 to 0.8)."""
    bound = 1.0 / math.sqrt(cfg.short_conv_kernel_size)
    lo, hi = math.log(0.001), math.log(0.1)
    uniform = jax.random.uniform
    return parts.seeded_tree(_leaf_shapes(cfg), seed, cfg.param_dtype, draws={
        "A_log": lambda key, shape: jnp.log(16.0 * (1.0 - uniform(key, shape))),
        "dt_bias": lambda key, shape: parts.inverse_softplus(
            jnp.exp(uniform(key, shape, minval=lo, maxval=hi))
        ),
        "conv_w": lambda key, shape: uniform(key, shape, minval=-bound, maxval=bound),
        "router_bias": lambda key, shape: uniform(key, shape, minval=-0.1, maxval=0.1),
    })


# ---------------- the parts of a layer ----------------


def _kda_project(cfg, p, u):
    """The projections of u [..., D]: q, k and v side by side as the
    convolution takes them (`dtype`), beta [..., H] and the log-decay g
    [..., H, K] (float32) and the output gate [..., H * V] (float32, under
    its sigmoid)."""
    heads = cfg.kda_num_heads
    with jax.named_scope("llm.mixer.kda.proj"):
        qkv = jnp.concatenate(
            [parts.matmul(u, p[name], cfg.dtype) for name in ("q", "k", "v")], axis=-1
        ).astype(cfg.dtype)
        beta = jax.nn.sigmoid(parts.matmul(u, p["b"], cfg.dtype))
        if cfg.kda_allow_neg_eigval:
            beta = 2.0 * beta
        dt = jax.nn.softplus(
            parts.matmul(parts.matmul(u, p["fa"], cfg.dtype), p["fb"], cfg.dtype)
            + p["dt_bias"].astype(jnp.float32)
        ).reshape(u.shape[:-1] + (heads, -1))
        g = -jnp.exp(p["A_log"].astype(jnp.float32))[:, None] * dt
        gate = jax.nn.sigmoid(
            parts.matmul(parts.matmul(u, p["ga"], cfg.dtype), p["gb"], cfg.dtype)
            + p["g_bias"].astype(jnp.float32)
        )
    return qkv, beta, g, gate


def _kda_heads(cfg, conv):
    """silu of the convolution's output [..., conv_dim] float32, cut into
    q and k [..., H, K] (L2-normalised a head, q scaled) and v [..., H, V],
    all in `dtype`."""
    q, k, v = (
        x.reshape(x.shape[:-1] + (cfg.kda_num_heads, -1))
        for x in jnp.split(jax.nn.silu(conv), 3, axis=-1)
    )
    q = parts.l2_norm(q) * cfg.kda_head_dim ** -0.5
    return q.astype(cfg.dtype), parts.l2_norm(k).astype(cfg.dtype), v.astype(cfg.dtype)


def _kda_finish(cfg, p, o, gate):
    """The gated norm a head: o [..., H, V] float32 and the gate
    [..., H * V] -> the output projection's input [..., H * V]."""
    y = parts.rms_norm(o, p["norm"], cfg.rms_norm_eps)
    return (y * gate.reshape(y.shape)).reshape(gate.shape)


def kda_prefill(cfg, p, u, conv_tail, state, length):
    """A chunk of one sequence. u [T, D]; conv_tail [(taps - 1) * conv_dim]
    holds q, k, v of the positions before the chunk (zeros at a sequence's
    start) and state [H, K, V] the state there. Returns the mixer's output
    [T, D] and tail and state after token `length` - 1."""
    qkv, beta, g, gate = _kda_project(cfg, p, u)
    taps = cfg.short_conv_kernel_size
    with jax.named_scope("llm.mixer.kda.proj"):
        padded = jnp.concatenate(
            [conv_tail.reshape(taps - 1, cfg.conv_dim).astype(cfg.dtype), qkv], axis=0
        )
        w = p["conv_w"].astype(jnp.float32)
        conv = sum(
            padded[i : i + u.shape[0]].astype(jnp.float32) * w[i] for i in range(taps)
        )
        new_tail = jax.lax.dynamic_slice_in_dim(padded, length, taps - 1, axis=0)
        q, k, v = _kda_heads(cfg, conv)
    with jax.named_scope("llm.mixer.kda.scan"):
        o, new_state = kda_chunked(
            q, k, v, g, beta, state, length, cfg.kda_chunk, cfg.dtype
        )
        y = _kda_finish(cfg, p, o, gate)
    with jax.named_scope("llm.mixer.kda.proj"):
        out = parts.matmul(y, p["o"], cfg.dtype)
    return out, new_tail.reshape(-1), new_state


def kda_decode(cfg, p, u, conv_tail, state, live):
    """One token for each of a batch of sequences. u [B, D], conv_tail
    [B, (taps - 1) * conv_dim], state [B, H, K, V]; a lane that is not
    `live` [B] keeps its tail and state."""
    qkv, beta, g, gate = _kda_project(cfg, p, u)
    taps = cfg.short_conv_kernel_size
    with jax.named_scope("llm.mixer.kda.proj"):
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
        q, k, v = _kda_heads(cfg, conv)
    with jax.named_scope("llm.mixer.kda.update"):
        o, new_state = kda_update(q, k, v, g, beta, state, live)
        y = _kda_finish(cfg, p, o, gate)
        new_tail = parts.where_live(
            live, window[:, 1:].reshape(u.shape[0], -1), conv_tail
        )
    with jax.named_scope("llm.mixer.kda.proj"):
        out = parts.matmul(y, p["o"], cfg.dtype)
    return out, new_tail, new_state


def attention_qkv(cfg, kind, p, u, positions=None):
    """u [..., D] -> q [..., Hq, d], k and v [..., Hkv, d] in `dtype`. This
    model's attention has no positions and no QK-norm."""
    def heads(w):
        return parts.matmul(u, w, cfg.dtype).astype(cfg.dtype).reshape(
            u.shape[:-1] + (-1, cfg.head_dim)
        )

    return heads(p["q"]), heads(p["k"]), heads(p["v"])


def attention_out(cfg, kind, p, u, mixed):
    """The elementwise gate and the output projection: mixed [..., Hq, d]
    times sigmoid(u Wg) [..., Hq * d], element by element -> [..., D]
    float32."""
    gate = jax.nn.sigmoid(parts.matmul(u, p["g"], cfg.dtype))
    flat = mixed.reshape(u.shape[:-1] + (-1,))
    return parts.matmul((flat.astype(jnp.float32) * gate).astype(cfg.dtype), p["o"], cfg.dtype)


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


def run_layers(
    cfg: SolarOpen2Config, params, h, mixers: Dict[str, Callable], *,
    grouped: bool, valid=None,
):
    """The layer stack over the residual rows h [T, D]. `mixers[kind](i,
    p, u)` is the mixer of the i-th layer of its kind: it owns where the
    layer's memory lives. Returns h and the routing's counts summed over
    the layers."""
    seen = dict.fromkeys(mixers, 0)
    totals: Optional[Dict[str, jax.Array]] = None
    for kind, p in zip(cfg.layer_types, params["layers"]):
        u = parts.rms_norm(h, p["norm1"], cfg.rms_norm_eps)
        mixed = mixers[kind](seen[kind], p["mixer"], u)
        seen[kind] += 1
        h = (h.astype(jnp.float32) + mixed).astype(cfg.dtype)
        x = parts.rms_norm(h, p["norm2"], cfg.rms_norm_eps)
        out, counts = parts.experts(cfg, p, x, grouped=grouped, valid=valid)
        totals = parts.add_counts(totals, counts)
        h = (h.astype(jnp.float32) + out).astype(cfg.dtype)
    return h, totals


def forward(cfg: SolarOpen2Config, params, tokens, *, grouped: bool = True):
    """Logits [T, vocab] of one whole sequence `tokens` [T] from an empty
    state and no cache: the chunked delta rule and the grouped experts as
    the prefill programs run them, dense causal attention."""
    t_len = tokens.shape[0]
    arrays = recurrent_kinds(cfg)[KDA].arrays

    def linear(_, p, u):
        empty = [jnp.zeros(shape, dtype) for _, shape, dtype in arrays]
        return kda_prefill(cfg, p, u, *empty, t_len)[0]

    def attend(_, p, u):
        with jax.named_scope("llm.mixer.attention.proj"):
            q, k, v = attention_qkv(cfg, GQA, p, u)
        with jax.named_scope("llm.mixer.attention.full"):
            mixed = causal_attention(cfg, q, k, v).astype(cfg.dtype)
        with jax.named_scope("llm.mixer.attention.proj"):
            return attention_out(cfg, GQA, p, u, mixed)

    h, _ = run_layers(
        cfg, params, embed(cfg, params, tokens), {KDA: linear, GQA: attend},
        grouped=grouped,
    )
    return head(cfg, params, h)
