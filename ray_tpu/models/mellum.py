"""JetBrains Mellum 2 (`mellum`), on the training path: three
sliding-window layers to one full-attention layer, rotary positions of two
forms over the whole head, 32 query heads over 4 key heads, every layer's
MLP a set of routed experts with no shared expert beside them, an untied
head.

Pure functions over a plain tree of parameters, as `ray_tpu.models.laguna`,
and differentiable: the batched full-sequence `forward` goes through the
flash kernels (`ray_tpu.ops.flash_attention`, which know the window and the
group; interpreted on the CPU) and the dropless grouped experts
(`ray_tpu.ops.grouped_experts.routed_grouped`, which carries its own
backward). With `h` the residual stream and RMS norms (a learned weight,
eps 1e-6), no bias anywhere:

    h = wte[ids]
    a layer:  u = norm1(h);  q = u Wq [32 x 128];  k, v = u Wk, u Wv [4 x 128]
              q, k rotated over all 128 dimensions, pairs (i, i + 64):
                sliding_attention: default frequencies, base 500,000
                full_attention:    YaRN (base 500,000, factor 16 over 8,192,
                                   beta 32 / 1), cos and sin x attention_factor
              query head j reads key head j // 8;  scores x 128^-0.5
              sliding: position i sees i - 1,024 < j <= i;  full: every j <= i
              h = h + attention(q, k, v) Wo
              x = norm2(h);  p = softmax(x Wr) over all 64 experts (float32)
              a token takes its 8 largest, divided by their sum
              h = h + sum over the chosen e of p_e W2_e (silu(W1g_e x) * W1u_e x)
    logits = norm_f(h) lm_head;  loss = mean cross entropy of the next token

A chip of an expert-parallel host holds `experts_held` of a layer's experts
and computes their part of the sum (`ray_tpu.models.parts.experts`), and
the rows `vocab_rows` of the embedding and the head: ids, logits and the
loss are then over that slice, an id being its row in the slice.

Parameters are held in `param_dtype` (float32 masters for training), matrix
products take `dtype` operands (bfloat16) and accumulate in float32,
rotation, router and softmax are float32. `remat` recomputes a layer in the
backward pass (`jax.checkpoint` a layer), which is what lets an 8,192-token
step fit beside the optimizer's state; it changes no result. A recomputed
layer keeps its input and the two arrays its flash kernel's own backward
reads, the kernel's output and the rows' log-sum-exp
(`flash_attention.RESIDUAL_NAMES`), so the forward kernel runs once a step
and not twice: 2 x tokens x query heads x head_dim bytes in bfloat16 and
4 x tokens x query heads, 136 MB a layer at two sequences of 8,192.

Not imported by `ray_tpu` or `ray_tpu.models`: import this module by name.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from ray_tpu.models import parts
from ray_tpu.models.parts import num_params  # noqa: F401  (as the other models name it)
from ray_tpu.ops.flash_attention import RESIDUAL_NAMES, flash_attention

FULL, SLIDING = "full_attention", "sliding_attention"
MELLUM_PERIOD = (SLIDING, SLIDING, SLIDING, FULL)
# The rotary parameters of the two kinds of layer as Mellum2-12B-A2.5B's
# published config.json has them.
MELLUM2_ROPE = {
    FULL: {
        "rope_type": "yarn", "rope_theta": 500000, "factor": 16,
        "original_max_position_embeddings": 8192, "beta_fast": 32,
        "beta_slow": 1, "attention_factor": 1.2772588722239782,
    },
    SLIDING: {"rope_type": "default", "rope_theta": 500000},
}
# The parts of a layer a trace's time is split by (`ray_tpu.util.device_report`),
# under the names the served models use.
SCOPES = (
    "llm.mixer.attention.proj", "llm.mixer.attention.full",
    "llm.mixer.attention.window", "llm.moe.router", "llm.moe.routed", "llm.head",
)
ATTENTION_SCOPE = {FULL: "llm.mixer.attention.full", SLIDING: "llm.mixer.attention.window"}
# Seeded embedding rows have unit variance, so that what a router sees of an
# untrained model is the token and not the running mean of its context
# (attention's output, nearly the same for every position, is 0.04 wide at
# normal(0.02) weights). With the embedding at 0.02 too, adamw at 3e-4 walks
# every router along that common direction and the routing collapses inside
# a hundred steps: the share of a token's choices that a quarter of the
# experts holds fell from 24% to under 5%, the fullest expert at 9.9 times
# the mean (chip run, PR 39, call 1). At 1.0 the load is even from the first
# step (fullest over mean 1.2 against 2.6, call 2) and stays so.
EMBEDDING_STD = 1.0
# And the seeded head is narrow. What a body learns fastest from targets
# drawn at random is to make every position's hidden state the same, which
# removes the logits' variance (0.92 at normal(0.02), 0.46 of loss): adamw
# at 3e-4 gets there in fifteen to twenty steps, and a router that sees one
# hidden state sends every token to the same experts (fullest over mean 1.3
# -> 5, call 6). That pull is as strong as the logits are wide; at 0.02 / 16
# the loss starts at the targets' entropy and what is left is the noise of
# the targets, under which the load stays even for a window's steps (call 8).
HEAD_STD = 0.02 / 16
# The routing's counts `loss_and_counts` returns, summed over layers;
# `load` is [experts held], the rest scalars (`walked`: the sorted rows the
# grouped experts visited for the `held` ones).
COUNTS = ("held", "absent", "touched", "load_max", "walked", "load")


@dataclasses.dataclass(frozen=True)
class MellumConfig:
    """Keys as the published config.json names them, plus `experts_held`
    (which of a layer's routed experts this chip holds), `vocab_rows` (the
    first and one past the last row of the vocabulary it holds), `remat`
    (a layer's forward is taken again in the backward pass, all but its
    flash kernel, whose output and log-sum-exp are kept: the module's note),
    `hold_router` (`train_step` leaves the routers' weights as they are)
    and the types. `intermediate_size` is published and unused: no layer
    is dense."""

    vocab_size: int = 98304
    hidden_size: int = 2304
    intermediate_size: int = 7168
    layer_types: Tuple[str, ...] = MELLUM_PERIOD * 7
    num_attention_heads: int = 32
    num_key_value_heads: int = 4
    head_dim: int = 128
    rope_parameters: Any = parts.frozen(MELLUM2_ROPE)
    sliding_window: int = 1024
    num_experts: int = 64
    num_experts_per_tok: int = 8
    moe_intermediate_size: int = 896
    norm_topk_prob: bool = True
    experts_held: Tuple[int, ...] = tuple(range(64))
    vocab_rows: Tuple[int, int] = (0, 98304)
    rms_norm_eps: float = 1e-6
    max_position_embeddings: int = 131072
    remat: bool = True
    hold_router: bool = False
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32

    # The router's rule (`ray_tpu.ops.grouped_experts.route`): a softmax
    # over every expert, the chosen shares divided by their sum.
    router_score = "all"

    def __post_init__(self):
        if isinstance(self.rope_parameters, dict):
            object.__setattr__(self, "rope_parameters", parts.frozen(self.rope_parameters))
        for name in ("layer_types", "experts_held", "vocab_rows"):
            object.__setattr__(self, name, tuple(getattr(self, name)))
        if set(self.layer_types) - {FULL, SLIDING}:
            raise ValueError(f"unknown layer types in {self.layer_types}")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("query heads must be a multiple of key heads")
        if self.sliding_window < 1 or self.head_dim % 2:
            raise ValueError("sliding_window >= 1 and an even head_dim")
        if not self.norm_topk_prob:
            raise ValueError("norm_topk_prob false is not implemented")
        first, end = self.vocab_rows
        if not 0 <= first < end <= self.vocab_size:
            raise ValueError(f"vocab_rows {self.vocab_rows} of {self.vocab_size}")
        parts.check_experts_held(self.experts_held, self.num_experts)

    @property
    def num_layers(self) -> int:
        return len(self.layer_types)

    @property
    def rows_held(self) -> int:
        return self.vocab_rows[1] - self.vocab_rows[0]

    @property
    def attention_scale(self) -> float:
        return self.head_dim ** -0.5

    def rope(self, kind: str) -> Dict[str, Any]:
        return dict(dict(self.rope_parameters)[kind])

    def window_of(self, kind: str):
        return self.sliding_window if kind == SLIDING else None

    def local_of(self) -> jax.Array:
        return parts.local_of(self.num_experts, self.experts_held)


# ---------------- parameters ----------------


def _leaf_shapes(cfg: MellumConfig) -> Dict[str, Any]:
    d, held, hd = cfg.hidden_size, len(cfg.experts_held), cfg.head_dim
    q, kv = cfg.num_attention_heads * hd, cfg.num_key_value_heads * hd
    layer = {
        "norm1": (d,), "norm2": (d,),
        "mixer": {"q": (d, q), "k": (d, kv), "v": (d, kv), "o": (q, d)},
        "router": (d, cfg.num_experts),
        "experts_in": (held, d, 2 * cfg.moe_intermediate_size),
        "experts_out": (held, cfg.moe_intermediate_size, d),
    }
    return {
        "wte": (cfg.rows_held, d), "norm_f": (d,), "lm_head": (d, cfg.rows_held),
        "layers": [layer for _ in cfg.layer_types],
    }


def param_shapes(cfg: MellumConfig):
    """The parameter tree as `jax.ShapeDtypeStruct`s (a compile without a
    chip, a count without an array)."""
    return jax.tree_util.tree_map(
        lambda shape: jax.ShapeDtypeStruct(shape, cfg.param_dtype),
        _leaf_shapes(cfg), is_leaf=lambda v: isinstance(v, tuple),
    )


def init_params(cfg: MellumConfig, seed: int) -> Dict[str, Any]:
    """Seeded weights in `param_dtype`, leaf by leaf on the device:
    normal(0.02) matrices, ones for the norms, the embedding at
    normal(EMBEDDING_STD) and the head at normal(HEAD_STD)."""
    stds = {"wte": EMBEDDING_STD, "lm_head": HEAD_STD}
    return parts.seeded_tree(
        _leaf_shapes(cfg), seed, cfg.param_dtype, lambda name: stds.get(name, 0.02)
    )


# ---------------- a layer ----------------


def attention(cfg: MellumConfig, kind: str, p, u, positions):
    """u [B, T, D] at `positions` [T] -> [B, T, D] float32: projections,
    rotation by the kind's rule, flash attention under the kind's mask,
    the output projection."""
    def heads(w):
        return parts.matmul(u, w, cfg.dtype).reshape(u.shape[:-1] + (-1, cfg.head_dim))

    with jax.named_scope("llm.mixer.attention.proj"):
        cos, sin = parts.rotary_tables(cfg.rope(kind), cfg.head_dim, positions)
        q = parts.rotate(heads(p["q"]), cos, sin).astype(cfg.dtype)
        k = parts.rotate(heads(p["k"]), cos, sin).astype(cfg.dtype)
        v = heads(p["v"]).astype(cfg.dtype)
    with jax.named_scope(ATTENTION_SCOPE[kind]):
        mixed = flash_attention(
            q, k, v, causal=True, sm_scale=cfg.attention_scale,
            window=cfg.window_of(kind),
        )
    with jax.named_scope("llm.mixer.attention.proj"):
        return parts.matmul(mixed.reshape(u.shape[:-1] + (-1,)), p["o"], cfg.dtype)


def layer(cfg: MellumConfig, kind: str, p, h, positions):
    """One layer over h [B, T, D]: (h, the routing's counts)."""
    u = parts.rms_norm(h, p["norm1"], cfg.rms_norm_eps)
    h = (h.astype(jnp.float32) + attention(cfg, kind, p["mixer"], u, positions)).astype(cfg.dtype)
    x = parts.rms_norm(h, p["norm2"], cfg.rms_norm_eps)
    out, counts = parts.experts(cfg, p, x.reshape(-1, x.shape[-1]), grouped=True)
    return (h.astype(jnp.float32) + out.reshape(h.shape)).astype(cfg.dtype), counts


def hidden(cfg: MellumConfig, params, tokens):
    """tokens [B, T] (rows of the slice) -> the residual stream after the
    last layer [B, T, D], and the counts summed over layers."""
    positions = jnp.arange(tokens.shape[1])
    h = parts.embed(params["wte"], tokens, cfg.dtype)
    totals = None
    keep = jax.checkpoint_policies.save_only_these_names(*RESIDUAL_NAMES)
    for kind, p in zip(cfg.layer_types, params["layers"]):
        run = functools.partial(layer, cfg, kind)
        if cfg.remat:
            run = jax.checkpoint(run, policy=keep)
        h, counts = run(p, h, positions)
        totals = parts.add_counts(totals, counts)
    return h, totals


def forward(cfg: MellumConfig, params, tokens):
    """Logits [B, T, rows held] (float32) of whole sequences `tokens`
    [B, T]."""
    h, _ = hidden(cfg, params, tokens)
    return parts.head(
        h, params["norm_f"], cfg.rms_norm_eps, params["lm_head"], cfg.dtype, tied=False
    )


def loss_and_counts(cfg: MellumConfig, params, tokens):
    """(mean cross entropy of the next token over the slice's logits, the
    routing's counts summed over layers: COUNTS)."""
    h, counts = hidden(cfg, params, tokens)
    logits = parts.head(
        h[:, :-1], params["norm_f"], cfg.rms_norm_eps, params["lm_head"], cfg.dtype,
        tied=False,
    )
    picked = jnp.take_along_axis(logits, tokens[:, 1:, None], axis=-1)[..., 0]
    loss = jnp.mean(jax.nn.logsumexp(logits, axis=-1) - picked)
    return loss, counts


def train_step(cfg: MellumConfig, tx):
    """`step(params, opt_state, tokens) -> (params, opt_state, loss,
    counts)`: one step of the optax transformation `tx` on the loss above,
    the routing's counts beside the loss. For `train.prepare_step(step,
    donate_argnums=(0, 1))`; jitted, the program is `jit_step`.

    With `cfg.hold_router` the routers' weights stay as they are: their
    gradient is computed and the optimizer's state follows it, the update
    is not applied. A chip that holds a part of the experts sees a part of
    the loss, in which an expert it holds adds its output and an absent one
    adds nothing, so its routers' gradient points away from its own experts
    whatever the data (in the deployment the gates' gradients of all of a
    token's experts come back with the exchange). Trained on that, the
    share of a token's choices held here fell from 25% to 13-18% within
    eighty steps (chip runs, PR 39, call 4)."""
    import optax

    def step(params, opt_state, tokens):
        (loss, counts), grads = jax.value_and_grad(
            lambda p: loss_and_counts(cfg, p, tokens), has_aux=True
        )(params)
        updates, opt_state = tx.update(grads, opt_state, params)
        if cfg.hold_router:
            updates = dict(updates, layers=[
                dict(layer, router=jnp.zeros_like(layer["router"]))
                for layer in updates["layers"]
            ])
        return optax.apply_updates(params, updates), opt_state, loss, counts

    return step
