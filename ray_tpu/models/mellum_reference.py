"""The plain reference of `ray_tpu.models.mellum`: the equations of that
module's header in straightforward `jax.numpy`, float32 throughout under
`jax.default_matmul_precision("highest")`, dense masked attention with K and
V repeated to the query heads, a loop over a token's chosen experts with no
sort and no grouped product, the mean next-token cross entropy, and
gradients by `jax.grad` of that plain loss. It shares no layer code with the
model (only the configuration class and the rotary frequencies' table,
`parts.rope_frequencies`, which `tests/test_laguna_model.py` holds to
Hugging Face's numbers).

Given `experts_held` and `vocab_rows` it leaves out exactly what the model
leaves out: a choice served by an expert held elsewhere adds nothing (the
gates are NOT renormalised over the held ones), and ids, logits and the
loss are over the slice's rows.

Departures from the published description, each the model's too: no
QK-norm and no auxiliary router loss (the config names neither), no
multi-token-prediction head (`described_as` mentions one, unconfirmed), and
`intermediate_size` unused because no layer is dense.

For tests at small sizes: the experts' loop gathers a token's expert
matrices, [T, D, 2F] a choice. `benchmark/lib/reference_mellum.py` is the
benchmark's own copy, computed in blocks at the published widths.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ray_tpu.models import parts
from ray_tpu.models.mellum import SLIDING, MellumConfig


def _norm(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * weight


def _rotate(x, cfg: MellumConfig, kind: str):
    """x [T, H, d]: pairs (i, i + d/2) turned by the position's angle."""
    inv, scale = parts.rope_frequencies(cfg.rope(kind), cfg.head_dim)
    angles = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * jnp.asarray(inv)
    cos, sin = (f(angles)[:, None, :] * scale for f in (jnp.cos, jnp.sin))
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _attention(cfg: MellumConfig, kind: str, p, u):
    t_len = u.shape[0]
    q = _rotate((u @ p["q"]).reshape(t_len, -1, cfg.head_dim), cfg, kind)
    k = _rotate((u @ p["k"]).reshape(t_len, -1, cfg.head_dim), cfg, kind)
    v = (u @ p["v"]).reshape(t_len, -1, cfg.head_dim)
    group = cfg.num_attention_heads // cfg.num_key_value_heads
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k) * cfg.head_dim ** -0.5
    i, j = jnp.arange(t_len)[:, None], jnp.arange(t_len)[None, :]
    seen = j <= i
    if kind == SLIDING:
        seen = seen & (j > i - cfg.sliding_window)
    weights = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
    return jnp.einsum("hqk,khd->qhd", weights, v).reshape(t_len, -1) @ p["o"]


def _experts(cfg: MellumConfig, p, x):
    shares = jax.nn.softmax(x @ p["router"], axis=-1)
    top, ids = jax.lax.top_k(shares, cfg.num_experts_per_tok)
    gates = top / jnp.sum(top, axis=-1, keepdims=True)
    row_of = cfg.local_of()
    out = jnp.zeros_like(x)
    for choice in range(cfg.num_experts_per_tok):
        row = row_of[ids[:, choice]]  # [T], -1 where held elsewhere
        w_in, w_out = p["experts_in"][row], p["experts_out"][row]
        g, u = jnp.split(jnp.einsum("td,tdf->tf", x, w_in), 2, axis=-1)
        y = jnp.einsum("tf,tfd->td", jax.nn.silu(g) * u, w_out)
        out = out + jnp.where(row[:, None] >= 0, gates[:, choice, None] * y, 0.0)
    return out


def forward(cfg: MellumConfig, params, tokens):
    """Logits [B, T, rows held] float32 of `tokens` [B, T], a sequence at a
    time."""
    params = jax.tree_util.tree_map(lambda w: w.astype(jnp.float32), params)

    def one(ids):
        h = params["wte"][ids]
        for kind, p in zip(cfg.layer_types, params["layers"]):
            h = h + _attention(cfg, kind, p["mixer"], _norm(h, p["norm1"], cfg.rms_norm_eps))
            h = h + _experts(cfg, p, _norm(h, p["norm2"], cfg.rms_norm_eps))
        return _norm(h, params["norm_f"], cfg.rms_norm_eps) @ params["lm_head"]

    with jax.default_matmul_precision("highest"):
        return jnp.stack([one(ids) for ids in tokens])


def loss(cfg: MellumConfig, params, tokens):
    """Mean cross entropy of the next token over the slice's logits."""
    logits = forward(cfg, params, tokens)[:, :-1]
    picked = jnp.take_along_axis(logits, tokens[:, 1:, None], axis=-1)[..., 0]
    return jnp.mean(jax.nn.logsumexp(logits, axis=-1) - picked)


def loss_and_grads(cfg: MellumConfig, params, tokens):
    return jax.value_and_grad(lambda p: loss(cfg, p, tokens))(params)
