"""The plain reference of `laguna` (poolside Laguna).

The forward pass of one whole sequence written out in `jax.numpy`: float32
throughout under `jax.default_matmul_precision("highest")` (on a TPU a
float32 product otherwise runs in lower precision), full [T, T] masks
(causal; causal and i - j < `sliding_window` on a sliding layer), rotary
tables computed from the published formulas in float64, the routed experts
as a loop over the experts held, no cache, no batching, no kernel, and
nothing of `ray_tpu` but the names of the parameter tree
(`ray_tpu.models.laguna.init_params`) and of the configuration's fields.
Attention runs a head at a time and, where `query_block` is given, a block
of queries at a time (each block against its own rows of the same masks),
so that a long sequence fits.

It follows the published config.json of Laguna-S-2.1 and Hugging Face's
`_compute_yarn_parameters` / `rotate_half`. The config does not spell out
four pointwise choices; what is assumed, and the alternative:

  * the gate (`gating: per-head`) is sigmoid(u Wg), one scalar a head of
    the layer's normalised input u, multiplying the head's attention output
    before the output projection (the head-wise gate of arXiv:2505.06708);
    the alternative is an elementwise gate [T, H x d];
  * no RMS norm on q and k (the config has no key for one);
  * the router's score is a softmax over all `num_experts` before the top
    `num_experts_per_tok`, whose shares are divided by their sum
    (`norm_topk_prob`) and multiplied by `moe_routed_scaling_factor`; the
    alternative is a sigmoid score;
  * the shared expert is added with weight 1, with no gate of its own.

Departures, each deliberate:

  * `experts_held`: the sum over a token's chosen experts runs over the
    ones held here only, each times its gate, the gates computed over all
    chosen and not renormalised over the held ones. With every expert held
    this is the published layer. It is the share one chip of an
    expert-parallel deployment computes; what the absent experts would add
    is left out here and in the program alike.
  * Weights are whatever tree it is given (seeded random for tests and the
    benchmark), upcast to float32; the published checkpoint is bfloat16.
  * `window`, `scores_dtype`, `gate_dtype`: a window of another length,
    attention scores or the gate rounded to a lower precision. Not part of
    the model: what a comparison against this reference has to notice.
"""

from __future__ import annotations

import math

FULL, SLIDING = "full_attention", "sliding_attention"


def _rms_norm(x, weight, eps):
    import jax.numpy as jnp

    return x * (1.0 / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)) * weight


def _silu(x):
    import jax.numpy as jnp

    return x / (1.0 + jnp.exp(-x))


def _gated_mlp(x, w_in, w_out):
    import jax.numpy as jnp

    g, u = jnp.split(x @ w_in, 2, axis=-1)
    return (_silu(g) * u) @ w_out


def _rounded(x, dtype):
    """x with the precision of `dtype` (None: as it is). Not a pair of
    casts: XLA may keep the excess precision."""
    import jax
    import jax.numpy as jnp

    if dtype is None:
        return x
    info = jnp.finfo(dtype)
    return jax.lax.reduce_precision(x, info.nexp, info.nmant)


def rope_of(cfg, kind):
    """The kind's entry of `rope_parameters`, given as the published nested
    dict or as sorted pairs."""
    return dict(dict(cfg.rope_parameters)[kind])


def rotary_tables(cfg, kind, t_len):
    """(cos, sin) [T, rotated / 2] float32 of positions 0..T-1, computed in
    float64 from the formulas: default, inv_i = base^(-2i/d); YaRN over the
    rotated dimension d, f_i = base^(2i/d), dim(n) = d ln(L / (2 pi n)) /
    (2 ln base), low = floor(dim(beta_fast)), high = ceil(dim(beta_slow))
    clipped to [0, d - 1], ramp_i = clip((i - low) / (high - low), 0, 1),
    inv_i = (1 - ramp_i) / f_i + ramp_i / (factor f_i), cos and sin times
    `attention_factor`. The program takes its angles in float32 from the
    float32 inverse frequencies, and so does this."""
    import numpy as np

    rope = rope_of(cfg, kind)
    d = int(cfg.head_dim * rope.get("partial_rotary_factor", 1))
    base = float(rope["rope_theta"])
    f = base ** (np.arange(0, d, 2, dtype=np.float64) / d)
    inv, scale = 1.0 / f, 1.0
    if rope.get("rope_type", "default") == "yarn":
        factor, length = float(rope["factor"]), rope["original_max_position_embeddings"]

        def dim(n):
            return d * math.log(length / (2 * math.pi * n)) / (2 * math.log(base))

        low = max(math.floor(dim(rope["beta_fast"])), 0)
        high = min(math.ceil(dim(rope["beta_slow"])), d - 1)
        if low == high:
            high += 0.001
        ramp = np.clip((np.arange(d // 2) - low) / (high - low), 0.0, 1.0)
        inv = (1 - ramp) / f + ramp / (factor * f)
        scale = rope.get("attention_factor")
        if scale is None:
            scale = 0.1 * math.log(factor) + 1.0
    angles = np.arange(t_len, dtype=np.float32)[:, None] * inv.astype(np.float32)[None, :]
    return (np.cos(angles) * np.float32(scale)), (np.sin(angles) * np.float32(scale))


def _rotate(x, cos, sin):
    """x [T, H, d]: the first 2 * cos.shape[-1] dimensions rotated in pairs
    (i, i + half) (`rotate_half`), the rest passed through."""
    import jax.numpy as jnp

    half = cos.shape[-1]
    x1, x2, rest = x[..., :half], x[..., half : 2 * half], x[..., 2 * half :]
    c, s = cos[:, None, :], sin[:, None, :]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s, rest], axis=-1)


def attention(cfg, kind, p, u, *, window=None, scores_dtype=None, gate_dtype=None,
              query_block=None):
    """u [T, D] -> [T, D]: the gated attention of one layer of `kind`."""
    import jax
    import jax.numpy as jnp

    t_len, d = u.shape[0], cfg.head_dim
    hkv = cfg.num_key_value_heads
    hq = p["q"].shape[1] // d
    cos, sin = (jnp.asarray(t) for t in rotary_tables(cfg, kind, t_len))
    q = _rotate((u @ p["q"]).reshape(t_len, hq, d), cos, sin)
    k = _rotate((u @ p["k"]).reshape(t_len, hkv, d), cos, sin)
    v = (u @ p["v"]).reshape(t_len, hkv, d)
    k, v = (jnp.repeat(t, hq // hkv, axis=1) for t in (k, v))
    if kind == SLIDING and window is None:
        window = cfg.sliding_window
    block = t_len if query_block is None else min(query_block, t_len)
    cols = jnp.arange(t_len)[None, :]

    def one_head(head):
        q_h, k_h, v_h = head

        def one_block(rows_and_q):
            rows, q_b = rows_and_q
            seen = cols <= rows[:, None]
            if kind == SLIDING:
                seen = seen & (rows[:, None] - cols < window)
            scores = _rounded((q_b @ k_h.T) * d ** -0.5, scores_dtype)
            return jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1) @ v_h

        pad = -t_len % block
        rows = jnp.arange(t_len + pad).reshape(-1, block)
        q_blocks = jnp.pad(q_h, ((0, pad), (0, 0))).reshape(-1, block, d)
        # A padded query row sees every key: finite, and cut below.
        return jax.lax.map(one_block, (rows, q_blocks)).reshape(-1, d)[:t_len]

    mixed = jax.lax.map(one_head, tuple(t.transpose(1, 0, 2) for t in (q, k, v)))
    gate = _rounded(1.0 / (1.0 + jnp.exp(-(u @ p["g"]))), gate_dtype)  # [T, H]
    mixed = mixed.transpose(1, 0, 2) * gate[:, :, None]
    return mixed.reshape(t_len, hq * d) @ p["o"]


def routed_experts(cfg, p, x):
    """The held experts' part of the routed sum for x [T, D]."""
    import jax
    import jax.numpy as jnp

    share = jax.nn.softmax(x @ p["router"], axis=-1)
    top, ids = jax.lax.top_k(share, cfg.num_experts_per_tok)
    gates = top / jnp.sum(top, axis=-1, keepdims=True) * cfg.moe_routed_scaling_factor
    held = jnp.asarray(cfg.experts_held, jnp.int32)

    def one(total, expert):
        number, w_in, w_out = expert
        gate = jnp.sum(jnp.where(ids == number, gates, 0.0), axis=-1)
        return total + gate[:, None] * _gated_mlp(x, w_in, w_out), None

    return jax.lax.scan(
        one, jnp.zeros_like(x), (held, p["experts_in"], p["experts_out"])
    )[0]


def layer(cfg, kind, mlp, p, h, **variant):
    """One layer on the residual rows h [T, D]; p float32."""
    u = _rms_norm(h, p["norm1"], cfg.rms_norm_eps)
    h = h + attention(cfg, kind, p["mixer"], u, **variant)
    x = _rms_norm(h, p["norm2"], cfg.rms_norm_eps)
    if mlp == "dense":
        return h + _gated_mlp(x, p["mlp_in"], p["mlp_out"])
    return h + routed_experts(cfg, p, x) + _gated_mlp(x, p["shared_in"], p["shared_out"])


def forward(cfg, params, tokens, **variant):
    """Logits [T, vocab] float32 of one sequence `tokens` [T]."""
    import jax
    import jax.numpy as jnp

    def f32(tree):
        return jax.tree_util.tree_map(lambda x: x.astype(jnp.float32), tree)

    with jax.default_matmul_precision("highest"):
        h = params["wte"].astype(jnp.float32)[tokens]
        for kind, mlp, p in zip(cfg.layer_types, cfg.mlp_layer_types, params["layers"]):
            h = layer(cfg, kind, mlp, f32(p), h, **variant)
        h = _rms_norm(h, params["norm_f"].astype(jnp.float32), cfg.rms_norm_eps)
        return h @ params["lm_head"].astype(jnp.float32)
