"""The plain reference of `olmo_hybrid` (Ai2 Olmo Hybrid).

The forward pass of one whole sequence written out in `jax.numpy`: float32
throughout under `jax.default_matmul_precision("highest")` (on a TPU a
float32 product otherwise runs in lower precision), the gated delta rule
token by token as a plain `lax.scan` over positions (no chunk, no solve:
decay the state, read what it holds for the key, correct, read out), the
convolution as four shifted sums, dense masked attention, no cache, no
batching, no kernel, and nothing of `ray_tpu` but the names of the parameter
tree (`ray_tpu.models.olmo_hybrid.init_params`).

It follows the published config.json and, for the linear layers, Yang et
al., "Gated Delta Networks" (arXiv:2412.06464) and the `GatedDeltaNet` layer
of flash-linear-attention, whose argument names the config's keys repeat.
What the config does not spell out is ASSUMED, here and in the program
alike, each with the alternative an argument of `forward` gives:

  * `rope_parameters.rope_theta` is null: no rotary positions in the full
    layers; the recurrent layers carry order (alternative `rope_theta`:
    rotary over all of a head's dimensions at that base, Olmo 3's 500,000).
  * The norm is on each sub-layer's OUTPUT in both kinds of layer, Olmo
    2's reordered norm (alternative `pre_norm_linear`: the linear layers
    normalise their input instead).
  * No bias on the convolutions (nothing to switch: the tree has none).
  * The state is float32 (alternative `state_dtype`: rounded to that type
    after every position, what a state kept one precision down would give).

Departures from the published description, each deliberate:

  * The L2 norm of q and k has flash-linear-attention's eps under the root
    (1e-6), which the paper's `q / |q|` lacks.
  * Weights are whatever tree it is given (seeded random for tests and the
    benchmark), upcast to float32; the published checkpoint is bfloat16.
  * `beta_factor` (2, the config's `linear_allow_neg_eigval`) and `qk_norm`
    are arguments so that a test can show what leaving each out moves.
"""

from __future__ import annotations


def _rms_norm(x, weight, eps):
    import jax.numpy as jnp

    return x * (1.0 / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)) * weight


def _l2_norm(x):
    import jax.numpy as jnp

    return x / jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def _silu(x):
    import jax.numpy as jnp

    return x / (1.0 + jnp.exp(-x))


def _conv(x, w):
    """Depthwise and causal: x [T, C], w [taps, C]; w[-1] meets the
    position itself."""
    import jax.numpy as jnp

    taps, t_len = w.shape[0], x.shape[0]
    padded = jnp.concatenate([jnp.zeros((taps - 1, x.shape[1])), x], axis=0)
    return sum(padded[i : i + t_len] * w[i] for i in range(taps))


def linear_mixer(cfg, p, x, state_dtype=None, beta_factor=2.0):
    """x [T, D] -> [T, D]: the gated delta rule from an empty state,
    position by position."""
    import jax
    import jax.numpy as jnp

    t_len = x.shape[0]
    heads, dk, dv = cfg.linear_num_value_heads, cfg.linear_key_head_dim, cfg.linear_value_head_dim
    key_dim = heads * dk
    w = p["conv_w"]
    q = _silu(_conv(x @ p["q"], w[:, :key_dim])).reshape(t_len, heads, dk)
    k = _silu(_conv(x @ p["k"], w[:, key_dim : 2 * key_dim])).reshape(t_len, heads, dk)
    v = _silu(_conv(x @ p["v"], w[:, 2 * key_dim :])).reshape(t_len, heads, dv)
    q, k = _l2_norm(q) * dk ** -0.5, _l2_norm(k)
    beta = beta_factor / (1.0 + jnp.exp(-(x @ p["b"])))
    g = -jnp.exp(p["A_log"]) * jnp.logaddexp(x @ p["a"] + p["dt_bias"], 0.0)

    def step(s, inputs):
        q_t, k_t, v_t, g_t, beta_t = inputs
        s = jnp.exp(g_t)[:, None, None] * s
        d = beta_t[:, None] * (v_t - jnp.sum(s * k_t[:, :, None], axis=1))
        s = s + k_t[:, :, None] * d[:, None, :]
        if state_dtype is not None:
            # Not a pair of casts: XLA may keep the excess precision.
            info = jnp.finfo(state_dtype)
            s = jax.lax.reduce_precision(s, info.nexp, info.nmant)
        return s, jnp.sum(s * q_t[:, :, None], axis=1)

    _, o = jax.lax.scan(step, jnp.zeros((heads, dk, dv)), (q, k, v, g, beta))
    gate = _silu(x @ p["g"]).reshape(t_len, heads, dv)
    y = _rms_norm(o, p["norm"], cfg.rms_norm_eps) * gate
    return y.reshape(t_len, heads * dv) @ p["o"]


def _rotated(x, theta):
    """x [T, H, d] under rotary positions 0.. at base `theta`, pairs
    (i, i + d/2): the alternative to this model's no positions."""
    import jax.numpy as jnp

    t_len, _, d = x.shape
    inv = theta ** (-jnp.arange(0, d, 2) / d)
    angles = jnp.arange(t_len)[:, None] * inv
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2 :]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def attention_mixer(cfg, p, x, qk_norm=True, rope_theta=None):
    import jax
    import jax.numpy as jnp

    t_len = x.shape[0]
    hq, hkv = cfg.num_attention_heads, cfg.num_key_value_heads
    d = cfg.hidden_size // hq
    q, k = x @ p["q"], x @ p["k"]
    if qk_norm:
        q = _rms_norm(q, p["norm_q"], cfg.rms_norm_eps)
        k = _rms_norm(k, p["norm_k"], cfg.rms_norm_eps)
    q, k = q.reshape(t_len, hq, d), k.reshape(t_len, hkv, d)
    if rope_theta is not None:
        q, k = _rotated(q, rope_theta), _rotated(k, rope_theta)
    k = jnp.repeat(k, hq // hkv, axis=1)
    v = jnp.repeat((x @ p["v"]).reshape(t_len, hkv, d), hq // hkv, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k) * d ** -0.5
    scores = jnp.where(jnp.tril(jnp.ones((t_len, t_len), bool)), scores, -jnp.inf)
    weights = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("hqk,khd->qhd", weights, v).reshape(t_len, hq * d) @ p["o"]


def layer(cfg, kind, p, h, *, state_dtype=None, beta_factor=2.0, qk_norm=True,
          rope_theta=None, pre_norm_linear=False):
    """One layer on the residual rows h [T, D]; p float32."""
    import jax.numpy as jnp

    eps = cfg.rms_norm_eps
    if kind == "linear_attention":
        if pre_norm_linear:
            h = h + linear_mixer(
                cfg, p["mixer"], _rms_norm(h, p["norm1"], eps), state_dtype, beta_factor
            )
        else:
            h = h + _rms_norm(
                linear_mixer(cfg, p["mixer"], h, state_dtype, beta_factor), p["norm1"], eps
            )
    else:
        h = h + _rms_norm(
            attention_mixer(cfg, p["mixer"], h, qk_norm, rope_theta), p["norm1"], eps
        )
    g, u = jnp.split(h @ p["mlp_in"], 2, axis=-1)
    return h + _rms_norm((_silu(g) * u) @ p["mlp_out"], p["norm2"], eps)


def forward(cfg, params, tokens, **variant):
    """Logits [T, vocab] float32 of one sequence `tokens` [T]. `variant`:
    the header's alternatives (`layer`'s keywords)."""
    import jax
    import jax.numpy as jnp

    def f32(tree):
        return jax.tree_util.tree_map(lambda x: x.astype(jnp.float32), tree)

    with jax.default_matmul_precision("highest"):
        h = params["wte"].astype(jnp.float32)[tokens]
        for kind, p in zip(cfg.layer_types, params["layers"]):
            h = layer(cfg, kind, f32(p), h, **variant)
        h = _rms_norm(h, params["norm_f"].astype(jnp.float32), cfg.rms_norm_eps)
        return h @ params["lm_head"].astype(jnp.float32)
