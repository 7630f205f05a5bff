"""The plain reference of `granitemoehybrid` (IBM Granite 4.0-H).

The forward pass of one whole sequence written out in `jax.numpy`: float32
throughout under `jax.default_matmul_precision("highest")` (on a TPU a
float32 product otherwise runs in lower precision), the Mamba-2 recurrence
as a plain `lax.scan` over positions (no chunks), the convolution as four
shifted sums, the routed experts as a loop over the experts held, dense
causal attention, no cache, no batching, no kernel, and nothing of
`ray_tpu` but the names of the parameter tree
(`ray_tpu.models.granite_hybrid.init_params`).

It follows the published description (Hugging Face `modeling_granitemoehybrid`
and Dao & Gu 2024 for the mixer). Departures, each deliberate:

  * `experts_held`: the sum over a token's chosen experts runs over the
    ones held here only, each times its gate, the gates a softmax over all
    `num_experts_per_tok` chosen logits and not renormalised. With every
    expert held this is the published layer. It is the share one chip of
    an expert-parallel deployment computes; what the absent experts would
    add is left out here and in the program alike.
  * Weights are whatever tree it is given (seeded random for tests and the
    benchmark), upcast to float32; the published checkpoint is bfloat16.
  * `state_dtype` (None = float32) rounds the recurrent state to that type
    after every position: what a state kept one precision down would give.
    Not part of the model; the benchmark reports it beside the comparison.
"""

from __future__ import annotations


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


def mamba_mixer(cfg, p, u, state_dtype=None):
    """u [T, D] -> [T, D]: Mamba-2 from an empty state, position by
    position."""
    import jax
    import jax.numpy as jnp

    t_len = u.shape[0]
    heads, p_dim, n = cfg.mamba_n_heads, cfg.mamba_d_head, cfg.mamba_d_state
    d_inner = heads * p_dim
    zxbcdt = u @ p["in_proj"]
    z = zxbcdt[:, :d_inner]
    xbc = zxbcdt[:, d_inner : d_inner + cfg.conv_dim]
    dt = zxbcdt[:, d_inner + cfg.conv_dim :]
    taps = cfg.mamba_d_conv
    padded = jnp.concatenate([jnp.zeros((taps - 1, cfg.conv_dim)), xbc], axis=0)
    conv = sum(padded[i : i + t_len] * p["conv_w"][i] for i in range(taps))
    xbc = _silu(conv + p["conv_b"])
    x = xbc[:, :d_inner].reshape(t_len, heads, p_dim)
    b = xbc[:, d_inner : d_inner + n]
    c = xbc[:, d_inner + n :]
    dt = jnp.logaddexp(dt + p["dt_bias"], 0.0)  # softplus
    a = -jnp.exp(p["A_log"])

    def step(s, inputs):
        x_t, b_t, c_t, dt_t = inputs
        s = jnp.exp(dt_t * a)[:, None, None] * s + (
            (dt_t[:, None] * x_t)[:, :, None] * b_t[None, None, :]
        )
        if state_dtype is not None:
            # Not a pair of casts: XLA may keep the excess precision.
            info = jnp.finfo(state_dtype)
            s = jax.lax.reduce_precision(s, info.nexp, info.nmant)
        return s, s @ c_t

    _, y = jax.lax.scan(step, jnp.zeros((heads, p_dim, n)), (x, b, c, dt))
    y = y + p["D"][None, :, None] * x
    y = y.reshape(t_len, d_inner) * _silu(z)
    return _rms_norm(y, p["norm"], cfg.rms_norm_eps) @ p["out_proj"]


def attention_mixer(cfg, p, u):
    import jax
    import jax.numpy as jnp

    t_len = u.shape[0]
    hq, hkv, d = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    q = (u @ p["q"]).reshape(t_len, hq, d)
    k = jnp.repeat((u @ p["k"]).reshape(t_len, hkv, d), hq // hkv, axis=1)
    v = jnp.repeat((u @ p["v"]).reshape(t_len, hkv, d), hq // hkv, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k) * cfg.attention_multiplier
    scores = jnp.where(jnp.tril(jnp.ones((t_len, t_len), bool)), scores, -jnp.inf)
    weights = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("hqk,khd->qhd", weights, v).reshape(t_len, hq * d) @ p["o"]


def routed_experts(cfg, p, x):
    """The held experts' part of the routed sum for x [T, D]."""
    import jax
    import jax.numpy as jnp

    logits = x @ p["router"]
    top, ids = jax.lax.top_k(logits, cfg.num_experts_per_tok)
    gates = jax.nn.softmax(top, axis=-1)
    out = jnp.zeros_like(x)
    for row, expert in enumerate(cfg.experts_held):
        gate = jnp.sum(jnp.where(ids == expert, gates, 0.0), axis=-1)
        out = out + gate[:, None] * _gated_mlp(
            x, p["experts_in"][row], p["experts_out"][row]
        )
    return out


def layer(cfg, kind, p, h, state_dtype=None):
    """One layer on the residual rows h [T, D]; p float32."""
    u = _rms_norm(h, p["norm1"], cfg.rms_norm_eps)
    if kind == "mamba":
        mixed = mamba_mixer(cfg, p["mixer"], u, state_dtype)
    else:
        mixed = attention_mixer(cfg, p["mixer"], u)
    h = h + cfg.residual_multiplier * mixed
    x = _rms_norm(h, p["norm2"], cfg.rms_norm_eps)
    out = routed_experts(cfg, p, x) + _gated_mlp(x, p["shared_in"], p["shared_out"])
    return h + cfg.residual_multiplier * out


def forward(cfg, params, tokens, state_dtype=None):
    """Logits [T, vocab] float32 of one sequence `tokens` [T]."""
    import jax
    import jax.numpy as jnp

    def f32(tree):
        return jax.tree_util.tree_map(lambda x: x.astype(jnp.float32), tree)

    with jax.default_matmul_precision("highest"):
        wte = params["wte"].astype(jnp.float32)
        h = wte[tokens] * cfg.embedding_multiplier
        for kind, p in zip(cfg.layer_types, params["layers"]):
            h = layer(cfg, kind, f32(p), h, state_dtype)
        h = _rms_norm(h, params["norm_f"].astype(jnp.float32), cfg.rms_norm_eps)
        return (h @ wte.T) / cfg.logits_scaling
