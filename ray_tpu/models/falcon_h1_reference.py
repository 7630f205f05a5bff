"""The plain reference of `falcon_h1` (TII Falcon-H1).

The forward pass of one whole sequence written out in `jax.numpy`: float32
throughout under `jax.default_matmul_precision("highest")` (on a TPU a
float32 product otherwise runs in lower precision), the Mamba-2 recurrence
token by token as a plain `lax.scan` over positions (no chunk), the
convolution as four shifted sums, dense masked attention with rotary
positions, no cache, no batching, no kernel, and nothing of `ray_tpu` but
the names of the parameter tree (`ray_tpu.models.falcon_h1.init_params`).

It follows the published description (the config.json of
`tiiuae/Falcon-H1-34B-Instruct`, Hugging Face `modeling_falcon_h1` and Dao
& Gu 2024 for the mixer): in every layer a Mamba-2 mixer and grouped-query
attention read the same normed input and their outputs are added to the
stream, then a gated MLP; the scalar multipliers sit where the published
layer puts them (`ssm_in_multiplier` on the mixer's input, `ssm_multipliers`
on in_proj's five segments, `ssm_out_multiplier` on out_proj's output,
`attention_in_multiplier` on attention's input, `key_multiplier` on the keys
before the rotation, `attention_out_multiplier` on the output projection,
`mlp_multipliers` on the gate's pre-activation and on the MLP's output,
`embedding_multiplier`, `lm_head_multiplier`).

What the config does not spell out is assumed, and each choice has its
alternative as an argument of `forward`, so that a test can show what it
moves:

  * `norm_groups` (None = `mamba_n_groups`): the gated norm takes its mean
    square over each group's channels (2,048 of 4,096 at the published
    size; `mamba_norm_before_gate` false puts the gate first).
    Alternative: 1, one mean square over the whole inner width.
  * `multiplier_order` ((0, 1, 2, 3, 4)): `ssm_multipliers[i]` scales the
    i-th of the segments z, x, B, C, dt. Alternative: any other order.
  * `dt_limit` (None): the step is not clamped after the softplus.
    Alternative: (lo, hi), Mamba-2's `dt_limit`.
  * `state_dtype` (None = float32) rounds the recurrent state to that type
    after every position: what a state kept one precision down would give.

Departures, each deliberate:

  * Weights are whatever tree it is given (seeded random for tests and the
    benchmark), upcast to float32; the published checkpoint is bfloat16.
  * `branches` and `shared_group` are not part of the model: a layer with
    one of its two mixers left out, and every head reading group 0's B and
    C, are what a comparison is shown to notice.
"""

from __future__ import annotations


def _rms_norm(x, weight, eps):
    import jax.numpy as jnp

    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * weight


def _silu(x):
    import jax.numpy as jnp

    return x / (1.0 + jnp.exp(-x))


def mamba_mixer(cfg, p, u, norm_groups=None, multiplier_order=(0, 1, 2, 3, 4),
                dt_limit=None, state_dtype=None, shared_group=False):
    """u [T, D] -> [T, D]: the Mamba-2 branch from an empty state, position
    by position."""
    import jax
    import jax.numpy as jnp

    t_len = u.shape[0]
    heads, p_dim, n = cfg.mamba_n_heads, cfg.mamba_d_head, cfg.mamba_d_state
    groups, d_ssm = cfg.mamba_n_groups, cfg.mamba_d_ssm
    widths = (d_ssm, d_ssm, groups * n, groups * n, heads)
    mup = jnp.concatenate([
        jnp.full((width,), cfg.ssm_multipliers[multiplier_order[i]])
        for i, width in enumerate(widths)
    ])
    zxbcdt = ((cfg.ssm_in_multiplier * u) @ p["in_proj"]) * mup
    conv_dim = d_ssm + 2 * groups * n
    z = zxbcdt[:, :d_ssm]
    xbc = zxbcdt[:, d_ssm : d_ssm + conv_dim]
    dt = zxbcdt[:, d_ssm + conv_dim :]
    taps = cfg.mamba_d_conv
    padded = jnp.concatenate([jnp.zeros((taps - 1, conv_dim)), xbc], axis=0)
    conv = sum(padded[i : i + t_len] * p["conv_w"][i] for i in range(taps))
    xbc = _silu(conv + p["conv_b"])
    x = xbc[:, :d_ssm].reshape(t_len, heads, p_dim)
    b = xbc[:, d_ssm : d_ssm + groups * n].reshape(t_len, groups, n)
    c = xbc[:, d_ssm + groups * n :].reshape(t_len, groups, n)
    # Head j reads the B and C of group j // (heads / groups).
    group_of = jnp.arange(heads) // (heads // groups)
    if shared_group:
        group_of = jnp.zeros_like(group_of)
    b, c = b[:, group_of], c[:, group_of]  # [T, H, N]
    dt = jnp.logaddexp(dt + p["dt_bias"], 0.0)  # softplus
    if dt_limit is not None:
        dt = jnp.clip(dt, *dt_limit)
    a = -jnp.exp(p["A_log"])

    def step(s, inputs):
        x_t, b_t, c_t, dt_t = inputs
        s = jnp.exp(dt_t * a)[:, None, None] * s + (
            (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :]
        )
        if state_dtype is not None:
            # Not a pair of casts: XLA may keep the excess precision.
            info = jnp.finfo(state_dtype)
            s = jax.lax.reduce_precision(s, info.nexp, info.nmant)
        return s, jnp.sum(s * c_t[:, None, :], axis=-1)

    _, y = jax.lax.scan(step, jnp.zeros((heads, p_dim, n)), (x, b, c, dt))
    y = y + p["D"][None, :, None] * x
    y = y.reshape(t_len, d_ssm) * _silu(z)
    by = groups if norm_groups is None else norm_groups
    y = y.reshape(t_len, by, d_ssm // by)
    y = y / jnp.sqrt(jnp.mean(y * y, axis=-1, keepdims=True) + cfg.rms_norm_eps)
    y = y.reshape(t_len, d_ssm) * p["norm"]
    return cfg.ssm_out_multiplier * (y @ p["out_proj"])


def _rope(x, theta):
    """x [T, H, d] rotated in the pairs (i, i + d / 2) by its position."""
    import jax.numpy as jnp

    t_len, _, d = x.shape
    inverse = float(theta) ** (-jnp.arange(0, d, 2) / d)
    angles = jnp.arange(t_len)[:, None] * inverse[None, :]
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2 :]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def attention_mixer(cfg, p, u):
    """u [T, D] -> [T, D]: the attention branch, dense and causal."""
    import jax
    import jax.numpy as jnp

    t_len = u.shape[0]
    hq, hkv, d = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    ua = cfg.attention_in_multiplier * u
    q = _rope((ua @ p["q"]).reshape(t_len, hq, d), cfg.rope_theta)
    k = _rope((cfg.key_multiplier * (ua @ p["k"])).reshape(t_len, hkv, d), cfg.rope_theta)
    v = (ua @ p["v"]).reshape(t_len, hkv, d)
    k, v = (jnp.repeat(t, hq // hkv, axis=1) for t in (k, v))
    scores = jnp.einsum("qhd,khd->hqk", q, k) * d ** -0.5
    scores = jnp.where(jnp.tril(jnp.ones((t_len, t_len), bool)), scores, -jnp.inf)
    weights = jax.nn.softmax(scores, axis=-1)
    mixed = jnp.einsum("hqk,khd->qhd", weights, v).reshape(t_len, hq * d)
    return cfg.attention_out_multiplier * (mixed @ p["o"])


def layer(cfg, p, h, branches=("mamba", "full_attention"), **mamba_options):
    """One layer on the residual rows h [T, D]; p float32."""
    import jax.numpy as jnp

    u = _rms_norm(h, p["norm1"], cfg.rms_norm_eps)
    if "mamba" in branches:
        h = h + mamba_mixer(cfg, p["mamba"], u, **mamba_options)
    if "full_attention" in branches:
        h = h + attention_mixer(cfg, p["full_attention"], u)
    f = _rms_norm(h, p["norm2"], cfg.rms_norm_eps)
    g, up = jnp.split(f @ p["mlp_in"], 2, axis=-1)
    gate_m, out_m = cfg.mlp_multipliers
    return h + out_m * ((_silu(gate_m * g) * up) @ p["mlp_out"])


def forward(cfg, params, tokens, **options):
    """Logits [T, vocab] float32 of one sequence `tokens` [T]. `options`:
    the assumed choices' alternatives and the two faults named in the
    header (`layer`, `mamba_mixer`)."""
    import jax
    import jax.numpy as jnp

    def f32(tree):
        return jax.tree_util.tree_map(lambda x: x.astype(jnp.float32), tree)

    with jax.default_matmul_precision("highest"):
        h = cfg.embedding_multiplier * params["wte"].astype(jnp.float32)[tokens]
        for p in params["layers"]:
            h = layer(cfg, f32(p), h, **options)
        h = _rms_norm(h, params["norm_f"].astype(jnp.float32), cfg.rms_norm_eps)
        return cfg.lm_head_multiplier * (h @ params["lm_head"].astype(jnp.float32))
