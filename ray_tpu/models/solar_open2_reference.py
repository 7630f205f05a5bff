"""The plain reference of `solar_open2` (Upstage Solar-Open2-250B).

The forward pass of one whole sequence written out in `jax.numpy`: float32
throughout under `jax.default_matmul_precision("highest")` (on a TPU a
float32 product otherwise runs in lower precision), the delta rule with a
decay a key channel token by token as a plain `lax.scan` over positions (no
chunk, no solve: decay every key row of the state by its own factor, read
what the state holds for the key, correct, read out), the convolution as
four shifted sums, dense masked attention with no positions, the routed
experts as a loop over the experts held, no cache, no batching, no kernel,
and nothing of `ray_tpu` but the names of the parameter tree
(`ray_tpu.models.solar_open2.init_params`) and of the configuration's fields.

With `u` the RMS-normed input of a sub-layer (eps 1e-5) and D the hidden size:

    a layer:  h = h + mixer(norm1(h));  h = h + moe(norm2(h))
    logits = norm_f(h) @ lm_head

A "kda" layer (Kimi Delta Attention, arXiv:2510.26692, as
flash-linear-attention's `KimiDeltaAttention`; H heads of key and value size
128, a causal depthwise convolution over 4 positions):

    q = l2norm_head(silu(conv(u Wq))) * 128**-0.5
    k = l2norm_head(silu(conv(u Wk)));  v = silu(conv(u Wv))
    g = -exp(A_log[h]) * softplus((u Fa) Fb + dt_bias)     [T, H, 128], <= 0
    beta = 2 sigmoid(u Wb)                                 (the 2: `kda_allow_neg_eigval`)
    a head:  S <- diag(exp(g_t)) S;  d = beta_t (v_t - S^T k_t)
             S <- S + k_t (outer) d;  o_t = S^T q_t
    out = (rmsnorm_head(o) * sigmoid((u Ga) Gb + gb)) Wo

A "gqa" layer (`use_rope` false: no positions; `use_gqa_gate` true):
`q, k, v = u Wq, u Wk, u Wv`, causal softmax at scale head_dim**-0.5 over
`num_key_value_heads` cached heads, `out = (attn * sigmoid(u Wg)) Wo`.

The expert MLP of every layer (`first_k_dense_replace` 0): `s = sigmoid(u
Wr)`, the chosen are the `num_experts_per_tok` largest of `s + b` (`b` the
selection bias), their weights `s[chosen] / sum(s[chosen]) *
routed_scaling_factor`, `y = sum_e w_e W2_e(silu(W1_e u) * W3_e u)` plus the
shared expert with weight 1.

The catalog's row of the published config.json carries shape keys only and
the released modelling code has not been seen. What the config does not
spell out is ASSUMED, here and in the program alike, each with the
alternative an argument of `forward` gives; where the released code
differs, the released form wins:

  * the router scores by a sigmoid and chooses with a selection bias that
    takes no part in the weights, one group (the convention of the
    `n_routed_experts` / `n_shared_experts` / `norm_topk_prob` /
    `routed_scaling_factor` family and of Solar Open 100B); alternatives
    `router_score="softmax"` (a softmax over all, then the largest) and
    `selection_bias=False`;
  * the attention gate is elementwise, `Wg [D, Hq * 128]` of the normed
    input; alternative `gate_form="headwise"` (one scalar a head, read here
    off every 128th column of the same matrix) and `gate_form=None`;
  * no QK-norm in the GQA layers; alternative `qk_norm=True` (a weightless
    RMS norm a head);
  * the two low-rank pairs (decay and output gate) have rank 128 and the
    gate's second matrix a bias `gb`, as `KimiDeltaAttention`;
  * pre-norm blocks; alternative `post_norm=True` (Olmo 2's norm on each
    sub-layer's output);
  * the shared expert is added with weight 1, ungated; alternative
    `shared_expert=False` shows what leaving it out moves.

Departures, each deliberate:

  * `experts_held`: the sum over a token's chosen experts runs over the ones
    held here only, each times its weight, the weights computed over all
    chosen and not renormalised over the held ones. With every expert held
    this is the published layer; what the absent experts would add is left
    out here and in the program alike.
  * The L2 norm of q and k has flash-linear-attention's eps under the root.
  * Weights are whatever tree it is given (seeded random for tests and the
    benchmark), upcast to float32; the published checkpoint is bfloat16.
  * `beta_factor`, `scalar_decay` (the decay averaged over a head's
    channels: the scalar rule of arXiv:2412.06464 under this model's name)
    and `state_dtype` (the state rounded after every position) are not part
    of the model: what a comparison against this reference has to notice.
"""

from __future__ import annotations

KDA, GQA = "kda", "gqa"


def _rms_norm(x, weight, eps):
    import jax.numpy as jnp

    return x * (1.0 / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)) * weight


def _l2_norm(x):
    import jax.numpy as jnp

    return x / jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def _silu(x):
    import jax.numpy as jnp

    return x / (1.0 + jnp.exp(-x))


def _sigmoid(x):
    import jax.numpy as jnp

    return 1.0 / (1.0 + jnp.exp(-x))


def _gated_mlp(x, w_in, w_out):
    import jax.numpy as jnp

    g, u = jnp.split(x @ w_in, 2, axis=-1)
    return (_silu(g) * u) @ w_out


def _conv(x, w):
    """Depthwise and causal: x [T, C], w [taps, C]; w[-1] meets the
    position itself."""
    import jax.numpy as jnp

    taps, t_len = w.shape[0], x.shape[0]
    padded = jnp.concatenate([jnp.zeros((taps - 1, x.shape[1])), x], axis=0)
    return sum(padded[i : i + t_len] * w[i] for i in range(taps))


def kda_mixer(cfg, p, u, *, beta_factor=2.0, scalar_decay=False, state_dtype=None):
    """u [T, D] -> [T, D]: the delta rule with a decay a key channel from an
    empty state, position by position."""
    import jax
    import jax.numpy as jnp

    t_len = u.shape[0]
    heads, dk = cfg.kda_num_heads, cfg.kda_head_dim
    width = heads * dk
    w = p["conv_w"]
    q = _silu(_conv(u @ p["q"], w[:, :width])).reshape(t_len, heads, dk)
    k = _silu(_conv(u @ p["k"], w[:, width : 2 * width])).reshape(t_len, heads, dk)
    v = _silu(_conv(u @ p["v"], w[:, 2 * width :])).reshape(t_len, heads, dk)
    q, k = _l2_norm(q) * dk ** -0.5, _l2_norm(k)
    beta = beta_factor * _sigmoid(u @ p["b"])
    dt = jnp.logaddexp((u @ p["fa"]) @ p["fb"] + p["dt_bias"], 0.0)
    g = -jnp.exp(p["A_log"])[None, :, None] * dt.reshape(t_len, heads, dk)
    if scalar_decay:
        g = jnp.broadcast_to(jnp.mean(g, axis=-1, keepdims=True), g.shape)

    def step(s, inputs):
        q_t, k_t, v_t, g_t, beta_t = inputs
        s = jnp.exp(g_t)[:, :, None] * s
        d = beta_t[:, None] * (v_t - jnp.sum(s * k_t[:, :, None], axis=1))
        s = s + k_t[:, :, None] * d[:, None, :]
        if state_dtype is not None:
            # Not a pair of casts: XLA may keep the excess precision.
            info = jnp.finfo(state_dtype)
            s = jax.lax.reduce_precision(s, info.nexp, info.nmant)
        return s, jnp.sum(s * q_t[:, :, None], axis=1)

    _, o = jax.lax.scan(step, jnp.zeros((heads, dk, dk)), (q, k, v, g, beta))
    gate = _sigmoid((u @ p["ga"]) @ p["gb"] + p["g_bias"]).reshape(t_len, heads, dk)
    y = _rms_norm(o, p["norm"], cfg.rms_norm_eps) * gate
    return y.reshape(t_len, width) @ p["o"]


def gqa_mixer(cfg, p, u, *, gate_form="elementwise", qk_norm=False):
    """u [T, D] -> [T, D]: gated grouped-query attention with no positions."""
    import jax
    import jax.numpy as jnp

    t_len, d = u.shape[0], cfg.head_dim
    hq, hkv = cfg.num_attention_heads, cfg.num_key_value_heads
    q = (u @ p["q"]).reshape(t_len, hq, d)
    k = (u @ p["k"]).reshape(t_len, hkv, d)
    v = (u @ p["v"]).reshape(t_len, hkv, d)
    if qk_norm:
        q, k = _rms_norm(q, 1.0, cfg.rms_norm_eps), _rms_norm(k, 1.0, cfg.rms_norm_eps)
    k, v = (jnp.repeat(t, hq // hkv, axis=1) for t in (k, v))
    scores = jnp.einsum("qhd,khd->hqk", q, k) * d ** -0.5
    scores = jnp.where(jnp.tril(jnp.ones((t_len, t_len), bool)), scores, -jnp.inf)
    mixed = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), v)
    if gate_form == "elementwise":
        mixed = mixed * _sigmoid(u @ p["g"]).reshape(t_len, hq, d)
    elif gate_form == "headwise":
        mixed = mixed * _sigmoid(u @ p["g"][:, ::d])[:, :, None]
    elif gate_form is not None:
        raise ValueError(f"unknown gate form {gate_form!r}")
    return mixed.reshape(t_len, hq * d) @ p["o"]


def routed_experts(cfg, p, x, *, router_score="sigmoid", selection_bias=True):
    """The held experts' part of the routed sum for x [T, D]."""
    import jax
    import jax.numpy as jnp

    logits = x @ p["router"]
    if router_score == "sigmoid":
        score = _sigmoid(logits)
    elif router_score == "softmax":
        score = jax.nn.softmax(logits, axis=-1)
    else:
        raise ValueError(f"unknown router score {router_score!r}")
    chooser = score + p["router_bias"] if selection_bias else score
    _, ids = jax.lax.top_k(chooser, cfg.num_experts_per_tok)
    top = jnp.take_along_axis(score, ids, axis=-1)
    weights = top / jnp.sum(top, axis=-1, keepdims=True) * cfg.routed_scaling_factor
    held = jnp.asarray(cfg.experts_held, jnp.int32)

    def one(total, expert):
        number, w_in, w_out = expert
        weight = jnp.sum(jnp.where(ids == number, weights, 0.0), axis=-1)
        return total + weight[:, None] * _gated_mlp(x, w_in, w_out), None

    return jax.lax.scan(
        one, jnp.zeros_like(x), (held, p["experts_in"], p["experts_out"])
    )[0]


def moe(cfg, p, x, *, shared_expert=True, **routing):
    out = routed_experts(cfg, p, x, **routing)
    if shared_expert:
        out = out + _gated_mlp(x, p["shared_in"], p["shared_out"])
    return out


_KDA_OPTIONS = ("beta_factor", "scalar_decay", "state_dtype")
_GQA_OPTIONS = ("gate_form", "qk_norm")
_MOE_OPTIONS = ("router_score", "selection_bias", "shared_expert")


def layer(cfg, kind, p, h, *, post_norm=False, **variant):
    """One layer on the residual rows h [T, D]; p float32."""
    def options(names):
        return {k: v for k, v in variant.items() if k in names}

    unknown = set(variant) - set(_KDA_OPTIONS + _GQA_OPTIONS + _MOE_OPTIONS)
    if unknown:
        raise TypeError(f"unknown alternatives {sorted(unknown)}")
    eps = cfg.rms_norm_eps

    def mixer(u):
        if kind == KDA:
            return kda_mixer(cfg, p["mixer"], u, **options(_KDA_OPTIONS))
        return gqa_mixer(cfg, p["mixer"], u, **options(_GQA_OPTIONS))

    if post_norm:
        h = h + _rms_norm(mixer(h), p["norm1"], eps)
        return h + _rms_norm(moe(cfg, p, h, **options(_MOE_OPTIONS)), p["norm2"], eps)
    h = h + mixer(_rms_norm(h, p["norm1"], eps))
    return h + moe(cfg, p, _rms_norm(h, p["norm2"], eps), **options(_MOE_OPTIONS))


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
