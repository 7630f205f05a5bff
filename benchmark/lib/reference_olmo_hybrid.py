"""The plain reference of `olmo_hybrid` (Ai2 Olmo Hybrid) and the comparison
that decides `correct` for its cells.

The benchmark's own copy: it imports nothing of the program. `layer` is the
forward pass written out in `jax.numpy`, float32 under
`jax.default_matmul_precision("highest")`: Olmo 2's reordered norm (`h +
norm(mixer(h))`, `h + norm(mlp(h))`), the gated-delta-rule mixer (five
projections, a causal depthwise convolution over four positions and SiLU on
q, k and v, L2-normalised q and k a head, `beta = 2 sigmoid`, `g = -exp(A_log)
softplus(. + dt_bias)`, the recurrence `S <- exp(g) S; d = beta (v - S^T k);
S <- S + k (outer) d; o = S^T q` as a plain `lax.scan` over positions, the
gated RMS norm a head, the output projection), full attention with QK-norm
over the whole projections and no positions, a gated MLP, an untied head.
What it takes from the program is the seeded parameter tree, by the names
`ray_tpu/models/olmo_hybrid.py` gives the leaves, upcast one layer at a time
(bfloat16 to float32 is exact; a float32 copy of the tree does not fit beside
the bfloat16 one).

`OlmoHybridServingReference` has the interface of
`lib/reference.ServingReference` (`judge`, `control_gaps`). The controls are
the same forward with one thing changed, each of which the comparison is
held to notice (`CONTROLS`):

  int8            the weights of every dense layer (a scale an output
                  channel) and each such layer's input (a scale a token)
                  through symmetric int8, the nearest precision below the
                  bfloat16 the configuration states
  bf16_state      the recurrent state rounded to bfloat16 after every position
  beta_without_2  `beta = sigmoid`, without `linear_allow_neg_eigval`'s factor
  conv_tail_cut   the convolution sees zeros for the positions before every
                  256th of the prompt and before the first decoded token:
                  a tail dropped where a chunk or the decode takes over
  no_qk_norm      QK-norm left out of the full layers
"""

from __future__ import annotations

import types

CONTROLS = {
    "int8": {"int8": True},
    "bf16_state": {"state_dtype": "bfloat16"},
    "beta_without_2": {"beta_factor": 1.0},
    "conv_tail_cut": {"conv_cut": 256},
    "no_qk_norm": {"qk_norm": False},
}


def _int8(x, axis: int):
    """What a symmetric int8 path keeps of `x`, one scale along `axis`."""
    import jax.numpy as jnp

    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    scale = jnp.where(scale > 0, scale, 1.0)
    return jnp.round(x / scale) * scale


def _dense(x, w, int8: bool):
    if int8:
        x, w = _int8(x, -1), _int8(w, -2)
    return x @ w


def _rms_norm(x, weight, eps):
    import jax.numpy as jnp

    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * weight


def _l2_norm(x):
    import jax.numpy as jnp

    return x / jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def _silu(x):
    import jax.numpy as jnp

    return x / (1.0 + jnp.exp(-x))


def _conv(x, w, cut_at=None):
    """Depthwise and causal over w.shape[0] positions: x [T, C], w [taps, C],
    w[-1] meets the position itself. `cut_at` [T] bool: a position where the
    history before it reads as zeros."""
    import jax.numpy as jnp

    taps, t_len = w.shape[0], x.shape[0]
    padded = jnp.concatenate([jnp.zeros((taps - 1, x.shape[1])), x], axis=0)
    out = 0.0
    for back in range(taps):  # the position `back` before
        term = padded[taps - 1 - back : taps - 1 - back + t_len] * w[taps - 1 - back]
        if cut_at is not None and back:
            # Gone where a cut lies at the position or up to back - 1 before.
            since = sum(
                jnp.concatenate([jnp.zeros((j,), bool), cut_at[: t_len - j]])
                for j in range(back)
            )
            term = jnp.where((since > 0)[:, None], 0.0, term)
        out = out + term
    return out


def _linear(cfg, p, x, int8, state_dtype, beta_factor, cut_at):
    import jax
    import jax.numpy as jnp

    t_len = x.shape[0]
    heads, dk, dv = cfg.linear_num_value_heads, cfg.linear_key_head_dim, cfg.linear_value_head_dim
    key_dim = heads * dk
    w = p["conv_w"]
    q = _silu(_conv(_dense(x, p["q"], int8), w[:, :key_dim], cut_at))
    k = _silu(_conv(_dense(x, p["k"], int8), w[:, key_dim : 2 * key_dim], cut_at))
    v = _silu(_conv(_dense(x, p["v"], int8), w[:, 2 * key_dim :], cut_at))
    q = _l2_norm(q.reshape(t_len, heads, dk)) * dk ** -0.5
    k = _l2_norm(k.reshape(t_len, heads, dk))
    v = v.reshape(t_len, heads, dv)
    beta = beta_factor / (1.0 + jnp.exp(-_dense(x, p["b"], int8)))
    g = -jnp.exp(p["A_log"]) * jnp.logaddexp(_dense(x, p["a"], int8) + p["dt_bias"], 0.0)

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
    gate = _silu(_dense(x, p["g"], int8)).reshape(t_len, heads, dv)
    y = _rms_norm(o, p["norm"], cfg.rms_norm_eps) * gate
    return _dense(y.reshape(t_len, heads * dv), p["o"], int8)


def _attention(cfg, p, x, int8, qk_norm):
    import jax
    import jax.numpy as jnp

    t_len = x.shape[0]
    hq, hkv = cfg.num_attention_heads, cfg.num_key_value_heads
    d = cfg.hidden_size // hq
    q, k = _dense(x, p["q"], int8), _dense(x, p["k"], int8)
    if qk_norm:
        q = _rms_norm(q, p["norm_q"], cfg.rms_norm_eps)
        k = _rms_norm(k, p["norm_k"], cfg.rms_norm_eps)
    q = q.reshape(t_len, hq, d)
    k = jnp.repeat(k.reshape(t_len, hkv, d), hq // hkv, axis=1)
    v = jnp.repeat(_dense(x, p["v"], int8).reshape(t_len, hkv, d), hq // hkv, axis=1)
    causal = jnp.tril(jnp.ones((t_len, t_len), bool))

    # A head at a time: the [heads, T, T] scores of 3,072 positions would
    # take 1.1 GB.
    def one(head):
        q_h, k_h, v_h = head
        scores = jnp.where(causal, (q_h @ k_h.T) * d ** -0.5, -jnp.inf)
        return jax.nn.softmax(scores, axis=-1) @ v_h

    mixed = jax.lax.map(one, tuple(t.transpose(1, 0, 2) for t in (q, k, v)))
    return _dense(mixed.transpose(1, 0, 2).reshape(t_len, hq * d), p["o"], int8)


def layer(cfg, kind, p, h, cut_at=None, int8=False, state_dtype=None,
          beta_factor=2.0, qk_norm=True):
    """One layer on the residual rows h [T, D] of one sequence; `p` the
    layer's parameters in float32."""
    import jax.numpy as jnp

    if kind == "linear_attention":
        mixed = _linear(cfg, p["mixer"], h, int8, state_dtype, beta_factor, cut_at)
    else:
        mixed = _attention(cfg, p["mixer"], h, int8, qk_norm)
    h = h + _rms_norm(mixed, p["norm1"], cfg.rms_norm_eps)
    g, u = jnp.split(_dense(h, p["mlp_in"], int8), 2, axis=-1)
    out = _dense(_silu(g) * u, p["mlp_out"], int8)
    return h + _rms_norm(out, p["norm2"], cfg.rms_norm_eps)


def sizes(fields: dict):
    """The configuration file's `model` section as the object the
    functions here read sizes off."""
    fields = dict(fields)
    fields["layer_types"] = tuple(fields["layer_types"])
    return types.SimpleNamespace(**fields)


class OlmoHybridServingReference:
    """The reference over one parameter tree, a layer at a time, at padded
    lengths that are multiples of `pad_to` (one compilation each)."""

    def __init__(self, cfg, params, pad_to: int = 1024):
        import jax
        import jax.numpy as jnp

        self.cfg = cfg
        self.pad_to = pad_to
        self._params = params

        def f32(tree):
            return jax.tree_util.tree_map(lambda x: x.astype(jnp.float32), tree)

        def run_layer(kind, variant):
            options = dict(variant)
            cut = options.pop("conv_cut", None) is not None
            if "state_dtype" in options:
                options["state_dtype"] = getattr(jnp, options["state_dtype"])

            def run(p, h, cut_at):
                with jax.default_matmul_precision("highest"):
                    return layer(cfg, kind, f32(p), h, cut_at if cut else None, **options)
            return jax.jit(run)

        self._layers = {}
        self._run_layer = run_layer

        def embed(wte, tokens, int8):
            wte = wte.astype(jnp.float32)
            if int8:
                wte = _int8(wte, -1)
            return wte[tokens]

        def head(weight, norm, h, int8):
            with jax.default_matmul_precision("highest"):
                x = _rms_norm(h, norm.astype(jnp.float32), cfg.rms_norm_eps)
                return _dense(x, weight.astype(jnp.float32), int8)

        self._embed = jax.jit(embed, static_argnums=2)
        self._head = jax.jit(head, static_argnums=3)

    def logits(self, tokens, rows: slice, prompt_len: int = 0, **variant):
        """Reference logits [rows, vocab] of the sequence `tokens`, under a
        variant of `CONTROLS` where given (`conv_cut` cuts at its multiples
        inside the first `prompt_len` positions and at `prompt_len`)."""
        import numpy as np

        padded = np.zeros((-(-len(tokens) // self.pad_to) * self.pad_to,), np.int32)
        padded[: len(tokens)] = tokens
        cut_at = np.zeros(padded.shape, bool)
        if variant.get("conv_cut"):
            cut_at[variant["conv_cut"] : prompt_len : variant["conv_cut"]] = True
            cut_at[prompt_len : prompt_len + 1] = True
        int8 = bool(variant.get("int8"))
        params = self._params
        h = self._embed(params["wte"], padded, int8)
        for kind, p in zip(self.cfg.layer_types, params["layers"]):
            key = (kind, tuple(sorted(variant.items())))
            if key not in self._layers:
                self._layers[key] = self._run_layer(kind, variant)
            h = self._layers[key](p, h, cut_at)
        # The head only at the rows asked for, padded to one shape.
        index = np.arange(len(tokens))[rows]
        wanted = np.zeros((-(-len(index) // self.pad_to) * self.pad_to,), np.int32)
        wanted[: len(index)] = index
        out = self._head(params["lm_head"], params["norm_f"], h[wanted], int8)
        return np.asarray(out)[: len(index)]

    def judge(self, prompt, answer, tolerance: float) -> dict:
        """One request's emitted tokens against the reference: how far each
        lies below the reference's best at its position."""
        import numpy as np

        tokens = list(prompt) + list(answer)
        positions = slice(len(prompt) - 1, len(tokens) - 1)
        rows = self.logits(tokens[:-1], positions)
        if not np.isfinite(rows).all():
            return {"ok": False, "why": "reference logits not finite"}
        answer = np.asarray(answer)
        gaps = rows.max(axis=-1) - rows[np.arange(len(answer)), answer]
        return {
            "ok": bool((gaps < tolerance).all()),
            "tokens": int(len(answer)),
            "flipped": int((gaps > 0).sum()),
            "worst_gap": float(gaps.max()),
            "gap_sum": float(gaps.sum()),
            "logit_spread": float(rows.std()),
        }

    def control_gaps(self, prompt, answer, controls=("int8",)) -> dict:
        """Each control's reading on the same prompt and tokens: how far the
        token the changed reference puts first lies below the reference's
        best, position by position."""
        import numpy as np

        tokens = list(prompt) + list(answer)
        positions = slice(len(prompt) - 1, len(tokens) - 1)
        rows = self.logits(tokens[:-1], positions)
        readings = {}
        for control in controls:
            moved = self.logits(tokens[:-1], positions, len(prompt), **CONTROLS[control])
            picks = moved.argmax(axis=-1)
            gaps = rows.max(axis=-1) - rows[np.arange(len(picks)), picks]
            readings[control] = {
                "tokens": int(len(picks)), "flipped": int((gaps > 0).sum()),
                "worst_gap": float(gaps.max()), "gap_sum": float(gaps.sum()),
                "logit_move": float(np.abs(moved - rows).max()),
            }
        return readings
