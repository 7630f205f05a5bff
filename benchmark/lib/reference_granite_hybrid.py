"""The plain reference of `granitemoehybrid` (IBM Granite 4.0-H) and the
comparison that decides `correct` for its cells.

The benchmark's own copy: it imports nothing of the program. `layer` and
`logits_of` are the forward pass written out in `jax.numpy`, float32 under
`jax.default_matmul_precision("highest")`: RMS norms, the Mamba-2 mixer
(in_proj, a causal depthwise convolution over `mamba_d_conv` positions,
SiLU, the recurrence `S_t = exp(dt_t A) S_{t-1} + dt_t x_t (outer) B_t`,
`y_t = S_t C_t + D x_t` as a plain `lax.scan` over positions, the gated RMS
norm, out_proj), grouped-query attention without positions scaled by
`attention_multiplier`, routed experts as a loop over the experts held
(gates a softmax over the `num_experts_per_tok` chosen logits, not
renormalised over the held ones) plus the shared expert, Granite's four
multipliers, a head tied to the embedding. What it takes from the program
is the seeded parameter tree, by the names `ray_tpu/models/granite_hybrid.py`
gives the leaves, upcast one layer at a time (bfloat16 to float32 is exact;
a float32 copy of the tree does not fit beside the bfloat16 one).

`HybridServingReference` has the interface of `lib/reference.ServingReference`
(`judge`, `control_gaps`). The control is the same forward with the weights
of every dense layer (a scale an output channel) and each such layer's input
(a scale a token) through symmetric int8, the nearest precision below the
bfloat16 the configuration states; the router stays float32, as the model
keeps it. `state_dtype` rounds the recurrent state after every position:
what a state kept one precision down would read.
"""

from __future__ import annotations

import types


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


def _silu(x):
    import jax.numpy as jnp

    return x / (1.0 + jnp.exp(-x))


def _gated_mlp(x, w_in, w_out, int8):
    import jax.numpy as jnp

    g, u = jnp.split(_dense(x, w_in, int8), 2, axis=-1)
    return _dense(_silu(g) * u, w_out, int8)


def _mamba(cfg, p, u, int8, state_dtype):
    import jax
    import jax.numpy as jnp

    t_len = u.shape[0]
    heads, p_dim, n = cfg.mamba_n_heads, cfg.mamba_d_head, cfg.mamba_d_state
    d_inner = heads * p_dim
    conv_dim = d_inner + 2 * n
    zxbcdt = _dense(u, p["in_proj"], int8)
    z, xbc, dt = jnp.split(zxbcdt, [d_inner, d_inner + conv_dim], axis=-1)
    taps = cfg.mamba_d_conv
    padded = jnp.concatenate([jnp.zeros((taps - 1, conv_dim)), xbc], axis=0)
    conv = sum(padded[i : i + t_len] * p["conv_w"][i] for i in range(taps))
    xbc = _silu(conv + p["conv_b"])
    x = xbc[:, :d_inner].reshape(t_len, heads, p_dim)
    b, c = xbc[:, d_inner : d_inner + n], xbc[:, d_inner + n :]
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
        return s, jnp.sum(s * c_t[None, None, :], axis=-1)

    _, y = jax.lax.scan(step, jnp.zeros((heads, p_dim, n)), (x, b, c, dt))
    y = (y + p["D"][None, :, None] * x).reshape(t_len, d_inner) * _silu(z)
    return _dense(_rms_norm(y, p["norm"], cfg.rms_norm_eps), p["out_proj"], int8)


def _attention(cfg, p, u, int8):
    import jax
    import jax.numpy as jnp

    t_len = u.shape[0]
    hq, hkv = cfg.num_attention_heads, cfg.num_key_value_heads
    d = cfg.hidden_size // hq
    q = _dense(u, p["q"], int8).reshape(t_len, hq, d)
    k = jnp.repeat(_dense(u, p["k"], int8).reshape(t_len, hkv, d), hq // hkv, axis=1)
    v = jnp.repeat(_dense(u, p["v"], int8).reshape(t_len, hkv, d), hq // hkv, axis=1)
    causal = jnp.tril(jnp.ones((t_len, t_len), bool))
    # A head at a time: the [heads, T, T] scores of 6,400 positions would
    # take 5 GB.
    def one(head):
        q_h, k_h, v_h = head
        scores = jnp.where(causal, (q_h @ k_h.T) * cfg.attention_multiplier, -jnp.inf)
        return jax.nn.softmax(scores, axis=-1) @ v_h

    mixed = jax.lax.map(one, tuple(t.transpose(1, 0, 2) for t in (q, k, v)))
    return _dense(mixed.transpose(1, 0, 2).reshape(t_len, hq * d), p["o"], int8)


def _routed(cfg, p, x, int8):
    import jax
    import jax.numpy as jnp

    top, ids = jax.lax.top_k(x @ p["router"], cfg.num_experts_per_tok)
    gates = jax.nn.softmax(top, axis=-1)
    held = jnp.asarray(cfg.experts_held, jnp.int32)

    def one(total, expert):
        number, w_in, w_out = expert
        gate = jnp.sum(jnp.where(ids == number, gates, 0.0), axis=-1)
        return total + gate[:, None] * _gated_mlp(x, w_in, w_out, int8), None

    return jax.lax.scan(
        one, jnp.zeros_like(x), (held, p["experts_in"], p["experts_out"])
    )[0]


def layer(cfg, kind, p, h, int8=False, state_dtype=None):
    """One layer on the residual rows h [T, D] of one sequence; `p` the
    layer's parameters in float32."""
    u = _rms_norm(h, p["norm1"], cfg.rms_norm_eps)
    if kind == "mamba":
        mixed = _mamba(cfg, p["mixer"], u, int8, state_dtype)
    else:
        mixed = _attention(cfg, p["mixer"], u, int8)
    h = h + cfg.residual_multiplier * mixed
    x = _rms_norm(h, p["norm2"], cfg.rms_norm_eps)
    out = _routed(cfg, p, x, int8) + _gated_mlp(x, p["shared_in"], p["shared_out"], int8)
    return h + cfg.residual_multiplier * out


def sizes(fields: dict):
    """The configuration file's `model` section as the object the
    functions here read sizes off."""
    fields = dict(fields)
    fields["experts_held"] = tuple(fields["experts_held"])
    fields["layer_types"] = tuple(fields["layer_types"])
    return types.SimpleNamespace(**fields)


class HybridServingReference:
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

        def run_layer(kind, int8, state_dtype):
            def run(p, h):
                with jax.default_matmul_precision("highest"):
                    return layer(cfg, kind, f32(p), h, int8, state_dtype)
            return jax.jit(run)

        self._layers = {}
        self._run_layer = run_layer

        def embed(wte, tokens, int8):
            wte = wte.astype(jnp.float32)
            if int8:
                wte = _int8(wte, -1)
            return wte[tokens] * cfg.embedding_multiplier

        def head(wte, norm, h, int8):
            with jax.default_matmul_precision("highest"):
                wte = wte.astype(jnp.float32)
                x = _rms_norm(h, norm.astype(jnp.float32), cfg.rms_norm_eps)
                if int8:
                    x, wte = _int8(x, -1), _int8(wte, -1)
                return (x @ wte.T) / cfg.logits_scaling

        self._embed = jax.jit(embed, static_argnums=2)
        self._head = jax.jit(head, static_argnums=3)

    def logits(self, tokens, rows: slice, int8: bool = False, state_dtype=None):
        """Reference logits [rows, vocab] of the sequence `tokens`."""
        import numpy as np

        padded = np.zeros((-(-len(tokens) // self.pad_to) * self.pad_to,), np.int32)
        padded[: len(tokens)] = tokens
        params = self._params
        h = self._embed(params["wte"], padded, int8)
        for kind, p in zip(self.cfg.layer_types, params["layers"]):
            key = (kind, int8, state_dtype)
            if key not in self._layers:
                self._layers[key] = self._run_layer(*key)
            h = self._layers[key](p, h)
        # The head only at the rows asked for, padded to one shape.
        index = np.arange(len(tokens))[rows]
        wanted = np.zeros((-(-len(index) // self.pad_to) * self.pad_to,), np.int32)
        wanted[: len(index)] = index
        out = self._head(params["wte"], params["norm_f"], h[wanted], int8)
        return np.asarray(out)[: len(index)]

    def judge(self, prompt, answer, tolerance: float, noise: bool = False) -> dict:
        """One request's emitted tokens against the reference: how far each
        lies below the reference's best at its position. `noise` adds the
        same gaps of the reference with its recurrent state rounded to
        bfloat16 after every position."""
        import jax.numpy as jnp
        import numpy as np

        tokens = list(prompt) + list(answer)
        positions = slice(len(prompt) - 1, len(tokens) - 1)
        rows = self.logits(tokens[:-1], positions)
        if not np.isfinite(rows).all():
            return {"ok": False, "why": "reference logits not finite"}
        answer = np.asarray(answer)
        gaps = rows.max(axis=-1) - rows[np.arange(len(answer)), answer]
        verdict = {
            "ok": bool((gaps < tolerance).all()),
            "tokens": int(len(answer)),
            "flipped": int((gaps > 0).sum()),
            "worst_gap": float(gaps.max()),
            "gap_sum": float(gaps.sum()),
            "logit_spread": float(rows.std()),
        }
        if noise:
            verdict["bf16_state"] = self._picks_gaps(
                rows, tokens, positions, state_dtype=jnp.bfloat16
            )
        return verdict

    def _picks_gaps(self, rows, tokens, positions, **variant) -> dict:
        """How far the token a variant of the reference puts first lies
        below the reference's best, position by position."""
        import numpy as np

        moved = self.logits(tokens[:-1], positions, **variant)
        picks = moved.argmax(axis=-1)
        gaps = rows.max(axis=-1) - rows[np.arange(len(picks)), picks]
        return {"tokens": int(len(picks)), "flipped": int((gaps > 0).sum()),
                "worst_gap": float(gaps.max()), "gap_sum": float(gaps.sum()),
                "logit_move": float(np.abs(moved - rows).max())}

    def control_gaps(self, prompt, answer) -> dict:
        """The int8 control's reading on the same prompt and tokens."""
        tokens = list(prompt) + list(answer)
        positions = slice(len(prompt) - 1, len(tokens) - 1)
        rows = self.logits(tokens[:-1], positions)
        return self._picks_gaps(rows, tokens, positions, int8=True)
