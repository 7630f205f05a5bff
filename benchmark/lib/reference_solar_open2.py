"""The plain reference of `solar_open2` (Upstage Solar-Open2-250B) and the
comparison that decides `correct` for its cells.

The benchmark's own copy: it imports nothing of the program. `layer` is the
forward pass written out in `jax.numpy`, float32 under
`jax.default_matmul_precision("highest")`: pre-norm blocks (`h + mixer(norm
h)`, `h + moe(norm h)`); the KDA mixer (q, k, v projections, a causal
depthwise convolution over four positions and SiLU on each, L2-normalised q
and k a head, `beta = 2 sigmoid`, `g = -exp(A_log) softplus((u Fa) Fb +
dt_bias)` a key CHANNEL, the recurrence `S <- diag(exp(g)) S; d = beta (v -
S^T k); S <- S + k (outer) d; o = S^T q` as a plain `lax.scan` over
positions, the RMS norm a head times `sigmoid((u Ga) Gb + gb)`, the output
projection); grouped-query attention with no positions and no QK-norm, a
head and `query_block` queries at a time, its output times `sigmoid(u Wg)`
element by element; the expert MLP (sigmoid scores, the 8 largest of score +
selection bias, their scores divided by their sum, the held experts' part of
the sum as a loop over the experts held, a shared expert with weight 1); an
untied head. What it takes from the program is the seeded parameter tree, by
the names `ray_tpu/models/solar_open2.py` gives the leaves, upcast one layer
at a time (bfloat16 to float32 is exact; a float32 copy of the tree does not
fit beside the bfloat16 one).

`SolarOpen2ServingReference` has the interface of
`lib/reference.ServingReference` (`judge`, `control_gaps`). The controls are
the same forward with one thing changed (`CONTROLS`). Those the comparison
is held to notice (`MUST_FAIL`):

  int8               the weights of every dense layer (a scale an output
                     channel) and each such layer's input (a scale a token)
                     through symmetric int8, the nearest precision below the
                     bfloat16 the configuration states; the router stays
                     float32
  scalar_decay       the decay averaged over a head's channels: the scalar
                     rule of `olmo_hybrid` under this model's name
  beta_without_2     `beta = sigmoid`, without `kda_allow_neg_eigval`'s factor
  no_selection_bias  the 8 largest scores chosen, the bias left out
  softmax_router     a softmax over all 320 in place of the sigmoid
  no_attention_gate  `use_gqa_gate` left out
  no_shared_expert   the shared expert left out
  conv_tail_cut      the convolution sees zeros for the positions before every
                     2,048th of the prompt and before the first decoded token:
                     a tail dropped where a chunk or the decode takes over

and reported, seen or not:

  bf16_state         the recurrent state rounded to bfloat16 after every position
"""

from __future__ import annotations

import types

KDA, GQA = "kda", "gqa"
CONTROLS = {
    "int8": {"int8": True},
    "scalar_decay": {"scalar_decay": True},
    "beta_without_2": {"beta_factor": 1.0},
    "no_selection_bias": {"selection_bias": False},
    "softmax_router": {"router_score": "softmax"},
    "no_attention_gate": {"attention_gate": False},
    "no_shared_expert": {"shared_expert": False},
    "conv_tail_cut": {"conv_cut": 2048},
    "bf16_state": {"state_dtype": "bfloat16"},
}
MUST_FAIL = tuple(name for name in CONTROLS if name != "bf16_state")
_KDA_OPTIONS = ("beta_factor", "scalar_decay", "state_dtype")
_MOE_OPTIONS = ("selection_bias", "router_score", "shared_expert")


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


def _sigmoid(x):
    import jax.numpy as jnp

    return 1.0 / (1.0 + jnp.exp(-x))


def _gated_mlp(x, w_in, w_out, int8=False):
    import jax.numpy as jnp

    g, u = jnp.split(_dense(x, w_in, int8), 2, axis=-1)
    return _dense(_silu(g) * u, w_out, int8)


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


def _kda(cfg, p, u, int8, cut_at, beta_factor=2.0, scalar_decay=False, state_dtype=None):
    import jax
    import jax.numpy as jnp

    t_len = u.shape[0]
    heads, dk = cfg.kda_num_heads, cfg.kda_head_dim
    width = heads * dk
    w = p["conv_w"]
    q = _silu(_conv(_dense(u, p["q"], int8), w[:, :width], cut_at))
    k = _silu(_conv(_dense(u, p["k"], int8), w[:, width : 2 * width], cut_at))
    v = _silu(_conv(_dense(u, p["v"], int8), w[:, 2 * width :], cut_at))
    q = _l2_norm(q.reshape(t_len, heads, dk)) * dk ** -0.5
    k = _l2_norm(k.reshape(t_len, heads, dk))
    v = v.reshape(t_len, heads, dk)
    beta = beta_factor * _sigmoid(_dense(u, p["b"], int8))
    dt = jnp.logaddexp(
        _dense(_dense(u, p["fa"], int8), p["fb"], int8) + p["dt_bias"], 0.0
    )
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
    gate = _sigmoid(
        _dense(_dense(u, p["ga"], int8), p["gb"], int8) + p["g_bias"]
    ).reshape(t_len, heads, dk)
    y = _rms_norm(o, p["norm"], cfg.rms_norm_eps) * gate
    return _dense(y.reshape(t_len, width), p["o"], int8)


def _attention(cfg, p, u, int8, query_block, attention_gate=True):
    import jax
    import jax.numpy as jnp

    t_len, d = u.shape[0], cfg.head_dim
    hq, hkv = cfg.num_attention_heads, cfg.num_key_value_heads
    q = _dense(u, p["q"], int8).reshape(t_len, hq, d)
    k = jnp.repeat(_dense(u, p["k"], int8).reshape(t_len, hkv, d), hq // hkv, axis=1)
    v = jnp.repeat(_dense(u, p["v"], int8).reshape(t_len, hkv, d), hq // hkv, axis=1)
    block = t_len if query_block is None else min(query_block, t_len)
    cols = jnp.arange(t_len)[None, :]

    # A head and a block of queries at a time: the [heads, T, T] scores of
    # 17,408 positions would take 78 GB.
    def one_head(head):
        q_h, k_h, v_h = head

        def one_block(rows_and_q):
            rows, q_b = rows_and_q
            scores = jnp.where(cols <= rows[:, None], (q_b @ k_h.T) * d ** -0.5, -jnp.inf)
            return jax.nn.softmax(scores, axis=-1) @ v_h

        pad = -t_len % block
        rows = jnp.arange(t_len + pad).reshape(-1, block)
        q_blocks = jnp.pad(q_h, ((0, pad), (0, 0))).reshape(-1, block, d)
        # A padded query row sees every key: finite, and cut below.
        return jax.lax.map(one_block, (rows, q_blocks)).reshape(-1, d)[:t_len]

    mixed = jax.lax.map(one_head, tuple(t.transpose(1, 0, 2) for t in (q, k, v)))
    mixed = mixed.transpose(1, 0, 2).reshape(t_len, hq * d)
    if attention_gate:
        mixed = mixed * _sigmoid(_dense(u, p["g"], int8))
    return _dense(mixed, p["o"], int8)


def _moe(cfg, p, x, int8, selection_bias=True, router_score="sigmoid",
         shared_expert=True):
    """The held experts' part of the routed sum for x [T, D], and the shared
    expert."""
    import jax
    import jax.numpy as jnp

    logits = x @ p["router"]
    score = _sigmoid(logits) if router_score == "sigmoid" else jax.nn.softmax(logits, axis=-1)
    chooser = score + p["router_bias"] if selection_bias else score
    _, ids = jax.lax.top_k(chooser, cfg.num_experts_per_tok)
    top = jnp.take_along_axis(score, ids, axis=-1)
    weights = top / jnp.sum(top, axis=-1, keepdims=True) * cfg.routed_scaling_factor
    held = jnp.asarray(cfg.experts_held, jnp.int32)

    def one(total, expert):
        number, w_in, w_out = expert
        weight = jnp.sum(jnp.where(ids == number, weights, 0.0), axis=-1)
        return total + weight[:, None] * _gated_mlp(x, w_in, w_out, int8), None

    out = jax.lax.scan(
        one, jnp.zeros_like(x), (held, p["experts_in"], p["experts_out"])
    )[0]
    if shared_expert:
        out = out + _gated_mlp(x, p["shared_in"], p["shared_out"], int8)
    return out


def layer(cfg, kind, p, h, cut_at=None, int8=False, query_block=None, **variant):
    """One layer on the residual rows h [T, D] of one sequence; `p` the
    layer's parameters in float32."""
    u = _rms_norm(h, p["norm1"], cfg.rms_norm_eps)
    if kind == KDA:
        options = {k: v for k, v in variant.items() if k in _KDA_OPTIONS}
        h = h + _kda(cfg, p["mixer"], u, int8, cut_at, **options)
    else:
        h = h + _attention(
            cfg, p["mixer"], u, int8, query_block,
            attention_gate=variant.get("attention_gate", True),
        )
    x = _rms_norm(h, p["norm2"], cfg.rms_norm_eps)
    options = {k: v for k, v in variant.items() if k in _MOE_OPTIONS}
    return h + _moe(cfg, p, x, int8, **options)


def sizes(fields: dict):
    """The configuration file's `model` section as the object the
    functions here read sizes off."""
    fields = dict(fields)
    fields["experts_held"] = tuple(fields["experts_held"])
    fields["layer_types"] = tuple(
        GQA if i in fields["gqa_layers"] else KDA
        for i in range(fields["num_hidden_layers"])
    )
    return types.SimpleNamespace(**fields)


class SolarOpen2ServingReference:
    """The reference over one parameter tree, a layer at a time, at padded
    lengths that are multiples of `pad_to` (one compilation each), attention
    `query_block` queries at a time."""

    def __init__(self, cfg, params, pad_to: int = 2048, query_block: int = 2048):
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
                    return layer(cfg, kind, f32(p), h, cut_at if cut else None,
                                 query_block=query_block, **options)
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
