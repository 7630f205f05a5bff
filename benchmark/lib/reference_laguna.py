"""The plain reference of `laguna` (poolside Laguna) and the comparison
that decides `correct` for its cells.

The benchmark's own copy: it imports nothing of the program. `layer` is the
forward pass written out in `jax.numpy`, float32 under
`jax.default_matmul_precision("highest")`: RMS norms (eps 1e-6), q / k / v
projections without bias, rotary positions from tables computed in float64
from the published formulas (a sliding layer rotates a head's 128
dimensions with base 10,000; a full layer its first 64 with YaRN's
frequencies, base 500,000, factor 128, original length 8,192, beta 32 and 1,
cos and sin times 1.4852...; pairs (i, i + half)), causal attention of query
head j over cached head j // group under full [T, T] masks (on a sliding
layer also i - j < 512), taken a head and a block of queries at a time so
that 14,336 positions fit, the head-wise sigmoid gate, the output
projection; a gated MLP in the leading dense layer, elsewhere routed experts
as a loop over the experts held (softmax over all 256, the top 10, divided
by their sum, times 2.5) plus the shared expert; a final norm and an untied
head. What it takes from the program is the seeded parameter tree, by the
names `ray_tpu/models/laguna.py` gives the leaves, upcast one layer at a
time (bfloat16 to float32 is exact; a float32 copy of the tree does not fit
beside the bfloat16 one). The four pointwise choices the published config
does not spell out are listed in the configuration's `assumed`.

`LagunaServingReference` has the interface of `lib/reference.ServingReference`
(`judge`, `control_gaps`). The control is the same forward with the weights
of every dense layer (a scale an output channel) and each such layer's input
(a scale a token) through symmetric int8, the nearest precision below the
bfloat16 the configuration states; the router stays float32, as the model
keeps it. `window`, `scores_dtype` and `gate_dtype` are variants the
comparison has to notice: a window of another length, attention scores in
bfloat16, the gate in float16.
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


def _gated_mlp(x, w_in, w_out, int8=False):
    import jax.numpy as jnp

    g, u = jnp.split(_dense(x, w_in, int8), 2, axis=-1)
    return _dense(_silu(g) * u, w_out, int8)


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
              query_block=None, int8=False):
    """u [T, D] -> [T, D]: the gated attention of one layer of `kind`."""
    import jax
    import jax.numpy as jnp

    t_len, d = u.shape[0], cfg.head_dim
    hkv = cfg.num_key_value_heads
    hq = p["q"].shape[1] // d
    cos, sin = (jnp.asarray(t) for t in rotary_tables(cfg, kind, t_len))
    q = _rotate(_dense(u, p["q"], int8).reshape(t_len, hq, d), cos, sin)
    k = _rotate(_dense(u, p["k"], int8).reshape(t_len, hkv, d), cos, sin)
    v = _dense(u, p["v"], int8).reshape(t_len, hkv, d)
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
    gate = _rounded(1.0 / (1.0 + jnp.exp(-_dense(u, p["g"], int8))), gate_dtype)  # [T, H]
    mixed = mixed.transpose(1, 0, 2) * gate[:, :, None]
    return _dense(mixed.reshape(t_len, hq * d), p["o"], int8)


def routed_experts(cfg, p, x, int8=False):
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
        return total + gate[:, None] * _gated_mlp(x, w_in, w_out, int8), None

    return jax.lax.scan(
        one, jnp.zeros_like(x), (held, p["experts_in"], p["experts_out"])
    )[0]


def layer(cfg, kind, mlp, p, h, int8=False, **variant):
    """One layer on the residual rows h [T, D] of one sequence; `p` the
    layer's parameters in float32."""
    u = _rms_norm(h, p["norm1"], cfg.rms_norm_eps)
    h = h + attention(cfg, kind, p["mixer"], u, int8=int8, **variant)
    x = _rms_norm(h, p["norm2"], cfg.rms_norm_eps)
    if mlp == "dense":
        return h + _gated_mlp(x, p["mlp_in"], p["mlp_out"], int8)
    return h + routed_experts(cfg, p, x, int8) + _gated_mlp(
        x, p["shared_in"], p["shared_out"], int8
    )


def sizes(fields: dict):
    """The configuration file's `model` section as the object the
    functions here read sizes off."""
    import types

    fields = dict(fields)
    for key in ("experts_held", "layer_types", "mlp_layer_types",
                "num_attention_heads_per_layer"):
        fields[key] = tuple(fields[key])
    return types.SimpleNamespace(**fields)


VARIANTS = {
    # What the comparison has to notice, beside the int8 control.
    "window_plus_16": {"window_delta": 16},
    "bf16_scores": {"scores_dtype": "bfloat16"},
    "f16_gate": {"gate_dtype": "float16"},
}


class LagunaServingReference:
    """The reference over one parameter tree, a layer at a time, at padded
    lengths that are multiples of `pad_to` (one compilation each), queries
    `query_block` at a time."""

    def __init__(self, cfg, params, pad_to: int = 2048, query_block: int = 2048):
        import jax
        import jax.numpy as jnp

        self.cfg = cfg
        self.pad_to = pad_to
        self._params = params

        def f32(tree):
            return jax.tree_util.tree_map(lambda x: x.astype(jnp.float32), tree)

        def run_layer(kind, mlp, int8, variant):
            options = dict(variant)
            if "window_delta" in options:
                options["window"] = cfg.sliding_window + options.pop("window_delta")
            for key in ("scores_dtype", "gate_dtype"):
                if key in options:
                    options[key] = getattr(jnp, options[key])

            def run(p, h):
                with jax.default_matmul_precision("highest"):
                    return layer(cfg, kind, mlp, f32(p), h, int8,
                                 query_block=query_block, **options)
            return jax.jit(run)

        self._layers = {}
        self._run_layer = run_layer

        def embed(wte, tokens, int8):
            wte = wte.astype(jnp.float32)
            if int8:
                wte = _int8(wte, -1)
            return wte[tokens]

        def head(lm_head, norm, h, int8):
            with jax.default_matmul_precision("highest"):
                x = _rms_norm(h, norm.astype(jnp.float32), cfg.rms_norm_eps)
                return _dense(x, lm_head.astype(jnp.float32), int8)

        self._embed = jax.jit(embed, static_argnums=2)
        self._head = jax.jit(head, static_argnums=3)

    def logits(self, tokens, rows: slice, int8: bool = False, variant: str = None):
        """Reference logits [rows, vocab] of the sequence `tokens`."""
        import numpy as np

        padded = np.zeros((-(-len(tokens) // self.pad_to) * self.pad_to,), np.int32)
        padded[: len(tokens)] = tokens
        params = self._params
        options = tuple(sorted(VARIANTS[variant].items())) if variant else ()
        h = self._embed(params["wte"], padded, int8)
        for kind, mlp, p in zip(self.cfg.layer_types, self.cfg.mlp_layer_types,
                                params["layers"]):
            key = (kind, mlp, int8, options)
            if key not in self._layers:
                self._layers[key] = self._run_layer(*key)
            h = self._layers[key](p, h)
        # The head only at the rows asked for, padded to one shape.
        index = np.arange(len(tokens))[rows]
        wanted = np.zeros((-(-len(index) // self.pad_to) * self.pad_to,), np.int32)
        wanted[: len(index)] = index
        out = self._head(params["lm_head"], params["norm_f"], h[wanted], int8)
        return np.asarray(out)[: len(index)]

    def judge(self, prompt, answer, tolerance: float, noise: bool = False) -> dict:
        """One request's emitted tokens against the reference: how far each
        lies below the reference's best at its position. `noise` adds the
        same gaps of the picks of each of VARIANTS."""
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
            "context": int(len(tokens)),
            "flipped": int((gaps > 0).sum()),
            "worst_gap": float(gaps.max()),
            "gap_sum": float(gaps.sum()),
            "logit_spread": float(rows.std()),
        }
        if noise:
            for name in VARIANTS:
                verdict[name] = self._picks_gaps(rows, tokens, positions, variant=name)
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
