"""The plain reference of `falcon_h1` (TII Falcon-H1) and the comparison that
decides `correct` for its cells.

The benchmark's own copy: it imports nothing of the program. `layer` is the
forward pass of one layer written out in `jax.numpy`, float32 under
`jax.default_matmul_precision("highest")`: on the same normed input a
Mamba-2 mixer (in_proj with `ssm_in_multiplier` on its input and
`ssm_multipliers` on its five segments z, x, B, C, dt; a causal depthwise
convolution over four positions with its bias and SiLU on x, B and C; `dt =
softplus(. + dt_bias)`, `A = -exp(A_log)`; the recurrence `S <- exp(dt A) S +
dt x (outer) B_g; y = S C_g + D x` as a plain `lax.scan` over positions,
head j reading group j // (heads / groups); the gate `y * silu(z)` and then
an RMS norm over each group's channels; out_proj times `ssm_out_multiplier`)
and grouped-query attention (`attention_in_multiplier`, `key_multiplier` on
the keys, rotary positions over the whole head, dense and causal, the output
projection times `attention_out_multiplier`), their outputs added to the
stream; then the gated MLP with `mlp_multipliers`; `embedding_multiplier` and
`lm_head_multiplier` at the ends. What it takes from the program is the
seeded parameter tree, by the names `ray_tpu/models/falcon_h1.py` gives the
leaves, upcast one layer at a time (bfloat16 to float32 is exact; a float32
copy of the tree does not fit beside the bfloat16 one), the embedding's rows
after the gather and the head a slab of the vocabulary at a time (either
matrix is 5.3 GB in float32).

`FalconH1ServingReference` has the interface of
`lib/reference.ServingReference` (`judge`, `control_gaps`). The controls are
the same forward with one thing changed (`CONTROLS`). Those the comparison
is held to notice (`MUST_FAIL`):

  int8              the weights of every dense layer (a scale an output
                    channel) and each such layer's input (a scale a token)
                    through symmetric int8, the nearest precision below the
                    bfloat16 the configuration states
  no_attention      the attention branch left out of every layer
  no_mamba          the Mamba-2 branch left out of every layer
  one_group         every head reads group 0's B and C (heads 16-31 the
                    wrong group's)
  no_key_multiplier `key_multiplier` left out
  conv_tail_cut     the convolution sees zeros for the positions before every
                    256th of the prompt and before the first decoded token:
                    a tail dropped where a chunk or the decode takes over

and those that are reported, seen or not:

  bf16_state        the recurrent state rounded to bfloat16 after every position
  norm_over_all     the gated norm over all 4,096 channels, not a group's 2,048
  no_mup            `ssm_multipliers` left out (ones)
"""

from __future__ import annotations

import types

from lib.reference_olmo_hybrid import _dense, _int8, _rms_norm, _silu

CONTROLS = {
    "int8": {"int8": True},
    "no_attention": {"attention": False},
    "no_mamba": {"mamba": False},
    "one_group": {"shared_group": True},
    "no_key_multiplier": {"key_multiplier": False},
    "conv_tail_cut": {"conv_cut": 256},
    "bf16_state": {"state_dtype": "bfloat16"},
    "norm_over_all": {"norm_groups": 1},
    "no_mup": {"mup": False},
}
MUST_FAIL = (
    "int8", "no_attention", "no_mamba", "one_group", "no_key_multiplier",
    "conv_tail_cut",
)
HEAD_SLABS = 8  # the vocabulary in so many runs of columns, a product each


def _conv(x, w, bias, cut_at=None):
    """Depthwise and causal over w.shape[0] positions: x [T, C], w [taps, C],
    w[-1] meets the position itself. `cut_at` [T] bool: a position where the
    history before it reads as zeros."""
    import jax.numpy as jnp

    taps, t_len = w.shape[0], x.shape[0]
    padded = jnp.concatenate([jnp.zeros((taps - 1, x.shape[1])), x], axis=0)
    out = bias
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


def _mamba(cfg, p, u, int8, state_dtype, shared_group, norm_groups, mup, cut_at):
    import jax
    import jax.numpy as jnp

    t_len = u.shape[0]
    heads, p_dim, n = cfg.mamba_n_heads, cfg.mamba_d_head, cfg.mamba_d_state
    groups, d_ssm = cfg.mamba_n_groups, cfg.mamba_d_ssm
    widths = (d_ssm, d_ssm, groups * n, groups * n, heads)
    zxbcdt = _dense(cfg.ssm_in_multiplier * u, p["in_proj"], int8)
    if mup:
        zxbcdt = zxbcdt * jnp.concatenate([
            jnp.full((width,), m) for width, m in zip(widths, cfg.ssm_multipliers)
        ])
    conv_dim = d_ssm + 2 * groups * n
    z = zxbcdt[:, :d_ssm]
    xbc = _silu(_conv(zxbcdt[:, d_ssm : d_ssm + conv_dim], p["conv_w"], p["conv_b"], cut_at))
    dt = jnp.logaddexp(zxbcdt[:, d_ssm + conv_dim :] + p["dt_bias"], 0.0)  # softplus
    x = xbc[:, :d_ssm].reshape(t_len, heads, p_dim)
    b = xbc[:, d_ssm : d_ssm + groups * n].reshape(t_len, groups, n)
    c = xbc[:, d_ssm + groups * n :].reshape(t_len, groups, n)
    group_of = jnp.arange(heads) // (heads // groups)
    if shared_group:
        group_of = jnp.zeros_like(group_of)
    b, c = b[:, group_of], c[:, group_of]  # [T, H, N]
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
    y = (y + p["D"][None, :, None] * x).reshape(t_len, d_ssm) * _silu(z)
    by = groups if norm_groups is None else norm_groups
    y = y.reshape(t_len, by, d_ssm // by)
    y = y / jnp.sqrt(jnp.mean(y * y, axis=-1, keepdims=True) + cfg.rms_norm_eps)
    y = y.reshape(t_len, d_ssm) * p["norm"]
    return cfg.ssm_out_multiplier * _dense(y, p["out_proj"], int8)


def _rope(x, theta):
    """x [T, H, d] rotated in the pairs (i, i + d / 2) by its position."""
    import jax.numpy as jnp

    t_len, _, d = x.shape
    inverse = float(theta) ** (-jnp.arange(0, d, 2) / d)
    angles = jnp.arange(t_len)[:, None] * inverse[None, :]
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2 :]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _attention(cfg, p, u, int8, key_multiplier):
    import jax
    import jax.numpy as jnp

    t_len = u.shape[0]
    hq, hkv, d = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    ua = cfg.attention_in_multiplier * u
    k = _dense(ua, p["k"], int8)
    if key_multiplier:
        k = cfg.key_multiplier * k
    q = _rope(_dense(ua, p["q"], int8).reshape(t_len, hq, d), cfg.rope_theta)
    k = jnp.repeat(_rope(k.reshape(t_len, hkv, d), cfg.rope_theta), hq // hkv, axis=1)
    v = jnp.repeat(_dense(ua, p["v"], int8).reshape(t_len, hkv, d), hq // hkv, axis=1)
    causal = jnp.tril(jnp.ones((t_len, t_len), bool))

    # A head at a time: [heads, T, T] scores of 3,072 positions are 755 MB.
    def one(head):
        q_h, k_h, v_h = head
        scores = jnp.where(causal, (q_h @ k_h.T) * d ** -0.5, -jnp.inf)
        return jax.nn.softmax(scores, axis=-1) @ v_h

    mixed = jax.lax.map(one, tuple(t.transpose(1, 0, 2) for t in (q, k, v)))
    mixed = mixed.transpose(1, 0, 2).reshape(t_len, hq * d)
    return cfg.attention_out_multiplier * _dense(mixed, p["o"], int8)


def layer(cfg, p, h, cut_at=None, int8=False, attention=True, mamba=True,
          shared_group=False, key_multiplier=True, state_dtype=None,
          norm_groups=None, mup=True):
    """One layer on the residual rows h [T, D] of one sequence; `p` the
    layer's parameters in float32."""
    import jax.numpy as jnp

    u = _rms_norm(h, p["norm1"], cfg.rms_norm_eps)
    mixed = 0.0
    if mamba:
        mixed = mixed + _mamba(
            cfg, p["mamba"], u, int8, state_dtype, shared_group, norm_groups, mup, cut_at
        )
    if attention:
        mixed = mixed + _attention(cfg, p["full_attention"], u, int8, key_multiplier)
    h = h + mixed
    f = _rms_norm(h, p["norm2"], cfg.rms_norm_eps)
    g, up = jnp.split(_dense(f, p["mlp_in"], int8), 2, axis=-1)
    gate_m, out_m = cfg.mlp_multipliers
    return h + out_m * _dense(_silu(gate_m * g) * up, p["mlp_out"], int8)


def sizes(fields: dict):
    """The configuration file's `model` section as the object the
    functions here read sizes off."""
    return types.SimpleNamespace(**fields)


class FalconH1ServingReference:
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

        def run_layer(variant):
            options = dict(variant)
            cut = options.pop("conv_cut", None) is not None
            if "state_dtype" in options:
                options["state_dtype"] = getattr(jnp, options["state_dtype"])

            def run(p, h, cut_at):
                with jax.default_matmul_precision("highest"):
                    return layer(cfg, f32(p), h, cut_at if cut else None, **options)
            return jax.jit(run)

        self._layers = {}
        self._run_layer = run_layer

        def embed(wte, tokens, int8):
            # A row's int8 scale is the row's own: the gathered rows' is the
            # whole table's.
            rows = wte[tokens].astype(jnp.float32)
            return cfg.embedding_multiplier * (_int8(rows, -1) if int8 else rows)

        def normed(norm, h):
            return _rms_norm(h, norm.astype(jnp.float32), cfg.rms_norm_eps)

        def head_slab(weight, x, int8):
            with jax.default_matmul_precision("highest"):
                return cfg.lm_head_multiplier * _dense(x, weight.astype(jnp.float32), int8)

        self._embed = jax.jit(embed, static_argnums=2)
        self._normed = jax.jit(normed)
        self._head_slab = jax.jit(head_slab, static_argnums=2)

    def _head(self, h, int8: bool):
        """Logits of the rows h, the vocabulary a slab at a time."""
        import numpy as np

        params = self._params
        x = self._normed(params["norm_f"], h)
        vocab = params["lm_head"].shape[1]
        slabs = HEAD_SLABS if vocab % HEAD_SLABS == 0 else 1
        width = vocab // slabs
        return np.concatenate([
            np.asarray(self._head_slab(
                params["lm_head"][:, i * width : (i + 1) * width], x, int8
            ))
            for i in range(slabs)
        ], axis=-1)

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
        key = tuple(sorted(variant.items()))
        if key not in self._layers:
            self._layers[key] = self._run_layer(variant)
        for p in params["layers"]:
            h = self._layers[key](p, h, cut_at)
        # The head only at the rows asked for, padded to one shape.
        index = np.arange(len(tokens))[rows]
        pad = min(self.pad_to, 256)
        wanted = np.zeros((-(-len(index) // pad) * pad,), np.int32)
        wanted[: len(index)] = index
        return self._head(h[wanted], int8)[: len(index)]

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
        best = rows.max(axis=-1)
        readings = {}
        for control in controls:
            moved = self.logits(tokens[:-1], positions, len(prompt), **CONTROLS[control])
            picks = moved.argmax(axis=-1)
            gaps = best - rows[np.arange(len(picks)), picks]
            readings[control] = {
                "tokens": int(len(picks)), "flipped": int((gaps > 0).sum()),
                "worst_gap": float(gaps.max()), "gap_sum": float(gaps.sum()),
                "logit_move": float(np.abs(moved - rows).max()),
            }
            del moved
        return readings
