"""The plain reference of `mellum` (JetBrains Mellum 2) on the training
path, and the comparison that decides `correct` for its cell.

The benchmark's own copy: it imports nothing of the program. `sequence_loss`
is the forward pass written out in `jax.numpy`, float32 under
`jax.default_matmul_precision("highest")`: RMS norms (eps 1e-6), q / k / v
projections without bias, rotary positions over the whole head from tables
computed in float64 from the published formulas (`lib/reference_laguna.py`'s
`rotary_tables`: a sliding layer with the default frequencies, a full layer
with YaRN's, base 500,000, factor 16 over 8,192, beta 32 and 1, cos and sin
times 1.2772...; pairs (i, i + 64)), causal attention of query head j over
key head j // 8 under dense [T, T] masks (on a sliding layer also i - j <
1,024), a key head and a block of 2,048 queries at a time so that 8,192
positions fit; the experts as a loop over the experts HELD, every token
through each, weighed by the gate (nought where the token did not choose
it: softmax over all 64, the 8 largest, divided by their sum), no sort and
no grouped product; a final norm, an untied head over the slice's rows, the
mean cross entropy of the next token. Gradients by `jax.grad` of that loss,
a layer and a block recomputed in the backward pass so that it fits.

What it takes from the program is the seeded parameter tree, by the names
`ray_tpu/models/mellum.py` gives the leaves. `dtype` (None: float32
"highest") gives every matrix product operands and a result of that type, the router's excepted, as the model keeps it: the same dense
steps in the training type, which is the lower reading the gradient
tolerances are derived from; `"int8"` sends every weight matrix (a scale an
output channel) and each such product's input (a scale a row) through
symmetric int8, rounding straight through in the backward pass: the nearest
precision below the bfloat16 the configuration states, the control that has
to come out not correct. `window_delta` widens the sliding layers' window:
a variant the comparison has to notice.
"""

from __future__ import annotations

from lib.reference_laguna import SLIDING, _rms_norm, _rotate, _silu, rotary_tables
from lib.reference_laguna import _int8 as reference_laguna_int8

QUERY_BLOCK = 2048


def sizes(fields: dict):
    """The configuration file's `model` section as the object the
    functions here read sizes off."""
    import types

    fields = dict(fields)
    for key in ("experts_held", "layer_types", "vocab_rows"):
        fields[key] = tuple(fields[key])
    return types.SimpleNamespace(**fields)


INT8 = "int8"


def _int8(x, axis: int):
    """What a symmetric int8 path keeps of `x`, one scale along `axis`;
    its gradient is the identity's (straight through the rounding)."""
    import jax

    return x + jax.lax.stop_gradient(reference_laguna_int8(x, axis) - x)


def _mm(x, w, dtype):
    import jax.numpy as jnp

    if dtype is None:
        return x @ w
    if dtype == INT8:  # a scale a row of the input and an output channel
        return _int8(x, -1) @ _int8(w, -2)
    return (x.astype(dtype) @ w.astype(dtype)).astype(jnp.float32)


def attention(cfg, kind, p, u, dtype=None, window_delta=0):
    """u [T, D] -> [T, D]: the attention of one layer of `kind`."""
    import jax
    import jax.numpy as jnp

    t_len, d, hkv = u.shape[0], cfg.head_dim, cfg.num_key_value_heads
    group = cfg.num_attention_heads // hkv
    cos, sin = (jnp.asarray(t) for t in rotary_tables(cfg, kind, t_len))
    q = _rotate(_mm(u, p["q"], dtype).reshape(t_len, -1, d), cos, sin)
    k = _rotate(_mm(u, p["k"], dtype).reshape(t_len, hkv, d), cos, sin)
    v = _mm(u, p["v"], dtype).reshape(t_len, hkv, d)
    block = min(QUERY_BLOCK, t_len)
    if t_len % block:
        raise ValueError(f"{t_len} positions in blocks of {block}")
    cols = jnp.arange(t_len)[None, :]
    window = cfg.sliding_window + window_delta

    @jax.checkpoint
    def one_block(rows, q_b, k_h, v_h):  # q_b [block, group, d]
        seen = cols <= rows[:, None]
        if kind == SLIDING:
            seen = seen & (rows[:, None] - cols < window)
        scores = jnp.einsum("qgd,kd->gqk", *_operands(q_b, k_h, dtype))
        scores = scores.astype(jnp.float32) * d ** -0.5
        weights = jax.nn.softmax(jnp.where(seen[None], scores, -jnp.inf), axis=-1)
        return jnp.einsum("gqk,kd->qgd", *_operands(weights, v_h, dtype)).astype(jnp.float32)

    def one_head(head):
        q_h, k_h, v_h = head  # [T, group, d], [T, d], [T, d]
        rows = jnp.arange(t_len).reshape(-1, block)
        q_blocks = q_h.reshape(-1, block, group, d)
        return jax.lax.map(
            lambda rq: one_block(rq[0], rq[1], k_h, v_h), (rows, q_blocks)
        ).reshape(t_len, group, d)

    heads = (
        q.reshape(t_len, hkv, group, d).transpose(1, 0, 2, 3),
        k.transpose(1, 0, 2), v.transpose(1, 0, 2),
    )
    mixed = jax.lax.map(one_head, heads)  # [hkv, T, group, d]
    return _mm(mixed.transpose(1, 0, 2, 3).reshape(t_len, -1), p["o"], dtype)


def _operands(a, b, dtype):
    """Attention's own products keep float32 under the int8 control, as an
    int8 path for the weights would."""
    return (a, b) if dtype in (None, INT8) else (a.astype(dtype), b.astype(dtype))


def routed_experts(cfg, p, x, dtype=None):
    """The held experts' part of the routed sum for x [T, D]."""
    import jax
    import jax.numpy as jnp

    share = jax.nn.softmax(x @ p["router"], axis=-1)
    top, ids = jax.lax.top_k(share, cfg.num_experts_per_tok)
    gates = top / jnp.sum(top, axis=-1, keepdims=True)
    held = jnp.asarray(cfg.experts_held, jnp.int32)

    def one(total, expert):
        number, w_in, w_out = expert
        gate = jnp.sum(jnp.where(ids == number, gates, 0.0), axis=-1)
        g, u = jnp.split(_mm(x, w_in, dtype), 2, axis=-1)
        return total + gate[:, None] * _mm(_silu(g) * u, w_out, dtype), None

    return jax.lax.scan(
        jax.checkpoint(one), jnp.zeros_like(x), (held, p["experts_in"], p["experts_out"])
    )[0]


def layer(cfg, kind, p, h, dtype=None, window_delta=0):
    """One layer on the residual rows h [T, D] of one sequence."""
    u = _rms_norm(h, p["norm1"], cfg.rms_norm_eps)
    h = h + attention(cfg, kind, p["mixer"], u, dtype, window_delta)
    return h + routed_experts(cfg, p, _rms_norm(h, p["norm2"], cfg.rms_norm_eps), dtype)


def sequence_logits(cfg, params, ids, dtype=None, window_delta=0):
    """Logits [T, rows held] float32 of one sequence `ids` [T]."""
    import jax

    h = params["wte"][ids]
    for kind, p in zip(cfg.layer_types, params["layers"]):
        h = jax.checkpoint(
            lambda p, h, kind=kind: layer(cfg, kind, p, h, dtype, window_delta)
        )(p, h)
    return _mm(_rms_norm(h, params["norm_f"], cfg.rms_norm_eps), params["lm_head"], dtype)


def batch_loss(cfg, params, tokens, dtype=None, window_delta=0):
    """Mean cross entropy of the next token over `tokens` [B, T], a
    sequence at a time."""
    import jax
    import jax.numpy as jnp

    @jax.checkpoint
    def one(params, ids):
        logits = sequence_logits(cfg, params, ids, dtype, window_delta)[:-1]
        picked = jnp.take_along_axis(logits, ids[1:, None], axis=-1)[:, 0]
        return jnp.mean(jax.nn.logsumexp(logits, axis=-1) - picked)

    return jnp.mean(jax.lax.map(lambda ids: one(params, ids), tokens))


def training_reference_step(cfg, tx, dtype=None, window_delta=0):
    """A jitted `step(params, opt_state, tokens) -> (params, opt_state,
    loss)`: one optimizer step of the reference on `tokens` [B, T] under
    the optax transformation `tx` the timed loop steps with; where the
    configuration says `hold_router`, the routers' weights stay as they
    are, as in the timed step."""
    import jax
    import optax

    def step(params, opt_state, tokens):
        with jax.default_matmul_precision("highest"):
            loss, grads = jax.value_and_grad(
                lambda p: batch_loss(cfg, p, tokens, dtype, window_delta)
            )(params)
        updates, opt_state = tx.update(grads, opt_state, params)
        if getattr(cfg, "hold_router", False):  # computed, followed, not applied
            updates = dict(updates, layers=[
                dict(layer, router=0.0 * layer["router"]) for layer in updates["layers"]
            ])
        return optax.apply_updates(params, updates), opt_state, loss

    return jax.jit(step, donate_argnums=(0, 1))


def _kinds(cfg, tree):
    """(kind of leaf, leaf) over the tree: a layer's leaves by the layer's
    kind and the leaf's name (`sliding_attention/q`,
    `full_attention/experts_in`), the rest by name."""
    for name in ("wte", "norm_f", "lm_head"):
        yield name, tree[name]
    for kind, p in zip(cfg.layer_types, tree["layers"]):
        for name, leaf in p.items():
            if name == "mixer":
                for inner, w in leaf.items():
                    yield f"{kind}/{inner}", w
            else:
                yield f"{kind}/{name}", leaf


def relative_distance(cfg, tree, reference) -> dict:
    """|tree - reference| / |reference| in the 2-norm: `all` over every
    leaf, one entry a kind of leaf (`_kinds`), `worst_matrix`, the
    largest entry among the matrices: projections, router, both expert
    matrices, embedding and head, by kind of layer (the norms' weights are
    left out of that one, as `lib/reference.py` leaves LayerNorm's out),
    and `worst_attention`, the largest among q, k, v and o alone: a
    token's choice of experts is discrete, so any rounding flips some and
    the routed kinds read several times what the attention's matrices do;
    a fault in a mask or a rotation would drown in them.
    Either tree may live on the host: a leaf at a time goes to the
    device."""
    import jax.numpy as jnp
    import numpy as np

    sums: dict = {}
    for (kind, a), (_, b) in zip(_kinds(cfg, tree), _kinds(cfg, reference)):
        a, b = jnp.asarray(a, jnp.float32), jnp.asarray(b, jnp.float32)
        diff, norm = float(jnp.sum((a - b) ** 2)), float(jnp.sum(b ** 2))
        for key in ("all", kind):
            have = sums.setdefault(key, [0.0, 0.0])
            have[0] += diff
            have[1] += norm
    out = {
        kind: float(np.sqrt(diff / norm)) if norm > 0 else float(diff > 0)
        for kind, (diff, norm) in sums.items()
    }
    out["worst_matrix"] = max(
        value for kind, value in out.items()
        if kind != "all" and not kind.rsplit("/", 1)[-1].startswith("norm")
    )
    out["worst_attention"] = max(
        value for kind, value in out.items() if kind.rsplit("/", 1)[-1] in tuple("qkvo")
    )
    return out
