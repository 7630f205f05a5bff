"""The gated delta rule (Yang et al., "Gated Delta Networks",
arXiv:2412.06464), two ways.

Per head the state `S` ([K, V]: key size by value size) follows

    S <- a_t S;   d_t = beta_t (v_t - S^T k_t);   S <- S + k_t (outer) d_t
    o_t = S^T q_t

with `a_t = exp(g_t)` in (0, 1] a scalar a head and token and `beta_t` in
[0, 2]. It is not a decayed sum: a token corrects the state by what the
state already holds for its key. `gated_delta_chunked` is the prefill form
(the WY / UT transform of arXiv:2406.06484): with `run` the running sum of
`g` inside a chunk and `S0` the state the chunk starts from,

    (I + N) D = beta V - (beta exp(run) K) S0,
    N[i, j] = beta_i exp(run_i - run_j) (k_i . k_j)  for j < i, else 0

so the corrected values `D` of a chunk are `U - W S0` for `U = (I + N)^-1
beta V` and `W = (I + N)^-1 beta exp(run) K`, which need no state and are
solved for every chunk at once (a unit lower-triangular solve, float32);
then only the chunk boundaries are walked in sequence:

    o_i = exp(run_i) S0^T q_i + sum_{j <= i} exp(run_i - run_j) (q_i . k_j) d_j
    S_end = exp(run_end) S0 + sum_j exp(run_end - run_j) k_j (outer) d_j

It starts from a given state and returns the state after the last *real*
token: positions at or past `length` have `g` and `beta` set to zero, which
makes them a no-op of the recurrence (decay 1, correction 0), so a bucket's
padding never reaches the state. Every exponent is a difference of running
sums that is at most zero, so a decay near 0 underflows to 0 and nothing
divides. `gated_delta_update` is the one-token recurrence over a batch of
states in one pass over the state: both read-outs are taken from the OLD
state (`o = a S^T q + (k . q) d` with `d = beta (v - a S^T k)`), so the
state is read for the read-outs and for `S' = a S + k (outer) d` and
written once. It is elementwise and bound by reading and writing the
states.

A slot's state is kept PACKED, `[H / 2, K, 2 V]`: heads 2p and 2p + 1 side
by side in the last axis. At V = 192 a float32 `[K, V]` tile pads its rows
to 256 lanes on the TPU, a third more memory and a third more bytes a
step; 384 is three whole tiles. `pack_state` / `unpack_state` go between
the two; the chunked form takes and returns the packed state too.

The decays, their running sums, the solve and the states are float32
whatever `dtype` the matrix products take their operands in.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
from jax.scipy.linalg import solve_triangular

PACK = 2  # heads side by side in a packed state's last axis


def pack_state(state: jax.Array) -> jax.Array:
    """[..., H, K, V] -> [..., H / 2, K, 2 V]."""
    *lead, h, k, v = state.shape
    pairs = state.reshape(*lead, h // PACK, PACK, k, v)
    return jnp.moveaxis(pairs, -3, -2).reshape(*lead, h // PACK, k, PACK * v)


def unpack_state(packed: jax.Array) -> jax.Array:
    """[..., H / 2, K, 2 V] -> [..., H, K, V]."""
    *lead, h2, k, v2 = packed.shape
    pairs = packed.reshape(*lead, h2, k, PACK, v2 // PACK)
    return jnp.moveaxis(pairs, -2, -3).reshape(*lead, h2 * PACK, k, v2 // PACK)


def gated_delta_chunked(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    g: jax.Array,
    beta: jax.Array,
    state: jax.Array,
    length,
    chunk: int = 64,
    dtype=jnp.float32,
) -> Tuple[jax.Array, jax.Array]:
    """q and k [T, H, K] (normalised, q scaled), v [T, H, V], g [T, H]
    (log-decay, at most 0) and beta [T, H], state [H / 2, K, 2 V] float32
    (packed) -> (o [T, H, V] float32, the packed state after token
    `length` - 1). T is padded up to a multiple of `chunk` here; `length`
    (traced or not) is the number of real tokens."""
    t_len, heads, _ = q.shape
    pad = -t_len % chunk
    real = (jnp.arange(t_len + pad) < length)[:, None]
    if pad:
        q, k, v, g, beta = (
            jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1))
            for x in (q, k, v, g, beta)
        )
    g = jnp.where(real, g.astype(jnp.float32), 0.0)
    beta = jnp.where(real, beta.astype(jnp.float32), 0.0)
    chunks = (t_len + pad) // chunk

    def by_head(x):  # [T, H, ...] -> [c, H, Q, ...]
        return jnp.swapaxes(x.reshape((chunks, chunk) + x.shape[1:]), 1, 2)

    q, k, v = (by_head(x).astype(dtype) for x in (q, k, v))
    beta = by_head(beta)  # [c, H, Q]
    run = jnp.cumsum(by_head(g), axis=-1)  # [c, H, Q]
    total = run[..., -1]  # [c, H]
    lower = jnp.tril(jnp.ones((chunk, chunk), bool))
    gap = run[..., :, None] - run[..., None, :]  # [c, H, i, j]
    decay = jnp.where(lower, jnp.exp(jnp.minimum(gap, 0.0)), 0.0)

    def product(pattern, x, y):
        return jnp.einsum(pattern, x, y, preferred_element_type=jnp.float32)

    # The solve, for every chunk at once: U and W of the header.
    kk = product("chik,chjk->chij", k, k)
    n = jnp.where(jnp.tril(lower, -1), beta[..., None] * decay * kk, 0.0)
    rhs = jnp.concatenate(
        [
            beta[..., None] * v.astype(jnp.float32),
            (beta * jnp.exp(run))[..., None] * k.astype(jnp.float32),
        ],
        axis=-1,
    )
    solved = solve_triangular(
        n + jnp.eye(chunk, dtype=jnp.float32), rhs, lower=True, unit_diagonal=True
    )
    v_dim = v.shape[-1]
    u, w = solved[..., :v_dim], solved[..., v_dim:].astype(dtype)
    mixed = (product("chik,chjk->chij", q, k) * decay).astype(dtype)
    q_in = (q.astype(jnp.float32) * jnp.exp(run)[..., None]).astype(dtype)
    k_out = (k.astype(jnp.float32) * jnp.exp(total[..., None] - run)[..., None]).astype(dtype)

    # The chunk boundaries, in sequence.
    def boundary(s, step):
        u_c, w_c, mixed_c, q_c, k_c, total_c = step
        s_in = s.astype(dtype)
        d = (u_c - product("hik,hkv->hiv", w_c, s_in)).astype(dtype)
        o = product("hik,hkv->hiv", q_c, s_in) + product("hij,hjv->hiv", mixed_c, d)
        s = jnp.exp(total_c)[:, None, None] * s + product("hjk,hjv->hkv", k_c, d)
        return s, o

    state, o = jax.lax.scan(
        boundary, unpack_state(state.astype(jnp.float32)),
        (u, w, mixed, q_in, k_out, total),
    )
    o = jnp.swapaxes(o, 1, 2).reshape(chunks * chunk, heads, v_dim)
    return o[:t_len], pack_state(state)


def _side_by_side(x: jax.Array, width: int) -> jax.Array:
    """x [B, H, ...] (a scalar, or a [K] column, a head) -> [B, H / 2, ...,
    2 width]: each of a pair's over its half of a packed state's last axis.
    A select between two broadcasts, which fuses into what reads it; a
    repeat's reshape would be written out at the state's size."""
    b, h = x.shape[:2]
    pairs = x.reshape((b, h // PACK, PACK) + x.shape[2:])
    first = jnp.arange(PACK * width) < width
    return jnp.where(first, pairs[:, :, 0, ..., None], pairs[:, :, 1, ..., None])


def gated_delta_update(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    g: jax.Array,
    beta: jax.Array,
    state: jax.Array,
) -> Tuple[jax.Array, jax.Array]:
    """One token a sequence: q and k [B, H, K], v [B, H, V], g and beta
    [B, H], state [B, H / 2, K, 2 V] float32 (packed) -> (o [B, H, V]
    float32, the new states). Multiplies and sums, not matrix products: the
    MXU would round the float32 state to its input type."""
    b, h, v_dim = v.shape
    q, k, v = (x.astype(jnp.float32) for x in (q, k, v))
    a = jnp.exp(g.astype(jnp.float32))
    beta = beta.astype(jnp.float32)

    def read(x):  # S^T x of the old state, [B, H, V]
        return jnp.sum(state * _side_by_side(x, v_dim), axis=-2).reshape(b, h, v_dim)

    d = beta[..., None] * (v - a[..., None] * read(k))
    o = a[..., None] * read(q) + jnp.sum(k * q, axis=-1, keepdims=True) * d
    new = (
        _side_by_side(a, v_dim)[:, :, None, :] * state
        + _side_by_side(k, v_dim) * d.reshape(b, h // PACK, 1, PACK * v_dim)
    )
    return o, new
