"""The delta rule with a decay a key channel (Kimi Delta Attention,
arXiv:2510.26692), two ways.

Per head the state `S` ([K, V]: key size by value size) follows

    S <- diag(a_t) S;   d_t = beta_t (v_t - S^T k_t);   S <- S + k_t (outer) d_t
    o_t = S^T q_t

with `a_t = exp(g_t)` in (0, 1]^K a VECTOR over the key channels of a head
and token and `beta_t` in [0, 2]: `ray_tpu.ops.gated_delta`'s rule, whose
decay is one scalar a head and token, with every key row of the state
forgetting at its own rate. `kda_chunked` is the prefill form. With `run`
the running sum of `g` inside a chunk ([C, K] a head) and `S0` the state the
chunk starts from,

    (I + N) D = beta V - (beta K exp(run)) S0,
    N[i, j] = beta_i sum_c k_i[c] k_j[c] exp(run_i[c] - run_j[c])  for j < i

so the corrected values `D` of a chunk are `U - W S0` for `U = (I + N)^-1
beta V` and `W = (I + N)^-1 (beta K exp(run))`, solved for every chunk at
once (a unit lower-triangular solve, float32); then only the chunk
boundaries are walked in sequence:

    o_i = (q_i exp(run_i)) S0 + sum_{j <= i} M[i, j] d_j,
          M[i, j] = sum_c q_i[c] k_j[c] exp(run_i[c] - run_j[c])
    S_end = diag(exp(run_end)) S0 + sum_j (k_j exp(run_end - run_j)) (outer) d_j

A chunk's decay no longer factors out of `N` and `M` as one scalar a token,
and writing them as `(k_i exp(run_i)) . (k_j exp(-run_j))` overflows where a
channel forgets fast. `_decayed_products` keeps every exponent a difference
of running sums that is at most zero: a chunk is cut into sub-chunks of
`sub` tokens; rows of sub-chunk `a` against the columns of earlier
sub-chunks go through the reference point `r_a`, the running sum at the last
token before `a` (`run_i - r_a <= 0` for i in a, `r_a - run_j <= 0` for j
before it), as one matrix product of the two scaled operands; inside a
sub-chunk the `[sub, sub, K]` differences are taken directly. A decay near 0
underflows to 0 and nothing divides.

Positions at or past `length` have `g` and `beta` set to zero, which makes
them a no-op of the recurrence (decay 1, correction 0), so a bucket's
padding never reaches the state. `kda_update` is the one-token recurrence
over a batch of states in one pass over the state, a Pallas kernel
(interpreted on the CPU) as `gated_delta_update`: a tile of all key rows of
a head scales each row by its own decay, takes both read-outs from the
decayed state (`o = (a S)^T q + (k . q) d` with `d = beta (v - (a S)^T k)`)
and writes the new state in place; the state is read once and written once.
A head's `[K, V]` float32 tile at K = V = 128 is whole TPU tiles, so the
state is kept plain, `[H, K, V]`, and not packed.

The decays, their running sums, the solve and the states are float32
whatever `dtype` the matrix products take their operands in.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.scipy.linalg import solve_triangular

from ray_tpu.ops.flash_attention import _on_cpu

# Heads a step of the update kernel's grid holds at most: 8 of [128, 128]
# float32 are 0.5 MB a block, 2 MB with the state in and out and the
# pipeline's second buffers.
_HEAD_BLOCK = 8


def _decayed_products(a, k, run, sub: int, dtype):
    """a [..., R, C, K] and k [..., C, K] in `dtype`, run [..., C, K] float32
    (the running sum of a log-decay that is at most 0, so non-increasing
    along C) -> [..., R, C, C] float32: for each of the R row operands
    `sum_c a_i[c] k_j[c] exp(run_i[c] - run_j[c])` where j <= i, else 0."""
    *lead, chunk, width = run.shape
    blocks = chunk // sub
    run_b = run.reshape(*lead, blocks, sub, width)
    a_b = a.reshape(*a.shape[:-2], blocks, sub, width)
    k_b = k.reshape(*lead, blocks, sub, width)
    # r_a: the running sum at the last token before sub-chunk a (0 before
    # the first, which has no earlier columns).
    ref = jnp.concatenate(
        [jnp.zeros_like(run_b[..., :1, -1, :]), run_b[..., :-1, -1, :]], axis=-2
    )  # [..., blocks, K]
    rows = (
        a_b.astype(jnp.float32) * jnp.exp(run_b - ref[..., None, :])[..., None, :, :, :]
    ).astype(dtype)  # [..., R, blocks, sub, K]
    cols = (
        k.astype(jnp.float32)[..., None, :, :]
        * jnp.exp(jnp.minimum(ref[..., :, None, :] - run[..., None, :, :], 0.0))
    ).astype(dtype)  # [..., blocks, C, K]: the columns as sub-chunk a sees them
    across = jnp.einsum(
        "...raik,...ajk->...raij", rows, cols, preferred_element_type=jnp.float32
    )  # [..., R, blocks, sub, C]
    first = (jnp.arange(blocks) * sub)[:, None, None]  # a's first column
    across = jnp.where(jnp.arange(chunk)[None, None, :] < first, across, 0.0)
    across = across.reshape(*a.shape[:-2], chunk, chunk)
    # Inside a sub-chunk: the differences themselves, [sub, sub, K].
    gap = jnp.minimum(run_b[..., :, None, :] - run_b[..., None, :, :], 0.0)
    inside = jnp.sum(
        a_b.astype(jnp.float32)[..., :, None, :]
        * (k_b.astype(jnp.float32)[..., None, :, :] * jnp.exp(gap))[..., None, :, :, :, :],
        axis=-1,
    )  # [..., R, blocks, sub, sub]
    inside = jnp.where(jnp.tril(jnp.ones((sub, sub), bool)), inside, 0.0)
    inside = jnp.einsum(
        "...aij,ab->...aibj", inside, jnp.eye(blocks, dtype=jnp.float32)
    ).reshape(*a.shape[:-2], chunk, chunk)
    return across + inside


def kda_chunked(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    g: jax.Array,
    beta: jax.Array,
    state: jax.Array,
    length,
    chunk: int = 64,
    dtype=jnp.float32,
    sub: int = 16,
) -> Tuple[jax.Array, jax.Array]:
    """q and k [T, H, K] (normalised, q scaled), v [T, H, V], g [T, H, K]
    (log-decay a key channel, at most 0) and beta [T, H], state [H, K, V]
    float32 -> (o [T, H, V] float32, the state after token `length` - 1). T
    is padded up to a multiple of `chunk` here; `length` (traced or not) is
    the number of real tokens. `sub` (a divisor of `chunk`, or it is taken
    down to one) is the sub-chunk of `_decayed_products`."""
    t_len, heads, _ = q.shape
    sub = max(s for s in range(1, min(sub, chunk) + 1) if chunk % s == 0)
    pad = -t_len % chunk
    real = jnp.arange(t_len + pad) < length
    if pad:
        q, k, v, g, beta = (
            jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1))
            for x in (q, k, v, g, beta)
        )
    g = jnp.where(real[:, None, None], g.astype(jnp.float32), 0.0)
    beta = jnp.where(real[:, None], beta.astype(jnp.float32), 0.0)
    chunks = (t_len + pad) // chunk

    def by_head(x):  # [T, H, ...] -> [c, H, Q, ...]
        return jnp.swapaxes(x.reshape((chunks, chunk) + x.shape[1:]), 1, 2)

    q, k, v = (by_head(x).astype(dtype) for x in (q, k, v))
    beta = by_head(beta)  # [c, H, Q]
    run = jnp.cumsum(by_head(g), axis=-2)  # [c, H, Q, K]
    total = run[..., -1, :]  # [c, H, K]

    def product(pattern, x, y):
        return jnp.einsum(pattern, x, y, preferred_element_type=jnp.float32)

    both = _decayed_products(jnp.stack([k, q], axis=2), k, run, sub, dtype)
    # The solve, for every chunk at once: U and W of the header.
    n = jnp.where(
        jnp.tril(jnp.ones((chunk, chunk), bool), -1), beta[..., None] * both[:, :, 0], 0.0
    )
    k32 = k.astype(jnp.float32)
    rhs = jnp.concatenate(
        [beta[..., None] * v.astype(jnp.float32), beta[..., None] * jnp.exp(run) * k32],
        axis=-1,
    )
    solved = solve_triangular(
        n + jnp.eye(chunk, dtype=jnp.float32), rhs, lower=True, unit_diagonal=True
    )
    v_dim = v.shape[-1]
    u, w = solved[..., :v_dim], solved[..., v_dim:].astype(dtype)
    mixed = both[:, :, 1].astype(dtype)
    q_in = (q.astype(jnp.float32) * jnp.exp(run)).astype(dtype)
    k_out = (k32 * jnp.exp(total[..., None, :] - run)).astype(dtype)

    # The chunk boundaries, in sequence.
    def boundary(s, step):
        u_c, w_c, mixed_c, q_c, k_c, total_c = step
        s_in = s.astype(dtype)
        d = (u_c - product("hik,hkv->hiv", w_c, s_in)).astype(dtype)
        o = product("hik,hkv->hiv", q_c, s_in) + product("hij,hjv->hiv", mixed_c, d)
        s = jnp.exp(total_c)[:, :, None] * s + product("hjk,hjv->hkv", k_c, d)
        return s, o

    state, o = jax.lax.scan(
        boundary, state.astype(jnp.float32), (u, w, mixed, q_in, k_out, total)
    )
    o = jnp.swapaxes(o, 1, 2).reshape(chunks * chunk, heads, v_dim)
    return o[:t_len], state


def _update_kernel(live_ref, cols_ref, rows_ref, state_ref, o_ref, new_ref):
    """One lane's block of P heads. cols [K, 3 P]: a head's decay, k and q as
    columns over the key rows; rows [3, P, V]: beta, v and k . q; state
    [P, K, V], read from VMEM once."""
    heads, k_dim, width = state_ref.shape[1:]
    alive = live_ref[pl.program_id(0)] != 0
    cols = cols_ref[0, 0]

    def column(j):
        return jnp.broadcast_to(cols[:, j : j + 1], (k_dim, width))

    for p in range(heads):
        old = state_ref[0, p]
        decayed = column(3 * p) * old
        k, q = column(3 * p + 1), column(3 * p + 2)
        beta, v, kq = (rows_ref[0, 0, r, p : p + 1, :] for r in range(3))
        d = beta * (v - jnp.sum(decayed * k, axis=0, keepdims=True))
        o_ref[0, 0, p : p + 1, :] = jnp.sum(decayed * q, axis=0, keepdims=True) + kq * d
        new_ref[0, p] = jnp.where(alive, decayed + k * d, old)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _update(q, k, v, g, beta, state, live, *, interpret):
    """One traced function a shape: a program traces the kernel's body and
    lowers it once however many layers call it. What XLA makes for the
    kernel is small, [B, H, K] rows and columns: nothing of the state's size."""
    b, h, v_dim = v.shape
    k_dim = k.shape[-1]
    block = max(p for p in range(1, _HEAD_BLOCK + 1) if h % p == 0)
    groups = h // block
    q, k, v = (x.astype(jnp.float32) for x in (q, k, v))

    def wide(x):  # [B, H], a scalar a head -> over the value columns
        return jnp.broadcast_to(x[..., None], (b, h, v_dim))

    rows = jnp.stack(
        [wide(beta.astype(jnp.float32)), v, wide(jnp.sum(k * q, axis=-1))], axis=1
    ).reshape(b, 3, groups, block, v_dim)
    # A head's decay, k, q: [B, groups, 3 P, K], then columns over the key rows.
    cols = jnp.stack(
        [x.reshape(b, groups, block, k_dim) for x in (jnp.exp(g.astype(jnp.float32)), k, q)],
        axis=3,
    ).reshape(b, groups, 3 * block, k_dim)

    def spec(*block_shape):  # of lane i, its j-th group of heads
        rest = (0,) * (len(block_shape) - 2)
        return pl.BlockSpec(block_shape, lambda i, j, live: (i, j) + rest)

    o, new = pl.pallas_call(
        _update_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, groups),
            in_specs=[
                spec(1, 1, k_dim, 3 * block),
                spec(1, 1, 3, block, v_dim),
                spec(1, block, k_dim, v_dim),
            ],
            out_specs=[spec(1, 1, block, v_dim), spec(1, block, k_dim, v_dim)],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((b, groups, block, v_dim), jnp.float32),
            jax.ShapeDtypeStruct(state.shape, jnp.float32),
        ],
        input_output_aliases={3: 1},  # the states, in place
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")
        ),
        interpret=interpret,
    )(
        live.astype(jnp.int32), jnp.swapaxes(cols, -1, -2),
        jnp.swapaxes(rows, 1, 2), state,
    )
    return o.reshape(b, h, v_dim), new


def kda_update(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    g: jax.Array,
    beta: jax.Array,
    state: jax.Array,
    live: jax.Array,
) -> Tuple[jax.Array, jax.Array]:
    """One token a sequence: q and k [B, H, K], v [B, H, V], g [B, H, K]
    (log-decay a key channel), beta [B, H], state [B, H, K, V] float32, live
    [B] bool -> (o [B, H, V] float32, the new states; a lane that is not
    live keeps its state, bit for bit, and its o means nothing). Multiplies
    and sums, not matrix products: the MXU would round the float32 state to
    its input type."""
    return _update(q, k, v, g, beta, state, live, interpret=_on_cpu())
