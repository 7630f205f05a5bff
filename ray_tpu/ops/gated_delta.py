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
state (`o = a S^T q + (k . q) d` with `d = beta (v - a S^T k)`), and a tile
of all key rows and any value columns needs nothing outside itself for its
columns' read-outs, its `d`, its `o` and its new values. So it is one Pallas
kernel (interpreted on the CPU) that holds such tiles in VMEM: the state is
read once and written once, in place, at the rate of a copy. A reduction
that XLA would have to finish before the write could start made it read the
state twice.

A slot's state is kept PACKED, `[H / 2, K, 2 V]`: heads 2p and 2p + 1 side
by side in the last axis. At V = 192 a float32 `[K, V]` tile pads its rows
to 256 lanes on the TPU, a third more memory and a third more bytes a
step; 384 is three whole tiles. `pack_state` / `unpack_state` go between
the two; the chunked form takes and returns the packed state too.

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

PACK = 2  # heads side by side in a packed state's last axis
# Head pairs a step of the update kernel's grid holds at most. On a v5e 3, 5
# and 15 pairs of [96, 384] float32 all run at the rate of a copy through
# the same blocks (0.44 ms for a layer's [64, 15, 96, 384], 640 GB/s: PR 43);
# 5 are 0.7 MB a block, 2.9 MB with the state in and out and the pipeline's
# second buffers, and a third of 15's body to trace and compile.
_PAIR_BLOCK = 5


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


def _update_kernel(live_ref, cols_ref, rows_ref, state_ref, o_ref, new_ref):
    """One lane's block of P head pairs. cols [K, 4 P]: k of a pair's two
    heads, then q of them, as columns over the key rows; rows [4, P, 2 V]:
    a, beta, v and k . q, each head's over its half; state [P, K, 2 V], read
    from VMEM for the read-outs and again for the write."""
    pairs, k_dim, width = state_ref.shape[1:]
    alive = live_ref[pl.program_id(0)] != 0
    first = jax.lax.broadcasted_iota(jnp.int32, (k_dim, width), 1) < width // PACK
    cols = cols_ref[0, 0]

    def column(j):  # a pair's two columns, each over its half
        return jnp.where(
            first,
            jnp.broadcast_to(cols[:, j : j + 1], (k_dim, width)),
            jnp.broadcast_to(cols[:, j + 1 : j + 2], (k_dim, width)),
        )

    for p in range(pairs):
        old = state_ref[0, p]
        k, q = column(2 * PACK * p), column(2 * PACK * p + PACK)
        a, beta, v, kq = (rows_ref[0, 0, r, p : p + 1, :] for r in range(4))
        d = beta * (v - a * jnp.sum(old * k, axis=0, keepdims=True))
        o_ref[0, 0, p : p + 1, :] = (
            a * jnp.sum(old * q, axis=0, keepdims=True) + kq * d
        )
        new_ref[0, p] = jnp.where(alive, a * old + k * d, old)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _update(q, k, v, g, beta, state, live, *, interpret):
    """One traced function a shape: a program traces the kernel's body and
    lowers it once however many layers call it. What XLA makes for the
    kernel is small, [B, H] rows and columns: nothing of the state's size."""
    b, h, v_dim = v.shape
    k_dim, half, width = k.shape[-1], h // PACK, PACK * v_dim
    block = max(p for p in range(1, _PAIR_BLOCK + 1) if half % p == 0)
    groups = half // block
    q, k, v = (x.astype(jnp.float32) for x in (q, k, v))

    def halves(x):  # [B, H], a scalar a head -> each over its half, [B, H / 2, 2 V]
        return jnp.repeat(x, v_dim, axis=1).reshape(b, half, width)

    rows = jnp.stack(
        [
            halves(jnp.exp(g.astype(jnp.float32))), halves(beta.astype(jnp.float32)),
            v.reshape(b, half, width), halves(jnp.sum(k * q, axis=-1)),
        ],
        axis=1,
    ).reshape(b, 4, groups, block, width)
    # A pair's k, k, q, q: [B, groups, 4 P, K], then columns over the key rows.
    cols = jnp.stack(
        [x.reshape(b, groups, block, PACK, k_dim) for x in (k, q)], axis=3
    ).reshape(b, groups, 2 * PACK * block, k_dim)

    def spec(*block_shape):  # of lane i, its j-th group of pairs
        rest = (0,) * (len(block_shape) - 2)
        return pl.BlockSpec(block_shape, lambda i, j, live: (i, j) + rest)

    o, new = pl.pallas_call(
        _update_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, groups),
            in_specs=[
                spec(1, 1, k_dim, 2 * PACK * block),
                spec(1, 1, 4, block, width),
                spec(1, block, k_dim, width),
            ],
            out_specs=[spec(1, 1, block, width), spec(1, block, k_dim, width)],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((b, groups, block, width), jnp.float32),
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


def gated_delta_update(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    g: jax.Array,
    beta: jax.Array,
    state: jax.Array,
    live: jax.Array,
) -> Tuple[jax.Array, jax.Array]:
    """One token a sequence: q and k [B, H, K], v [B, H, V], g and beta
    [B, H], state [B, H / 2, K, 2 V] float32 (packed), live [B] bool ->
    (o [B, H, V] float32, the new states; a lane that is not live keeps its
    state, bit for bit, and its o means nothing). Multiplies and sums, not
    matrix products: the MXU would round the float32 state to its input
    type."""
    return _update(q, k, v, g, beta, state, live, interpret=_on_cpu())
