"""Mamba-2's selective state-space recurrence, two ways.

Per head the state `S` ([P, N]: head size by state size) follows

    S_t = exp(dt_t * A) * S_{t-1} + dt_t * x_t (outer) B_t
    y_t = S_t @ C_t

with `A` < 0 a scalar a head and `B`, `C` shared by the heads of a group:
head h of H reads group h // (H / G) of G (granite has one group, Falcon-H1
two). `ssd_chunked_scan` is the prefill form (Dao & Gu 2024,
"state space duality"): the sequence is cut into chunks of `chunk` tokens,
inside a chunk the recurrence is one masked matrix product (the decay
between positions i >= j is exp(cumsum(a)_i - cumsum(a)_j)), and only the
chunk boundaries are walked in sequence. It starts from a given state and
returns the state after the last *real* token: positions at or past
`length` have their `dt` set to zero, which makes them a no-op of the
recurrence (decay 1, input 0), so a bucket's padding never reaches the
state. `ssm_decode_update` is the one-token recurrence over a batch of
states, elementwise, bound by reading and writing the states; a lane that
does not decode keeps its state.

Both take the heads as [G, H / G] inside, so that a group's `B` and `C`
are broadcast over its heads and never repeated: nothing of the states'
size is made beside the states.

The decays, their cumulative sums and the states are float32 whatever
`dtype` the matrix products take their operands in.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp


def ssd_chunked_scan(
    x: jax.Array,
    dt: jax.Array,
    a: jax.Array,
    b: jax.Array,
    c: jax.Array,
    state: jax.Array,
    *,
    chunk: int,
    length,
    dtype=jnp.float32,
) -> Tuple[jax.Array, jax.Array]:
    """x [T, H, P], dt [T, H] (after softplus), a [H] (negative), b and c
    [T, G, N], state [H, P, N] float32 -> (y [T, H, P] float32, the state
    after token `length` - 1). T is padded up to a multiple of `chunk`
    here; `length` (traced or not) is the number of real tokens."""
    t_len, heads, p = x.shape
    groups, n = b.shape[-2:]
    per = heads // groups
    pad = -t_len % chunk
    real = (jnp.arange(t_len + pad) < length)[:, None]
    if pad:
        x, dt, b, c = (
            jnp.pad(v, ((0, pad),) + ((0, 0),) * (v.ndim - 1))
            for v in (x, dt, b, c)
        )
    dt = jnp.where(real, dt.astype(jnp.float32), 0.0)
    chunks = (t_len + pad) // chunk
    x = x.reshape(chunks, chunk, groups, per, p)
    dt = dt.reshape(chunks, chunk, groups, per)
    b = b.reshape(chunks, chunk, groups, n).astype(dtype)
    c = c.reshape(chunks, chunk, groups, n).astype(dtype)
    # Log-decays and their running sum inside each chunk: [c, Q, G, H/G].
    log_decay = dt * a.astype(jnp.float32).reshape(groups, per)
    run = jnp.cumsum(log_decay, axis=1)
    total = run[:, -1]  # [c, G, H/G]
    xdt = (x.astype(jnp.float32) * dt[..., None]).astype(dtype)

    # Inside a chunk: y_i += sum_{j <= i} (C_i . B_j) exp(run_i - run_j) xdt_j,
    # C and B those of the head's group.
    scores = jnp.einsum(
        "cign,cjgn->cijg", c, b, preferred_element_type=jnp.float32
    )
    lower = jnp.tril(jnp.ones((chunk, chunk), bool))
    gap = run[:, :, None] - run[:, None]  # [c, i, j, G, H/G]
    decay = jnp.where(
        lower[None, :, :, None, None], jnp.exp(jnp.minimum(gap, 0.0)), 0.0
    )
    mixed = (scores[..., None] * decay).astype(dtype)  # [c, i, j, G, H/G]
    y = jnp.einsum(
        "cijgh,cjghp->cighp", mixed, xdt, preferred_element_type=jnp.float32
    )

    # What each chunk adds to the state at its own end: [c, G, H/G, P, N].
    to_end = jnp.exp(total[:, None] - run)  # [c, Q, G, H/G]
    added = jnp.einsum(
        "cjghp,cjgn->cghpn",
        (xdt.astype(jnp.float32) * to_end[..., None]).astype(dtype),
        b, preferred_element_type=jnp.float32,
    )

    # The chunk boundaries, in sequence: `entering[k]` is the state chunk k
    # starts from.
    def boundary(s, step):
        add, decay_k = step
        return jnp.exp(decay_k)[..., None, None] * s + add, s

    state, entering = jax.lax.scan(
        boundary, state.astype(jnp.float32).reshape(groups, per, p, n),
        (added, total),
    )
    # y_i += exp(run_i) * (entering state) C_i.
    carried = jnp.einsum(
        "cghpn,cign->cighp", entering.astype(dtype), c,
        preferred_element_type=jnp.float32,
    )
    y = y + carried * jnp.exp(run)[..., None]
    return (
        y.reshape(chunks * chunk, heads, p)[:t_len], state.reshape(heads, p, n)
    )


def ssm_decode_update(
    x: jax.Array,
    dt: jax.Array,
    a: jax.Array,
    b: jax.Array,
    c: jax.Array,
    state: jax.Array,
    live: jax.Array,
) -> Tuple[jax.Array, jax.Array]:
    """One token a sequence: x [B, H, P], dt [B, H], a [H], b and c
    [B, G, N], state [B, H, P, N] float32, live [B] bool -> (y [B, H, P]
    float32, the states: a lane that is not `live` keeps its own)."""
    lanes, heads, p = x.shape
    groups, n = b.shape[-2:]
    by_group = (lanes, groups, heads // groups, p)
    dt = dt.astype(jnp.float32)
    decay = jnp.exp(dt * a.astype(jnp.float32)).reshape(by_group[:3])
    xdt = (x.astype(jnp.float32) * dt[..., None]).reshape(by_group)
    before = state.reshape(by_group + (n,))
    after = (
        decay[..., None, None] * before
        + xdt[..., None] * b.astype(jnp.float32)[:, :, None, None, :]
    )
    # A multiply and a lane sum, not a matrix product: the MXU would round
    # the float32 state to its input type.
    y = jnp.sum(after * c.astype(jnp.float32)[:, :, None, None, :], axis=-1)
    after = jnp.where(live[:, None, None, None, None], after, before)
    return y.reshape(x.shape), after.reshape(state.shape)
