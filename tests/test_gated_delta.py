"""`ray_tpu.ops.gated_delta` against the recurrence it is the chunked and
the one-pass form of (the second a Pallas kernel, interpreted on the CPU),
written here token by token in float32:

    S <- a S;  d = beta (v - S^T k);  S <- S + k (outer) d;  o = S^T q

Seeded, on the CPU, float32 operands throughout (the serving path's
bfloat16 products are the model tests' business).
"""

import gc

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops.gated_delta import (
    gated_delta_chunked,
    gated_delta_update,
    pack_state,
    unpack_state,
)

H, K, V = 4, 8, 12


@pytest.fixture(scope="module", autouse=True)
def _leave_a_small_heap():
    """What this file traced goes when it is done: the worker that ran it
    runs other files after, and some of them time a full `gc.collect()`."""
    yield
    jax.clear_caches()
    gc.collect()


def recurrence(q, k, v, g, beta, state):
    """Token by token, two read-outs a token. Plain [H, K, V] state."""
    def step(s, x):
        q_t, k_t, v_t, g_t, b_t = x
        s = jnp.exp(g_t)[:, None, None] * s
        d = b_t[:, None] * (v_t - jnp.einsum("hkv,hk->hv", s, k_t))
        s = s + k_t[:, :, None] * d[:, None, :]
        return s, jnp.einsum("hkv,hk->hv", s, q_t)

    with jax.default_matmul_precision("highest"):
        state, o = jax.lax.scan(step, state, (q, k, v, g, beta))
    return o, state


def inputs(t_len, seed, decay="mixed", dims=(H, K, V)):
    H, K, V = dims
    rng = np.random.default_rng(seed)
    q = rng.standard_normal((t_len, H, K)).astype(np.float32)
    k = rng.standard_normal((t_len, H, K)).astype(np.float32)
    q = q / np.linalg.norm(q, axis=-1, keepdims=True) * K ** -0.5
    k = k / np.linalg.norm(k, axis=-1, keepdims=True)
    v = rng.standard_normal((t_len, H, V)).astype(np.float32)
    # beta over the whole of [0, 2], its ends included.
    beta = rng.uniform(0.0, 2.0, (t_len, H)).astype(np.float32)
    beta[rng.random((t_len, H)) < 0.1] = 2.0
    g = {
        "near_one": -rng.uniform(1e-5, 1e-3, (t_len, H)),
        "near_zero": -rng.uniform(5.0, 30.0, (t_len, H)),
        "mixed": -np.exp(rng.uniform(np.log(1e-4), np.log(20.0), (t_len, H))),
    }[decay].astype(np.float32)
    state = rng.standard_normal((H, K, V)).astype(np.float32)
    return tuple(jnp.asarray(x) for x in (q, k, v, g, beta, state))


def close(got, want, tol=2e-4):
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=tol, atol=tol)


def test_pack_and_unpack_are_inverses():
    state = jnp.arange(3 * H * K * V, dtype=jnp.float32).reshape(3, H, K, V)
    packed = pack_state(state)
    assert packed.shape == (3, H // 2, K, 2 * V)
    # Heads 2p and 2p + 1 side by side in the last axis.
    np.testing.assert_array_equal(packed[1, 1, :, :V], state[1, 2])
    np.testing.assert_array_equal(packed[1, 1, :, V:], state[1, 3])
    np.testing.assert_array_equal(unpack_state(packed), state)


@pytest.mark.parametrize("decay", ["mixed", "near_one", "near_zero"])
@pytest.mark.parametrize("chunk", [64, 8])
@pytest.mark.parametrize("t_len", [1, 7, 64, 77, 150])
def test_chunked_is_the_recurrence_from_a_carried_state(t_len, chunk, decay):
    q, k, v, g, beta, state = inputs(t_len, t_len + chunk, decay)
    want_o, want_s = recurrence(q, k, v, g, beta, state)
    o, packed = gated_delta_chunked(q, k, v, g, beta, pack_state(state), t_len, chunk)
    close(o, want_o)
    close(unpack_state(packed), want_s)


@pytest.mark.parametrize("chunk", [64, 8])
def test_any_cut_into_two_chunks_is_one_sequence(chunk):
    t_len = 21
    q, k, v, g, beta, state = inputs(t_len, 5)
    whole_o, whole_s = gated_delta_chunked(
        q, k, v, g, beta, pack_state(state), t_len, chunk
    )
    for cut in range(1, t_len):
        first_o, mid = gated_delta_chunked(
            q[:cut], k[:cut], v[:cut], g[:cut], beta[:cut], pack_state(state), cut, chunk
        )
        rest_o, end = gated_delta_chunked(
            q[cut:], k[cut:], v[cut:], g[cut:], beta[cut:], mid, t_len - cut, chunk
        )
        close(jnp.concatenate([first_o, rest_o]), whole_o)
        close(end, whole_s)


@pytest.mark.parametrize("length", [0, 1, 9, 64, 70])
def test_padded_positions_leave_the_state_alone(length):
    """A bucket of 96 with `length` real tokens: the state is the one the
    last real token left, whatever the padding holds, and the real
    positions' outputs are the unpadded call's."""
    q, k, v, g, beta, state = inputs(96, 11)
    want_o, want_s = recurrence(
        q[:length], k[:length], v[:length], g[:length], beta[:length], state
    )
    o, packed = jax.jit(gated_delta_chunked)(
        q, k, v, g, beta, pack_state(state), jnp.int32(length)
    )
    close(o[:length], want_o)
    close(unpack_state(packed), want_s)
    if length == 0:
        np.testing.assert_array_equal(unpack_state(packed), state)


# Lanes and (H, K, V): a toy, and Olmo Hybrid's slot, [15, 96, 384] packed.
UPDATE_SHAPES = {"toy": (5, (H, K, V)), "real": (2, (30, 96, 192))}


@pytest.mark.parametrize("decay", ["mixed", "near_one", "near_zero"])
@pytest.mark.parametrize("shape", list(UPDATE_SHAPES))
@pytest.mark.parametrize("idle", [-1, 1])  # -1: every lane is live
def test_one_pass_update_is_the_two_step_recurrence(decay, shape, idle):
    """The kernel (interpreted here) against the recurrence, lane by lane;
    a lane that is not live keeps its state bit for bit, whatever its o."""
    lanes, dims = UPDATE_SHAPES[shape]
    q, k, v, g, beta, _ = inputs(lanes, 3, decay, dims)
    rng = np.random.default_rng(4)
    states = jnp.asarray(rng.standard_normal((lanes, *dims)).astype(np.float32))
    live = jnp.arange(lanes) != idle
    o, new = jax.jit(gated_delta_update)(q, k, v, g, beta, pack_state(states), live)
    assert o.shape == v.shape and o.dtype == new.dtype == jnp.float32
    for lane in range(lanes):
        if lane == idle:
            np.testing.assert_array_equal(unpack_state(new)[lane], states[lane])
            continue
        want_o, want_s = recurrence(
            *(x[lane][None] for x in (q, k, v, g, beta)), states[lane]
        )
        close(o[lane], want_o[0], 1e-5)
        close(unpack_state(new)[lane], want_s, 1e-5)


def test_update_after_chunks_is_the_recurrence():
    """Prefill in two chunks, then decode token by token, as the runner
    does: one sequence's outputs and state."""
    t_len, prompt = 60, 37
    q, k, v, g, beta, state = inputs(t_len, 9)
    want_o, want_s = recurrence(q, k, v, g, beta, state)
    cut = 20
    _, s = gated_delta_chunked(
        q[:cut], k[:cut], v[:cut], g[:cut], beta[:cut], pack_state(state), cut, 8
    )
    o, s = gated_delta_chunked(
        q[cut:prompt], k[cut:prompt], v[cut:prompt], g[cut:prompt],
        beta[cut:prompt], s, prompt - cut, 8,
    )
    close(o, want_o[cut:prompt])
    for t in range(prompt, t_len):
        o_t, s = gated_delta_update(
            q[t][None], k[t][None], v[t][None], g[t][None], beta[t][None], s[None],
            jnp.ones((1,), bool),
        )
        s = s[0]
        close(o_t[0], want_o[t])
    close(unpack_state(s), want_s)
