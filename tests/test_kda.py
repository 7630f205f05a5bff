"""`ray_tpu.ops.kda` against the recurrence it is the chunked and the
one-pass form of (the second a Pallas kernel, interpreted on the CPU),
written here token by token in float32, the decay a vector over a head's key
channels:

    S <- diag(a) S;  d = beta (v - S^T k);  S <- S + k (outer) d;  o = S^T q

Seeded, on the CPU, float32 operands throughout (the serving path's
bfloat16 products are the model tests' business).
"""

import gc

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops.kda import _decayed_products, kda_chunked, kda_update

H, K, V = 4, 8, 12


@pytest.fixture(scope="module", autouse=True)
def _leave_a_small_heap():
    """What this file traced goes when it is done: the worker that ran it
    runs other files after, and some of them time a full `gc.collect()`."""
    yield
    jax.clear_caches()
    gc.collect()


def recurrence(q, k, v, g, beta, state):
    """Token by token, as `models/solar_open2_reference.kda_mixer`'s step."""
    def step(s, x):
        q_t, k_t, v_t, g_t, b_t = x
        s = jnp.exp(g_t)[:, :, None] * s
        d = b_t[:, None] * (v_t - jnp.sum(s * k_t[:, :, None], axis=1))
        s = s + k_t[:, :, None] * d[:, None, :]
        return s, jnp.sum(s * q_t[:, :, None], axis=1)

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
    shape = (t_len, H, K)
    g = {
        "near_one": -rng.uniform(1e-5, 1e-3, shape),
        "near_zero": -rng.uniform(5.0, 30.0, shape),
        "mixed": -np.exp(rng.uniform(np.log(1e-4), np.log(20.0), shape)),
        # exp(-30) a token in the even channels, 1 in the odd ones.
        "split": np.where(np.arange(K) % 2 == 0, -30.0, 0.0) * np.ones(shape),
    }[decay].astype(np.float32)
    state = rng.standard_normal((H, K, V)).astype(np.float32)
    return tuple(jnp.asarray(x) for x in (q, k, v, g, beta, state))


def close(got, want, tol=2e-4):
    got, want = np.asarray(got), np.asarray(want)
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, want, rtol=tol, atol=tol)


@pytest.mark.parametrize("decay", ["mixed", "near_one", "near_zero", "split"])
@pytest.mark.parametrize("chunk", [64, 8])
@pytest.mark.parametrize("t_len", [1, 7, 64, 77, 150])
def test_chunked_is_the_recurrence_from_a_carried_state(t_len, chunk, decay):
    q, k, v, g, beta, state = inputs(t_len, t_len + chunk, decay)
    want_o, want_s = recurrence(q, k, v, g, beta, state)
    o, new = kda_chunked(q, k, v, g, beta, state, t_len, chunk)
    close(o, want_o)
    close(new, want_s)


@pytest.mark.parametrize("sub", [1, 2, 4, 8, 16])
def test_the_sub_chunk_does_not_show(sub):
    """Across sub-chunks through a reference point, inside one by the
    differences themselves: the same numbers wherever the cut lies."""
    q, k, v, g, beta, state = inputs(40, 2, "near_zero")
    want_o, want_s = recurrence(q, k, v, g, beta, state)
    o, new = kda_chunked(q, k, v, g, beta, state, 40, 16, sub=sub)
    close(o, want_o)
    close(new, want_s)


def test_decayed_products_are_the_sums_written_out():
    rng = np.random.default_rng(0)
    a = rng.standard_normal((2, 8, K)).astype(np.float32)
    k = rng.standard_normal((8, K)).astype(np.float32)
    run = np.cumsum(-rng.uniform(0.0, 40.0, (8, K)), axis=0).astype(np.float32)
    got = _decayed_products(jnp.asarray(a), jnp.asarray(k), jnp.asarray(run), 4, jnp.float32)
    want = np.zeros((2, 8, 8))
    for i in range(8):
        for j in range(i + 1):
            want[:, i, j] = (
                a[:, i].astype(np.float64) * k[j] * np.exp(run[i].astype(np.float64) - run[j])
            ).sum(-1)
    close(got, want, 1e-5)


@pytest.mark.parametrize("chunk", [64, 8])
def test_any_cut_into_two_chunks_is_one_sequence(chunk):
    t_len = 21
    q, k, v, g, beta, state = inputs(t_len, 5)
    whole_o, whole_s = kda_chunked(q, k, v, g, beta, state, t_len, chunk)
    for cut in range(1, t_len):
        first_o, mid = kda_chunked(
            q[:cut], k[:cut], v[:cut], g[:cut], beta[:cut], state, cut, chunk
        )
        rest_o, end = kda_chunked(
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
    o, new = jax.jit(kda_chunked)(q, k, v, g, beta, state, jnp.int32(length))
    close(o[:length], want_o)
    close(new, want_s)
    if length == 0:
        np.testing.assert_array_equal(new, state)


def test_a_scalar_decay_is_the_gated_delta_rule():
    """With every channel of a head forgetting alike this is
    `ops.gated_delta`'s rule: the two chunked forms agree."""
    from ray_tpu.ops.gated_delta import gated_delta_chunked, pack_state, unpack_state

    q, k, v, g, beta, state = inputs(50, 6)
    g = jnp.broadcast_to(g[..., :1], g.shape)
    o, new = kda_chunked(q, k, v, g, beta, state, 50, 8)
    want_o, want_s = gated_delta_chunked(
        q, k, v, g[..., 0], beta, pack_state(state), 50, 8
    )
    close(o, want_o)
    close(new, unpack_state(want_s))


# Lanes and (H, K, V): a toy, and Solar-Open2's slot, [64, 128, 128].
UPDATE_SHAPES = {"toy": (5, (H, K, V)), "real": (2, (64, 128, 128))}


@pytest.mark.parametrize("decay", ["mixed", "near_one", "near_zero", "split"])
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
    o, new = jax.jit(kda_update)(q, k, v, g, beta, states, live)
    assert o.shape == v.shape and o.dtype == new.dtype == jnp.float32
    for lane in range(lanes):
        if lane == idle:
            np.testing.assert_array_equal(new[lane], states[lane])
            continue
        want_o, want_s = recurrence(
            *(x[lane][None] for x in (q, k, v, g, beta)), states[lane]
        )
        close(o[lane], want_o[0], 1e-5)
        close(new[lane], want_s, 1e-5)


def test_update_after_chunks_is_the_recurrence():
    """Prefill in two chunks, then decode token by token, as the runner
    does: one sequence's outputs and state."""
    t_len, prompt = 60, 37
    q, k, v, g, beta, state = inputs(t_len, 9)
    want_o, want_s = recurrence(q, k, v, g, beta, state)
    cut = 20
    _, s = kda_chunked(q[:cut], k[:cut], v[:cut], g[:cut], beta[:cut], state, cut, 8)
    o, s = kda_chunked(
        q[cut:prompt], k[cut:prompt], v[cut:prompt], g[cut:prompt],
        beta[cut:prompt], s, prompt - cut, 8,
    )
    close(o, want_o[cut:prompt])
    for t in range(prompt, t_len):
        o_t, s = kda_update(
            q[t][None], k[t][None], v[t][None], g[t][None], beta[t][None], s[None],
            jnp.ones((1,), bool),
        )
        s = s[0]
        close(o_t[0], want_o[t])
    close(s, want_s)
