"""`ray_tpu.ops.ssd` with groups of B and C: the chunked scan and the
one-token update against the recurrence written out token by token, in
float32 on seeded inputs.

    S_t = exp(dt_t A) S_{t-1} + dt_t x_t (outer) B_t;   y_t = S_t C_t

per head, head h of H reading group h // (H / G) of G. Granite has one
group and Falcon-H1 two; the cases run one, two and four through the same
two functions. Tolerance: 1e-4 absolute on outputs and states about 4 wide
and up to 20 (the chunked form sums in another order than the recurrence,
float32 against float64; that reads under 3e-5 at a chunk of 128).
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops.ssd import ssd_chunked_scan, ssm_decode_update

TOLERANCE = 1e-4
HEADS, P, N = 8, 4, 16
GROUPS = pytest.mark.parametrize("groups", [1, 2, 4], ids=["1group", "2groups", "4groups"])


def inputs(t_len, groups, seed=0):
    rng = np.random.RandomState(seed)
    x = rng.standard_normal((t_len, HEADS, P)).astype(np.float32)
    dt = np.exp(rng.uniform(np.log(0.001), np.log(0.5), (t_len, HEADS))).astype(np.float32)
    a = -rng.uniform(0.5, 16.0, (HEADS,)).astype(np.float32)
    b = rng.standard_normal((t_len, groups, N)).astype(np.float32)
    c = rng.standard_normal((t_len, groups, N)).astype(np.float32)
    state = rng.standard_normal((HEADS, P, N)).astype(np.float32)
    return x, dt, a, b, c, state


def recurrence(x, dt, a, b, c, state):
    """Token by token, a head at a time: (y [T, H, P], the last state)."""
    groups = b.shape[1]
    state = state.astype(np.float64).copy()
    ys = np.zeros(x.shape, np.float64)
    for t in range(x.shape[0]):
        for h in range(HEADS):
            g = h // (HEADS // groups)
            state[h] = np.exp(dt[t, h] * a[h]) * state[h] + dt[t, h] * np.outer(x[t, h], b[t, g])
            ys[t, h] = state[h] @ c[t, g]
    return ys, state


@functools.lru_cache(maxsize=None)
def scan(chunk):
    return jax.jit(
        functools.partial(ssd_chunked_scan, chunk=chunk), static_argnames=()
    )


def run_scan(x, dt, a, b, c, state, chunk, length=None):
    y, last = scan(chunk)(
        *(jnp.asarray(v) for v in (x, dt, a, b, c, state)),
        length=jnp.int32(x.shape[0] if length is None else length),
    )
    return np.asarray(y), np.asarray(last)


# Lengths that are and are not multiples of the chunk, from a carried state.
@GROUPS
@pytest.mark.parametrize("chunk,length", [
    (8, 1), (8, 5), (8, 8), (8, 13), (8, 21), (8, 40),
    (128, 100), (128, 128), (128, 200), (128, 300),
])
def test_chunked_scan_matches_the_recurrence(groups, chunk, length):
    args = inputs(length, groups, seed=length)
    want_y, want_state = recurrence(*args)
    y, state = run_scan(*args, chunk)
    assert float(np.abs(y - want_y).max()) < TOLERANCE
    assert float(np.abs(state - want_state).max()) < TOLERANCE


@GROUPS
def test_a_sequence_cut_anywhere_into_two_chunks_is_one(groups):
    t_len = 21
    x, dt, a, b, c, state = inputs(t_len, groups, seed=3)
    whole_y, whole_state = run_scan(x, dt, a, b, c, state, 8)
    for cut in range(1, t_len):
        y1, s1 = run_scan(x[:cut], dt[:cut], a, b[:cut], c[:cut], state, 8)
        y2, s2 = run_scan(x[cut:], dt[cut:], a, b[cut:], c[cut:], s1, 8)
        assert float(np.abs(np.concatenate([y1, y2]) - whole_y).max()) < TOLERANCE, cut
        assert float(np.abs(s2 - whole_state).max()) < TOLERANCE, cut


@GROUPS
@pytest.mark.parametrize("length", [1, 7, 8, 19])
def test_a_buckets_padding_never_reaches_the_state(groups, length):
    """Positions at or past `length` (here noise, not zeros) are a no-op of
    the recurrence: exact for any length up to the bucket."""
    x, dt, a, b, c, state = inputs(24, groups, seed=length)
    want_y, want_state = recurrence(x[:length], dt[:length], a, b[:length], c[:length], state)
    y, last = run_scan(x, dt, a, b, c, state, 8, length=length)
    assert float(np.abs(y[:length] - want_y).max()) < TOLERANCE
    assert float(np.abs(last - want_state).max()) < TOLERANCE


@GROUPS
def test_one_token_update_matches_the_recurrence_and_leaves_idle_lanes(groups):
    lanes = 5
    rng = np.random.RandomState(7)
    per_lane = [inputs(1, groups, seed=10 + lane) for lane in range(lanes)]
    x, dt, b, c = (
        np.stack([args[i][0] for args in per_lane]) for i in (0, 1, 3, 4)
    )
    a = per_lane[0][2]
    states = rng.standard_normal((lanes, HEADS, P, N)).astype(np.float32)
    live = np.array([True, False, True, True, False])
    y, after = jax.jit(ssm_decode_update)(
        *(jnp.asarray(v) for v in (x, dt, a, b, c, states, live))
    )
    y, after = np.asarray(y), np.asarray(after)
    for lane in range(lanes):
        if not live[lane]:
            np.testing.assert_array_equal(after[lane], states[lane])
            continue
        want_y, want_state = recurrence(
            x[lane][None], dt[lane][None], a, b[lane][None], c[lane][None], states[lane]
        )
        assert float(np.abs(y[lane] - want_y[0]).max()) < TOLERANCE
        assert float(np.abs(after[lane] - want_state).max()) < TOLERANCE


@pytest.mark.parametrize("chunk,length", [(8, 21), (128, 200)])
def test_one_group_is_every_group_reading_the_same_b_and_c(chunk, length):
    """Granite's case: one group gives, bit for bit where the sums are the
    same and within rounding where XLA reorders them, what two groups that
    hold the same B and C give, and both give the recurrence."""
    x, dt, a, b, c, state = inputs(length, 1, seed=5)
    one_y, one_state = run_scan(x, dt, a, b, c, state, chunk)
    two_y, two_state = run_scan(
        x, dt, a, np.repeat(b, 2, axis=1), np.repeat(c, 2, axis=1), state, chunk
    )
    assert float(np.abs(one_y - two_y).max()) < 1e-5
    assert float(np.abs(one_state - two_state).max()) < 1e-5
    want_y, want_state = recurrence(x, dt, a, b, c, state)
    assert float(np.abs(one_y - want_y).max()) < TOLERANCE
    assert float(np.abs(one_state - want_state).max()) < TOLERANCE
