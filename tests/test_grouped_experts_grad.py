"""The gradient of the dropless grouped experts
(`ray_tpu.ops.grouped_experts.routed_grouped`, whose backward is its own):
with respect to the rows, the gates and both expert matrices, against JAX's
differentiation of `routed_dense` and of a per-token float32 loop, under
uniform routing, one expert taking every token, one taking none, and some
choices served by no expert here. No capacity, so no case drops a token.

`routed_grouped` touches only a prefix of the sorted assignments, one rung
of a ladder chosen from the held count (PR 41): no choice held at all,
every choice held, a held count exactly on the first rung and one past it,
and a bucket's padded tail give the same, and `rows_walked` is the rung.

And the pin of the served models' forward: sha256 of the lowered text
(`tests/test_paged_window.py`'s fingerprint) of Laguna's toy step programs,
both attention implementations. The decode programs' are the ones recorded
on fc42792, before `routed_grouped` had a backward of its own, and held
since (decode takes `routed_dense`); the four chunk programs' were re-taken
at PR 41, whose `routed_grouped` walks a rung of the sorted rows through
kernels of its own and whose chunk returns the rows walked; granite's are
pinned in `tests/test_paged_window.py`.
"""

import functools
import gc
import hashlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models.parts import local_of
from ray_tpu.ops.grouped_experts import ladder, routed_dense, routed_grouped, rows_walked

T, D, F, EXPERTS, K = 24, 16, 8, 6, 3
WRT = ("rows", "gates", "w_in", "w_out")
# Shapes whose ladder has two rungs, 256 and 512 of 128 x 4 sorted rows
# (three of eight experts held: a quarter over their share is 240 rows).
WIDE_T, WIDE_K, WIDE_EXPERTS, WIDE_HELD = 128, 4, 8, (0, 1, 2)
CASES = ["uniform", "one_takes_every_token", "one_takes_none", "some_absent"]
PREFIX_CASES = [
    "none_held", "every_choice_held", "on_a_rung", "one_past_a_rung", "padded_tail",
]


def per_token(x, ids, gates, table, w_in, w_out):
    """Token by token, choice by choice: w_out_e (silu(g) * u) times the
    gate, for the choices an expert here serves."""
    out = []
    for t in range(x.shape[0]):
        total = jnp.zeros(x.shape[1], jnp.float32)
        for c in range(ids.shape[1]):
            row = int(table[ids[t, c]])
            if row < 0:
                continue
            g, u = jnp.split(x[t] @ w_in[row], 2)
            total = total + gates[t, c] * ((jax.nn.silu(g) * u) @ w_out[row])
        out.append(total)
    return jnp.stack(out)


def routing(case):
    """(ids [T, K], experts held) of a case."""
    rng = np.random.RandomState(3)
    held = tuple(range(EXPERTS))
    if case in ("uniform", "padded_tail"):
        ids = np.stack([rng.permutation(EXPERTS)[:K] for _ in range(T)])
    elif case == "one_takes_every_token":
        ids = np.stack([np.concatenate([[2], rng.permutation([0, 1, 3, 4, 5])[: K - 1]])
                        for _ in range(T)])
    elif case == "one_takes_none":
        ids = np.stack([rng.permutation([0, 1, 2, 3, 5])[:K] for _ in range(T)])
    elif case == "some_absent":
        ids = np.stack([rng.permutation(EXPERTS)[:K] for _ in range(T)])
        held = (4, 1, 3)  # and not in the order of their ids
    elif case == "none_held":
        ids = np.stack([rng.permutation([0, 2, 5])[:K] for _ in range(T)])
        held = (4, 1, 3)
    elif case == "every_choice_held":
        ids = np.stack([rng.permutation([4, 1, 3, 0])[:K] for _ in range(T)])
        held = (4, 1, 3, 0)  # of six: the ladder has a rung under every row
    elif case in ("on_a_rung", "one_past_a_rung"):
        # Every token's first two choices are held, 256 rows, the first
        # rung to the row; one more held choice is one row past it.
        absent = np.stack([rng.permutation([3, 4, 5, 6, 7])[:2] for _ in range(WIDE_T)])
        ids = np.concatenate(
            [np.zeros((WIDE_T, 1), int), np.ones((WIDE_T, 1), int), absent], axis=1
        )
        if case == "one_past_a_rung":
            ids[7, 2] = 2
        held = WIDE_HELD
    else:
        raise ValueError(case)
    return jnp.asarray(ids, jnp.int32), held


def count_valid(case):
    """How many leading tokens of a case are real (the rest a bucket's padding)."""
    t_len = routing(case)[0].shape[0]
    return 17 if case == "padded_tail" else t_len


@functools.lru_cache(maxsize=None)
def gradients(case):
    ids, held = routing(case)
    t_len, k = ids.shape
    experts = WIDE_EXPERTS if t_len == WIDE_T else EXPERTS
    table = local_of(experts, held)
    keys = jax.random.split(jax.random.PRNGKey(0), 5)
    x = jax.random.normal(keys[0], (t_len, D), jnp.float32)
    gates = jax.nn.softmax(jax.random.normal(keys[1], (t_len, k), jnp.float32))
    w_in = 0.3 * jax.random.normal(keys[2], (len(held), D, 2 * F), jnp.float32)
    w_out = 0.3 * jax.random.normal(keys[3], (len(held), F, D), jnp.float32)
    pull = jax.random.normal(keys[4], (t_len, D), jnp.float32)
    real = count_valid(case)
    valid = jnp.arange(t_len) < real

    def of_the_real_tokens(form):
        """A reference knows no padding: it is given the real tokens, and
        the padded ones' rows of the result are nought."""
        def padded(x, g, a, b):
            out = form(x[:real], ids[:real], g[:real], table, a, b)
            return jnp.pad(out, ((0, t_len - real), (0, 0)))
        return padded

    forms = {
        "grouped": lambda x, g, a, b: routed_grouped(x, ids, g, table, a, b, valid),
        "dense": of_the_real_tokens(routed_dense),
        "per_token": of_the_real_tokens(per_token),
    }
    out = {}
    with jax.default_matmul_precision("highest"):
        for name, form in forms.items():
            value, grads = jax.value_and_grad(
                lambda *args: jnp.sum(form(*args) * pull), argnums=(0, 1, 2, 3)
            )(x, gates, w_in, w_out)
            out[name] = dict(zip(WRT, grads), value=value)
    return out


@pytest.fixture(scope="module", autouse=True)
def _leave_a_small_heap():
    """What this file traced goes when it is done: the worker that ran it
    runs other files after, and some of them time a full `gc.collect()`."""
    yield
    for cached in (gradients, laguna_programs):
        cached.cache_clear()
    jax.clear_caches()
    gc.collect()


@pytest.mark.parametrize("wrt", WRT + ("value",))
@pytest.mark.parametrize("reference", ["dense", "per_token"])
@pytest.mark.parametrize("case", CASES + PREFIX_CASES)
def test_grouped_gradient_is_the_reference(case, reference, wrt):
    found = gradients(case)
    want = np.asarray(found[reference][wrt])
    assert (np.abs(want).max() > 0) == (case != "none_held")
    np.testing.assert_allclose(found["grouped"][wrt], want, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("case", CASES + PREFIX_CASES)
def test_rows_walked_is_the_rung_the_held_count_takes(case):
    ids, held = routing(case)
    t_len, k = ids.shape
    experts = WIDE_EXPERTS if t_len == WIDE_T else EXPERTS
    local = np.asarray(local_of(experts, held))[np.asarray(ids)[: count_valid(case)]]
    here, absent = int((local >= 0).sum()), int((local < 0).sum())
    rungs = ladder(ids.size, len(held) / experts)
    assert rungs[-1] >= ids.size and all(a < b for a, b in zip(rungs, rungs[1:]))
    walked = int(jax.jit(rows_walked, static_argnums=(1, 2))(
        jnp.int32(here), ids.size, len(held) / experts
    ))
    assert walked == min(next(r for r in rungs if r >= here), ids.size)
    # Padding is no assignment, so a padded bucket may walk past both.
    assert here <= walked <= max(here + absent, min(rungs[0], ids.size))
    if case in ("on_a_rung", "one_past_a_rung"):
        assert rungs == (256, 512)
        assert (here, walked) == ((256, 256) if case == "on_a_rung" else (257, 512))
    if case == "every_choice_held":
        assert len(rungs) == 1 and walked == here == ids.size


def test_it_does_not_matter_which_absent_expert_an_absent_choice_names():
    ids, held = routing("some_absent")
    table = local_of(EXPERTS, held)
    absent = [e for e in range(EXPERTS) if e not in held]
    renamed = jnp.where(jnp.isin(ids, jnp.asarray(absent)), absent[0], ids)
    assert bool((renamed != ids).any())
    keys = jax.random.split(jax.random.PRNGKey(2), 4)
    args = (
        jax.random.normal(keys[0], (T, D), jnp.float32),
        jax.nn.softmax(jax.random.normal(keys[1], (T, K), jnp.float32)),
        jax.random.normal(keys[2], (len(held), D, 2 * F), jnp.float32),
        jax.random.normal(keys[3], (len(held), F, D), jnp.float32),
    )

    def both(ids):
        return jax.value_and_grad(
            lambda x, g, a, b: jnp.sum(
                routed_grouped(x, ids, g, table, a, b, jnp.ones((T,), bool)) ** 2
            ),
            argnums=(0, 1, 2, 3),
        )(*args)

    for one, other in zip(jax.tree_util.tree_leaves(both(ids)),
                          jax.tree_util.tree_leaves(both(renamed))):
        np.testing.assert_array_equal(one, other)


def test_an_expert_without_tokens_gets_a_zero_gradient():
    found = gradients("one_takes_none")["grouped"]
    assert not np.asarray(found["w_in"][4]).any()
    assert not np.asarray(found["w_out"][4]).any()
    assert np.asarray(found["w_in"][0]).any()


def test_padding_is_routed_nowhere_in_the_backward():
    ids, held = routing("uniform")
    table = local_of(EXPERTS, held)
    keys = jax.random.split(jax.random.PRNGKey(1), 4)
    x = jax.random.normal(keys[0], (T, D), jnp.float32)
    gates = jnp.full((T, K), 1.0 / K)
    w_in = jax.random.normal(keys[1], (EXPERTS, D, 2 * F), jnp.float32)
    w_out = jax.random.normal(keys[2], (EXPERTS, F, D), jnp.float32)
    valid = jnp.arange(T) < T // 2

    def total(x, w_in):
        return jnp.sum(routed_grouped(x, ids, gates, table, w_in, w_out, valid))

    d_x, d_w = jax.grad(total, argnums=(0, 1))(x, w_in)
    assert not np.asarray(d_x[T // 2:]).any() and np.asarray(d_x[: T // 2]).any()
    half = jax.grad(
        lambda w: jnp.sum(routed_grouped(
            x[: T // 2], ids[: T // 2], gates[: T // 2], table, w, w_out,
            jnp.ones((T // 2,), bool)))
    )(w_in)
    np.testing.assert_allclose(d_w, half, rtol=1e-5, atol=1e-5)


def test_the_backward_runs_in_bfloat16_under_jit():
    ids, held = routing("some_absent")
    table = local_of(EXPERTS, held)
    x = jnp.ones((T, D), jnp.bfloat16)
    w_in = jnp.ones((3, D, 2 * F), jnp.bfloat16) * 0.1
    w_out = jnp.ones((3, F, D), jnp.bfloat16) * 0.1
    gates = jnp.full((T, K), 1.0 / K)

    @jax.jit
    def grads(x, gates, w_in, w_out):
        return jax.grad(
            lambda *a: jnp.sum(routed_grouped(a[0], ids, a[1], table, a[2], a[3],
                                              jnp.ones((T,), bool))),
            argnums=(0, 1, 2, 3),
        )(x, gates, w_in, w_out)

    d_x, d_g, d_in, d_out = grads(x, gates, w_in, w_out)
    assert (d_x.dtype, d_g.dtype, d_in.dtype) == (jnp.bfloat16, jnp.float32, jnp.bfloat16)
    assert all(bool(jnp.isfinite(g.astype(jnp.float32)).all()) for g in (d_x, d_g, d_in, d_out))


# ---------------- the served forward programs, as before ----------------

LAGUNA_PINS = {
    "laguna.jit__decode_step.None.reference": "a846084433c778b2",
    "laguna.jit__prefill_step.16.reference": "729b88cbd8397497",
    "laguna.jit__prefill_suffix_step.16.reference": "b089a75bc9ec2eee",
    "laguna.jit__decode_step.None.pallas": "9db81997cd542f86",
    "laguna.jit__prefill_step.16.pallas": "1e60821fcd732b00",
    "laguna.jit__prefill_suffix_step.16.pallas": "10ab911daf83f953",
}


@functools.lru_cache(maxsize=None)
def laguna_programs(impl):
    from laguna_toy import toy_config

    from ray_tpu.llm.config import EngineConfig
    from ray_tpu.llm.model_runner import build_runner

    ecfg = EngineConfig(
        block_size=8, num_blocks=32, max_decode_slots=4, max_blocks_per_seq=8,
        prefill_buckets=(16, 32), max_prefill_tokens_per_step=16, attn_impl=impl,
    )
    runner = build_runner(toy_config(), ecfg, seed=0)
    found = {}
    for name, width, lowered in runner._lowered():
        text = re.sub(r'jax\.result_info = "[^"]*"', "", lowered.as_text())
        found[f"laguna.{name}.{width}.{impl}"] = hashlib.sha256(text.encode()).hexdigest()[:16]
    return found


@pytest.mark.parametrize("name", list(LAGUNA_PINS))
def test_served_forward_programs_lower_as_before(name):
    assert laguna_programs(name.rsplit(".", 1)[-1])[name] == LAGUNA_PINS[name]
