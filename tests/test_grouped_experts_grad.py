"""The gradient of the dropless grouped experts
(`ray_tpu.ops.grouped_experts.routed_grouped`, whose backward is its own):
with respect to the rows, the gates and both expert matrices, against JAX's
differentiation of `routed_dense` and of a per-token float32 loop, under
uniform routing, one expert taking every token, one taking none, and some
choices served by no expert here. No capacity, so no case drops a token.

And the pin that the backward left the served models' forward alone:
sha256 of the lowered text (`tests/test_paged_window.py`'s fingerprint) of
Laguna's toy step programs, both attention implementations, recorded on the
parent commit of the PR that gave `routed_grouped` its own backward
(fc42792); granite's are pinned in `tests/test_paged_window.py` and held.
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
from ray_tpu.ops.grouped_experts import routed_dense, routed_grouped

T, D, F, EXPERTS, K = 24, 16, 8, 6, 3
WRT = ("rows", "gates", "w_in", "w_out")


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
    if case == "uniform":
        ids = np.stack([rng.permutation(EXPERTS)[:K] for _ in range(T)])
    elif case == "one_takes_every_token":
        ids = np.stack([np.concatenate([[2], rng.permutation([0, 1, 3, 4, 5])[: K - 1]])
                        for _ in range(T)])
    elif case == "one_takes_none":
        ids = np.stack([rng.permutation([0, 1, 2, 3, 5])[:K] for _ in range(T)])
    elif case == "some_absent":
        ids = np.stack([rng.permutation(EXPERTS)[:K] for _ in range(T)])
        held = (4, 1, 3)  # and not in the order of their ids
    else:
        raise ValueError(case)
    return jnp.asarray(ids, jnp.int32), held


@functools.lru_cache(maxsize=None)
def gradients(case):
    ids, held = routing(case)
    table = local_of(EXPERTS, held)
    keys = jax.random.split(jax.random.PRNGKey(0), 5)
    x = jax.random.normal(keys[0], (T, D), jnp.float32)
    gates = jax.nn.softmax(jax.random.normal(keys[1], (T, K), jnp.float32))
    w_in = 0.3 * jax.random.normal(keys[2], (len(held), D, 2 * F), jnp.float32)
    w_out = 0.3 * jax.random.normal(keys[3], (len(held), F, D), jnp.float32)
    pull = jax.random.normal(keys[4], (T, D), jnp.float32)
    valid = jnp.ones((T,), bool)
    forms = {
        "grouped": lambda x, g, a, b: routed_grouped(x, ids, g, table, a, b, valid),
        "dense": lambda x, g, a, b: routed_dense(x, ids, g, table, a, b),
        "per_token": lambda x, g, a, b: per_token(x, ids, g, table, a, b),
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
@pytest.mark.parametrize(
    "case", ["uniform", "one_takes_every_token", "one_takes_none", "some_absent"]
)
def test_grouped_gradient_is_the_reference(case, reference, wrt):
    found = gradients(case)
    want = np.asarray(found[reference][wrt])
    assert np.abs(want).max() > 0
    np.testing.assert_allclose(found["grouped"][wrt], want, rtol=2e-5, atol=2e-5)


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
    "laguna.jit__prefill_step.16.reference": "901f5df56d98c1de",
    "laguna.jit__prefill_suffix_step.16.reference": "1207afd30bac8e0e",
    "laguna.jit__decode_step.None.pallas": "9db81997cd542f86",
    "laguna.jit__prefill_step.16.pallas": "6045433be798389a",
    "laguna.jit__prefill_suffix_step.16.pallas": "bed05f7d26093af3",
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
