"""`ray_tpu.models.falcon_h1` against its plain float32 reference, at toy
widths on seeded weights.

Tolerance, everywhere here: logits agree to 5e-5 absolute (at these widths
they are about 1 wide, by the seeded draw's design). Both sides compute in
float32 and differ in the order of sums and in the chunked scan against the
recurrence token by token, which reads under 1e-5; each alternative to an
`assumed` choice, each multiplier left out, each branch left out, a state
kept in bfloat16 and heads that read the wrong group's B and C move a logit
by far more than the tolerance (asserted below).
"""

import dataclasses
import functools
import gc
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import falcon_h1 as fh
from ray_tpu.models import falcon_h1_reference as ref

from falcon_h1_toy import toy_config

TOLERANCE = 5e-5
CFG = toy_config()


@pytest.fixture(scope="module", autouse=True)
def _leave_a_small_heap():
    """What this file traced goes when it is done: the worker that ran it
    runs other files after, and some of them time a full `gc.collect()`."""
    yield
    _jitted.cache_clear()
    jax.clear_caches()
    gc.collect()


@functools.lru_cache(maxsize=None)
def _jitted(fn, cfg, **static):
    return jax.jit(functools.partial(fn, cfg, **static))


def _reference(params, tokens, cfg=CFG, **variant):
    return np.asarray(_jitted(ref.forward, cfg, **variant)(params, tokens))


def _tokens(n, seed=0):
    return jnp.asarray(np.random.RandomState(seed).randint(0, 512, n))


@pytest.fixture(scope="module")
def params():
    return fh.init_params(CFG, 7)


# Lengths that are and are not multiples of the chunk (8).
@pytest.mark.parametrize("length", [1, 5, 8, 16, 21, 40])
def test_forward_matches_reference(params, length):
    tokens = _tokens(length, length)
    got = np.asarray(_jitted(fh.forward, CFG)(params, tokens))
    want = _reference(params, tokens)
    assert want.std() > 0.3
    assert float(np.abs(got - want).max()) < TOLERANCE


@pytest.mark.parametrize("variant", [
    dict(norm_groups=1), dict(multiplier_order=(4, 3, 2, 1, 0)),
    dict(dt_limit=(0.001, 0.1)), dict(state_dtype=jnp.bfloat16),
    dict(branches=("mamba",)), dict(branches=("full_attention",)),
    dict(shared_group=True),
], ids=["norm_over_all", "multiplier_order", "dt_clamped", "bf16_state",
        "no_attention", "no_mamba", "one_groups_b_and_c"])
def test_an_alternative_moves_the_logits_far_past_the_tolerance(params, variant):
    tokens = _tokens(40, 3)
    moved = np.abs(_reference(params, tokens, **variant) - _reference(params, tokens))
    assert float(moved.max()) > 10 * TOLERANCE, float(moved.max())


MULTIPLIERS = [
    "embedding_multiplier", "attention_in_multiplier", "attention_out_multiplier",
    "key_multiplier", "lm_head_multiplier", "ssm_in_multiplier", "ssm_out_multiplier",
    "mlp_multipliers", "ssm_multipliers",
]


@pytest.mark.parametrize("name", MULTIPLIERS)
def test_a_multiplier_left_out_moves_the_logits(params, name):
    value = getattr(CFG, name)
    ones = tuple(1.0 for _ in value) if isinstance(value, tuple) else 1.0
    without = dataclasses.replace(CFG, **{name: ones})
    tokens = _tokens(40, 4)
    moved = np.abs(_reference(params, tokens, without) - _reference(params, tokens))
    assert float(moved.max()) > 10 * TOLERANCE, float(moved.max())
    # and the model puts it where the reference does, at another value too
    other = tuple(1.5 * v for v in value) if isinstance(value, tuple) else 1.5 * value
    changed = dataclasses.replace(CFG, **{name: other})
    got = np.asarray(_jitted(fh.forward, changed)(params, tokens))
    assert float(np.abs(got - _reference(params, tokens, changed)).max()) < TOLERANCE


def test_every_branch_adds_something_of_the_streams_size(params):
    """The seeded draw's purpose (`init_std`): at the published multipliers
    each of a layer's three branches is within a factor of four of the
    stream it is added to, in every layer."""
    tokens = _tokens(40, 5)
    f32 = lambda tree: jax.tree_util.tree_map(lambda x: x.astype(jnp.float32), tree)  # noqa: E731
    rms = lambda x: float(jnp.sqrt(jnp.mean(x * x)))  # noqa: E731
    with jax.default_matmul_precision("highest"):
        h = CFG.embedding_multiplier * params["wte"][tokens]
        assert 0.8 < rms(h) < 1.25
        for p in map(f32, params["layers"]):
            u = ref._rms_norm(h, p["norm1"], CFG.rms_norm_eps)
            m = ref.mamba_mixer(CFG, p["mamba"], u)
            a = ref.attention_mixer(CFG, p["full_attention"], u)
            after = ref.layer(CFG, p, h)
            mlp = after - (h + m + a)
            for branch in (m, a, mlp):
                assert 0.25 < rms(branch) / rms(h) < 4.0, (rms(branch), rms(h))
            h = after


def test_a_dropped_carry_between_chunks_fails_the_tolerance(params):
    """A mixer's second chunk from the first's tail and state is the whole
    sequence's; from an empty tail, or an empty state, it is not."""
    p = params["layers"][0][fh.MAMBA]
    u = jax.random.normal(jax.random.PRNGKey(1), (24, CFG.hidden_size))
    arrays = fh.recurrent_kinds(CFG)[fh.MAMBA].arrays
    empty = [jnp.zeros(shape, dtype) for _, shape, dtype in arrays]
    whole, tail, ssm = fh.mamba_prefill(CFG, p, u, *empty, 24)
    first, tail1, ssm1 = fh.mamba_prefill(CFG, p, u[:13], *empty, 13)
    second, tail2, ssm2 = fh.mamba_prefill(CFG, p, u[13:], tail1, ssm1, 11)
    assert float(jnp.abs(jnp.concatenate([first, second]) - whole).max()) < TOLERANCE
    assert float(jnp.abs(tail2 - tail).max()) == 0.0
    assert float(jnp.abs(ssm2 - ssm).max()) < TOLERANCE
    for carried in ((empty[0], ssm1), (tail1, empty[1])):
        wrong = fh.mamba_prefill(CFG, p, u[13:], *carried, 11)[0]
        assert float(jnp.abs(wrong - whole[13:]).max()) > 10 * TOLERANCE


def test_decode_continues_a_prefilled_mixer(params):
    """One token at a time from a chunk's tail and state gives what the
    whole sequence gives, and a lane that is not live keeps both."""
    p = params["layers"][1][fh.MAMBA]
    u = jax.random.normal(jax.random.PRNGKey(2), (20, CFG.hidden_size))
    arrays = fh.recurrent_kinds(CFG)[fh.MAMBA].arrays
    empty = [jnp.zeros(shape, dtype) for _, shape, dtype in arrays]
    whole = fh.mamba_prefill(CFG, p, u, *empty, 20)[0]
    _, tail, ssm = fh.mamba_prefill(CFG, p, u[:15], *empty, 15)
    noise = [jax.random.normal(jax.random.PRNGKey(3), a.shape, a.dtype) for a in (tail, ssm)]
    tails, ssms = (jnp.stack([a, b]) for a, b in zip((tail, ssm), noise))
    live = jnp.asarray([True, False])
    for t in range(15, 20):
        out, tails, ssms = fh.mamba_decode(
            CFG, p, jnp.stack([u[t], u[t]]), tails, ssms, live
        )
        assert float(jnp.abs(out[0] - whole[t]).max()) < TOLERANCE
    np.testing.assert_array_equal(np.asarray(tails[1]), np.asarray(noise[0]))
    np.testing.assert_array_equal(np.asarray(ssms[1]), np.asarray(noise[1]))


def test_parameter_count_at_the_published_widths():
    """From the shapes alone: a layer and the whole of Falcon-H1-34B."""
    cfg = fh.FalconH1Config()
    shapes = fh._leaf_shapes(cfg)
    count = lambda tree: sum(  # noqa: E731
        int(np.prod(s)) for s in jax.tree_util.tree_leaves(
            tree, is_leaf=lambda v: isinstance(v, tuple))
    )
    assert count(shapes["layers"][0]) == 430_120_032
    assert count(shapes) == 72 * 430_120_032 + 2 * 261_120 * 5_120 + 5_120
    assert fh.mup_vector(cfg).shape == (9_248,)
    stds = fh.init_std(cfg)
    assert abs(stds["q"] - 0.01976) < 1e-4 and abs(stds["wte"] - 0.17678) < 1e-4


def test_config_refuses_what_it_cannot_run():
    for changes in (
        dict(mamba_n_groups=3), dict(num_key_value_heads=3), dict(mamba_d_ssm=48),
        dict(mamba_norm_before_gate=True), dict(ssm_multipliers=(1.0, 1.0)),
    ):
        with pytest.raises(ValueError):
            toy_config(**changes)


def test_import_ray_tpu_imports_none_of_the_new_modules():
    code = (
        "import sys, ray_tpu, ray_tpu.models, ray_tpu.llm;"
        "bad = [m for m in sys.modules if 'falcon_h1' in m];"
        "print(bad); sys.exit(bool(bad))"
    )
    done = subprocess.run([sys.executable, "-c", code], capture_output=True, text=True)
    assert done.returncode == 0, done.stdout + done.stderr
