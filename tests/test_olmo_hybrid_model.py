"""`ray_tpu.models.olmo_hybrid` against its plain float32 reference, at toy
widths on seeded weights.

Tolerance, everywhere here: logits agree to 2e-5 absolute (at these widths
they are about 0.16 wide). Both sides compute in float32 and differ in the
order of sums and in the chunk's solve against the recurrence token by
token, which reads under 2e-6; each alternative to an `assumed` choice, a
state kept in bfloat16, `beta` without its factor 2 and QK-norm left out
move a logit by far more than the tolerance (asserted below).
"""

import functools
import gc
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import olmo_hybrid as oh
from ray_tpu.models import olmo_hybrid_reference as ref

from olmo_toy import toy_config

TOLERANCE = 2e-5
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


def _reference(params, tokens, **variant):
    return np.asarray(_jitted(ref.forward, CFG, **variant)(params, tokens))


def _tokens(n, seed=0):
    return jnp.asarray(np.random.RandomState(seed).randint(0, 512, n))


@pytest.fixture(scope="module")
def params():
    return oh.init_params(CFG, 7)


# Lengths that are and are not multiples of the chunk (8).
@pytest.mark.parametrize("length", [1, 5, 8, 16, 21, 40])
def test_forward_matches_reference(params, length):
    tokens = _tokens(length, length)
    got = np.asarray(_jitted(oh.forward, CFG)(params, tokens))
    want = _reference(params, tokens)
    assert want.std() > 0.05
    assert float(np.abs(got - want).max()) < TOLERANCE


@pytest.mark.parametrize("variant", [
    dict(rope_theta=500000.0), dict(pre_norm_linear=True),
    dict(state_dtype=jnp.bfloat16), dict(beta_factor=1.0), dict(qk_norm=False),
], ids=["rotary", "pre_norm", "bf16_state", "beta_without_2", "no_qk_norm"])
def test_an_alternative_moves_the_logits_far_past_the_tolerance(params, variant):
    tokens = _tokens(40, 3)
    moved = np.abs(_reference(params, tokens, **variant) - _reference(params, tokens))
    assert float(moved.max()) > 10 * TOLERANCE, float(moved.max())


def test_a_dropped_carry_between_chunks_fails_the_tolerance(params):
    """A mixer's second chunk from the first's tail and state is the
    sequence; from an empty state, or with the convolution's tail dropped,
    it is not, by far more than the tolerance."""
    p = params["layers"][0]["mixer"]
    u = jax.random.normal(jax.random.PRNGKey(2), (32, CFG.hidden_size)) * 0.1
    arrays = oh.recurrent_kinds(CFG)[oh.LINEAR].arrays
    empty = [jnp.zeros(shape, dtype) for _, shape, dtype in arrays]
    want = oh.gdn_prefill(CFG, p, u, *empty, 32)[0][16:]
    _, tail, state = oh.gdn_prefill(CFG, p, u[:16], *empty, 16)

    def gap(tail, state):
        return float(jnp.abs(oh.gdn_prefill(CFG, p, u[16:], tail, state, 16)[0] - want).max())

    assert gap(tail, state) < 1e-6
    assert gap(jnp.zeros_like(tail), state) > 1e-4
    assert gap(tail, jnp.zeros_like(state)) > 1e-4


def test_decode_steps_after_a_chunk_are_the_sequence(params):
    """`gdn_decode` lane by lane after `gdn_prefill`, through the model's
    own functions: the mixer's outputs of a whole sequence."""
    p = params["layers"][0]["mixer"]
    u = jax.random.normal(jax.random.PRNGKey(1), (24, CFG.hidden_size)) * 0.1
    arrays = oh.recurrent_kinds(CFG)[oh.LINEAR].arrays
    empty = [jnp.zeros(shape, dtype) for _, shape, dtype in arrays]
    want, *_ = oh.gdn_prefill(CFG, p, u, *empty, 24)
    out, tail, state = oh.gdn_prefill(CFG, p, u[:13], *empty, 13)
    np.testing.assert_allclose(out, want[:13], atol=1e-6)
    for t in range(13, 24):
        out, tail, state = oh.gdn_decode(
            CFG, p, u[t][None], tail[None], state[None], jnp.ones((1,), bool)
        )
        tail, state = tail[0], state[0]
        np.testing.assert_allclose(out[0], want[t], atol=1e-6)


def test_parameter_count_is_the_published_models():
    cfg = oh.OlmoHybridConfig()
    shapes = oh._leaf_shapes(cfg)
    size = lambda tree: sum(  # noqa: E731
        int(np.prod(s)) for s in jax.tree_util.tree_leaves(
            tree, is_leaf=lambda v: isinstance(v, tuple)
        )
    )
    assert size(shapes["layers"][0]) == 215_570_172  # a linear layer
    assert size(shapes["layers"][3]) == 185_809_920  # a full layer
    assert size(shapes) == 8 * 832_520_436 + 770_703_360 + 3840
    kind = oh.recurrent_kinds(cfg)[oh.LINEAR]
    slot = sum(int(np.prod(shape)) * jnp.dtype(dtype).itemsize for _, shape, dtype in kind.arrays)
    assert slot == 2_280_960  # a layer's state and tail a lane


def test_config_refuses_what_it_cannot_run():
    with pytest.raises(ValueError, match="layer types"):
        toy_config(layer_types=("mamba",))
    with pytest.raises(ValueError, match="side by side"):
        toy_config(linear_num_key_heads=3, linear_num_value_heads=3)
    with pytest.raises(ValueError, match="key heads"):
        toy_config(linear_num_key_heads=2)


def test_import_ray_tpu_imports_none_of_the_new_modules():
    code = (
        "import sys, ray_tpu, ray_tpu.models, ray_tpu.llm; "
        "assert not [m for m in sys.modules if 'olmo' in m or 'gated_delta' in m]"
    )
    subprocess.run([sys.executable, "-c", code], check=True)
