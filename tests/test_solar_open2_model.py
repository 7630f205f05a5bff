"""`ray_tpu.models.solar_open2` against its plain float32 reference, at toy
widths on seeded weights, the router's third rule, and the shares of the
routed experts against the uncut layer.

Tolerance, everywhere here: logits agree to 2e-5 absolute (at these widths
they are about 0.15 wide). Both sides compute in float32 and differ in the
order of sums and in the chunk's solve against the recurrence token by
token, which reads under 3e-6; each alternative to an `assumed` choice, the
scalar decay, `beta` without its factor 2, a missing selection bias, gate or
shared expert and a state kept in bfloat16 move a logit by far more than the
tolerance (asserted below).
"""

import functools
import gc
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import parts
from ray_tpu.models import solar_open2 as so
from ray_tpu.models import solar_open2_reference as ref
from ray_tpu.ops.grouped_experts import route

from solar_open2_toy import held_params, toy_config

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
    return so.init_params(CFG, 7)


# Lengths that are and are not multiples of the chunk (8).
@pytest.mark.parametrize("grouped", [True, False], ids=["grouped", "dense"])
@pytest.mark.parametrize("length", [1, 5, 8, 16, 21, 40])
def test_forward_matches_reference(params, length, grouped):
    tokens = _tokens(length, length)
    got = np.asarray(_jitted(so.forward, CFG, grouped=grouped)(params, tokens))
    want = _reference(params, tokens)
    assert want.std() > 0.05
    assert float(np.abs(got - want).max()) < TOLERANCE


@pytest.mark.parametrize("variant", [
    dict(router_score="softmax"), dict(selection_bias=False),
    dict(gate_form="headwise"), dict(gate_form=None), dict(qk_norm=True),
    dict(post_norm=True), dict(shared_expert=False), dict(scalar_decay=True),
    dict(beta_factor=1.0), dict(state_dtype=jnp.bfloat16),
], ids=["softmax_router", "no_selection_bias", "headwise_gate", "no_gate", "qk_norm",
        "post_norm", "no_shared_expert", "scalar_decay", "beta_without_2", "bf16_state"])
def test_an_alternative_moves_the_logits_far_past_the_tolerance(params, variant):
    tokens = _tokens(40, 3)
    moved = np.abs(_reference(params, tokens, **variant) - _reference(params, tokens))
    assert float(moved.max()) > 10 * TOLERANCE, float(moved.max())


def test_a_dropped_carry_between_chunks_fails_the_tolerance(params):
    """A mixer's second chunk from the first's tail and state is the
    sequence; from an empty state, or with the convolution's tail dropped,
    it is not, by far more than the tolerance."""
    p = params["layers"][1]["mixer"]
    u = jax.random.normal(jax.random.PRNGKey(2), (32, CFG.hidden_size))
    arrays = so.recurrent_kinds(CFG)[so.KDA].arrays
    empty = [jnp.zeros(shape, dtype) for _, shape, dtype in arrays]
    want = so.kda_prefill(CFG, p, u, *empty, 32)[0][16:]
    _, tail, state = so.kda_prefill(CFG, p, u[:16], *empty, 16)

    def gap(tail, state):
        return float(jnp.abs(so.kda_prefill(CFG, p, u[16:], tail, state, 16)[0] - want).max())

    assert gap(tail, state) < 1e-6
    assert gap(jnp.zeros_like(tail), state) > 1e-4
    assert gap(tail, jnp.zeros_like(state)) > 1e-4


def test_decode_steps_after_a_chunk_are_the_sequence(params):
    """`kda_decode` lane by lane after `kda_prefill`, through the model's
    own functions: the mixer's outputs of a whole sequence."""
    p = params["layers"][1]["mixer"]
    u = jax.random.normal(jax.random.PRNGKey(1), (24, CFG.hidden_size))
    arrays = so.recurrent_kinds(CFG)[so.KDA].arrays
    empty = [jnp.zeros(shape, dtype) for _, shape, dtype in arrays]
    want, *_ = so.kda_prefill(CFG, p, u, *empty, 24)
    out, tail, state = so.kda_prefill(CFG, p, u[:13], *empty, 13)
    np.testing.assert_allclose(out, want[:13], atol=1e-6)
    for t in range(13, 24):
        out, tail, state = so.kda_decode(
            CFG, p, u[t][None], tail[None], state[None], jnp.ones((1,), bool)
        )
        tail, state = tail[0], state[0]
        np.testing.assert_allclose(out[0], want[t], atol=1e-6)


def test_the_seeded_decay_differs_across_the_channels_of_a_head(params):
    """A channel's decay a token lies between exp(-1.6) and 1 and a head's
    channels do not forget alike (the scalar rule would not be noticed)."""
    p = params["layers"][1]["mixer"]
    u = jax.random.normal(jax.random.PRNGKey(3), (16, CFG.hidden_size))
    _, _, g, _ = so._kda_project(CFG, p, u)
    g = np.asarray(g)
    assert g.shape == (16, 4, 8) and (g < 0).all() and g.min() > -1.7
    assert (g.max(-1) / g.min(-1)).mean() < 0.5


# ---------------- the router's rule and the shares ----------------


def test_route_sigmoid_with_a_selection_bias():
    """The bias moves the choice and not the weights; weights sum to the
    scale; without a bias the choice is the largest scores'."""
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.standard_normal((9, 16)).astype(np.float32))
    router = jnp.asarray(rng.standard_normal((16, 8)).astype(np.float32))
    bias = jnp.asarray(rng.uniform(-0.5, 0.5, 8).astype(np.float32))
    scores = 1.0 / (1.0 + np.exp(-(np.asarray(x) @ np.asarray(router))))
    ids, gates = route(x, router, 3, score="sigmoid", scale=1.0, bias=bias)
    order = np.argsort(-(scores + np.asarray(bias)), axis=-1)[:, :3]
    assert (np.sort(np.asarray(ids), -1) == np.sort(order, -1)).all()
    top = np.take_along_axis(scores, np.asarray(ids), -1)
    assert np.abs(np.asarray(gates) - top / top.sum(-1, keepdims=True)).max() < 1e-6
    assert np.allclose(np.asarray(gates).sum(-1), 1.0, atol=1e-6)
    plain, plain_gates = route(x, router, 3, score="sigmoid", scale=2.5)
    assert (np.sort(np.asarray(plain), -1) == np.sort(np.argsort(-scores, -1)[:, :3], -1)).all()
    assert np.allclose(np.asarray(plain_gates).sum(-1), 2.5, atol=1e-5)
    # The bias changed some token's choice, and where it did not, no weight.
    same = (np.sort(np.asarray(plain), -1) == np.sort(np.asarray(ids), -1)).all(-1)
    assert 0 < same.sum() < len(same)
    assert np.abs(
        np.sort(np.asarray(plain_gates)[same], -1) / 2.5 - np.sort(np.asarray(gates)[same], -1)
    ).max() < 1e-6


@pytest.mark.parametrize("grouped", [True, False])
def test_the_shares_add_up(grouped):
    """The eight shares' routed parts, with the shared expert counted once,
    equal the uncut layer: in the program's expert layer and in the
    reference's."""
    cfg_all = toy_config(experts_held=tuple(range(8)))
    params = so.init_params(cfg_all, 7)
    p = params["layers"][2]
    x = jnp.asarray(np.random.default_rng(6).standard_normal((11, cfg_all.hidden_size)), jnp.float32)
    whole, counts = parts.experts(cfg_all, p, x, grouped=grouped)
    shared = parts.gated_mlp(x, p["shared_in"], p["shared_out"], cfg_all.dtype)
    total, held, ref_total = shared, 0, 0.0
    for expert in range(8):
        cfg_one = toy_config(experts_held=(expert,))
        p_one = held_params(params, cfg_all, (expert,))["layers"][2]
        out, counts_one = parts.experts(cfg_one, p_one, x, grouped=grouped)
        total = total + (out - shared)
        held += int(counts_one["held"])
        assert int(counts_one["held"]) + int(counts_one["absent"]) == 11 * 3
        ref_total = ref_total + ref.routed_experts(cfg_one, p_one, x)
    assert np.abs(np.asarray(total) - np.asarray(whole)).max() < TOLERANCE
    assert held == 11 * 3 == int(counts["held"]) and int(counts["absent"]) == 0
    ref_whole = ref.moe(cfg_all, p, x)
    assert np.abs(np.asarray(ref_total + shared) - np.asarray(ref_whole)).max() < TOLERANCE
    assert np.abs(np.asarray(whole) - np.asarray(ref_whole)).max() < TOLERANCE


def test_a_share_through_the_whole_model(params):
    """Half the experts held (the toy's default) against the reference given
    the same share, and not the uncut model's answer."""
    tokens = _tokens(24, 9)
    cfg_all = toy_config(experts_held=tuple(range(8)))
    all_params = so.init_params(cfg_all, 3)
    cut = held_params(all_params, cfg_all, CFG.experts_held)
    got = np.asarray(_jitted(so.forward, CFG)(cut, tokens))
    want = _reference(cut, tokens)
    uncut = np.asarray(_jitted(ref.forward, cfg_all)(all_params, tokens))
    assert np.abs(got - want).max() < TOLERANCE
    assert np.abs(got - uncut).max() > 1000 * TOLERANCE


# ---------------- the configuration ----------------


def test_parameter_count_is_the_published_models():
    cfg = so.SolarOpen2Config()
    shapes = so._leaf_shapes(cfg)
    size = lambda tree: sum(  # noqa: E731
        int(np.prod(s)) for s in jax.tree_util.tree_leaves(
            tree, is_leaf=lambda v: isinstance(v, tuple)
        )
    )
    assert cfg.layer_types[:5] == ("gqa", "kda", "kda", "kda", "gqa")
    assert cfg.layer_types.count("gqa") == 12
    routed = 320 * 3 * 4096 * 1280
    outside = 3 * 4096 * 1280 + 4096 * 320 + 320 + 2 * 4096  # shared, router, bias, norms
    assert size(shapes["layers"][1]) == 137_740_480 + outside + routed  # a KDA layer
    assert size(shapes["layers"][0]) == 109_051_904 + outside + routed  # a GQA layer
    assert size(shapes) > 245e9  # "250B"
    kind = so.recurrent_kinds(cfg)[so.KDA]
    slot = sum(int(np.prod(shape)) * jnp.dtype(dtype).itemsize for _, shape, dtype in kind.arrays)
    assert slot == 64 * 128 * 128 * 4 + 3 * 24576 * 2  # a layer's state and tail a lane


def test_config_refuses_what_it_cannot_run():
    with pytest.raises(ValueError, match="gqa_layers"):
        toy_config(gqa_layers=(0, 7))
    with pytest.raises(ValueError, match="cached heads"):
        toy_config(num_key_value_heads=3)
    with pytest.raises(ValueError, match="experts_held"):
        toy_config(experts_held=(0, 0))
    with pytest.raises(ValueError, match="shared expert"):
        toy_config(n_shared_experts=2)


def test_import_ray_tpu_imports_none_of_the_new_modules():
    code = (
        "import sys, ray_tpu, ray_tpu.models, ray_tpu.llm; "
        "assert not [m for m in sys.modules if 'solar' in m or 'kda' in m]"
    )
    subprocess.run([sys.executable, "-c", code], check=True)
