"""`ray_tpu.models.granite_hybrid` against its plain float32 reference, at
toy widths on seeded weights.

Tolerance, everywhere here: logits agree to 2e-8 absolute (at these widths
they are about 0.003 wide). Both sides compute in float32 and differ only
in the order of sums (chunked scan against a scan over positions, grouped
against looped experts), which reads under 2e-9; the same reference with
its recurrent state rounded to bfloat16 moves a logit by over 1e-7
(asserted below), so computing the state one precision under the stated
float32 fails the comparisons of this file five times over.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import granite_hybrid as gh
from ray_tpu.models import granite_hybrid_reference as ref

from hybrid_toy import held_params, toy_config

TOLERANCE = 2e-8
ALL = tuple(range(8))
HALF = (0, 1, 2, 3)


@functools.lru_cache(maxsize=None)
def _jitted(fn, cfg, **static):
    """`fn(cfg, ...)` compiled once a configuration: run eagerly, the layer
    code dispatches thousands of small operations."""
    return jax.jit(functools.partial(fn, cfg, **static))


def _model(cfg, params, tokens, grouped=True):
    return _jitted(gh.forward, cfg, grouped=grouped)(params, tokens)


def _reference(cfg, params, tokens, state_dtype=None):
    return _jitted(ref.forward, cfg, state_dtype=state_dtype)(params, tokens)


def _tokens(n, seed=0):
    return jnp.asarray(np.random.RandomState(seed).randint(0, 512, n))


@pytest.fixture(scope="module")
def weights():
    cfg = toy_config(ALL)
    return cfg, gh.init_params(cfg, 7)


# (a) full-sequence forward against the reference's logits: lengths that are
# and are not multiples of the chunk (8), every expert held and half of them.
@pytest.mark.parametrize("held", [ALL, HALF], ids=["all", "half"])
@pytest.mark.parametrize("length", [1, 5, 8, 16, 21, 40])
def test_forward_matches_reference(weights, length, held):
    cfg_all, params = weights
    cfg = toy_config(held)
    params = held_params(params, cfg_all, held)
    tokens = _tokens(length, seed=length)
    want = _reference(cfg, params, tokens)
    for grouped in (True, False):
        got = _model(cfg, params, tokens, grouped)
        assert float(jnp.abs(got - want).max()) < TOLERANCE, grouped


def test_a_bfloat16_state_fails_the_tolerance(weights):
    cfg, params = weights
    tokens = _tokens(40, seed=3)
    exact = _reference(cfg, params, tokens)
    rounded = _reference(cfg, params, tokens, jnp.bfloat16)
    assert float(jnp.abs(exact - rounded).max()) > 5 * TOLERANCE


def test_a_dropped_carry_between_chunks_fails_the_tolerance(weights):
    """The seeded A, dt and convolution keep the state alive across chunks:
    a scan that restarted from an empty state at a chunk boundary would not
    pass."""
    cfg, params = weights
    tokens = _tokens(24, seed=4)
    whole = _model(cfg, params, tokens)
    restarted = _model(cfg, params, tokens[16:])
    assert float(jnp.abs(whole[16:] - restarted).max()) > 50 * TOLERANCE


# (b) the share test: the routed parts of both halves plus the shared expert
# counted once equal the uncut reference's layer output.
@pytest.mark.parametrize("layer", [0, 2], ids=["mamba_layer", "attention_layer"])
def test_both_halves_and_the_shared_expert_once_are_the_whole_layer(weights, layer):
    cfg_all, params = weights
    p_all = params["layers"][layer]
    x = jax.random.normal(jax.random.PRNGKey(1), (19, cfg_all.hidden_size))
    with jax.default_matmul_precision("highest"):
        whole = ref.routed_experts(cfg_all, p_all, x) + ref._gated_mlp(
            x, p_all["shared_in"], p_all["shared_out"]
        )
    total = jnp.zeros_like(x)
    for held in (HALF, (4, 5, 6, 7)):
        cfg = toy_config(held)
        p = held_params(params, cfg_all, held)["layers"][layer]
        for grouped in (True, False):
            out, counts = gh.experts(cfg, p, x, grouped=grouped)
            shared = gh._gated_mlp(x, p["shared_in"], p["shared_out"], cfg.dtype)
            routed = out - shared
            # every token's choices are held somewhere, and counted once
            assert int(counts["held"] + counts["absent"]) == 19 * 2
        total = total + routed
    total = total + shared
    assert float(jnp.abs(total - whole).max()) < TOLERANCE


def test_routing_counts(weights):
    cfg_all, params = weights
    cfg = toy_config(HALF)
    p = held_params(params, cfg_all, HALF)["layers"][0]
    x = jax.random.normal(jax.random.PRNGKey(2), (11, cfg.hidden_size))
    valid = jnp.arange(11) < 9
    _, counts = gh.experts(cfg, p, x, grouped=True, valid=valid)
    ids, _ = gh.route(x, p["router"], 2)
    ids = np.asarray(ids)[:9]
    assert int(counts["held"]) == int((ids < 4).sum())
    assert int(counts["absent"]) == int((ids >= 4).sum())
    load = np.bincount(ids[ids < 4], minlength=4)
    assert int(counts["load_max"]) == load.max()
    assert int(counts["touched"]) == int((load > 0).sum())


def test_padding_is_routed_nowhere_and_changes_nothing(weights):
    cfg_all, params = weights
    p = params["layers"][0]
    x = jax.random.normal(jax.random.PRNGKey(3), (12, cfg_all.hidden_size))
    valid = jnp.arange(12) < 7
    padded, _ = gh.experts(cfg_all, p, x, grouped=True, valid=valid)
    alone, _ = gh.experts(cfg_all, p, x[:7], grouped=True)
    assert float(jnp.abs(padded[:7] - alone).max()) < TOLERANCE


def test_a_token_does_not_depend_on_the_batch(weights):
    cfg_all, params = weights
    p = params["layers"][0]
    x = jax.random.normal(jax.random.PRNGKey(4), (16, cfg_all.hidden_size))
    for grouped in (True, False):
        among, _ = gh.experts(cfg_all, p, x, grouped=grouped)
        alone, _ = gh.experts(cfg_all, p, x[5:6], grouped=grouped)
        assert float(jnp.abs(among[5:6] - alone).max()) < TOLERANCE


def test_config_refuses_what_it_cannot_run():
    with pytest.raises(ValueError):
        toy_config(mamba_n_groups=3)  # 8 heads do not divide into 3 groups
    with pytest.raises(ValueError):
        toy_config(experts_held=(0, 0))
    with pytest.raises(ValueError):
        toy_config(num_key_value_heads=3)


def test_import_ray_tpu_imports_none_of_the_new_modules():
    import subprocess
    import sys

    code = (
        "import sys, ray_tpu, ray_tpu.models, ray_tpu.llm;"
        "bad = [m for m in sys.modules if 'granite_hybrid' in m or "
        "'hybrid_runner' in m or m.endswith('ops.ssd') or 'grouped_experts' in m];"
        "print(bad); sys.exit(bool(bad))"
    )
    done = subprocess.run([sys.executable, "-c", code], capture_output=True, text=True)
    assert done.returncode == 0, done.stdout + done.stderr
