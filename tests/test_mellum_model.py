"""`ray_tpu.models.mellum` (flash kernels interpreted, grouped experts with
their own backward, a layer recomputed in the backward pass all but its
flash kernel, whose two residuals it keeps) against
`ray_tpu.models.mellum_reference` (dense masked attention, a loop over a
token's chosen experts, `jax.grad` of the plain loss), in float32 at the toy
widths of `tests/mellum_toy.py`: logits, loss and every kind of gradient, a
layer kind at a time; a window one key off and default frequencies in the
full layer, which must each fail by orders of magnitude; and the share
tests: the four chips' expert shares of one layer add up to the uncut
reference's, and the four vocabulary slices' logits are the whole head's.
"""

import functools
import gc

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from mellum_toy import ROPE, WINDOW, toy_config

from ray_tpu.models import mellum, parts
from ray_tpu.models import mellum_reference as reference

TOLERANCE = 2e-5  # relative 2-norm; the two differ in the order of sums only
LAYER_LEAVES = ("norm1", "norm2", "q", "k", "v", "o", "router", "experts_in", "experts_out")
KINDS = [("wte",), ("norm_f",), ("lm_head",)] + [
    (kind, leaf) for kind in (mellum.SLIDING, mellum.FULL) for leaf in LAYER_LEAVES
]


def relative(a, b):
    return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))


@functools.lru_cache(maxsize=None)
def case():
    cfg = toy_config()
    params = mellum.init_params(cfg, 0)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 64), 0, cfg.rows_held)
    with jax.default_matmul_precision("highest"):
        logits = mellum.forward(cfg, params, tokens)
        (loss, counts), grads = jax.value_and_grad(
            lambda p: mellum.loss_and_counts(cfg, p, tokens), has_aux=True
        )(params)
    want_loss, want_grads = reference.loss_and_grads(cfg, params, tokens)
    return {
        "cfg": cfg, "params": params, "tokens": tokens, "logits": logits,
        "loss": loss, "counts": counts, "grads": grads,
        "want_logits": reference.forward(cfg, params, tokens),
        "want_loss": want_loss, "want_grads": want_grads,
    }


@pytest.fixture(scope="module", autouse=True)
def _leave_a_small_heap():
    """What this file traced goes when it is done: the worker that ran it
    runs other files after, and some of them time a full `gc.collect()`."""
    yield
    for cached in (case, uncut):
        cached.cache_clear()
    jax.clear_caches()
    gc.collect()


def leaves_of(cfg, tree, kind):
    """The leaves of a kind: a top-level leaf, or a layer leaf of every
    layer of one kind of attention."""
    if len(kind) == 1:
        return [tree[kind[0]]]
    layers = [p for k, p in zip(cfg.layer_types, tree["layers"]) if k == kind[0]]
    return [p["mixer"][kind[1]] if kind[1] in "qkvo" else p[kind[1]] for p in layers]


def test_logits_are_the_references():
    found = case()
    assert found["logits"].shape == (2, 64, 128)
    assert relative(found["logits"], found["want_logits"]) < TOLERANCE


def test_loss_is_the_references():
    found = case()
    assert abs(float(found["loss"]) - float(found["want_loss"])) < 1e-5
    assert 4.5 < float(found["loss"]) < 5.5  # about ln(128)


@pytest.mark.parametrize("kind", KINDS, ids=["/".join(k) for k in KINDS])
def test_gradient_is_the_references(kind):
    found = case()
    got = leaves_of(found["cfg"], found["grads"], kind)
    want = leaves_of(found["cfg"], found["want_grads"], kind)
    assert len(got) == (3 if kind[0] == mellum.SLIDING else 1)
    for mine, theirs in zip(got, want):
        assert float(jnp.linalg.norm(theirs)) > 0
        assert relative(mine, theirs) < TOLERANCE


@pytest.mark.parametrize(
    "change",
    [
        {"sliding_window": WINDOW + 1},
        {"rope_parameters": {**ROPE, mellum.FULL: ROPE[mellum.SLIDING]}},
    ],
    ids=["window_one_key_off", "default_frequencies_in_the_full_layer"],
)
def test_a_wrong_mask_or_rotation_is_orders_of_magnitude_off(change):
    """In the gradients of the queries and keys of the layers it touches, and
    in the logits well over the tolerance too (the seeded embedding is wide
    beside a layer's output, so a logit moves less than a gradient)."""
    found = case()
    wrong = toy_config(**change)
    _, grads = reference.loss_and_grads(wrong, found["params"], found["tokens"])
    kind = mellum.SLIDING if "sliding_window" in change else mellum.FULL
    for leaf in "qk":
        got = leaves_of(found["cfg"], found["grads"], (kind, leaf))
        want = leaves_of(found["cfg"], grads, (kind, leaf))
        assert min(relative(a, b) for a, b in zip(got, want)) > 1000 * TOLERANCE
    other = reference.forward(wrong, found["params"], found["tokens"])
    assert relative(found["logits"], other) > 5 * TOLERANCE


def test_counts_cover_every_choice():
    found = case()
    cfg, counts = found["cfg"], found["counts"]
    choices = found["tokens"].size * cfg.num_experts_per_tok * cfg.num_layers
    assert int(counts["held"]) + int(counts["absent"]) == choices
    assert int(jnp.sum(counts["load"])) == int(counts["held"])
    assert counts["load"].shape == (len(cfg.experts_held),)
    assert int(counts["touched"]) <= cfg.num_layers * len(cfg.experts_held)
    assert set(counts) == set(mellum.COUNTS)


def test_recomputing_a_layer_changes_nothing():
    found = case()
    plain = toy_config(remat=False)
    with jax.default_matmul_precision("highest"):
        grads = jax.grad(
            lambda p: mellum.loss_and_counts(plain, p, found["tokens"])[0]
        )(found["params"])
    for mine, theirs in zip(
        jax.tree_util.tree_leaves(grads), jax.tree_util.tree_leaves(found["grads"])
    ):
        np.testing.assert_allclose(mine, theirs, rtol=1e-5, atol=1e-8)


# ---------------- what a recomputed layer keeps ----------------


def flash_kernels(jaxpr, inside=False):
    """The `pallas_call`s under the attention scopes, the grouped experts'
    own kernels left out."""
    found = 0
    for eqn in jaxpr.eqns:
        stack = str(eqn.source_info.name_stack)
        here = inside or any(scope in stack for scope in mellum.ATTENTION_SCOPE.values())
        found += here and eqn.primitive.name == "pallas_call"
        found += sum(flash_kernels(sub, here) for sub in jax.core.jaxprs_in_params(eqn.params))
    return found


def _ask_for_no_name(patch):
    """`hidden`'s checkpoint with no policy: every value of a layer is
    taken again in the backward pass, as before the names."""
    patch.setattr(jax.checkpoint_policies, "save_only_these_names", lambda *names: None)


def _loss_of(cfg, tokens):
    return lambda p: mellum.loss_and_counts(cfg, p, tokens)[0]


def _saved(cfg, params, tokens, capsys):
    """The lines `print_saved_residuals` gives for values computed on the
    way (no argument, no constant)."""
    capsys.readouterr()
    jax.ad_checkpoint.print_saved_residuals(
        lambda p: jnp.sum(mellum.hidden(cfg, p, tokens)[0]), params
    )
    lines = capsys.readouterr().out.splitlines()
    return [l for l in lines if "from the argument" not in l and "from a constant" not in l]


def test_a_recomputed_layer_keeps_its_input_and_the_flash_residuals(capsys):
    found = case()
    cfg, layers = found["cfg"], found["cfg"].num_layers
    lines = _saved(cfg, found["params"], found["tokens"], capsys)
    flash = [l for l in lines if "flash_attention.py" in l]
    # heads folded into the batch: [2 x 8, 64, 16] and its rows' log-sum-exp
    assert sum(l.startswith("f32[16,64,16] ") for l in flash) == layers
    assert sum(l.startswith("f32[16,64] named 'flash_lse'") for l in flash) == layers
    assert len(flash) == 2 * layers
    inputs = [l for l in lines if l.startswith("f32[2,64,64] ")]
    assert len(inputs) == layers and sum("(layer)" in l for l in inputs) == layers - 1
    # and nothing else of a layer: what is left is the positions and the
    # embedding's index, made before the first one
    rest = [l for l in lines if l not in flash and l not in inputs]
    assert len(rest) == 2 and all("(hidden)" in l or "(embed)" in l for l in rest), rest
    grad = jax.make_jaxpr(jax.grad(_loss_of(cfg, found["tokens"])))(found["params"])
    assert flash_kernels(grad.jaxpr) == 3 * layers  # forward, dQ, dK/dV


def test_a_bare_checkpoint_keeps_the_input_alone_and_runs_the_forward_twice(
    monkeypatch, capsys
):
    found = case()
    _ask_for_no_name(monkeypatch)
    cfg, layers = found["cfg"], found["cfg"].num_layers
    lines = _saved(cfg, found["params"], found["tokens"], capsys)
    assert not [l for l in lines if "flash_attention.py" in l]
    grad = jax.make_jaxpr(jax.grad(_loss_of(cfg, found["tokens"])))(found["params"])
    assert flash_kernels(grad.jaxpr) == 4 * layers


def test_keeping_the_flash_residuals_changes_no_bit_of_a_gradient(monkeypatch):
    found = case()
    with monkeypatch.context() as bare, jax.default_matmul_precision("highest"):
        _ask_for_no_name(bare)
        want = jax.grad(_loss_of(found["cfg"], found["tokens"]))(found["params"])
    paths = jax.tree_util.tree_leaves_with_path(want)
    assert len(paths) == 3 + 9 * found["cfg"].num_layers
    for (path, theirs), mine in zip(paths, jax.tree_util.tree_leaves(found["grads"])):
        assert float(jnp.linalg.norm(theirs)) > 0, path
        np.testing.assert_array_equal(mine, theirs, err_msg=jax.tree_util.keystr(path))


# ---------------- the shares of a four-chip host ----------------

SHARES = [(0, 1), (2, 3), (4, 5), (6, 7)]


@functools.lru_cache(maxsize=None)
def uncut():
    cfg = toy_config(experts_held=tuple(range(8)), vocab_rows=(0, 256))
    params = mellum.init_params(cfg, 2)
    tokens = jax.random.randint(jax.random.PRNGKey(3), (1, 48), 0, 256)
    with jax.default_matmul_precision("highest"):
        h, counts = mellum.hidden(cfg, params, tokens)
    return cfg, params, h, counts


@pytest.mark.parametrize("layer", range(4))
def test_expert_shares_add_up_to_the_uncut_layer(layer):
    cfg, params, h, _ = uncut()
    p = params["layers"][layer]
    x = parts.rms_norm(h[0], p["norm2"], cfg.rms_norm_eps)
    with jax.default_matmul_precision("highest"):
        want = reference._experts(cfg, p, x)
        total, held = jnp.zeros_like(want), 0
        for share in SHARES:
            mine = toy_config(experts_held=share, vocab_rows=(0, 256))
            rows = jnp.asarray(share)
            cut = {**p, "experts_in": p["experts_in"][rows], "experts_out": p["experts_out"][rows]}
            out, counts = parts.experts(mine, cut, x, grouped=True)
            total, held = total + out, held + int(counts["held"])
    assert relative(total, want) < TOLERANCE
    assert held == x.shape[0] * cfg.num_experts_per_tok  # every choice on one chip


def test_vocabulary_slices_are_the_whole_head():
    cfg, params, h, _ = uncut()

    def head(weight):
        return parts.head(h, params["norm_f"], cfg.rms_norm_eps, weight, cfg.dtype, tied=False)

    with jax.default_matmul_precision("highest"):
        whole = head(params["lm_head"])
        slices = [head(params["lm_head"][:, a : a + 64]) for a in range(0, 256, 64)]
    np.testing.assert_allclose(jnp.concatenate(slices, axis=-1), whole, rtol=1e-6, atol=1e-7)


def test_the_cell_as_held_is_595_million_parameters():
    cell = mellum.MellumConfig(
        layer_types=mellum.MELLUM_PERIOD, experts_held=tuple(range(16)),
        vocab_rows=(0, 24576),
    )
    assert mellum.num_params(mellum.param_shapes(cell)) == 595_153_152
    whole = mellum.MellumConfig()
    assert mellum.num_params(mellum.param_shapes(whole)) == 12_149_915_904


@pytest.mark.parametrize(
    "change,match",
    [
        ({"num_key_value_heads": 3}, "multiple"),
        ({"vocab_rows": (64, 300)}, "vocab_rows"),
        ({"layer_types": ("dense",)}, "layer types"),
        ({"experts_held": (1, 1)}, "experts_held"),
        ({"norm_topk_prob": False}, "norm_topk_prob"),
    ],
)
def test_bad_configurations_are_refused(change, match):
    with pytest.raises(ValueError, match=match):
        toy_config(**change)


def test_yarn_parameters_of_the_published_config():
    """Mellum 2's full layers: factor 16 over 8,192, the published
    attention factor is YaRN's own 0.1 ln(16) + 1."""
    inv, scale = parts.rope_frequencies(mellum.MELLUM2_ROPE[mellum.FULL], 128)
    assert inv.shape == (64,) and abs(scale - 1.2772588722239782) < 1e-12
    assert abs(scale - (0.1 * np.log(16) + 1)) < 1e-12
    plain, one = parts.rope_frequencies(mellum.MELLUM2_ROPE[mellum.SLIDING], 128)
    assert one == 1.0 and inv[0] == plain[0] and inv[-1] < plain[-1]


@pytest.mark.parametrize("hold", [False, True])
def test_a_held_router_keeps_its_weights_and_still_has_a_gradient(hold):
    import optax

    cfg = toy_config(hold_router=hold)
    params = mellum.init_params(cfg, 0)
    tx = optax.adamw(3e-3)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 64), 0, cfg.rows_held)
    after, state, _, _ = jax.jit(mellum.train_step(cfg, tx))(params, tx.init(params), tokens)
    momentum = next(part for part in state if hasattr(part, "mu")).mu
    for before, now, mu in zip(params["layers"], after["layers"], momentum["layers"]):
        moved = bool(jnp.any(before["router"] != now["router"]))
        assert moved != hold
        assert float(jnp.linalg.norm(mu["router"])) > 0  # computed and followed
        assert bool(jnp.any(before["experts_in"] != now["experts_in"]))
