"""`ray_tpu.models.laguna` against its plain float32 reference, at toy widths
on seeded weights (tests/laguna_toy.py): logits of a full forward pass, the
parts that are new with this model one by one, and the tie between a chip's
share of the routed experts and the uncut layer.

Tolerance: 2e-6 absolute on logits about 0.16 wide. Program and reference
both compute in float32 here and differ in the order of sums (grouped
experts, the rotation's tables taken in float32 from float64 frequencies);
that reads 3e-7 at most. A sliding layer that saw every earlier position
moves a logit by 0.05 or more at these contexts, a rotation of the wrong
half or with the wrong pairs by 0.1, and a gate left out by 0.1.
"""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import laguna as lg
from ray_tpu.models import laguna_reference as ref
from ray_tpu.models import parts
from ray_tpu.ops.grouped_experts import route

from laguna_toy import HEADS, WINDOW, held_params, toy_config

TOLERANCE = 2e-6
CFG = toy_config()
T = 70  # several windows long, and past YaRN's toy original length (16)


@pytest.fixture(scope="module")
def params():
    return lg.init_params(CFG, 3)


@pytest.fixture(scope="module")
def tokens():
    return jnp.asarray(np.random.default_rng(0).integers(0, CFG.vocab_size, size=T))


@functools.lru_cache(maxsize=None)
def reference(**variant):
    return jax.jit(functools.partial(ref.forward, CFG, **variant))


@pytest.mark.parametrize("grouped", [True, False])
def test_forward_matches_the_reference(params, tokens, grouped):
    got = jax.jit(functools.partial(lg.forward, CFG, grouped=grouped))(params, tokens)
    want = reference()(params, tokens)
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < TOLERANCE


def test_the_reference_in_query_blocks_is_the_reference(params, tokens):
    want = reference()(params, tokens)
    blocked = reference(query_block=16)(params, tokens)
    assert np.abs(np.asarray(blocked) - np.asarray(want)).max() < 1e-7


@pytest.mark.parametrize("window", [WINDOW + 1, WINDOW - 1, 10 * T])
def test_a_sliding_layer_with_another_window_fails(params, tokens, window):
    """Contexts are longer than the toy window: a sliding layer that saw
    one key more, one less or everything is far outside the tolerance."""
    got = jax.jit(functools.partial(lg.forward, CFG))(params, tokens)
    other = reference(window=window)(params, tokens)
    assert np.abs(np.asarray(got) - np.asarray(other)).max() > 1000 * TOLERANCE


def test_the_first_window_of_positions_does_not_see_the_window(params, tokens):
    """Below the window's length every layer is causal and no more."""
    short = tokens[:WINDOW]
    want = reference()(params, short)
    everything = reference(window=10 * T)(params, short)
    assert np.abs(np.asarray(want) - np.asarray(everything)).max() == 0.0


# ---------------- rotary positions ----------------


def yarn_by_the_formula(rope, d, positions):
    """cos and sin of the published formulas, written out position by
    position in float64."""
    base, factor = rope["rope_theta"], rope["factor"]
    length = rope["original_max_position_embeddings"]

    def dim(n):
        return d * math.log(length / (2 * math.pi * n)) / (2 * math.log(base))

    low = max(math.floor(dim(rope["beta_fast"])), 0)
    high = min(math.ceil(dim(rope["beta_slow"])), d - 1)
    cos, sin = [], []
    for p in positions:
        row_c, row_s = [], []
        for i in range(d // 2):
            f = base ** (2 * i / d)
            ramp = min(max((i - low) / (high - low), 0.0), 1.0)
            inv = (1 - ramp) / f + ramp / (factor * f)
            row_c.append(math.cos(p * inv) * rope["attention_factor"])
            row_s.append(math.sin(p * inv) * rope["attention_factor"])
        cos.append(row_c)
        sin.append(row_s)
    return np.asarray(cos), np.asarray(sin)


@pytest.mark.parametrize("which", ["published", "toy"])
def test_yarn_table_against_the_formula(which):
    """At positions below and past `original_max_position_embeddings`,
    with Laguna-S-2.1's own parameters (64 rotated dimensions of 128) and
    with the toy's."""
    cfg = lg.LagunaConfig() if which == "published" else CFG
    rope, d = cfg.rope(lg.FULL), cfg.rotary_dim(lg.FULL)
    assert d == cfg.head_dim // 2
    original = rope["original_max_position_embeddings"]
    positions = [0, 1, original - 1, original, 3 * original + 5]
    cos, sin = lg.rotary_tables(cfg, lg.FULL, jnp.asarray(positions))
    want_cos, want_sin = yarn_by_the_formula(rope, d, positions)
    # float32 angles: a position of 24,581 times an inverse frequency near 1.
    assert np.abs(np.asarray(cos) - want_cos).max() < 5e-3 * rope["attention_factor"]
    assert np.abs(np.asarray(sin) - want_sin).max() < 5e-3 * rope["attention_factor"]
    small = [0, 1, 2, 3]
    cos, sin = lg.rotary_tables(cfg, lg.FULL, jnp.asarray(small))
    want_cos, want_sin = yarn_by_the_formula(rope, d, small)
    assert np.abs(np.asarray(cos) - want_cos).max() < 1e-6
    # The ramp does something: high frequencies are kept, low ones divided.
    inv, scale = lg.rope_frequencies(rope, d)
    plain = rope["rope_theta"] ** (-np.arange(0, d, 2) / d)
    assert inv[0] == pytest.approx(plain[0]) and scale == rope["attention_factor"]
    assert inv[-1] == pytest.approx(plain[-1] / rope["factor"], rel=1e-5)
    # The reference's own tables, computed apart, agree.
    ref_cos, ref_sin = ref.rotary_tables(cfg, lg.FULL, 4)
    assert np.abs(ref_cos - want_cos).max() < 1e-6 and np.abs(ref_sin - want_sin).max() < 1e-6


def test_default_table_is_whole_and_unscaled():
    d = CFG.rotary_dim(lg.SLIDING)
    assert d == CFG.head_dim
    cos, sin = lg.rotary_tables(CFG, lg.SLIDING, jnp.asarray([0, 7, 60]))
    inv = 10000.0 ** (-np.arange(0, d, 2) / d)
    want = np.asarray([0, 7, 60])[:, None] * inv[None, :]
    assert np.abs(np.asarray(cos) - np.cos(want)).max() < 1e-5
    assert np.abs(np.asarray(sin) - np.sin(want)).max() < 1e-5


@pytest.mark.parametrize("kind", [lg.FULL, lg.SLIDING])
def test_rotation_partial_and_whole(kind):
    """Pairs are (i, i + half) of the rotated dimensions; what lies past
    them passes through; position 0 changes nothing but YaRN's factor."""
    rng = np.random.default_rng(1)
    x = rng.standard_normal((5, 3, CFG.head_dim)).astype(np.float32)
    positions = jnp.asarray([0, 1, 9, 30, 69])
    cos, sin = lg.rotary_tables(CFG, kind, positions)
    got = np.asarray(lg.rotate(jnp.asarray(x), cos, sin))
    rotated = CFG.rotary_dim(kind)
    half = rotated // 2
    cos, sin = np.asarray(cos), np.asarray(sin)
    for t in range(5):
        for i in range(half):
            a, b = x[t, :, i], x[t, :, i + half]
            assert np.allclose(got[t, :, i], a * cos[t, i] - b * sin[t, i], atol=1e-6)
            assert np.allclose(got[t, :, i + half], b * cos[t, i] + a * sin[t, i], atol=1e-6)
    assert (got[..., rotated:] == x[..., rotated:]).all()
    assert (rotated == CFG.head_dim) == (kind == lg.SLIDING)
    # Scores depend on the distance alone: shift every position by 11.
    q = rng.standard_normal((1, 1, CFG.head_dim)).astype(np.float32)
    k = rng.standard_normal((1, 1, CFG.head_dim)).astype(np.float32)

    def score(pq, pk):
        cq, sq = lg.rotary_tables(CFG, kind, jnp.asarray([pq]))
        ck, sk = lg.rotary_tables(CFG, kind, jnp.asarray([pk]))
        return float(jnp.sum(lg.rotate(jnp.asarray(q), cq, sq) * lg.rotate(jnp.asarray(k), ck, sk)))

    assert score(20, 13) == pytest.approx(score(31, 24), abs=1e-4)
    assert abs(score(20, 13) - score(20, 12)) > 1e-3


# ---------------- the gate, the heads, the head ----------------


def test_the_gate_is_one_sigmoid_a_head(params):
    p = params["layers"][1]["mixer"]
    heads = HEADS[1]
    rng = np.random.default_rng(2)
    u = jnp.asarray(rng.standard_normal((7, CFG.hidden_size)).astype(np.float32))
    mixed = jnp.asarray(rng.standard_normal((7, heads, CFG.head_dim)).astype(np.float32))
    got = np.asarray(lg.attention_out(CFG, lg.SLIDING, p, u, mixed))
    gate = 1.0 / (1.0 + np.exp(-(np.asarray(u) @ np.asarray(p["g"]))))  # [7, heads]
    assert gate.shape == (7, heads)
    want = (np.asarray(mixed) * gate[:, :, None]).reshape(7, -1) @ np.asarray(p["o"])
    assert np.abs(got - want).max() < 1e-5
    ungated = np.asarray(mixed).reshape(7, -1) @ np.asarray(p["o"])
    assert np.abs(got - ungated).max() > 0.01
    # A gate weight of nought halves every head.
    half = lg.attention_out(CFG, lg.SLIDING, {**p, "g": jnp.zeros_like(p["g"])}, u, mixed)
    assert np.abs(np.asarray(half) - 0.5 * ungated).max() < 1e-5


def test_layers_of_two_head_counts_in_one_model(params):
    shapes = lg._leaf_shapes(CFG)
    d, hd = CFG.hidden_size, CFG.head_dim
    for kind, heads, layer, p in zip(CFG.layer_types, HEADS, shapes["layers"], params["layers"]):
        assert layer["mixer"]["q"] == (d, heads * hd) == p["mixer"]["q"].shape
        assert layer["mixer"]["o"] == (heads * hd, d)
        assert layer["mixer"]["g"] == (d, heads)
        assert layer["mixer"]["k"] == (d, CFG.num_key_value_heads * hd)
        assert heads == (4 if kind == lg.FULL else 6)
    assert CFG.heads_of(lg.FULL) == (4,) and CFG.heads_of(lg.SLIDING) == (6,)
    assert "mlp_in" in shapes["layers"][0] and "router" not in shapes["layers"][0]
    assert all("router" in layer for layer in shapes["layers"][1:])
    # The published configuration: 72 and 48 over 8, a leading dense layer.
    real = lg.LagunaConfig()
    assert real.heads_of(lg.SLIDING) == (72,) and real.heads_of(lg.FULL) == (48,)
    assert [c.layers for c in real.cache_classes] == [12, 36]
    assert real.cache_classes[1].horizon == 512 and real.cache_classes[0].horizon is None


def test_the_head_is_untied(params):
    h = jnp.asarray(np.random.default_rng(4).standard_normal((3, CFG.hidden_size)), jnp.float32)
    got = np.asarray(lg.head(CFG, params, h))
    normed = np.asarray(parts.rms_norm(h, params["norm_f"], CFG.rms_norm_eps))
    assert np.abs(got - normed @ np.asarray(params["lm_head"])).max() < 1e-5
    tied = normed @ np.asarray(params["wte"]).T
    assert np.abs(got - tied).max() > 0.01


# ---------------- the router's rule and the shares ----------------


def test_route_softmax_over_all_then_top_k():
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.standard_normal((9, 16)).astype(np.float32))
    router = jnp.asarray(rng.standard_normal((16, 8)).astype(np.float32))
    ids, gates = route(x, router, 3, score="all", scale=2.5)
    logits = np.asarray(x) @ np.asarray(router)
    share = np.exp(logits - logits.max(-1, keepdims=True))
    share /= share.sum(-1, keepdims=True)
    order = np.argsort(-share, axis=-1)[:, :3]
    assert (np.sort(np.asarray(ids), -1) == np.sort(order, -1)).all()
    top = np.take_along_axis(share, np.asarray(ids), -1)
    assert np.abs(np.asarray(gates) - 2.5 * top / top.sum(-1, keepdims=True)).max() < 1e-6
    assert np.allclose(np.asarray(gates).sum(-1), 2.5, atol=1e-5)
    # Granite's rule is the default and is unchanged by the new argument.
    ids_c, gates_c = route(x, router, 3)
    top_logits = np.take_along_axis(logits, np.asarray(ids_c), -1)
    soft = np.exp(top_logits - top_logits.max(-1, keepdims=True))
    assert np.abs(np.asarray(gates_c) - soft / soft.sum(-1, keepdims=True)).max() < 1e-6
    with pytest.raises(ValueError):
        route(x, router, 3, score="tanh")


@pytest.mark.parametrize("grouped", [True, False])
def test_the_shares_add_up(grouped):
    """The eight shares' routed parts, with the shared expert counted once,
    equal the uncut layer: in the program's expert layer and in the
    reference's."""
    cfg_all = toy_config(experts_held=tuple(range(8)))
    params = lg.init_params(cfg_all, 7)
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
    ref_whole = ref.routed_experts(cfg_all, p, x)
    assert np.abs(np.asarray(ref_total) - np.asarray(ref_whole)).max() < TOLERANCE
    assert np.abs(np.asarray(whole - shared) - np.asarray(ref_whole)).max() < TOLERANCE


def test_a_share_through_the_whole_model(params, tokens):
    """Half the experts held (the toy's default) against the reference given
    the same share, and not the uncut model's answer."""
    cfg_all = toy_config(experts_held=tuple(range(8)))
    all_params = lg.init_params(cfg_all, 3)
    cut = held_params(all_params, cfg_all, CFG.experts_held)
    got = jax.jit(functools.partial(lg.forward, CFG))(cut, tokens)
    want = reference()(cut, tokens)
    uncut = jax.jit(functools.partial(ref.forward, cfg_all))(all_params, tokens)
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < TOLERANCE
    assert np.abs(np.asarray(got) - np.asarray(uncut)).max() > 1000 * TOLERANCE


# ---------------- the configuration ----------------


def test_config_refuses_what_it_cannot_run():
    with pytest.raises(ValueError):
        toy_config(layer_types=("full_attention",) * 4)  # lengths disagree
    with pytest.raises(ValueError):
        toy_config(experts_held=(0, 0))
    with pytest.raises(ValueError):
        toy_config(num_key_value_heads=4)  # 6 heads over 4
    with pytest.raises(ValueError):
        toy_config(sliding_window=0)
    with pytest.raises(ValueError):
        lg.rope_frequencies({"rope_type": "linear", "rope_theta": 10000}, 16)


def test_config_hashes_and_declares_its_classes():
    assert hash(CFG) == hash(toy_config()) and CFG == toy_config()
    assert CFG.rope(lg.FULL)["rope_type"] == "yarn"
    classes = CFG.cache_classes
    assert [(c.name, c.layers, c.horizon) for c in classes] == [
        ("full", 2, None), ("window", 3, WINDOW),
    ]
    assert CFG.cache_class_of(lg.FULL) == 0 and CFG.cache_class_of(lg.SLIDING) == 1
    assert lg.num_params(lg.init_params(CFG, 0)) == sum(
        math.prod(s) for s in jax.tree_util.tree_leaves(
            lg._leaf_shapes(CFG), is_leaf=lambda v: isinstance(v, tuple)
        )
    )


def test_import_ray_tpu_imports_none_of_the_new_modules():
    import subprocess
    import sys

    code = (
        "import sys, ray_tpu, ray_tpu.models, ray_tpu.llm;"
        "bad = [m for m in sys.modules if 'laguna' in m or m.endswith('models.parts')];"
        "print(bad); sys.exit(bool(bad))"
    )
    done = subprocess.run([sys.executable, "-c", code], capture_output=True, text=True)
    assert done.returncode == 0, done.stdout + done.stderr
