"""The yardstick of the `mellum` training cell, on the CPU: `lib/mellum_costs`
against hand counts at the published widths, `lib/reference_mellum` against
the program's own plain reference (`ray_tpu/models/mellum_reference.py`) on
one seed, the new readers on a made-up trace, a whole `--rehearse` of the
cell, and the same with a gradient of one expert matrix zeroed in the step,
which must come out not correct. The rehearsals take about a minute each."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import run
from lib import layer_metrics, manifest, mellum_costs as costs, reference_mellum
from ray_tpu.models import mellum, mellum_reference

CELL = "mellum2-12b-a2.5b-1of4-train.packed-8k"
CONFIG = manifest.cell(manifest.load(), CELL)["config_file"]
MODEL = CONFIG["model"]


# ---------------- operations, by hand (ISSUE 39's reckoning) ----------------


def test_parameters_as_held():
    assert costs.attention_params(MODEL) == 21_233_664
    assert costs.expert_params(MODEL) == 6_193_152
    assert costs.parameter_count(MODEL) == 595_153_152
    whole = dict(MODEL, layer_types=MODEL["layer_types"] * 7,
                 experts_held=list(range(64)), vocab_rows=[0, 98304])
    assert costs.parameter_count(whole) == 12_149_915_904


def test_the_configuration_keeps_every_published_width():
    published = CONFIG["published"]
    for key in ("hidden_size", "num_attention_heads", "num_key_value_heads", "head_dim",
                "moe_intermediate_size", "num_experts_per_tok", "sliding_window",
                "rope_parameters", "intermediate_size", "rms_norm_eps"):
        assert MODEL[key] == published[key] == CONFIG[key], key
    assert MODEL["num_experts"] == published["num_experts"] == 64  # the router's outputs
    assert CONFIG["num_experts"] == len(MODEL["experts_held"]) == 16
    assert CONFIG["num_hidden_layers"] == len(MODEL["layer_types"]) == 4
    assert MODEL["layer_types"] == published["layer_types"][:4]
    assert CONFIG["vocab_size"] == costs.rows_held(MODEL) == 24576
    assert sorted(CONFIG["reduced"]) == ["num_experts", "num_hidden_layers", "vocab_size"]


def test_operations_a_token_forward():
    # projections and router 42.5 M a layer, the head 113 M, forward (2 a weight)
    layer = 2 * (costs.attention_params(MODEL) + 2304 * 64)
    assert layer == 42_762_240
    assert costs.dense_flops_per_token(MODEL) == 3 * (4 * layer + 2 * 2304 * 24576)
    # a held assignment: 2 x 3 x 2,304 x 896 forward
    assert costs.expert_flops(MODEL, 1) == 6 * 6_193_152


def test_visible_pairs_at_8k():
    assert costs.visible_pairs(MODEL, costs.FULL, 8192) == 8192 * 8193 // 2 == 33_558_528
    sliding = costs.visible_pairs(MODEL, costs.SLIDING, 8192)
    assert sliding == sum(min(p + 1, 1024) for p in range(8192)) == 7_864_832
    # 67 M operations a token forward in the full layer, 16 M in a sliding one
    assert round(costs.attention_flops(MODEL, costs.FULL, 8192) / 3 / 8192 / 1e6) == 67
    assert round(costs.attention_flops(MODEL, costs.SLIDING, 8192) / 3 / 8192 / 1e6) == 16


def test_a_sequence_is_twelve_teraflops():
    # 2 of a token's 8 choices are held on average
    per_token = costs.train_flops_per_token(MODEL, 8192, held_per_token=2.0 * 4)
    assert 12.0e12 < per_token * 8192 < 12.4e12


# ---------------- the reference, against the program's own ----------------


def toy():
    fields = dict(CONFIG["rehearsal"]["model"], dtype="float32")
    types = {k: getattr(jnp, fields[k]) for k in ("dtype", "param_dtype")}
    return fields, mellum.MellumConfig(**{**fields, **types})


def test_reference_is_the_programs_reference():
    fields, cfg = toy()
    sized = reference_mellum.sizes(fields)
    params = mellum.init_params(cfg, 5)
    tokens = jax.random.randint(jax.random.PRNGKey(6), (2, 96), 0, cfg.rows_held)
    want_loss, want = mellum_reference.loss_and_grads(cfg, params, tokens)
    with jax.default_matmul_precision("highest"):
        loss, grads = jax.value_and_grad(
            lambda p: reference_mellum.batch_loss(sized, p, tokens)
        )(params)
    assert abs(float(loss) - float(want_loss)) < 1e-5
    distance = reference_mellum.relative_distance(sized, grads, want)
    assert distance["all"] < 1e-5 and distance["worst_matrix"] < 1e-5
    assert {"wte", "lm_head", "norm_f", "sliding_attention/q", "full_attention/experts_in",
            "sliding_attention/router", "full_attention/norm2"} <= set(distance)


@pytest.mark.parametrize("variant", [{"window_delta": 1}, {"dtype": jnp.bfloat16}])
def test_a_variant_is_another_answer(variant):
    fields, cfg = toy()
    sized = reference_mellum.sizes(fields)
    params = mellum.init_params(cfg, 5)
    tokens = jax.random.randint(jax.random.PRNGKey(6), (1, 96), 0, cfg.rows_held)
    with jax.default_matmul_precision("highest"):
        plain = jax.grad(lambda p: reference_mellum.batch_loss(sized, p, tokens))(params)
        other = jax.grad(
            lambda p: reference_mellum.batch_loss(sized, p, tokens, **variant)
        )(params)
    assert reference_mellum.relative_distance(sized, other, plain)["worst_matrix"] > 1e-3


def test_a_lost_expert_gradient_reads_one_in_its_own_entry():
    fields, cfg = toy()
    sized = reference_mellum.sizes(fields)
    tree = mellum.init_params(cfg, 1)
    lost = jax.tree_util.tree_map(lambda x: x, tree)
    lost["layers"][3] = dict(lost["layers"][3], experts_out=jnp.zeros_like(tree["layers"][3]["experts_out"]))
    distance = reference_mellum.relative_distance(sized, lost, tree)
    assert distance["full_attention/experts_out"] == 1.0 == distance["worst_matrix"]
    assert distance["sliding_attention/experts_out"] == 0.0
    assert distance["all"] < 1.0


def test_the_reference_step_is_an_adamw_step():
    fields, cfg = toy()
    sized = reference_mellum.sizes(fields)
    tx = optax.adamw(3e-4)
    params = mellum.init_params(cfg, 2)
    tokens = jax.random.randint(jax.random.PRNGKey(3), (2, 96), 0, cfg.rows_held)
    want_loss, grads = mellum_reference.loss_and_grads(cfg, params, tokens)
    state = tx.init(params)
    _, after, loss = reference_mellum.training_reference_step(sized, tx)(
        jax.tree_util.tree_map(jnp.copy, params), state, tokens
    )
    assert abs(float(loss) - float(want_loss)) < 1e-5
    momentum = next(part for part in after if hasattr(part, "mu")).mu
    scaled = jax.tree_util.tree_map(lambda g: 0.1 * g, grads)  # (1 - b1) g after one step
    assert reference_mellum.relative_distance(sized, momentum, scaled)["worst_matrix"] < 1e-4


# ---------------- the readers ----------------


def collected(scopes=True):
    ops = {
        "jit_step/fusion.1": 0.30, "jit_step/fusion.2": 0.10, "jit_step/fusion.3": 0.20,
        "jit_step/fusion.4": 0.15, "jit_other/fusion.1": 5.0,
    }
    scope_map = {"jit_step": {
        "fusion.1": "llm.moe.routed", "fusion.2": "llm.mixer.attention.full",
        "fusion.3": "llm.mixer.attention.window", "fusion.4": "llm.head",
    }}
    return {
        "train": {
            "model": MODEL, "steps": 100, "sequences_per_step": 2, "tokens_per_sequence": 8192,
            "experts": {"held": 100 * 131072, "absent": 100 * 393216, "touched": 6400,
                        "load_max": 100 * 4 * 3000, "load": [819200] * 16},
        },
        "device_report": {"op_scopes": scope_map} if scopes else {},
        "trace": {"op_seconds": ops, "busy_s": 1.0, "window_s": 1.1,
                  "modules": {"jit_step(1)": {"runs": 2}, "jit_other(2)": {"runs": 9}}},
    }


@pytest.fixture
def peak(monkeypatch):
    monkeypatch.setattr(costs, "peaks", lambda: {"bf16_flops_per_s": 197e12})


def read(name, data):
    return layer_metrics.read_all({name: {}}, data)[name]


def test_new_readers_on_a_made_up_trace(peak):
    data = collected()
    # two traced steps of 131,072 held assignments each
    want = 100 * 6 * 6_193_152 * 2 * 131072 / 197e12 / 0.30
    assert read("train_moe_roofline", data) == pytest.approx(want)
    full = 100 * 12 * 33_558_528 * 4096 * 2 * 2 / 197e12 / 0.10
    assert read("train_full_attn_roofline", data) == pytest.approx(full)
    window = 100 * 12 * 7_864_832 * 4096 * 3 * 2 * 2 / 197e12 / 0.20
    assert read("train_window_attn_roofline", data) == pytest.approx(window)
    assert read("train_moe_busy_share", data) == pytest.approx(30.0)
    assert read("train_attn_busy_share", data) == pytest.approx(30.0)
    assert read("train_expert_load_max_over_mean", data) == pytest.approx(3000 * 4 * 16 / 131072)


@pytest.mark.parametrize("name", [
    "train_moe_roofline", "train_full_attn_roofline", "train_window_attn_roofline",
    "train_moe_busy_share", "train_attn_busy_share",
])
def test_a_program_without_a_scope_map_reads_nothing(peak, name):
    assert read(name, collected(scopes=False)) is None
    assert read(name, {"train": {}, "trace": None}) is None


def test_a_loop_without_counts_reads_nothing():
    assert read("train_expert_load_max_over_mean", {"train": {"tokens_per_s": 1.0}}) is None


# ---------------- whole rehearsals ----------------


def rehearse(capsys, seed, *more):
    assert run.main(["--workload", CELL, "--rehearse", "--seconds", "6", "--seed", str(seed),
                     "--trace", "1", *more]) == 0
    lines = [json.loads(line) for line in capsys.readouterr().out.splitlines()
             if line.startswith("{")]
    by_kind = {line["info"]: line for line in lines}
    return by_kind["rehearsal_done"], by_kind["train"]


def test_a_sound_rehearsal_is_correct(capsys):
    done, train = rehearse(capsys, 3900000101)
    assert done["correct"] and not done["problems"]
    assert max(train["loss_distances"]) < train["loss_tolerance"]
    assert train["gradient_distance"]["worst_matrix"] < train["gradient_tolerance"]
    assert train["experts"]["held"] + train["experts"]["absent"] == train["assignments_expected"]
    assert train["run_report_experts"]["held"] == train["experts"]["held"]
    assert "train_expert_load_max_over_mean" in done["would_report"]


def test_another_seeds_reference_is_not_correct(capsys):
    done, train = rehearse(capsys, 3900000102, "--reference-seed", "7")
    assert not done["correct"]
    assert train["gradient_distance"]["all"] > 0.5


def test_a_zeroed_expert_gradient_is_not_correct(capsys, monkeypatch):
    def faulty(cfg, tx):
        def step(params, opt_state, tokens):
            (loss, counts), grads = jax.value_and_grad(
                lambda p: mellum.loss_and_counts(cfg, p, tokens), has_aux=True
            )(params)
            lost = dict(grads["layers"][1])
            lost["experts_out"] = jnp.zeros_like(lost["experts_out"])
            grads = dict(grads, layers=[lost if i == 1 else g for i, g in enumerate(grads["layers"])])
            updates, opt_state = tx.update(grads, opt_state, params)
            return optax.apply_updates(params, updates), opt_state, loss, counts

        return step

    monkeypatch.setattr(mellum, "train_step", faulty)
    done, train = rehearse(capsys, 3900000103)
    assert not done["correct"]
    assert train["gradient_distance"]["worst_matrix"] > train["gradient_tolerance"]
    assert train["gradient_distance"]["all"] < train["gradient_distance"]["worst_matrix"]
