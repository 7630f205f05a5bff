"""What PR 57 added for `solar-open2-250b-1of8`: the benchmark's own reference
against the repository's, every control and an altered token coming out not
correct, the cost functions against counts worked by hand, the three readers
on a recorded toy `collected` (and None on one without the spans), the
configuration against the catalog's row and the driver's rules, and the
recorded chip readings against the limits."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from lib import layer_metrics, manifest
from lib import solar_open2_costs as costs
from lib.peaks import peaks_for
from lib.reference_solar_open2 import (
    CONTROLS, MUST_FAIL, SolarOpen2ServingReference, sizes,
)
from ray_tpu.models import solar_open2 as so
from ray_tpu.models import solar_open2_reference as repo_reference
from runners.serve import within_limits

CONFIG = "solar-open2-250b-1of8"
CELL = CONFIG + ".doc-qa-long"


@pytest.fixture(scope="module")
def loaded():
    return manifest.load()


@pytest.fixture(scope="module")
def config(loaded):
    return manifest.cell(loaded, CELL)["config_file"]


@pytest.fixture(scope="module")
def toy(config):
    fields = dict(config["rehearsal"]["model"], dtype="float32", param_dtype="float32")
    cfg = so.SolarOpen2Config(**{**fields, "dtype": jnp.float32, "param_dtype": jnp.float32})
    return cfg, fields, so.init_params(cfg, 5)


def test_the_benchmarks_reference_is_the_repositorys(toy):
    cfg, fields, params = toy
    tokens = list(np.random.RandomState(0).randint(1, 512, 40))
    mine = SolarOpen2ServingReference(sizes(fields), params, pad_to=16, query_block=16)
    got = mine.logits(tokens, slice(0, 40))
    want = np.asarray(repo_reference.forward(cfg, params, jnp.asarray(tokens)))
    # float32 both, another order of sums: 1e-6 on logits 0.15 wide
    assert float(np.abs(got - want).max()) < 2e-5
    for name, variant in (
        ("scalar_decay", dict(scalar_decay=True)), ("beta_without_2", dict(beta_factor=1.0)),
        ("no_selection_bias", dict(selection_bias=False)),
        ("softmax_router", dict(router_score="softmax")),
        ("no_attention_gate", dict(gate_form=None)),
        ("no_shared_expert", dict(shared_expert=False)),
    ):
        moved = mine.logits(tokens, slice(0, 40), **CONTROLS[name])
        theirs = np.asarray(repo_reference.forward(cfg, params, jnp.asarray(tokens), **variant))
        assert float(np.abs(moved - theirs).max()) < 2e-5, name
        assert float(np.abs(moved - want).max()) > 2e-4, name  # and it is another answer


def test_every_control_and_an_altered_token_come_out_not_correct(toy):
    cfg, fields, params = toy
    reference = SolarOpen2ServingReference(sizes(fields), params, pad_to=16)
    prompt = list(np.random.RandomState(1).randint(1, 512, 30))
    answer = []
    for _ in range(12):  # greedy by the reference itself: every gap is nought
        row = reference.logits(prompt + answer, slice(len(prompt) + len(answer) - 1, None))
        answer.append(int(row[0].argmax()))
    limits = {"logit_tolerance": 1e-4, "mean_gap_limit": 1e-6}

    def pooled(reading):
        return {"worst_gap": reading["worst_gap"],
                "mean_gap": reading["gap_sum"] / reading["tokens"]}

    sound = reference.judge(prompt, answer, limits["logit_tolerance"])
    assert sound["ok"] and sound["flipped"] == 0 and within_limits(pooled(sound), limits)
    altered = list(answer)
    altered[6] = (altered[6] + 1) % 512
    judged = reference.judge(prompt, altered, limits["logit_tolerance"])
    assert not judged["ok"] and not within_limits(pooled(judged), limits)
    # The controls are read at every position of a longer stretch.
    stretch = list(np.random.RandomState(2).randint(1, 512, 200))
    readings = reference.control_gaps(prompt, stretch, tuple(CONTROLS))
    assert set(readings) == set(CONTROLS) and set(MUST_FAIL) == set(CONTROLS) - {"bf16_state"}
    for name, reading in readings.items():
        assert reading["logit_move"] > 1e-4, name
        assert reading["flipped"] > 0 and not within_limits(pooled(reading), limits), name


def test_costs_against_counts_worked_by_hand(config):
    toy = {"num_layers": 3, "num_heads": 4, "key_dim": 8, "value_dim": 8, "decay_width": 8,
           "conv_width": 4, "conv_dim": 96, "chunk_size": 8, "state_itemsize": 4,
           "conv_itemsize": 2}
    assert costs.state_slot_bytes(toy) == 3 * (4 * 8 * 8 * 4 + 3 * 96 * 2)
    # A head and token: 8 x (16 + 16 + 8) inside the chunk, 6 x 8 x 8 for the state.
    assert costs.scan_flops_per_token(toy) == 4 * (8 * 40 + 384) == 2816
    assert costs.scan_flops(10, toy) == 10 * 3 * 2816
    real = {"num_layers": 3, "num_heads": 64, "key_dim": 128, "value_dim": 128,
            "decay_width": 128, "conv_width": 4, "conv_dim": 24576, "chunk_size": 64,
            "state_itemsize": 4, "conv_itemsize": 2}
    # 3 x (64 x 128 x 128 x 4 + 3 x 24,576 x 2) = 3 x 4,341,760 = 13.0 MB a lane
    assert costs.state_slot_bytes(real) == 3 * 4341760 == 13025280
    # 64 heads x (64 x (256 + 256 + 128) + 6 x 128 x 128) = 8.913 M a token and layer
    assert costs.scan_flops_per_token(real) == 64 * (64 * 640 + 98304) == 8912896
    model = config["model"]
    assert costs.parameter_count(model) == 3308377920
    assert round(2 * costs.parameter_count(model) / 1e9, 2) == 6.62
    whole = dict(model, num_hidden_layers=48, gqa_layers=config["published"]["gqa_layers"],
                 experts_held=list(range(320)), vocab_size=196608)
    assert round(costs.parameter_count(whole) / 1e9, 1) == 250.3


def test_the_program_counts_the_same_parameters_and_bytes(config):
    fields = dict(config["model"], dtype=jnp.bfloat16, param_dtype=jnp.bfloat16)
    cfg = so.SolarOpen2Config(**fields)
    leaves = jax.tree_util.tree_leaves(
        so._leaf_shapes(cfg), is_leaf=lambda v: isinstance(v, tuple)
    )
    assert sum(int(np.prod(s)) for s in leaves) == costs.parameter_count(config["model"])
    declared = sum(
        int(np.prod(shape)) * jnp.dtype(dtype).itemsize
        for _, shape, dtype in so.recurrent_kinds(cfg)[so.KDA].arrays
    )
    assert 3 * declared == costs.state_slot_bytes(so.recurrent_shape(cfg))


@pytest.fixture
def collected(monkeypatch):
    """A toy of what a traced run collects: two decode runs and one chunk."""
    monkeypatch.setattr(costs, "peaks", lambda: peaks_for("TPU v5 lite"))
    shape = {"num_layers": 3, "num_heads": 4, "key_dim": 8, "value_dim": 8, "decay_width": 8,
             "conv_width": 4, "conv_dim": 96, "chunk_size": 8, "state_itemsize": 4,
             "conv_itemsize": 2}
    return {
        "engine_after": {"recurrent_shape": shape},
        "engine_window": {"decode_state_bytes": 8.0e9, "decode_dispatches": 10,
                          "prefill_scan_tokens": 5000, "prefill_chunk_dispatches": 5},
        "trace": {
            "busy_s": 0.01,
            "modules": {"jit__decode_step(1)": {"runs": 2}, "jit__prefill_step(2)": {"runs": 1}},
            "op_seconds": {
                "jit__decode_step/fusion.1 fusion": 0.003,
                "jit__decode_step/fusion.2 fusion": 0.002,
                "jit__prefill_step/fusion.7 fusion": 0.001,
                "jit__prefill_step/fusion.8 fusion": 0.004,
            },
        },
        "device_report": {"op_scopes": {
            "jit__decode_step": {"fusion.1": "llm.mixer.kda.update", "fusion.2": "llm.moe.routed"},
            "jit__prefill_step": {"fusion.7": "llm.mixer.kda.scan", "fusion.8": "llm.mixer.kda.proj"},
        }},
    }


def test_the_readers_on_a_recorded_toy(collected):
    peaks = peaks_for("TPU v5 lite")
    update = layer_metrics.read("kda_update_roofline", collected)
    # 0.8 GB a dispatch x 2 runs over 3 ms and the HBM peak
    assert update == pytest.approx(100 * 1.6e9 / peaks["hbm_bytes_per_s"] / 0.003)
    scan = layer_metrics.read("kda_scan_roofline", collected)
    flops = costs.scan_flops(1000, collected["engine_after"]["recurrent_shape"])
    assert scan == pytest.approx(100 * flops / peaks["bf16_flops_per_s"] / 0.001)
    busy = layer_metrics.read("kda_busy_share", collected)
    assert busy == pytest.approx(100 * (0.003 + 0.001 + 0.004) / 0.01)


def test_the_readers_find_nothing_on_a_program_without_the_scopes(collected):
    """The parent commit, or another model: no such scope, no such counter,
    no `recurrent_shape` of this kind. Each reader returns None, none raises."""
    other = dict(collected, device_report={"op_scopes": {
        "jit__decode_step": {"fusion.1": "llm.mixer.gdn.update"},
        "jit__prefill_step": {"fusion.7": "llm.mixer.gdn.scan"},
    }})
    bare = {"engine_after": {}, "engine_window": {}, "trace": collected["trace"],
            "device_report": {}}
    for found in (other, bare, {**bare, "trace": None}):
        for name in ("kda_update_roofline", "kda_scan_roofline", "kda_busy_share"):
            assert layer_metrics.read(name, found) is None, name


def test_the_configuration_is_the_catalogs_row_cut_as_it_says(loaded, config):
    entry = next(c for c in loaded["configs"] if c["name"] == CONFIG)
    cut = ["num_hidden_layers", "n_routed_experts", "vocab_size"]
    assert entry["reduced"] == config["reduced"] == cut
    published = config["published"]
    for key, value in published.items():
        if key not in cut:
            assert config[key] == value, key  # every other key as published, top level
    assert (config["num_hidden_layers"], config["n_routed_experts"], config["vocab_size"]) == (
        4, 40, 24576)
    assert (published["num_hidden_layers"], published["n_routed_experts"],
            published["vocab_size"]) == (48, 320, 196608)
    model = config["model"]
    assert model["gqa_layers"] == [0] and model["num_hidden_layers"] == 4  # one whole period
    assert model["n_routed_experts"] == 320 and model["experts_held"] == list(range(40))
    assert model["vocab_size"] == 196608 // 8
    for key in ("hidden_size", "num_attention_heads", "num_key_value_heads", "head_dim",
                "moe_intermediate_size", "num_experts_per_tok", "n_shared_experts",
                "rms_norm_eps", "max_position_embeddings", "kda_allow_neg_eigval"):
        assert model[key] == published[key], key  # no width or head count cut
    linear = published["linear_attn_config"]
    assert (model["kda_num_heads"], model["kda_head_dim"], model["short_conv_kernel_size"]) == (
        linear["num_heads"], linear["head_dim"], linear["short_conv_kernel_size"])
    assert set(config["assumed"]) >= {"router_score", "attention_gate", "qk_norm",
                                      "low_rank", "norm_placement", "shared_expert",
                                      "initialisation", "released_code"}
    assert "eight" in config["deployment"] and "pipeline" in config["deployment"]
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f) if r["name"] == "Solar-Open2-250B")
        assert published == row["config"] and config["source"] == row["source_url"]


def test_the_cell_has_the_issues_traffic(loaded):
    cell = manifest.cell(loaded, CELL)
    mix = cell["traffic_mix"]
    assert cell["chips"] == 1 and mix["loop"] == "closed"
    assert (mix["clients"], mix["requests_per_client"]) == (128, 16)
    assert (mix["sessions"], mix["shared_prefix"], mix["schedule_seed"]) == (0, 0, 57)
    assert mix["prompt"] == {"median": 4096, "sigma": 0.7, "min": 256, "max": 16384}
    assert mix["answer"] == {"median": 192, "sigma": 0.5, "min": 32, "max": 512}
    mine = manifest.metrics_of(loaded, CELL)
    assert set(mine["end_to_end"]) == {"completed_tokens_per_s", "setup_s"}
    assert {"kda_update_roofline", "kda_scan_roofline", "kda_busy_share",
            "moe_decode_roofline", "moe_prefill_roofline", "moe_busy_share",
            "expert_load_max_over_mean", "full_attn_roofline", "mixed_attn_busy_share",
            "decode_occupancy", "tput_preemptions", "tput_decode_step_device_ms",
            "tput_device_idle_share", "setup_trace_lower_s"} <= set(mine["per_layer"])
    assert not [name for name in mine["per_layer"]
                if name.startswith(("gdn_", "ssm_", "window_"))]
    assert len(loaded["workloads"]) >= 9 and len(loaded["configs"]) >= 8  # later PRs add more


def test_the_new_manifest_passes_the_drivers_rules(loaded):
    manifest.validate(loaded)
    engine = manifest.cell(loaded, CELL)["config_file"]["engine"]
    # The mix's longest request: 16,384 + 512 positions, every lane at once.
    assert engine["block_size"] * engine["max_blocks_per_seq"] == 16896
    assert engine["max_decode_slots"] == 64 and engine["num_blocks"] % 256 == 0
    assert engine["num_blocks"] >= 64 * engine["max_blocks_per_seq"]
    assert engine["prefill_buckets"] == [256, 1024, 2048]
    # One option off its default, and why: auto is a quarter of the context,
    # 4,224, over the widest bucket, and the engine then refuses a prompt
    # longer than a bucket.
    assert engine["max_prefill_tokens_per_step"] == 2048


# (mean_logit_gap, worst_logit_gap) of every sound run on the chip so far
# (PR 57, calls 1, B and C: lead-ins 30, 50 and 70 s, 17 seeds and two traced).
SOUND = [
    (0.012555, 0.5456), (0.010914, 0.3189), (0.012443, 0.4436), (0.014568, 0.5391),
    (0.010439, 0.4898), (0.011173, 0.4614), (0.010234, 0.4614), (0.010088, 0.3953),
    (0.009076, 0.4605), (0.007475, 0.4813), (0.009368, 0.4949), (0.011101, 0.6799),
    (0.009076, 0.5376), (0.010365, 0.5383), (0.009243, 0.4595), (0.008361, 0.3447),
    (0.007131, 0.5032), (0.009424, 0.3996), (0.012604, 0.4147),
]
# The controls of call 1 (seed 2157000002), the altered token and another
# seed's weights (call B): each must come out not correct.
NOT_CORRECT = {
    "int8": (0.112125, 1.3866), "scalar_decay": (1.946254, 5.5954),
    "beta_without_2": (0.514930, 2.4690), "no_selection_bias": (0.344607, 2.5019),
    "softmax_router": (0.696222, 3.3210), "no_attention_gate": (3.078428, 6.9004),
    "no_shared_expert": (4.662649, 8.7501), "conv_tail_cut": (0.118161, 5.1389),
    "altered_token": (0.031480, 6.2571), "reference_seed": (5.255119, 10.0822),
    # call C, seed 2157000017
    "int8_c": (0.120308, 1.2808), "scalar_decay_c": (1.937281, 5.4728),
    "beta_without_2_c": (0.550780, 3.1144), "no_selection_bias_c": (0.343454, 2.4258),
    "softmax_router_c": (0.607820, 3.0145), "no_attention_gate_c": (3.083460, 7.6209),
    "no_shared_expert_c": (4.579653, 9.1566), "conv_tail_cut_c": (0.095578, 4.2478),
    "altered_token_c": (0.030734, 3.3398),
}
BF16_STATE = (0.001030, 0.1354)  # reported, seen or not: it is not seen


def test_the_recorded_chip_readings_against_the_limits(config):
    limits = config["correctness"]
    assert (limits["logit_tolerance"], limits["mean_gap_limit"]) == (1.5, 0.03)

    def ok(reading):
        return within_limits({"mean_gap": reading[0], "worst_gap": reading[1]}, limits)

    assert all(ok(reading) for reading in SOUND)
    assert set(NOT_CORRECT) >= set(MUST_FAIL)
    for name, reading in NOT_CORRECT.items():
        assert not ok(reading), name
    assert ok(BF16_STATE)
    # Room on both sides: twice over the largest sound reading, three times
    # under the nearest control's, for the mean; the worst gap guards the
    # altered token alone (the int8 control's passes it and fails the mean).
    largest = max(mean for mean, _ in SOUND)
    assert 2 * largest < limits["mean_gap_limit"] < NOT_CORRECT["int8"][0] / 3
    widest = max(worst for _, worst in SOUND)
    assert 2 * widest < limits["logit_tolerance"] < NOT_CORRECT["altered_token_c"][1] / 2
    assert NOT_CORRECT["int8"][1] < limits["logit_tolerance"]
