"""What PR 46 added for `falcon-h1-34b-6l`: the benchmark's own reference
against the repository's, every control that must and an altered token
coming out not correct, the cost functions against counts done by loops at
toy sizes and against the seeded tree, `head_roofline`'s reader on a recorded
toy `collected`, the `chat-short` schedule against the mix's expectations,
and the configuration against the catalog's row and the driver's rules."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from lib import falcon_h1_costs as costs
from lib import hybrid_costs, layer_metrics, manifest, traffic
from lib.peaks import peaks_for
from lib.reference_falcon_h1 import (
    CONTROLS, MUST_FAIL, FalconH1ServingReference, _conv, sizes,
)
from ray_tpu.models import falcon_h1 as fh
from ray_tpu.models import falcon_h1_reference as repo_reference
from runners.serve import within_limits

CONFIG = "falcon-h1-34b-6l"
CELL = CONFIG + ".chat-short"


@pytest.fixture(scope="module")
def loaded():
    return manifest.load()


@pytest.fixture(scope="module")
def config(loaded):
    return manifest.cell(loaded, CELL)["config_file"]


@pytest.fixture(scope="module")
def toy(config):
    fields = dict(config["rehearsal"]["model"], dtype="float32", param_dtype="float32")
    cfg = fh.FalconH1Config(**{**fields, "dtype": jnp.float32, "param_dtype": jnp.float32})
    return cfg, fields, fh.init_params(cfg, 5)


def test_the_benchmarks_reference_is_the_repositorys(toy):
    cfg, fields, params = toy
    tokens = list(np.random.RandomState(0).randint(1, 512, 40))
    mine = FalconH1ServingReference(sizes(fields), params, pad_to=16)
    got = mine.logits(tokens, slice(0, 40))
    want = np.asarray(repo_reference.forward(cfg, params, jnp.asarray(tokens)))
    # float32 both, another order of sums: 1e-5 on logits 1 wide
    assert want.std() > 0.3 and float(np.abs(got - want).max()) < 5e-5
    for name, variant in (
        ("no_attention", dict(branches=("mamba",))),
        ("no_mamba", dict(branches=("full_attention",))),
        ("one_group", dict(shared_group=True)),
        ("norm_over_all", dict(norm_groups=1)),
    ):
        moved = mine.logits(tokens, slice(0, 40), **CONTROLS[name])
        theirs = np.asarray(repo_reference.forward(cfg, params, jnp.asarray(tokens), **variant))
        assert float(np.abs(moved - theirs).max()) < 5e-5, name
        assert float(np.abs(moved - want).max()) > 5e-3, name  # and it is another answer


def test_a_cut_tail_is_a_sequence_started_again_at_the_cut():
    """`conv_tail_cut` at position 16 of a prompt of 24: the convolution's
    outputs from there on are those of the sequence's tail alone, for three
    positions; before it nothing moves."""
    x = jnp.asarray(np.random.RandomState(3).randn(24, 5), jnp.float32)
    w = jnp.asarray(np.random.RandomState(4).randn(4, 5), jnp.float32)
    bias = jnp.asarray(np.random.RandomState(5).randn(5), jnp.float32)
    cut = np.zeros(24, bool)
    cut[16] = True
    got, whole, tail = _conv(x, w, bias, jnp.asarray(cut)), _conv(x, w, bias), _conv(x[16:], w, bias)
    np.testing.assert_allclose(got[:16], whole[:16], atol=1e-6)
    np.testing.assert_allclose(got[16:], tail, atol=1e-6)
    assert float(jnp.abs(got[16:19] - whole[16:19]).max()) > 0.1
    np.testing.assert_allclose(got[19:], whole[19:], atol=1e-6)


def test_every_control_and_an_altered_token_come_out_not_correct(toy):
    cfg, fields, params = toy
    reference = FalconH1ServingReference(sizes(fields), params, pad_to=16)
    prompt = list(np.random.RandomState(1).randint(1, 512, 30))
    answer = []
    for _ in range(12):  # greedy by the reference itself: every gap is nought
        row = reference.logits(prompt + answer, slice(len(prompt) + len(answer) - 1, None))
        answer.append(int(row[0].argmax()))
    limits = {"logit_tolerance": 1e-3, "mean_gap_limit": 1e-5}

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
    assert set(readings) == set(CONTROLS) and set(MUST_FAIL) <= set(CONTROLS)
    for name, reading in readings.items():
        assert reading["logit_move"] > 1e-3, name
        if name in MUST_FAIL:  # the others are reported, seen or not
            assert reading["flipped"] > 0 and not within_limits(pooled(reading), limits), name


def _scan_flops_by_loops(heads, groups, head_dim, state, chunk):
    """One chunk of one layer, multiply-adds counted one by one (x 2)."""
    macs = 0
    for i in range(chunk):
        macs += groups * state * (i + 1)  # C_i . B_j for j <= i, a group
        for _ in range(heads):
            macs += head_dim * (i + 1)  # sum_j <= i of mixed_ij xdt_j
            macs += 2 * head_dim * state  # the state's gain and its read-out
    return 2 * macs


def test_costs_against_counts_done_by_loops(config):
    shape = {"num_layers": 3, "num_heads": 4, "head_dim": 16, "state_size": 16,
             "num_groups": 2, "conv_width": 4, "conv_dim": 128, "chunk_size": 8,
             "state_itemsize": 4, "conv_itemsize": 2}
    by_loops = _scan_flops_by_loops(4, 2, 16, 16, 8)
    counted = costs.scan_flops_per_token(shape) * 8
    # A causal half is chunk / 2 a token here where the loop has
    # (chunk + 1) / 2: within one row, and never over it.
    assert 0 <= by_loops - counted <= 8 * (2 * 16 + 4 * 16)
    real = fh.recurrent_shape(fh.FalconH1Config(num_hidden_layers=6))
    assert costs.scan_flops_per_token(real) == 2 * 256 * 128 + 4096 * 128 + 4 * 4096 * 256
    # The accepted `ssm_scan_roofline` counts one group's C B^T: it reads
    # this model a little low (0.7%), never high.
    one = hybrid_costs.ssd_scan_cost(
        1.0, real["num_heads"], real["head_dim"], real["state_size"],
        real["chunk_size"], real["conv_dim"], real["conv_itemsize"],
    )["flops"]
    mine = costs.scan_flops_per_token(real)
    assert one < mine and (mine - one) / mine == pytest.approx(0.00685, abs=2e-4)
    # 6 x (32 x 128 x 256 x 4 + 3 x 5,120 x 2) = 6 x 4,225,024 a lane
    assert hybrid_costs.state_slot_bytes(real) == 25_350_144
    model = config["model"]
    assert costs.layer_parameter_count(model) == 430_120_032
    assert costs.parameter_count(model) == 5_254_594_112
    assert 2 * costs.parameter_count(model) == 10_509_188_224
    whole = dict(model, num_hidden_layers=config["published"]["num_hidden_layers"])
    assert round(costs.parameter_count(whole) / 1e9, 2) == 33.64
    assert costs.head_bytes({"vocab_size": 261120, "hidden_size": 5120,
                             "weight_itemsize": 2}) == 2_673_868_800


def test_the_program_counts_the_same_parameters_and_bytes(config, toy):
    cfg = fh.FalconH1Config(num_hidden_layers=config["model"]["num_hidden_layers"])
    leaves = jax.tree_util.tree_leaves(
        fh._leaf_shapes(cfg), is_leaf=lambda v: isinstance(v, tuple)
    )
    assert sum(int(np.prod(s)) for s in leaves) == costs.parameter_count(config["model"])
    declared = sum(
        int(np.prod(shape)) * jnp.dtype(dtype).itemsize
        for _, shape, dtype in fh.recurrent_kinds(cfg)[fh.MAMBA].arrays
    )
    assert 6 * declared == hybrid_costs.state_slot_bytes(fh.recurrent_shape(cfg))
    # and of the seeded toy tree
    toy_cfg, fields, params = toy
    assert fh.num_params(params) == costs.parameter_count(fields)


@pytest.fixture
def collected(monkeypatch):
    """A toy of what a traced run collects: two decode runs and one chunk."""
    monkeypatch.setattr(hybrid_costs, "peaks", lambda: peaks_for("TPU v5 lite"))
    monkeypatch.setattr(costs, "peaks", lambda: peaks_for("TPU v5 lite"))
    return {
        "engine_after": {"head_shape": {"vocab_size": 261120, "hidden_size": 5120,
                                        "weight_itemsize": 2}},
        "engine_window": {},
        "trace": {
            "busy_s": 0.05,
            "modules": {"jit__decode_step(1)": {"runs": 2}, "jit__prefill_step(2)": {"runs": 1}},
            "op_seconds": {
                "jit__decode_step/fusion.1 fusion": 0.004,
                "jit__decode_step/fusion.2 fusion": 0.004,
                "jit__decode_step/fusion.3 fusion": 0.002,
                "jit__prefill_step/fusion.9 fusion": 0.001,
            },
        },
        "device_report": {"op_scopes": {
            "jit__decode_step": {"fusion.1": "llm.head", "fusion.2": "llm.head",
                                 "fusion.3": "llm.mlp"},
            "jit__prefill_step": {"fusion.9": "llm.head"},
        }},
    }


def test_head_roofline_on_a_recorded_toy(collected):
    peaks = peaks_for("TPU v5 lite")
    got = layer_metrics.read("head_roofline", collected)
    # 2.67 GB a run x 2 decode runs over the decode program's 8 ms of head
    # (the chunk's head is not counted) and the HBM peak
    assert got == pytest.approx(100 * 2 * 2_673_868_800 / peaks["hbm_bytes_per_s"] / 0.008)
    assert 0 < got < 100


def test_head_roofline_finds_nothing_on_a_program_without_the_shape(collected):
    """The parent commit: no `head_shape`. Or no scope map, or no trace. The
    reader returns None and does not raise."""
    parent = dict(collected, engine_after={})
    unscoped = dict(collected, device_report={})
    untraced = dict(collected, trace=None)
    no_head = dict(collected, device_report={"op_scopes": {
        "jit__decode_step": {"fusion.3": "llm.mlp"}}})
    for found in (parent, unscoped, untraced, no_head):
        assert layer_metrics.read("head_roofline", found) is None


def test_the_schedule_is_the_mix(loaded):
    cell = manifest.cell(loaded, CELL)
    mix, engine = cell["traffic_mix"], cell["config_file"]["engine"]
    limit = engine["block_size"] * engine["max_blocks_per_seq"]
    schedule = traffic.generate(mix, 1, 45.0, 261120, limit)
    requests = schedule["requests"]
    assert len(requests) == 192 * 16
    prompts = np.array([len(r["prompt_ids"]) for r in requests])
    answers = np.array([r["max_new_tokens"] for r in requests])
    assert prompts.min() >= 8 and prompts.max() <= 2048
    assert answers.min() >= 16 and answers.max() <= 768
    assert (prompts + answers).max() <= limit
    # lognormal(median 128, sigma 1.0) cut to 8-2,048 has mean about 205;
    # lognormal(median 192, sigma 0.6) cut to 16-768 about 225
    assert 185 < prompts.mean() < 225 and 110 < np.median(prompts) < 146
    assert 210 < answers.mean() < 240 and 176 < np.median(answers) < 208
    # ids from the whole vocabulary, no two prompts sharing their first block
    ids = np.concatenate([r["prompt_ids"] for r in requests[:256]])
    assert ids.max() > 250_000 and ids.min() >= 0
    firsts = {tuple(r["prompt_ids"][:16]) for r in requests if len(r["prompt_ids"]) >= 16}
    assert len(firsts) == sum(len(r["prompt_ids"]) >= 16 for r in requests)
    # the same schedule (lengths, order) whatever --seed draws
    again = traffic.generate(mix, 4_600_000_123, 45.0, 261120, limit)
    assert [r["max_new_tokens"] for r in again["requests"]] == list(answers)


def test_the_configuration_is_the_catalogs_row_cut_as_it_says(loaded, config):
    entry = next(c for c in loaded["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == config["reduced"] == ["num_hidden_layers"]
    published = config["published"]
    for key, value in published.items():
        if key != "num_hidden_layers":
            assert config[key] == value, key  # every other key as published, top level
    assert config["num_hidden_layers"] == 6 and published["num_hidden_layers"] == 72
    model = config["model"]
    for key, value in model.items():  # no width, head, group, state or vocabulary row cut
        if key in published and key != "num_hidden_layers":
            assert value == published[key], key
    assert model["num_hidden_layers"] == 6
    assert set(config["assumed"]) >= {"gated_norm", "ssm_multipliers_order", "dt",
                                      "precision", "initialisation"}
    assert "twelve" in config["deployment"] and "pipeline" in config["deployment"]
    assert "last stage" in config["deployment"]
    cfg = fh.FalconH1Config(num_hidden_layers=6)
    for name, std in fh.init_std(cfg).items():
        assert f"{name} {std:.4g}" in config["assumed"]["initialisation"], name
    rehearsal = config["rehearsal"]["model"]
    assert rehearsal["num_hidden_layers"] == 3 and rehearsal["mamba_n_groups"] == 2
    assert rehearsal["mamba_n_heads"] == 4
    assert rehearsal["num_attention_heads"] == 5 * rehearsal["num_key_value_heads"]
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f) if r["name"] == "Falcon-H1-34B-Instruct")
        assert published == row["config"] and config["source"] == row["source_url"]


def test_the_cell_has_the_issues_traffic(loaded):
    cell = manifest.cell(loaded, CELL)
    mix = cell["traffic_mix"]
    assert cell["chips"] == 1 and mix["loop"] == "closed"
    assert (mix["clients"], mix["requests_per_client"], mix["lead_in_s"]) == (192, 16, 20)
    assert (mix["sessions"], mix["shared_prefix"], mix["schedule_seed"]) == (0, 0, 46)
    assert mix["prompt"] == {"median": 128, "sigma": 1.0, "min": 8, "max": 2048}
    assert mix["answer"] == {"median": 192, "sigma": 0.6, "min": 16, "max": 768}
    mine = manifest.metrics_of(loaded, CELL)
    assert set(mine["end_to_end"]) == {"completed_tokens_per_s", "setup_s"}
    assert {"ssm_update_roofline", "ssm_scan_roofline", "ssm_busy_share",
            "full_attn_roofline", "mixed_attn_busy_share", "head_roofline",
            "decode_occupancy", "tput_preemptions", "tput_decode_step_device_ms",
            "tput_device_idle_share", "setup_trace_lower_s"} <= set(mine["per_layer"])
    assert not [name for name in mine["per_layer"]
                if name.startswith(("moe_", "gdn_", "window_", "expert_"))]
    others = [w["name"] for w in loaded["workloads"] if w["name"] != CELL]
    head = next(m for m in loaded["per_layer"] if m["name"] == "head_roofline")
    assert head["workloads"] == [CELL] and head["layer"] == "jitted step programs"
    assert len(others) == 7


def test_the_new_manifest_passes_the_drivers_rules(loaded):
    manifest.validate(loaded)
    engine = manifest.cell(loaded, CELL)["config_file"]["engine"]
    assert engine["block_size"] * engine["max_blocks_per_seq"] == 2816
    assert engine["max_decode_slots"] in (96, 80) and engine["num_blocks"] % 256 == 0
    assert engine["prefill_buckets"] == [256, 1024, 2048]
    assert set(engine) == {"block_size", "num_blocks", "max_blocks_per_seq",
                           "max_decode_slots", "prefill_buckets"}  # every option at its default
    serve = manifest.cell(loaded, CELL)["config_file"]["serve"]
    assert serve == {"max_concurrent_queries": 224}
