"""What PR 32 added for `granite-4.0-h-small-1of2`: the benchmark's own
reference against the repository's, the control and an altered token coming
out not correct, the cost functions against numbers worked by hand, and the
configuration against the catalog's row and the driver's rules."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from lib import hybrid_costs as costs
from lib import manifest
from lib.reference_granite_hybrid import HybridServingReference, sizes
from ray_tpu.models import granite_hybrid as gh
from ray_tpu.models import granite_hybrid_reference as repo_reference
from runners.serve import within_limits

CONFIG = "granite-4.0-h-small-1of2"
CELL = CONFIG + ".rag-batch"


@pytest.fixture(scope="module")
def loaded():
    return manifest.load()


@pytest.fixture(scope="module")
def config(loaded):
    return manifest.cell(loaded, CELL)["config_file"]


@pytest.fixture(scope="module")
def toy(config):
    fields = dict(config["rehearsal"]["model"], dtype="float32", param_dtype="float32")
    cfg = gh.GraniteHybridConfig(**{
        **fields, "dtype": jnp.float32, "param_dtype": jnp.float32,
        "layer_types": tuple(fields["layer_types"]),
        "experts_held": tuple(fields["experts_held"]),
    })
    params = gh.init_params(cfg, 5)
    return cfg, fields, params


def test_the_benchmarks_reference_is_the_repositorys(toy):
    cfg, fields, params = toy
    tokens = list(np.random.RandomState(0).randint(1, 512, 40))
    mine = HybridServingReference(sizes(fields), params, pad_to=16)
    want = np.asarray(repo_reference.forward(cfg, params, jnp.asarray(tokens)))
    got = mine.logits(tokens, slice(0, 40))
    # float32 both, another order of sums: 2e-9 on logits 0.003 wide
    assert float(np.abs(got - want).max()) < 2e-8
    rounded = mine.logits(tokens, slice(0, 40), state_dtype=jnp.bfloat16)
    assert float(np.abs(rounded - want).max()) > 1e-7  # the rounding is not optimised away


def test_the_control_and_an_altered_token_come_out_not_correct(toy):
    cfg, fields, params = toy
    reference = HybridServingReference(sizes(fields), params, pad_to=16)
    prompt = list(np.random.RandomState(1).randint(1, 512, 30))
    answer = []
    for _ in range(12):  # greedy by the reference itself: every gap is nought
        row = reference.logits(prompt + answer, slice(len(prompt) + len(answer) - 1, None))
        answer.append(int(row[0].argmax()))
    limits = {"logit_tolerance": 1e-6, "mean_gap_limit": 1e-8}

    def pooled(reading):
        return {"worst_gap": reading["worst_gap"],
                "mean_gap": reading["gap_sum"] / reading["tokens"]}

    sound = reference.judge(prompt, answer, limits["logit_tolerance"], noise=True)
    assert sound["ok"] and sound["flipped"] == 0 and within_limits(pooled(sound), limits)
    assert sound["bf16_state"]["logit_move"] > 0
    altered = list(answer)
    altered[6] = (altered[6] + 1) % 512
    judged = reference.judge(prompt, altered, limits["logit_tolerance"])
    assert not judged["ok"] and not within_limits(pooled(judged), limits)
    # The control is read at every position of a longer stretch: at these
    # widths int8 moves a logit by a tenth of the spread between the best two.
    stretch = list(np.random.RandomState(2).randint(1, 512, 200))
    control = reference.control_gaps(prompt, stretch)
    assert control["logit_move"] > 100 * sound["bf16_state"]["logit_move"]
    assert control["flipped"] > 0 and not within_limits(pooled(control), limits)


def test_costs_against_numbers_worked_by_hand(config):
    model = config["model"]
    recurrent = {"num_layers": 9, "num_heads": 128, "head_dim": 64, "state_size": 128,
                 "conv_width": 4, "conv_dim": 8448, "state_itemsize": 4, "conv_itemsize": 2}
    # 9 x (128 x 64 x 128 x 4 = 4.194 MB + 8,448 x 3 x 2 = 0.051 MB) = 38.2 MB a slot
    assert costs.state_slot_bytes(recurrent) == 9 * (4194304 + 50688)
    assert round(costs.state_slot_bytes(recurrent) / 1e6, 1) == 38.2
    expert = {"hidden_size": 4096, "expert_width": 768, "weight_itemsize": 2}
    assert costs.expert_params(expert) == 9437184  # 9.437 M
    assert round(costs.expert_bytes(expert) / 1e6, 2) == 18.87
    # 9 x 121.46 M + 61.12 M + 10 x 339.74 M + 411.04 M = 4,962.7 M: 9.93 GB
    assert costs.parameter_count(model) == 4962732672
    assert round(2 * costs.parameter_count(model) / 1e9, 2) == 9.93
    whole = dict(model, layer_types=config["published"]["layer_types"],
                 experts_held=list(range(72)))
    assert round(costs.parameter_count(whole) / 1e9, 1) == 32.2  # 32B-A9B
    scan = costs.ssd_scan_cost(2048, 128, 64, 128, 256, 8448)
    assert scan["flops"] == 2048 * (128 * 256 + 8192 * 256 + 4 * 8192 * 128)
    assert scan["bytes"] == 2048 * ((8448 + 16384) * 2 + 512) + 8 * 2 * 8192 * 128 * 4


def test_the_program_counts_the_same_parameters(config):
    shapes = gh._leaf_shapes(gh.GraniteHybridConfig(experts_held=tuple(range(36))))
    leaves = jax.tree_util.tree_leaves(shapes, is_leaf=lambda v: isinstance(v, tuple))
    assert sum(int(np.prod(s)) for s in leaves) == costs.parameter_count(config["model"])


def test_scope_seconds_reads_the_map_and_gives_nothing_without_one():
    collected = {
        "trace": {"busy_s": 1.0, "op_seconds": {
            "jit__decode_step/fusion.1 fusion": 0.25,
            "jit__decode_step/fusion.2 fusion": 0.5,
            "jit__prefill_step/fusion.1 fusion": 0.125,
        }},
        "device_report": {"op_scopes": {
            "jit__decode_step": {"fusion.1": "llm.moe.routed", "fusion.2": "llm.head"},
            "jit__prefill_step": {"fusion.1": "llm.moe.routed"},
        }},
    }
    assert costs.scope_seconds(collected, costs.DECODE, r"^llm\.moe\.routed$") == 0.25
    assert costs.scope_seconds(collected, r"^jit__", r"^llm\.moe\.routed$") == 0.375
    assert costs.scope_seconds(collected, costs.PREFILL, r"^llm\.head$") is None
    # the parent commit publishes no map: every reader built on it is silent
    assert costs.scope_seconds({**collected, "device_report": {}}, costs.DECODE, ".") is None
    assert costs.scope_seconds({"trace": collected["trace"]}, costs.DECODE, ".") is None


def test_the_configuration_is_the_catalogs_row_cut_as_it_says(loaded, config):
    entry = next(c for c in loaded["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == config["reduced"] == ["num_hidden_layers", "num_local_experts"]
    published = config["published"]
    for key, value in published.items():
        if key in config["reduced"]:
            continue
        assert config[key] == value, key  # every other key as published, top level
    assert config["num_hidden_layers"] == 10 and published["num_hidden_layers"] == 40
    assert config["num_local_experts"] == 36 and published["num_local_experts"] == 72
    model = config["model"]
    assert model["layer_types"] == published["layer_types"][:10]  # one whole period
    assert model["experts_held"] == list(range(36))
    for key, value in model.items():  # no width cut, the router and top 10 whole
        if key in published and key != "layer_types":
            assert value == published[key], key
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f) if r["name"] == "granite-4.0-h-small")
        assert published == row["config"] and config["source"] == row["source_url"]


def test_the_cell_has_the_issues_traffic(loaded):
    cell = manifest.cell(loaded, CELL)
    mix = cell["traffic_mix"]
    assert cell["chips"] == 1 and mix["loop"] == "closed"
    assert (mix["clients"], mix["requests_per_client"], mix["lead_in_s"]) == (96, 16, 8)
    assert (mix["sessions"], mix["shared_prefix"], mix["schedule_seed"]) == (0, 0, 32)
    assert mix["prompt"] == {"median": 1536, "sigma": 0.6, "min": 64, "max": 6144}
    assert mix["answer"] == {"median": 128, "sigma": 0.5, "min": 8, "max": 256}
    mine = manifest.metrics_of(loaded, CELL)
    assert set(mine["end_to_end"]) == {"completed_tokens_per_s", "setup_s"}
    assert {"ssm_update_roofline", "ssm_scan_roofline", "moe_decode_roofline",
            "moe_prefill_roofline", "ssm_busy_share", "moe_busy_share",
            "expert_load_max_over_mean", "decode_occupancy",
            "tput_decode_step_device_ms"} <= set(mine["per_layer"])
    assert not {"tput_paged_attn_roofline", "tput_paged_attn_busy_share"} & set(mine["per_layer"])


def test_the_new_manifest_passes_the_drivers_rules(loaded):
    manifest.validate(loaded)
    engine = manifest.cell(loaded, CELL)["config_file"]["engine"]
    assert engine["block_size"] * engine["max_blocks_per_seq"] == 6400
    assert engine["prefill_buckets"] == [256, 1024, 2048]
    assert set(engine) == {"block_size", "num_blocks", "max_blocks_per_seq",
                           "max_decode_slots", "prefill_buckets"}  # every option at its default
