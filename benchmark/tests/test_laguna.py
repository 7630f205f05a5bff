"""What PR 35 added for `laguna-s-2.1-1of8`: the benchmark's own reference
against the repository's and against the program at rehearsal sizes, the
control, the variants and an altered token coming out not correct, the cost
functions against numbers worked by hand, each new reader on a recorded
`collected`, and the configuration against the catalog's row and the
driver's rules."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from lib import laguna_costs as costs
from lib import layer_metrics, manifest
from lib.reference_laguna import VARIANTS, LagunaServingReference, sizes
from ray_tpu.models import laguna as lg
from ray_tpu.models import laguna_reference as repo_reference
from runners.serve import within_limits

CONFIG = "laguna-s-2.1-1of8"
CELL = CONFIG + ".code-gen-mixed"
NEW_METRICS = ("full_attn_roofline", "window_attn_roofline",
               "window_attn_prefill_roofline", "mixed_attn_busy_share",
               "window_cache_saving")


@pytest.fixture(scope="module")
def loaded():
    return manifest.load()


@pytest.fixture(scope="module")
def config(loaded):
    return manifest.cell(loaded, CELL)["config_file"]


@pytest.fixture(scope="module")
def toy(config):
    fields = dict(config["rehearsal"]["model"], dtype="float32", param_dtype="float32")
    cfg = lg.LagunaConfig(**{**fields, "dtype": jnp.float32, "param_dtype": jnp.float32})
    return cfg, fields, lg.init_params(cfg, 5)


def test_the_benchmarks_reference_is_the_repositorys_and_the_programs(toy):
    cfg, fields, params = toy
    tokens = list(np.random.RandomState(0).randint(1, 512, 90))  # several windows of 24
    mine = LagunaServingReference(sizes(fields), params, pad_to=16, query_block=16)
    got = mine.logits(tokens, slice(0, 90))
    want = np.asarray(repo_reference.forward(cfg, params, jnp.asarray(tokens)))
    program = np.asarray(lg.forward(cfg, params, jnp.asarray(tokens)))
    # float32 all three, another order of sums: 3e-7 on logits 0.16 wide
    assert float(np.abs(got - want).max()) < 2e-6
    assert float(np.abs(got - program).max()) < 2e-6
    for name in VARIANTS:  # no variant is optimised away
        moved = mine.logits(tokens, slice(0, 90), variant=name)
        assert float(np.abs(moved - want).max()) > 1e-6, name


def test_the_rehearsals_window_is_shorter_than_its_contexts(config):
    toy_model, engine = config["rehearsal"]["model"], config["rehearsal"]["engine"]
    assert toy_model["sliding_window"] * 4 < engine["block_size"] * engine["max_blocks_per_seq"]
    original = toy_model["rope_parameters"]["full_attention"]["original_max_position_embeddings"]
    assert original < engine["block_size"] * engine["max_blocks_per_seq"]
    assert set(toy_model) == set(config["model"])


def test_the_control_and_an_altered_token_come_out_not_correct(toy):
    cfg, fields, params = toy
    reference = LagunaServingReference(sizes(fields), params, pad_to=16, query_block=16)
    prompt = list(np.random.RandomState(1).randint(1, 512, 40))
    answer = []
    for _ in range(12):  # greedy by the reference itself: every gap is nought
        row = reference.logits(prompt + answer, slice(len(prompt) + len(answer) - 1, None))
        answer.append(int(row[0].argmax()))
    limits = {"logit_tolerance": 1e-4, "mean_gap_limit": 1e-6}

    def pooled(reading):
        return {"worst_gap": reading["worst_gap"],
                "mean_gap": reading["gap_sum"] / reading["tokens"]}

    sound = reference.judge(prompt, answer, limits["logit_tolerance"], noise=True)
    assert sound["ok"] and sound["flipped"] == 0 and within_limits(pooled(sound), limits)
    assert sound["context"] == 52
    for name in VARIANTS:
        assert sound[name]["logit_move"] > 0, name
    altered = list(answer)
    altered[6] = (altered[6] + 1) % 512
    judged = reference.judge(prompt, altered, limits["logit_tolerance"])
    assert not judged["ok"] and not within_limits(pooled(judged), limits)
    # `--reference-seed`: the same tokens against another seed's weights.
    other = LagunaServingReference(
        sizes(fields), lg.init_params(cfg, 6), pad_to=16, query_block=16
    )
    assert not within_limits(pooled(other.judge(prompt, answer, 1e-4)), limits)
    # The control is read at every position of a longer stretch.
    stretch = list(np.random.RandomState(2).randint(1, 512, 200))
    control = reference.control_gaps(prompt, stretch)
    assert control["flipped"] > 0 and not within_limits(pooled(control), limits)


def test_the_chips_readings_against_the_limits(config):
    """Every (mean, worst) gap read on the chip (PERF.md section 2) through
    the function that decides `correct`, at the limits the file states."""
    limits = config["correctness"]

    def correct(mean, worst):
        return within_limits({"mean_gap": mean, "worst_gap": worst}, limits)

    # Sound runs: the extremes of 18 seeds, and the two widest worst gaps.
    for mean, worst in [(0.00374, 0.283), (0.00446, 0.434), (0.00434, 0.716),
                        (0.00446, 0.626)]:
        assert correct(mean, worst)
    assert not correct(0.0395, 0.775)  # the int8 control: by the mean
    assert not correct(0.0051, 5.37)  # one served token altered: by the worst
    assert not correct(0.0081, 0.434)  # a window 16 keys longer, alone: by the mean
    assert not correct(4.88, 9.45)  # another seed's weights (`--reference-seed`)
    # Not seen alone at these widths: bfloat16 scores, a float16 gate.
    assert correct(0.0055, 0.434) and correct(0.00024, 0.37)
    assert not correct(0.0055 + 0.00374, 0.434)  # bfloat16 scores in a served program


FULL = {"num_layers": 3, "num_heads": 8, "head_dim": 128, "kv_itemsize": 2,
        "num_query_heads": 48}
WINDOW = {"num_layers": 9, "num_heads": 8, "head_dim": 128, "kv_itemsize": 2,
          "num_query_heads": 72, "horizon": 512}


def test_costs_against_numbers_worked_by_hand():
    # K and V of a token: 8 x 128 x 2 x 2 = 4,096 B a layer
    assert costs.token_bytes(FULL) == 3 * 4096 == 12288
    assert costs.token_bytes(WINDOW) == 9 * 4096 == 36864
    assert costs.decode_read_bytes(48 * 3600, FULL) == 48 * 3600 * 12288
    # A window of 4: positions 0..9 see 1, 2, 3, 4, 4, 4, 4, 4, 4, 4 keys.
    assert costs.window_pairs(0, 10, 4) == 1 + 2 + 3 + 4 * 7 == 34
    assert costs.window_pairs(2, 3, 4) == 3 + 4 + 4
    assert costs.window_pairs(7, 5, 4) == 5 * 4
    assert costs.window_pairs(0, 3, 100) == 1 + 2 + 3
    for offset, tokens, window in [(0, 1, 1), (5, 9, 7), (100, 64, 512), (500, 40, 512)]:
        assert costs.window_pairs(offset, tokens, window) == sum(
            min(p + 1, window) for p in range(offset, offset + tokens)
        )
    # A 2,048-token chunk from position 0 in one sliding layer: the first
    # 511 positions see 1..511 keys, the other 1,537 see 512 each.
    pairs = costs.window_pairs(0, 2048, 512)
    assert pairs == 511 * 512 // 2 + 1537 * 512 == 917760
    # QK^T and PV: 4 x 72 heads x 128 a pair, in 9 layers
    assert costs.prefill_pair_flops(pairs, WINDOW) == 4.0 * 917760 * 72 * 128 * 9
    # The program's own count of the same pairs.
    from ray_tpu.llm.hybrid_runner import visible_pairs

    assert visible_pairs(300, 700, 512) == costs.window_pairs(300, 700, 512)


def recorded():
    """A `collected` as a traced run leaves it, small numbers: 10 decode
    runs in the trace, 2 chunk runs, the window's counters over 100 decode
    dispatches and 20 chunks."""
    return {
        "trace": {
            "busy_s": 2.0,
            "op_seconds": {
                "jit__decode_step/fusion.1 fusion": 0.25,
                "jit__decode_step/custom.2 tpu_custom_call": 0.5,
                "jit__decode_step/fusion.3 fusion": 0.125,
                "jit__prefill_suffix_step/custom.7 tpu_custom_call": 0.25,
                "jit__prefill_step/custom.7 tpu_custom_call": 0.25,
                "jit__prefill_step/fusion.9 fusion": 0.5,
            },
            "modules": {
                "jit__decode_step(1)": {"runs": 10}, "jit__prefill_step(2)": {"runs": 1},
                "jit__prefill_suffix_step(3)": {"runs": 1},
            },
        },
        "device_report": {"op_scopes": {
            "jit__decode_step": {"fusion.1": "llm.mixer.attention.full",
                                 "custom.2": "llm.mixer.attention.window",
                                 "fusion.3": "llm.mixer.attention.proj"},
            "jit__prefill_step": {"custom.7": "llm.mixer.attention.window",
                                  "fusion.9": "llm.moe.routed"},
            "jit__prefill_suffix_step": {"custom.7": "llm.mixer.attention.window"},
        }},
        "engine_window": {
            "decode_dispatches": 100, "decode_context_tokens": 100 * 48 * 4000,
            "decode_window_tokens": 100 * 48 * 512, "prefill_chunk_dispatches": 20,
            "prefill_window_pairs": 20 * 917760,
            "held_tokens_full": 1000, "held_tokens_window": 125,
        },
        "engine_after": {
            "attention_shape": {"full": FULL, "window": WINDOW},
            "cache_classes": {"full": {"layers": 3}, "window": {"layers": 9}},
        },
    }


def test_each_new_reader_on_a_recorded_collected(monkeypatch):
    monkeypatch.setattr(costs, "peaks", lambda: {
        "hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12,
    })
    got = layer_metrics.read_all(NEW_METRICS, recorded())
    # 10 runs x 48 x 4,000 tokens x 12,288 B over 0.25 s and 819 GB/s
    assert got["full_attn_roofline"] == pytest.approx(
        100 * 10 * 48 * 4000 * 12288 / 819e9 / 0.25
    )
    assert got["window_attn_roofline"] == pytest.approx(
        100 * 10 * 48 * 512 * 36864 / 819e9 / 0.5
    )
    assert got["window_attn_prefill_roofline"] == pytest.approx(
        100 * 2 * 4.0 * 917760 * 72 * 128 * 9 / 197e12 / 0.5
    )
    assert got["mixed_attn_busy_share"] == pytest.approx(100 * (0.25 + 0.5 + 0.125 + 0.5) / 2.0)
    # 12 x 1,000 over 3 x 1,000 + 9 x 125
    assert got["window_cache_saving"] == pytest.approx(12000 / 4125)
    assert all(0 < got[name] < 100 for name in NEW_METRICS if name.endswith("roofline"))


def test_the_new_readers_are_silent_on_a_program_without_the_counters(monkeypatch):
    """The parent commit has neither the scopes nor the counters nor the
    classes: every new reader gives None and does not raise."""
    monkeypatch.setattr(costs, "peaks", lambda: {
        "hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12,
    })
    older = recorded()
    older["device_report"] = {}
    older["engine_window"] = {"decode_dispatches": 100, "decode_context_tokens": 5}
    older["engine_after"] = {"attention_shape": {"num_layers": 36, "num_heads": 20,
                                                 "head_dim": 64, "kv_itemsize": 2}}
    assert layer_metrics.read_all(NEW_METRICS, older) == dict.fromkeys(NEW_METRICS)
    untraced = recorded()
    untraced["trace"] = None
    silent = layer_metrics.read_all(NEW_METRICS, untraced)
    assert silent["window_cache_saving"] is not None
    assert all(silent[name] is None for name in NEW_METRICS[:4])


def test_the_configuration_is_the_catalogs_row_cut_as_it_says(loaded, config):
    entry = next(c for c in loaded["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == config["reduced"] == ["num_hidden_layers", "num_experts"]
    published = config["published"]
    for key, value in published.items():
        if key in config["reduced"]:
            continue
        assert config[key] == value, key  # every other key as published, top level
    assert config["num_hidden_layers"] == 12 and published["num_hidden_layers"] == 48
    assert config["num_experts"] == 32 and published["num_experts"] == 256
    model = config["model"]
    # Three whole periods, the leading dense layer counted once.
    assert model["layer_types"] == published["layer_types"][:12]
    assert model["layer_types"].count("full_attention") == 3
    assert model["mlp_layer_types"] == ["dense"] + ["sparse"] * 11
    assert model["num_attention_heads_per_layer"] == [48, 72, 72, 72] * 3
    assert model["experts_held"] == list(range(32))
    per_layer = ("layer_types", "mlp_layer_types", "num_attention_heads_per_layer")
    for key, value in model.items():  # no width cut, the router and top 10 whole
        if key in published and key not in per_layer:
            assert value == published[key], key
    assert model["num_experts"] == 256 and model["sliding_window"] == 512
    for item in ("gate", "qk_norm", "router_score", "shared_expert_gate"):
        assert "alternative" in config["assumed"][item], item
    assert "eight chips" in config["deployment"] and "12 layers" in config["deployment"]
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f) if r["name"] == "Laguna-S-2.1")
        assert published == row["config"] and config["source"] == row["source_url"]
        assert entry["source"] == row["source_url"]


def test_the_program_holds_the_parameters_the_file_states(config):
    fields = dict(config["model"])
    cfg = lg.LagunaConfig(**{**fields, "dtype": jnp.bfloat16, "param_dtype": jnp.bfloat16})
    shapes = lg._leaf_shapes(cfg)
    leaves = jax.tree_util.tree_leaves(shapes, is_leaf=lambda v: isinstance(v, tuple))
    held = sum(int(np.prod(s)) for s in leaves)
    # 157.4 + 9 x 375.4 + 2 x 356.4 + 616.6 M (+ the norms): 9.73 GB in bfloat16
    assert held == 4865018880 and round(2 * held / 1e9, 2) == 9.73
    assert "4,865,018,880" in config["weights"]
    assert [(c.name, c.layers, c.horizon) for c in cfg.cache_classes] == [
        ("full", 3, None), ("window", 9, 512),
    ]


def test_the_cell_has_the_issues_traffic(loaded):
    cell = manifest.cell(loaded, CELL)
    mix = cell["traffic_mix"]
    assert cell["chips"] == 1 and mix["loop"] == "closed"
    assert (mix["clients"], mix["requests_per_client"]) == (96, 6)
    assert (mix["sessions"], mix["shared_prefix"], mix["schedule_seed"]) == (0, 0, 35)
    assert mix["prompt"] == {"median": 2048, "sigma": 0.9, "min": 64, "max": 12288}
    assert mix["answer"] == {"median": 1024, "sigma": 0.4, "min": 128, "max": 2048}
    mine = manifest.metrics_of(loaded, CELL)
    assert set(mine["end_to_end"]) == {"completed_tokens_per_s", "setup_s"}
    assert set(NEW_METRICS) | {"moe_decode_roofline", "moe_prefill_roofline",
                               "moe_busy_share", "expert_load_max_over_mean",
                               "decode_occupancy", "tput_preemptions",
                               "tput_decode_step_device_ms"} <= set(mine["per_layer"])
    assert not {"tput_paged_attn_roofline", "tput_paged_attn_busy_share",
                "tput_prefix_hit_share", "ssm_busy_share"} & set(mine["per_layer"])
    for name in NEW_METRICS:
        entry = next(m for m in loaded["per_layer"] if m["name"] == name)
        # In the list, which later cells may join.
        assert CELL in entry["workloads"] and entry["moves"] == "completed_tokens_per_s"


def test_the_new_manifest_passes_the_drivers_rules(loaded):
    manifest.validate(loaded)
    engine = manifest.cell(loaded, CELL)["config_file"]["engine"]
    # The longest request whole: 12,288 of prompt and 2,048 of answer.
    assert engine["block_size"] * engine["max_blocks_per_seq"] == 14336
    assert engine["max_decode_slots"] == 48
    assert set(engine) == {"block_size", "num_blocks", "max_blocks_per_seq",
                           "max_decode_slots", "prefill_buckets"}  # every option at its default
    assert [len(w["why"]) <= 200 for w in loaded["workloads"]]
    assert sum(w["chips"] == 4 for w in loaded["workloads"]) == 0
