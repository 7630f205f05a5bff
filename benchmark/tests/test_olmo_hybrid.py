"""What PR 42 added for `olmo-hybrid-7b-16l`: the benchmark's own reference
against the repository's, every control and an altered token coming out not
correct, the cost functions against counts done by loops at toy sizes, the
three readers on a recorded toy `collected`, and the configuration against
the catalog's row and the driver's rules."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from lib import layer_metrics, manifest
from lib import olmo_hybrid_costs as costs
from lib.peaks import peaks_for
from lib.reference_olmo_hybrid import CONTROLS, OlmoHybridServingReference, sizes
from ray_tpu.models import olmo_hybrid as oh
from ray_tpu.models import olmo_hybrid_reference as repo_reference
from runners.serve import within_limits

CONFIG = "olmo-hybrid-7b-16l"
CELL = CONFIG + ".gen-batch"


@pytest.fixture(scope="module")
def loaded():
    return manifest.load()


@pytest.fixture(scope="module")
def config(loaded):
    return manifest.cell(loaded, CELL)["config_file"]


@pytest.fixture(scope="module")
def toy(config):
    fields = dict(config["rehearsal"]["model"], dtype="float32", param_dtype="float32")
    cfg = oh.OlmoHybridConfig(**{**fields, "dtype": jnp.float32, "param_dtype": jnp.float32})
    return cfg, fields, oh.init_params(cfg, 5)


def test_the_benchmarks_reference_is_the_repositorys(toy):
    cfg, fields, params = toy
    tokens = list(np.random.RandomState(0).randint(1, 512, 40))
    mine = OlmoHybridServingReference(sizes(fields), params, pad_to=16)
    got = mine.logits(tokens, slice(0, 40))
    want = np.asarray(repo_reference.forward(cfg, params, jnp.asarray(tokens)))
    # float32 both, another order of sums: 1e-6 on logits 0.16 wide
    assert float(np.abs(got - want).max()) < 2e-5
    for name, variant in (("beta_without_2", dict(beta_factor=1.0)),
                          ("no_qk_norm", dict(qk_norm=False))):
        moved = mine.logits(tokens, slice(0, 40), **CONTROLS[name])
        theirs = np.asarray(repo_reference.forward(cfg, params, jnp.asarray(tokens), **variant))
        assert float(np.abs(moved - theirs).max()) < 2e-5, name
        assert float(np.abs(moved - want).max()) > 2e-4, name  # and it is another answer


def test_a_rounded_state_is_rounded_alike_in_both_references(toy):
    """Layer by layer from the same rows: over the stack a rounding that
    flips on a last bit grows to a hundredth of a logit, in one layer the two
    agree to 1e-5 while the rounding moves the rows by a thousand times that."""
    from lib.reference_olmo_hybrid import layer

    cfg, fields, params = toy
    tokens = jnp.asarray(np.random.RandomState(0).randint(1, 512, 40))
    f32 = lambda tree: jax.tree_util.tree_map(lambda x: x.astype(jnp.float32), tree)  # noqa: E731
    moved = 0.0
    with jax.default_matmul_precision("highest"):
        h = params["wte"][tokens]
        for kind, p in zip(cfg.layer_types, params["layers"]):
            mine = layer(sizes(fields), kind, f32(p), h, state_dtype=jnp.bfloat16)
            theirs = repo_reference.layer(cfg, kind, f32(p), h, state_dtype=jnp.bfloat16)
            assert float(jnp.abs(mine - theirs).max()) < 5e-5, kind
            h = repo_reference.layer(cfg, kind, f32(p), h)
            moved = max(moved, float(jnp.abs(theirs - h).max()))
    assert moved > 5e-3  # the rounding is not optimised away


def test_a_cut_tail_is_a_sequence_started_again_at_the_cut(toy):
    """`conv_tail_cut` at position 16 of a prompt of 24: the convolution's
    outputs from there on are those of the sequence's tail alone, for three
    positions; before it nothing moves."""
    from lib.reference_olmo_hybrid import _conv

    x = jnp.asarray(np.random.RandomState(3).randn(24, 5), jnp.float32)
    w = jnp.asarray(np.random.RandomState(4).randn(4, 5), jnp.float32)
    cut = np.zeros(24, bool)
    cut[16] = True
    got, whole, tail = _conv(x, w, jnp.asarray(cut)), _conv(x, w), _conv(x[16:], w)
    np.testing.assert_allclose(got[:16], whole[:16], atol=1e-6)
    np.testing.assert_allclose(got[16:], tail, atol=1e-6)
    assert float(jnp.abs(got[16:19] - whole[16:19]).max()) > 0.1
    np.testing.assert_allclose(got[19:], whole[19:], atol=1e-6)


def test_every_control_and_an_altered_token_come_out_not_correct(toy):
    cfg, fields, params = toy
    reference = OlmoHybridServingReference(sizes(fields), params, pad_to=16)
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
    assert set(readings) == set(CONTROLS)
    for name, reading in readings.items():
        assert reading["logit_move"] > 1e-4, name
        assert reading["flipped"] > 0 and not within_limits(pooled(reading), limits), name


def _scan_flops_by_loops(heads, key, value, chunk):
    """One chunk of one layer, multiply-adds counted one by one (x 2)."""
    macs = 0
    for _ in range(heads):
        for i in range(chunk):
            macs += key * i  # k_i . k_j for j < i
            macs += key * (i + 1)  # q_i . k_j for j <= i
            macs += (key + value) * i  # the substitution's row i on beta K | beta V
            macs += value * (i + 1)  # sum_j <= i of mixed_ij d_j
            macs += 3 * key * value  # W S, Q S and K^T D, a token's share
    return 2 * macs


def test_costs_against_counts_done_by_loops(config):
    toy = {"num_layers": 3, "num_heads": 4, "key_dim": 8, "value_dim": 16, "conv_width": 4,
           "conv_dim": 128, "chunk_size": 8, "state_itemsize": 4, "conv_itemsize": 2}
    assert costs.state_slot_bytes(toy) == 3 * (4 * 8 * 16 * 4 + 3 * 128 * 2)
    by_loops = _scan_flops_by_loops(4, 8, 16, 8)
    counted = costs.scan_flops_per_token(toy) * 8
    # The functions count a causal half as chunk / 2 a token where the loop
    # has (chunk -+ 1) / 2: within one row in `chunk`, and never under it by more.
    assert abs(counted - by_loops) <= 2 * 4 * 8 * (2 * 8 + 2 * 16)
    assert costs.scan_flops(10, toy) == 10 * 3 * costs.scan_flops_per_token(toy)
    real = {"num_layers": 12, "num_heads": 30, "key_dim": 96, "value_dim": 192,
            "conv_width": 4, "conv_dim": 11520, "chunk_size": 64, "state_itemsize": 4,
            "conv_itemsize": 2}
    # 12 x (30 x 96 x 192 x 4 + 3 x 11,520 x 2) = 12 x 2,280,960 = 27.4 MB a lane
    assert costs.state_slot_bytes(real) == 12 * 2280960
    # 30 heads x (64 x (192 + 288 + 192) + 6 x 96 x 192) = 4.608 M a token and layer
    assert costs.scan_flops_per_token(real) == 30 * (64 * 672 + 110592) == 4608000
    model = config["model"]
    assert costs.parameter_count(model) == 4100788944
    assert round(2 * costs.parameter_count(model) / 1e9, 2) == 8.20
    whole = dict(model, layer_types=config["published"]["layer_types"])
    assert round(costs.parameter_count(whole) / 1e9, 2) == 7.43


def test_the_program_counts_the_same_parameters_and_bytes(config):
    cfg = oh.OlmoHybridConfig(layer_types=tuple(config["model"]["layer_types"]))
    leaves = jax.tree_util.tree_leaves(
        oh._leaf_shapes(cfg), is_leaf=lambda v: isinstance(v, tuple)
    )
    assert sum(int(np.prod(s)) for s in leaves) == costs.parameter_count(config["model"])
    declared = sum(
        int(np.prod(shape)) * jnp.dtype(dtype).itemsize
        for _, shape, dtype in oh.recurrent_kinds(cfg)[oh.LINEAR].arrays
    )
    assert 12 * declared == costs.state_slot_bytes(oh.recurrent_shape(cfg))


@pytest.fixture
def collected(monkeypatch):
    """A toy of what a traced run collects: two decode runs and one chunk."""
    monkeypatch.setattr(costs, "peaks", lambda: peaks_for("TPU v5 lite"))
    shape = {"num_layers": 3, "num_heads": 4, "key_dim": 8, "value_dim": 16, "conv_width": 4,
             "conv_dim": 128, "chunk_size": 8, "state_itemsize": 4, "conv_itemsize": 2}
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
            "jit__decode_step": {"fusion.1": "llm.mixer.gdn.update", "fusion.2": "llm.mlp"},
            "jit__prefill_step": {"fusion.7": "llm.mixer.gdn.scan", "fusion.8": "llm.mixer.gdn.proj"},
        }},
    }


def test_the_readers_on_a_recorded_toy(collected):
    peaks = peaks_for("TPU v5 lite")
    update = layer_metrics.read("gdn_update_roofline", collected)
    # 0.8 GB a dispatch x 2 runs over 3 ms and the HBM peak
    assert update == pytest.approx(100 * 1.6e9 / peaks["hbm_bytes_per_s"] / 0.003)
    scan = layer_metrics.read("gdn_scan_roofline", collected)
    flops = costs.scan_flops(1000, collected["engine_after"]["recurrent_shape"])
    assert scan == pytest.approx(100 * flops / peaks["bf16_flops_per_s"] / 0.001)
    busy = layer_metrics.read("gdn_busy_share", collected)
    assert busy == pytest.approx(100 * (0.003 + 0.001 + 0.004) / 0.01)


def test_the_readers_find_nothing_on_a_program_without_the_scopes(collected):
    """The parent commit, or another model: no such scope, no such counter,
    no `recurrent_shape` of this kind. Each reader returns None, none raises."""
    other = dict(collected, device_report={"op_scopes": {
        "jit__decode_step": {"fusion.1": "llm.mixer.mamba.update"},
        "jit__prefill_step": {"fusion.7": "llm.mixer.mamba.scan"},
    }})
    bare = {"engine_after": {}, "engine_window": {}, "trace": collected["trace"],
            "device_report": {}}
    for found in (other, bare, {**bare, "trace": None}):
        for name in ("gdn_update_roofline", "gdn_scan_roofline", "gdn_busy_share"):
            assert layer_metrics.read(name, found) is None, name


def test_the_configuration_is_the_catalogs_row_cut_as_it_says(loaded, config):
    entry = next(c for c in loaded["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == config["reduced"] == ["num_hidden_layers"]
    published = config["published"]
    for key, value in published.items():
        if key not in ("num_hidden_layers", "layer_types"):
            assert config[key] == value, key  # every other key as published, top level
    assert config["num_hidden_layers"] == 16 and published["num_hidden_layers"] == 32
    model = config["model"]
    assert config["layer_types"] == model["layer_types"] == published["layer_types"][:16]
    assert model["layer_types"] == ["linear_attention"] * 3 + ["full_attention"]  + model["layer_types"][4:]
    for key, value in model.items():  # no width, head count or vocabulary row cut
        if key in published and key != "layer_types":
            assert value == published[key], key
    assert set(config["assumed"]) >= {"positions", "norm_placement", "conv_bias",
                                      "initialisation", "precision"}
    assert "two" in config["deployment"] and "pipeline" in config["deployment"]
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f) if r["name"] == "Olmo-Hybrid-7B")
        assert published == row["config"] and config["source"] == row["source_url"]


def test_the_cell_has_the_issues_traffic(loaded):
    cell = manifest.cell(loaded, CELL)
    mix = cell["traffic_mix"]
    assert cell["chips"] == 1 and mix["loop"] == "closed"
    assert (mix["clients"], mix["requests_per_client"], mix["lead_in_s"]) == (128, 16, 20)
    assert (mix["sessions"], mix["shared_prefix"], mix["schedule_seed"]) == (0, 0, 42)
    assert mix["prompt"] == {"median": 384, "sigma": 0.8, "min": 32, "max": 2048}
    assert mix["answer"] == {"median": 384, "sigma": 0.5, "min": 64, "max": 1024}
    mine = manifest.metrics_of(loaded, CELL)
    assert set(mine["end_to_end"]) == {"completed_tokens_per_s", "setup_s"}
    assert {"gdn_update_roofline", "gdn_scan_roofline", "gdn_busy_share",
            "full_attn_roofline", "mixed_attn_busy_share", "decode_occupancy",
            "tput_preemptions", "tput_decode_step_device_ms", "tput_device_idle_share",
            "setup_trace_lower_s"} <= set(mine["per_layer"])
    assert not [name for name in mine["per_layer"]
                if name.startswith(("moe_", "ssm_", "window_", "expert_"))]


def test_the_new_manifest_passes_the_drivers_rules(loaded):
    manifest.validate(loaded)
    engine = manifest.cell(loaded, CELL)["config_file"]["engine"]
    assert engine["block_size"] * engine["max_blocks_per_seq"] == 3200
    assert engine["max_decode_slots"] == 64 and engine["num_blocks"] % 256 == 0
    assert engine["prefill_buckets"] == [256, 1024, 2048]
    assert set(engine) == {"block_size", "num_blocks", "max_blocks_per_seq",
                           "max_decode_slots", "prefill_buckets"}  # every option at its default
