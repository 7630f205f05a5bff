import copy
import json
import os

import pytest

from lib import layer_metrics, manifest


@pytest.fixture(scope="module")
def loaded():
    return manifest.load()


def test_benchmark_json_passes_the_drivers_rules(loaded):
    assert loaded["command"] == ["python3", "benchmark/run.py"]
    assert loaded["paths"] == ["benchmark"]
    assert sum(w["chips"] == 4 for w in loaded["workloads"]) <= 1


def test_every_cell_finds_its_files_and_its_readers(loaded):
    for entry in loaded["workloads"]:
        cell = manifest.cell(loaded, entry["name"])
        config = cell["config_file"]
        for key in ("runner", "source", "reduced", "assumed", "deployment", "correctness"):
            assert key in config, (entry["name"], key)
        assert os.path.exists(os.path.join(manifest.BENCH, "runners", config["runner"] + ".py"))
        assert cell["traffic_mix"]["loop"] in ("open", "closed", "steps")
        mine = manifest.metrics_of(loaded, entry["name"])
        assert "setup_s" in mine["end_to_end"] and len(mine["end_to_end"]) >= 2
        for name in mine["per_layer"]:
            with open(os.path.join(layer_metrics.DIR, name + ".json")) as f:
                reader = json.load(f)["reader"]
            if "same_as" in reader:
                with open(os.path.join(layer_metrics.DIR, reader["same_as"] + ".json")) as f:
                    reader = json.load(f)["reader"]
            assert "path" in reader or os.path.exists(
                os.path.join(layer_metrics.DIR, reader["python"])
            )


def test_pr_28s_cells_are_there_and_what_it_retired_is_gone(loaded):
    cells = {w["name"] for w in loaded["workloads"]}
    assert {"gpt2-large.chat-sessions-loaded", "gpt2-large.batch-unshared",
            "gpt2-small-train.packed-1k"} <= cells
    assert "gpt2-large.chat-sessions" not in cells
    names = {m["name"] for m in loaded["per_layer"]}
    assert not names & {"host_gap_mean_ms", "tput_host_gap_mean_ms"}


def test_a_per_layer_metric_lists_only_cells_that_report_what_it_moves(loaded):
    for m in loaded["per_layer"]:
        moved = next(e for e in loaded["end_to_end"] if e["name"] == m["moves"])
        cells = moved.get("workloads", [w["name"] for w in loaded["workloads"]])
        assert set(m["workloads"]) <= set(cells), m["name"]


def test_gpt2_large_is_36_layers_at_published_widths(loaded):
    config = manifest.cell(loaded, "gpt2-large.chat-sessions-loaded")["config_file"]
    model, published = config["model"], config["published"]
    assert (model["num_layers"], model["embed_dim"], model["num_heads"]) == (36, 1280, 20)
    assert (published["n_layer"], published["n_embd"], published["n_head"]) == (36, 1280, 20)
    assert model["max_seq_len"] == published["n_positions"] == 1024
    assert config["reduced"] == []


@pytest.mark.parametrize(
    "break_it",
    [
        lambda m: m["workloads"][0].update(name="has space"),
        lambda m: m["end_to_end"][0].update(unit="tokens per second"),
        lambda m: m["end_to_end"][0].update(unit="µs"),
        lambda m: m["end_to_end"][0].update(bound=0.2),
        lambda m: m["end_to_end"][0].update(why="no such key"),
        lambda m: m["per_layer"][0].update(moves="nothing"),
        lambda m: m["per_layer"][0].update(source="guess"),
        lambda m: m["end_to_end"][0].update(source="program_counter"),
        lambda m: m["workloads"][0].update(chips=2),
        lambda m: m["workloads"].append(dict(m["workloads"][0], name="again")),
        lambda m: m["configs"][0].update(file="elsewhere/x.json"),
        lambda m: m["command"].append("../outside.py"),
        lambda m: m.update(run_seconds=52),
        lambda m: m["end_to_end"].pop(-1),  # setup_s
        # a per-layer metric in every cell, its end-to-end metric in one
        lambda m: m["per_layer"][0].pop("workloads"),
    ],
)
def test_what_the_driver_would_refuse_is_refused(loaded, break_it):
    broken = copy.deepcopy(loaded)
    break_it(broken)
    with pytest.raises(manifest.ManifestError):
        manifest.validate(broken)


def test_reader_that_finds_nothing_says_so():
    collected = {"engine_window": {"num_preemptions": 3}, "trace": None}
    assert layer_metrics.read_all(["preemptions", "device_idle_share"], collected) == {
        "preemptions": 3.0, "device_idle_share": None,
    }


def test_a_twin_reads_what_its_original_reads():
    collected = {"engine_window": {"num_preemptions": 3}, "trace": {"idle_share": 0.25}}
    assert layer_metrics.read("tput_preemptions", collected) == 3.0
    assert layer_metrics.read("train_device_idle_share", collected) == 25.0


def test_every_reader_file_has_an_entry(loaded):
    listed = {m["name"] for m in loaded["per_layer"]}
    files = {f[:-5] for f in os.listdir(layer_metrics.DIR) if f.endswith(".json")}
    assert files == listed
