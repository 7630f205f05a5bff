"""`run.py` around a runner that measures nothing: what `setup_s` is, where
the compared numbers go, and when a compiled program makes a run incorrect.
The numbers are those of a warm run of `gpt2-large.batch-unshared` on the
chip (PR 28)."""

import importlib.util
import json
import os
import types

import pytest

import run
from lib.manifest import BENCH

RECORDED = {
    "start_to_open_s": 157.93, "compile_step_s": 17.41, "completed_tokens_per_s": 569.2,
    "worst_gap": 0.031, "mean_gap": 0.00021,
}


def stub_runner(ctx):
    return {
        "correct": True, "problems": [], "attempted": 330, "failed": 0,
        "window_open": run.PROCESS_START + RECORDED["start_to_open_s"],
        "setup_excluded_s": RECORDED["compile_step_s"],
        "compared": {"worst_logit_gap": [RECORDED["worst_gap"], 0.12],
                     "mean_logit_gap": [RECORDED["mean_gap"], 0.002]},
        "end_to_end": {"completed_tokens_per_s": RECORDED["completed_tokens_per_s"],
                       "itl_p50_ms": 20.0, "ttft_p90_ms": 900.0},
        "collected": {"compiles_in_window": 0, "memory_peak_bytes": 12430638080},
    }


@pytest.fixture
def on_a_chip(monkeypatch):
    found = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    monkeypatch.setattr(run, "load_runner", lambda name: types.SimpleNamespace(run=stub_runner))
    monkeypatch.setattr(run.device, "prepare_environment", lambda *a: None)
    monkeypatch.setattr(run.device, "place_cache", lambda: ".jax_cache")
    monkeypatch.setattr(run.device, "find_devices", lambda chips, rehearse: dict(found))
    monkeypatch.setattr(run.device, "memory_stats", lambda chips: [])
    monkeypatch.setattr(run.device, "CompileCounter", lambda: types.SimpleNamespace(count=0))


def test_serving_setup_s_leaves_out_the_compile_step(on_a_chip, capsys):
    assert run.main(["--workload", "gpt2-large.batch-unshared", "--seed", "1"]) == 0
    out, err = capsys.readouterr()
    final = json.loads(out.splitlines()[-1])
    assert final["metrics"]["setup_s"]["value"] == pytest.approx(157.93 - 17.41)
    assert final["metrics"]["completed_tokens_per_s"] == {"value": 569.2, "unit": "tokens/s"}
    assert final["device"]["memory_peak_bytes"] == 12430638080
    # Each number compared beside its limit: the line's last key, and the
    # last lines of standard error.
    assert list(final)[-1] == "compared"
    assert final["compared"]["mean_logit_gap"] == {"value": 0.00021, "limit": 0.002}
    assert err.splitlines()[-2:] == [
        "compared worst_logit_gap 0.031 limit 0.12",
        "compared mean_logit_gap 0.00021 limit 0.002",
    ]


def load_serve_runner():
    path = os.path.join(BENCH, "runners", "serve.py")
    spec = importlib.util.spec_from_file_location("runner_serve_under_test", path)
    serve = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(serve)
    return serve


def test_a_run_that_read_most_of_its_programs_may_compile_none():
    """Programs read from the cache and programs compiled and written, as the
    chip's runs counted them up to the window (PR 28)."""
    serve = load_serve_runner()
    assert serve.misses_of_a_warm_run(0, 13) == 0   # a checkout's first run
    assert serve.misses_of_a_warm_run(3, 10) == 0   # first of its cell, after another's
    assert serve.misses_of_a_warm_run(13, 0) == 0   # a warm run reads the cache
    assert serve.misses_of_a_warm_run(12, 1) == 1   # and this one did not


# What the chip read (PR 28): [widest gap, mean gap] of the served tokens and
# of the int8 control on the same prompts and tokens.
LIMITS = {"logit_tolerance": 0.12, "mean_gap_limit": 0.001}
SERVED = [[0.0225, 0.000347], [0.0317, 0.000397], [0.0215, 0.000259], [0.036, 0.00045],
          [0.012, 0.00010]]
CONTROL = [[0.058, 0.00150], [0.101, 0.00337], [0.063, 0.00222], [0.085, 0.00226], [0.090, 0.00231], [0.090, 0.00256],
           [0.156, 0.00332], [0.099, 0.00241], [0.106, 0.00299], [0.107, 0.00304]]


@pytest.mark.parametrize("reading", SERVED)
def test_the_served_tokens_of_the_chips_runs_are_within_the_limits(reading):
    gaps = {"worst_gap": reading[0], "mean_gap": reading[1]}
    assert load_serve_runner().within_limits(gaps, LIMITS)


@pytest.mark.parametrize("reading", CONTROL + [[2.26, 0.0102], [2.59, 0.0059], [None, None]])
def test_the_control_an_altered_token_and_no_reading_are_not(reading):
    gaps = {"worst_gap": reading[0], "mean_gap": reading[1]}
    assert not load_serve_runner().within_limits(gaps, LIMITS)
