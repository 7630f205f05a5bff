"""A whole run of a serving cell with the timed path broken underneath it
must come out not correct. `--rehearse` is the harness without its look for a
chip: toy widths on the CPU, the same deployment, load generator, reduction
and comparison with the reference. The one fault a served model on one chip
can have is a token altered where it is produced. About half a minute a run."""

import json

import numpy as np
import pytest

import run
from ray_tpu.llm.model_runner import GPTRunner

CELL = "gpt2-large.chat-sessions-loaded"


def rehearse(capsys, seed):
    assert run.main(["--workload", CELL, "--rehearse", "--seconds", "8", "--seed", str(seed),
                     "--control"]) == 0
    lines = [json.loads(line) for line in capsys.readouterr().out.splitlines()
             if line.startswith("{")]
    by_kind = {line["info"]: line for line in lines}
    return by_kind["rehearsal_done"], by_kind["reference"]


def test_a_sound_run_is_correct(capsys):
    done, reference = rehearse(capsys, 11)
    assert done["correct"] and not done["problems"]
    assert reference["checked"] >= 2 and reference["worst_gap"] < reference["logit_tolerance"]
    # `--control`: the same sample with one served token altered afterwards
    # goes through the same comparison and fails it.
    assert not reference["altered_token"]["ok"]
    assert reference["altered_token"]["worst_gap"] > reference["logit_tolerance"]


def test_a_token_altered_where_it_is_produced_is_not(capsys, monkeypatch):
    sound = GPTRunner.decode

    def altered(self, *args):
        tokens = np.array(sound(self, *args))
        tokens[0] = (tokens[0] + 1) % 512  # the first slot's token, every step
        return tokens

    monkeypatch.setattr(GPTRunner, "decode", altered)
    done, reference = rehearse(capsys, 12)
    assert not done["correct"]
    assert reference["worst_gap"] > reference["logit_tolerance"]
    assert any(not v["ok"] for v in reference["verdicts"].values())
