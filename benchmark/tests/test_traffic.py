import json
import os

import pytest

from lib import traffic
from lib.manifest import BENCH

MIXES = ("chat-sessions", "batch-unshared")


def mix(name):
    with open(os.path.join(BENCH, "traffic", name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_schedule_other_seed_other(name):
    a = traffic.generate(mix(name), 3, 20.0, 50257, 1024)
    b = traffic.generate(mix(name), 3, 20.0, 50257, 1024)
    c = traffic.generate(mix(name), 4, 20.0, 50257, 1024)
    assert traffic.fingerprint(a) == traffic.fingerprint(b)
    assert traffic.fingerprint(a) != traffic.fingerprint(c)


@pytest.mark.parametrize("name", MIXES)
def test_schedule_seed_fixes_the_work_and_seed_the_tokens(name):
    a = traffic.generate(mix(name), 3, 20.0, 50257, 1024)
    c = traffic.generate(mix(name), 4, 20.0, 50257, 1024)
    shape = lambda s: [  # noqa: E731
        (r.get("due_s"), r.get("client"), len(r["prompt_ids"]), r["max_new_tokens"])
        for r in s["prime"] + s["requests"]
    ]
    assert shape(a) == shape(c)
    assert [r["prompt_ids"] for r in a["requests"]] != [r["prompt_ids"] for r in c["requests"]]
    free = dict(mix(name), schedule_seed=None)
    assert shape(traffic.generate(free, 3, 20.0, 50257, 1024)) != shape(
        traffic.generate(free, 4, 20.0, 50257, 1024)
    )


@pytest.mark.parametrize("name", MIXES)
def test_every_request_fits_the_engine(name):
    schedule = traffic.generate(mix(name), 1, 45.0, 50257, 1024)
    for r in schedule["prime"] + schedule["requests"]:
        assert 1 <= len(r["prompt_ids"])
        assert len(r["prompt_ids"]) + r["max_new_tokens"] <= 1024
        assert all(1 <= t < 50257 for t in r["prompt_ids"])


def test_chat_sessions_share_the_prefix_and_grow():
    m = mix("chat-sessions")
    schedule = traffic.generate(m, 1, 45.0, 50257, 1024)
    prefix = schedule["requests"][0]["prompt_ids"][: m["shared_prefix"]]
    assert all(r["prompt_ids"][: m["shared_prefix"]] == prefix for r in schedule["requests"])
    assert len(schedule["prime"]) == m["sessions"]
    by_session = {}
    for r in schedule["requests"]:
        last = by_session.get(r["session"])
        if last is not None and r["turn"] == last["turn"] + 1:
            assert r["prompt_ids"][: len(last["prompt_ids"])] == last["prompt_ids"]
        by_session[r["session"]] = r
    due = [r["due_s"] for r in schedule["requests"]]
    assert due == sorted(due) and due[-1] < m["lead_in_s"] + 45.0
    # fixed_count: a Poisson process conditioned on its expected count.
    assert len(due) == round(m["arrivals"]["rate_per_s"] * (m["lead_in_s"] + 45.0))


def test_poisson_arrivals_have_the_rate_and_uniform_the_spacing():
    import random

    free = traffic.arrival_times("poisson", 5.0, 400.0, random.Random(1))
    assert abs(len(free) / 400.0 - 5.0) < 0.3 and free == sorted(free)
    fixed = traffic.arrival_times("poisson", 5.0, 400.0, random.Random(1), fixed_count=True)
    assert len(fixed) == 2000 and fixed == sorted(fixed) and fixed[-1] < 400.0
    even = traffic.arrival_times("uniform", 2.0, 3.0, random.Random(1))
    assert even == [0.5, 1.0, 1.5, 2.0, 2.5]


def test_unshared_prompts_share_no_block():
    schedule = traffic.generate(mix("batch-unshared"), 1, 45.0, 50257, 1024)
    firsts = [tuple(r["prompt_ids"][:16]) for r in schedule["requests"] if len(r["prompt_ids"]) >= 16]
    assert len(set(firsts)) == len(firsts)
    assert {r["client"] for r in schedule["requests"]} == set(range(48))


def test_scaled_keeps_requests_inside_a_toy_context():
    toy = traffic.scaled(mix("chat-sessions"), 128 / 1024)
    schedule = traffic.generate(toy, 1, 5.0, 512, 128)
    assert max(len(r["prompt_ids"]) + r["max_new_tokens"] for r in schedule["requests"]) <= 128


def test_step_batches_repeat_for_a_seed():
    m = {"loop": "steps", "sequences_per_step": 2, "tokens_per_sequence": 8}
    a, b = traffic.step_batches(m, 5, 100), traffic.step_batches(m, 5, 100)
    c = traffic.step_batches(m, 6, 100)
    first = next(a)
    assert first.shape == (2, 8) and (first == next(b)).all() and not (first == next(c)).all()
    assert not (first == next(a)).all()
