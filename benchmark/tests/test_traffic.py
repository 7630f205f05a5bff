import json
import os

import pytest

from lib import traffic
from lib.manifest import BENCH

MIXES = ("chat-sessions-loaded", "batch-unshared")
CHAT = "chat-sessions-loaded"


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
    m = mix(CHAT)
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
    toy = traffic.scaled(mix(CHAT), 128 / 1024)
    schedule = traffic.generate(toy, 1, 5.0, 512, 128)
    assert max(len(r["prompt_ids"]) + r["max_new_tokens"] for r in schedule["requests"]) <= 128


def test_step_batches_repeat_for_a_seed():
    m = {"loop": "steps", "sequences_per_step": 2, "tokens_per_sequence": 8}
    a, b = traffic.step_batches(m, 5, 100), traffic.step_batches(m, 5, 100)
    c = traffic.step_batches(m, 6, 100)
    first = next(a)
    assert first.shape == (2, 8) and (first == next(b)).all() and not (first == next(c)).all()
    assert not (first == next(a)).all()


@pytest.mark.parametrize("seed", [0, 5, 2**31 + 11, 2**32 - 1])
def test_step_batches_of_a_seed_numpy_takes_are_the_ones_it_always_drew(seed):
    import numpy as np

    m = {"loop": "steps", "sequences_per_step": 2, "tokens_per_sequence": 8}
    expected = np.random.RandomState(seed).randint(0, 100, size=(2, 8)).astype(np.int32)
    assert (next(traffic.step_batches(m, seed, 100)) == expected).all()


@pytest.mark.parametrize("seed", [2**32, 2**32 + 15, 5600000103])
def test_step_batches_take_a_seed_past_32_bits(seed):
    """numpy's legacy generator refuses it whole; it goes in as two words,
    and draws what no seed of one word draws."""
    m = {"loop": "steps", "sequences_per_step": 2, "tokens_per_sequence": 8}
    first = next(traffic.step_batches(m, seed, 100))
    assert (first == next(traffic.step_batches(m, seed, 100))).all()
    assert not (first == next(traffic.step_batches(m, seed % 2**32, 100))).all()
    assert not (first == next(traffic.step_batches(m, seed + 1, 100))).all()


# ---------------- PR 28: the closed loop's room and the loaded chat mix ----------------


def test_closed_loop_at_64_a_caller_begins_with_the_576_requests_of_12():
    """The draw is place-major from one generator, so a longer queue only
    appends: the work at an unchanged speed is the work measured before."""
    m = mix("batch-unshared")
    assert m["requests_per_client"] == 64
    schedule = traffic.generate(m, 1, 45.0, 50257, 1024)
    before = traffic.generate(dict(m, requests_per_client=12), 1, 45.0, 50257, 1024)
    assert len(before["requests"]) == 576
    assert traffic.fingerprint(before)[:16] == "653802896675cee6"  # as drawn at PR 22
    assert traffic.fingerprint({**schedule, "requests": schedule["requests"][:576]}) \
        == traffic.fingerprint(before)


def test_closed_loop_cannot_run_dry_below_2500_tokens_per_s():
    """With callers served alike, the caller with the fewest answer tokens
    finishes its queue first: at 48 x its tokens over lead-in + window."""
    m = mix("batch-unshared")
    schedule = traffic.generate(m, 1, 45.0, 50257, 1024)
    queued = {}
    for r in schedule["requests"]:
        queued[r["client"]] = queued.get(r["client"], 0) + r["max_new_tokens"]
    dry_at = m["clients"] * min(queued.values()) / (m["lead_in_s"] + 45.0)
    assert min(queued.values()) == 3802 and "3,802" in m["what"]
    assert round(dry_at) == 3443 and "3,443" in m["what"]
    assert dry_at > 2500


def _realised(m, schedule_seed, seconds=45.0):
    schedule = traffic.generate(dict(m, schedule_seed=schedule_seed), 1, seconds, 50257, 1024)
    lead, third = m["lead_in_s"], seconds / 3
    due = [r["due_s"] for r in schedule["requests"]]
    window = [r for r in schedule["requests"] if r["due_s"] >= lead]
    return {
        "lead_in": sum(d < lead for d in due),
        "thirds": [sum(lead + i * third <= d < lead + (i + 1) * third for d in due)
                   for i in range(3)],
        "answer_mean": sum(r["max_new_tokens"] for r in window) / len(window),
        "prompt_mean": sum(len(r["prompt_ids"]) for r in window) / len(window),
    }


def test_loaded_chat_realises_within_5_percent_of_its_expectations():
    """`schedule_seed` is the first seed whose 45 s realisation is typical:
    arrivals by the rate, lengths by the mean over 64 other seeds."""
    m = mix(CHAT)
    rate = m["arrivals"]["rate_per_s"]
    many = [_realised(m, seed) for seed in range(100, 164)]
    expect_answer = sum(r["answer_mean"] for r in many) / len(many)
    expect_prompt = sum(r["prompt_mean"] for r in many) / len(many)

    def typical(r):
        counts = [r["lead_in"] / (rate * m["lead_in_s"])] + [t / (rate * 15.0) for t in r["thirds"]]
        lengths = [r["answer_mean"] / expect_answer, r["prompt_mean"] / expect_prompt]
        return all(abs(x - 1.0) <= 0.05 for x in counts + lengths)

    assert typical(_realised(m, m["schedule_seed"]))
    assert not any(typical(_realised(m, seed)) for seed in range(m["schedule_seed"]))


def _blocks_held(m, schedule, block=16):
    """Blocks that keep every conversation's longest prompt + answer of the
    run cached: the shared prompt's once, each conversation's own beyond."""
    shared = m["shared_prefix"] // block
    longest = {}
    for r in schedule["prime"] + schedule["requests"]:
        total = len(r["prompt_ids"]) + r["max_new_tokens"]
        longest[r["session"]] = max(longest.get(r["session"], 0), total)
    return shared + sum(-(-n // block) - shared for n in longest.values())


def test_loaded_chat_fits_the_cache_as_the_24_sessions_fit_1024_blocks():
    with open(os.path.join(BENCH, "configs", "gpt2-large.json")) as f:
        blocks = json.load(f)["engine"]["num_blocks"]
    m = mix(CHAT)
    held = _blocks_held(m, traffic.generate(m, 1, 45.0, 50257, 1024))
    # What PR 22's cell had: 24 sessions at 1.5/s (schedule seed 16) in 1,024 blocks.
    old = dict(m, sessions=24, schedule_seed=16,
               arrivals=dict(m["arrivals"], rate_per_s=1.5))
    old_share = _blocks_held(old, traffic.generate(old, 1, 45.0, 50257, 1024)) / 1024
    # 2,363 of 3,072 (77%) against 757 of 1,024 (74%): over a fifth stays free.
    assert held <= 0.8 * blocks
    assert abs(held / blocks - old_share) <= 0.05, (held / blocks, old_share)
