"""The one traffic generator. A mix is a JSON file of parameters under
`benchmark/traffic/`; this module turns (mix, seed, the configuration's
vocabulary and context limit) into a schedule: plain data that the load
generator replays. Nothing here reads a clock, a model output or global RNG
state, so the same arguments give the same schedule byte for byte.

Derived from `ray_tpu/loadgen/arrivals.py` and `scenarios.py` (`_multiturn`,
`_longtail`), which PERF.md's Open questions list for a later PR to delete.

Two seeds. `schedule_seed` in the mix, where it is a number, draws what fixes
the amount of work: arrival times, lengths and which session speaks when.
`--seed` then draws only the token ids (and the runner's weights), so every
run of the cell does the same work on different data and the spread between
runs is the system's own. A mix whose `schedule_seed` is null draws
everything from `--seed`.

Parameters of a mix (all lengths in tokens):

  loop                  "open": requests are due at drawn arrival times
                        whether or not earlier ones have returned;
                        "closed": `clients` callers, each sending its next
                        request when the last one completes
  arrivals              open loop: {"process": "poisson" | "uniform",
                        "rate_per_s": r, "fixed_count": bool}. With
                        `fixed_count` a Poisson process is drawn conditioned
                        on its count: exactly round(r x (lead-in + window))
                        arrivals at independent uniform times, which is what
                        a Poisson process looks like given that count. The
                        bursts stay and the amount of work no longer depends
                        on the realisation.
  clients               closed loop: number of callers
  requests_per_client   closed loop: requests drawn for each caller (enough
                        to outlast lead-in and window; a caller that runs out
                        fails the run)
  lead_in_s             seconds of the cell's own traffic before the window
  sessions              0: every prompt is fresh and shares nothing;
                        n > 0: n conversations, taken in turn
  shared_prefix         tokens of one system prompt in front of every prompt
  prompt                {"median", "sigma", "min", "max"}: lognormal length of
                        a fresh prompt (sessions = 0) or of one user turn
  answer                the same for the answer; the request asks for exactly
                        that many new tokens and no stop token is set
  initial_history_max   sessions > 0: each conversation starts with a history
                        of a length drawn uniformly below this, as if taken
                        up mid-way, and the schedule carries one `prime`
                        request per conversation that puts it in the cache
                        before the lead-in

A conversation's next prompt is the last one plus an answer plus a user turn.
The answer in the history is a seeded stand-in of the drawn length, not the
model's own tokens: the schedule is fixed before anything is generated. When
prompt + answer would pass the context limit the conversation starts again.
"""

from __future__ import annotations

import hashlib
import json
import math
import random
from typing import List, Optional

LOOPS = ("open", "closed")  # a training mix has "loop": "steps", below


def arrival_times(process: str, rate_per_s: float, until_s: float,
                  rng: random.Random, fixed_count: bool = False) -> List[float]:
    """Arrival offsets in [0, until_s), in order."""
    if rate_per_s <= 0:
        raise ValueError(f"rate_per_s must be > 0, got {rate_per_s}")
    if fixed_count and process == "poisson":
        count = round(rate_per_s * until_s)
        return sorted(rng.uniform(0.0, until_s) for _ in range(count))
    out, t = [], 0.0
    while True:
        if process == "poisson":
            t += rng.expovariate(rate_per_s)
        elif process == "uniform":
            t += 1.0 / rate_per_s
        else:
            raise ValueError(f"unknown arrival process {process!r}")
        if t >= until_s:
            return out
        out.append(t)


def _length(rng: random.Random, spec: dict) -> int:
    drawn = int(rng.lognormvariate(math.log(spec["median"]), spec["sigma"]))
    return max(spec["min"], min(spec["max"], drawn))


def _tokens(rng: random.Random, n: int, vocab: int) -> List[int]:
    # Token 0 is the engine's warm-up filler; skipping it keeps a prompt
    # from meeting warm-up's cached blocks of zeros.
    return [rng.randrange(1, vocab) for _ in range(n)]


class _Prompts:
    """Draws one request after another: fresh prompts, or conversations
    taken in turn. Lengths come from `shape`, token ids from `data`."""

    def __init__(self, mix: dict, vocab: int, max_total: int,
                 shape: random.Random, data: random.Random):
        self.mix, self.vocab, self.max_total = mix, vocab, max_total
        self.shape, self.data = shape, data
        self.prefix = _tokens(data, mix.get("shared_prefix", 0), vocab)
        self.sessions = mix.get("sessions", 0)
        self.histories: List[List[int]] = []
        self.turns = [0] * self.sessions
        self.next_session = 0
        room = max_total - len(self.prefix) - mix["answer"]["max"] - mix["prompt"]["max"]
        for _ in range(self.sessions):
            start = shape.randrange(0, max(1, min(mix.get("initial_history_max", 0), room)))
            self.histories.append(_tokens(data, start, vocab))
        # One request per conversation that puts where it starts into the
        # cache (the shared prefix alone where it starts empty).
        self.prime = [
            {
                "phase": "prime", "session": s, "turn": -1,
                "prompt_ids": self.prefix + history, "max_new_tokens": 1,
            }
            for s, history in enumerate(self.histories)
            if self.prefix or history
        ]

    def draw(self) -> dict:
        turn = _length(self.shape, self.mix["prompt"])
        answer = _length(self.shape, self.mix["answer"])
        if not self.sessions:
            turn = min(turn, self.max_total - len(self.prefix) - answer)
            prompt = self.prefix + _tokens(self.data, turn, self.vocab)
            return {"session": None, "turn": None, "prompt_ids": prompt,
                    "max_new_tokens": answer}
        s = self.next_session
        self.next_session = (s + 1) % self.sessions
        if len(self.prefix) + len(self.histories[s]) + turn + answer > self.max_total:
            # The conversation outgrew the context: a new chat tab.
            self.histories[s], self.turns[s] = [], 0
            turn = min(turn, self.max_total - len(self.prefix) - answer)
        said = self.histories[s] + _tokens(self.data, turn, self.vocab)
        request = {"session": s, "turn": self.turns[s],
                   "prompt_ids": self.prefix + said, "max_new_tokens": answer}
        self.histories[s] = said + _tokens(self.data, answer, self.vocab)
        self.turns[s] += 1
        return request


def generate(mix: dict, seed: int, seconds: float, vocab: int,
             max_total: int) -> dict:
    """The schedule of one run: `{"loop", "lead_in_s", "seconds", "prime":
    [...], "requests": [...]}`. An open-loop request carries `due_s` from the
    start of the lead-in; a closed-loop one carries its `client` and its
    place in that caller's queue."""
    if mix.get("loop") not in LOOPS:
        raise ValueError(f"mix loop {mix.get('loop')!r} not in {LOOPS}")
    schedule_seed: Optional[int] = mix.get("schedule_seed")
    shape = random.Random(repr(("shape", seed if schedule_seed is None else schedule_seed)))
    data = random.Random(repr(("data", seed)))
    prompts = _Prompts(mix, vocab, max_total, shape, data)
    lead_in = float(mix["lead_in_s"])
    requests = []
    if mix["loop"] == "open":
        arrivals = mix["arrivals"]
        times = arrival_times(
            arrivals["process"], arrivals["rate_per_s"], lead_in + seconds, shape,
            arrivals.get("fixed_count", False),
        )
        for due in times:
            requests.append({"due_s": due, **prompts.draw()})
    else:
        for place in range(mix["requests_per_client"]):
            for client in range(mix["clients"]):
                requests.append({"client": client, "place": place, **prompts.draw()})
    for i, request in enumerate(requests):
        request["id"] = f"r{i:05d}"
        request["phase"] = "run"
    prime = prompts.prime
    for i, request in enumerate(prime):
        request["id"] = f"p{i:05d}"
    return {
        "loop": mix["loop"], "lead_in_s": lead_in, "seconds": float(seconds),
        "clients": mix.get("clients"), "prime": prime, "requests": requests,
    }


def step_batches(mix: dict, seed: int, vocab: int):
    """A training mix (`"loop": "steps"`): an endless host iterator of
    `[sequences_per_step, tokens_per_sequence]` int32 batches of packed
    sequences, a new one every step, drawn from the seed uniformly over the
    vocabulary."""
    import numpy as np

    if mix.get("loop") != "steps":
        raise ValueError(f"mix loop {mix.get('loop')!r} is not a training mix")
    # The legacy generator takes one word of 32 bits or a list of them: a
    # seed past 2**32 - 1 goes in as its two words, any other as it always did.
    rng = np.random.RandomState(seed if seed < 2**32 else [seed & 0xFFFFFFFF, seed >> 32])
    shape = (mix["sequences_per_step"], mix["tokens_per_sequence"])
    while True:
        yield rng.randint(0, vocab, size=shape).astype(np.int32)


def scaled(mix: dict, factor: float) -> dict:
    """The mix with every length multiplied by `factor`: a rehearsal at a
    toy context length keeps the mix's proportions."""
    def length(n: int) -> int:
        return max(1, int(n * factor))

    out = dict(mix)
    for key in ("shared_prefix", "initial_history_max"):
        if mix.get(key):
            out[key] = length(mix[key])
    for key in ("prompt", "answer"):
        out[key] = {
            **mix[key],
            **{k: length(mix[key][k]) for k in ("median", "min", "max")},
        }
    out["answer"]["min"] = max(2, out["answer"]["min"])
    return out


def fingerprint(schedule: dict) -> str:
    """Two runs replay the same schedule iff their fingerprints are equal."""
    canonical = json.dumps(schedule, sort_keys=True, separators=(",", ":"))
    return hashlib.sha256(canonical.encode()).hexdigest()


def summary(schedule: dict) -> dict:
    """Counts for the run's information line."""
    run = schedule["requests"]
    return {
        "loop": schedule["loop"],
        "prime_requests": len(schedule["prime"]),
        "requests_drawn": len(run),
        "prompt_tokens_mean": sum(len(r["prompt_ids"]) for r in run) / max(len(run), 1),
        "answer_tokens_mean": sum(r["max_new_tokens"] for r in run) / max(len(run), 1),
        "fingerprint": fingerprint(schedule)[:16],
    }
