"""A prompt's last chunk hands its token on as a device array (PR 48).

At pipeline depth 1 a chunk program is a dispatch and a commit, like a
decode: what it changes without its value (num_cached, the window class,
block publication) advances at the dispatch, and the token stays on the
device until the decode dispatch that the prompt joins has been made, which
takes that lane's token from the chunk's output (`runner.join_token`). The
step thread reads the chunk behind that dispatch. These tests hold, on the
tiny GPT and on the toy granite hybrid (state slots for lanes, routed
experts' counts riding the chunk's output):

  * greedy outputs token-identical at depth 0 and depth 1 for prompts that
    join a running batch, for a prompt of several chunks, for a join on a
    flushed step and on a chained one, and where the join cannot keep the
    record's lanes (the one `joined` flush left);
  * a first token that ends its request (max_new_tokens 1, EOS) has been
    fed to one decode: the overshoot is never emitted, blocks and the state
    slot are freed;
  * abort and deadline expiry between a chunk's dispatch and its commit;
  * an injected chunk failure dead-letters the culprit alone and the retry
    resumes, and a failed read is pinned on the chunk's dispatch step;
  * no host read between a last chunk's dispatch and the decode dispatch
    that consumes it; TTFT observed at the commit.
"""

import gc
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu._private import fault_injection as fi
from ray_tpu.llm import EngineConfig, LLMEngine
from ray_tpu.models import granite_hybrid as gh
from ray_tpu.models import laguna as lg
from ray_tpu.models.gpt import GPTConfig

from hybrid_toy import toy_config
from laguna_toy import toy_config as laguna_toy_config

TINY = GPTConfig(
    vocab_size=128, num_layers=2, num_heads=4, embed_dim=64, max_seq_len=128,
    dtype=jnp.float32, attention_impl="reference",
)
HYBRID = toy_config()
LAGUNA = laguna_toy_config()  # a window class beside the full one
MODELS = ("gpt", "hybrid")
BASE = dict(
    block_size=8, num_blocks=64, max_decode_slots=4, max_blocks_per_seq=12,
    prefill_buckets=(16, 32, 64), max_prefill_tokens_per_step=16,
    attn_impl="reference",
)


@pytest.fixture(scope="module", autouse=True)
def _leave_a_small_heap():
    yield
    _params.cache.clear()
    jax.clear_caches()
    gc.collect()


def _params(model: str):
    if model not in _params.cache:
        _params.cache[model] = (
            gh.init_params(HYBRID, 11) if model == "hybrid"
            else lg.init_params(LAGUNA, 11)
        )
    return _params.cache[model]


_params.cache = {}


def engine(model: str, depth: int, **changes) -> LLMEngine:
    ecfg = EngineConfig(async_scheduling=bool(depth), **{**BASE, **changes})
    if model == "gpt":
        return LLMEngine(TINY, ecfg, seed=0)
    cfg = HYBRID if model == "hybrid" else LAGUNA
    return LLMEngine(cfg, ecfg, params=_params(model))


def prompts_of(lengths, seed=0):
    rng = np.random.RandomState(seed)
    return [list(map(int, rng.randint(1, 128, size=n))) for n in lengths]


def serve(eng, arrivals, n_new, eos=None):
    """Run `arrivals` ((step, prompt) pairs, a request added before that
    step) to completion; returns the emitted tokens a request and the
    request ids. `n_new` is one budget or one a request."""
    budgets = n_new if isinstance(n_new, (list, tuple)) else [n_new] * len(arrivals)
    outs = [[] for _ in arrivals]
    rids = [None] * len(arrivals)
    step = 0
    while step <= max(at for at, _ in arrivals) or eng.has_work():
        for i, (at, prompt) in enumerate(arrivals):
            if at == step:
                rids[i] = eng.add_request(
                    prompt, max_new_tokens=budgets[i], on_token=outs[i].append,
                    eos_id=None if eos is None else eos[i],
                )
        eng.step()
        step += 1
        assert step < 500
    return outs, rids


def assert_drained(eng):
    stats = eng.stats()
    assert stats["inflight_steps"] == 0
    assert not eng._pending_chunks and not eng._unfed
    assert eng.allocator.num_allocated == 0
    if eng._window is not None:
        assert eng._window.allocator.num_allocated == 0
    if eng.scheduler.state_slots is not None:
        assert eng.scheduler.state_slots.num_in_use == 0


# Three prompts join a running stream one after another (each finds the batch
# chained), then one of three chunks, then two in one step.
JOINS = [(0, 9), (3, 5), (5, 13), (7, 40), (12, 7), (12, 6)]


def arrivals(spec=JOINS, seed=3):
    return [
        (at, prompt)
        for (at, _), prompt in zip(spec, prompts_of([n for _, n in spec], seed))
    ]


# ---------------- token identity ----------------


@pytest.mark.parametrize("model", MODELS + ("laguna",))
def test_joins_are_token_identical_at_both_depths(model):
    """Prompts that join a running batch, one of several chunks among them:
    depth 1 emits what depth 0 emits, and at depth 1 every first token
    reached its decode on the device."""
    outs = {}
    for depth in (0, 1):
        eng = engine(model, depth)
        outs[depth], _ = serve(eng, arrivals(), 12)
        assert_drained(eng)
        stats = eng.stats()
        assert stats["prompts_prefilled"] == len(JOINS)
        assert stats["first_tokens_on_device"] == (len(JOINS) if depth else 0)
        if depth:
            assert stats["chunked_prefill_requests"] == 1
            assert stats["pipeline_flushes_by_cause"]["joined"] == 0
    assert outs[1] == outs[0]
    assert all(len(o) == 12 for o in outs[1])


@pytest.mark.parametrize("model", MODELS)
def test_a_join_on_a_flushed_step_and_on_a_chained_one(model):
    """The first prompt finds nothing in flight: its step dispatches the
    chunk, the decode that takes the token from it, and only then reads.
    The second finds a chained batch: its chunk is dispatched behind the
    chained decode and left unread; the next step's chained dispatch takes
    the token, counts no flush, and the token is committed behind it."""
    first, second = prompts_of((9, 6), seed=5)
    eng = engine(model, 1)
    a, b = [], []
    eng.add_request(first, max_new_tokens=20, on_token=a.append)
    eng.step()
    stats = eng.stats()
    assert (stats["decode_dispatches"], stats["first_tokens_on_device"]) == (1, 1)
    assert len(a) == 1 and not eng._pending_chunks  # read in the same step
    steps = eng.flight_recorder.snapshot()["steps"]
    assert steps[-1]["chained"] is False and steps[-1]["tokens_out"] == 1
    for _ in range(3):
        eng.step()
    assert eng.flight_recorder.snapshot()["steps"][-1]["chained"]
    before = dict(eng.stats()["pipeline_flushes_by_cause"])

    eng.add_request(second, max_new_tokens=20, on_token=b.append)
    eng.step()  # chains, then dispatches the prompt's one chunk
    (chunk,) = eng._pending_chunks
    assert chunk.final and chunk.out is not None and eng._unfed
    assert chunk.seq.num_cached == len(second) and not chunk.seq.generated
    assert not b
    eng.step()  # the chained dispatch takes the token; then it is read
    record = eng.flight_recorder.snapshot()["steps"][-1]
    assert record["chained"] and record["batch_size"] == 2
    assert len(b) == 1 and not eng._pending_chunks and not eng._unfed
    stats = eng.stats()
    assert stats["first_tokens_on_device"] == 2
    assert stats["pipeline_flushes_by_cause"] == before
    while eng.has_work():
        eng.step()
    assert_drained(eng)
    ref = engine(model, 0)
    expect, _ = serve(ref, [(0, first), (4, second)], 20)
    assert [a, b] == expect


def test_a_join_that_cannot_keep_the_lanes_flushes_as_joined():
    """`GPTRunner`'s lane is a sequence's index in the batch: a joiner in
    front of the record's sequences would move them, so the step counts
    `joined`, flushes and schedules from committed state; the chunk stays
    unread through the flush and the step's own decode dispatch takes its
    token from the device. The scheduler's arrival order never produces
    this; the running list is turned by hand."""
    first, second = prompts_of((9, 6), seed=5)
    eng = engine("gpt", 1)
    a, b = [], []
    eng.add_request(first, max_new_tokens=12, on_token=a.append)
    for _ in range(4):
        eng.step()
    eng.add_request(second, max_new_tokens=12, on_token=b.append)
    eng.step()
    assert eng._unfed
    eng.scheduler.running.reverse()
    before = eng.stats()
    eng.step()
    after = eng.stats()
    flushed = {
        cause: n - before["pipeline_flushes_by_cause"][cause]
        for cause, n in after["pipeline_flushes_by_cause"].items()
    }
    assert flushed == {**dict.fromkeys(flushed, 0), "joined": 1}
    assert after["first_tokens_on_device"] == before["first_tokens_on_device"] + 1
    assert after["chained_decode_dispatches"] == before["chained_decode_dispatches"]
    assert len(b) == 1 and not eng._unfed
    while eng.has_work():
        eng.step()
    assert_drained(eng)
    expect, _ = serve(engine("gpt", 0), [(0, first), (4, second)], 12)
    assert [a, b] == expect


# ---------------- the overshoot rule covers the first token ----------------


@pytest.mark.parametrize("how", ("max_new_tokens", "eos"))
@pytest.mark.parametrize("model", MODELS)
def test_a_first_token_that_ends_its_request_is_the_only_one_emitted(model, how):
    """Two such prompts: one joins a chained batch, one arrives with the
    engine idle. Each has been fed to one decode by the time its token is
    read; nothing of that decode reaches the client, and blocks and state
    slots are back when the engine drains."""
    spec = [(0, 9), (4, 6), (30, 7)]  # the third finds the engine drained
    plan = arrivals(spec, seed=8)
    ref, _ = serve(engine(model, 0), plan, 10)
    if how == "eos":
        budgets, eos = 10, [None, ref[1][0], ref[2][0]]
        expect = [ref[0], ref[1][:1], ref[2][:1]]
    else:
        budgets, eos = [10, 1, 1], None
        expect = [ref[0], ref[1][:1], ref[2][:1]]
    eng = engine(model, 1)
    outs, _ = serve(eng, plan, budgets, eos=eos)
    assert outs == expect
    assert_drained(eng)
    stats = eng.stats()
    assert stats["first_tokens_on_device"] == 3
    # Each overshoot was a lane of a dispatched decode.
    assert stats["decode_dispatches"] >= 10


# ---------------- abort and expiry between dispatch and commit ----------------


@pytest.mark.parametrize("what", ("abort", "expiry"))
@pytest.mark.parametrize("model", MODELS)
def test_a_request_that_ends_between_its_chunks_dispatch_and_commit(model, what):
    """The chunk is out, unread; the request is aborted, or its deadline
    passes. The next step drops the chunk unread, emits nothing for the
    request, frees what it held, and the stream beside it is untouched."""
    first, second = prompts_of((9, 6), seed=5)
    expect, _ = serve(engine(model, 0), [(0, first)], 14)
    eng = engine(model, 1)
    a, b = [], []
    eng.add_request(first, max_new_tokens=14, on_token=a.append)
    for _ in range(4):
        eng.step()
    rid = eng.add_request(
        second, max_new_tokens=14, on_token=b.append,
        deadline_s=time.monotonic() + 3600.0,
    )
    eng.step()
    (chunk,) = eng._pending_chunks
    reads = []
    read = eng.runner.read_chunk
    eng.runner.read_chunk = lambda out: reads.append(out) or read(out)
    if what == "abort":
        assert eng.abort(rid)
    else:
        chunk.seq.request.deadline_s = time.monotonic() - 1.0
    eng.step()
    assert not eng._pending_chunks and not eng._unfed and not reads
    assert not eng.scheduler.is_active(rid)
    assert chunk.seq.finish_reason == ("aborted" if what == "abort" else "expired")
    while eng.has_work():
        eng.step()
    assert (a, b) == (expect[0], [])
    assert_drained(eng)


# ---------------- failures ----------------


def run_with_isolation(eng, plan, n_new):
    """`LLMServer._loop`'s contract on a bare engine: a step that raises
    fails the request it is pinned on and the next step resumes."""
    failures = []
    real_step = eng.step

    def step():
        try:
            real_step()
        except fi.InjectedFault as exc:
            culprit = eng.culprit_for(exc)
            assert culprit is not None
            failures.append((culprit, eng.failure_step()))
            assert eng.fail_request(culprit, exc)

    eng.step = step
    return serve(eng, plan, n_new), failures


@pytest.mark.parametrize("site", ("engine.prefill_chunk", "llm.prefill"))
@pytest.mark.parametrize("model", MODELS)
def test_an_injected_chunk_failure_dead_letters_the_culprit_alone(model, site):
    """The poisoned prompt arrives in the same step as another one and
    behind a prompt whose chunk is out unread: the step raises before the
    poisoned dispatch, the culprit alone is failed, and the retry resumes
    from what was dispatched: every other stream is depth 0's."""
    spec = [(0, 9), (4, 6), (5, 7), (5, 40)]
    plan = arrivals(spec, seed=21)
    ref, _ = serve(engine(model, 0), plan, 10)
    eng = engine(model, 1, max_prefill_tokens_per_step=32)
    poisoned = "poisoned-request"
    real_add = eng.add_request

    def add_request(prompt, **kw):
        if prompt == plan[2][1]:
            kw["request_id"] = poisoned
        return real_add(prompt, **kw)

    eng.add_request = add_request
    spec_ = fi.inject(site, match=poisoned)
    try:
        (outs, rids), failures = run_with_isolation(eng, plan, 10)
    finally:
        fi.remove(spec_)
    assert [culprit for culprit, _ in failures] == [poisoned]
    assert [d["request_id"] for d in eng.dead_letters()] == [poisoned]
    assert outs[2] == []
    assert [outs[0], outs[1], outs[3]] == [ref[0], ref[1], ref[3]]
    assert_drained(eng)


@pytest.mark.parametrize("model", MODELS)
def test_a_failed_chunk_program_surfaces_at_its_read(model):
    """The read of one request's chunk raises (what a failed program does):
    the failure is pinned on that request and on the step that DISPATCHED
    the chunk, one before the step that reads it; the retry drops the
    record unread and the other stream goes on."""
    first, second = prompts_of((9, 6), seed=5)
    expect, _ = serve(engine(model, 0), [(0, first)], 14)
    eng = engine(model, 1)
    a, b = [], []
    eng.add_request(first, max_new_tokens=14, on_token=a.append)
    for _ in range(4):
        eng.step()
    rid = eng.add_request(second, max_new_tokens=14, on_token=b.append)
    eng.step()
    (chunk,) = eng._pending_chunks
    dispatched_at = eng.stats()["steps"] - 1
    assert chunk.dispatch_step == dispatched_at
    read = eng.runner.read_chunk

    def failing(out):
        if out is chunk.out:
            raise fi.InjectedFault("chunk program failed")
        return read(out)

    eng.runner.read_chunk = failing
    with pytest.raises(fi.InjectedFault) as raised:
        eng.step()
    assert eng.culprit_for(raised.value) == rid
    assert eng.failure_step() == dispatched_at == eng.stats()["steps"] - 1
    assert eng.fail_request(rid, raised.value)
    assert eng.dead_letters()[-1]["step"] == dispatched_at
    while eng.has_work():
        eng.step()
    assert (a, b) == (expect[0], [])
    assert_drained(eng)


# ---------------- where the read is ----------------


@pytest.mark.parametrize("model", MODELS)
def test_no_host_read_between_a_last_chunk_and_the_decode_that_takes_it(model):
    """Spies on the runner's dispatches and on its read of a chunk: behind
    every last chunk's dispatch a decode dispatch comes before any chunk is
    read, at depth 1 (what is read in between is the decode that ran before
    the chunk); at depth 0 the chunk is read at once."""
    for depth in (0, 1):
        eng = engine(model, depth)
        runner, events = eng.runner, []

        def spy(name, call, events=events):
            def wrapped(*args, **kwargs):
                events.append(name)
                return call(*args, **kwargs)

            return wrapped

        runner.prefill = spy("chunk", runner.prefill)
        runner.prefill_suffix = spy("chunk", runner.prefill_suffix)
        runner.decode = spy("decode", runner.decode)
        runner.read_chunk = spy("read_chunk", runner.read_chunk)
        finals = []
        plans = eng.scheduler.schedule_prefill_chunks

        def planned(budget, finals=finals, events=events, plans=plans):
            out = plans(budget)
            # Index of the event each last chunk of this step will be.
            finals.extend(
                len(events) + i
                for i, (seq, take) in enumerate(out)
                if take >= seq.prefill_len - seq.num_cached
            )
            return out

        eng.scheduler.schedule_prefill_chunks = planned
        serve(eng, arrivals(), 8)
        assert len(finals) == len(JOINS)
        for at in finals:
            assert events[at] == "chunk"
            after = next(e for e in events[at + 1:] if e != "chunk")
            assert after == ("decode" if depth else "read_chunk"), (depth, at)
        assert events.count("read_chunk") == events.count("chunk")
        assert eng.stats()["first_tokens_on_device"] == (len(JOINS) if depth else 0)


def test_ttft_is_observed_where_the_token_is_on_the_host():
    """A prompt that joins a chained batch: after the step that dispatched
    its chunk the request has no first-token time and TTFT has no
    observation for it; the step that reads the token makes both, at the
    read and not at the dispatch."""
    first, second = prompts_of((9, 6), seed=5)
    eng = engine("gpt", 1)
    eng.add_request(first, max_new_tokens=20)
    for _ in range(4):
        eng.step()
    rid = eng.add_request(second, max_new_tokens=20)
    eng.step()
    dispatched = time.time()
    trace = eng._req_traces[rid]
    assert trace.first_token_s is None and trace.prefills == 0
    observed = []
    observe = eng._h_ttft.observe
    eng._h_ttft.observe = lambda v, tags=None: (
        observed.append(v), observe(v, tags=tags)
    )
    time.sleep(0.05)
    eng.step()
    assert trace.first_token_s is not None and trace.prefills == 1
    assert trace.first_token_s >= dispatched + 0.05
    assert len(observed) == 1
    assert observed[0] == pytest.approx(trace.first_token_s - trace.submit_s)
    while eng.has_work():
        eng.step()
    assert_drained(eng)
