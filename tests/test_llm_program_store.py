"""The program store (`ray_tpu.llm.program_store`): the step programs' lowered
modules kept beside the compile cache, so that a process that finds them
there traces none of its step programs.

On the CPU backend the process's own store is off, so these tests hand the
runners one over a `tmp_path` (`program_store.default` patched to return it).
A "process" here is a new `ProgramStore` over that directory with the two
program tables cleared (`model_runner._PROGRAM_CACHE`,
`hybrid_runner._PROGRAM_CACHE`): a table built after that is what a new
process would build, and its jits trace anew. Counters wrapped around the
traced functions say whether Python ran them, and a listener on JAX's own
trace spans says what was traced under which name.
"""

import collections
import dataclasses
import gc
import hashlib
import json
import os
import re
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.llm import hybrid_runner as hr
from ray_tpu.llm import model_runner as mr
from ray_tpu.llm import program_store
from ray_tpu.llm.config import EngineConfig
from ray_tpu.llm.engine import LLMEngine, LLMServer
from ray_tpu.llm.program_store import ProgramStore
from ray_tpu.models import falcon_h1 as fh
from ray_tpu.models import laguna as lg
from ray_tpu.models.gpt import GPTConfig
from ray_tpu.util.device_report import scopes_of

import falcon_h1_toy
import laguna_toy
from llm_in_process import in_process

TINY = GPTConfig(
    vocab_size=128, num_layers=2, num_heads=4, embed_dim=64, max_seq_len=128,
    dtype=jnp.float32, attention_impl="reference",
)
BASE = dict(
    block_size=8, num_blocks=64, max_decode_slots=4, max_blocks_per_seq=12,
    prefill_buckets=(16, 32), attn_impl="reference",
)
PROMPTS = [[3, 1, 4, 1, 5, 9, 2, 6], [2, 7, 1, 8], list(range(1, 40))]
PLAIN_JIT = type(jax.jit(lambda: 0))
STEP_NAMES = (
    "_decode_step", "_prefill_step", "_prefill_suffix_step", "_verify_step",
    "_copy_block_step", "_restore_block_step",
)
TRACE_SPAN = "/jax/core/compile/jaxpr_trace_duration"

# JAX's listeners cannot be taken off again: one for the file, read through
# the `spans` fixture.
_SPANS: list = []
_listening = False


def _on_span(event, start, end, fun_name=None, **_):
    if event == TRACE_SPAN:
        _SPANS.append(fun_name)


@pytest.fixture
def spans():
    global _listening
    if not _listening:
        jax.monitoring.register_event_time_span_listener(_on_span)
        _listening = True
    _SPANS.clear()
    return _SPANS


@pytest.fixture(scope="module", autouse=True)
def _leave_a_small_heap():
    yield
    jax.clear_caches()
    gc.collect()


@pytest.fixture
def process(tmp_path, monkeypatch):
    """`process()` starts a new process's life over the store at `tmp_path`
    (`process(None)`: over no store) and returns its `ProgramStore`."""
    monkeypatch.setattr(mr, "_PROGRAM_CACHE", {})
    monkeypatch.setattr(hr, "_PROGRAM_CACHE", {})

    def new(directory=str(tmp_path / "programs")):
        mr._PROGRAM_CACHE.clear()
        hr._PROGRAM_CACHE.clear()
        store = ProgramStore(directory)
        monkeypatch.setattr(program_store, "default", lambda: store)
        return store

    return new


@pytest.fixture
def bodies(monkeypatch):
    """name -> how often Python ran that step function's body."""
    calls = collections.Counter()
    for cls in (mr._StepPrograms, hr._HybridPrograms):
        for name in STEP_NAMES:
            fn = cls.__dict__.get(name)
            if fn is None:
                continue

            def counted(self, *args, _fn=fn, _name=name):
                calls[_name] += 1
                return _fn(self, *args)

            counted.__name__ = name
            monkeypatch.setattr(cls, name, counted)
    return calls


def hybrid_ecfg(**changes):
    return EngineConfig(**{
        **BASE, "prefill_buckets": (16, 32, 64),
        "max_prefill_tokens_per_step": 16, **changes,
    })


MODELS = {
    "gpt": lambda: (TINY, EngineConfig(**BASE), None),
    # A mesh of two: weights and pools sharded, attention under shard_map.
    "gpt_tensor_parallel_2": lambda: (
        TINY, EngineConfig(**BASE, tensor_parallel_size=2), None
    ),
    # A recurrent mixer in every layer: state slots beside the blocks.
    "falcon_h1": lambda: (
        falcon_h1_toy.toy_config(), hybrid_ecfg(),
        fh.init_params(falcon_h1_toy.toy_config(), 11),
    ),
    # A window class beside the full one: two K/V pools, two tables.
    "laguna": lambda: (
        laguna_toy.toy_config(), hybrid_ecfg(),
        lg.init_params(laguna_toy.toy_config(), 11),
    ),
}


def boot(model="gpt", **engine_changes):
    """An engine of `model` after one generate: (engine, tokens, every pool
    on the host)."""
    cfg, ecfg, params = MODELS[model]()
    ecfg = dataclasses.replace(ecfg, **engine_changes)
    engine = LLMEngine(cfg, ecfg, params=params, seed=0)
    tokens = engine.generate(PROMPTS, max_new_tokens=6)
    runner = engine.runner
    pools = [
        np.asarray(pool) for pool in jax.tree_util.tree_leaves(
            (runner._pools, getattr(runner, "state", ()))
        )
    ]
    return engine, tokens, pools


def counts(engine) -> dict:
    stats = engine.stats()
    return {
        key: stats[key] for key in
        ("programs_loaded", "programs_traced", "program_store_misses_by_reason")
    }


def entries(tmp_path):
    folder = tmp_path / "programs"
    return sorted(os.listdir(folder)) if folder.exists() else []


# ---------------- a second process loads and traces nothing ----------------


@pytest.mark.parametrize("model", list(MODELS))
def test_second_process_loads_every_program_and_traces_none(
    model, process, tmp_path, bodies, spans
):
    process(None)
    _, plain_tokens, plain_pools = boot(model)
    plain_bodies = dict(bodies)
    assert plain_bodies["_decode_step"] >= 1 and plain_bodies["_prefill_step"] >= 1
    bodies.clear()

    process()
    cold, cold_tokens, cold_pools = boot(model)
    made = counts(cold)
    # A process that finds no store traces each program once, not twice.
    assert dict(bodies) == plain_bodies
    assert made["programs_loaded"] == 0 and made["programs_traced"] >= 3
    assert made["program_store_misses_by_reason"] == {"absent": made["programs_traced"]}
    stored = entries(tmp_path)
    assert len(stored) == made["programs_traced"]

    process()
    spans.clear()
    warm, warm_tokens, warm_pools = boot(model)
    assert counts(warm) == {
        "programs_loaded": made["programs_traced"], "programs_traced": 0,
        "program_store_misses_by_reason": {},
    }
    # Python ran no step function again, and under a step program's name
    # JAX traced the wrapper that calls its stored module, once a program.
    assert dict(bodies) == plain_bodies
    named = [name for name in spans if name in STEP_NAMES + ("join_token",)]
    assert len(named) == made["programs_traced"], named
    assert entries(tmp_path) == stored
    # Token for token, and every K/V pool and state pool bit for bit: what
    # each layer computed at every position, not only the logits' argmax.
    assert cold_tokens == plain_tokens and warm_tokens == plain_tokens
    assert len(warm_pools) == len(plain_pools) >= 2
    for plain, from_cold, from_warm in zip(plain_pools, cold_pools, warm_pools):
        np.testing.assert_array_equal(from_cold, plain)
        np.testing.assert_array_equal(from_warm, plain)


def test_a_booting_server_writes_when_it_is_ready_and_its_rounds_say_loaded(
    process, tmp_path
):
    """`LLMServer`'s warm-up through the store: what the first boot traces
    is written after its warm-up, by a thread `shutdown` joins; the second
    boot's rounds load it, and the flight record says so a round."""
    ecfg = EngineConfig(**BASE, max_prefill_tokens_per_step=16)

    def serve(store):
        written_in_warmup = []
        release = store.release

        def releasing():
            written_in_warmup.append(entries(tmp_path))
            release()

        store.release = releasing
        server = in_process(LLMServer(TINY, ecfg, warmup=True))
        try:
            answer = server.generate(PROMPTS[0], max_new_tokens=4)
            return (
                server.metrics(), server.flight_record(0)["compile_events"],
                answer["token_ids"], written_in_warmup,
            )
        finally:
            server.shutdown()

    cold, cold_rounds, cold_tokens, written = serve(process())
    assert written == [[]]  # nothing on the way to serving
    assert cold["programs_loaded"] == 0 and cold["programs_traced"] >= 4
    assert len(entries(tmp_path)) == cold["programs_traced"]  # `shutdown` joined
    warm, warm_rounds, warm_tokens, _ = serve(process())
    assert warm["programs_traced"] == 0
    assert warm["programs_loaded"] == cold["programs_traced"]
    assert warm["program_store_misses_by_reason"] == {}
    assert [r["program"] for r in warm_rounds] == [r["program"] for r in cold_rounds]
    # A round that made no program (the chunk round finds both of its in the
    # process already) says neither.
    assert {r["loaded"] for r in cold_rounds} == {False, None}
    assert {r["loaded"] for r in warm_rounds} == {True, None}
    assert [r["loaded"] is None for r in warm_rounds] == [
        r["loaded"] is None for r in cold_rounds
    ]
    assert warm_tokens == cold_tokens


def test_held_entries_are_written_by_the_last_release(tmp_path):
    store = ProgramStore(str(tmp_path / "programs"))
    program = program_store.stored_jit(lambda x: x * 2, store=store, table="t")
    store.hold()
    store.hold()
    assert float(program(jnp.float32(3))) == 6.0
    store.release()
    store.join(timeout=30)
    assert entries(tmp_path) == []  # one server is still booting
    store.release()
    store.join(timeout=30)
    assert len(entries(tmp_path)) == 1
    # Outside any hold an entry is written where it is made.
    assert float(program(jnp.ones(2)).sum()) == 4.0
    assert len(entries(tmp_path)) == 2


# ---------------- donation, names and scopes of a loaded program ----------------


def test_loaded_program_donates_the_pools_and_stays_uncommitted(process):
    ecfg = EngineConfig(**BASE)
    slots, nb = ecfg.max_decode_slots, ecfg.max_blocks_per_seq
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)  # noqa: E731

    def decode_text(runner):
        return runner._decode_fn.lower(
            runner.params, *runner._pools, i32(slots), i32(slots),
            i32(slots, nb), i32(slots),
        ).compile().as_text()

    process(None)
    plain = decode_text(mr.GPTRunner(TINY, ecfg, seed=0))
    process()
    decode_text(mr.GPTRunner(TINY, ecfg, seed=0))  # stores the decode program
    store = process()
    runner = mr.GPTRunner(TINY, ecfg, seed=0)
    k_cache, v_cache = runner.k_cache, runner.v_cache
    lanes = np.zeros((slots,), np.int32)
    out = runner.decode(lanes, lanes, np.zeros((slots, nb), np.int32), lanes)
    assert np.asarray(out).shape == (slots,)
    assert store.totals()["programs_loaded"] == 1
    # No second pool is alive after a step: the old ones were donated.
    assert k_cache.is_deleted() and v_cache.is_deleted()
    assert not runner.k_cache.is_deleted()
    # A loaded program's results are as uncommitted as a plain jit's: a
    # decode fed the last decode's output is the one signature it was.
    assert not out.committed and not runner.k_cache.committed
    runner.decode(out, lanes, np.zeros((slots, nb), np.int32), lanes)
    assert runner._decode_fn._cache_size() == 1
    loaded = decode_text(runner)
    assert plain.count("may-alias") == 2  # the K and the V pool
    assert loaded.count("may-alias") == 2
    assert "HloModule jit__decode_step," in plain
    assert "HloModule jit__decode_step," in loaded


def _scopes_of_operations(text: str) -> collections.Counter:
    """How many instructions each scope has, the parameters of nested
    computations (a reduction's two operands) left out: they are no
    operation of a trace, and in a spliced module they inherit the scope of
    the instruction that calls them, where a traced one gives them none."""
    parameters = set(re.findall(r"^\s*%?([\w.-]+) = \S+ parameter\(", text, re.M))
    return collections.Counter(
        scope for name, scope in scopes_of(text).items() if name not in parameters
    )


def test_loaded_programs_are_named_and_scoped_as_the_traced_ones(process):
    cfg = laguna_toy.toy_config()
    params = lg.init_params(cfg, 11)

    def texts():
        runner = hr.HybridRunner(cfg, hybrid_ecfg(), params=params)
        return [
            (name, width, lowered.compile().as_text())
            for name, width, lowered in runner._lowered()
        ]

    process(None)
    traced_texts = texts()
    process()
    texts()  # stores every program
    store = process()
    loaded_texts = texts()
    assert store.totals()["programs_traced"] == 0
    assert store.totals()["programs_loaded"] == len(loaded_texts)
    for (name, width, traced_text), (_, _, loaded_text) in zip(
        traced_texts, loaded_texts
    ):
        assert f"HloModule {name}," in traced_text
        assert f"HloModule {name}," in loaded_text, (name, width)
        traced_scopes = _scopes_of_operations(traced_text)
        loaded_scopes = _scopes_of_operations(loaded_text)
        assert traced_scopes and loaded_scopes == traced_scopes, (name, width)


# ---------------- what must miss ----------------


def _damage(tmp_path, how):
    folder = tmp_path / "programs"
    names = entries(tmp_path)
    whole = {name: (folder / name).read_bytes() for name in names}
    for i, name in enumerate(names):
        if how == "truncated":
            (folder / name).write_bytes(whole[name][: len(whole[name]) // 2])
        elif how == "garbage":
            (folder / name).write_bytes(os.urandom(4096))
        elif how == "one_byte":  # inside the module: nothing parses it here
            flipped = bytearray(whole[name])
            flipped[-len(flipped) // 3] ^= 0xFF
            (folder / name).write_bytes(bytes(flipped))
        elif how == "another_entry":  # whole, under another entry's name
            (folder / name).write_bytes(whole[names[(i + 1) % len(names)]])
        elif how == "another_program":  # whole, and its header names this key
            _, _, blob = whole[names[(i + 1) % len(names)]].partition(b"\n")
            ProgramStore.write(str(folder / name), name, "moved", blob)
        else:  # "not_a_module": a header in order over bytes that are none
            ProgramStore.write(str(folder / name), name, "nothing", b"\x00" * 512)


MISSES = {
    # what changed -> the reason every program of the next process counts
    "engine_config_field": "absent",
    "model_width": "absent",
    "source_byte": "absent",
    "jax_version": "absent",
    "device_count": "absent",
    "truncated": "corrupt",
    "one_byte": "corrupt",
    "garbage": "unreadable",
    "another_entry": "unreadable",
    "another_program": "aval_mismatch",
    "not_a_module": "refused",
}


@pytest.mark.parametrize("what", list(MISSES))
def test_what_must_miss_is_traced_again_counted_and_stored(
    what, process, tmp_path, monkeypatch
):
    process()
    first, first_tokens, _ = boot()
    count = counts(first)["programs_traced"]
    stored = entries(tmp_path)
    assert len(stored) == count >= 3

    changes, model = {}, "gpt"
    if what == "engine_config_field":
        # A field no traced function reads: the key holds every field.
        changes = {"dead_letter_capacity": 65}
    elif what == "model_width":
        wide = dataclasses.replace(TINY, embed_dim=128)
        monkeypatch.setitem(MODELS, "wide", lambda: (wide, EngineConfig(**BASE), None))
        model = "wide"
    elif what == "source_byte":
        source = tmp_path / "kernel.py"
        source.write_text("BLOCK = 128\n")
        files = program_store.source_files()
        monkeypatch.setattr(
            program_store, "source_files",
            lambda package=None: files + [("kernel.py", str(source))],
        )
        process()
        boot()  # stores under a digest that holds the file
        stored = entries(tmp_path)
        source.write_text("BLOCK = 129\n")
    elif what == "jax_version":
        other = {**program_store.versions(), "jax": "0.9.1"}
        monkeypatch.setattr(program_store, "versions", lambda: other)
    elif what == "device_count":
        monkeypatch.setattr(jax, "device_count", lambda: 4)
    else:
        _damage(tmp_path, what)

    process()
    again, again_tokens, _ = boot(model, **changes)
    stats = counts(again)
    assert stats["programs_loaded"] == 0 and stats["programs_traced"] == count
    assert stats["program_store_misses_by_reason"] == {MISSES[what]: count}
    if model == "gpt":
        assert again_tokens == first_tokens
    now = entries(tmp_path)
    if MISSES[what] == "absent":  # new entries beside the old ones
        assert set(stored) < set(now) and len(now) == len(stored) + count
    else:  # replaced, nothing left over
        assert now == stored

    # And a good entry is left behind: the next process loads every one.
    process()
    warm, warm_tokens, _ = boot(model, **changes)
    assert counts(warm) == {
        "programs_loaded": count, "programs_traced": 0,
        "program_store_misses_by_reason": {},
    }
    assert warm_tokens == again_tokens


def _key(store, **changes) -> str:
    """The key of one decode-like program, one input changed."""
    fields = dict(
        table=(TINY, 8, "reference"), name="_decode_step", donated=(1, 2),
        pool=(2, 64, 8, 64), dtype=jnp.float32,
    )
    fields.update(changes)
    pool = jax.ShapeDtypeStruct(fields["pool"], fields["dtype"])
    args = ({"w": jax.ShapeDtypeStruct((4, 4), jnp.float32)}, pool, pool, None,
            jax.ShapeDtypeStruct((4,), jnp.int32))
    leaves, in_tree = jax.tree_util.tree_flatten(args)
    return store.key(
        fields["table"], fields["name"], fields["donated"], in_tree, leaves
    )


@pytest.mark.parametrize("changed", [
    {"table": (dataclasses.replace(TINY, num_layers=3), 8, "reference")},
    {"table": (TINY, 16, "reference")},
    {"table": (TINY, 8, "reference", repr(EngineConfig(**BASE)))},
    {"name": "_verify_step"},
    {"donated": (1, 2, 3)},
    {"pool": (2, 65, 8, 64)},
    {"dtype": jnp.bfloat16},
], ids=lambda changed: next(iter(changed)))
def test_key_holds_the_table_the_name_the_donation_and_the_arguments(changed, tmp_path):
    store = ProgramStore(str(tmp_path))
    assert _key(store) == _key(ProgramStore(str(tmp_path)))
    assert _key(store, **changed) != _key(store)


@pytest.mark.parametrize("field", [
    "source", "jax", "jaxlib", "libtpu", "runtime", "platform", "device_kind",
    "device_count", "XLA_FLAGS", "LIBTPU_INIT_ARGS", "jax_enable_x64",
    "jax_default_matmul_precision", "jax_numpy_dtype_promotion",
])
def test_key_holds_the_environment(field, tmp_path):
    store, other = ProgramStore(str(tmp_path)), ProgramStore(str(tmp_path))
    environment = store.environment()
    assert field in environment
    assert environment["jax"] == jax.__version__ and environment["platform"] == "cpu"
    assert environment["source"] == program_store.source_digest()
    other._environment = {**environment, field: "another"}
    assert _key(other) != _key(store)


def test_source_digest_reads_the_bytes_of_what_a_trace_runs_through(tmp_path):
    names = [name for name, _ in program_store.source_files()]
    assert "_private/jax_setup.py" in names
    for folder in ("llm", "models", "ops", "parallel"):
        assert any(name.startswith(folder + os.sep) for name in names), folder
    assert os.path.join("llm", "program_store.py") in names
    assert all(name.endswith(".py") for name in names)
    package = tmp_path / "package"
    (package / "ops").mkdir(parents=True)
    (package / "ops" / "kernel.py").write_text("BLOCK = 128\n")
    (package / "ops" / "notes.txt").write_text("not a source\n")
    before = program_store.source_digest(str(package))
    (package / "ops" / "notes.txt").write_text("still not a source\n")
    os.utime(package / "ops" / "kernel.py", (1, 1))  # a time is no byte
    assert program_store.source_digest(str(package)) == before
    (package / "ops" / "kernel.py").write_text("BLOCK = 129\n")
    assert program_store.source_digest(str(package)) != before


# ---------------- never in a replica's way ----------------


def test_program_that_cannot_be_exported_stays_a_plain_jit(tmp_path):
    """A host callback is nothing `jax.export` serializes: the program is
    traced in place, counted by reason, and nothing is written for it."""
    store = ProgramStore(str(tmp_path / "programs"))
    seen = []

    def step(x):
        jax.debug.callback(lambda v: seen.append(float(v)), x.sum())
        return x + 1

    program = program_store.stored_jit(step, store=store, table="test")
    assert float(program(jnp.ones(3)).sum()) == 6.0
    jax.effects_barrier()
    assert seen == [3.0]
    totals = store.totals()
    assert totals["programs_traced"] == 1 and totals["programs_loaded"] == 0
    (reason,) = totals["program_store_misses_by_reason"]
    assert reason.startswith("not_exported:")
    assert entries(tmp_path) == []


def test_store_that_cannot_be_written_is_a_warning_not_a_failed_boot(
    process, tmp_path, caplog
):
    (tmp_path / "programs").write_text("a file where the directory would be")
    process()
    with caplog.at_level("WARNING", logger="ray_tpu.llm.program_store"):
        engine, tokens, _ = boot()
    assert counts(engine)["programs_traced"] >= 3
    assert "is not stored" in caplog.text
    process(None)
    assert boot()[1] == tokens


def test_store_whose_lookup_raises_is_a_warning_not_a_failed_boot(
    process, monkeypatch, caplog
):
    def broken(self):
        raise RuntimeError("no such device")

    store = process()
    monkeypatch.setattr(ProgramStore, "environment", broken)
    with caplog.at_level("WARNING", logger="ray_tpu.llm.program_store"):
        engine, tokens, _ = boot()
    stats = counts(engine)
    assert stats["programs_loaded"] == 0 and stats["programs_traced"] >= 3
    assert stats["program_store_misses_by_reason"] == {
        "store_error:RuntimeError": stats["programs_traced"]
    }
    assert "stays a plain jax.jit" in caplog.text and store.directory
    process(None)
    assert boot()[1] == tokens


_WRITER = """
import sys, time
from ray_tpu.llm.program_store import ProgramStore
path, key, fill, start = sys.argv[1], sys.argv[2], sys.argv[3], float(sys.argv[4])
blob = fill.encode() * (8 << 20)
time.sleep(max(0.0, start - time.time()))
for _ in range(20):
    ProgramStore.write(path, key, "w" + fill, blob)
"""


def test_two_processes_writing_one_key_at_once_leave_one_whole_entry(tmp_path):
    folder = tmp_path / "programs"
    key = "k" * 64
    start = str(time.time() + 6.0)  # both have imported jax by then
    env = {**os.environ, "PYTHONPATH": os.pathsep.join(sys.path)}
    writers = [
        subprocess.Popen(
            [sys.executable, "-c", _WRITER, str(folder / key), key, fill, start],
            env=env,
        )
        for fill in "ab"
    ]
    assert [writer.wait(timeout=120) for writer in writers] == [0, 0]
    assert os.listdir(folder) == [key]  # no temporary file left behind
    header, _, blob = (folder / key).read_bytes().partition(b"\n")
    described = json.loads(header)
    assert described["key"] == key and described["name"] in ("wa", "wb")
    assert blob == described["name"][1].encode() * (8 << 20)
    assert described["sha256"] == hashlib.sha256(blob).hexdigest()


# ---------------- off on the CPU backend ----------------


def test_on_the_cpu_backend_the_store_is_off_and_the_tables_hold_plain_jits(
    monkeypatch, tmp_path
):
    # Whatever the environment places the compile cache at.
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    monkeypatch.setattr(program_store, "_default", None)
    monkeypatch.setattr(mr, "_PROGRAM_CACHE", {})
    monkeypatch.setattr(hr, "_PROGRAM_CACHE", {})
    assert program_store.default().directory is None
    gpt = mr._StepPrograms(TINY, 8, "reference", jnp.float32, 1)
    hybrid = hr._HybridPrograms(falcon_h1_toy.toy_config(), 8, "reference")
    for table, names in (
        (gpt, ("decode", "verify", "prefill", "prefill_suffix", "copy_block",
               "restore_block")),
        (hybrid, ("decode", "prefill", "prefill_suffix")),
    ):
        for name in names:
            jitted = getattr(table, f"{name}_fn")
            assert isinstance(jitted, PLAIN_JIT)
            # jax.jit of the traced method itself, not of a wrapper.
            assert jitted.__wrapped__ == getattr(table, f"_{name}_step")
        assert table.join_token_fn.__wrapped__ is mr.join_token
    engine, _, _ = boot()
    assert engine.runner._programs.decode_fn.__wrapped__.__name__ == "_decode_step"
    assert counts(engine) == {
        "programs_loaded": 0, "programs_traced": 0,
        "program_store_misses_by_reason": {},
    }
    assert os.listdir(tmp_path) == []


def test_beside_a_store_the_tables_hold_jits_of_the_same_names(process):
    process()
    engine = LLMEngine(TINY, EngineConfig(**BASE), seed=0)
    table = engine.runner._programs
    for name in ("decode", "verify", "prefill", "prefill_suffix", "copy_block",
                 "restore_block"):
        jitted = getattr(table, f"{name}_fn")
        assert isinstance(jitted, PLAIN_JIT)
        assert jitted.__wrapped__ != getattr(table, f"_{name}_step")
        assert jitted.__wrapped__.__name__ == f"_{name}_step"
    assert table.join_token_fn.__wrapped__.__name__ == "join_token"
