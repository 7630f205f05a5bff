"""The main paths' kernels, compiled for a described TPU v5e without one.

Interpret mode (every other kernel test) runs the kernel's arithmetic but
not Mosaic: a vector layout, a block shape or a VMEM budget the chip's
compiler refuses passes there and fails at the first warmup on the chip
(PR 21: every S > 1 shape of the paged kernel). These compile the real
thing at gpt2_760m / gpt2_125m widths. Nothing runs, so they say nothing
about results — the interpret-mode tests and chip_smoke.py do.

The second half compiles the runner's own step programs at GPT-2 large
widths (depth cut) and reads the compiled text: the KV pools are stored in
the layout the paged kernel takes its operands in, so no program may hold a
`copy` of a pool or temp the size of one (PR 25; before it the decode
program converted both pools on the way into the kernel and back, seven
whole-pool copies a step on the chip). They compile over the tree a runner
holds, matrices in the compute dtype, and no program rounds one (PR 33).
"""

import functools
import os
import re
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else libtpu logs to /tmp

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from ray_tpu.llm import program_store
from ray_tpu.llm.model_runner import _StepPrograms
from ray_tpu.models.gpt import GPTConfig, serving_params
from ray_tpu.ops import flash_attention, paged_flash_attention
from ray_tpu.ops.paged_flash import KV_SCALE_DTYPE


@pytest.fixture(scope="module")
def chip():
    """One chip of a described v5e 2x2, with the compile cache off: an
    executable compiled for a described chip is written to the cache but
    cannot be read back without one."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topology = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as exc:  # no libtpu, or it cannot describe a v5e
        pytest.skip(f"cannot describe a TPU topology here: {exc!r}")
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topology.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


def _paged(batch, fed, heads, pool_dtype=jnp.bfloat16, head_dim=64, block=16):
    """(fn, shapes) for the paged kernel over the smoke's geometry: 16,384
    cached tokens in blocks of 16, 1,024-token tables, heads of 64, the
    pools as stored (two layers of lane-dense [16, H*D] blocks, read at
    layer 1)."""

    def fn(q, k_cache, v_cache, tables, lens, new_k, new_v, k_scale, v_scale):
        return paged_flash_attention(
            q, k_cache, v_cache, tables, lens, new_k=new_k, new_v=new_v,
            layer=1, k_scale=k_scale, v_scale=v_scale, interpret=False,
        )

    q = ((batch, fed, heads, head_dim), jnp.bfloat16)
    blocks = (2, 16384 // block, block)
    pool = (blocks + (heads * head_dim,), pool_dtype)
    scale = (
        (blocks + (heads,), KV_SCALE_DTYPE) if pool_dtype == jnp.int8 else None
    )
    return fn, [
        q, pool, pool, ((batch, 1024 // block), jnp.int32),
        ((batch,), jnp.int32), q, q, scale, scale,
    ]


def _cell_decode(slots, table, heads, kv_heads, head_dim, blocks, window=None):
    """(fn, shapes) for a serving cell's decode call: the lanes, the table
    length and the pool it has there (three layers, read at the last), so
    the decode walk's compute block is the one `decode_tile` picks in the
    cell and its two-deep tiles, the block-diagonal q and the float32
    accumulator have to fit the scoped VMEM limit together."""

    def fn(q, k_cache, v_cache, tables, lens, new_k, new_v):
        return paged_flash_attention(
            q, k_cache, v_cache, tables, lens, new_k=new_k, new_v=new_v,
            layer=2, num_kv_heads=kv_heads, window=window, interpret=False,
        )

    pool = ((3, blocks, 16, kv_heads * head_dim), jnp.bfloat16)
    new = ((slots, 1, kv_heads, head_dim), jnp.bfloat16)
    return fn, [
        ((slots, 1, heads, head_dim), jnp.bfloat16), pool, pool,
        ((slots, table), jnp.int32), ((slots,), jnp.int32), new, new,
    ]


def _flash_train():
    """Forward and backward of the blockwise training kernel at bench.py's
    per-chip GPT-2 125M batch. The packed kernel the model runs at
    S <= 2048 unrolls 12 heads in two subtiles and takes 64 s to compile,
    too long for this file: chip_smoke.py's train phase proves it."""

    def loss(q):
        return flash_attention(q, q, q, causal=True).astype(jnp.float32).sum()

    return jax.grad(loss), [((24, 1024, 12, 64), jnp.bfloat16)]


CASES = {
    "paged_decode_bf16": lambda: _paged(8, 1, 20),
    "paged_decode_int8": lambda: _paged(8, 1, 20, jnp.int8),
    "paged_decode_tp_local_5_heads": lambda: _paged(8, 1, 5),
    "paged_decode_tp_local_5_heads_int8": lambda: _paged(8, 1, 5, jnp.int8),
    "paged_decode_16_slots": lambda: _paged(16, 1, 20),
    "paged_decode_heads_of_128": lambda: _paged(8, 1, 8, head_dim=128),
    # The engine's default block: 16 table entries a compute block.
    "paged_decode_blocks_of_8": lambda: _paged(16, 1, 20, block=8),
    "paged_decode_blocks_of_8_int8": lambda: _paged(16, 1, 20, jnp.int8, block=8),
    "paged_prefill_64_bucket_blocks_of_8": lambda: _paged(1, 64, 20, block=8),
    "paged_verify_16_slots_4_fed": lambda: _paged(16, 4, 20),
    "paged_prefill_smallest_bucket": lambda: _paged(1, 16, 20),
    "paged_prefill_64_bucket": lambda: _paged(1, 64, 20),
    "paged_prefill_largest_bucket": lambda: _paged(1, 256, 20),
    "paged_prefill_largest_bucket_int8": lambda: _paged(1, 256, 20, jnp.int8),
    "flash_fwd_bwd_gpt2_125m": _flash_train,
    # The serving cells' decode calls, whose compute block is sized in
    # bytes (PR 51): 512 tokens at Falcon-H1's and Laguna's rows, 384 at
    # GPT-2 large's, 128 at Olmo Hybrid's.
    "cell_decode_falcon_20_over_4": lambda: _cell_decode(96, 176, 20, 4, 128, 7424),
    "cell_decode_laguna_full_48_over_8": lambda: _cell_decode(48, 896, 48, 8, 128, 13312),
    "cell_decode_laguna_window_72_over_8": lambda: _cell_decode(
        48, 896, 72, 8, 128, 1858, window=512
    ),
    "cell_decode_olmo_30_heads": lambda: _cell_decode(64, 200, 30, 30, 128, 4608),
    "cell_decode_gpt2_large_20_heads_of_64": lambda: _cell_decode(16, 64, 20, 20, 64, 3072),
}


@pytest.mark.parametrize("case", list(CASES))
def test_kernel_compiles_for_v5e(chip, monkeypatch, case):
    # The training kernels choose interpret mode from the backend, which
    # is the CPU here; the paged kernel takes interpret=False directly.
    # (`ray_tpu.ops.flash_attention` names the function, hence sys.modules.)
    monkeypatch.setattr(
        sys.modules["ray_tpu.ops.flash_attention"], "_on_cpu", lambda: False
    )
    fn, shapes = CASES[case]()
    args = [
        None if s is None else jax.ShapeDtypeStruct(*s, sharding=chip)
        for s in shapes
    ]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


# ---------------- the runner's step programs: no copy of a pool ----------------

# GPT-2 large widths (20 heads of 64, the real vocabulary), depth cut to 3;
# the benchmark's geometry: 1,024 blocks of 16, 16 slots, 64-block tables.
_LAYERS, _BLOCKS, _BLOCK, _HEADS, _HEAD_DIM = 3, 1024, 16, 20, 64
_SLOTS, _TABLE = 16, 64


def _step_program(programs, name):
    """(jitted program, shapes of its arguments after params and pools)."""
    i32 = lambda *shape: (shape, jnp.int32)  # noqa: E731
    return {
        "decode": (
            programs.decode_fn,
            [i32(_SLOTS), i32(_SLOTS), i32(_SLOTS, _TABLE), i32(_SLOTS)],
        ),
        "prefill_suffix": (
            programs.prefill_suffix_fn, [i32(1, 64), i32(_TABLE), i32(), i32()],
        ),
        "prefill_full": (
            programs.prefill_fn, [i32(1, 256), i32(256 // _BLOCK), i32()],
        ),
        "verify": (
            programs.verify_fn,
            [i32(_SLOTS, 4), i32(_SLOTS, _TABLE), i32(_SLOTS), i32(_SLOTS)],
        ),
    }[name]


def _compile_step_program(chip, monkeypatch, program, kv_dtype, jit=jax.jit):
    """(config, compiled program) over the tree a runner holds: the shapes
    of `serving_params` of a seed init, on the described chip."""
    for module in ("ray_tpu.ops.flash_attention", "ray_tpu.ops.paged_flash"):
        monkeypatch.setattr(sys.modules[module], "_on_cpu", lambda: False)
    cfg = GPTConfig(
        num_layers=_LAYERS, num_heads=_HEADS, embed_dim=_HEADS * _HEAD_DIM
    )
    # Not through the process-wide program cache: these are traced with the
    # kernels forced out of interpret mode.
    programs = _StepPrograms(cfg, _BLOCK, "pallas", kv_dtype, 1, jit=jit)

    def on_chip(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    params = jax.tree_util.tree_map(
        lambda leaf: on_chip(leaf.shape, leaf.dtype),
        jax.eval_shape(
            lambda: serving_params(
                cfg,
                programs.model.init(
                    jax.random.PRNGKey(0), jnp.zeros((1, _BLOCK), jnp.int32)
                ),
            )
        ),
    )
    blocks = (_LAYERS, _BLOCKS, _BLOCK)
    pool = on_chip(blocks + (_HEADS * _HEAD_DIM,), kv_dtype)
    scale = (
        on_chip(blocks + (_HEADS,), KV_SCALE_DTYPE)
        if kv_dtype == jnp.int8 else None
    )
    fn, rest = _step_program(programs, program)
    return cfg, fn.lower(
        params, pool, pool, scale, scale, *(on_chip(*s) for s in rest)
    ).compile()


_PROGRAMS = ["decode", "prefill_suffix", "prefill_full", "verify"]


def _assert_holds_no_copy_of_a_pool(compiled, program, kv_dtype):
    text = compiled.as_text()
    if program != "prefill_full":  # full prefill reads no cache
        assert "tpu_custom_call" in text
    # Wherever the program names an array of the pools' shape, arguments
    # and results included, it is in the kernel's layout: row-major.
    stored = re.escape(
        f"[{_LAYERS},{_BLOCKS},{_BLOCK},{_HEADS * _HEAD_DIM}]"
    )
    layouts = set(re.findall(stored + r"\{([\d,]+)", text))
    assert layouts == {"3,2,1,0"}, layouts
    pool_copies = [
        line.strip()[:200] for line in text.splitlines()
        if re.search(r"= \w+" + stored + r"\S* copy\(", line)
    ]
    assert not pool_copies, pool_copies
    # Temp stays under ONE layer of one pool: before PR 25 it was several
    # whole pools, and until the runner held its matrices in the compute
    # dtype a bf16 copy of the embedding table (129 MB) lay beside it.
    layer_pool_bytes = _BLOCKS * _BLOCK * _HEADS * _HEAD_DIM * kv_dtype.itemsize
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < layer_pool_bytes, (temp, layer_pool_bytes)


@pytest.mark.parametrize("kv_dtype", ["bfloat16", "int8"])
@pytest.mark.parametrize("program", _PROGRAMS)
def test_step_program_holds_no_copy_of_a_pool(
    chip, monkeypatch, program, kv_dtype
):
    kv_dtype = jnp.dtype(kv_dtype)
    _, compiled = _compile_step_program(chip, monkeypatch, program, kv_dtype)
    _assert_holds_no_copy_of_a_pool(compiled, program, kv_dtype)


_MODULE_NAMES = {
    "decode": "jit__decode_step", "prefill_suffix": "jit__prefill_suffix_step",
    "prefill_full": "jit__prefill_step", "verify": "jit__verify_step",
}


@pytest.mark.parametrize("program", _PROGRAMS)
def test_stored_step_program_compiles_as_the_traced_one(
    chip, monkeypatch, tmp_path, program
):
    """The same programs through the program store (`llm.program_store`):
    exported for the TPU, written, read back, deserialized and compiled
    for the described chip inside the jit that runs a stored module. An
    export that drops the pools' donation, loses a kernel or renames a
    program (the benchmark's readers find programs by name) fails here, on
    the CPU, and not in a trace on the chip."""
    kv_dtype = jnp.dtype("bfloat16")
    _, traced = _compile_step_program(chip, monkeypatch, program, kv_dtype)
    # The store lowers for the process's backend, the CPU here.
    monkeypatch.setattr(program_store, "_platform", lambda: "tpu")

    def through(store):
        return functools.partial(program_store.stored_jit, store=store, table="test")

    store = program_store.ProgramStore(str(tmp_path))
    _compile_step_program(chip, monkeypatch, program, kv_dtype, jit=through(store))
    assert store.totals()["programs_traced"] == 1
    store = program_store.ProgramStore(str(tmp_path))
    _, loaded = _compile_step_program(
        chip, monkeypatch, program, kv_dtype, jit=through(store)
    )
    assert store.totals() == {
        "programs_loaded": 1, "programs_traced": 0,
        "program_store_misses_by_reason": {},
    }
    _assert_holds_no_copy_of_a_pool(loaded, program, kv_dtype)
    traced_text, loaded_text = traced.as_text(), loaded.as_text()
    assert f"HloModule {_MODULE_NAMES[program]}," in traced_text
    assert f"HloModule {_MODULE_NAMES[program]}," in loaded_text
    kernel = 'custom_call_target="tpu_custom_call"'
    assert loaded_text.count(kernel) == traced_text.count(kernel)
    # Both pools are updated in place: the donation holds through the call.
    assert traced_text.count("may-alias") == 2
    assert loaded_text.count("may-alias") == 2
    assert (
        loaded.memory_analysis().alias_size_in_bytes
        == traced.memory_analysis().alias_size_in_bytes
    )


@pytest.mark.parametrize("program", _PROGRAMS)
def test_step_program_rounds_no_weight(chip, monkeypatch, program):
    """A step program over the tree the runner holds reads its matrices as
    it multiplies them. Over the float32 tree every one of these programs
    took `f32[50304,1280]` as a parameter and wrote it out again rounded,
    for the gather and the tied head to share: 0.60 ms of a 5.0 ms decode
    step on the chip, and the float32 layer matrices 3.5 ms (PR 33)."""
    cfg, compiled = _compile_step_program(
        chip, monkeypatch, program, jnp.dtype("bfloat16")
    )
    text = compiled.as_text()
    e, mlp = cfg.embed_dim, cfg.mlp_ratio * cfg.embed_dim
    wte = f"[{cfg.vocab_size},{e}]"
    assert "bf16" + wte in text  # the table is there, as held
    matrices = "|".join(
        re.escape(shape) for shape in (
            wte, f"[{cfg.max_seq_len},{e}]", f"[{e},{3 * e}]", f"[{e},{e}]",
            f"[{e},{mlp}]", f"[{mlp},{e}]",
        )
    )
    assert not re.findall(rf"f32(?:{matrices})", text)
    rounded = [
        line.strip()[:200] for line in text.splitlines()
        if re.search(rf"= bf16(?:{matrices})\S* convert\(", line)
    ]
    assert not rounded, rounded
