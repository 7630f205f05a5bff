"""granite-4.0-h-small's decode program at its real widths, compiled for a
described TPU v5e without one (the style of tests/test_tpu_compile.py): the
benchmark cell's geometry (layers 0-9, 36 of 72 experts held, 32 lanes with
their state slots, 16,384 blocks of 16, 400-block tables), parameters as
shapes only. Nothing runs. What it proves: Mosaic takes the grouped-query
paged kernel at 32 query heads over 8 cached heads of 128, the weights,
both K/V pools and every state pool fit one chip beside the program's
temporaries with room for the widest prefill chunk (14.4 GB of the chip's
16.9: the 2,048-token chunk needs 1.1 GB more than decode), and the donated
pools alias, so no step copies a pool. About ten seconds: not slow.

And the paged kernel fed Laguna's widest chunk at the widths its cell runs
(3,584 tokens at 72 query heads over 8 cached heads of 128 under a window
of 512, and at 48 over 8 without; blocks of 16, tables of 896): a cached
head's query heads are the rows of one product, 576 and 384 of them, and
the q tile's blocks stay under the compiler's scoped VMEM limit.

And one chunk program of each at the cell's widths with the grouped experts'
own kernels in it (PR 41): granite's 2,048-token and Laguna's 1,024-token
starting chunk compile, fit beside the pools, keep them in place, name every
instruction of the grouped path `llm.moe.routed`, and hand Mosaic the
experts' kernels once a rung of the ladder, not once a layer.

And Olmo Hybrid's decode program and widest chunk at the widths its cell
runs (PR 42: layers 0-15, 64 lanes, 4,608 blocks of 16, 200-block tables):
the paged kernel at 30 cached heads of 128 with one query head each, the
packed float32 state pools [64, 15, 96, 384] and every K/V pool updated in
place, no temporary of a state pool's size (a `repeat` over the packed axis
once cost 1.9 GB of them), and 14.8 / 15.0 GB held of the chip's 16.9
(the chip's allocator reads 14.84 GB at its peak: 0.15 GB under this analysis).
Since PR 43 the decode program's update is a Pallas kernel a layer
(`ops/gated_delta.py`): Mosaic takes it at that shape, it is lowered once
for its twelve callers, each call sits under `llm.mixer.gdn.update`, and no
XLA operation outside the calls reads or writes an array of a state pool's
shape.

And Falcon-H1's decode program and widest chunk at the widths its cell runs
(PR 46: layers 0-5, 96 lanes, 7,424 blocks of 16, 176-block tables): every
layer has BOTH a state pool [96, 32, 128, 256] float32 with its flat tail
and a layer of the K/V pools [6, 7424, 16, 512], all updated in place by the
one program; the paged kernel at 5 query heads a cached head of 128; the
grouped scan and update lowered as plain XLA with no temporary of a state
pool's size; the head of 261,120 rows (decode's logits are 100 MB); 14.61 /
14.97 GB held of the chip's 16.91 (the chip's allocator reads 14.87 GB at
its peak: 0.10 GB under this analysis).
"""

import gc
import os
import re
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else libtpu logs to /tmp

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from ray_tpu.llm import hybrid_runner as hr
from ray_tpu.llm.config import EngineConfig
from ray_tpu.models import falcon_h1 as fh
from ray_tpu.models import granite_hybrid as gh
from ray_tpu.models import laguna
from ray_tpu.models import olmo_hybrid as oh
from ray_tpu.ops import gated_delta, grouped_experts
from ray_tpu.ops.paged_flash import paged_flash_attention

SLOTS, TABLE, BLOCK, BLOCKS = 32, 400, 16, 16384


@pytest.fixture(scope="module")
def chip():
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


@pytest.fixture(scope="module", autouse=True)
def _leave_a_small_heap():
    """What this file traced goes when it is done: the worker that ran it
    runs other files after, and some of them time a full `gc.collect()`."""
    yield
    jax.clear_caches()
    gc.collect()


def test_real_width_decode_program_fits_a_v5e(chip, monkeypatch):
    # The paged kernel chooses interpret mode from the backend, the CPU here.
    monkeypatch.setattr(sys.modules["ray_tpu.ops.paged_flash"], "_on_cpu", lambda: False)
    cfg = gh.GraniteHybridConfig(experts_held=tuple(range(36)))
    programs = hr._HybridPrograms(cfg, BLOCK, "pallas")

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    params = jax.tree_util.tree_map(
        lambda shape: sds(shape, jnp.bfloat16), gh._leaf_shapes(cfg),
        is_leaf=lambda v: isinstance(v, tuple),
    )
    weights = 2 * sum(x.size for x in jax.tree_util.tree_leaves(params))
    assert 9.92e9 < weights < 9.94e9  # 4.96 B parameters in bfloat16
    kv = sds((1, BLOCKS, BLOCK, 8 * 128), jnp.bfloat16)
    conv = tuple(sds((SLOTS, 3, cfg.conv_dim), jnp.bfloat16) for _ in range(9))
    ssm = tuple(sds((SLOTS, 128, 64, 128), jnp.float32) for _ in range(9))
    i32 = lambda *shape: sds(shape, jnp.int32)  # noqa: E731
    compiled = programs.decode_fn.lower(
        params, (kv,), (kv,), (conv, ssm), i32(SLOTS + len(hr.DECODE_COUNTS)),
        i32(SLOTS), (i32(SLOTS, TABLE),), i32(SLOTS),
    ).compile()
    memory = compiled.memory_analysis()
    pools = 2 * BLOCKS * BLOCK * 1024 * 2 + SLOTS * 9 * (128 * 64 * 128 * 4 + 3 * 8448 * 2)
    assert memory.alias_size_in_bytes >= pools  # every pool updated in place
    held = (
        memory.argument_size_in_bytes + memory.temp_size_in_bytes
        + memory.output_size_in_bytes - memory.alias_size_in_bytes
    )
    assert held < 14.4e9, held
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1  # the paged kernel
    scopes = set(hr.scopes_of(text).values())
    assert {"llm.mixer.mamba.update", "llm.moe.routed", "llm.mixer.attention"} <= scopes


# layers 0-11 of Laguna-S-2.1 and its cell's geometry (benchmark/configs)
LAGUNA = laguna.LagunaConfig(
    layer_types=laguna.LAGUNA_PERIOD * 3,
    num_attention_heads_per_layer=(48, 72, 72, 72) * 3,
    mlp_layer_types=(laguna.DENSE,) + (laguna.SPARSE,) * 11,
    experts_held=tuple(range(32)),
)
LAGUNA_ENGINE = EngineConfig(
    block_size=16, num_blocks=13312, max_blocks_per_seq=896, max_decode_slots=48,
    prefill_buckets=(256, 1024, 2048, 3584),
)


@pytest.mark.parametrize(
    "cls,layers,blocks,q_tile,rows",
    [("window", 9, 1858, 64, 576), ("full", 3, 13312, 64, 384)],
)
def test_lagunas_widest_chunk_stacks_a_cached_heads_query_heads(
    chip, cls, layers, blocks, q_tile, rows
):
    # What `stats()["attention_shape"]` would say, without the 9.7 GB of
    # parameters a runner makes: the method reads these four fields.
    runner = object.__new__(hr.HybridRunner)
    runner.model_config, runner.engine_config = LAGUNA, LAGUNA_ENGINE
    runner.classes, runner.kv_cache_dtype = LAGUNA.cache_classes, LAGUNA.dtype
    shape = runner.attention_shape()[cls]
    assert (shape["num_layers"], shape["num_heads"]) == (layers, 8)
    assert shape["prefill_q_tile"] == q_tile
    assert shape["prefill_rows_per_product"] == rows
    heads, chunk = shape["num_query_heads"], max(LAGUNA_ENGINE.chunk_widths())
    assert (chunk, rows) == (3584, heads // 8 * q_tile)
    # A decode walk's compute block, either class: 2 MiB of K and V, which
    # at 8 cached heads of 128 in bf16 are 512 tokens (32 table entries).
    assert shape["decode_tile_tokens"] == 512
    assert shape["decode_bytes_in_flight"] == 2 * 512 * 8 * 128 * 2 == 2 * 1024 * 1024

    def sds(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    def fn(q, k_cache, v_cache, tables, lens, new_k, new_v):
        return paged_flash_attention(
            q, k_cache, v_cache, tables, lens, new_k=new_k, new_v=new_v,
            layer=layers - 1, num_kv_heads=8, interpret=False,
            **({} if "horizon" not in shape else {"window": shape["horizon"]}),
        )

    pool, fed = sds((layers, blocks, 16, 8 * 128)), sds((1, chunk, 8, 128))
    compiled = jax.jit(fn).lower(
        sds((1, chunk, heads, 128)), pool, pool, sds((1, 896), jnp.int32),
        sds((1,), jnp.int32), fed, fed,
    ).compile()  # raises where the blocks pass the scoped VMEM limit
    assert compiled.as_text().count('custom_call_target="tpu_custom_call"') == 1


def _grouped_path_is_routed(text):
    """Every instruction `routed_grouped` traced, its kernels among them,
    is timed as the routed experts; how many of them are Mosaic kernels."""
    scopes = hr.scopes_of(text)
    routed = [
        line for line in text.splitlines()
        if re.search(r"jit\((_forward|rows_by_group|summed_by_token)\)", line)
    ]
    named = [re.match(r"\s*(?:ROOT )?%?([\w.\-]+) = ", line) for line in routed]
    assert routed and all(named)
    off = [m[1] for m in named if scopes.get(m[1]) != "llm.moe.routed"]
    assert not off, off[:5]
    return sum('custom_call_target="tpu_custom_call"' in line for line in routed)


@pytest.mark.parametrize("model", ["granite", "laguna"])
def test_a_chunk_program_with_the_grouped_kernels_fits_a_v5e(chip, monkeypatch, model):
    monkeypatch.setattr(sys.modules["ray_tpu.ops.paged_flash"], "_on_cpu", lambda: False)
    monkeypatch.setattr(grouped_experts, "_on_cpu", lambda: False)

    def sds(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    i32 = lambda *shape: sds(shape, jnp.int32)  # noqa: E731
    if model == "granite":
        cfg = gh.GraniteHybridConfig(experts_held=tuple(range(36)))
        shapes, chunk, expert_layers = gh._leaf_shapes(cfg), 2048, 10
        pools = [sds((1, BLOCKS, BLOCK, 8 * 128))]
        conv = tuple(sds((SLOTS, 3, cfg.conv_dim)) for _ in range(9))
        ssm = tuple(sds((SLOTS, 128, 64, 128), jnp.float32) for _ in range(9))
        state = (conv, ssm)
        tables, limit = (i32(TABLE),), 14.4e9
        rungs = grouped_experts.ladder(chunk * 10, 36 / 72)
        assert rungs == (12800, 20480)
    else:
        cfg, chunk, expert_layers = LAGUNA, 1024, 11
        shapes = laguna._leaf_shapes(cfg)
        pools = [sds((3, 13312, 16, 8 * 128)), sds((9, 1858, 16, 8 * 128))]
        state = ()
        tables, limit = (i32(896), i32(896)), 15.2e9
        rungs = grouped_experts.ladder(chunk * 10, 32 / 256)
        assert rungs == (1792, 7168, 10240)
    params = jax.tree_util.tree_map(
        sds, shapes, is_leaf=lambda v: isinstance(v, tuple)
    )
    programs = hr._HybridPrograms(cfg, BLOCK, "pallas")
    lowered = programs.prefill_fn.lower(
        params, tuple(pools), tuple(pools), state, i32(1, chunk), tables,
        i32(), i32(),
    )
    # Handed to Mosaic: the paged kernel an attention layer, and the grouped
    # experts' three once a rung however many layers call them.
    attention = sum(kind != gh.MAMBA for kind in cfg.layer_types)
    kernels = lowered.as_text().count("tpu_custom_call")
    assert kernels == attention + 3 * len(rungs), kernels
    compiled = lowered.compile()
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes >= 2 * sum(
        2 * pool.size for pool in pools
    )  # every K/V pool updated in place
    held = (
        memory.argument_size_in_bytes + memory.temp_size_in_bytes
        + memory.output_size_in_bytes - memory.alias_size_in_bytes
    )
    assert held < limit, held
    # In the compiled program every layer has its own copy of each rung's.
    assert _grouped_path_is_routed(compiled.as_text()) == 3 * len(rungs) * expert_layers


@pytest.mark.parametrize("program,held_limit,temp_limit", [
    ("decode", 14.85e9, 0.4e9), ("chunk2048", 15.1e9, 0.65e9),
])
def test_olmo_hybrids_programs_fit_a_v5e_with_the_state_in_place(
    chip, monkeypatch, program, held_limit, temp_limit
):
    monkeypatch.setattr(sys.modules["ray_tpu.ops.paged_flash"], "_on_cpu", lambda: False)
    monkeypatch.setattr(gated_delta, "_on_cpu", lambda: False)
    slots, table, blocks = 64, 200, 4608
    cfg = oh.OlmoHybridConfig(layer_types=oh.OLMO_HYBRID_PERIOD * 4)

    def sds(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    i32 = lambda *shape: sds(shape, jnp.int32)  # noqa: E731
    params = jax.tree_util.tree_map(
        sds, oh._leaf_shapes(cfg), is_leaf=lambda v: isinstance(v, tuple)
    )
    assert 2 * sum(x.size for x in jax.tree_util.tree_leaves(params)) == 2 * 4_100_788_944
    kv = sds((4, blocks, BLOCK, 30 * 128))
    state = tuple(
        tuple(sds((slots, *shape), dtype) for _ in range(layers))
        for _, layers, (_, shape, dtype) in hr.state_layout(cfg)
    )
    assert [pools[0].shape for pools in state] == [(64, 3 * 11520), (64, 15, 96, 384)]
    programs = hr._HybridPrograms(cfg, BLOCK, "pallas")
    if program == "decode":
        lowered = programs.decode_fn.lower(
            params, (kv,), (kv,), state, i32(slots), i32(slots), (i32(slots, table),),
            i32(slots),
        )
    else:
        lowered = programs.prefill_suffix_fn.lower(
            params, (kv,), (kv,), state, i32(1, 2048), (i32(table),), i32(), i32(), i32(),
        )
    # Handed to Mosaic: the paged kernel a full layer, and in the decode
    # program the delta rule's update once for its twelve layers.
    update = program == "decode"
    assert lowered.as_text().count("tpu_custom_call") == 4 + update
    compiled = lowered.compile()
    memory = compiled.memory_analysis()
    pools = 2 * kv.size * 2 + sum(
        x.size * x.dtype.itemsize for x in jax.tree_util.tree_leaves(state)
    )
    assert pools == 2 * 4 * blocks * BLOCK * 3840 * 2 + slots * 12 * 2_280_960
    assert memory.alias_size_in_bytes >= pools  # every pool updated in place
    assert memory.temp_size_in_bytes < temp_limit, memory.temp_size_in_bytes
    held = (
        memory.argument_size_in_bytes + memory.temp_size_in_bytes
        + memory.output_size_in_bytes - memory.alias_size_in_bytes
    )
    assert held < held_limit, held
    text = compiled.as_text()
    scopes = hr.scopes_of(text)
    mine = "llm.mixer.gdn.update" if update else "llm.mixer.gdn.scan"
    assert {
        mine, "llm.mixer.gdn.proj", "llm.mixer.attention.full", "llm.mlp"
    } <= set(scopes.values())
    kernels = [
        re.match(r"\s*(?:ROOT )?%?([\w.\-]+) = ", line)[1]
        for line in text.splitlines() if 'custom_call_target="tpu_custom_call"' in line
    ]
    by_scope = [scopes.get(name) for name in kernels]
    assert by_scope.count("llm.mixer.attention.full") == 4
    if not update:
        assert len(kernels) == 4
        return
    # A layer's update is one kernel under the update's scope, and it alone
    # touches the layer's states: they pass from the program's parameters
    # through the kernel to its results, and no fusion reads or writes an
    # array of their shape (a second pass over them, the runner's select).
    assert len(kernels) == 16 and by_scope.count("llm.mixer.gdn.update") == 12
    touching = [
        line for line in text.splitlines()
        if "f32[64,15,96,384]" in line.split(" = ", 1)[-1]
        and not line.startswith(("HloModule", "ENTRY"))
    ]
    assert touching
    passing = re.compile(r" (parameter|get-tuple-element|bitcast|tuple|custom-call)\(")
    off = [line.strip()[:160] for line in touching if not passing.search(line)]
    assert not off, off[:3]
    assert sum(" custom-call(" in line for line in touching) == 12


@pytest.mark.parametrize("program,held_limit,temp_limit", [
    ("decode", 14.7e9, 0.3e9), ("chunk2048", 15.05e9, 0.7e9),
])
def test_falcon_h1s_programs_fit_a_v5e_with_both_memories_in_place(
    chip, monkeypatch, program, held_limit, temp_limit
):
    monkeypatch.setattr(sys.modules["ray_tpu.ops.paged_flash"], "_on_cpu", lambda: False)
    slots, table, blocks, layers = 96, 176, 7424, 6
    cfg = fh.FalconH1Config(num_hidden_layers=layers)

    def sds(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    i32 = lambda *shape: sds(shape, jnp.int32)  # noqa: E731
    params = jax.tree_util.tree_map(
        sds, fh._leaf_shapes(cfg), is_leaf=lambda v: isinstance(v, tuple)
    )
    assert 2 * sum(x.size for x in jax.tree_util.tree_leaves(params)) == 10_509_188_224
    kv = sds((layers, blocks, BLOCK, 4 * 128))
    state = tuple(
        tuple(sds((slots, *shape), dtype) for _ in range(count))
        for _, count, (_, shape, dtype) in hr.state_layout(cfg)
    )
    assert [(len(pools), pools[0].shape) for pools in state] == [
        (6, (96, 3 * 5120)), (6, (96, 32, 128, 256)),
    ]
    programs = hr._HybridPrograms(cfg, BLOCK, "pallas")
    if program == "decode":
        lowered = programs.decode_fn.lower(
            params, (kv,), (kv,), state, i32(slots), i32(slots), (i32(slots, table),),
            i32(slots),
        )
    else:
        lowered = programs.prefill_suffix_fn.lower(
            params, (kv,), (kv,), state, i32(1, 2048), (i32(table),), i32(), i32(), i32(),
        )
    # Handed to Mosaic: the paged kernel, once a layer.
    assert lowered.as_text().count("tpu_custom_call") == layers
    compiled = lowered.compile()
    memory = compiled.memory_analysis()
    pools = 2 * kv.size * 2 + sum(
        x.size * x.dtype.itemsize for x in jax.tree_util.tree_leaves(state)
    )
    assert pools == 2 * layers * blocks * BLOCK * 512 * 2 + slots * 25_350_144
    assert memory.alias_size_in_bytes >= pools  # every pool updated in place
    assert memory.temp_size_in_bytes < temp_limit, memory.temp_size_in_bytes
    held = (
        memory.argument_size_in_bytes + memory.temp_size_in_bytes
        + memory.output_size_in_bytes - memory.alias_size_in_bytes
    )
    assert held < held_limit, held
    text = compiled.as_text()
    scopes = hr.scopes_of(text)
    mine = "llm.mixer.mamba.update" if program == "decode" else "llm.mixer.mamba.scan"
    assert {
        mine, "llm.mixer.mamba.proj", "llm.mixer.attention.proj",
        "llm.mixer.attention.full", "llm.mlp", "llm.head",
    } <= set(scopes.values())
    kernels = [
        re.match(r"\s*(?:ROOT )?%?([\w.\-]+) = ", line)[1]
        for line in text.splitlines() if 'custom_call_target="tpu_custom_call"' in line
    ]
    assert [scopes.get(name) for name in kernels] == ["llm.mixer.attention.full"] * layers
