"""Solar-Open2's decode program and widest chunk at the widths its cell runs
(`benchmark/configs/solar-open2-250b-1of8.json`: layers 0-3, 40 of 320
experts held, an eighth of the vocabulary, 64 lanes, blocks of 16, tables of
1,056), compiled for a described TPU v5e without one (the style of
tests/test_hybrid_compile.py): parameters as shapes only, nothing runs. What
it proves: Mosaic takes the KDA update kernel at a lane's [64, 128, 128]
float32 state and the paged kernel at 8 query heads a cached head of 128,
the per-channel chunked scan lowers as plain XLA with temporaries that leave
room beside the pools, every pool is updated in place, and each new scope
names its operations.
"""

import gc
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else libtpu logs to /tmp

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from ray_tpu.llm import hybrid_runner as hr
from ray_tpu.models import solar_open2 as so
from ray_tpu.ops import grouped_experts, kda

BLOCK, SLOTS, TABLE, BLOCKS = 16, 64, 1056, 67584


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


@pytest.mark.parametrize("program,held_limit,temp_limit", [
    ("decode", 12.6e9, 0.6e9), ("chunk2048", 14.0e9, 2.0e9),
])
def test_solar_open2s_programs_fit_a_v5e_with_the_state_in_place(
    chip, monkeypatch, program, held_limit, temp_limit
):
    monkeypatch.setattr(sys.modules["ray_tpu.ops.paged_flash"], "_on_cpu", lambda: False)
    monkeypatch.setattr(grouped_experts, "_on_cpu", lambda: False)
    monkeypatch.setattr(kda, "_on_cpu", lambda: False)
    cfg = so.SolarOpen2Config(
        vocab_size=24576, num_hidden_layers=4, gqa_layers=(0,),
        experts_held=tuple(range(40)),
    )

    def sds(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    i32 = lambda *shape: sds(shape, jnp.int32)  # noqa: E731
    params = jax.tree_util.tree_map(
        sds, so._leaf_shapes(cfg), is_leaf=lambda v: isinstance(v, tuple)
    )
    assert sum(x.size for x in jax.tree_util.tree_leaves(params)) == 3_308_377_920
    kv = sds((1, BLOCKS, BLOCK, 8 * 128))
    state = tuple(
        tuple(sds((SLOTS, *shape), dtype) for _ in range(layers))
        for _, layers, (_, shape, dtype) in hr.state_layout(cfg)
    )
    assert [pools[0].shape for pools in state] == [(64, 3 * 24576), (64, 64, 128, 128)]
    programs = hr._HybridPrograms(cfg, BLOCK, "pallas")
    if program == "decode":
        lowered = programs.decode_fn.lower(
            params, (kv,), (kv,), state, i32(SLOTS + len(hr.DECODE_COUNTS)), i32(SLOTS),
            (i32(SLOTS, TABLE),), i32(SLOTS),
        )
    else:
        lowered = programs.prefill_suffix_fn.lower(
            params, (kv,), (kv,), state, i32(1, 2048), (i32(TABLE),), i32(), i32(), i32(),
        )
    compiled = lowered.compile()
    memory = compiled.memory_analysis()
    pools = 2 * kv.size * 2 + sum(
        x.size * x.dtype.itemsize for x in jax.tree_util.tree_leaves(state)
    )
    assert pools == 2 * BLOCKS * BLOCK * 1024 * 2 + SLOTS * 3 * (4_194_304 + 147_456)
    assert memory.alias_size_in_bytes >= pools  # every pool updated in place
    held = (
        memory.argument_size_in_bytes + memory.temp_size_in_bytes
        + memory.output_size_in_bytes - memory.alias_size_in_bytes
    )
    assert memory.temp_size_in_bytes < temp_limit, memory.temp_size_in_bytes
    assert held < held_limit, held
    text = compiled.as_text()
    scopes = set(hr.scopes_of(text).values())
    mine = "llm.mixer.kda.update" if program == "decode" else "llm.mixer.kda.scan"
    assert {
        mine, "llm.mixer.kda.proj", "llm.mixer.attention.full", "llm.moe.routed",
        "llm.moe.router", "llm.moe.shared", "llm.head",
    } <= scopes
    if program == "decode":
        # The paged kernel of the one attention layer, and the update's
        # kernel lowered once for its three layers.
        assert lowered.as_text().count("tpu_custom_call") == 2
