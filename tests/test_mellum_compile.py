"""Mellum2-12B-A2.5B's train step at the widths and the batch its benchmark
cell runs (`benchmark/configs/mellum2-12b-a2.5b-1of4-train.json`: layers
0-3, experts 0-15 of 64, vocabulary rows 0-24,575, 8,192-token sequences,
adamw over float32 masters), compiled for a described TPU v5e without one
(the style of tests/test_tpu_compile.py and tests/test_hybrid_compile.py):
parameters and optimizer state as shapes only, nothing runs. What it
proves: Mosaic takes the flash kernels at 32 query heads over 4 key heads of
128 under a window of 1,024 at 8,192 tokens, forward and backward, and
without the window, and the grouped experts' own kernels (a row by its
group's matrix, a group's rows by its rows, a token's rows summed) at every
rung of the ladder, under `llm.moe.routed`, lowered once a rung and not once
a layer; arguments and temporaries fit the
chip's 16.9 GB at the cell's `sequences_per_step`; and the donated
parameters and optimizer state alias, so no step copies them.
"""

import gc
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else libtpu logs to /tmp

import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from ray_tpu.models import mellum
from ray_tpu.ops import grouped_experts
from ray_tpu.ops.flash_attention import flash_attention
from ray_tpu.util.device_report import scopes_of

SEQUENCES, TOKENS = 2, 8192
CELL = mellum.MellumConfig(
    layer_types=mellum.MELLUM_PERIOD, experts_held=tuple(range(16)),
    vocab_rows=(0, 24576),
)
CHIP_BYTES = 16.9e9


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


@pytest.fixture
def on_tpu(monkeypatch):
    # The flash kernels choose interpret mode from the backend, the CPU here.
    monkeypatch.setattr(sys.modules["ray_tpu.ops.flash_attention"], "_on_cpu", lambda: False)
    monkeypatch.setattr(grouped_experts, "_on_cpu", lambda: False)


@pytest.mark.parametrize("window", [1024, None])
def test_flash_kernels_compile_at_the_cell_shape(chip, on_tpu, window):
    def sds(heads):
        return jax.ShapeDtypeStruct((SEQUENCES, TOKENS, heads, 128), jnp.bfloat16, sharding=chip)

    def both(q, k, v):
        def total(q, k, v):
            out = flash_attention(q, k, v, causal=True, window=window)
            return out.astype(jnp.float32).sum()

        return jax.value_and_grad(total, argnums=(0, 1, 2))(q, k, v)

    text = jax.jit(both).lower(sds(32), sds(4), sds(4)).compile().as_text()
    # forward, dQ, dK/dV
    assert text.count('custom_call_target="tpu_custom_call"') == 3


def test_train_step_fits_a_v5e_and_updates_in_place(chip, on_tpu):
    import optax

    tx = optax.adamw(3e-4)
    params = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=chip),
        mellum.param_shapes(CELL),
    )
    state = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=chip),
        jax.eval_shape(tx.init, params),
    )
    tokens = jax.ShapeDtypeStruct((SEQUENCES, TOKENS), jnp.int32, sharding=chip)
    lowered = jax.jit(
        mellum.train_step(CELL, tx), donate_argnums=(0, 1)
    ).lower(params, state, tokens)
    # Kernel bodies Mosaic is handed: three flash kernels a layer (12: forward,
    # dQ, dK/dV, and the forward one not again for the recomputed layer, which
    # keeps that kernel's output and log-sum-exp), and the grouped experts'
    # once a rung whatever the layers: three forward and five backward at
    # each of the ladder's two rungs, and a forward kernel or two again where
    # a transformation (the recomputed layer) lowers its own copy. A kernel
    # lowered a layer would be 64 more.
    rungs = grouped_experts.ladder(SEQUENCES * TOKENS * 8, 16 / 64)
    assert rungs == (40960, 131072)
    kernels = lowered.as_text().count("tpu_custom_call")
    assert 12 + 8 * len(rungs) <= kernels <= 12 + 11 * len(rungs), kernels
    compiled = lowered.compile()
    memory = compiled.memory_analysis()
    masters = 4 * 595_153_152
    assert memory.alias_size_in_bytes >= 3 * masters  # weights and both moments
    held = (
        memory.argument_size_in_bytes + memory.temp_size_in_bytes
        + memory.output_size_in_bytes - memory.alias_size_in_bytes
    )
    print(f"held {held / 1e9:.2f} GB: arguments {memory.argument_size_in_bytes / 1e9:.2f}, "
          f"temporaries {memory.temp_size_in_bytes / 1e9:.2f}")
    assert held < 0.9 * CHIP_BYTES, held
    text = compiled.as_text()
    # three kernels a layer and again the forward one where a layer is recomputed
    assert text.count('custom_call_target="tpu_custom_call"') >= 12
    assert set(mellum.SCOPES) <= set(scopes_of(text).values())
    # Every instruction of the grouped path, its kernels among them, forward
    # and backward, is timed as the routed experts.
    scopes = scopes_of(text)
    routed = [
        line for line in text.splitlines()
        if re.search(r"jit\((_forward|_backward|rows_by_group|matrices_by_group|summed_by_token)\)", line)
    ]
    named = [re.match(r"\s*(?:ROOT )?%?([\w.\-]+) = ", line) for line in routed]
    assert len(routed) > 200 and all(named)
    off = [m[1] for m in named if scopes.get(m[1]) != "llm.moe.routed"]
    assert not off, off[:5]
    ours = [line for line in routed if 'custom_call_target="tpu_custom_call"' in line]
    assert len(ours) >= 4 * (3 + 3 + 5)  # a layer: forward, recomputed, backward
