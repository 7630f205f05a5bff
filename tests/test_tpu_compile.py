"""The main paths' kernels, compiled for a described TPU v5e without one.

Interpret mode (every other kernel test) runs the kernel's arithmetic but
not Mosaic: a vector layout, a block shape or a VMEM budget the chip's
compiler refuses passes there and fails at the first warmup on the chip
(PR 21: every S > 1 shape of the paged kernel). These compile the real
thing at gpt2_760m / gpt2_125m widths. Nothing runs, so they say nothing
about results — the interpret-mode tests and chip_smoke.py do.
"""

import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else libtpu logs to /tmp

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

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


def _paged(batch, fed, heads, pool_dtype=jnp.bfloat16):
    """(fn, shapes) for the paged kernel over the smoke's geometry: 1,024
    blocks of 16 tokens, 64-block tables, heads of 64."""

    def fn(q, k_cache, v_cache, tables, lens, new_k, new_v, k_scale, v_scale):
        return paged_flash_attention(
            q, k_cache, v_cache, tables, lens, new_k=new_k, new_v=new_v,
            k_scale=k_scale, v_scale=v_scale, interpret=False,
        )

    q = ((batch, fed, heads, 64), jnp.bfloat16)
    pool = ((1024, 16, heads, 64), pool_dtype)
    scale = ((1024, 16, heads), KV_SCALE_DTYPE) if pool_dtype == jnp.int8 else None
    return fn, [
        q, pool, pool, ((batch, 64), jnp.int32), ((batch,), jnp.int32),
        q, q, scale, scale,
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
    "paged_prefill_smallest_bucket": lambda: _paged(1, 16, 20),
    "paged_prefill_largest_bucket": lambda: _paged(1, 256, 20),
    "flash_fwd_bwd_gpt2_125m": _flash_train,
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
