"""The device boundary's set-up: where the compile cache goes, which
platform was asked for, how chips are counted (PR 21)."""

import os

import jax
import pytest

from ray_tpu import api
from ray_tpu._private import jax_setup


@pytest.fixture
def cache_config():
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


@pytest.mark.parametrize(
    "placed, backend, expected",
    [
        # Placed from outside: JAX reads the variable, the code sets nothing.
        ("/somewhere/else", "tpu", "/somewhere/else"),
        # Unset on an accelerator: one fixed path inside the checkout.
        (None, "tpu", "<checkout>/.jax_cache"),
        # Unset on the CPU backend (tests, rehearsals): no cache.
        (None, "cpu", None),
    ],
)
def test_compile_cache_placement(
    monkeypatch, cache_config, placed, backend, expected
):
    if placed is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", placed)
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    before = jax.config.jax_compilation_cache_dir
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if expected == "<checkout>/.jax_cache":
        expected = os.path.join(repo, ".jax_cache")
        assert jax_setup.ensure_compile_cache() == expected
        assert jax.config.jax_compilation_cache_dir == expected
    else:
        assert jax_setup.ensure_compile_cache() == expected
        assert jax.config.jax_compilation_cache_dir == before


@pytest.mark.parametrize(
    "nodes, expected",
    [
        ({"/dev/accel*": ["/dev/accel0", "/dev/accel1"]}, 2),
        # Behind VFIO (the v5e machines): numbered groups, not the
        # /dev/vfio/vfio control node.
        ({"/dev/vfio/[0-9]*": ["/dev/vfio/0", "/dev/vfio/3"]}, 2),
    ],
)
def test_tpu_chips_counted_from_device_nodes(monkeypatch, nodes, expected):
    monkeypatch.delenv("RAY_TPU_CHIPS", raising=False)
    monkeypatch.setenv("JAX_PLATFORMS", "tpu,cpu")
    monkeypatch.setattr(api.glob, "glob", lambda pattern: nodes.get(pattern, []))
    assert not jax_setup.cpu_requested()
    assert api._detect_num_tpu_chips() == expected
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert jax_setup.cpu_requested()
    assert api._detect_num_tpu_chips() == 0
