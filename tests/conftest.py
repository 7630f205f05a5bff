"""Shared test fixtures.

JAX runs on a virtual 8-device CPU mesh (the reference's fake_multi_node /
cluster_utils testing strategy translated to XLA: SURVEY.md §4 implication) —
set BEFORE jax import so XLA sees the flag.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import pytest

import ray_tpu
from ray_tpu.cluster_utils import Cluster


@pytest.fixture(autouse=True)
def _metrics_registry_isolation():
    """Metric isolation between tests: histogram tag-sets and counter
    values must not bleed from one test's engines/routers into the next
    test's prometheus_text(). Resetting AFTER each test leaves the registry
    empty for the next one; long-lived holders (module-scoped engines)
    re-register lazily on their next write (util.metrics.reset_registry)."""
    yield
    from ray_tpu.util import metrics

    metrics.reset_registry()


@pytest.fixture
def ray_start_regular():
    """Single-node runtime, 4 CPUs (reference: tests/conftest.py:351)."""
    runtime = ray_tpu.init(num_cpus=4)
    yield runtime
    ray_tpu.shutdown()


@pytest.fixture
def ray_start_2_cpus():
    runtime = ray_tpu.init(num_cpus=2)
    yield runtime
    ray_tpu.shutdown()


@pytest.fixture
def ray_start_cluster():
    """Multi-node logical cluster (reference: tests/conftest.py:432)."""
    cluster = Cluster(initialize_head=True, head_node_args={"num_cpus": 2})
    yield cluster
    cluster.shutdown()


@pytest.fixture
def ray_start_tpu_pod():
    """Fake v5e-16 pod: 4 hosts x 4 chips, plus a CPU-only head."""
    cluster = Cluster(initialize_head=True, head_node_args={"num_cpus": 8})
    for host in range(4):
        cluster.add_node(
            num_cpus=8,
            num_tpus=4,
            labels={"tpu-slice": "slice-0", "tpu-host": str(host)},
        )
    yield cluster
    cluster.shutdown()
