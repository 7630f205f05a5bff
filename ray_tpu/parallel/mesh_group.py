"""Multi-host mesh formation: one real OS process per TPU host.

The bridge from dynamic task scheduling to static SPMD (SURVEY.md §7 hard
part 2, §2.5): XLA wants every host of a slice to run the same program with a
coordinated `jax.distributed.initialize`; the reference reaches multi-host
through torch.distributed process groups formed inside Train worker actors
(train/torch/config.py:69 _setup_torch_process_group). Here the analog is a
group of PROCESS-ISOLATED actors — each owns a fresh interpreter, sets its
XLA platform/flags before first jax import, joins the distributed runtime,
and then executes arbitrary SPMD functions against the GLOBAL mesh.

On test hardware (no pod), `jax_platform="cpu"` (the default) with
`local_device_count=K` forms a genuine multi-process K*num_hosts-device mesh
with gloo-backed cross-process collectives — the same code path a v5e pod
takes over ICI/DCN with `jax_platform="tpu"` on real hosts. The platform is
a request, not a hint: a host process that comes up on another platform
refuses to join, so a group asked for chips never forms on CPUs.
"""

from __future__ import annotations

import re
import socket
from typing import Any, Callable, Optional, Sequence


class MeshHostWorker:
    """Actor hosted in its own process: one per TPU host of the slice."""

    def __init__(
        self,
        process_id: int,
        num_processes: int,
        coordinator_address: str,
        local_device_count: Optional[int] = None,
        jax_platform: Optional[str] = "cpu",
    ):
        import os

        # Platform/flags MUST land before the first jax import in this
        # process (the whole reason these workers are process-isolated).
        if jax_platform:
            os.environ["JAX_PLATFORMS"] = jax_platform
        if local_device_count:
            flags = re.sub(
                r"--xla_force_host_platform_device_count=\d+",
                "",
                os.environ.get("XLA_FLAGS", ""),
            )
            os.environ["XLA_FLAGS"] = (
                flags + f" --xla_force_host_platform_device_count={local_device_count}"
            ).strip()
        import jax

        if jax_platform:
            jax.config.update("jax_platforms", jax_platform)
        jax.distributed.initialize(
            coordinator_address=coordinator_address,
            num_processes=num_processes,
            process_id=process_id,
        )
        actual = jax.local_devices()[0].platform
        if jax_platform and actual != jax_platform:
            raise RuntimeError(
                f"mesh host {process_id} was asked for jax platform "
                f"{jax_platform!r} but its devices are {actual!r}"
            )
        self.process_id = process_id

    def device_counts(self) -> tuple[int, int]:
        import jax

        return jax.device_count(), jax.local_device_count()

    def run(self, fn: Callable, *args, **kwargs) -> Any:
        """Execute fn in this host process (fn sees the global mesh via
        jax.devices(); every host must run the same SPMD program)."""
        return fn(*args, **kwargs)

    def build_mesh_and_run(
        self, axis_shape: Sequence[int], axis_names: Sequence[str], fn: Callable,
        *args, **kwargs
    ) -> Any:
        """Convenience: build a Mesh over the GLOBAL device list and pass it
        to fn as the first argument."""
        import numpy as np
        import jax
        from jax.sharding import Mesh

        devices = np.array(jax.devices()).reshape(tuple(axis_shape))
        mesh = Mesh(devices, tuple(axis_names))
        return fn(mesh, *args, **kwargs)


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


class MeshWorkerGroup:
    """N process-isolated actors forming one jax.distributed world.

    Usage::

        group = MeshWorkerGroup(num_hosts=2, local_device_count=4)
        group.start()                      # blocks until the world is formed
        results = group.run(spmd_fn, x)    # one result per host
        group.shutdown()
    """

    def __init__(
        self,
        num_hosts: int,
        local_device_count: Optional[int] = None,
        jax_platform: Optional[str] = "cpu",
        coordinator_address: Optional[str] = None,
        placement_group=None,
    ):
        self.num_hosts = num_hosts
        self.local_device_count = local_device_count
        self.jax_platform = jax_platform
        self.coordinator_address = coordinator_address or f"127.0.0.1:{_free_port()}"
        self._placement_group = placement_group
        self.workers: list = []

    def start(self, timeout: float = 120.0) -> "MeshWorkerGroup":
        import ray_tpu
        from ray_tpu.util.scheduling_strategies import (
            PlacementGroupSchedulingStrategy,
        )

        actor_cls = ray_tpu.remote(MeshHostWorker)
        options: dict = {"isolation": "process", "num_cpus": 0}
        for i in range(self.num_hosts):
            if self._placement_group is not None:
                options["scheduling_strategy"] = PlacementGroupSchedulingStrategy(
                    placement_group=self._placement_group,
                    placement_group_bundle_index=i,
                )
            self.workers.append(
                actor_cls.options(**options).remote(
                    process_id=i,
                    num_processes=self.num_hosts,
                    coordinator_address=self.coordinator_address,
                    local_device_count=self.local_device_count,
                    jax_platform=self.jax_platform,
                )
            )
        # Barrier: every host reports the same global device count.
        counts = ray_tpu.get(
            [w.device_counts.remote() for w in self.workers], timeout=timeout
        )
        globals_ = {c[0] for c in counts}
        if len(globals_) != 1:
            raise RuntimeError(f"inconsistent global device counts: {counts}")
        self.global_device_count = counts[0][0]
        self.local_device_counts = [c[1] for c in counts]
        return self

    def run(self, fn: Callable, *args, timeout: Optional[float] = None, **kwargs):
        """Run the same SPMD fn on every host; returns one result per host."""
        import ray_tpu

        return ray_tpu.get(
            [w.run.remote(fn, *args, **kwargs) for w in self.workers],
            timeout=timeout,
        )

    def run_with_mesh(
        self,
        axis_shape: Sequence[int],
        axis_names: Sequence[str],
        fn: Callable,
        *args,
        timeout: Optional[float] = None,
        **kwargs,
    ):
        import ray_tpu

        return ray_tpu.get(
            [
                w.build_mesh_and_run.remote(
                    tuple(axis_shape), tuple(axis_names), fn, *args, **kwargs
                )
                for w in self.workers
            ],
            timeout=timeout,
        )

    def shutdown(self) -> None:
        import ray_tpu

        for w in self.workers:
            try:
                ray_tpu.kill(w)
            except Exception:
                pass
        self.workers = []
