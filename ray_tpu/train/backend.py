"""Training backends — per-framework worker-group setup.

Reference: the Backend plugin protocol (train/_internal/backend_executor.py
drives Backend.on_start/on_shutdown; torch impl at train/torch/config.py:155).
The TPU re-design replaces "start a torch.distributed process group over NCCL"
with "form the device mesh + host collective group" (SURVEY.md §2.5: mesh
formation IS the framework's job; gradient collectives are XLA's).
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Optional


@dataclass
class BackendConfig:
    @property
    def backend_cls(self):
        return Backend


class Backend:
    """Hooks called by the BackendExecutor around the worker group."""

    def on_start(self, worker_group, backend_config: "BackendConfig") -> None:
        pass

    def on_training_start(self, worker_group, backend_config: "BackendConfig") -> None:
        pass

    def on_shutdown(self, worker_group, backend_config: "BackendConfig") -> None:
        pass


# ---------------------------------------------------------------------------
# JAX backend
# ---------------------------------------------------------------------------


@dataclass
class JaxBackendConfig(BackendConfig):
    """Mesh-forming backend config.

    mesh_strategy/axes: how to arrange this trainer's chips
    (ray_tpu.parallel.auto_mesh strategies, or explicit MeshSpec).
    coordinator_port: jax.distributed rendezvous port for real multi-host pods.
    """

    mesh_spec: Optional[Any] = None  # parallel.MeshSpec
    mesh_strategy: str = "dp"
    collective_group: str = "train"
    multihost: bool = False
    coordinator_port: int = 8476

    @property
    def backend_cls(self):
        return JaxBackend


def _form_mesh(context, config: JaxBackendConfig, num_workers: int):
    """Runs ON each worker: initialize distributed jax (multi-host), build the
    mesh over the worker's visible devices, and join the host collective group.

    Single-controller-per-host model (SURVEY.md CS4): world_size == number of
    hosts; each worker drives all chips jax exposes to its process. In the
    in-process test runtime all workers share one jax client, so the mesh spans
    the same devices in every worker — exactly what a real pod's global SPMD
    mesh looks like from each host.
    """
    import jax

    from ray_tpu._private.jax_setup import ensure_compile_cache
    from ray_tpu.parallel import MeshSpec, auto_mesh
    from ray_tpu.util import collective

    ensure_compile_cache()
    if config.multihost and num_workers > 1:
        from ray_tpu.parallel.mesh import initialize_multi_host

        # Rank 0's host address is published via the named collective actor in
        # a real deployment; in-process this is a no-op path.
        initialize_multi_host(
            coordinator_address=f"localhost:{config.coordinator_port}",
            num_processes=num_workers,
            process_id=context.world_rank,
        )
    # Membership is stashed on the worker context: the train loop runs on a
    # different thread (the runner), which resolves groups via its session.
    state = collective.create_group_state(
        world_size=num_workers,
        rank=context.world_rank,
        group_name=config.collective_group,
    )
    context.extras.setdefault("collective_groups", {})[config.collective_group] = state
    devices = jax.devices()
    spec = config.mesh_spec or auto_mesh(len(devices), strategy=config.mesh_strategy)
    context.devices = devices
    context.mesh = spec.build(devices)
    return len(devices)


class JaxBackend(Backend):
    def on_start(self, worker_group, backend_config: JaxBackendConfig) -> None:
        worker_group.execute(
            _form_mesh, backend_config, worker_group.num_workers
        )

    def on_shutdown(self, worker_group, backend_config: JaxBackendConfig) -> None:
        def _leave(context):
            import ray_tpu

            state = context.extras.get("collective_groups", {}).pop(
                backend_config.collective_group, None
            )
            # Rank 0 kills the rendezvous actor so the next trainer can form a
            # group of a different size under the same name.
            if state is not None and context.world_rank == 0:
                try:
                    ray_tpu.kill(state.handle)
                except Exception:
                    pass

        try:
            worker_group.execute(_leave)
        except Exception:
            pass
