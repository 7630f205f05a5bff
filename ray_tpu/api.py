"""Public core API (reference: python/ray/_private/worker.py — init :1186,
remote :3016, get :2506, put :2621, wait :2684, kill :2840, cancel :2870,
get_actor :2805)."""

from __future__ import annotations

import glob
import os
from typing import Any, Iterable, Optional, Sequence, Union

from ray_tpu._private import runtime as runtime_mod
from ray_tpu._private.engine import CONTEXT
from ray_tpu._private.jax_setup import cpu_requested
from ray_tpu._private.object_ref import ObjectRef
from ray_tpu._private.runtime import Runtime, get_runtime
from ray_tpu.actor import ActorClass, ActorHandle
from ray_tpu.remote_function import RemoteFunction


def _detect_num_tpu_chips() -> int:
    """Count local TPU chips without initializing JAX.

    Mirrors the accelerator-detection idea of the reference's resource probe
    (the reference counts GPUs for the `GPU` resource); TPU chips appear as
    /dev/accel* or, behind VFIO (the v5e machines), as numbered groups
    /dev/vfio/<n>. Explicit `num_tpus` or the RAY_TPU_CHIPS env var always
    wins.
    """
    env = os.environ.get("RAY_TPU_CHIPS")
    if env is not None:
        return int(env)
    if cpu_requested():
        return 0
    chips = len(glob.glob("/dev/accel*")) or len(glob.glob("/dev/vfio/[0-9]*"))
    if chips:
        return chips
    # jax already imported and initialized? use it (cheap, no side effects).
    import sys

    jax = sys.modules.get("jax")
    if jax is not None:
        try:
            return sum(1 for d in jax.devices() if d.platform != "cpu")
        except Exception:
            return 0
    return 0


def init(
    *,
    address: Optional[str] = None,
    num_cpus: Optional[float] = None,
    num_tpus: Optional[float] = None,
    num_gpus: Optional[float] = None,
    resources: Optional[dict[str, float]] = None,
    namespace: str = "default",
    ignore_reinit_error: bool = False,
    client_server_port: Optional[int] = None,
    _system_config: Optional[dict] = None,
) -> Runtime:
    """Start the runtime with one (head) node, or connect to a remote one.

    `address="host:port"` connects this process as a remote driver to a head
    started with `client_server_port=...` (the ray-client analog,
    reference: python/ray/util/client/) — the returned proxy serves the full
    task/actor/object API over the wire protocol.

    Unlike the reference the local case never spawns daemons — the control
    plane is in-process. Multi-node tests use ray_tpu.cluster_utils.Cluster
    to add logical nodes.
    """
    if runtime_mod._RUNTIME is not None:
        if ignore_reinit_error:
            return runtime_mod._RUNTIME
        raise RuntimeError("ray_tpu.init() called twice; pass ignore_reinit_error=True")
    if address is not None:
        ignored = {
            "num_cpus": num_cpus,
            "num_tpus": num_tpus,
            "num_gpus": num_gpus,
            "resources": resources,
            "client_server_port": client_server_port,
            "_system_config": _system_config,
        }
        bad = [k for k, v in ignored.items() if v is not None]
        if bad:
            raise ValueError(
                f"init(address=...) connects to an existing head; {bad} "
                "only apply when starting a local runtime"
            )
        from ray_tpu._private.client import connect

        proxy = connect(address, namespace=namespace)
        runtime_mod._RUNTIME = proxy
        return proxy
    node_resources = dict(resources or {})
    node_resources["CPU"] = float(num_cpus if num_cpus is not None else (os.cpu_count() or 1))
    tpus = float(num_tpus if num_tpus is not None else _detect_num_tpu_chips())
    if tpus:
        node_resources["TPU"] = tpus
    if num_gpus:
        node_resources["GPU"] = float(num_gpus)
    runtime = Runtime(
        resources=node_resources, system_config=_system_config, namespace=namespace
    )
    if client_server_port is not None:
        connect_address = runtime.serve_clients(port=client_server_port)
        # Surface the credentialed connect string — the auto-generated auth
        # token lives only in this address (or RAY_TPU_CLIENT_TOKEN on both
        # sides), so remote drivers have no other way to obtain it.
        print(f"ray_tpu client server listening; connect with "
              f'ray_tpu.init(address="{connect_address}")')
    return runtime


def is_initialized() -> bool:
    return runtime_mod._RUNTIME is not None


def shutdown() -> None:
    if runtime_mod._RUNTIME is not None:
        runtime_mod._RUNTIME.shutdown()


def remote(*args, **kwargs):
    """@remote decorator for functions and classes (worker.py:3016)."""

    def make(target, options):
        if isinstance(target, type):
            return ActorClass(target, options)
        if callable(target):
            return RemoteFunction(target, options)
        raise TypeError(f"@remote target must be a function or class, got {target!r}")

    if len(args) == 1 and not kwargs and (callable(args[0]) or isinstance(args[0], type)):
        return make(args[0], {})
    if args:
        raise TypeError("@remote only takes keyword options, e.g. @remote(num_cpus=2)")

    def decorator(target):
        return make(target, kwargs)

    return decorator


def put(value: Any) -> ObjectRef:
    return get_runtime().put(value)


def get(
    refs: Union[ObjectRef, Sequence[ObjectRef]],
    *,
    timeout: Optional[float] = None,
):
    runtime = get_runtime()
    if isinstance(refs, ObjectRef):
        return runtime.get([refs], timeout)[0]
    if isinstance(refs, (list, tuple)):
        bad = [r for r in refs if not isinstance(r, ObjectRef)]
        if bad:
            raise TypeError(f"get() expects ObjectRefs, got {type(bad[0]).__name__}")
        return runtime.get(list(refs), timeout)
    raise TypeError(f"get() expects an ObjectRef or list, got {type(refs).__name__}")


def wait(
    refs: Sequence[ObjectRef],
    *,
    num_returns: int = 1,
    timeout: Optional[float] = None,
    fetch_local: bool = True,
) -> tuple[list[ObjectRef], list[ObjectRef]]:
    if isinstance(refs, ObjectRef):
        raise TypeError("wait() expects a list of ObjectRefs")
    refs = list(refs)
    if len(set(refs)) != len(refs):
        raise ValueError("wait() got duplicate ObjectRefs")
    if num_returns > len(refs):
        raise ValueError("num_returns cannot exceed the number of refs")
    return get_runtime().wait(refs, num_returns, timeout)


def kill(actor: ActorHandle, *, no_restart: bool = True) -> None:
    if not isinstance(actor, ActorHandle):
        raise TypeError("kill() expects an ActorHandle; use cancel() for tasks")
    get_runtime().kill_actor(actor._actor_id, no_restart=no_restart)


def cancel(ref: ObjectRef, *, force: bool = False, recursive: bool = True) -> None:
    get_runtime().cancel(ref, force=force, recursive=recursive)


def get_actor(name: str, namespace: Optional[str] = None) -> ActorHandle:
    runtime = get_runtime()
    actor_id = runtime.controller.get_named_actor(name, namespace or runtime.namespace)
    if actor_id is None:
        raise ValueError(f"Failed to look up actor with name {name!r}")
    record = runtime.controller.get_actor_record(actor_id)
    return ActorHandle(actor_id, record.class_name if record else "Actor")


class RuntimeContext:
    """reference: ray.runtime_context.RuntimeContext."""

    def __init__(self, runtime: Runtime):
        self._runtime = runtime

    def get_job_id(self) -> str:
        return self._runtime.job_id.hex()

    def get_task_id(self) -> Optional[str]:
        task_id = CONTEXT.task_id
        return task_id.hex() if task_id else None

    def get_actor_id(self) -> Optional[str]:
        actor_id = CONTEXT.actor_id
        return actor_id.hex() if actor_id else None

    def get_node_id(self) -> Optional[str]:
        node_id = CONTEXT.node_id or self._runtime.controller.head_node_id
        return node_id.hex() if node_id else None

    def get_assigned_resources(self) -> dict[str, float]:
        return dict(CONTEXT.resource_grant)


def get_runtime_context() -> RuntimeContext:
    return RuntimeContext(get_runtime())


def get_tpu_ids() -> list[int]:
    """Chip indices granted to the current task/actor (the TPU analog of
    ray.get_gpu_ids, _private/worker.py:916)."""
    grant = CONTEXT.resource_grant
    count = int(grant.get("TPU", 0)) if grant else 0
    for name in grant or {}:
        if name.startswith("TPU_group_"):
            count = max(count, int(grant[name]))
    return list(range(count))


def nodes() -> list[dict]:
    runtime = get_runtime()
    return [
        {
            "NodeID": n.node_id.hex(),
            "Alive": n.alive,
            "Resources": dict(n.total),
            "Available": dict(n.available),
            "Labels": dict(n.labels),
        }
        for n in runtime.controller.alive_nodes()
    ]


def cluster_resources() -> dict[str, float]:
    totals: dict[str, float] = {}
    for node in get_runtime().controller.alive_nodes():
        for name, amount in node.total.items():
            totals[name] = totals.get(name, 0.0) + amount
    return totals


def available_resources() -> dict[str, float]:
    totals: dict[str, float] = {}
    for node in get_runtime().controller.alive_nodes():
        for name, amount in node.available.items():
            totals[name] = totals.get(name, 0.0) + amount
    return totals


def timeline(
    filename: Optional[str] = None, trace_id: Optional[str] = None
):
    """Chrome-trace timeline of task executions AND buffered tracing spans
    (reference: ray.timeline, _private/state.py:831 backed by GCS profile
    events; here backed by the runtime's task-event buffer plus the span
    buffer, so `llm.*` serving and `train.*` training spans appear on the
    same timeline as their tasks). Returns the trace records, and writes
    them as JSON when `filename` is given — load in chrome://tracing or
    Perfetto.

    With `trace_id`, exports ONE request's connected timeline instead:
    a Perfetto trace object with per-actor process rows (handle →
    router → ingress → engine) and flow events stitching the
    cross-actor span ids (observability.perfetto)."""
    runtime = get_runtime()
    if trace_id is not None:
        from ray_tpu.observability.perfetto import (
            perfetto_trace,
            write_perfetto_trace,
        )

        if filename:
            return write_perfetto_trace(
                filename, trace_id=trace_id, runtime=runtime
            )
        return perfetto_trace(trace_id=trace_id, runtime=runtime)
    from ray_tpu.util import tracing

    events = runtime.task_events.chrome_trace() + tracing.chrome_spans(runtime)
    if filename:
        import json

        with open(filename, "w") as f:
            json.dump(events, f)
    return events
