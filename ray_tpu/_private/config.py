"""Flag system — single source of truth for runtime tunables.

Mirrors the reference's `RAY_CONFIG(type, name, default)` registry
(src/ray/common/ray_config_def.h) including env-var override: every flag can be
overridden with env `RAY_TPU_<NAME>`, and `init(_system_config={...})` overrides
both.
"""

from __future__ import annotations

import os
from dataclasses import dataclass, fields


def _env_override(name: str, default):
    raw = os.environ.get(f"RAY_TPU_{name.upper()}")
    if raw is None:
        return default
    if isinstance(default, bool):
        return raw.lower() in ("1", "true", "yes")
    if isinstance(default, int):
        return int(raw)
    if isinstance(default, float):
        return float(raw)
    return raw


@dataclass
class Config:
    # Scheduling (reference: hybrid policy, ray_config_def.h:193 spread threshold)
    scheduler_spread_threshold: float = 0.5
    scheduler_top_k_fraction: float = 0.2
    max_pending_lease_requests: int = 10
    # Objects: args larger than this are implicitly put in the store rather than
    # inlined in the task spec (ray_config_def.h:213 max_direct_call_object_size).
    max_direct_call_object_size: int = 100 * 1024
    # Memory cap for the host object store (0 = derive from system memory; the
    # reference defaults to 30% of RAM with a 200GB cap, ray_constants.py:51-53).
    object_store_memory: int = 0
    object_store_memory_fraction: float = 0.3
    object_store_memory_cap: int = 200 * 1024**3
    # Fault tolerance
    task_max_retries: int = 3
    actor_max_restarts: int = 0
    # Active worker-process health probing (ping/pong over the wire): a
    # worker that fails to pong within period*threshold is declared hung and
    # killed, driving the normal crash/restart path (reference:
    # gcs_health_check_manager.h:39, flags ray_config_def.h:784-790).
    # Default deadline = 3s * 10 = 30s of silence: generous enough that a
    # long GIL-holding native call (giant pickle, XLA compile) is not
    # misdiagnosed as a hang.
    health_check_period_s: float = 3.0
    health_check_failure_threshold: int = 10
    # Host-memory monitor (reference: common/memory_monitor.h:52 + the
    # retriable-FIFO worker-killing policy): above the usage threshold,
    # dispatch is backpressured and one process-backed worker is killed per
    # tick with an OOM error (its task retries). 0 disables.
    memory_usage_threshold: float = 0.95
    memory_monitor_refresh_s: float = 1.0
    # Minimum gap between OOM kills: after sacrificing a worker the monitor
    # waits this many refresh periods for the reclaimed memory to show up in
    # /proc before picking another victim (the reference spaces kills the
    # same way so one pressure spike doesn't massacre the pool).
    memory_monitor_kill_cooldown_ticks: int = 5
    # Control-plane persistence: when set, KV/job-counter/detached-actor/PG
    # tables are snapshotted here and restored by the next session
    # (reference: gcs_table_storage.h + the Redis `gcs_storage` backend).
    gcs_storage_path: str = ""
    # After restoring a snapshot, infeasible restored actors/PGs PARK this
    # many seconds (daemons re-registering after a head restart) before the
    # scheduler reverts to failing them fast.
    head_restart_grace_s: float = 60.0
    # Copy (serialize/deserialize) task args even in the in-process engine so
    # mutation bugs surface in tests; direct zero-copy handoff when False.
    inproc_copy_args: bool = False
    # Store sealed objects as serialized bytes so every `get` returns a fresh
    # copy (the reference's immutability contract). False = zero-copy sharing
    # between thread-workers (fast, but mutations alias).
    serialize_objects: bool = True
    # Native shared-memory store (src/store/, plasma equivalent): objects at
    # least this large go to shm; 0 disables. Requires the C++ lib to build.
    native_store_threshold: int = 512 * 1024
    native_store_enabled: bool = True
    # Object spilling: when the store is over budget and every remaining
    # object is still referenced, primary copies move to disk (reference:
    # raylet local_object_manager + external_storage.py).
    object_spilling_enabled: bool = True
    object_spill_directory: str = ""
    # Worker isolation: "thread" (in-process engine, fast) or "process"
    # (real OS worker processes with serialization + fate-sharing — the
    # reference's execution model; env override RAY_TPU_ISOLATION).
    isolation: str = "thread"
    # JAX platform forced into process-isolated workers ("" = inherit the
    # driver's environment). "cpu" because a chip belongs to one process —
    # the driver; a worker granted TPU chips under it fails its task.
    worker_jax_platform: str = "cpu"
    # Worker pool
    prestart_workers: bool = True
    idle_worker_killing_time_s: float = 60.0
    # Logging
    log_to_driver: bool = True
    # Web dashboard (dashboard/head.py): started by init() when enabled.
    # Port 0 picks an ephemeral port (tests); the reference defaults to 8265.
    include_dashboard: bool = False
    dashboard_host: str = "127.0.0.1"
    dashboard_port: int = 8265

    def __post_init__(self):
        for f in fields(self):
            setattr(self, f.name, _env_override(f.name, getattr(self, f.name)))

    def apply_overrides(self, overrides: dict | None):
        if not overrides:
            return self
        valid = {f.name for f in fields(self)}
        for key, value in overrides.items():
            if key not in valid:
                raise ValueError(f"Unknown _system_config key: {key!r}")
            setattr(self, key, value)
        return self


GLOBAL_CONFIG = Config()
