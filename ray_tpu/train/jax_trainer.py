"""JaxTrainer + in-loop helpers — the TPU-native Train path.

The BASELINE.json north-star surface: `JaxTrainer` is the `TorchTrainer`
equivalent whose workers drive TPU chips and whose gradient sync is XLA
(`lax.psum` over ICI) instead of NCCL DDP. One worker per TPU host
(single-controller-per-host); the backend forms the mesh before the user loop
starts (reference flow: CS4 in SURVEY.md).

In-loop helpers (the `prepare_model`/`prepare_data_loader` analogs,
train/torch/train_loop_utils.py:245,329): `prepare_params` shards a param tree
onto the mesh, `prepare_batch` shards inputs over the data axes, `prepare_step`
jits the step with donated params.
"""

from __future__ import annotations

from typing import Any, Callable, Optional

import ray_tpu
from ray_tpu.air.config import ScalingConfig
from ray_tpu.air import session
from ray_tpu.train.backend import JaxBackendConfig
from ray_tpu.train.data_parallel_trainer import DataParallelTrainer


class JaxTrainer(DataParallelTrainer):
    _default_backend_config = JaxBackendConfig()

    def __init__(self, train_loop_per_worker: Callable, **kwargs):
        kwargs.setdefault("backend_config", JaxBackendConfig())
        super().__init__(train_loop_per_worker, **kwargs)


# -- in-loop helpers ---------------------------------------------------------


def prepare_params(params: Any, rules: Optional[dict] = None) -> Any:
    """Shard a parameter pytree onto the session mesh (FSDP heuristic when the
    tree carries no logical-axis metadata)."""
    import jax

    from ray_tpu.parallel import FSDP_RULES, infer_param_sharding

    mesh = session.get_mesh()
    shardings = infer_param_sharding(mesh, params, rules or FSDP_RULES)
    return jax.device_put(params, shardings)


def prepare_batch(batch: Any) -> Any:
    """Shard a batch pytree over the mesh's data axes. Under an
    instrumented session the host→device put counts as `data_wait` (it is
    the step's wait-for-input tail), and batches feed the samples/sec
    clock unless a profiled dataset iterator is already counting them."""
    import jax

    from ray_tpu.parallel import batch_sharding
    from ray_tpu.train.observability import batch_rows, current_profiler

    mesh = session.get_mesh()
    sharding = batch_sharding(mesh)
    profiler = current_profiler()
    if profiler is None:
        return jax.tree_util.tree_map(
            lambda x: jax.device_put(x, sharding), batch
        )
    with profiler.phase("data_wait"):
        out = jax.tree_util.tree_map(
            lambda x: jax.device_put(x, sharding), batch
        )
    if not profiler.has_data_sources():
        profiler.add_samples(batch_rows(batch))
    return out


def _committed_to_mesh(tree: Any) -> Any:
    """`tree` with the array leaves no one placed (a `jax.jit(tx.init)`'s
    optimizer state lands uncommitted on the default device) committed to the
    session mesh, replicated: where `prepare_params` puts a parameter no
    rule shards, and where a step puts what it returns. The same device, so
    nothing is copied on a one-device mesh."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec

    replicated = NamedSharding(session.get_mesh(), PartitionSpec())

    def commit(leaf):
        if isinstance(leaf, jax.Array) and not leaf.committed:
            return jax.device_put(leaf, replicated)
        return leaf

    return jax.tree_util.tree_map(commit, tree)


def prepare_step(step_fn: Callable, donate_argnums=(0,)) -> Callable:
    """jit the train step; shardings propagate from the (already-sharded)
    inputs, XLA inserts the gradient collectives. Under an instrumented
    session each call is timed into the `compute` phase and bounded by
    block_until_ready — otherwise async dispatch would bill device time to
    whatever host code touches the result next.

    A step's donated arguments are what it returns, one call later. The
    first call's are placed as those will be (`_committed_to_mesh`), so the
    second call finds the first call's executable: a state that arrives
    uncommitted has another cache key than the committed one the step
    hands back, and the step was traced, lowered and read from the compile
    cache twice at set-up. On a mesh of several devices that covers the
    leaves nobody placed (adam's count); what `jax.jit(tx.init)` placed
    there stays where it is."""
    import jax

    from ray_tpu.train.observability import current_profiler

    jitted = jax.jit(step_fn, donate_argnums=donate_argnums)
    # The session's profiler is fixed for the loop's lifetime, so decide
    # once at prepare time: driver-side callers get the jit callable itself
    # — full jit API (.lower, .clear_cache), zero per-call overhead.
    if session._get_session() is None:
        return jitted
    profiler = current_profiler()
    donated = {donate_argnums} if isinstance(donate_argnums, int) else set(donate_argnums)
    placed = False  # the first call's donated arguments, yet

    def step(*args, **kwargs):
        nonlocal placed
        if not placed:
            placed = True
            args = tuple(
                _committed_to_mesh(arg) if i in donated else arg
                for i, arg in enumerate(args)
            )
        if profiler is None:
            return jitted(*args, **kwargs)
        # `train.compute` is the phase's annotation; the wait inside it
        # tells the dispatch from the blocked host on a device trace.
        with profiler.phase("compute"):
            out = jitted(*args, **kwargs)
            with jax.profiler.TraceAnnotation("train.compute.wait"):
                jax.block_until_ready(out)
        return out

    step.jitted = jitted  # for `step_device_report`, `.lower`, `.clear_cache`
    return step


def step_device_report(step: Callable, *args) -> dict:
    """What a prepared step holds and is made of, the training counterpart
    of the serving runners' `device_report()`: for the callable
    `prepare_step` returned and the arguments a call takes (the parameters
    first; arrays, which are not consumed: the step is lowered and compiled
    again, a read where the compile cache has it, and never run),

      - `op_scopes`: {program name: {HLO instruction: the part of a layer
        it was traced under}} (`ray_tpu.util.device_report.scopes_of`, forward
        and backward instructions alike), which splits a trace's device
        time by part;
      - `param_bytes_by_device`: bytes of the first argument a device;
      - `step_argument_bytes`, `step_temp_bytes`: XLA's account of the
        program's arguments and of its scratch space."""
    import jax

    from ray_tpu.util.device_report import bytes_by_device, scopes_of

    jitted = getattr(step, "jitted", step)
    compiled = jitted.lower(*args).compile()
    memory = compiled.memory_analysis()
    name = "jit_" + getattr(jitted, "__name__", "step")
    return {
        "op_scopes": {name: scopes_of(compiled.as_text())},
        "param_bytes_by_device": bytes_by_device(jax.tree_util.tree_leaves(args[0])),
        "step_argument_bytes": int(memory.argument_size_in_bytes),
        "step_temp_bytes": int(memory.temp_size_in_bytes),
    }


def report_from_rank0(metrics: dict, checkpoint=None) -> None:
    """report() with identical metrics from every rank; checkpoint only from
    rank 0 (the reference persists the master rank's checkpoint)."""
    if session.get_world_rank() == 0:
        session.report(metrics, checkpoint=checkpoint)
    else:
        session.report(metrics)
