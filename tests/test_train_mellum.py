"""A toy `mellum` through `JaxTrainer`, the way its benchmark cell trains it
(`train.prepare_params`, `prepare_batch`, `prepare_step`, `train.report`):
the loss falls, and the routing's counts a report carries arrive in
`train.report`'s history, in the step profiler's round record, in
`TrainRunRecord.report()` and in the `train_*` metric family, with
`held + absent == tokens x choices x layers`, and beside them the sorted
rows the grouped experts walked for the held ones (`walked`). And the
training side's device report: the scope map of the prepared step, backward
instructions under the scope their forward was traced in. And a prepared
step called as the benchmark's runners call it holds one executable: it is
lowered once at set-up, not again when it first meets its own outputs.
"""

import gc

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from mellum_toy import toy_config

from ray_tpu import train
from ray_tpu.models import mellum
from ray_tpu.train import JaxTrainer, ScalingConfig
from ray_tpu.train import observability as tobs
from ray_tpu.util import metrics
from ray_tpu.util.device_report import scopes_of

STEPS, EVERY, BATCH, SEQ = 12, 4, 8, 64  # 8: the test mesh is 8 CPU devices, data-parallel
SCALARS = ("held", "absent", "touched", "load_max", "walked")


@pytest.fixture(scope="module", autouse=True)
def _leave_a_small_heap():
    """What this file traced goes when it is done: the worker that ran it
    runs other files after, and some of them time a full `gc.collect()`."""
    yield
    jax.clear_caches()
    gc.collect()


@pytest.fixture(scope="module")
def fitted():
    """One fit for the whole file; the metric family is read here, before
    the registry's reset after the first test."""
    import ray_tpu

    ray_tpu.init(num_cpus=4)
    tobs.reset_runs()
    cfg = toy_config()
    seen = {}

    def loop(_config):
        rng = np.random.RandomState(0)
        params = train.prepare_params(mellum.init_params(cfg, 0))
        tx = optax.adamw(3e-3)
        opt_state = jax.jit(tx.init)(params)
        step = train.prepare_step(mellum.train_step(cfg, tx), donate_argnums=(0, 1))
        # One batch over and over: a loss that falls is then the step's doing.
        tokens = rng.randint(0, cfg.rows_held, size=(BATCH, SEQ)).astype(np.int32)
        pending = []
        for i in range(STEPS):
            params, opt_state, loss, counts = step(
                params, opt_state, train.prepare_batch(tokens)
            )
            pending.append((loss, counts))
            if len(pending) == EVERY:
                fetched = jax.device_get(pending)
                experts = {k: int(sum(c[k] for _, c in fetched)) for k in SCALARS}
                experts["load"] = np.sum([c["load"] for _, c in fetched], axis=0).tolist()
                train.report({"step": i + 1, "loss": float(fetched[-1][0]), "experts": experts})
                pending = []
        seen["report"] = train.step_device_report(
            step, params, opt_state, train.prepare_batch(tokens)
        )

    try:
        result = JaxTrainer(
            loop, scaling_config=ScalingConfig(num_workers=1, cpus_per_worker=1)
        ).fit()
    finally:
        ray_tpu.shutdown()
    assert result.error is None, result.error
    counters = {
        name: dict(metrics.get_or_create(metrics.Counter, name)._series())
        for name in ("train_expert_assignments", "train_expert_load_max")
    }
    return cfg, result, seen["report"], counters


def test_the_loss_falls(fitted):
    _, result, _, _ = fitted
    losses = [m["loss"] for m in result.metrics_history]
    assert len(losses) == STEPS // EVERY
    assert losses[-1] < losses[0] - 0.05


def test_counts_arrive_in_the_reports(fitted):
    cfg, result, _, _ = fitted
    choices = EVERY * BATCH * SEQ * cfg.num_experts_per_tok * cfg.num_layers
    for reported in result.metrics_history:
        experts = reported["experts"]
        assert experts["held"] + experts["absent"] == choices
        assert sum(experts["load"]) == experts["held"]


def test_counts_arrive_in_the_round_records(fitted):
    _, result, _, _ = fitted
    rounds = result.train_report["rounds"]
    assert len(rounds) == STEPS // EVERY
    for row, reported in zip(rounds, result.metrics_history):
        (rank,) = row["ranks"]
        assert rank["experts"] == tobs.expert_counts(reported["experts"])


def test_counts_are_summed_in_the_run_report(fitted):
    _, result, _, _ = fitted
    total = result.train_report["experts"]
    for key in SCALARS:
        assert total[key] == sum(m["experts"][key] for m in result.metrics_history)
    assert total["load"] == np.sum(
        [m["experts"]["load"] for m in result.metrics_history], axis=0
    ).tolist()


@pytest.mark.parametrize("where", ["held", "absent", "walked"])
def test_counts_reach_the_metric_family(fitted, where):
    _, result, _, counters = fitted
    series = counters["train_expert_assignments"]
    assert series[(("where", where),)] == result.train_report["experts"][where]


def test_walked_lies_between_the_held_rows_and_all_of_them(fitted):
    from ray_tpu.ops.grouped_experts import ladder

    cfg, result, _, _ = fitted
    rungs = ladder(
        BATCH * SEQ * cfg.num_experts_per_tok, len(cfg.experts_held) / cfg.num_experts
    )
    calls = EVERY * cfg.num_layers  # of the grouped experts, a report
    for reported in result.metrics_history:
        experts = reported["experts"]
        assert experts["held"] <= experts["walked"] <= experts["held"] + experts["absent"]
        # every call walked one rung of its ladder
        assert calls * rungs[0] <= experts["walked"] <= calls * rungs[-1]
    assert result.train_report["experts"]["walked"] == sum(
        m["experts"]["walked"] for m in result.metrics_history
    )


@pytest.mark.parametrize("devices", [1, 8])
def test_a_prepared_step_is_lowered_once(devices):
    """From `prepare_params` and `jax.jit(tx.init)(params)`, as the
    benchmark's runners call it: the optimizer state arrives uncommitted,
    the step hands back a committed one, and the second call must find the
    first call's executable (on the parent it held two: the step was traced,
    lowered and read from the compile cache twice at set-up). On one device,
    and on the test mesh's eight, data-parallel, as found."""
    import ray_tpu
    from ray_tpu.air import session
    from ray_tpu.parallel import MeshSpec

    cfg = toy_config()
    seen = {}

    def loop(_config):
        if devices == 1:
            session._require_session().context.mesh = MeshSpec().build(jax.devices()[:1])
        assert train.get_mesh().devices.size == devices
        params = train.prepare_params(mellum.init_params(cfg, 0))
        tx = optax.adamw(3e-3)
        opt_state = jax.jit(tx.init)(params)
        seen["uncommitted"] = sum(
            not leaf.committed for leaf in jax.tree_util.tree_leaves(opt_state)
        )
        step = train.prepare_step(mellum.train_step(cfg, tx), donate_argnums=(0, 1))
        tokens = np.random.RandomState(0).randint(
            0, cfg.rows_held, size=(BATCH, SEQ)
        ).astype(np.int32)
        held = []
        for _ in range(3):
            params, opt_state, loss, _ = step(params, opt_state, train.prepare_batch(tokens))
            held.append(step.jitted._cache_size())
        seen["held"], seen["loss"] = held, float(loss)

    ray_tpu.init(num_cpus=4)
    try:
        result = JaxTrainer(
            loop, scaling_config=ScalingConfig(num_workers=1, cpus_per_worker=1)
        ).fit()
    finally:
        ray_tpu.shutdown()
    assert result.error is None, result.error
    assert seen["uncommitted"] > 0  # else this proves nothing
    assert seen["held"] == [1, 1, 1] and np.isfinite(seen["loss"])


def test_load_max_reaches_the_metric_family(fitted):
    _, result, _, counters = fitted
    total = sum(counters["train_expert_load_max"].values())
    assert total == result.train_report["experts"]["load_max"]


def test_device_report_names_every_part(fitted):
    _, _, report, _ = fitted
    assert set(report) == {
        "op_scopes", "param_bytes_by_device", "step_argument_bytes", "step_temp_bytes",
    }
    assert set(report["op_scopes"]) == {"jit_step"}
    assert set(mellum.SCOPES) <= set(report["op_scopes"]["jit_step"].values())
    cfg = fitted[0]
    held = 4 * mellum.num_params(mellum.param_shapes(cfg))
    # replicated over the test mesh's data-parallel devices: whole on each
    assert set(report["param_bytes_by_device"].values()) == {held}
    assert report["step_argument_bytes"] >= 3 * held  # weights and both moments


def test_a_report_without_counts_leaves_none():
    profiler = tobs.StepProfiler(rank=0, world_size=1)
    assert "experts" not in profiler.end_round()
    assert "experts" not in profiler.end_round(experts="not counts")
    record = profiler.end_round(experts={"held": jnp.int32(3), "absent": 5, "load": [1, 2]})
    assert record["experts"] == {
        "held": 3, "absent": 5, "touched": 0, "load_max": 0, "load": [1, 2],
    }
    # `walked` rides where the step counts it, and only there.
    record = profiler.end_round(experts={"held": 3, "absent": 5, "walked": jnp.int32(4)})
    assert record["experts"]["walked"] == 4


@pytest.mark.parametrize(
    "path,scope",
    [
        ("jit(step)/jit(main)/llm.moe.routed/dot_general", "llm.moe.routed"),
        ("jit(step)/jit(main)/jvp(llm.moe.routed)/mul", "llm.moe.routed"),
        ("jit(step)/jit(main)/transpose(jvp(llm.moe.routed))/dot_general", "llm.moe.routed"),
        ("jit(step)/transpose(jvp(jvp()))/checkpoint/llm.mixer.attention.window/pallas_call",
         "llm.mixer.attention.window"),
        ("jit(step)/transpose(jvp(llm.moe.router))/llm.moe.routed/add", "llm.moe.routed"),
        ("jit(step)/jit(main)/transpose(jvp(llm.head))/dot_general", "llm.head"),
        ("ragged-dot-metadata", "llm.moe.routed"),
    ],
)
def test_scopes_of_reads_backward_names(path, scope):
    text = f'  %fusion.7 = f32[8]{{0}} fusion(%p), kind=kLoop, metadata={{op_name="{path}"}}'
    assert scopes_of(text) == {"fusion.7": scope}


def test_scopes_of_leaves_out_what_has_no_part():
    text = '  %add.1 = f32[] add(%a, %b), metadata={op_name="jit(step)/jit(main)/add"}'
    assert scopes_of(text) == {}
