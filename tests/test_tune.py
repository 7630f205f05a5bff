"""Tune: searchers, schedulers, controller event loop, trainer integration.

Mirrors the reference's tune test strategy (tune/tests/test_api.py,
test_trial_scheduler.py, test_tune_restore.py — SURVEY.md §4) at unit scale.
"""

import os

import pytest

import ray_tpu
from ray_tpu import tune
from ray_tpu.air import session
from ray_tpu.air.checkpoint import Checkpoint
from ray_tpu.tune.experiment.trial import Trial
from ray_tpu.tune.schedulers import AsyncHyperBandScheduler, PopulationBasedTraining
from ray_tpu.tune.search.variant_generator import count_variants, generate_variants


# -- variant generation (no cluster needed) ---------------------------------


def test_grid_search_cartesian_product():
    space = {
        "lr": tune.grid_search([0.1, 0.01]),
        "momentum": tune.grid_search([0.9, 0.99]),
        "fixed": 7,
    }
    variants = list(generate_variants(space))
    assert len(variants) == 4
    assert {(v["lr"], v["momentum"]) for v in variants} == {
        (0.1, 0.9), (0.1, 0.99), (0.01, 0.9), (0.01, 0.99)
    }
    assert all(v["fixed"] == 7 for v in variants)
    assert count_variants(space) == 4


def test_sampled_domains_and_num_samples():
    space = {
        "lr": tune.loguniform(1e-5, 1e-1),
        "layers": tune.randint(1, 5),
        "act": tune.choice(["relu", "gelu"]),
        "nested": {"dropout": tune.uniform(0.0, 0.5)},
    }
    variants = list(generate_variants(space, num_samples=10, seed=0))
    assert len(variants) == 10
    for v in variants:
        assert 1e-5 <= v["lr"] <= 1e-1
        assert v["layers"] in (1, 2, 3, 4)
        assert v["act"] in ("relu", "gelu")
        assert 0.0 <= v["nested"]["dropout"] <= 0.5
    # Seeded: reproducible.
    again = list(generate_variants(space, num_samples=10, seed=0))
    assert variants == again


def test_grid_times_samples():
    space = {"a": tune.grid_search([1, 2, 3])}
    assert len(list(generate_variants(space, num_samples=2))) == 6


# -- schedulers (pure logic) -------------------------------------------------


def _result(metric, it):
    return {"score": metric, "training_iteration": it}


def test_asha_stops_bottom_trials():
    sched = AsyncHyperBandScheduler(
        metric="score", mode="max", grace_period=1, reduction_factor=2, max_t=100
    )
    trials = [Trial("t", {}, trial_id=f"x{i}") for i in range(4)]
    # All four report at milestone 1 with increasing scores.
    decisions = [
        sched.on_trial_result(t, _result(score, 1))
        for t, score in zip(trials, [0.1, 0.2, 0.3, 0.4])
    ]
    # The early trials can't be judged (no cutoff yet); later low performers
    # would stop. At minimum the best trial continues, and once the rung has
    # >= reduction_factor entries, below-median trials stop.
    assert decisions[-1] == "CONTINUE"
    t5 = Trial("t", {}, trial_id="x5")
    assert sched.on_trial_result(t5, _result(0.05, 1)) == "STOP"


def test_asha_max_t_terminates():
    sched = AsyncHyperBandScheduler(metric="score", mode="max", max_t=5)
    t = Trial("t", {}, trial_id="y0")
    assert sched.on_trial_result(t, _result(1.0, 5)) == "STOP"


def test_pbt_exploit_bottom_from_top():
    sched = PopulationBasedTraining(
        metric="score",
        mode="max",
        perturbation_interval=2,
        hyperparam_mutations={"lr": tune.loguniform(1e-4, 1e-1)},
        quantile_fraction=0.5,
        seed=0,
    )
    good = Trial("t", {"lr": 0.01}, trial_id="good")
    bad = Trial("t", {"lr": 0.0001}, trial_id="bad")
    for t in (good, bad):
        sched.on_trial_add(t)
    sched.on_trial_result(good, _result(0.9, 2))
    sched.on_trial_result(bad, _result(0.1, 2))
    assert "bad" in sched.pending_exploits
    src, new_config = sched.pending_exploits["bad"]
    assert src is good
    assert "lr" in new_config


# -- end-to-end on the runtime ----------------------------------------------


def train_quadratic(config):
    # Minimize (x - 3)^2 over iterations: report decreasing loss.
    x = config["x"]
    for i in range(5):
        loss = (x - 3.0) ** 2 + 1.0 / (i + 1)
        session.report({"loss": loss})


def test_tuner_function_trainable(ray_start_regular):
    tuner = tune.Tuner(
        train_quadratic,
        param_space={"x": tune.grid_search([0.0, 3.0, 5.0])},
        tune_config=tune.TuneConfig(metric="loss", mode="min"),
    )
    results = tuner.fit()
    assert len(results) == 3
    best = results.get_best_result()
    assert abs(best.metrics["loss"] - 0.2) < 1e-6  # x=3 → 0 + 1/5
    df = results.get_dataframe()
    assert len(df) == 3 and "config/x" in df.columns


class _Counter(tune.Trainable):
    def setup(self, config):
        self.count = config.get("start", 0)

    def step(self):
        self.count += 1
        return {"count": self.count}

    def save_checkpoint(self):
        return {"count": self.count}

    def load_checkpoint(self, state):
        self.count = state["count"]


def test_tuner_class_trainable_stop_criteria(ray_start_regular):
    results = tune.run(
        _Counter,
        config={"start": tune.grid_search([0, 100])},
        metric="count",
        mode="max",
        stop={"training_iteration": 4},
    )
    assert len(results) == 2
    for r in results:
        assert r.metrics["training_iteration"] == 4
    assert results.get_best_result().metrics["count"] == 104


def test_tuner_checkpoint_at_end(ray_start_regular):
    results = tune.run(
        _Counter,
        config={"start": 10},
        metric="count",
        mode="max",
        stop={"training_iteration": 2},
        checkpoint_at_end=True,
    )
    ckpt = results[0].checkpoint
    assert ckpt is not None
    assert ckpt.to_dict()["user_state"]["count"] == 12


def test_asha_end_to_end_kills_bad_trials(ray_start_regular):
    def train_fn(config):
        for i in range(20):
            session.report({"acc": config["quality"] * (i + 1) / 20.0})

    # Strong trials first, one trial at a time: the two strong ones have
    # populated every rung before a weak one reports, so each weak trial
    # arrives at its first rung as the lowest of three and is cut there.
    # With all four at once which trial reaches a rung first is the
    # interpreter's choice, and in ascending order nothing is ever cut.
    results = tune.run(
        train_fn,
        config={"quality": tune.grid_search([1.0, 0.9, 0.2, 0.1])},
        metric="acc",
        mode="max",
        scheduler=AsyncHyperBandScheduler(
            metric="acc", mode="max", grace_period=2, reduction_factor=2, max_t=20
        ),
        max_concurrent_trials=1,
    )
    iters = {
        r.metrics.get("training_iteration", 0): r.metrics.get("acc") for r in results
    }
    # The best trial survives to max_t; at least one weak trial died early.
    assert max(iters.keys()) >= 19
    assert min(iters.keys()) < 20
    assert results.get_best_result().metrics["acc"] >= 0.9


def test_trial_failure_and_retry(ray_start_regular):
    attempts = {"n": 0}

    class Flaky(tune.Trainable):
        def setup(self, config):
            self.it = 0

        def step(self):
            self.it += 1
            if self.it == 2 and not os.environ.get("_TUNE_FLAKY_DONE"):
                os.environ["_TUNE_FLAKY_DONE"] = "1"
                raise RuntimeError("transient failure")
            return {"it": self.it}

        def save_checkpoint(self):
            return {"it": self.it}

        def load_checkpoint(self, state):
            self.it = state["it"]

    os.environ.pop("_TUNE_FLAKY_DONE", None)
    results = tune.run(
        Flaky,
        metric="it",
        mode="max",
        stop={"training_iteration": 4},
        max_failures=1,
    )
    assert results.num_errors == 0
    assert results[0].metrics["training_iteration"] == 4


def test_pbt_end_to_end(ray_start_regular):
    def train_fn(config):
        score = 0.0
        ckpt = session.get_checkpoint()
        if ckpt:
            score = ckpt.to_dict()["score"]
        lr = config["lr"]
        for _ in range(12):
            score += lr  # higher lr climbs faster
            session.report(
                {"score": score}, checkpoint=Checkpoint.from_dict({"score": score})
            )

    pbt = PopulationBasedTraining(
        metric="score",
        mode="max",
        perturbation_interval=3,
        hyperparam_mutations={"lr": tune.uniform(0.01, 1.0)},
        quantile_fraction=0.5,
        seed=1,
    )
    results = tune.run(
        train_fn,
        config={"lr": tune.grid_search([0.02, 0.8])},
        metric="score",
        mode="max",
        scheduler=pbt,
        stop={"training_iteration": 12},
    )
    assert len(results) == 2
    # The weak trial must have been pulled up by exploitation: its final score
    # exceeds what 12 steps of lr=0.02 alone could reach.
    worst = min(r.metrics["score"] for r in results)
    assert worst > 12 * 0.02 + 1e-9


def test_experiment_state_written(ray_start_regular, tmp_path):
    from ray_tpu.air.config import RunConfig

    tuner = tune.Tuner(
        train_quadratic,
        param_space={"x": 1.0},
        tune_config=tune.TuneConfig(metric="loss", mode="min"),
        run_config=RunConfig(name="exp1", storage_path=str(tmp_path)),
    )
    tuner.fit()
    state = os.path.join(str(tmp_path), "exp1", "experiment_state.json")
    assert os.path.exists(state)


def test_custom_searcher_num_samples_cap(ray_start_regular):
    searcher = tune.RandomSearch({"x": tune.uniform(0, 1)}, seed=0)
    results = tune.run(
        train_quadratic,
        metric="loss",
        mode="min",
        search_alg=searcher,
        num_samples=4,
    )
    assert len(results) == 4  # RandomSearch alone would never terminate


def test_stop_criteria_min_mode_not_inverted(ray_start_regular):
    """stop={'loss': ...} means stop when loss >= threshold even in min mode."""
    def fn(config):
        for i in range(10):
            session.report({"loss": 100.0 - i, "training_iteration": i + 1})

    results = tune.run(
        fn, metric="loss", mode="min", stop={"training_iteration": 3}
    )
    assert results[0].metrics["training_iteration"] == 3


def test_qrandn_quantized():
    from ray_tpu.tune.search.sample import QNormal
    import random

    dom = tune.qrandn(0.0, 1.0, 0.25)
    assert isinstance(dom, QNormal)
    rng = random.Random(0)
    for _ in range(20):
        v = dom.sample(rng)
        assert abs(v / 0.25 - round(v / 0.25)) < 1e-9


def test_tuner_restore_reruns_unfinished(ray_start_regular, tmp_path):
    from ray_tpu.air.config import CheckpointConfig, RunConfig

    calls = []

    def fn(config):
        ckpt = session.get_checkpoint()
        start = ckpt.to_dict()["i"] if ckpt else 0
        calls.append(start)
        for i in range(start, 4):
            session.report(
                {"i": i}, checkpoint=Checkpoint.from_dict({"i": i + 1})
            )
        if config.get("fail") and start == 0:
            raise RuntimeError("die before finishing")

    rc = RunConfig(
        name="resume_exp",
        storage_path=str(tmp_path),
        checkpoint_config=CheckpointConfig(checkpoint_frequency=1),
    )
    tuner = tune.Tuner(
        fn,
        param_space={"fail": True},
        tune_config=tune.TuneConfig(metric="i", mode="max"),
        run_config=rc,
    )
    first = tuner.fit()
    assert first.num_errors == 1

    restored = tune.Tuner.restore(
        os.path.join(str(tmp_path), "resume_exp"), fn
    )
    second = restored.fit()
    assert second.num_errors == 0
    # Resumed from a persisted checkpoint, not from scratch.
    assert calls[-1] > 0


def test_jax_trainer_with_tuner(ray_start_regular):
    """Trainer-as-trainable: JaxTrainer grid over lr (BASELINE config #4)."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.train import JaxTrainer
    from ray_tpu.air.config import ScalingConfig

    def loop(config):
        lr = config["lr"]
        w = jnp.zeros(())

        @jax.jit
        def step(w):
            grad = 2 * (w - 5.0)
            return w - lr * grad

        for _ in range(8):
            w = step(w)
            session.report({"dist": float(abs(w - 5.0))})

    trainer = JaxTrainer(
        loop,
        scaling_config=ScalingConfig(num_workers=1, chips_per_worker=0),
    )
    tuner = tune.Tuner(
        trainer,
        param_space={"train_loop_config": {"lr": tune.grid_search([0.01, 0.3])}},
        tune_config=tune.TuneConfig(metric="dist", mode="min"),
    )
    results = tuner.fit()
    assert len(results) == 2
    assert results.get_best_result().metrics["dist"] < 0.1


def test_hyperband_scheduler(ray_start_regular):
    from ray_tpu import tune
    from ray_tpu.tune.schedulers import HyperBandScheduler

    def trainable(config):
        from ray_tpu.air import session

        for i in range(30):
            session.report({"score": config["base"] + i * 0.1})

    tuner = tune.Tuner(
        trainable,
        param_space={"base": tune.grid_search([0.0, 1.0, 2.0, 3.0])},
        tune_config=tune.TuneConfig(
            metric="score",
            mode="max",
            scheduler=HyperBandScheduler(max_t=27, reduction_factor=3),
        ),
    )
    results = tuner.fit()
    best = results.get_best_result()
    # The strongest config survives to the end.
    assert best.config["base"] == 3.0
    # At least one weak trial stopped early.
    iters = [r.metrics.get("training_iteration", 0) for r in results]
    assert min(iters) < max(iters)


# -- model-based search -------------------------------------------------------


def test_tpe_beats_random_fixed_budget():
    """Seeded comparison on a sharp 2-D optimum: TPE's best-found value
    after a fixed budget must beat random search with the same budget
    (averaged over seeds so the margin is structural, not luck)."""
    from ray_tpu.tune.search.tpe import TPESearch

    def objective(config):
        x, y = config["x"], config["y"]
        return -((x - 0.73) ** 2) * 8.0 - ((y + 0.21) ** 2) * 8.0

    space = {"x": tune.uniform(-2, 2), "y": tune.uniform(-2, 2)}
    budget = 60

    def run_searcher(searcher):
        best = -float("inf")
        for i in range(budget):
            tid = f"t{i}"
            config = searcher.suggest(tid)
            score = objective(config)
            searcher.on_trial_complete(tid, {"score": score})
            best = max(best, score)
        return best

    tpe_wins = 0
    for seed in range(5):
        tpe = TPESearch(space, metric="score", mode="max",
                        n_startup_trials=12, seed=seed)
        rnd = tune.RandomSearch(space, seed=seed)
        rnd.metric, rnd.mode = "score", "max"
        if run_searcher(tpe) >= run_searcher(rnd):
            tpe_wins += 1
    assert tpe_wins >= 4, f"TPE won only {tpe_wins}/5 seeds"


def test_tpe_end_to_end_with_tuner(ray_start_regular):
    from ray_tpu.tune.search.tpe import TPESearch

    def train_fn(config):
        session.report(
            {"loss": (config["lr"] - 0.01) ** 2 + config["width"] * 0.0}
        )

    space = {"lr": tune.loguniform(1e-4, 1.0), "width": tune.choice([32, 64])}
    tuner = tune.Tuner(
        train_fn,
        param_space=space,
        tune_config=tune.TuneConfig(
            metric="loss",
            mode="min",
            num_samples=20,
            search_alg=TPESearch(space, metric="loss", mode="min",
                                 n_startup_trials=6, seed=0),
        ),
    )
    results = tuner.fit()
    assert len(results) == 20
    assert results.get_best_result().metrics["loss"] < 0.05


def test_pb2_gp_explore_mechanics():
    """PB2 chooses continuous exploration points via GP-UCB once it has
    improvement observations; values stay inside the mutation bounds."""
    from ray_tpu.tune.schedulers import PB2
    from ray_tpu.tune.experiment.trial import Trial

    sched = PB2(
        metric="score",
        mode="max",
        perturbation_interval=1,
        hyperparam_mutations={"lr": tune.loguniform(1e-4, 1e-1)},
        seed=0,
    )
    trials = [
        Trial(f"t{i}", config={"lr": 10 ** (-1 - i % 3)}) for i in range(4)
    ]
    for t in trials:
        sched.on_trial_add(t)
    # Feed several rounds of results: higher lr -> bigger improvement here.
    for step in range(1, 4):
        for i, t in enumerate(trials):
            sched.on_trial_result(
                t,
                {
                    "score": step * (1.0 + i),
                    "training_iteration": step,
                },
            )
    assert sched._gp_data, "GP observations were not collected"
    explored = sched._explore({"lr": 1e-3})
    assert 1e-4 <= explored["lr"] <= 1e-1
    # With >=4 observations the explore step is the GP path (deterministic
    # under the seed), not plain PBT perturbation.
    assert len(sched._gp_data) >= 4


def test_pb2_end_to_end(ray_start_regular):
    """PB2 drives the same exploit machinery as PBT, with GP-UCB choosing
    the continuous exploration point: the weak trial gets pulled up and its
    explored lr stays in bounds."""
    from ray_tpu.tune.schedulers import PB2

    def train_fn(config):
        score = 0.0
        ckpt = session.get_checkpoint()
        if ckpt:
            score = ckpt.to_dict()["score"]
        for _ in range(12):
            score += config["lr"]
            session.report(
                {"score": score},
                checkpoint=Checkpoint.from_dict({"score": score}),
            )

    pb2 = PB2(
        metric="score",
        mode="max",
        perturbation_interval=3,
        hyperparam_mutations={"lr": tune.uniform(0.01, 1.0)},
        quantile_fraction=0.5,
        seed=1,
    )
    results = tune.run(
        train_fn,
        config={"lr": tune.grid_search([0.02, 0.8])},
        metric="score",
        mode="max",
        scheduler=pb2,
        stop={"training_iteration": 12},
    )
    assert len(results) == 2
    worst = min(r.metrics["score"] for r in results)
    assert worst > 12 * 0.02 + 1e-9  # exploitation happened
    assert pb2._gp_data, "PB2 collected no GP observations"
    for r in results:
        assert 0.01 <= r.metrics["config"]["lr"] <= 1.0 if "config" in r.metrics else True


# -- BOHB (multi-fidelity TPE) ------------------------------------------------


def test_bohb_models_highest_informative_budget():
    """TuneBOHB fits its TPE split on the highest rung with enough
    observations, and its suggestions concentrate near the good region."""
    from ray_tpu.tune.search.bohb import TuneBOHB

    space = {"x": tune.uniform(-2.0, 2.0)}
    bohb = TuneBOHB(
        space, metric="score", mode="max", max_t=9, reduction_factor=3,
        random_fraction=0.0, seed=0,
    )
    # Feed observations at budget 3 AND budget 9 — the 9-rung has too few
    # points, so the model must come from rung 3.
    for i in range(10):
        tid = f"lo{i}"
        x = -2.0 + 4.0 * i / 9.0
        bohb._pending[tid] = {"x": x}
        score = -abs(x - 0.7)  # optimum at 0.7
        bohb.on_trial_result(tid, {"score": score, "training_iteration": 3})
    bohb._pending["hi0"] = {"x": 0.0}
    bohb.on_trial_result("hi0", {"score": 0.0, "training_iteration": 9})
    assert bohb._model_budget() == 3
    suggestions = [bohb._suggest_config()["x"] for _ in range(20)]
    mean_dist = sum(abs(x - 0.7) for x in suggestions) / len(suggestions)
    # Uniform sampling over [-2,2] averages ~1.12 from 0.7.
    assert mean_dist < 0.75, f"model did not concentrate: {mean_dist:.2f}"


def test_bohb_end_to_end_with_tuner(ray_start_regular):
    """BOHB = HyperBandForBOHB brackets driving the TuneBOHB model: weak
    trials stop at rungs, the model concentrates, the best config wins."""
    from ray_tpu.tune.schedulers import HyperBandForBOHB
    from ray_tpu.tune.search.bohb import TuneBOHB

    def train_fn(config):
        for _ in range(9):
            session.report({"loss": (config["lr"] - 0.01) ** 2})

    space = {"lr": tune.loguniform(1e-4, 1.0)}
    tuner = tune.Tuner(
        train_fn,
        param_space=space,
        tune_config=tune.TuneConfig(
            metric="loss",
            mode="min",
            num_samples=18,
            search_alg=TuneBOHB(
                space, metric="loss", mode="min", max_t=9,
                reduction_factor=3, seed=0,
            ),
            scheduler=HyperBandForBOHB(
                metric="loss", mode="min", max_t=9, reduction_factor=3,
            ),
        ),
    )
    results = tuner.fit()
    assert len(results) == 18
    assert results.get_best_result().metrics["loss"] < 0.05
    # Successive halving actually stopped weak trials early.
    iters = [r.metrics.get("training_iteration", 0) for r in results]
    assert min(iters) < max(iters)


def test_resource_changing_scheduler(ray_start_regular):
    """A trial's resource request grows mid-run: the scheduler pauses it,
    the controller restarts it from checkpoint at the NEW size."""
    from ray_tpu.tune.schedulers import FIFOScheduler, ResourceChangingScheduler

    def train_fn(config):
        start = 0
        ckpt = session.get_checkpoint()
        if ckpt:
            start = ckpt.to_dict()["i"] + 1
        for i in range(start, 6):
            session.report(
                {"score": float(i), "resumed_from": start},
                checkpoint=Checkpoint.from_dict({"i": i}),
            )

    def grow_after_two(controller, trial, result, scheduler):
        if result.get("training_iteration", 0) >= 2:
            return {**trial.resources, "CPU": 2.0}
        return None

    scheduler = ResourceChangingScheduler(
        base_scheduler=FIFOScheduler(),
        resources_allocation_function=grow_after_two,
    )
    tuner = tune.Tuner(
        train_fn,
        param_space={"lr": 0.1},
        tune_config=tune.TuneConfig(
            metric="score", mode="max", scheduler=scheduler
        ),
        resources_per_trial={"CPU": 1.0},
    )
    results = tuner.fit()
    assert len(results) == 1
    trial = tuner._controller.trials[0]
    assert trial.resources["CPU"] == 2.0, "resize never applied"
    assert results.get_best_result().metrics["score"] == 5.0
    # The resized run RESUMED from the checkpoint, not from scratch.
    assert results.get_best_result().metrics["resumed_from"] > 0
    assert not scheduler.pending_resources


def test_distribute_resources_policy():
    """DistributeResources grows a trial's CPU request toward an even share
    of the cluster and never shrinks below the base request."""
    from ray_tpu.tune.schedulers import DistributeResources

    class _Ctl:
        _live = {"a": 1, "b": 1}

    class _Trial:
        resources = {"CPU": 1.0}

    runtime = ray_tpu.init(num_cpus=8)
    try:
        policy = DistributeResources(base_resources={"CPU": 1.0})
        new = policy(_Ctl(), _Trial(), {}, None)
        assert new["CPU"] == 4.0  # 8 CPUs / 2 live trials
    finally:
        ray_tpu.shutdown()
