"""Tune tests: grid/random search, best-result selection, ASHA early
stopping (reference: python/ray/tune/tests/test_tune_* shapes)."""

import time

import ray_tpu
from ray_tpu import tune
from ray_tpu.tune import ASHAScheduler, TuneConfig, Tuner, grid_search


RAY_START = dict(num_cpus=8, object_store_memory=128 * 1024 * 1024)


def test_grid_search(ray_start):
    def trainable(config):
        tune.report(score=config["a"] * 10 + config["b"])

    tuner = Tuner(trainable,
                  param_space={"a": grid_search([1, 2, 3]),
                               "b": grid_search([0, 5])})
    results = tuner.fit()
    assert len(results) == 6
    best = results.get_best_result("score", mode="max")
    assert best.config == {"a": 3, "b": 5}
    assert best.metrics["score"] == 35


def test_random_search(ray_start):
    def trainable(config):
        tune.report(val=config["x"])

    tuner = Tuner(trainable,
                  param_space={"x": tune.uniform(0, 1)},
                  tune_config=TuneConfig(num_samples=5, seed=42))
    results = tuner.fit()
    assert len(results) == 5
    vals = [r.metrics["val"] for r in results]
    assert all(0 <= v <= 1 for v in vals)
    assert len(set(vals)) == 5


def test_asha_scheduler_unit():
    """Deterministic halving semantics, incl. reports that stride past
    rung values (first-result-at-or-past-rung evaluation)."""
    from ray_tpu.tune.schedulers import CONTINUE, STOP
    sched = ASHAScheduler(metric="acc", mode="max", max_t=100,
                          grace_period=4, reduction_factor=2)
    # two good trials seed rung 4 high (the later one leads, so both pass
    # the top-1/rf cut)
    assert sched.on_result("good1", {"training_iteration": 4,
                                     "acc": 10.0}) == CONTINUE
    assert sched.on_result("good2", {"training_iteration": 4,
                                     "acc": 11.0}) == CONTINUE
    # bad trial reporting on a stride (3, 6 — never exactly 4) must still
    # be evaluated at rung 4 and cut
    assert sched.on_result("bad", {"training_iteration": 3,
                                   "acc": 0.1}) == CONTINUE
    assert sched.on_result("bad", {"training_iteration": 6,
                                   "acc": 0.2}) == STOP
    # max_t stops unconditionally
    assert sched.on_result("good1", {"training_iteration": 100,
                                     "acc": 99.0}) == STOP


def test_asha_integration(ray_start):
    def trainable(config):
        for step in range(20):
            tune.report(acc=config["lr"] * (step + 1))
            time.sleep(0.05)

    tuner = Tuner(
        trainable,
        param_space={"lr": grid_search([0.01, 0.02, 1.0, 2.0])},
        tune_config=TuneConfig(
            scheduler=ASHAScheduler(metric="acc", mode="max", max_t=20,
                                    grace_period=4, reduction_factor=2)))
    results = tuner.fit()
    assert len(results) == 4
    best = results.get_best_result("acc", mode="max")
    assert best.config["lr"] == 2.0


def test_trial_error_captured(ray_start):
    def trainable(config):
        if config["boom"]:
            raise RuntimeError("exploded")
        tune.report(ok=1)

    tuner = Tuner(trainable,
                  param_space={"boom": grid_search([False, True])})
    results = tuner.fit()
    errs = [r for r in results if r.error]
    oks = [r for r in results if not r.error]
    assert len(errs) == 1 and "exploded" in errs[0].error
    assert len(oks) == 1 and oks[0].metrics["ok"] == 1


def test_median_stopping_rule(ray_start):
    """Bad trials stop early under the median rule."""
    from ray_tpu import tune
    from ray_tpu.tune import MedianStoppingRule

    def trainable(config):
        import time as _t
        for i in range(12):
            tune.report({"score": config["quality"] * (i + 1)})
            _t.sleep(0.05)

    tuner = tune.Tuner(
        trainable,
        param_space={"quality": tune.grid_search([0.1, 0.2, 1.0, 2.0])},
        tune_config=tune.TuneConfig(
            scheduler=MedianStoppingRule(metric="score", grace_period=3),
            max_concurrent_trials=4),
    )
    grid = tuner.fit()
    best = grid.get_best_result("score")
    assert best.config["quality"] == 2.0
    # a bottom trial must have been cut before finishing all 12 reports
    shortest = min(len(r.history) for r in grid)
    assert shortest < 12


def test_pbt_exploit_and_checkpoint(ray_start):
    """A weak PBT trial adopts a strong trial's checkpointed weight and a
    mutated config."""
    from ray_tpu import tune
    from ray_tpu.tune import PopulationBasedTraining

    def trainable(config):
        import time as _t
        ckpt = tune.get_checkpoint()
        weight = ckpt["weight"] if ckpt else 0.0
        for _ in range(20):
            weight += config["lr"]
            tune.report({"score": weight}, checkpoint={"weight": weight})
            _t.sleep(0.25)

    pbt = PopulationBasedTraining(
        metric="score", perturbation_interval=4, quantile_fraction=0.5,
        hyperparam_mutations={"lr": [0.1, 0.5, 1.0, 2.0]})
    tuner = tune.Tuner(
        trainable,
        param_space={"lr": tune.grid_search([0.01, 0.02, 1.0, 2.0])},
        tune_config=tune.TuneConfig(scheduler=pbt,
                                    max_concurrent_trials=4),
    )
    grid = tuner.fit()
    best = grid.get_best_result("score")
    assert best.metrics["score"] > 10.0   # strong configs dominate
    # every trial ends with a meaningful score: weak ones exploited into
    # high-weight checkpoints or kept compounding a strong lr
    final_scores = sorted(r.metrics.get("score", 0.0) for r in grid)
    assert final_scores[0] > 1.0, final_scores


def test_tpe_search(ray_start):
    """Native TPE beats its own random warmup on a smooth objective
    (reference: the Optuna/HyperOpt search-algorithm integrations)."""
    from ray_tpu.tune.search import TPESearch

    def objective(config):
        x = config["x"]
        bonus = 0.0 if config["kind"] == "good" else 2.0
        tune.report({"loss": (x - 3.0) ** 2 + bonus})

    space = {"x": tune.uniform(-10.0, 10.0),
             "kind": tune.choice(["good", "bad"])}
    alg = TPESearch(space, metric="loss", mode="min", n_initial=8, seed=7)
    tuner = tune.Tuner(
        objective, param_space=space,
        tune_config=tune.TuneConfig(num_samples=32, metric="loss",
                                    mode="min", search_alg=alg,
                                    max_concurrent_trials=2))
    grid = tuner.fit()
    best = grid.get_best_result(metric="loss", mode="min")
    assert best.metrics["loss"] < 1.0, best.metrics
    assert best.config["kind"] == "good"
    # the model phase concentrated samples near the optimum: the best of
    # the suggested (post-warmup) trials beats the random warmup's best
    ordered = sorted(grid, key=lambda r: r.trial_id)
    warmup = ordered[:8]
    suggested = ordered[8:]
    best_warm = min(r.metrics["loss"] for r in warmup if r.metrics)
    best_sugg = min(r.metrics["loss"] for r in suggested if r.metrics)
    assert best_sugg <= best_warm + 1e-9
