"""Tune tests (model: python/ray/tune/tests/ — test_tuner.py,
test_trial_scheduler.py, test_var.py)."""

import json
import os

import pytest

import ray_tpu
from ray_tpu import tune
from ray_tpu.train.config import FailureConfig, RunConfig


@pytest.fixture(autouse=True, scope="module")
def _rt(rt):
    yield rt


@pytest.fixture()
def run_cfg(tmp_path):
    def make(**kw):
        kw.setdefault("storage_path", str(tmp_path / "tune"))
        kw.setdefault("name", "exp")
        return RunConfig(**kw)

    return make


def test_variant_generation_grid_and_samples():
    from ray_tpu.tune.search_space import generate_variants

    space = {"a": tune.grid_search([1, 2, 3]),
             "b": tune.choice(["x", "y"]),
             "nested": {"c": tune.grid_search([10, 20])}}
    variants = list(generate_variants(space, num_samples=2, seed=0))
    assert len(variants) == 12  # 3 * 2 grid, x2 samples
    assert {v["a"] for v in variants} == {1, 2, 3}
    assert {v["nested"]["c"] for v in variants} == {10, 20}
    assert all(v["b"] in ("x", "y") for v in variants)


def test_sampling_domains():
    from ray_tpu.tune.search_space import generate_variants

    space = {"lr": tune.loguniform(1e-5, 1e-1),
             "dim": tune.randint(8, 64),
             "drop": tune.quniform(0.1, 0.5, 0.1)}
    vs = list(generate_variants(space, num_samples=50, seed=1))
    assert all(1e-5 <= v["lr"] <= 1e-1 for v in vs)
    assert all(8 <= v["dim"] < 64 for v in vs)
    assert all(abs(v["drop"] * 10 - round(v["drop"] * 10)) < 1e-9
               for v in vs)


def test_tuner_grid_best(run_cfg):
    def objective(config):
        # quadratic with max at x=3
        score = -(config["x"] - 3) ** 2
        tune.report({"score": score})

    tuner = tune.Tuner(
        objective,
        param_space={"x": tune.grid_search([0, 1, 2, 3, 4, 5])},
        tune_config=tune.TuneConfig(metric="score", mode="max",
                                    max_concurrent_trials=3),
        run_config=run_cfg())
    grid = tuner.fit()
    assert len(grid) == 6
    best = grid.get_best_result()
    assert best.config["x"] == 3
    assert best.metrics["score"] == 0


def test_tuner_multi_step_and_dataframe(run_cfg):
    def objective(config):
        acc = 0.0
        for step in range(5):
            acc += config["lr"]
            tune.report({"acc": acc, "step": step})

    tuner = tune.Tuner(
        objective,
        param_space={"lr": tune.grid_search([0.1, 0.2])},
        tune_config=tune.TuneConfig(metric="acc", mode="max"),
        run_config=run_cfg())
    grid = tuner.fit()
    best = grid.get_best_result()
    assert best.config["lr"] == pytest.approx(0.2)
    assert best.metrics["training_iteration"] == 5
    df = grid.get_dataframe()
    assert len(df) == 2 and "config/lr" in df.columns


def test_asha_stops_bad_trials(run_cfg):
    def objective(config):
        for step in range(1, 21):
            tune.report({"score": config["quality"] * step,
                         "training_iteration": step})

    sched = tune.ASHAScheduler(max_t=20, grace_period=2,
                               reduction_factor=2)
    # Sequential execution, strong trials first: async SHA can only cut a
    # trial against scores already recorded at its rung.
    tuner = tune.Tuner(
        objective,
        param_space={"quality": tune.grid_search(
            [5.0, 2.0, 1.0, 0.5, 0.2, 0.1])},
        tune_config=tune.TuneConfig(metric="score", mode="max",
                                    scheduler=sched,
                                    max_concurrent_trials=1),
        run_config=run_cfg())
    grid = tuner.fit()
    best = grid.get_best_result()
    assert best.config["quality"] == 5.0
    # Bad trials must have been cut early.
    iters = [t.iterations for t in grid._trials]
    assert min(iters) < 20
    assert max(iters) == 20


def test_median_stopping(run_cfg):
    def objective(config):
        for step in range(1, 11):
            tune.report({"score": config["q"] * step})

    sched = tune.MedianStoppingRule(grace_period=3, min_samples_required=2)
    tuner = tune.Tuner(
        objective,
        param_space={"q": tune.grid_search([1.0, 1.0, 0.01])},
        tune_config=tune.TuneConfig(metric="score", mode="max",
                                    scheduler=sched,
                                    max_concurrent_trials=3),
        run_config=run_cfg())
    grid = tuner.fit()
    worst = min(grid._trials, key=lambda t: t.config["q"])
    assert worst.iterations < 10


def test_trial_failure_retry(run_cfg, tmp_path):
    marker = str(tmp_path / "failed_once")

    def objective(config):
        if config["x"] == 1 and not os.path.exists(marker):
            open(marker, "w").close()
            raise RuntimeError("transient")
        tune.report({"score": config["x"]})

    tuner = tune.Tuner(
        objective,
        param_space={"x": tune.grid_search([1, 2])},
        tune_config=tune.TuneConfig(metric="score", mode="max"),
        run_config=run_cfg(failure_config=FailureConfig(max_failures=1)))
    grid = tuner.fit()
    assert not grid.errors
    assert len(grid) == 2


def test_trial_error_surfaces(run_cfg):
    def objective(config):
        raise ValueError("boom")

    tuner = tune.Tuner(
        objective, param_space={"x": tune.grid_search([1])},
        tune_config=tune.TuneConfig(metric="score", mode="max"),
        run_config=run_cfg())
    grid = tuner.fit()
    assert grid.errors and "boom" in grid.errors[0]


def test_experiment_state_and_restore(run_cfg, tmp_path):
    storage = str(tmp_path / "tune")

    def objective(config):
        tune.report({"score": config["x"]})

    rc = RunConfig(storage_path=storage, name="exp1")
    tuner = tune.Tuner(
        objective, param_space={"x": tune.grid_search([1, 2, 3])},
        tune_config=tune.TuneConfig(metric="score", mode="max"),
        run_config=rc)
    tuner.fit()
    exp_dir = os.path.join(storage, "exp1")
    state = json.load(open(os.path.join(exp_dir, "experiment_state.json")))
    assert len(state["trials"]) == 3
    assert all(t["status"] == "TERMINATED" for t in state["trials"])

    # Restore: finished trials are not re-run (objective would now fail).
    def poisoned(config):
        raise RuntimeError("must not re-run finished trials")

    restored = tune.Tuner.restore(
        exp_dir, poisoned,
        param_space={"x": tune.grid_search([1, 2, 3])})
    grid = restored.fit()
    assert not grid.errors
    assert grid.get_best_result(metric="score", mode="max").metrics[
        "score"] == 3


def test_checkpointed_resume(run_cfg, tmp_path):
    """Trials save checkpoints; after an interrupt the trial resumes from
    its checkpoint instead of restarting."""
    storage = str(tmp_path / "tune")

    def objective(config):
        import json as _json
        start = 0
        ckpt = tune.get_checkpoint()
        if ckpt:
            start = _json.load(open(os.path.join(ckpt.path, "s.json")))["step"] + 1
        for step in range(start, 6):
            d = os.path.join(tune.get_trial_dir(), f"ckpt_{step}")
            os.makedirs(d, exist_ok=True)
            _json.dump({"step": step}, open(os.path.join(d, "s.json"), "w"))
            tune.report({"score": step, "start": start}, checkpoint=d)
            if step == 2 and start == 0 and config["x"] == 1:
                raise RuntimeError("interrupt")

    rc = RunConfig(storage_path=storage, name="ck",
                   failure_config=FailureConfig(max_failures=1))
    tuner = tune.Tuner(
        objective, param_space={"x": tune.grid_search([1])},
        tune_config=tune.TuneConfig(metric="score", mode="max"),
        run_config=rc)
    grid = tuner.fit()
    assert not grid.errors
    best = grid.get_best_result()
    assert best.metrics["score"] == 5
    assert best.metrics["start"] == 3  # resumed, not restarted


def test_pbt_exploits_and_perturbs(run_cfg):
    """Low-performing trials adopt (perturbed) configs of better trials."""
    def objective(config):
        import json as _json
        lr = config["lr"]
        w = 0.0
        ckpt = tune.get_checkpoint()
        start = 0
        if ckpt:
            st = _json.load(open(os.path.join(ckpt.path, "w.json")))
            w, start = st["w"], st["step"] + 1
        for step in range(start, 12):
            w += lr  # "performance" ~ lr
            d = os.path.join(tune.get_trial_dir(), f"c{step}")
            os.makedirs(d, exist_ok=True)
            _json.dump({"w": w, "step": step},
                       open(os.path.join(d, "w.json"), "w"))
            tune.report({"score": w, "lr": lr,
                         "training_iteration": step + 1}, checkpoint=d)

    sched = tune.PopulationBasedTraining(
        perturbation_interval=3,
        hyperparam_mutations={"lr": tune.uniform(0.5, 2.0)},
        seed=0)
    tuner = tune.Tuner(
        objective,
        param_space={"lr": tune.grid_search([0.001, 1.0])},
        tune_config=tune.TuneConfig(metric="score", mode="max",
                                    scheduler=sched,
                                    max_concurrent_trials=2),
        run_config=run_cfg(name="pbt"))
    grid = tuner.fit()
    assert not grid.errors
    scores = sorted(t.last_result["score"] for t in grid._trials)
    # The weak trial (lr=0.001 alone would end near 0.012) must have
    # exploited the strong one's checkpoint + lr.
    assert scores[0] > 1.0


def test_tuner_over_trainer(run_cfg):
    """Tuner(trainer) runs the full Train gang per trial (reference:
    Tuner(trainer) in tuner.py — trainers as trainables)."""
    from ray_tpu import train as rt_train
    from ray_tpu.train import ScalingConfig

    def loop(config):
        w = 0.0
        for _ in range(4):
            w += config["lr"]
        rt_train.report({"w": w})

    trainer = rt_train.DataParallelTrainer(
        loop, scaling_config=ScalingConfig(num_workers=2))
    tuner = tune.Tuner(
        trainer,
        param_space={"lr": tune.grid_search([0.5, 1.0])},
        tune_config=tune.TuneConfig(metric="w", mode="max",
                                    max_concurrent_trials=1),
        run_config=run_cfg(name="trainer_tune"))
    grid = tuner.fit()
    assert not grid.errors
    assert grid.get_best_result().metrics["w"] == pytest.approx(4.0)


def test_tpe_searcher_beats_random_on_quadratic(run_cfg):
    """TPE concentrates samples near the optimum of a smooth function
    (reference analogue: search-algorithm convergence tests). What the
    searcher's rule gives is read from the rule, at a fixed seed and with
    no runtime under it (40 ``suggest``/``on_trial_complete`` rounds in
    this process: a pure function of the seed); the run through the Tuner
    is held to the path: every trial finishes with its own config's score,
    and its startup trials are that seed's plain draws."""
    import random

    from ray_tpu.tune import TPESearcher

    def score_of(config):
        x, y = config["x"], config["y"]
        return -(x - 3.0) ** 2 - (y + 1.0) ** 2

    def objective(config):
        tune.report({"score": score_of(config)})

    space = {"x": tune.uniform(-10, 10), "y": tune.uniform(-10, 10)}
    # the rule alone
    searcher = TPESearcher(n_startup=8)
    searcher.set_experiment(space, "score", "max", 40, 3)
    configs = []
    for i in range(40):
        configs.append(searcher.suggest(f"t{i}"))
        searcher.on_trial_complete(f"t{i}", {"score": score_of(configs[-1])})
    assert searcher.suggest("t40") is None
    rng = random.Random(3)                   # the startup phase: plain draws
    assert configs[:8] == [{"x": space["x"].sample(rng),
                            "y": space["y"].sample(rng)} for _ in range(8)]
    scores = [score_of(c) for c in configs]
    # 40 samples over a 20x20 box: pure random's best is ~-3 in
    # expectation; TPE lands clearly closer to the optimum, and the
    # post-startup suggestions outperform the random phase
    assert max(scores) > -2.5, max(scores)
    assert max(scores[8:]) >= max(scores[:8])
    # each suggestion past the startup lies inside the box, drawn around
    # one of the good quarter of what was seen before it
    for i in range(8, 40):
        seen = sorted(zip(scores[:i], range(i)), reverse=True)
        good = [configs[j] for _, j in seen[:-(-i // 4)]]
        for dim in ("x", "y"):
            assert -10 <= configs[i][dim] <= 10
            assert min(abs(configs[i][dim] - g[dim]) for g in good) < 8.0
    # the same searcher under the Tuner
    tuner = tune.Tuner(
        objective, param_space=space,
        tune_config=tune.TuneConfig(
            metric="score", mode="max", num_samples=40,
            search_alg=TPESearcher(n_startup=8), seed=3,
            # sequential: every suggestion sees every completed result
            max_concurrent_trials=1),
        run_config=run_cfg(name="tpe"))
    results = tuner.fit()
    assert not results.errors and len(results) == 40
    ran = [r.config for r in results]
    assert all(r.metrics["score"] == score_of(r.config) for r in results)
    assert all(c in ran for c in configs[:8])
    assert results.get_best_result().metrics["score"] == max(
        r.metrics["score"] for r in results)


def test_searcher_interface_basic_variant(run_cfg):
    from ray_tpu.tune import BasicVariantGenerator

    def objective(config):
        tune.report({"score": config["a"]})

    tuner = tune.Tuner(
        objective, param_space={"a": tune.choice([1, 2, 5])},
        tune_config=tune.TuneConfig(
            metric="score", mode="max", num_samples=6,
            search_alg=BasicVariantGenerator(), seed=0),
        run_config=run_cfg(name="bvg"))
    results = tuner.fit()
    assert len(results) == 6
    assert results.get_best_result().metrics["score"] == 5


def test_tpe_categorical_and_log(run_cfg):
    from ray_tpu.tune import TPESearcher

    def objective(config):
        bonus = 5.0 if config["opt"] == "adam" else 0.0
        tune.report(
            {"score": bonus - abs(__import__("math").log10(config["lr"])
                                  + 3.0)})

    space = {"lr": tune.loguniform(1e-5, 1e-1),
             "opt": tune.choice(["sgd", "adam", "rmsprop"])}
    tuner = tune.Tuner(
        objective, param_space=space,
        tune_config=tune.TuneConfig(
            metric="score", mode="max", num_samples=30,
            search_alg=TPESearcher(n_startup=6), seed=1),
        run_config=run_cfg(name="tpelog"))
    best = tuner.fit().get_best_result()
    assert best.config["opt"] == "adam"
    assert best.metrics["score"] > 4.0


def test_restore_with_searcher(run_cfg, tmp_path):
    """Interrupted searcher-driven experiment resumes with history intact
    and completes the remaining budget (verdict acceptance: no lost
    trials)."""
    from ray_tpu.tune import TPESearcher

    def objective(config):
        tune.report({"score": -(config["x"] - 1.0) ** 2})

    space = {"x": tune.uniform(-5, 5)}
    rc = run_cfg(name="restore_tpe")

    # phase 1: run a partial budget
    r1 = tune.Tuner(
        objective, param_space=space,
        tune_config=tune.TuneConfig(metric="score", mode="max",
                                    num_samples=6,
                                    search_alg=TPESearcher(n_startup=4),
                                    seed=0),
        run_config=rc).fit()
    assert len(r1) == 6
    exp_dir = os.path.join(rc.resolved_storage_path(), "restore_tpe")

    # phase 2: restore with a LARGER budget; the 6 finished trials must be
    # kept (not rerun) and only the delta executed
    tuner = tune.Tuner.restore(
        exp_dir, objective, param_space=space,
        tune_config=tune.TuneConfig(metric="score", mode="max",
                                    num_samples=10,
                                    search_alg=TPESearcher(n_startup=4),
                                    seed=0))
    r2 = tuner.fit()
    assert len(r2) == 10
    ids = [r.trial_id for r in r2]
    assert len(set(ids)) == 10
    # the original trials' results survived
    old = {r.trial_id: r.metrics.get("score") for r in r1}
    new = {r.trial_id: r.metrics.get("score") for r in r2}
    for tid, score in old.items():
        assert new[tid] == score


def _ckpt_objective_factory(optimum: float, max_steps: int):
    """Checkpointing objective: score grows with steps, capped by how
    close config['x'] is to the optimum — separates good configs only
    after enough budget, which is what bracket schedulers exploit."""
    def objective(config):
        import json as _json
        quality = 1.0 - abs(config["x"] - optimum)
        ckpt = tune.get_checkpoint()
        start = 0
        if ckpt:
            start = _json.load(
                open(os.path.join(ckpt.path, "s.json")))["step"] + 1
        for step in range(start, max_steps):
            d = os.path.join(tune.get_trial_dir(), f"c{step}")
            os.makedirs(d, exist_ok=True)
            _json.dump({"step": step},
                       open(os.path.join(d, "s.json"), "w"))
            tune.report({"score": quality * (step + 1),
                         "training_iteration": step + 1}, checkpoint=d)
    return objective


def test_hyperband_brackets_beat_random_budget(run_cfg):
    """HyperBand (reference: schedulers/hyperband.py): synchronized
    brackets pause at rungs and promote the top 1/eta. Same trial count
    as exhaustive random search, but the bad trials burn far less budget
    and the best config still wins."""
    objective = _ckpt_objective_factory(optimum=0.7, max_steps=9)
    xs = [0.05, 0.2, 0.35, 0.5, 0.68, 0.9, 0.15, 0.45, 0.72]
    sched = tune.HyperBandScheduler(max_t=9, reduction_factor=3)
    tuner = tune.Tuner(
        objective,
        param_space={"x": tune.grid_search(xs)},
        tune_config=tune.TuneConfig(metric="score", mode="max",
                                    scheduler=sched,
                                    max_concurrent_trials=3),
        run_config=run_cfg(name="hyperband"))
    grid = tuner.fit()
    assert not grid.errors
    best = grid.get_best_result()
    # the best configs (0.68 / 0.72) survive every rung
    assert abs(best.config["x"] - 0.7) < 0.05, best.config
    # budget: exhaustive = 9 trials x 9 iters = 81; brackets must cut
    # a large share of that
    total_iters = sum(t.iterations for t in grid._trials)
    assert total_iters < 65, total_iters


def test_bohb_beats_random_search(run_cfg):
    """BOHB = HyperBandForBOHB + the TPE-based BOHBSearcher (reference:
    schedulers/hb_bohb.py + TuneBOHB). The searcher's rule, at a fixed seed
    and with no runtime under it: it models the deepest budget that has
    enough observations (a trial stopped at a low rung scores low because
    of its budget, not its config) and proposes beside that budget's best.
    The run through the Tuner, three trials at a time under the brackets,
    is held to the path: every trial of both searchers gets a config from
    the space and ends without an error, at a rung or at the last step."""
    objective = _ckpt_objective_factory(optimum=0.37, max_steps=6)
    n = 14
    space = {"x": tune.uniform(0.0, 1.0)}

    # the rule alone: six trials stopped at 2 steps, all far from the
    # optimum; five that ran all 6, the best at 0.36 and 0.2
    searcher = tune.BOHBSearcher(n_startup=5)
    searcher.set_experiment(space, "score", "max", n, 5)
    shallow = [0.9, 0.8, 0.95, 0.7, 0.85, 0.6]
    deep = [0.36, 0.1, 0.75, 0.55, 0.2]
    for i, (x, steps) in enumerate([(x, 2) for x in shallow]
                                   + [(x, 6) for x in deep]):
        searcher.register(f"t{i}", {"x": x})
        searcher.on_trial_complete(f"t{i}", {
            "score": (1.0 - abs(x - 0.37)) * steps,
            "training_iteration": steps})
    assert sorted(searcher._by_budget) == [2, 6]
    proposed = searcher.suggest("t11")["x"]
    assert sorted(o[0][("x",)] for o in searcher._obs) == sorted(deep)
    # two good points of five (gamma 0.25), a bandwidth of 0.07 from their
    # spread: the proposal lies within three of them of one
    assert min(abs(proposed - good) for good in (0.36, 0.2)) < 0.21, proposed
    # with three at the deepest budget (fewer than it asks for) it falls
    # back to the budget below
    few = tune.BOHBSearcher(n_startup=8)
    few.set_experiment(space, "score", "max", n, 5)
    for i, (x, steps) in enumerate([(x, 2) for x in shallow]
                                   + [(x, 6) for x in deep[:3]]):
        few.register(f"t{i}", {"x": x})
        few.on_trial_complete(f"t{i}", {"score": 1.0 - abs(x - 0.37),
                                        "training_iteration": steps})
    few.suggest("t9")
    assert sorted(o[0][("x",)] for o in few._obs) == sorted(shallow)

    def run(search_alg, name):
        tuner = tune.Tuner(
            objective, param_space=space,
            tune_config=tune.TuneConfig(
                metric="score", mode="max", num_samples=n,
                search_alg=search_alg,
                scheduler=tune.HyperBandForBOHB(max_t=6,
                                                reduction_factor=3),
                max_concurrent_trials=3, seed=5),
            run_config=run_cfg(name=name))
        grid = tuner.fit()
        assert not grid.errors
        trials = [t for t in grid._trials if t.config]
        assert len(trials) == n
        assert all(0.0 <= t.config["x"] <= 1.0 for t in trials)
        assert all(1 <= t.iterations <= 6 for t in trials)
        return grid

    bohb = run(tune.BOHBSearcher(n_startup=5), "bohb")
    run(tune.BasicVariantGenerator(), "bohb_rand")
    # a trial's score is its config's quality times the steps it was given
    for t in bohb._trials:
        assert t.last_result["score"] == pytest.approx(
            (1.0 - abs(t.config["x"] - 0.37)) * t.iterations)


def test_pb2_learns_better_configs(run_cfg):
    """PB2 (reference: schedulers/pb2.py): GP-UCB explore. The
    population's bad trials adopt model-proposed configs; the final best
    score must beat what the initial population could produce alone."""
    def objective(config):
        import json as _json
        ckpt = tune.get_checkpoint()
        w, start = 0.0, 0
        if ckpt:
            st = _json.load(open(os.path.join(ckpt.path, "w.json")))
            w, start = st["w"], st["step"] + 1
        for step in range(start, 16):
            lr = config["lr"]
            w += 1.0 - abs(lr - 0.6)   # best gain at lr=0.6
            d = os.path.join(tune.get_trial_dir(), f"c{step}")
            os.makedirs(d, exist_ok=True)
            _json.dump({"w": w, "step": step},
                       open(os.path.join(d, "w.json"), "w"))
            tune.report({"score": w, "training_iteration": step + 1},
                        checkpoint=d)

    sched = tune.PB2(hyperparam_bounds={"lr": [0.0, 1.0]},
                     perturbation_interval=3, seed=3)
    tuner = tune.Tuner(
        objective,
        param_space={"lr": tune.grid_search([0.05, 0.95, 0.3, 0.85])},
        tune_config=tune.TuneConfig(metric="score", mode="max",
                                    scheduler=sched,
                                    max_concurrent_trials=2),
        run_config=run_cfg(name="pb2"))
    grid = tuner.fit()
    assert not grid.errors
    best = grid.get_best_result().metrics["score"]
    # the best INITIAL config (0.3: gain 0.7/step) alone gives 11.2
    # over 16 steps; exploit+GP-explore must end above it
    assert best > 11.3, best


def test_resource_changing_scheduler_reallocates(run_cfg):
    """ResourceChangingScheduler (reference:
    tune/schedulers/resource_changing_scheduler.py): after the allocation
    function raises a trial's request, the trial checkpoints, restarts
    under the new resources, and resumes from where it left off."""
    def objective(config):
        import json as _json
        ckpt = tune.get_checkpoint()
        start, restarts = 0, 0
        if ckpt:
            st = _json.load(open(os.path.join(ckpt.path, "s.json")))
            start, restarts = st["step"] + 1, st["restarts"] + 1
        for step in range(start, 6):
            d = os.path.join(tune.get_trial_dir(), f"c{step}")
            os.makedirs(d, exist_ok=True)
            _json.dump({"step": step, "restarts": restarts},
                       open(os.path.join(d, "s.json"), "w"))
            tune.report({"score": float(step), "restarts": restarts,
                         "training_iteration": step + 1}, checkpoint=d)

    def grow_after_two(total_cpus, num_running, trial, base):
        if trial.last_result.get("training_iteration", 0) >= 2:
            return {"num_cpus": 2}
        return dict(base)

    sched = tune.ResourceChangingScheduler(
        resources_allocation_function=grow_after_two)
    tuner = tune.Tuner(
        objective,
        param_space={"x": tune.grid_search([0])},
        tune_config=tune.TuneConfig(metric="score", mode="max",
                                    scheduler=sched),
        run_config=run_cfg(name="rcs"))
    grid = tuner.fit()
    assert not grid.errors
    t = grid._trials[0]
    # completed all steps, under the grown allocation, via exactly one
    # checkpointed restart (steps are not re-run from scratch)
    assert t.last_result["score"] == 5.0
    assert t.resources == {"num_cpus": 2}
    assert t.last_result["restarts"] == 1
    assert sched._realloc_count == 1


def test_evenly_distribute_cpus_policy():
    from ray_tpu.tune.schedulers import evenly_distribute_cpus

    base = {"num_cpus": 1}
    assert evenly_distribute_cpus(8.0, 2, None, base)["num_cpus"] == 4
    # never below the base request
    assert evenly_distribute_cpus(2.0, 4, None, base)["num_cpus"] == 1


def test_resource_changing_wraps_pbt_protocol():
    """Wrapping PBT must forward its exploit protocol: the controller
    reads AND assigns pending_exploit on the scheduler it holds, and
    calls explore() — all three must reach the wrapped scheduler."""
    pbt = tune.PopulationBasedTraining(
        perturbation_interval=2,
        hyperparam_mutations={"lr": tune.uniform(0.1, 1.0)}, seed=0)
    rcs = tune.ResourceChangingScheduler(base_scheduler=pbt)
    rcs.set_experiment("score", "max")
    pbt.pending_exploit = {"donor_id": "t1"}
    assert rcs.pending_exploit == {"donor_id": "t1"}
    rcs.pending_exploit = None
    assert pbt.pending_exploit is None
    out = rcs.explore({"lr": 0.5})
    assert 0.1 <= out["lr"] <= 1.0


def test_gp_searcher_beats_random_on_quadratic(run_cfg):
    """In-tree GP/EI Bayesian optimization (reference role:
    tune/search/bayesopt): on a smooth 2-D objective it must beat random
    search at equal budget and sharpen after the random startup phase."""
    from ray_tpu.tune import BasicVariantGenerator, GPSearcher

    def objective(config):
        x, y = config["x"], config["y"]
        tune.report({"score": -(x - 3.0) ** 2 - (y + 1.0) ** 2})

    space = {"x": tune.uniform(-10, 10), "y": tune.uniform(-10, 10)}

    def run(alg, name):
        tuner = tune.Tuner(
            objective, param_space=space,
            tune_config=tune.TuneConfig(
                metric="score", mode="max", num_samples=30,
                search_alg=alg, seed=5, max_concurrent_trials=1),
            run_config=run_cfg(name=name))
        return tuner.fit()

    gp = run(GPSearcher(n_startup=6), "gp")
    rnd = run(BasicVariantGenerator(), "gp-rnd")
    gp_best = gp.get_best_result().metrics["score"]
    rnd_best = rnd.get_best_result().metrics["score"]
    assert gp_best > rnd_best, (gp_best, rnd_best)
    # 30 random samples over the 20x20 box land ~-3 in expectation; the
    # GP must get close to the optimum
    assert gp_best > -0.5, gp_best
    scores = [r.metrics["score"] for r in gp if r.metrics]
    assert max(scores[6:]) >= max(scores[:6]), scores
