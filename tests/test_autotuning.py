"""Autotuner tests (ref: tests/unit/test_autotuning.py — experiment
generation/pruning checks without full tuning jobs, plus a small real
tune run here since experiments are in-process)."""

import json
import os

import numpy as np
import pytest

from deepspeed_tpu.autotuning import (
    Autotuner, Experiment, GridSearchTuner, ModelBasedTuner, RandomTuner,
    ResourceManager)
from deepspeed_tpu.autotuning.cost_model import RidgeCostModel
from deepspeed_tpu.autotuning.utils import (
    canonical_name, deep_update, dict_to_feature, flatten, gen_combinations)
from tests.simple_model import random_batch, simple_model_loss, simple_model_params

HIDDEN = 16


def _autotuner(tmp_path, base_overrides=None, at_overrides=None):
    base = {
        "train_batch_size": 16,
        "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
        "steps_per_print": 10000,
        "autotuning": {"num_tuning_steps": 1, "zero_stages": [0, 1],
                       "tuner_type": "gridsearch"},
    }
    base.update(base_overrides or {})
    base["autotuning"].update(at_overrides or {})
    params = simple_model_params(hidden_dim=HIDDEN, nlayers=1)
    return Autotuner(simple_model_loss, params, base,
                     make_batch=lambda bs: random_batch(bs, HIDDEN),
                     results_dir=str(tmp_path / "results"))


# ------------------------------------------------------------- utils

def test_gen_combinations():
    space = {"zero_optimization": {"stage": [0, 1]},
             "train_micro_batch_size_per_gpu": [1, 2, 4]}
    combos = gen_combinations(space)
    assert len(combos) == 6
    assert {"zero_optimization": {"stage": 1},
            "train_micro_batch_size_per_gpu": 4} in combos


def test_flatten_and_features():
    flat = flatten({"a": {"b": 2, "c": True}, "d": "x"})
    assert flat["a_b"] == 2 and flat["a_c"] is True
    feat = dict_to_feature(flat, ["a_b", "a_c", "d", "missing"])
    assert feat[0] == 2.0 and feat[1] == 1.0 and feat[3] == 0.0


def test_deep_update_no_mutation():
    base = {"zero_optimization": {"stage": 0}, "x": 1}
    out = deep_update(base, {"zero_optimization": {"stage": 3}})
    assert out["zero_optimization"]["stage"] == 3
    assert base["zero_optimization"]["stage"] == 0


def test_canonical_name():
    assert canonical_name({"zero_optimization": {"stage": 2},
                           "train_micro_batch_size_per_gpu": 4,
                           "gradient_accumulation_steps": 2}) == "z2_mbs4_gas2"


# --------------------------------------------------------- cost model

def test_ridge_cost_model_learns_quadratic():
    rng = np.random.default_rng(0)
    xs = rng.uniform(0, 4, (40, 2))
    ys = 3 * xs[:, 0] - xs[:, 1] ** 2 + 5
    m = RidgeCostModel(alpha=1e-6)
    m.fit(xs, ys)
    pred = m.predict(xs)
    assert float(np.max(np.abs(pred - ys))) < 0.1


# ------------------------------------------------------------- tuners

def _fake_rm(scores):
    """runner scores configs by mbs (bigger better) via lookup."""
    return ResourceManager(
        lambda cfg: scores[cfg["train_micro_batch_size_per_gpu"]])


def _exps(mbs_list):
    return [Experiment(f"mbs{m}", {"train_micro_batch_size_per_gpu": m,
                                   "zero_optimization": {"stage": 0}})
            for m in mbs_list]


@pytest.mark.parametrize("tuner_cls", [GridSearchTuner, RandomTuner,
                                       ModelBasedTuner])
def test_tuners_find_best(tuner_cls):
    scores = {1: 10.0, 2: 25.0, 4: 40.0, 8: 30.0}
    rm = _fake_rm(scores)
    tuner = tuner_cls(_exps(scores.keys()), rm, "throughput")
    n = tuner.tune(sample_size=1, n_trials=10)
    assert n == 4
    assert tuner.best_exp.ds_config["train_micro_batch_size_per_gpu"] == 4
    assert tuner.best_metric_val == 40.0


def test_tuner_early_stopping():
    scores = {m: 100.0 - m for m in [1, 2, 3, 4, 5, 6, 7, 8]}  # first is best
    rm = _fake_rm(scores)
    tuner = GridSearchTuner(_exps(scores.keys()), rm, "throughput")
    n = tuner.tune(sample_size=1, n_trials=100, early_stopping=3)
    assert n < 8  # stopped before exhausting the space


def test_failed_experiment_recorded():
    def runner(cfg):
        raise MemoryError("oom")
    rm = ResourceManager(runner)
    rm.schedule_experiments(_exps([1]))
    rm.run()
    assert rm.finished_experiments[0].error is not None
    assert rm.best() is None


# ----------------------------------------------------------- autotuner

def test_memory_model_pruning(tmp_path, devices):
    at = _autotuner(tmp_path)
    at.model_info_profile_run()
    assert at.model_info["num_params"] > 0
    m0 = at.get_instantiation_memory_required_per_gpu(0)
    m3 = at.get_instantiation_memory_required_per_gpu(3)
    assert m3 < m0  # sharding reduces per-chip state

    # per-stage state bytes follow the 12/4/6-per-param accounting
    n = at.model_info["num_params"]
    assert m0 == pytest.approx((12 + 4 + 4 + 2) * n)


def test_generate_experiments_respects_global_batch(tmp_path, devices):
    at = _autotuner(tmp_path, at_overrides={"micro_batch_sizes": [1, 2, 8]})
    exps = at._generate_experiments(zero_stage=0)
    dp = 8  # conftest virtual devices
    for e in exps:
        cfg = e.ds_config
        assert cfg["train_micro_batch_size_per_gpu"] * dp * \
            cfg["gradient_accumulation_steps"] == 16
    # mbs=8 -> 8*8=64 > 16 global: excluded
    assert all(e.ds_config["train_micro_batch_size_per_gpu"] != 8
               for e in exps)


def test_tune_end_to_end(tmp_path, devices):
    """Small real tune: builds engines in-process, writes optimal config
    (ref: autotuner.py:396 tune + ds_config_optimal output)."""
    at = _autotuner(tmp_path, at_overrides={"micro_batch_sizes": [1, 2]})
    best = at.tune()
    assert best is not None
    assert best["train_batch_size"] == 16
    opt_path = os.path.join(str(tmp_path / "results"), "ds_config_optimal.json")
    with open(opt_path) as f:
        saved = json.load(f)
    assert saved == best
    at.print_tuning_results()  # must not raise
    # experiment records were persisted
    assert any(f.endswith(".json") for f in os.listdir(tmp_path / "results"))


# ------------------------------------- subprocess experiment dispatch
# (the reference schedules every experiment as its own
#  job with failure capture — ref: autotuning/scheduler.py:35 run_job,
#  :183 parse_results; here that is SubprocessRunner + classified
#  ExperimentError kinds)

import sys

from deepspeed_tpu.autotuning import ExperimentError, SubprocessRunner


def test_subprocess_runner_success_and_config_file():
    """Default mode: ds_config lands in a temp JSON whose path is argv[-1]
    (the reference's per-job materialized ds_config.json)."""
    code = ("import json,sys; cfg=json.load(open(sys.argv[1])); "
            "print(json.dumps({'metric': cfg['mbs'] * 2.0}))")
    r = SubprocessRunner([sys.executable, "-c", code], timeout_s=60)
    assert r({"mbs": 4}) == 8.0


def test_subprocess_runner_classifies_timeout():
    r = SubprocessRunner([sys.executable, "-c",
                          "import time; time.sleep(30)"], timeout_s=1)
    with pytest.raises(ExperimentError) as ei:
        r({})
    assert ei.value.kind == "timeout"


def test_subprocess_runner_classifies_oom():
    code = ("import sys; sys.stderr.write('RESOURCE_EXHAUSTED: failed to "
            "allocate 9.9G\\n'); sys.exit(1)")
    r = SubprocessRunner([sys.executable, "-c", code], timeout_s=60)
    with pytest.raises(ExperimentError) as ei:
        r({})
    assert ei.value.kind == "oom"


def test_subprocess_runner_failures_dont_kill_the_sweep():
    """A hung + an OOMing + a healthy experiment: the loop finishes,
    records the two classified losses, and best() is the survivor."""
    flaky = {"hang": "import time; time.sleep(30)",
             "oom": ("import sys; sys.stderr.write('out of memory'); "
                     "sys.exit(1)"),
             "ok": "import json; print(json.dumps({'metric': 7.0}))"}
    r = SubprocessRunner(
        cmd_builder=lambda cfg: [sys.executable, "-c", flaky[cfg["kind"]]],
        timeout_s=3)
    rm = ResourceManager(r)
    rm.schedule_experiments(
        [Experiment(k, {"kind": k}) for k in ("hang", "oom", "ok")])
    rm.run()
    assert len(rm.finished_experiments) == 3
    errs = {e.name: e.error for e in rm.finished_experiments}
    assert "timeout" in errs["hang"] and "oom" in errs["oom"]
    assert rm.best().name == "ok" and rm.best().metric_val == 7.0
