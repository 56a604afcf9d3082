"""Autotuner tests (reference ``tests/unit/autotuning/test_autotuning.py``
territory): tuner ordering/early-stopping, space generation, override merging, and an
end-to-end in-process tune over a real engine."""

import json
import os

import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.autotuning import (Autotuner, AutotuningConfig, GridSearchTuner,
                                      ModelBasedTuner, RandomTuner, apply_overrides)

from tests.unit.simple_model import base_config, random_batches, simple_model


class TestTuners:
    EXPS = [{"x": i} for i in range(10)]

    def test_gridsearch_order(self):
        t = GridSearchTuner(list(self.EXPS))
        seen = []
        best = t.tune(lambda e: seen.append(e["x"]) or float(e["x"]), n_trials=10)
        assert seen == list(range(10))
        assert best == {"x": 9}

    def test_random_covers_all(self):
        t = RandomTuner(list(self.EXPS))
        seen = []
        t.tune(lambda e: seen.append(e["x"]) or 0.0, n_trials=100,
               early_stopping=None)
        assert sorted(seen) == list(range(10))

    def test_early_stopping(self):
        t = GridSearchTuner(list(self.EXPS))
        count = [0]

        def measure(e):
            count[0] += 1
            return -float(e["x"])  # first is best, rest never improve

        t.tune(measure, n_trials=100, early_stopping=3)
        assert count[0] == 4  # 1 best + 3 non-improving

    def test_infeasible_skipped(self):
        t = GridSearchTuner(list(self.EXPS))
        best = t.tune(lambda e: None if e["x"] < 9 else 1.0, n_trials=10)
        assert best == {"x": 9}

    def test_model_based_exploits(self):
        """After warmup, the KNN tuner should reach the optimum (x=7 peak) faster
        than exhaustive order."""
        exps = [{"x": i} for i in range(50)]
        t = ModelBasedTuner(exps, warmup=5, seed=1)
        order = []

        def measure(e):
            order.append(e["x"])
            return 100.0 - abs(e["x"] - 7) * 3.0

        best = t.tune(measure, n_trials=15, early_stopping=None)
        assert best["x"] == min(order, key=lambda x: abs(x - 7))
        assert abs(best["x"] - 7) <= 2  # homed in without trying all 50


class TestSpace:
    def _tuner(self, at_cfg=None, cfg_extra=None):
        cfg = base_config(batch_size=16, stage=0)
        cfg.update(cfg_extra or {})

        def engine_factory(overrides):
            merged = apply_overrides(cfg, overrides)
            eng, *_ = deepspeed_tpu.initialize(model=simple_model(16),
                                               config=merged)
            return eng

        def batch_factory(batch_size):
            return random_batches(1, batch_size)[0]

        return Autotuner(cfg, engine_factory, batch_factory,
                         autotuning_config=at_cfg)

    def test_space_generation(self):
        at = self._tuner(AutotuningConfig(
            max_train_micro_batch_size_per_gpu=8,
            tuning_space={"zero_optimization.stage": [0, 1]}))
        exps = at.tuning_space()
        micros = {e["train_micro_batch_size_per_gpu"] for e in exps}
        stages = {e["zero_optimization.stage"] for e in exps}
        assert stages == {0, 1}
        assert micros <= {1, 2, 4, 8}
        assert len(exps) == len(micros) * 2

    def test_apply_overrides(self):
        cfg = {"zero_optimization": {"stage": 0}, "train_batch_size": 16,
               "gradient_accumulation_steps": 2}
        out = apply_overrides(cfg, {"zero_optimization.stage": 3,
                                    "train_micro_batch_size_per_gpu": 4})
        assert out["zero_optimization"]["stage"] == 3
        assert out["train_micro_batch_size_per_gpu"] == 4
        assert "gradient_accumulation_steps" not in out
        assert cfg["zero_optimization"]["stage"] == 0  # original untouched

    def test_memory_pruning(self):
        at = self._tuner(AutotuningConfig(max_train_micro_batch_size_per_gpu=2,
                                          tuning_space={}))
        at.hbm_bytes = 10  # absurdly small: everything must prune
        at.model_info = {"num_params": 10 ** 6}
        assert at._measure({"zero_optimization.stage": 0,
                            "train_micro_batch_size_per_gpu": 1}) is None
        assert at.records[-1]["status"] == "pruned"


class TestExperimentScheduler:
    """Subprocess experiment scheduler (reference autotuning/scheduler.py
    ResourceManager): crash isolation, timeouts, parallel slots."""

    def _sched(self, tmp_path, **kw):
        from deepspeed_tpu.autotuning.scheduler import ExperimentScheduler
        kw.setdefault("results_dir", str(tmp_path))
        return ExperimentScheduler("tests.unit.autotuning.fake_runner",
                                   {"train_batch_size": 8}, **kw)

    def test_crash_isolation_and_results(self, tmp_path):
        """A hard-exiting experiment (os._exit — the failure the in-process
        measure path cannot survive) yields a failed record; the others finish."""
        sched = self._sched(tmp_path, timeout_s=60)
        recs = sched.run([{"behavior": "ok", "value": 2.0},
                          {"behavior": "crash"},
                          {"behavior": "ok", "value": 5.0}])
        assert [r["status"] for r in recs] == ["ok", "failed", "ok"]
        assert recs[1]["returncode"] == 9
        assert recs[2]["throughput"] == 5.0
        assert recs[0]["seen_config"] == ["train_batch_size"]

    def test_timeout_kills_hung_experiment(self, tmp_path):
        # timeout must exceed interpreter startup (the runner imports jax, ~5 s)
        # while staying far below the runner's 120 s hang
        sched = self._sched(tmp_path, timeout_s=15)
        recs = sched.run([{"behavior": "hang"}, {"behavior": "ok", "value": 1.0}])
        assert recs[0]["status"] == "timeout"
        assert recs[0]["wall_s"] >= 15
        assert recs[1]["status"] == "ok"

    def test_parallel_slots_with_env_overlays(self, tmp_path):
        sched = self._sched(
            tmp_path, timeout_s=60, max_parallel=2,
            slot_envs=[{"DS_TPU_SLOT_TAG": "a"}, {"DS_TPU_SLOT_TAG": "b"}])
        recs = sched.run([{"behavior": "ok", "value": v} for v in (1, 2, 3, 4)])
        assert all(r["status"] == "ok" for r in recs)
        assert {r["slot_tag"] for r in recs} == {"a", "b"}

    def test_autotuner_subprocess_mode_selects_best(self, tmp_path):
        """End-to-end: Autotuner with experiment_runner set schedules all
        surviving experiments and picks the best by metric, surviving a crash."""
        cfg = {"train_batch_size": 8,
               "autotuning": {"tuning_space": {
                   "behavior": ["ok", "crash"], "value": [2.0, 7.0]}}}
        at_cfg = AutotuningConfig(
            enabled=True, results_dir=str(tmp_path),
            experiment_runner="tests.unit.autotuning.fake_runner",
            experiment_timeout_s=60, max_parallel_experiments=2,
            min_train_micro_batch_size_per_gpu=1,
            max_train_micro_batch_size_per_gpu=1,
            tuning_space={"behavior": ["ok", "crash"], "value": [2.0, 7.0]})
        at = Autotuner(cfg, lambda ovr: (_ for _ in ()).throw(
            AssertionError("in-process factory must not run in subprocess mode")),
            lambda bs: None, at_cfg)
        best = at.tune()
        assert best is not None and best["behavior"] == "ok"
        assert best["value"] == 7.0
        results = json.loads((tmp_path / "autotuning_results.json").read_text())
        statuses = sorted(r["status"] for r in results["records"])
        assert statuses.count("failed") == 2      # the two crash configs
        assert statuses.count("ok") == 2


class TestEndToEnd:
    def test_tune_simple_model(self, tmp_path):
        cfg = base_config(batch_size=16, stage=0)

        def engine_factory(overrides):
            merged = apply_overrides(cfg, overrides)
            eng, *_ = deepspeed_tpu.initialize(model=simple_model(16),
                                               config=merged)
            return eng

        at_cfg = AutotuningConfig(
            enabled=True, start_profile_step=1, end_profile_step=3,
            max_train_micro_batch_size_per_gpu=2,
            num_tuning_micro_batch_sizes=2,
            results_dir=str(tmp_path),
            tuning_space={"zero_optimization.stage": [0, 1]})
        at = Autotuner(cfg, engine_factory,
                       lambda bs: random_batches(1, bs)[0], at_cfg)
        best = at.tune()
        assert best is not None
        assert best["zero_optimization.stage"] in (0, 1)
        results = json.loads((tmp_path / "autotuning_results.json").read_text())
        assert results["best"] == best
        ok = [r for r in results["records"] if r["status"] == "ok"]
        assert len(ok) >= 2
        assert all(r["throughput"] > 0 for r in ok)
        assert results["model_info"]["num_params"] == 544

    def test_real_runner_subprocess(self, tmp_path):
        """The REAL experiment runner (deepspeed_tpu.autotuning.runner): builds
        an actual engine in the subprocess from the merged config's model block,
        measures steps, and the tuner picks a winner from real measurements —
        the reference's launch-a-training-job lane (autotuner.py:39)."""
        base = {
            # divisible for both 1 real device and the 8-device CPU-mesh flag
            # the runner child inherits (micro 4 × dp {1,8} | 32)
            "train_batch_size": 32,
            "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
            "zero_optimization": {"stage": 1},
            "steps_per_print": 10**9,
            "model": {"factory": "deepspeed_tpu.models:gpt2_model",
                      "config_class": "deepspeed_tpu.models:GPT2Config",
                      "config": {"vocab_size": 128, "n_positions": 32,
                                 "n_embd": 32, "n_layer": 2, "n_head": 4,
                                 "dropout": 0.0},
                      "sample_seq_len": 32, "measure_steps": 2,
                      "warmup_steps": 1},
        }
        at_cfg = AutotuningConfig(
            enabled=True, results_dir=str(tmp_path), metric="throughput",
            experiment_runner="deepspeed_tpu.autotuning.runner",
            experiment_timeout_s=300, max_parallel_experiments=1,
            min_train_micro_batch_size_per_gpu=4,
            max_train_micro_batch_size_per_gpu=4,
            tuning_space={"model.config.remat": [False, True]},
            model_info={"num_params": 10000})
        best = Autotuner(base, lambda o: (_ for _ in ()).throw(
            AssertionError("in-process factory must not run")),
            lambda bs: None, at_cfg).tune()
        assert best is not None and "model.config.remat" in best
        results = json.loads((tmp_path / "autotuning_results.json").read_text())
        ok = [r for r in results["records"] if r["status"] == "ok"]
        assert len(ok) == 2
        assert all(r["throughput"] > 0 and r["loss"] == r["loss"] for r in ok)
