"""Data-efficiency suite tests: curriculum scheduler (reference
``tests/unit/runtime/test_data_efficiency.py`` territory), random-LTD schedule +
token drop/restore, and the mmap indexed dataset round-trip."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import deepspeed_tpu
from deepspeed_tpu.runtime.data_pipeline import CurriculumScheduler, RandomLTDScheduler
from deepspeed_tpu.runtime.data_pipeline.data_routing.basic_layer import (
    random_ltd_layer, token_drop, token_restore)
from deepspeed_tpu.runtime.data_pipeline.data_sampling.indexed_dataset import (
    MMapIndexedDataset, MMapIndexedDatasetBuilder)

from tests.unit.simple_model import base_config, random_batches, simple_model


class TestCurriculumScheduler:
    def test_fixed_linear(self):
        s = CurriculumScheduler({
            "curriculum_type": "seqlen", "min_difficulty": 8, "max_difficulty": 64,
            "schedule_type": "fixed_linear",
            "schedule_config": {"total_curriculum_step": 100, "difficulty_step": 8}})
        assert s.get_current_difficulty() == 8
        d50 = s.update_difficulty(50)
        assert d50 == 8 + ((0.5 * 56) // 8) * 8 == 32
        assert s.update_difficulty(100) == 64
        assert s.update_difficulty(1000) == 64  # clamped

    def test_fixed_root(self):
        s = CurriculumScheduler({
            "min_difficulty": 8, "max_difficulty": 64,
            "schedule_type": "fixed_root",
            "schedule_config": {"total_curriculum_step": 100, "difficulty_step": 8,
                                "root_degree": 2}})
        # sqrt pacing reaches difficulty faster than linear early on
        assert s.get_difficulty(25) >= 8 + 0.5 * 56 - 8
        assert s.get_difficulty(100) == 64

    def test_fixed_discrete(self):
        s = CurriculumScheduler({
            "min_difficulty": 1, "max_difficulty": 3,
            "schedule_type": "fixed_discrete",
            "schedule_config": {"difficulty": [1, 2, 3], "max_step": [5, 10]}})
        assert s.get_difficulty(3) == 1
        assert s.get_difficulty(7) == 2
        assert s.get_difficulty(11) == 3

    def test_custom(self):
        s = CurriculumScheduler({
            "min_difficulty": 1, "max_difficulty": 10,
            "schedule_type": "custom"})
        s.set_custom_get_difficulty(lambda step: min(10, 1 + step // 2))
        assert s.update_difficulty(6) == 4

    def test_state_roundtrip(self):
        s = CurriculumScheduler({
            "min_difficulty": 8, "max_difficulty": 64,
            "schedule_type": "fixed_linear",
            "schedule_config": {"total_curriculum_step": 100, "difficulty_step": 8}})
        s.update_difficulty(50)
        state = s.get_state()
        s2 = CurriculumScheduler({
            "min_difficulty": 8, "max_difficulty": 64,
            "schedule_type": "fixed_linear",
            "schedule_config": {"total_curriculum_step": 100, "difficulty_step": 8}})
        s2.set_state(state)
        assert s2.get_current_difficulty() == s.get_current_difficulty()

    def test_engine_wiring(self):
        """Legacy curriculum_learning block creates a scheduler the engine advances."""
        cfg = base_config(batch_size=16, stage=0)
        cfg["curriculum_learning"] = {
            "enabled": True, "curriculum_type": "seqlen",
            "min_difficulty": 2, "max_difficulty": 10,
            "schedule_type": "fixed_linear",
            "schedule_config": {"total_curriculum_step": 4, "difficulty_step": 2}}
        eng, *_ = deepspeed_tpu.initialize(model=simple_model(16), config=cfg)
        assert eng.get_data_difficulty() == 2
        for b in random_batches(4, 16):
            eng.train_batch(b)
        assert eng.get_data_difficulty() == 10


class TestRandomLTD:
    def _sched(self):
        return RandomLTDScheduler({
            "total_layer_num": 12, "random_ltd_layer_num": 10,
            "global_batch_size": 4,
            "random_ltd_schedule": {
                "min_value": 16, "max_value": 128,
                "schedule_type": "fixed_linear",
                "schedule_config": {"total_layer_saving_step": 100,
                                    "seq_per_step": 16}}})

    def test_schedule_monotonic(self):
        s = self._sched()
        vals = [s.update_seq(step) for step in range(0, 120, 10)]
        assert vals[0] == 16 and vals[-1] == 128
        assert all(b >= a for a, b in zip(vals, vals[1:]))
        assert all(v % 16 == 0 for v in vals)

    def test_layer_token_accounting(self):
        s = self._sched()
        total = s.get_total_layer_tokens(10)
        # bounded between all-min and all-max consumption
        lo = 10 * 4 * (16 * 10 + 128 * 2)
        hi = 10 * 4 * 128 * 12
        assert lo <= total <= hi

    def test_token_drop_restore(self):
        x = jnp.arange(2 * 8 * 4, dtype=jnp.float32).reshape(2, 8, 4)
        short, idx = token_drop(x, jax.random.PRNGKey(0), kept_len=5)
        assert short.shape == (2, 5, 4)
        assert np.all(np.diff(np.asarray(idx)) > 0)  # sorted unique
        restored = token_restore(x, short * 10.0, idx)
        kept = np.asarray(idx)
        np.testing.assert_array_equal(np.asarray(restored[:, kept]),
                                      np.asarray(x[:, kept] * 10.0))
        dropped = [i for i in range(8) if i not in kept]
        np.testing.assert_array_equal(np.asarray(restored[:, dropped]),
                                      np.asarray(x[:, dropped]))

    def test_random_ltd_layer_full_length_passthrough(self):
        x = jnp.ones((2, 8, 4))
        out = random_ltd_layer(lambda h: h * 2.0, x, jax.random.PRNGKey(0),
                               kept_len=8)
        np.testing.assert_array_equal(np.asarray(out), 2.0 * np.asarray(x))


class TestIndexedDataset:
    def test_roundtrip(self, tmp_path):
        prefix = str(tmp_path / "corpus")
        builder = MMapIndexedDatasetBuilder(prefix, dtype=np.int32)
        docs = [[1, 2, 3, 4], [9, 8], [5, 5, 5, 5, 5, 5]]
        for d in docs:
            builder.add_item(d)
            builder.end_document()
        builder.finalize()

        assert MMapIndexedDataset.exists(prefix)
        ds = MMapIndexedDataset(prefix)
        assert len(ds) == 3
        for i, d in enumerate(docs):
            np.testing.assert_array_equal(ds[i], np.asarray(d, np.int32))
        np.testing.assert_array_equal(ds.sizes, [4, 2, 6])
        np.testing.assert_array_equal(ds.doc_idx, [0, 1, 2, 3])
        # partial reads
        np.testing.assert_array_equal(ds.get(2, offset=2, length=3), [5, 5, 5])

    def test_uint16_dtype(self, tmp_path):
        prefix = str(tmp_path / "c16")
        b = MMapIndexedDatasetBuilder(prefix, dtype=np.uint16)
        b.add_item([65535, 1])
        b.end_document()
        b.finalize()
        ds = MMapIndexedDataset(prefix)
        assert ds.dtype == np.uint16
        np.testing.assert_array_equal(ds[0], np.asarray([65535, 1], np.uint16))

    def test_bad_magic(self, tmp_path):
        bad = tmp_path / "bad.idx"
        bad.write_bytes(b"NOTMAGIC!" + b"\x00" * 32)
        (tmp_path / "bad.bin").write_bytes(b"")
        with pytest.raises(ValueError, match="magic"):
            MMapIndexedDataset(str(tmp_path / "bad"))


class TestDataSampler:
    def _cfg(self, difficulty_type="value", max_d=64):
        return {
            "seed": 7,
            "data_sampling": {
                "num_epochs": 4,
                "curriculum_learning": {
                    "enabled": True,
                    "curriculum_metrics": {
                        "seqlen": {
                            "difficulty_type": difficulty_type,
                            "clustering_type": "schedule_based",
                            "min_difficulty": 8, "max_difficulty": max_d,
                            "schedule_type": "fixed_linear",
                            "schedule_config": {"total_curriculum_step": 10,
                                                "difficulty_step": 8}}}}}}

    def test_value_based_gating(self):
        from deepspeed_tpu.runtime.data_pipeline.data_sampling.data_sampler import (
            DeepSpeedDataSampler)
        n = 256
        seqlens = np.random.default_rng(0).integers(1, 65, n)
        s = DeepSpeedDataSampler(self._cfg(), n, micro_batch_size=4,
                                 data_parallel_rank=0, data_parallel_size=2,
                                 gradient_accumulation_steps=2,
                                 metric_values={"seqlen": seqlens})
        it = iter(s)
        early = [next(it) for _ in range(4)]
        # early batches contain only easy samples (difficulty starts at 8)
        for mb in early[:2]:
            assert mb.shape == (4,)
            assert (seqlens[mb] <= 16).all(), seqlens[mb]
        # drain most of the schedule: difficulty reaches max, all samples eligible
        for _ in range(40):
            next(it)
        late = next(it)
        assert s.current_difficulties["seqlen"] == 64
        assert (seqlens[late] <= 64).all()

    def test_percentile_based_gating(self):
        from deepspeed_tpu.runtime.data_pipeline.data_sampling.data_sampler import (
            DeepSpeedDataSampler)
        n = 200
        scores = np.arange(n, dtype=np.float64)  # sample i has difficulty rank i
        cfg = self._cfg(difficulty_type="percentile", max_d=100)
        s = DeepSpeedDataSampler(cfg, n, micro_batch_size=8,
                                 data_parallel_rank=0, data_parallel_size=1,
                                 gradient_accumulation_steps=1,
                                 metric_values={"seqlen": scores})
        batch = s.get_next_global_batch()
        # first difficulty ~8th percentile -> only the lowest-ranked samples
        assert batch.max() < n * 0.2

    def test_ranks_partition_disjointly(self):
        from deepspeed_tpu.runtime.data_pipeline.data_sampling.data_sampler import (
            DeepSpeedDataSampler)
        n = 128
        vals = np.full(n, 1)
        cfg = self._cfg()

        def rank_stream(rank):
            s = DeepSpeedDataSampler(cfg, n, micro_batch_size=4,
                                     data_parallel_rank=rank,
                                     data_parallel_size=2,
                                     gradient_accumulation_steps=1,
                                     metric_values={"seqlen": vals})
            it = iter(s)
            return [next(it) for _ in range(3)]

        a, b = rank_stream(0), rank_stream(1)
        for mb_a, mb_b in zip(a, b):
            assert set(mb_a.tolist()).isdisjoint(mb_b.tolist())

    def test_state_roundtrip(self):
        from deepspeed_tpu.runtime.data_pipeline.data_sampling.data_sampler import (
            DeepSpeedDataSampler)
        n = 64
        vals = np.random.default_rng(1).integers(1, 65, n)
        mk = lambda: DeepSpeedDataSampler(self._cfg(), n, micro_batch_size=4,
                                          data_parallel_rank=0,
                                          data_parallel_size=1,
                                          gradient_accumulation_steps=1,
                                          metric_values={"seqlen": vals})
        a = mk()
        it = iter(a)
        for _ in range(5):
            next(it)
        state = a.state_dict()
        next_a = next(it)

        b = mk()
        b.load_state_dict(state)
        next_b = next(iter(b))
        np.testing.assert_array_equal(next_a, next_b)


# ------------------------------------------------------------------ DataAnalyzer
class TestDataAnalyzer:
    """Offline metric map/reduce (reference data_analyzer.py) feeding the curriculum
    sampler end to end."""

    def _dataset(self, n=128, seed=0):
        rng = np.random.default_rng(seed)
        lens = rng.integers(4, 64, n)
        return [{"input_ids": np.concatenate(
            [rng.integers(1, 50, l), np.zeros(64 - l, np.int64)])}
            for l in lens], lens

    def test_map_reduce_multiworker(self, tmp_path):
        from deepspeed_tpu.runtime.data_pipeline.data_sampling.data_analyzer import (
            DataAnalyzer, load_metric_values, metric_seqlen)
        data, lens = self._dataset()
        for w in range(3):   # three "processes" map their shards
            DataAnalyzer(data, ["seqlen"], [metric_seqlen(0)],
                         ["single_value_per_sample"], num_workers=3, worker_id=w,
                         batch_size=16, save_path=str(tmp_path)).run_map()
        DataAnalyzer(data, ["seqlen"], [metric_seqlen(0)],
                     ["single_value_per_sample"], num_workers=3,
                     save_path=str(tmp_path)).run_reduce()
        vals = load_metric_values(str(tmp_path))
        np.testing.assert_array_equal(vals["seqlen"], lens)
        # reverse index round-trips: clusters point at samples with that value
        rev = np.load(str(tmp_path / "seqlen" / "metric_to_sample.npz"))
        v0 = rev["values"][0]
        ids = rev["sample_order"][rev["starts"][0]:
                                  (rev["starts"][1] if len(rev["starts"]) > 1
                                   else None)]
        assert (lens[ids] == v0).all()

    def test_accumulate_metric(self, tmp_path):
        from deepspeed_tpu.runtime.data_pipeline.data_sampling.data_analyzer import (
            DataAnalyzer)
        data, lens = self._dataset(n=32)

        def total_tokens(batch):
            return np.asarray([int(np.sum(np.asarray(r["input_ids"]) != 0))
                               for r in batch]).sum()

        for w in range(2):
            DataAnalyzer(data, ["total"], [total_tokens],
                         ["accumulate_value_over_samples"], num_workers=2,
                         worker_id=w, save_path=str(tmp_path)).run_map()
        DataAnalyzer(data, ["total"], [total_tokens],
                     ["accumulate_value_over_samples"], num_workers=2,
                     save_path=str(tmp_path)).run_reduce()
        total = np.load(str(tmp_path / "total" / "metric_value.npy"))
        assert int(total) == int(lens.sum())

    def test_end_to_end_with_sampler(self, tmp_path):
        """analyze corpus → sampler consumes the files → difficulty schedule
        honoured."""
        from deepspeed_tpu.runtime.data_pipeline.data_sampling.data_analyzer import (
            DataAnalyzer, load_metric_values, metric_seqlen)
        from deepspeed_tpu.runtime.data_pipeline.data_sampling.data_sampler import (
            DeepSpeedDataSampler)
        data, lens = self._dataset(n=256)
        DataAnalyzer(data, ["seqlen"], [metric_seqlen(0)],
                     ["single_value_per_sample"],
                     save_path=str(tmp_path)).run_map()
        DataAnalyzer(data, ["seqlen"], [metric_seqlen(0)],
                     ["single_value_per_sample"],
                     save_path=str(tmp_path)).run_reduce()
        cfg = {"data_sampling": {"curriculum_learning": {
            "enabled": True,
            "curriculum_metrics": {"seqlen": {
                "difficulty_type": "value",
                "clustering_type": "schedule_based",
                "min_difficulty": 8, "max_difficulty": 64,
                "schedule_type": "fixed_linear",
                "schedule_config": {"total_curriculum_step": 10,
                                    "difficulty_step": 8}}}}}}
        s = DeepSpeedDataSampler(cfg, 256, micro_batch_size=4,
                                 data_parallel_rank=0, data_parallel_size=1,
                                 gradient_accumulation_steps=1,
                                 metric_values=load_metric_values(str(tmp_path)))
        it = iter(s)
        first = next(it)
        assert (lens[first] <= 8 + 8).all()   # schedule starts easy
