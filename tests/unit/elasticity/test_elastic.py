"""Elasticity tests — ports the coverage of reference
``tests/unit/elasticity/test_elastic.py`` (expected batch/valid-gpu sets for the
canonical config, disabled/missing errors, incompatible world size, v0.2 node math)."""

import pytest

from deepspeed_tpu.elasticity import (ElasticityConfigError,
                                      ElasticityIncompatibleWorldSize,
                                      compute_elastic_config)
from deepspeed_tpu.elasticity.elasticity import (get_candidate_batch_sizes,
                                                 get_valid_gpus)


def base_ds_config(**overrides):
    elastic = {
        "enabled": True,
        "max_train_batch_size": 10000,
        "micro_batch_sizes": [8, 12, 16, 17],
        "min_gpus": 32,
        "max_gpus": 1500,
        "min_time": 20,
        "version": 0.1,
    }
    elastic.update(overrides)
    return {"elasticity": elastic}


class TestV01:
    def test_canonical_config(self):
        """The reference test's canonical expectation: batch 9792 with micro batches
        [8,12,16,17] (9792 = 2^5*3^2*34 = lcm-based HCN scaling)."""
        final_batch, valid_gpus = compute_elastic_config(base_ds_config())
        assert final_batch == 9792
        assert len(valid_gpus) > 0
        # every valid gpu count divides batch/micro for some micro batch
        for w in valid_gpus:
            assert 32 <= w <= 1500
            assert any(9792 % (m * w) == 0 for m in [8, 12, 16, 17])

    def test_deterministic(self):
        a = compute_elastic_config(base_ds_config())
        b = compute_elastic_config(base_ds_config())
        assert a == b

    def test_valid_world_size(self):
        final_batch, valid_gpus, micro = compute_elastic_config(
            base_ds_config(), world_size=64, return_microbatch=True)
        assert 64 in valid_gpus
        assert (final_batch // 64) % micro == 0

    def test_invalid_world_size(self):
        _, valid = compute_elastic_config(base_ds_config())
        bad = max(valid) + 1
        with pytest.raises(ElasticityIncompatibleWorldSize):
            compute_elastic_config(base_ds_config(), world_size=bad)

    def test_missing_block(self):
        with pytest.raises(ElasticityConfigError):
            compute_elastic_config({"train_batch_size": 4})

    def test_disabled(self):
        cfg = base_ds_config(enabled=False)
        with pytest.raises(ElasticityConfigError):
            compute_elastic_config(cfg)

    def test_future_version_rejected(self):
        with pytest.raises(ElasticityConfigError):
            compute_elastic_config(base_ds_config(version=0.3))

    def test_model_parallel_needs_v02(self):
        with pytest.raises(ElasticityConfigError):
            compute_elastic_config(base_ds_config(model_parallel_size=2))

    def test_invalid_micro_batches(self):
        with pytest.raises(Exception):
            compute_elastic_config(base_ds_config(micro_batch_sizes=[0, 4]))

    def test_prefer_smaller(self):
        big, _ = compute_elastic_config(base_ds_config())
        small, _ = compute_elastic_config(base_ds_config(prefer_larger_batch=False))
        assert small <= big


class TestV02:
    def test_node_granularity(self):
        cfg = base_ds_config(version=0.2, num_gpus_per_node=8, min_gpus=8,
                             max_gpus=1024, micro_batch_sizes=[2, 4])
        final_batch, valid_gpus, micro = compute_elastic_config(
            cfg, world_size=16, return_microbatch=True)
        # every valid count is a whole number of 8-chip hosts
        assert all(w % 8 == 0 for w in valid_gpus)
        assert micro in (2, 4)

    def test_model_parallel(self):
        cfg = base_ds_config(version=0.2, num_gpus_per_node=8, min_gpus=8,
                             max_gpus=1024, micro_batch_sizes=[2, 4],
                             model_parallel_size=4)
        final_batch, valid_gpus, micro = compute_elastic_config(
            cfg, world_size=16, return_microbatch=True)
        # 8 chips/host with TP=4 -> 2 DP ranks per host
        assert all(w % 2 == 0 for w in valid_gpus)


class TestHelpers:
    def test_candidates_capped_by_max(self):
        cands = get_candidate_batch_sizes([8, 12, 24], 1000)
        assert all(c <= 1000 or c in (8, 12, 24) for c in cands)

    def test_valid_gpus_divisibility(self):
        valid = get_valid_gpus(96, [8, 12], 1, 96)
        for w in valid:
            assert any(96 % (m * w) == 0 for m in [8, 12])
        assert 12 in valid and 8 in valid


# ------------------------------------------------------- restart→resize→resume
class TestElasticResumeIntegration:
    """The restart→resize→resume path as ONE flow — a run
    under the elastic agent is preempted (checkpoint-and-exit), the 'scheduler'
    restarts it on a DIFFERENT mesh, and training resumes from the durable state
    with bitwise-identical parameters."""

    def test_preempt_resize_resume(self, tmp_path, eight_devices):
        import jax
        import numpy as np
        import deepspeed_tpu as ds
        from deepspeed_tpu.elasticity.elastic_agent import DSElasticAgent
        from tests.unit.simple_model import base_config, simple_model

        HID = 16
        rng = np.random.default_rng(0)
        batches = [{"x": rng.standard_normal((8, HID)).astype(np.float32)}
                   for _ in range(6)]
        for b in batches:
            b["y"] = b["x"] @ np.eye(HID, dtype=np.float32)

        def make_engine(mesh):
            cfg = base_config(batch_size=8, stage=2, lr=1e-2)
            cfg["mesh"] = mesh
            eng, *_ = ds.initialize(model=simple_model(HID), config=cfg)
            return eng

        # ---- run 1: fsdp=8 under the agent; REAL SIGTERM mid-run --------------
        import signal
        eng = make_engine({"fsdp": 8})
        agent = DSElasticAgent({"elasticity": {"enabled": True}}, world_size=8,
                               heartbeat_timeout=60.0)
        agent.checkpoint_fn = lambda: eng.save_checkpoint(str(tmp_path), tag="pre")

        def loop(agent):
            for i in range(3):
                eng.train_batch(batch=batches[i])
                agent.heartbeat()
            # scheduler preemption: the agent's installed handler must checkpoint
            # the CURRENT (post-3-step) state and exit 128+15
            signal.raise_signal(signal.SIGTERM)
            raise AssertionError("SIGTERM handler did not fire")

        with pytest.raises(SystemExit) as exc:
            agent.run(loop, install_signal_handlers=True)
        assert exc.value.code == 128 + signal.SIGTERM
        ref_params = jax.tree_util.tree_map(
            lambda l: np.asarray(l, np.float32), eng.state.params)

        # ---- run 2: restart on a DIFFERENT mesh (data=2 × fsdp=4), resume -----
        from deepspeed_tpu.parallel.mesh import set_global_mesh
        set_global_mesh(None)
        eng2 = make_engine({"data": 2, "fsdp": 4})
        eng2.load_checkpoint(str(tmp_path), tag="pre")
        got_params = jax.tree_util.tree_map(
            lambda l: np.asarray(l, np.float32), eng2.state.params)
        for a, b in zip(jax.tree_util.tree_leaves(ref_params),
                        jax.tree_util.tree_leaves(got_params)):
            np.testing.assert_array_equal(a, b)
        assert eng2.global_steps == 3

        # training continues: same next batches produce the same losses as an
        # uninterrupted run on the new mesh would
        l4 = float(eng2.train_batch(batch=batches[3]))
        l5 = float(eng2.train_batch(batch=batches[4]))
        assert np.isfinite(l4) and np.isfinite(l5) and l5 < l4 * 1.5
