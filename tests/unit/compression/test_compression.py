"""Compression suite tests (reference ``tests/unit/compression/test_compression.py``
territory): quantization numerics + STE grads, pruning mask structure, scheduler
gating/annealing, engine QAT integration, layer reduction."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import deepspeed_tpu
from deepspeed_tpu.compression import (CompressionConfig, channel_mask, head_mask,
                                       init_compression, quantize_dequantize,
                                       redundancy_clean, row_mask, sparse_mask,
                                       stacked_layer_reduction,
                                       student_initialization)

from tests.unit.simple_model import base_config, random_batches, simple_model
from deepspeed_tpu.utils.jax_compat import shard_map


class TestQuantize:
    def test_symmetric_error_bound(self):
        x = jnp.asarray(np.random.default_rng(0).standard_normal(256), jnp.float32)
        q = quantize_dequantize(x, bits=8, quantization_type="symmetric")
        step = float(jnp.max(jnp.abs(x))) / 127
        assert float(jnp.max(jnp.abs(q - x))) <= step * 0.5 + 1e-7

    def test_asymmetric_error_bound(self):
        x = jnp.asarray(np.random.default_rng(1).standard_normal(256) + 3.0,
                        jnp.float32)
        q = quantize_dequantize(x, bits=8, quantization_type="asymmetric")
        step = float(jnp.max(x) - jnp.min(x)) / 255
        assert float(jnp.max(jnp.abs(q - x))) <= step * 0.5 + 1e-6

    def test_fewer_bits_more_error(self):
        x = jnp.asarray(np.random.default_rng(2).standard_normal(512), jnp.float32)
        e8 = float(jnp.mean((quantize_dequantize(x, 8) - x) ** 2))
        e2 = float(jnp.mean((quantize_dequantize(x, 2) - x) ** 2))
        assert e2 > e8 * 10

    def test_grouped(self):
        # one outlier group must not destroy the rest's resolution
        x = np.random.default_rng(3).standard_normal(256).astype(np.float32)
        x[:16] *= 100
        xq1 = quantize_dequantize(jnp.asarray(x), 8, groups=1)
        xq16 = quantize_dequantize(jnp.asarray(x), 8, groups=16)
        tail = slice(16, None)
        assert float(jnp.mean((xq16[tail] - x[tail]) ** 2)) < \
            float(jnp.mean((xq1[tail] - x[tail]) ** 2))

    def test_ste_gradient_identity(self):
        x = jnp.asarray([0.3, -1.2, 2.4], jnp.float32)
        g = jax.grad(lambda v: jnp.sum(quantize_dequantize(v, 4) * 2.0))(x)
        np.testing.assert_allclose(np.asarray(g), 2.0)

    def test_stochastic_rounding_unbiased(self):
        x = jnp.full((1,), 0.3, jnp.float32)
        outs = [float(quantize_dequantize(
            x, 2, stochastic=True, rng=jax.random.PRNGKey(i))[0])
            for i in range(300)]
        assert abs(np.mean(outs) - 0.3) < 0.1  # between the two levels, mean ≈ x


class TestMasks:
    def test_sparse_ratio(self):
        w = jnp.asarray(np.random.default_rng(0).standard_normal((32, 32)),
                        jnp.float32)
        m = sparse_mask(w, 0.25)
        assert abs(float(m.mean()) - 0.25) < 0.05
        # kept entries are the largest magnitudes (top 25% of |N(0,1)| holds ~52% of
        # total L1 mass)
        assert float(jnp.abs(w * m).sum()) > 0.45 * float(jnp.abs(w).sum())
        kept_min = float(jnp.min(jnp.where(m > 0, jnp.abs(w), jnp.inf)))
        dropped_max = float(jnp.max(jnp.where(m == 0, jnp.abs(w), -jnp.inf)))
        assert kept_min >= dropped_max

    def test_row_mask(self):
        w = jnp.asarray(np.random.default_rng(1).standard_normal((16, 8)),
                        jnp.float32)
        m = row_mask(w, 0.5)
        assert m.shape == (16, 1)
        per_row = np.asarray(m).reshape(-1)
        assert per_row.sum() == 8
        assert set(np.unique(per_row)) <= {0.0, 1.0}

    def test_head_mask(self):
        w = jnp.asarray(np.random.default_rng(2).standard_normal((32, 8)),
                        jnp.float32)
        m = head_mask(w, 0.5, num_heads=4)
        assert m.shape == (32, 1)
        blocks = np.asarray(m).reshape(4, 8)
        # head granular: each 8-row block all-on or all-off; half on
        assert all(b.min() == b.max() for b in blocks)
        assert sum(b[0] for b in blocks) == 2

    def test_channel_mask(self):
        w = jnp.asarray(np.random.default_rng(3).standard_normal((8, 16, 3, 3)),
                        jnp.float32)
        m = channel_mask(w, 0.5)
        assert m.shape == (1, 16, 1, 1)
        assert np.asarray(m).sum() == 8


def _wq_config(start_bits=8, target_bits=8, offset=0, period=1):
    return {"weight_quantization": {
        "shared_parameters": {"enabled": True, "schedule_offset": offset,
                              "quantize_groups": 4},
        "different_groups": {"wq1": {"params": {
            "start_bits": start_bits, "target_bits": target_bits,
            "quantization_period": period}, "modules": ["*"]}}}}


class TestScheduler:
    def test_offset_gating(self):
        params = {"w0": jnp.ones((8, 8)) * 0.37}
        sched = init_compression(params, {"compression_training":
                                          _wq_config(offset=10)})
        before = sched.qat(params, jnp.int32(5))
        np.testing.assert_allclose(np.asarray(before["w0"]), 0.37, rtol=1e-6)
        # 8-bit quantization of a constant tensor is exact; use a varied tensor
        varied = {"w0": jnp.linspace(-1, 1, 64).reshape(8, 8)}
        sched2 = init_compression(varied, {"compression_training":
                                           _wq_config(start_bits=2, target_bits=2,
                                                      offset=10)})
        assert np.allclose(np.asarray(sched2.qat(varied, jnp.int32(5))["w0"]),
                           np.asarray(varied["w0"]))
        assert not np.allclose(np.asarray(sched2.qat(varied, jnp.int32(10))["w0"]),
                               np.asarray(varied["w0"]))

    def test_bits_anneal(self):
        params = {"w": jnp.linspace(-1, 1, 64).reshape(8, 8)}
        cfg = {"compression_training": _wq_config(start_bits=8, target_bits=2,
                                                  period=100)}
        sched = init_compression(params, cfg)
        err_early = float(jnp.mean(
            (sched.qat(params, jnp.int32(0))["w"] - params["w"]) ** 2))
        err_late = float(jnp.mean(
            (sched.qat(params, jnp.int32(500))["w"] - params["w"]) ** 2))
        assert err_late > err_early * 10

    def test_module_scope_matching(self):
        params = {"attn": {"w": jnp.linspace(-1, 1, 16).reshape(4, 4)},
                  "mlp": {"w": jnp.linspace(-1, 1, 16).reshape(4, 4)}}
        cfg = _wq_config(start_bits=2, target_bits=2)
        cfg["weight_quantization"]["different_groups"]["wq1"]["modules"] = ["attn"]
        sched = init_compression(params, {"compression_training": cfg})
        out = sched.qat(params, jnp.int32(0))
        assert not np.allclose(np.asarray(out["attn"]["w"]),
                               np.asarray(params["attn"]["w"]))
        np.testing.assert_array_equal(np.asarray(out["mlp"]["w"]),
                                      np.asarray(params["mlp"]["w"]))

    def test_biases_untouched(self):
        params = {"w": jnp.linspace(-1, 1, 16).reshape(4, 4),
                  "b": jnp.linspace(-1, 1, 4)}
        sched = init_compression(params, {"compression_training":
                                          _wq_config(start_bits=2, target_bits=2)})
        out = sched.qat(params, jnp.int32(0))
        np.testing.assert_array_equal(np.asarray(out["b"]), np.asarray(params["b"]))


class TestEngineIntegration:
    def test_qat_training(self):
        cfg = base_config(batch_size=16, stage=0)
        cfg["compression_training"] = _wq_config(start_bits=8, target_bits=8)
        eng, *_ = deepspeed_tpu.initialize(model=simple_model(16), config=cfg)
        assert eng._compression is not None and eng._compression.active
        losses = [float(eng.train_batch(b)) for b in random_batches(3, 16)]
        assert np.isfinite(losses).all()

    def test_redundancy_clean(self):
        params = {"w": jnp.asarray(
            np.random.default_rng(0).standard_normal((16, 16)), jnp.float32)}
        cfg = {"compression_training": {"sparse_pruning": {
            "shared_parameters": {"enabled": True, "schedule_offset": 0,
                                  "method": "l1"},
            "different_groups": {"sp1": {"params": {"dense_ratio": 0.5},
                                         "modules": ["*"]}}}}}
        cleaned = redundancy_clean(params, cfg)
        zeros = float((np.asarray(cleaned["w"]) == 0).mean())
        assert abs(zeros - 0.5) < 0.1


class TestLayerReduction:
    def test_student_initialization(self):
        teacher = {"encoder": {"layer": {str(i): {"w": jnp.full((2, 2), float(i))}
                                         for i in range(12)}}}
        student = {"encoder": {"layer": {str(i): {"w": jnp.zeros((2, 2))}
                                         for i in range(3)}}}
        cfg = {"compression_training": {"layer_reduction": {
            "enabled": True, "keep_number_layer": 3,
            "module_name_prefix": "encoder.layer",
            "teacher_layer": [2, 6, 10]}}}
        out = student_initialization(student, teacher, cfg)
        for i, t in enumerate([2, 6, 10]):
            np.testing.assert_array_equal(
                np.asarray(out["encoder"]["layer"][str(i)]["w"]), float(t))

    def test_stacked_reduction(self):
        stack = {"w": jnp.arange(12, dtype=jnp.float32)[:, None, None]
                 * jnp.ones((12, 2, 2))}
        student = stacked_layer_reduction(stack, [1, 5, 9])
        np.testing.assert_array_equal(np.asarray(student["w"][:, 0, 0]), [1, 5, 9])


class TestOnebitOptimizers:
    def test_onebit_matches_adam_in_warmup(self):
        from deepspeed_tpu.ops.adam.fused_adam import fused_adam
        from deepspeed_tpu.runtime.fp16.onebit import onebit_adam
        params = {"w": jnp.asarray(
            np.random.default_rng(0).standard_normal(64), jnp.float32)}
        a, ob = fused_adam(adam_w_mode=False), onebit_adam(freeze_step=100)
        sa, sb = a.init(params), ob.init(params)
        pa, pb = params, params
        for i in range(5):
            g = {"w": jnp.asarray(
                np.random.default_rng(10 + i).standard_normal(64), jnp.float32)}
            pa, sa = a.update(g, sa, pa, jnp.float32(1e-2))
            pb, sb = ob.update(g, sb, pb, jnp.float32(1e-2))
        np.testing.assert_allclose(np.asarray(pa["w"]), np.asarray(pb["w"]),
                                   rtol=1e-6)

    def test_variance_frozen_after_freeze_step(self):
        from deepspeed_tpu.runtime.fp16.onebit import onebit_adam
        params = {"w": jnp.ones(8)}
        ob = onebit_adam(freeze_step=2)
        s = ob.init(params)
        p = params
        for i in range(2):
            p, s = ob.update({"w": jnp.full(8, 0.5)}, s, p, jnp.float32(1e-2))
        v_at_freeze = np.asarray(s.exp_avg_sq["w"]).copy()
        for i in range(3):
            p, s = ob.update({"w": jnp.full(8, 5.0)}, s, p, jnp.float32(1e-2))
        np.testing.assert_array_equal(np.asarray(s.exp_avg_sq["w"]), v_at_freeze)
        # error feedback is live
        assert float(jnp.abs(s.error["w"]).sum()) >= 0

    def test_onebit_converges(self):
        """sign-compressed momentum still minimises a quadratic."""
        from deepspeed_tpu.runtime.fp16.onebit import onebit_adam
        target = jnp.asarray(np.random.default_rng(0).standard_normal(16),
                             jnp.float32)
        p = {"w": jnp.zeros(16)}
        ob = onebit_adam(freeze_step=10)
        s = ob.init(p)
        loss_fn = lambda w: jnp.mean((w["w"] - target) ** 2)
        for i in range(300):
            g = jax.grad(loss_fn)(p)
            p, s = ob.update(g, s, p, jnp.float32(5e-2))
        assert float(loss_fn(p)) < 0.05

    def test_zero_one_adam_runs(self):
        from deepspeed_tpu.runtime.fp16.onebit import zero_one_adam
        p = {"w": jnp.ones(8)}
        zo = zero_one_adam(var_freeze_step=10)
        s = zo.init(p)
        for i in range(5):
            p, s = zo.update({"w": jnp.full(8, 0.1)}, s, p, jnp.float32(1e-2))
        assert np.isfinite(np.asarray(p["w"])).all()
        assert int(s.var_interval) >= 1

    def test_engine_onebit_config(self):
        cfg = base_config(batch_size=16, stage=1)
        cfg["optimizer"] = {"type": "OneBitAdam",
                            "params": {"lr": 1e-2, "freeze_step": 2}}
        eng, *_ = deepspeed_tpu.initialize(model=simple_model(16), config=cfg)
        losses = [float(eng.train_batch(b)) for b in random_batches(4, 16)]
        assert np.isfinite(losses).all()


class TestCompressedAllreduce:
    def test_error_feedback_identity(self):
        from deepspeed_tpu.comm.compressed import compress_signs, _unpack_bits
        x = jnp.asarray(np.random.default_rng(0).standard_normal(100), jnp.float32)
        e = jnp.zeros(100)
        packed, scale, new_e = compress_signs(x, e)
        signs = _unpack_bits(packed, 100)
        decompressed = jnp.where(signs, scale, -scale)
        np.testing.assert_allclose(np.asarray(decompressed + new_e),
                                   np.asarray(x), rtol=1e-6, atol=1e-6)

    def test_allreduce_under_shard_map(self, eight_devices):
        from jax.sharding import Mesh, PartitionSpec as P
        from deepspeed_tpu.comm.compressed import compressed_allreduce
        mesh = Mesh(np.asarray(eight_devices), ("data",))
        # 8 workers with distinct tensors
        local = np.random.default_rng(0).standard_normal((8, 64)).astype(np.float32)

        def f(x):
            avg, err = compressed_allreduce(x[0], jnp.zeros_like(x[0]), "data")
            return avg[None], err[None]

        avg, err = jax.jit(shard_map(
            f, mesh=mesh, in_specs=P("data"), out_specs=P("data")))(local)
        avg = np.asarray(avg)
        # every worker agrees on the compressed average
        assert np.allclose(avg, avg[0:1], atol=1e-6)
        # compressed average ≈ scale-weighted sign mean, correlates with true mean
        true_mean = local.mean(axis=0)
        corr = np.corrcoef(avg[0], true_mean)[0, 1]
        assert corr > 0.5
        # error feedback reconstructs each worker's input exactly
        scales = np.abs(local).mean(axis=1, keepdims=True)
        recon = np.where(local >= 0, scales, -scales) + np.asarray(err)
        np.testing.assert_allclose(recon, local, rtol=1e-5, atol=1e-5)


class TestMoQ:
    def test_quantize_training_maps_to_compression(self):
        """Reference MoQ block (runtime 'quantize_training') drives the same QAT
        scheduler as compression_training.weight_quantization."""
        from deepspeed_tpu.config.config import DeepSpeedConfig, DeepSpeedConfigError
        cfg = DeepSpeedConfig({
            "train_batch_size": 8,
            "quantize_training": {
                "enabled": True,
                "quantize_bits": {"start_bits": 12, "target_bits": 4},
                "quantize_groups": 8, "quantize_period": 100,
                "quantize_algo": {"q_type": "asymmetric",
                                  "rounding": "nearest"},
                "schedule_offset": 50}})
        wq = cfg.compression_config["weight_quantization"]
        assert wq["shared_parameters"]["enabled"]
        assert wq["shared_parameters"]["quantization_type"] == "asymmetric"
        assert wq["different_groups"]["moq"]["params"]["start_bits"] == 12
        assert wq["different_groups"]["moq"]["params"]["target_bits"] == 4
        with pytest.raises(DeepSpeedConfigError, match="not both"):
            DeepSpeedConfig({
                "train_batch_size": 8,
                "quantize_training": {"enabled": True},
                "compression_training": {"weight_quantization": {
                    "shared_parameters": {"enabled": True},
                    "different_groups": {"g": {"params": {
                        "start_bits": 8, "target_bits": 8}}}}}})

    def test_moq_trains(self):
        cfg = base_config(batch_size=16)
        cfg["quantize_training"] = {
            "enabled": True,
            "quantize_bits": {"start_bits": 8, "target_bits": 8},
            "schedule_offset": 0}
        eng, *_ = deepspeed_tpu.initialize(model=simple_model(16), config=cfg)
        assert eng._compression is not None and eng._compression.active
        losses = [float(eng.train_batch(b)) for b in random_batches(2, 16)]
        assert np.isfinite(losses).all()


class TestLambEndToEnd:
    def test_lamb_trains_end_to_end(self):
        """LAMB end to end, beyond the trust-ratio unit test."""
        cfg = base_config(batch_size=16, lr=5e-2)
        cfg["optimizer"] = {"type": "Lamb", "params": {"lr": 5e-2,
                                                       "weight_decay": 0.01}}
        eng, *_ = deepspeed_tpu.initialize(model=simple_model(16), config=cfg)
        losses = [float(eng.train_batch(b)) for b in random_batches(10, 16)]
        assert np.isfinite(losses).all()
        assert losses[-1] < losses[0]


class TestOnebitLamb:
    def test_matches_lamb_in_warmup(self):
        from deepspeed_tpu.ops.lamb.fused_lamb import fused_lamb
        from deepspeed_tpu.runtime.fp16.onebit import onebit_lamb
        import jax.numpy as jnp
        params = {"w": jnp.asarray(
            np.random.default_rng(0).standard_normal(64), jnp.float32)}
        a, ob = fused_lamb(), onebit_lamb(freeze_step=100)
        sa, sb = a.init(params), ob.init(params)
        pa, pb = params, params
        for i in range(5):
            g = {"w": jnp.asarray(
                np.random.default_rng(20 + i).standard_normal(64), jnp.float32)}
            pa, sa = a.update(g, sa, pa, jnp.float32(1e-2))
            pb, sb = ob.update(g, sb, pb, jnp.float32(1e-2))
        np.testing.assert_allclose(np.asarray(pa["w"]), np.asarray(pb["w"]),
                                   rtol=1e-6)

    def test_frozen_stage_invariants(self):
        from deepspeed_tpu.runtime.fp16.onebit import onebit_lamb
        import jax.numpy as jnp
        params = {"w": jnp.ones(16)}
        ob = onebit_lamb(freeze_step=2)
        s = ob.init(params)
        p = params
        for _ in range(2):
            p, s = ob.update({"w": jnp.full(16, 0.3)}, s, p, jnp.float32(1e-2))
        v0 = np.asarray(s.exp_avg_sq["w"]).copy()
        trust0 = float(s.frozen_trust["w"])
        for _ in range(3):
            p, s = ob.update({"w": jnp.full(16, 3.0)}, s, p, jnp.float32(1e-2))
        np.testing.assert_array_equal(np.asarray(s.exp_avg_sq["w"]), v0)
        assert float(s.frozen_trust["w"]) == trust0
        assert np.isfinite(np.asarray(p["w"])).all()

    def test_engine_config(self):
        cfg = base_config(batch_size=16)
        cfg["optimizer"] = {"type": "OneBitLamb",
                            "params": {"lr": 1e-2, "freeze_step": 2}}
        eng, *_ = deepspeed_tpu.initialize(model=simple_model(16), config=cfg)
        losses = [float(eng.train_batch(b)) for b in random_batches(4, 16)]
        assert np.isfinite(losses).all()
