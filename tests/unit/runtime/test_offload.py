"""ZeRO-Offload tier tests.

Mirrors reference ``tests/unit/runtime/zero/test_zero_offload*`` +
``tests/unit/ops/adam/test_cpu_adam.py``: native host Adam equivalence against torch,
offload-vs-in-graph training equivalence, host placement of optimizer state, and
checkpoint round-trip of the host tier.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import deepspeed_tpu
from deepspeed_tpu.ops.adam.cpu_adam import (DeepSpeedCPUAdam, adam_step,
                                             fp32_to_bf16, native_available)

from tests.unit.simple_model import base_config, random_batches, simple_model

HID = 16


def _offload_config(stage=1, gas=1, dtype=None, **extra):
    cfg = base_config(batch_size=16, gas=gas, stage=stage, lr=1e-2, **extra)
    cfg["zero_optimization"]["offload_optimizer"] = {"device": "cpu"}
    if dtype == "bf16":
        cfg["bf16"] = {"enabled": True}
    return cfg


# --------------------------------------------------------------------- native op
class TestCPUAdamOp:
    @pytest.mark.parametrize("adamw", [False, True])
    def test_matches_torch(self, adamw):
        torch = pytest.importorskip("torch")
        rng = np.random.default_rng(0)
        p0 = rng.standard_normal(2049).astype(np.float32)  # odd size: exercises SIMD tail
        p_np = p0.copy()
        m = np.zeros_like(p_np)
        v = np.zeros_like(p_np)
        p_t = torch.nn.Parameter(torch.tensor(p0))
        cls = torch.optim.AdamW if adamw else torch.optim.Adam
        opt = cls([p_t], lr=1e-2, betas=(0.9, 0.999), eps=1e-8, weight_decay=0.05)
        for step in range(1, 6):
            g = rng.standard_normal(p_np.size).astype(np.float32)
            adam_step(p_np, m, v, g, lr=1e-2, beta1=0.9, beta2=0.999, eps=1e-8,
                      weight_decay=0.05, adam_w_mode=adamw, step=step)
            p_t.grad = torch.tensor(g)
            opt.step()
            np.testing.assert_allclose(p_np, p_t.detach().numpy(), rtol=2e-5, atol=2e-6)

    def test_pytree_optimizer_inplace(self):
        params = [np.ones(64, np.float32), np.full(32, 2.0, np.float32)]
        opt = DeepSpeedCPUAdam(params, weight_decay=0.0, adamw_mode=False)
        before = [p.copy() for p in opt.params]
        opt.step([np.ones(64, np.float32), np.ones(32, np.float32)], lr=0.1)
        for b, a in zip(before, opt.params):
            assert not np.allclose(b, a)
        assert opt.step_count == 1

    def test_bf16_roundtrip(self):
        import ml_dtypes
        x = np.array([1.0, -2.5, 3.14159, 1e-30, 65504.0], np.float32)
        got = fp32_to_bf16(x)
        expect = x.astype(ml_dtypes.bfloat16)
        np.testing.assert_array_equal(got.view(np.uint16), expect.view(np.uint16))

    def test_native_build_reported(self):
        # informational: the native path should build in this image (g++ baked in)
        assert native_available(), "native cpu_adam failed to build; check op_builder logs"


# --------------------------------------------------------------------- engine tier
class TestOffloadEngine:
    def _train(self, cfg, n_steps=5, seed_data=0):
        model = simple_model(HID)
        eng, *_ = deepspeed_tpu.initialize(model=model, config=cfg)
        losses = []
        for b in random_batches(n_steps, 16, HID, seed=seed_data):
            losses.append(float(eng.train_batch(b)))
        return eng, losses

    def test_matches_in_graph_adam(self):
        """fp32 offload training ≡ in-graph fused_adam (same data, same seeds)."""
        eng_a, losses_a = self._train(base_config(batch_size=16, stage=0, lr=1e-2))
        eng_b, losses_b = self._train(_offload_config(stage=0))
        np.testing.assert_allclose(losses_a, losses_b, rtol=2e-4, atol=1e-5)
        pa = jax.tree_util.tree_leaves(eng_a.state.params)
        pb = jax.tree_util.tree_leaves(eng_b.state.params)
        for a, b in zip(pa, pb):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-4, atol=1e-5)

    def test_opt_state_on_host(self):
        eng, losses = self._train(_offload_config(stage=1, dtype="bf16"), n_steps=3)
        # no optimizer state on device
        assert eng.state.opt_state == ()
        # masters + moments are host numpy
        tier = eng._offload_tier
        assert all(isinstance(m, np.ndarray) for m in tier.masters)
        assert all(isinstance(m, np.ndarray) for m in tier.opt.m)
        # device params hold compute dtype (bf16), not fp32 masters
        for leaf in jax.tree_util.tree_leaves(eng.state.params):
            assert leaf.dtype == jnp.bfloat16
        assert np.isfinite(losses).all()

    def test_offload_zero3_sharded(self):
        """Offload composes with stage-3 param sharding on the 8-device mesh."""
        cfg = _offload_config(stage=3, gas=2, dtype="bf16")
        cfg["zero_optimization"]["stage3_param_persistence_threshold"] = 0
        eng, losses = self._train(cfg, n_steps=3)
        sharded = [l for l in jax.tree_util.tree_leaves(eng.state.params)
                   if "fsdp" in str(l.sharding.spec)]
        assert sharded, "expected at least one fsdp-sharded param"
        assert np.isfinite(losses).all()

    def test_offload_fp16_overflow_skip(self):
        cfg = _offload_config(stage=0)
        cfg["fp16"] = {"enabled": True, "initial_scale_power": 4}
        model = simple_model(HID)
        eng, *_ = deepspeed_tpu.initialize(model=model, config=cfg)
        batch = random_batches(1, 16, HID)[0]
        eng.train_batch(batch)
        masters_before = [m.copy() for m in eng._offload_tier.masters]
        bad = {"x": np.full_like(batch["x"], 1e30), "y": batch["y"]}
        eng.train_batch(bad)
        # overflow step: masters untouched, loss scale halved, skip counted
        for b, a in zip(masters_before, eng._offload_tier.masters):
            np.testing.assert_array_equal(b, a)
        assert eng.skipped_steps == 1

    def test_checkpoint_roundtrip(self, tmp_path):
        cfg = _offload_config(stage=1, dtype="bf16")
        eng_a, _ = self._train(cfg, n_steps=3)
        eng_a.save_checkpoint(str(tmp_path))

        model = simple_model(HID)
        eng_b, *_ = deepspeed_tpu.initialize(model=model, config=cfg)
        eng_b.load_checkpoint(str(tmp_path))
        ta, tb = eng_a._offload_tier, eng_b._offload_tier
        for a, b in zip(ta.masters, tb.masters):
            np.testing.assert_array_equal(a, b)
        for a, b in zip(ta.opt.m, tb.opt.m):
            np.testing.assert_array_equal(a, b)
        assert tb.opt.step_count == ta.opt.step_count
        # and training continues identically from the restored state
        batch = random_batches(1, 16, HID, seed=77)[0]
        la = float(eng_a.train_batch(batch))
        lb = float(eng_b.train_batch(batch))
        assert la == pytest.approx(lb, rel=1e-6)

    def test_module_only_load_reseeds_masters(self, tmp_path):
        """load_module_only=True must reseed host masters from the loaded weights —
        otherwise the first host step would overwrite them with init-time masters."""
        cfg = _offload_config(stage=0)
        eng_a, _ = self._train(cfg, n_steps=3)
        eng_a.save_checkpoint(str(tmp_path))
        trained = [np.asarray(l) for l in
                   jax.tree_util.tree_leaves(eng_a.state.params)]

        eng_b, *_ = deepspeed_tpu.initialize(model=simple_model(HID), config=cfg)
        eng_b.load_checkpoint(str(tmp_path), load_module_only=True)
        for m, t in zip(eng_b._offload_tier.masters, trained):
            np.testing.assert_allclose(m.reshape(t.shape), t, rtol=1e-6)
        # a step after the module-only load moves FROM the loaded weights
        eng_b.train_batch(random_batches(1, 16, HID, seed=5)[0])
        for l, t in zip(jax.tree_util.tree_leaves(eng_b.state.params), trained):
            assert np.abs(np.asarray(l, np.float32) - t).max() < 0.1

    def test_nvme_offload_matches_cpu_offload(self, tmp_path):
        """ZeRO-Infinity tier: moments on disk via the native aio handle produce
        bit-identical training to the in-RAM host tier."""
        from deepspeed_tpu.ops.aio.aio_handle import aio_available
        if not aio_available():
            pytest.skip("native aio op unavailable")
        cfg_cpu = _offload_config(stage=0)
        cfg_nvme = _offload_config(stage=0)
        cfg_nvme["zero_optimization"]["offload_optimizer"] = {
            "device": "nvme", "nvme_path": str(tmp_path / "swap")}
        eng_a, losses_a = self._train(cfg_cpu, n_steps=4)
        eng_b, losses_b = self._train(cfg_nvme, n_steps=4)
        np.testing.assert_allclose(losses_a, losses_b, rtol=1e-6)
        for a, b in zip(eng_a._offload_tier.masters, eng_b._offload_tier.masters):
            np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-7)
        # moments really live on disk
        assert eng_b._offload_tier.nvme is not None
        import os
        files = os.listdir(tmp_path / "swap")
        assert any(f.startswith("moments_leaf") for f in files)
        # and round-trip through state_dict
        sd = eng_b._offload_tier.state_dict()
        for i, m_ram in enumerate(eng_a._offload_tier.opt.m):
            np.testing.assert_allclose(
                np.asarray(sd["m"][f"leaf{i}"]).reshape(-1), m_ram,
                rtol=1e-6, atol=1e-7)

    def test_eager_api_offload(self):
        """forward/backward/step triple works in offload mode and matches train_batch."""
        cfg = _offload_config(stage=0)
        eng_a, *_ = deepspeed_tpu.initialize(model=simple_model(HID), config=cfg)
        eng_b, *_ = deepspeed_tpu.initialize(model=simple_model(HID), config=cfg)
        for b in random_batches(3, 16, HID, seed=3):
            eng_a.train_batch(b)
            eng_b.forward(b)
            eng_b.backward()
            eng_b.step()
        pa = jax.tree_util.tree_leaves(eng_a.state.params)
        pb = jax.tree_util.tree_leaves(eng_b.state.params)
        for a, b in zip(pa, pb):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-4, atol=1e-5)


class TestInterleavedPush:
    def test_push_interleaves_with_adam(self, monkeypatch):
        """The r3 interleaved-push optimization is real, not incidental: leaf i's
        H2D push is dispatched immediately after leaf i's SIMD update and BEFORE
        leaf i+1's update (reference cpu_adam.cpp copy/compute tiling) — pinned
        by event order, which is timing-independent."""
        import deepspeed_tpu.ops.adam.cpu_adam as cpu_adam_mod
        from deepspeed_tpu.runtime.zero.offload import OffloadOptimizerTier

        eng, *_ = deepspeed_tpu.initialize(model=simple_model(HID),
                                           config=_offload_config())
        tier = eng._offload_tier
        events = []
        real_adam = cpu_adam_mod.adam_step
        real_push = tier._push_leaf
        counter = {"i": 0}

        def spy_adam(*a, **kw):
            events.append(("adam", counter["i"]))
            counter["i"] += 1
            return real_adam(*a, **kw)

        monkeypatch.setattr(cpu_adam_mod, "adam_step", spy_adam)
        monkeypatch.setattr(tier, "_push_leaf",
                            lambda i: (events.append(("push", i)),
                                       real_push(i))[1])
        batch = random_batches(1, 16)[0]
        eng.train_batch(batch)
        n = len(tier.masters)
        assert counter["i"] == n
        # interleaved: ... adam i, push i, adam i+1, push i+1 ... (never
        # update-all-then-push-all)
        expected = [ev for i in range(n) for ev in (("adam", i), ("push", i))]
        assert events == expected, events
