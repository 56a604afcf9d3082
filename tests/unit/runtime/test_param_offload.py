"""ZeRO-3 parameter-offload tests.

Mirrors reference ``tests/unit/runtime/zero/test_zero.py`` stage-3 offload cases
(``offload_param`` device=cpu/nvme): streamed-vs-resident training equivalence, peak
device-bytes stays below the full model (the point of the tier), tied-embedding gradient
flow through two segments, checkpoint round-trip, and the loud guards.
"""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import deepspeed_tpu
from deepspeed_tpu.models.causal_lm import (CausalLMConfig, causal_lm_model,
                                            causal_lm_segments)

VOCAB, SEQ = 64, 16


def _cfg(n_layer=4, tie=True, dtype=jnp.float32):
    return CausalLMConfig(vocab_size=VOCAB, max_seq_len=32, n_embd=32,
                          n_layer=n_layer, n_head=4, dtype=dtype,
                          tie_word_embeddings=tie, name="tiny")


def _ds_config(offload=True, gas=1, lr=1e-2, nvme_path=None, fp16=False):
    # stage 3 on the 8-device CPU mesh → fsdp=8, so dp_world_size is 8
    cfg = {
        "train_batch_size": 8 * gas,
        "train_micro_batch_size_per_gpu": 1,
        "gradient_accumulation_steps": gas,
        "optimizer": {"type": "adamw",
                      "params": {"lr": lr, "weight_decay": 0.01}},
        "gradient_clipping": 1.0,
        "zero_optimization": {"stage": 3},
        "steps_per_print": 100,
    }
    if offload:
        cfg["zero_optimization"]["offload_param"] = {"device": "cpu"}
        if nvme_path is not None:
            # ZeRO-Infinity moments tier: masters stay in RAM, moments on disk
            cfg["zero_optimization"]["offload_optimizer"] = {
                "device": "nvme", "nvme_path": nvme_path}
    if fp16:
        cfg["fp16"] = {"enabled": True, "initial_scale_power": 8}
    return cfg


def _batches(n, batch=8, seed=0):
    rng = np.random.RandomState(seed)
    return [{"input_ids": rng.randint(0, VOCAB, size=(batch, SEQ)).astype(np.int32)}
            for _ in range(n)]


class TestSegmentDecomposition:
    @pytest.mark.parametrize("tie", [True, False])
    def test_segment_union_matches_monolithic_tree(self, tie):
        cfg = _cfg(tie=tie)
        model = causal_lm_model(cfg, sample_seq_len=SEQ)
        mono = jax.eval_shape(model.init_fn, jax.random.PRNGKey(0))
        segs = model.segments
        init_keys = [k for s in segs for k in s.init_keys]
        assert sorted(init_keys) == sorted(mono.keys())          # no dup, no gap
        for seg in segs:
            sub = jax.eval_shape(seg.init_fn, jax.random.PRNGKey(0))
            assert len(sub) == len(seg.init_keys)
            for key, subtree in zip(seg.init_keys, sub):
                mono_leaves = jax.tree_util.tree_leaves(mono[key])
                seg_leaves = jax.tree_util.tree_leaves(subtree)
                assert [tuple(l.shape) for l in mono_leaves] == \
                    [tuple(l.shape) for l in seg_leaves], key

    def test_tied_wte_is_shared_not_reinitialised(self):
        segs = causal_lm_segments(_cfg(tie=True), layers_per_group=2)
        last = segs[-1]
        assert "wte" in last.param_keys and "wte" not in last.init_keys


class TestStreamedEquivalence:
    def test_matches_resident_engine(self):
        """Streamed (offload_param) training == resident fused-step training: same
        losses and same final parameters, from the same initial weights."""
        cfg = _cfg(n_layer=4)
        batches = _batches(4)

        model_a = causal_lm_model(cfg, sample_seq_len=SEQ)
        eng_a, _, _, _ = deepspeed_tpu.initialize(
            model=model_a, config=_ds_config(offload=False))
        model_b = causal_lm_model(cfg, sample_seq_len=SEQ, layers_per_group=2)
        eng_b, _, _, _ = deepspeed_tpu.initialize(
            model=model_b, config=_ds_config(offload=True))

        # same starting point: seed the streamed masters from the resident params
        host_params = jax.tree_util.tree_map(
            lambda l: np.asarray(l, dtype=np.float32), eng_a.state.params)
        eng_b._param_offload.load_full_params(host_params)

        for b in batches:
            la = float(eng_a.train_batch(batch=b))
            lb = float(eng_b.train_batch(batch=b))
            np.testing.assert_allclose(la, lb, rtol=2e-4)

        final_a = jax.tree_util.tree_map(
            lambda l: np.asarray(l, dtype=np.float32), eng_a.state.params)
        final_b = eng_b._param_offload.full_params_host()
        flat_a = jax.tree_util.tree_leaves(final_a)
        flat_b = jax.tree_util.tree_leaves(
            {k: final_b[k] for k in sorted(final_a.keys())})
        assert len(flat_a) == len(flat_b)
        for a, b in zip(flat_a, flat_b):
            np.testing.assert_allclose(a, np.asarray(b), rtol=2e-3, atol=2e-4)

    def test_gradient_accumulation(self):
        cfg = _cfg(n_layer=2)
        model = causal_lm_model(cfg, sample_seq_len=SEQ, layers_per_group=1)
        eng, _, _, _ = deepspeed_tpu.initialize(
            model=model, config=_ds_config(offload=True, gas=2))
        rng = np.random.RandomState(1)
        batch = {"input_ids": rng.randint(0, VOCAB, size=(16, SEQ)).astype(np.int32)}
        l0 = float(eng.train_batch(batch=batch))
        l1 = float(eng.train_batch(batch=batch))
        assert l1 < l0

    def test_eval_matches_train_loss_direction(self):
        cfg = _cfg(n_layer=2)
        model = causal_lm_model(cfg, sample_seq_len=SEQ, layers_per_group=1)
        eng, _, _, _ = deepspeed_tpu.initialize(
            model=model, config=_ds_config(offload=True))
        batch = _batches(1)[0]
        before = float(eng.eval_batch(batch))
        for _ in range(5):
            eng.train_batch(batch=batch)
        after = float(eng.eval_batch(batch))
        assert after < before


class TestMemoryFootprint:
    def test_peak_device_bytes_below_full_model(self):
        """The point of the tier: concurrently device-resident parameter bytes stay a
        fraction of the full model (2-deep streaming window), independent of depth."""
        cfg = _cfg(n_layer=8)
        model = causal_lm_model(cfg, sample_seq_len=SEQ, layers_per_group=1)
        eng, _, _, _ = deepspeed_tpu.initialize(
            model=model, config=_ds_config(offload=True))
        eng.train_batch(batch=_batches(1)[0])
        tier = eng._param_offload
        total_bytes = tier.total_params * 4  # fp32 compute here
        peak = tier.cache.peak_live_bytes
        assert peak < 0.55 * total_bytes, (peak, total_bytes)

    def test_no_resident_state(self):
        cfg = _cfg(n_layer=2)
        model = causal_lm_model(cfg, sample_seq_len=SEQ, layers_per_group=1)
        eng, _, _, _ = deepspeed_tpu.initialize(
            model=model, config=_ds_config(offload=True))
        assert eng.state is None and eng.optimizer is None


class TestCheckpoint:
    def test_roundtrip(self, tmp_path):
        cfg = _cfg(n_layer=2)
        model = causal_lm_model(cfg, sample_seq_len=SEQ, layers_per_group=1)
        eng, _, _, _ = deepspeed_tpu.initialize(
            model=model, config=_ds_config(offload=True))
        batch = _batches(1)[0]
        for _ in range(2):
            eng.train_batch(batch=batch)
        loss_before = float(eng.eval_batch(batch))
        eng.save_checkpoint(str(tmp_path), tag="t1")

        model2 = causal_lm_model(cfg, sample_seq_len=SEQ, layers_per_group=1)
        eng2, _, _, _ = deepspeed_tpu.initialize(
            model=model2, config=_ds_config(offload=True))
        eng2.load_checkpoint(str(tmp_path), tag="t1")
        assert eng2.global_steps == 2
        np.testing.assert_allclose(float(eng2.eval_batch(batch)), loss_before,
                                   rtol=1e-5)
        # optimizer moments restored: one more step matches on both engines
        l1 = float(eng.train_batch(batch=batch))
        l2 = float(eng2.train_batch(batch=batch))
        np.testing.assert_allclose(l1, l2, rtol=1e-5)

    def test_nvme_moments(self, tmp_path):
        cfg = _cfg(n_layer=2)
        model = causal_lm_model(cfg, sample_seq_len=SEQ, layers_per_group=1)
        dsc = _ds_config(offload=True, nvme_path=str(tmp_path / "swap"))
        eng, _, _, _ = deepspeed_tpu.initialize(model=model, config=dsc)
        batch = _batches(1)[0]
        l0 = float(eng.train_batch(batch=batch))
        l1 = float(eng.train_batch(batch=batch))
        assert l1 < l0
        assert eng._param_offload.nvme is not None
        assert os.path.isdir(str(tmp_path / "swap"))


class TestNVMeParams:
    """Full ZeRO-Infinity: fp32 masters + grad accumulators + moments ALL on disk
    (reference ``swap_tensor/partitioned_param_swapper.py`` — the 'model larger than
    host RAM' capability)."""

    def _nvme_config(self, path, gas=1, fp16=False):
        cfg = _ds_config(offload=True, gas=gas, fp16=fp16)
        cfg["zero_optimization"]["offload_param"] = {
            "device": "nvme", "nvme_path": path}
        return cfg

    def test_matches_ram_mode(self, tmp_path):
        """device='nvme' training == device='cpu' training: same losses, same final
        masters, from the same init seed — the disk tier changes WHERE state lives,
        never its values."""
        cfg = _cfg(n_layer=4)
        batches = _batches(3)

        model_a = causal_lm_model(cfg, sample_seq_len=SEQ, layers_per_group=2)
        eng_a, _, _, _ = deepspeed_tpu.initialize(
            model=model_a, config=_ds_config(offload=True))
        model_b = causal_lm_model(cfg, sample_seq_len=SEQ, layers_per_group=2)
        eng_b, _, _, _ = deepspeed_tpu.initialize(
            model=model_b, config=self._nvme_config(str(tmp_path / "swap")))
        co_b = eng_b._param_offload
        assert co_b.nvme_params and co_b.masters is None and co_b.nvme is not None

        for b in batches:
            la = float(eng_a.train_batch(batch=b))
            lb = float(eng_b.train_batch(batch=b))
            np.testing.assert_allclose(la, lb, rtol=1e-6)

        fa = eng_a._param_offload.full_params_host()
        fb = co_b.full_params_host()
        for a, b in zip(jax.tree_util.tree_leaves(fa),
                        jax.tree_util.tree_leaves(fb)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-6, atol=1e-7)

    def test_gradient_accumulation_reads_back_accum(self, tmp_path):
        """gas>1 exercises the read-modify-write path of the on-disk grad
        accumulators (first microbatch writes, later ones read+add)."""
        cfg = _cfg(n_layer=2)
        model_a = causal_lm_model(cfg, sample_seq_len=SEQ, layers_per_group=1)
        eng_a, _, _, _ = deepspeed_tpu.initialize(
            model=model_a, config=_ds_config(offload=True, gas=2))
        model_b = causal_lm_model(cfg, sample_seq_len=SEQ, layers_per_group=1)
        eng_b, _, _, _ = deepspeed_tpu.initialize(
            model=model_b, config=self._nvme_config(str(tmp_path / "swap"), gas=2))
        rng = np.random.RandomState(1)
        batch = {"input_ids": rng.randint(0, VOCAB, size=(16, SEQ)).astype(np.int32)}
        for _ in range(2):
            la = float(eng_a.train_batch(batch=batch))
            lb = float(eng_b.train_batch(batch=batch))
            np.testing.assert_allclose(la, lb, rtol=1e-6)

    def test_host_ram_bounded_by_scratch(self, tmp_path):
        """The tier's host footprint is the double-buffer scratch — a fixed multiple
        of the LARGEST LEAF — while the streamed state (masters+grads+moments =
        16 bytes/param) scales with the model. Deeper model, same scratch."""
        cfg = _cfg(n_layer=8)
        model = causal_lm_model(cfg, sample_seq_len=SEQ, layers_per_group=1)
        eng, _, _, _ = deepspeed_tpu.initialize(
            model=model, config=self._nvme_config(str(tmp_path / "swap")))
        co = eng._param_offload
        eng.train_batch(batch=_batches(1)[0])
        streamed_bytes = co.total_params * 16       # 4 masters + 4 grads + 8 moments
        host_bytes = co.param_tier.scratch_bytes + \
            sum(b.nbytes for b in co.nvme._scratch)
        assert co.masters is None and co._accum is None
        assert host_bytes < streamed_bytes / 4, (host_bytes, streamed_bytes)
        # on-disk state actually exists
        assert len(os.listdir(str(tmp_path / "swap"))) >= len(co.leaf_sizes)

    def test_checkpoint_roundtrip(self, tmp_path):
        cfg = _cfg(n_layer=2)
        model = causal_lm_model(cfg, sample_seq_len=SEQ, layers_per_group=1)
        eng, _, _, _ = deepspeed_tpu.initialize(
            model=model, config=self._nvme_config(str(tmp_path / "swap")))
        batch = _batches(1)[0]
        for _ in range(2):
            eng.train_batch(batch=batch)
        loss_before = float(eng.eval_batch(batch))
        eng.save_checkpoint(str(tmp_path / "ckpt"), tag="t1")

        model2 = causal_lm_model(cfg, sample_seq_len=SEQ, layers_per_group=1)
        eng2, _, _, _ = deepspeed_tpu.initialize(
            model=model2, config=self._nvme_config(str(tmp_path / "swap2")))
        eng2.load_checkpoint(str(tmp_path / "ckpt"), tag="t1")
        np.testing.assert_allclose(float(eng2.eval_batch(batch)), loss_before,
                                   rtol=1e-5)
        # moments + step restored: one more step matches
        l1 = float(eng.train_batch(batch=batch))
        l2 = float(eng2.train_batch(batch=batch))
        np.testing.assert_allclose(l1, l2, rtol=1e-5)

    def test_requires_nvme_path(self):
        cfg = _cfg(n_layer=2)
        model = causal_lm_model(cfg, sample_seq_len=SEQ, layers_per_group=1)
        dsc = _ds_config(offload=True)
        dsc["zero_optimization"]["offload_param"] = {"device": "nvme"}
        with pytest.raises(ValueError, match="nvme_path"):
            deepspeed_tpu.initialize(model=model, config=dsc)


class TestOffloadCombos:
    """QAT and flops-profiler compose with the streamed step (these were
    fail-loud NotImplementedError combos once)."""

    def test_qat_under_offload(self):
        """Compression QAT rides the push transform: pushed weights quantize once
        the schedule offset passes, and training still learns."""
        cfg = _cfg(n_layer=2)
        model = causal_lm_model(cfg, sample_seq_len=SEQ, layers_per_group=1)
        dsc = _ds_config(offload=True)
        dsc["compression_training"] = {"weight_quantization": {
            "shared_parameters": {"enabled": True, "schedule_offset": 1,
                                  "quantize_groups": 4},
            "different_groups": {"wq1": {"params": {
                "start_bits": 8, "target_bits": 8, "quantization_period": 1},
                "modules": ["*"]}}}}
        eng, _, _, _ = deepspeed_tpu.initialize(model=model, config=dsc)
        co = eng._param_offload
        assert co.qat_fn is not None
        # before the offset: pushed key equals the cast masters
        import jax
        raw, _ = co._push_key_raw("layers_0")
        q, _ = co._push_key("layers_0")
        for a, b in zip(jax.tree_util.tree_leaves(raw),
                        jax.tree_util.tree_leaves(q)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        batch = _batches(1)[0]
        losses = [float(eng.train_batch(batch=batch)) for _ in range(4)]
        assert losses[-1] < losses[0]
        # past the offset: pushed 2-D weights are quantized (differ from raw)
        co.cache.clear()
        raw, _ = co._push_key_raw("layers_0")
        q, _ = co._push_key("layers_0")
        diffs = [not np.allclose(np.asarray(a), np.asarray(b))
                 for a, b in zip(jax.tree_util.tree_leaves(raw),
                                 jax.tree_util.tree_leaves(q))
                 if a.ndim >= 2]
        assert any(diffs), "no pushed weight was quantized after the offset"

    def test_flops_profiler_under_offload(self):
        cfg = _cfg(n_layer=2)
        model = causal_lm_model(cfg, sample_seq_len=SEQ, layers_per_group=1)
        dsc = _ds_config(offload=True)
        dsc["flops_profiler"] = {"enabled": True, "profile_step": 2}
        eng, _, _, _ = deepspeed_tpu.initialize(model=model, config=dsc)
        batch = _batches(1)[0]
        eng.train_batch(batch=batch)
        eng.train_batch(batch=batch)       # profile fires before step 2
        assert eng.flops_profiler.result is not None
        assert eng.flops_profiler.result.total_flops > 0


class TestGuards:
    def test_requires_stage3(self):
        cfg = _cfg(n_layer=2)
        model = causal_lm_model(cfg, sample_seq_len=SEQ)
        dsc = _ds_config(offload=True)
        dsc["zero_optimization"]["stage"] = 1
        with pytest.raises(ValueError, match="stage 3"):
            deepspeed_tpu.initialize(model=model, config=dsc)

    def test_requires_segments(self):
        from tests.unit.simple_model import simple_model
        model = simple_model(hidden_dim=8)
        with pytest.raises(ValueError, match="segment"):
            deepspeed_tpu.initialize(model=model, config=_ds_config(offload=True))

    def test_eager_api_refuses(self):
        cfg = _cfg(n_layer=2)
        model = causal_lm_model(cfg, sample_seq_len=SEQ, layers_per_group=1)
        eng, _, _, _ = deepspeed_tpu.initialize(
            model=model, config=_ds_config(offload=True))
        with pytest.raises(NotImplementedError, match="train_batch"):
            eng.forward(_batches(1)[0])
