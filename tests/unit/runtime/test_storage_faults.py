"""Storage-tier fault injection.

The reference's swap tier inherits libaio's error surface; this framework's
O_DIRECT thread-pool backend must be equally loud: a truncated swap file, a
failed write, or a corrupt checkpoint moments file FAILS with an actionable
message instead of training on silently zeroed/garbled state. The async
checkpoint's commit-before-'latest' ordering must be crash-safe: when the
drain barrier dies, 'latest' still points at the previous durable tag.
"""

import os

import numpy as np
import pytest

import jax

import deepspeed_tpu
from deepspeed_tpu.models.causal_lm import CausalLMConfig, causal_lm_model

VOCAB, SEQ = 64, 16


def _cfg(n_layer=2):
    return CausalLMConfig(vocab_size=VOCAB, max_seq_len=32, n_embd=32,
                          n_layer=n_layer, n_head=4, dtype=jax.numpy.float32,
                          name="tiny")


def _nvme_engine(swap_path):
    model = causal_lm_model(_cfg(), sample_seq_len=SEQ, layers_per_group=1)
    cfg = {
        "train_batch_size": 8,
        "train_micro_batch_size_per_gpu": 1,
        "optimizer": {"type": "adamw", "params": {"lr": 1e-2}},
        "zero_optimization": {
            "stage": 3,
            "offload_param": {"device": "nvme", "nvme_path": str(swap_path)}},
        "steps_per_print": 10**9,
    }
    eng, *_ = deepspeed_tpu.initialize(model=model, config=cfg)
    batch = {"input_ids": np.random.RandomState(0).randint(
        0, VOCAB, size=(8, SEQ)).astype(np.int32)}
    eng.train_batch(batch=batch)
    return eng, batch


class TestSwapFileFaults:
    def test_truncated_master_file_fails_loud(self, tmp_path):
        """A swap master file truncated mid-run (disk error, manual deletion)
        must raise on the next read, not stream zeros into the model."""
        eng, _ = _nvme_engine(tmp_path / "swap")
        tier = eng._param_offload.param_tier
        f = tier._mfiles[0]
        with open(f, "r+b") as fh:
            fh.truncate(os.path.getsize(f) // 2)
        with pytest.raises(RuntimeError, match="truncated or unreadable"):
            tier.read_master(0)

    def test_truncated_master_fails_training_step(self, tmp_path):
        """The training loop itself (async fetch lane) dies loudly too."""
        eng, batch = _nvme_engine(tmp_path / "swap")
        tier = eng._param_offload.param_tier
        with open(tier._mfiles[1], "r+b") as fh:
            fh.truncate(0)
        with pytest.raises((OSError, RuntimeError)):
            eng.train_batch(batch=batch)

    def test_enospc_write_fails_loud(self):
        """ENOSPC mid-write: pwrite to a full device surfaces as an error at the
        wait barrier, not as a silently dropped update."""
        from deepspeed_tpu.ops.aio.aio_handle import AsyncIOHandle, aio_available
        if not aio_available():
            pytest.skip("native aio op unavailable")
        if not os.path.exists("/dev/full"):
            pytest.skip("/dev/full unavailable")
        h = AsyncIOHandle(o_direct=False)
        try:
            with pytest.raises(OSError, match="I/O operations failed"):
                h.sync_pwrite(np.zeros(1024, np.float32), "/dev/full")
        finally:
            h.close()


class TestCheckpointFaults:
    def test_corrupt_moments_on_restore_fails_loud(self, tmp_path):
        """A damaged moments file in a checkpoint must refuse to restore. The
        manifest layer now catches it FIRST (truncation named per shard); the
        tier-level length check remains the backstop when validation is off."""
        eng, _ = _nvme_engine(tmp_path / "swap")
        ckpt = tmp_path / "ckpt"
        eng.save_checkpoint(str(ckpt), tag="t0")
        moments_dir = ckpt / "t0" / "offload_state_moments"
        victim = sorted(moments_dir.iterdir())[0]
        victim.write_bytes(victim.read_bytes()[:100])     # corrupt: 100 bytes
        with pytest.raises(RuntimeError, match="truncated"):
            eng.load_checkpoint(str(ckpt), tag="t0")
        # backstop: with manifest validation disabled, the moments reader's own
        # length check still refuses the file
        with pytest.raises(RuntimeError, match="corrupt moments file"):
            eng.load_checkpoint(str(ckpt), tag="t0", validate=False)

    def test_missing_master_on_restore_fails_loud(self, tmp_path):
        eng, _ = _nvme_engine(tmp_path / "swap")
        ckpt = tmp_path / "ckpt"
        eng.save_checkpoint(str(ckpt), tag="t0")
        masters_dir = ckpt / "t0" / "offload_state_masters"
        sorted(masters_dir.iterdir())[0].unlink()
        with pytest.raises(RuntimeError, match="missing"):
            eng.load_checkpoint(str(ckpt), tag="t0")
        with pytest.raises(RuntimeError, match="missing master file"):
            eng.load_checkpoint(str(ckpt), tag="t0", validate=False)

    def test_crash_before_latest_keeps_previous_tag(self, tmp_path, monkeypatch):
        """Commit-before-latest ordering: kill the save between the data write
        and the 'latest' update (the commit drain raises) — 'latest' must still
        name the prior durable tag, and loading it must succeed."""
        eng, batch = _nvme_engine(tmp_path / "swap")
        ckpt = tmp_path / "ckpt"
        eng.save_checkpoint(str(ckpt), tag="good")
        assert (ckpt / "latest").read_text() == "good"

        eng.train_batch(batch=batch)
        real_commit = eng.checkpoint_engine.commit

        def dying_commit(tag):
            raise RuntimeError("simulated crash during checkpoint drain")

        monkeypatch.setattr(eng.checkpoint_engine, "commit", dying_commit)
        with pytest.raises(RuntimeError, match="simulated crash"):
            eng.save_checkpoint(str(ckpt), tag="bad")
        monkeypatch.setattr(eng.checkpoint_engine, "commit", real_commit)

        # 'latest' never advanced; the previous tag restores cleanly
        assert (ckpt / "latest").read_text() == "good"
        eng.load_checkpoint(str(ckpt))        # resolves via 'latest'
        loss = float(eng.train_batch(batch=batch))
        assert loss == loss
