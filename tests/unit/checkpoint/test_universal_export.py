"""Universal-checkpoint EXPORT round trip.

Export a trained engine as the reference universal format, then (a) read the
per-param ``zero/<name>/fp32.pt`` files with plain torch — the contract
``universal_checkpoint.py:load_hp_checkpoint_state`` consumes — and (b) re-import
the ``mp_rank_00_model_states.pt`` through this framework's own
``DeepSpeedCheckpoint`` importer, closing the export → reference tooling →
re-import loop.
"""

import os

import numpy as np
import pytest

import jax

import deepspeed_tpu
from deepspeed_tpu.checkpoint import (DeepSpeedCheckpoint,
                                      export_fp32_state_dict,
                                      export_universal_checkpoint)
from deepspeed_tpu.models.causal_lm import CausalLMConfig, causal_lm_model

torch = pytest.importorskip("torch")

VOCAB, SEQ = 64, 16


def _cfg(n_layer=2):
    return CausalLMConfig(vocab_size=VOCAB, max_seq_len=32, n_embd=32,
                          n_layer=n_layer, n_head=4, dtype=jax.numpy.float32,
                          name="tiny")


def _engine(offload=False, tmp=None):
    model = causal_lm_model(_cfg(), sample_seq_len=SEQ, layers_per_group=1)
    cfg = {
        "train_batch_size": 8,
        "train_micro_batch_size_per_gpu": 1,
        "optimizer": {"type": "adamw", "params": {"lr": 1e-2}},
        "zero_optimization": {"stage": 3 if offload else 2},
        "steps_per_print": 10**9,
    }
    if offload:
        cfg["zero_optimization"]["offload_param"] = {"device": "cpu"}
    eng, *_ = deepspeed_tpu.initialize(model=model, config=cfg)
    rng = np.random.RandomState(0)
    batch = {"input_ids": rng.randint(0, VOCAB, size=(8, SEQ)).astype(np.int32)}
    for _ in range(2):
        eng.train_batch(batch=batch)
    return eng


class TestUniversalExport:
    def test_resident_engine_roundtrip(self, tmp_path):
        eng = _engine()
        path = export_universal_checkpoint(eng, str(tmp_path), tag="u1")

        src = {k: np.asarray(v, np.float32) for k, v in
               dict_flatten(eng.state.params).items()}
        # (a) plain-torch read of the universal per-param files
        for name, arr in src.items():
            f = os.path.join(path, "zero", name, "fp32.pt")
            assert os.path.isfile(f), f
            got = torch.load(f, weights_only=False)["param"].numpy()
            np.testing.assert_array_equal(got, arr, err_msg=name)
        # moments present and matching the engine's AdamState
        m_src = dict_flatten(eng.state.opt_state.exp_avg)
        some = next(iter(m_src))
        got_m = torch.load(os.path.join(path, "zero", some, "exp_avg.pt"),
                           weights_only=False)["param"].numpy()
        np.testing.assert_allclose(got_m, np.asarray(m_src[some], np.float32),
                                   rtol=1e-6)

        # (b) re-import through this framework's reference importer
        ckpt = DeepSpeedCheckpoint(path)
        assert ckpt.get_iteration() == 2
        sd = ckpt.merged_state_dict()
        for name, arr in src.items():
            np.testing.assert_array_equal(np.asarray(sd[name]), arr,
                                          err_msg=name)

    def test_param_offload_engine_export(self, tmp_path):
        eng = _engine(offload=True)
        path = export_universal_checkpoint(eng, str(tmp_path), tag="u1")
        co = eng._param_offload
        # name order = the coordinator's global flat order (key_order, then
        # sorted leaves within a key) — NOT alphabetical; _dotted_tree preserves it
        from deepspeed_tpu.checkpoint.export import _dotted_tree
        src = _dotted_tree(co.full_params_host())
        # moment VALUES pinned against the coordinator's flat optimizer state —
        # guards the order-based flat-moments → dotted-names zip
        flat_m = co.opt.state_dict()["m"]
        assert len(flat_m) == len(src)
        for (name, arr), m in zip(src.items(), flat_m):
            got = torch.load(os.path.join(path, "zero", name, "fp32.pt"),
                             weights_only=False)["param"].numpy()
            np.testing.assert_array_equal(got, arr, err_msg=name)
            got_m = torch.load(os.path.join(path, "zero", name, "exp_avg.pt"),
                               weights_only=False)["param"].numpy()
            np.testing.assert_array_equal(
                got_m.reshape(-1), np.asarray(m, np.float32), err_msg=name)

    def test_optimizer_offload_engine_exports_masters(self, tmp_path):
        """ZeRO-Offload engines must export the fp32 HOST MASTERS (not the
        bf16-rounded device params) and the host Adam moments."""
        from tests.unit.simple_model import base_config, simple_model
        model = simple_model(16)
        cfg = base_config(batch_size=8, stage=2, lr=1e-2)
        cfg["bf16"] = {"enabled": True}
        cfg["zero_optimization"]["offload_optimizer"] = {"device": "cpu"}
        eng, *_ = deepspeed_tpu.initialize(model=model, config=cfg)
        rng = np.random.RandomState(0)
        batch = {"x": rng.standard_normal((8, 16)).astype(np.float32)}
        batch["y"] = batch["x"].copy()
        for _ in range(2):
            eng.train_batch(batch)
        path = export_universal_checkpoint(eng, str(tmp_path), tag="u1")
        tier = eng._offload_tier
        names = list(dict_flatten(eng.state.params).keys())
        co_m = tier.opt.state_dict()["m"]
        for i, name in enumerate(names):
            got = torch.load(os.path.join(path, "zero", name, "fp32.pt"),
                             weights_only=False)["param"].numpy()
            # fp32 master precision, not the bf16 device copy
            np.testing.assert_array_equal(
                got.reshape(-1), tier.masters[i], err_msg=name)
            got_m = torch.load(os.path.join(path, "zero", name, "exp_avg.pt"),
                               weights_only=False)["param"].numpy()
            np.testing.assert_array_equal(got_m.reshape(-1), co_m[i],
                                          err_msg=name)

    def test_pipeline_engine_export(self, tmp_path, eight_devices):
        """1F1B-trained pipeline export: the stacked body is
        un-stacked into reference per-layer files + per-layer dotted universal
        names, and the export re-imports through DeepSpeedCheckpoint exactly."""
        from deepspeed_tpu.models.gpt2 import GPT2Config
        from deepspeed_tpu.models.gpt2_pipe import gpt2_pipeline_module
        from deepspeed_tpu.parallel.mesh import MeshSpec
        from deepspeed_tpu.runtime.pipe.engine import PipelineEngine

        gcfg = GPT2Config(vocab_size=VOCAB, n_positions=32, n_embd=32,
                          n_layer=4, n_head=4, dropout=0.0,
                          dtype=jax.numpy.float32, split_qkv=True,
                          scan_layers=False, remat=False)
        mod = gpt2_pipeline_module(gcfg, num_stages=2, sample_seq_len=SEQ)
        mesh = MeshSpec({"pipe": 2, "data": 2}, eight_devices[:4])
        eng = PipelineEngine(model=mod, config={
            "train_batch_size": 4, "train_micro_batch_size_per_gpu": 1,
            "gradient_accumulation_steps": 2,
            "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
            "zero_optimization": {"stage": 0},
            "mesh": {"pipe": 2, "data": 2}, "steps_per_print": 10**9,
        }, mesh_spec=mesh)
        rng = np.random.RandomState(0)
        ids = rng.randint(0, VOCAB, size=(4, SEQ)).astype(np.int32)
        for _ in range(2):
            eng.train_batch(batch={"inputs": ids, "labels": ids})

        path = export_universal_checkpoint(eng, str(tmp_path), tag="u1")

        # (a) body un-stacking: layer file at body position i holds slice i
        params = eng.state.params
        bs = mod.body_start
        for i in range(bs, mod.body_end):
            f = os.path.join(path, f"layer_{i:02d}-model_00-model_states.pt")
            assert os.path.isfile(f), f
            sd = torch.load(f, weights_only=False)
            for name, t in sd.items():
                node = params["body"]
                for p in name.split("."):
                    node = node[p]
                np.testing.assert_array_equal(
                    t.numpy(), np.asarray(node, np.float32)[i - bs],
                    err_msg=f"layer {i} {name}")
        # (b) tied embedding at its first position; final norm in its post slot
        sd0 = torch.load(os.path.join(
            path, "layer_00-model_00-model_states.pt"), weights_only=False)
        np.testing.assert_array_equal(
            sd0["wte"].numpy(),
            np.asarray(params["tied"]["embed"]["wte"], np.float32))
        # (c) universal zero/ entries + moments, and re-import equality
        ckpt = DeepSpeedCheckpoint(path)
        assert ckpt.get_iteration() == 2
        merged = ckpt.merged_state_dict()
        got = merged["01.q_attn.kernel"]
        np.testing.assert_array_equal(
            np.asarray(got),
            np.asarray(params["body"]["q_attn"]["kernel"], np.float32)[1 - bs])
        m_file = os.path.join(path, "zero", "01.q_attn.kernel", "exp_avg.pt")
        got_m = torch.load(m_file, weights_only=False)["param"].numpy()
        np.testing.assert_array_equal(
            got_m,
            np.asarray(eng.state.opt_state.exp_avg["body"]["q_attn"]["kernel"],
                       np.float32)[1 - bs])

    def test_fp32_state_dict(self, tmp_path):
        eng = _engine()
        out = str(tmp_path / "pytorch_model.bin")
        export_fp32_state_dict(eng, out)
        sd = torch.load(out, weights_only=False)
        src = dict_flatten(eng.state.params)
        assert set(sd.keys()) == set(src.keys())
        for name, t in sd.items():
            assert t.dtype == torch.float32
            np.testing.assert_array_equal(
                t.numpy(), np.asarray(src[name], np.float32), err_msg=name)


def dict_flatten(tree, prefix=""):
    out = {}
    if isinstance(tree, dict):
        for k in sorted(tree.keys()):
            key = f"{prefix}.{k}" if prefix else str(k)
            out.update(dict_flatten(tree[k], key))
        return out
    out[prefix] = tree
    return out
