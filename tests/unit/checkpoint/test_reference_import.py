"""Reference-format checkpoint importer tests.

Mirrors reference ``tests/unit/checkpoint`` reshape/merge coverage: a synthetic
Megatron-DeepSpeed 3D checkpoint (layer_* tp shards, mp_rank_* module states,
zero_pp_rank_* fp32 partitions) round-trips through :mod:`deepspeed_tpu.checkpoint`
into a CausalLM parameter tree whose forward matches the ground truth.
"""

import os
from collections import OrderedDict

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.checkpoint import (DeepSpeedCheckpoint, Model3DDescriptor,
                                      get_model_3d_descriptor, reshape_3d,
                                      reshape_meg_2d_parallel, split_megatron_qkv,
                                      to_causal_lm_params)
from deepspeed_tpu.models.causal_lm import CausalLM, CausalLMConfig

torch = pytest.importorskip("torch")

TP = 2
CFG = CausalLMConfig(vocab_size=32, max_seq_len=16, n_embd=16, n_layer=2, n_head=2,
                     dtype=jnp.float32, tie_word_embeddings=True, name="tiny")


# ------------------------------------------------------------------ reshape maps
class TestReshapeMaps:
    def test_identity(self):
        m = reshape_meg_2d_parallel(2, 2, 2, 2)
        assert m == {(0, 0): [0], (0, 1): [1], (1, 0): [2], (1, 1): [3]}

    def test_tp_contraction(self):
        m = reshape_meg_2d_parallel(1, 4, 1, 2)
        assert m == {(0, 0): [0, 1], (0, 1): [2, 3]}

    def test_pp_contraction(self):
        m = reshape_meg_2d_parallel(4, 2, 2, 2)
        assert m[(0, 0)] == [0, 2] and m[(1, 1)] == [5, 7]

    def test_3d_dp_partition(self):
        maps = reshape_3d(Model3DDescriptor(2, 2, 2), Model3DDescriptor(2, 2, 1))
        # one target dp group holding both source dp replicas' files
        assert len(maps) == 1
        assert maps[0][(0, 0)] == [0, 4]

    def test_expansion_rejected(self):
        ok, errs = Model3DDescriptor(1, 2, 1).can_reshape(Model3DDescriptor(1, 4, 1))
        assert not ok and "TP" in errs[0]


# ------------------------------------------------------------------ synthesis
def _ground_truth_params():
    rng = jax.random.PRNGKey(0)
    module = CausalLM(CFG)
    return module.init({"params": rng},
                       jnp.zeros((1, 8), jnp.int32))["params"]


def _fuse_qkv(layer):
    """Our q/k/v kernels → Megatron fused interleaved weight (3nh, h) + bias."""
    n, hn = CFG.n_head, CFG.head_dim
    qw = np.asarray(layer["q_proj"]["kernel"]).T    # (nh, h)
    kw = np.asarray(layer["k_proj"]["kernel"]).T
    vw = np.asarray(layer["v_proj"]["kernel"]).T
    w = np.stack([qw.reshape(n, hn, -1), kw.reshape(n, hn, -1),
                  vw.reshape(n, hn, -1)], axis=1).reshape(3 * n * hn, -1)
    qb = np.asarray(layer["q_proj"]["bias"]).reshape(n, hn)
    kb = np.asarray(layer["k_proj"]["bias"]).reshape(n, hn)
    vb = np.asarray(layer["v_proj"]["bias"]).reshape(n, hn)
    b = np.stack([qb, kb, vb], axis=1).reshape(3 * n * hn)
    return w, b


def _write_reference_checkpoint(params, dir):
    """Emit layer_*-model_* tp shards + mp_rank_* files in Megatron naming."""
    os.makedirs(dir, exist_ok=True)

    def save(name, sd):
        torch.save({k: torch.tensor(np.asarray(v)) for k, v in sd.items()},
                   os.path.join(dir, name))

    def shard(arr, dim):
        return np.split(np.asarray(arr), TP, axis=dim)

    # embedding layer (id 00): wte tp-sharded on vocab, wpe replicated
    for tp in range(TP):
        save(f"layer_00-model_{tp:02d}-model_states.pt", {
            "word_embeddings.weight": shard(params["wte"], 0)[tp],
            "position_embeddings.weight": np.asarray(params["wpe"]),
        })
    # transformer layers (ids 02, 03)
    for i in range(CFG.n_layer):
        layer = params[f"layers_{i}"]
        qkv_w, qkv_b = _fuse_qkv(layer)
        full = {
            "input_layernorm.weight": layer["ln_attn"]["scale"],
            "input_layernorm.bias": layer["ln_attn"]["bias"],
            "self_attention.query_key_value.weight": qkv_w,
            "self_attention.query_key_value.bias": qkv_b,
            "self_attention.dense.weight": np.asarray(layer["o_proj"]["kernel"]).T,
            "self_attention.dense.bias": layer["o_proj"]["bias"],
            "post_attention_layernorm.weight": layer["ln_mlp"]["scale"],
            "post_attention_layernorm.bias": layer["ln_mlp"]["bias"],
            "mlp.dense_h_to_4h.weight": np.asarray(layer["fc_in"]["kernel"]).T,
            "mlp.dense_h_to_4h.bias": layer["fc_in"]["bias"],
            "mlp.dense_4h_to_h.weight": np.asarray(layer["fc_out"]["kernel"]).T,
            "mlp.dense_4h_to_h.bias": layer["fc_out"]["bias"],
        }
        col0 = {"self_attention.query_key_value.weight",
                "self_attention.query_key_value.bias",
                "mlp.dense_h_to_4h.weight", "mlp.dense_h_to_4h.bias"}
        row1 = {"self_attention.dense.weight", "mlp.dense_4h_to_h.weight"}
        for tp in range(TP):
            sd = {}
            for name, v in full.items():
                if name in col0:
                    sd[name] = shard(v, 0)[tp]
                elif name in row1:
                    sd[name] = shard(v, 1)[tp]
                else:
                    sd[name] = np.asarray(v)
            save(f"layer_{i + 2:02d}-model_{tp:02d}-model_states.pt", sd)
    # final layernorm (id 05)
    for tp in range(TP):
        save(f"layer_{CFG.n_layer + 3:02d}-model_{tp:02d}-model_states.pt", {
            "weight": params["ln_f"]["scale"], "bias": params["ln_f"]["bias"]})
    # mp_rank module files (iteration + args)
    for tp in range(TP):
        torch.save({"iteration": 123, "args": {"hidden_size": CFG.n_embd}},
                   os.path.join(dir, f"mp_rank_{tp:02d}_model_states.pt"))


class TestReferenceImport:
    def test_descriptor_and_merge_roundtrip(self, tmp_path):
        params = _ground_truth_params()
        _write_reference_checkpoint(params, str(tmp_path))

        desc = get_model_3d_descriptor(str(tmp_path))
        assert desc.tp_degree == TP and desc.pp_degree == 1

        ckpt = DeepSpeedCheckpoint(str(tmp_path))
        assert ckpt.get_iteration() == 123
        assert ckpt.layer_count == CFG.n_layer + 2

        tree = to_causal_lm_params(ckpt, n_head=CFG.n_head, n_layer=CFG.n_layer)
        # imported forward == ground-truth forward
        module = CausalLM(CFG)
        ids = jnp.asarray(np.random.RandomState(0).randint(0, CFG.vocab_size,
                                                           size=(2, 8)), jnp.int32)
        ref = module.apply({"params": params}, ids)
        # imported tree misses nothing the forward needs
        got = module.apply({"params": jax.tree_util.tree_map(jnp.asarray, tree)}, ids)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)

    def test_import_then_serve_end_to_end(self, tmp_path):
        """The composed reference workflow (train Megatron → serve
        injected): import a reference-format checkpoint, hand the
        converted tree straight to InferenceEngine, and pin the greedy rollout
        against the ground-truth module's full forward."""
        import deepspeed_tpu as ds
        from deepspeed_tpu.inference.engine import InferenceEngine
        from deepspeed_tpu.parallel.mesh import set_global_mesh

        params = _ground_truth_params()
        _write_reference_checkpoint(params, str(tmp_path))
        ckpt = DeepSpeedCheckpoint(str(tmp_path))
        tree = to_causal_lm_params(ckpt, n_head=CFG.n_head, n_layer=CFG.n_layer)

        set_global_mesh(None)
        engine = InferenceEngine(
            (CFG, jax.tree_util.tree_map(jnp.asarray, tree)),
            ds.inference.DeepSpeedInferenceConfig(dtype="float32",
                                                  max_out_tokens=CFG.max_seq_len))
        ids = np.random.RandomState(1).randint(
            0, CFG.vocab_size, size=(2, 6)).astype(np.int32)
        out = engine.generate(ids, max_new_tokens=4)

        module = CausalLM(CFG)
        cur = ids
        for _ in range(4):
            logits = module.apply({"params": params}, jnp.asarray(cur))
            nxt = np.asarray(jnp.argmax(logits[:, -1], axis=-1))[:, None]
            cur = np.concatenate([cur, nxt.astype(cur.dtype)], axis=1)
        np.testing.assert_array_equal(out, cur)

    def test_qkv_split_inverts_fuse(self):
        params = _ground_truth_params()
        layer = params["layers_0"]
        w, b = _fuse_qkv(layer)
        qw, kw, vw = split_megatron_qkv(w, CFG.n_head)
        np.testing.assert_allclose(qw.T, np.asarray(layer["q_proj"]["kernel"]),
                                   rtol=1e-6)
        np.testing.assert_allclose(vw.T, np.asarray(layer["v_proj"]["kernel"]),
                                   rtol=1e-6)
        qb, _, vb = split_megatron_qkv(b, CFG.n_head)
        np.testing.assert_allclose(qb, np.asarray(layer["q_proj"]["bias"]), rtol=1e-6)


class TestZeroReconstruct:
    def test_fp32_from_partitions(self, tmp_path):
        """zero_pp_rank_* fp32 flat partitions + mp_rank param_shapes → full fp32."""
        rng = np.random.RandomState(0)
        # total (29) deliberately NOT divisible by dp so the last-rank padding
        # path is actually exercised (pad = 1)
        shapes = OrderedDict([("w1", (4, 3)), ("b1", (4,)), ("w2", (2, 4)),
                              ("b2", (5,))])
        total = sum(int(np.prod(s)) for s in shapes.values())
        flat = rng.standard_normal(total).astype(np.float32)
        dp = 2
        pad = (-total) % dp
        padded = np.concatenate([flat, np.zeros(pad, np.float32)])
        parts = np.split(padded, dp)
        torch.save({"param_shapes": shapes, "iteration": 7},
                   os.path.join(tmp_path, "mp_rank_00_model_states.pt"))
        for r in range(dp):
            # reference layout: padding is recorded (and nonzero) only on the LAST
            # dp rank's shard (stage_1_and_2.py:333-339)
            torch.save({"optimizer_state_dict": {
                "single_partition_of_fp32_groups": [torch.tensor(parts[r])],
                "zero_stage": 2,
                "group_paddings": [pad if r == dp - 1 else 0],
                "partition_count": dp}},
                os.path.join(tmp_path, f"zero_pp_rank_{r}_mp_rank_00_optim_states.pt"))

        ckpt = DeepSpeedCheckpoint(str(tmp_path))
        assert ckpt.src_3d.dp_degree == dp
        sd = ckpt.reconstruct_fp32_state_dict()
        off = 0
        for name, shape in shapes.items():
            n = int(np.prod(shape))
            np.testing.assert_allclose(sd[name].reshape(-1), flat[off:off + n])
            off += n
