"""Inference engine tests — analogue of reference ``tests/unit/inference/test_inference.py``
(parametrized HF-model injection) + KV-cache correctness checks."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu as ds
from deepspeed_tpu.inference.engine import InferenceEngine
from deepspeed_tpu.models.causal_lm import (CausalLM, bloom_cfg, gpt2_cfg, gptneox_cfg,
                                            llama_cfg, opt_cfg)
from deepspeed_tpu.parallel.mesh import MeshSpec

TINY = dict(vocab_size=96, max_seq_len=64, n_embd=32, n_layer=2, n_head=4,
            dtype=jnp.float32)


def _greedy_nocache(cfg, params, ids, steps):
    """Ground truth: full forward + argmax each step (no cache)."""
    module = CausalLM(cfg)
    cur = np.asarray(ids)
    for _ in range(steps):
        logits = module.apply({"params": params}, jnp.asarray(cur))
        nxt = np.asarray(jnp.argmax(logits[:, -1], axis=-1))[:, None]
        cur = np.concatenate([cur, nxt], axis=1)
    return cur


@pytest.mark.parametrize("family", [gpt2_cfg, bloom_cfg, opt_cfg, llama_cfg, gptneox_cfg])
def test_cached_generate_matches_nocache(family):
    """The fused KV-cache decode path must reproduce the uncached greedy rollout —
    covers learned/alibi/rotary positions, parallel residual, RMSNorm, gated MLP."""
    cfg = family(**TINY)
    engine = InferenceEngine(cfg, ds.inference.DeepSpeedInferenceConfig(
        dtype="float32", max_out_tokens=64))
    rng = np.random.default_rng(0)
    ids = rng.integers(0, cfg.vocab_size, size=(2, 8)).astype(np.int32)
    out = engine.generate(ids, max_new_tokens=6)
    ref = _greedy_nocache(cfg, engine.params, ids, 6)
    np.testing.assert_array_equal(out, ref)


def test_gqa_cached_generate():
    cfg = llama_cfg(**{**TINY, "n_kv_head": 2})
    engine = InferenceEngine(cfg, ds.inference.DeepSpeedInferenceConfig(
        dtype="float32", max_out_tokens=64))
    rng = np.random.default_rng(1)
    ids = rng.integers(0, cfg.vocab_size, size=(2, 8)).astype(np.int32)
    out = engine.generate(ids, max_new_tokens=4)
    ref = _greedy_nocache(cfg, engine.params, ids, 4)
    np.testing.assert_array_equal(out, ref)


def test_tp_generate_matches_single(eight_devices):
    """TP-sharded serving computes the same tokens as unsharded (reference auto-TP
    correctness)."""
    cfg = gpt2_cfg(**TINY)
    e1 = InferenceEngine(cfg, ds.inference.DeepSpeedInferenceConfig(
        dtype="float32", max_out_tokens=64),
        mesh_spec=MeshSpec({"tensor": 1}, eight_devices[:1]))
    params = e1.params
    e2 = InferenceEngine((cfg, jax.tree_util.tree_map(np.asarray, params)),
                         ds.inference.DeepSpeedInferenceConfig(
                             dtype="float32", max_out_tokens=64,
                             tensor_parallel={"tp_size": 4}),
                         mesh_spec=MeshSpec({"tensor": 4}, eight_devices[:4]))
    rng = np.random.default_rng(2)
    ids = rng.integers(0, cfg.vocab_size, size=(2, 8)).astype(np.int32)
    out1 = e1.generate(ids, max_new_tokens=5)
    out2 = e2.generate(ids, max_new_tokens=5)
    np.testing.assert_array_equal(out1, out2)
    # params physically sharded over tensor axis
    qk = e2.params["layers_0"]["q_proj"]["kernel"]
    assert "tensor" in str(qk.sharding.spec)


def test_sampling_controls():
    cfg = gpt2_cfg(**TINY)
    engine = InferenceEngine(cfg, ds.inference.DeepSpeedInferenceConfig(
        dtype="float32", max_out_tokens=64))
    rng = np.random.default_rng(3)
    ids = rng.integers(0, cfg.vocab_size, size=(1, 4)).astype(np.int32)
    a = engine.generate(ids, max_new_tokens=5, do_sample=True, temperature=0.8, seed=0)
    b = engine.generate(ids, max_new_tokens=5, do_sample=True, temperature=0.8, seed=0)
    c = engine.generate(ids, max_new_tokens=5, do_sample=True, temperature=0.8, seed=1)
    np.testing.assert_array_equal(a, b)        # deterministic per seed
    assert a.shape == (1, 9)
    assert not np.array_equal(a, c) or True    # different seed may differ
    with pytest.raises(NotImplementedError):
        engine.generate(ids, max_new_tokens=2, num_beams=4)


def test_ragged_prompts_match_individual():
    """Right-padded unequal-length prompts (attention_mask / prompt_lengths) must produce
    the same continuations as generating each prompt separately unpadded."""
    cfg = gpt2_cfg(**TINY)
    engine = InferenceEngine(cfg, ds.inference.DeepSpeedInferenceConfig(
        dtype="float32", max_out_tokens=64))
    rng = np.random.default_rng(9)
    p0 = rng.integers(0, cfg.vocab_size, size=(1, 8)).astype(np.int32)
    p1 = rng.integers(0, cfg.vocab_size, size=(1, 5)).astype(np.int32)
    # batch them right-padded to 8
    ids = np.zeros((2, 8), dtype=np.int32)
    ids[0] = p0[0]
    ids[1, :5] = p1[0]
    mask = np.zeros((2, 8), dtype=np.int32)
    mask[0] = 1
    mask[1, :5] = 1

    out = engine.generate(ids, max_new_tokens=4, attention_mask=mask)
    ref0 = engine.generate(p0, max_new_tokens=4)
    ref1 = engine.generate(p1, max_new_tokens=4)
    np.testing.assert_array_equal(out[0, 8:], ref0[0, 8:])
    np.testing.assert_array_equal(out[1, 8:], ref1[0, 5:])
    # same via prompt_lengths
    out2 = engine.generate(ids, max_new_tokens=4, prompt_lengths=[8, 5])
    np.testing.assert_array_equal(out, out2)
    # left-padded masks are rejected
    bad = np.zeros((2, 8), dtype=np.int32)
    bad[0] = 1
    bad[1, 3:] = 1
    with pytest.raises(ValueError):
        engine.generate(ids, max_new_tokens=2, attention_mask=bad)


def test_eos_early_stop_on_device():
    """EOS termination happens inside the device loop: output stops early and finished
    sequences pad with eos."""
    cfg = gpt2_cfg(**TINY)
    engine = InferenceEngine(cfg, ds.inference.DeepSpeedInferenceConfig(
        dtype="float32", max_out_tokens=64))
    rng = np.random.default_rng(10)
    ids = rng.integers(0, cfg.vocab_size, size=(1, 6)).astype(np.int32)
    free = engine.generate(ids, max_new_tokens=8)
    first = int(free[0, 6])
    # use the first generated token as "eos": generation must stop after 1 token
    out = engine.generate(ids, max_new_tokens=8, eos_token_id=first)
    assert out.shape[1] == 7
    assert int(out[0, 6]) == first


def test_post_eos_rows_emit_eos_not_stale():
    """After a row hits EOS, every subsequent token it emits must be EOS — never
    stale decode-buffer contents — while unfinished rows decode on unaffected."""
    cfg = gpt2_cfg(**TINY)
    engine = InferenceEngine(cfg, ds.inference.DeepSpeedInferenceConfig(
        dtype="float32", max_out_tokens=64))
    for seed in range(8):
        rng = np.random.default_rng(100 + seed)
        ids = rng.integers(0, cfg.vocab_size, size=(2, 8)).astype(np.int32)
        free = engine.generate(ids, max_new_tokens=6)
        eos = int(free[0, 8])                  # row 0's first generated token
        if eos not in free[1, 8:].tolist():    # row 1 must stay alive
            break
    else:
        pytest.skip("tiny random model: no prompt pair with distinct streams")
    out = engine.generate(ids, max_new_tokens=6, eos_token_id=eos)
    assert out.shape[1] == 8 + 6               # row 1 kept the loop running
    assert int(out[0, 8]) == eos
    assert (out[0, 9:] == eos).all()           # post-EOS content is EOS only
    np.testing.assert_array_equal(out[1], free[1])   # row 1 unaffected


def test_unequal_prompt_finished_row_emits_eos_pad():
    """Unequal right-padded prompts where the SHORT row finishes first: its
    generated tokens overwrite cache pad slots, and once finished it must emit
    EOS — never stale buffer contents — while the long row decodes on."""
    cfg = gpt2_cfg(**TINY)
    engine = InferenceEngine(cfg, ds.inference.DeepSpeedInferenceConfig(
        dtype="float32", max_out_tokens=64))
    for seed in range(8):
        rng = np.random.default_rng(200 + seed)
        ids = np.zeros((2, 8), dtype=np.int32)
        ids[0] = rng.integers(0, cfg.vocab_size, size=8)
        ids[1, :5] = rng.integers(0, cfg.vocab_size, size=5)
        mask = np.zeros((2, 8), dtype=np.int32)
        mask[0] = 1
        mask[1, :5] = 1
        free = engine.generate(ids, max_new_tokens=6, attention_mask=mask)
        eos = int(free[1, 8])                  # short row's first generated token
        if eos not in free[0, 8:].tolist():
            break
    else:
        pytest.skip("tiny random model: no prompt pair with distinct streams")
    out = engine.generate(ids, max_new_tokens=6, attention_mask=mask,
                          eos_token_id=eos)
    assert out.shape[1] == 8 + 6
    assert int(out[1, 8]) == eos
    assert (out[1, 9:] == eos).all()           # finished row: EOS/pad only
    np.testing.assert_array_equal(out[0], free[0])   # long row unaffected


def test_generate_records_tpot_and_monitor_events(tmp_path):
    """generate records TPOT/decode tokens-per-second alongside ttft and, with a
    monitor attached, emits all three as events."""
    import json as _json

    from deepspeed_tpu.config.config import MonitorConfig
    from deepspeed_tpu.monitor import MonitorMaster
    cfg = gpt2_cfg(**TINY)
    engine = InferenceEngine(cfg, ds.inference.DeepSpeedInferenceConfig(
        dtype="float32", max_out_tokens=64))
    master = MonitorMaster(MonitorConfig(jsonl_monitor={
        "enabled": True, "output_path": str(tmp_path), "job_name": "gen"}))
    engine.set_monitor(master)
    rng = np.random.default_rng(13)
    ids = rng.integers(0, cfg.vocab_size, size=(2, 6)).astype(np.int32)
    engine.generate(ids, max_new_tokens=5)
    assert engine.ttft is not None and engine.ttft > 0
    assert engine.tpot is not None and engine.tpot > 0
    assert engine.decode_tps is not None and engine.decode_tps > 0
    import os as _os
    path = _os.path.join(str(tmp_path), "gen.jsonl")
    tags = {_json.loads(line)["tag"] for line in open(path)}
    assert {"inference/ttft_ms", "inference/tpot_ms",
            "inference/decode_tokens_per_sec"} <= tags


def test_int8_generate_close_to_fp():
    """dtype="int8": weights grouped-quantized at load (reference GroupQuantizer /
    dequantize.cu), generation stays close to the fp path."""
    cfg = gpt2_cfg(**TINY)
    e_fp = InferenceEngine(cfg, ds.inference.DeepSpeedInferenceConfig(
        dtype="float32", max_out_tokens=64))
    raw = jax.tree_util.tree_map(np.asarray, e_fp.params)
    e_q = InferenceEngine((cfg, raw), ds.inference.DeepSpeedInferenceConfig(
        dtype="int8", max_out_tokens=64))
    # weights are physically int8 on device
    qnode = e_q.params["layers_0"]["q_proj"]["kernel"]
    assert isinstance(qnode, dict) and qnode["__int8_q__"].dtype == jnp.int8

    rng = np.random.default_rng(11)
    ids = rng.integers(0, cfg.vocab_size, size=(2, 8)).astype(np.int32)
    logits_fp = np.asarray(e_fp(ids))
    logits_q = np.asarray(e_q(ids))
    # grouped 8-bit weight quantization on a tiny random model: logits stay close
    err = np.abs(logits_q - logits_fp).mean() / (np.abs(logits_fp).mean() + 1e-9)
    assert err < 0.05, f"relative logits error {err:.4f} too large"
    out = e_q.generate(ids, max_new_tokens=4)
    assert out.shape == (2, 12)


def test_int8_tp2_matches_tp1(eight_devices):
    """int8 serving composed with TP>1: grouped-quantized
    weights shard over the tensor axis and the quantized logits/rollout equal
    the single-device quantized engine exactly (same quantization grid)."""
    from deepspeed_tpu.parallel.mesh import MeshSpec
    cfg = gpt2_cfg(**TINY)
    e_fp = InferenceEngine(cfg, ds.inference.DeepSpeedInferenceConfig(
        dtype="float32", max_out_tokens=64),
        mesh_spec=MeshSpec({"tensor": 1}, eight_devices[:1]))
    raw = jax.tree_util.tree_map(np.asarray, e_fp.params)
    e_q1 = InferenceEngine((cfg, raw), ds.inference.DeepSpeedInferenceConfig(
        dtype="int8", max_out_tokens=64),
        mesh_spec=MeshSpec({"tensor": 1}, eight_devices[:1]))
    e_q2 = InferenceEngine((cfg, raw), ds.inference.DeepSpeedInferenceConfig(
        dtype="int8", max_out_tokens=64),
        mesh_spec=MeshSpec({"tensor": 2}, eight_devices[:2]))
    qnode = e_q2.params["layers_0"]["q_proj"]["kernel"]
    assert isinstance(qnode, dict) and qnode["__int8_q__"].dtype == jnp.int8
    assert "tensor" in str(qnode["__int8_q__"].sharding.spec), \
        qnode["__int8_q__"].sharding.spec

    rng = np.random.default_rng(12)
    ids = rng.integers(0, cfg.vocab_size, size=(2, 8)).astype(np.int32)
    l1, l2 = np.asarray(e_q1(ids)), np.asarray(e_q2(ids))
    # same quantization grid on both engines; residual is TP psum reduction
    # order (~1e-3), far below the int8 quantization error itself
    np.testing.assert_allclose(l2, l1, atol=2e-3, rtol=1e-2)
    out = e_q2.generate(ids, max_new_tokens=4)
    assert out.shape == (2, 12)


def test_int8_quantizer_roundtrip():
    from deepspeed_tpu.ops.quantizer import dequantize_grouped, quantize_grouped
    w = np.random.default_rng(0).normal(size=(256, 64)).astype(np.float32)
    q, s = quantize_grouped(w, group_size=128)
    assert q.dtype == jnp.int8 and s.shape == (2, 64)
    w2 = np.asarray(dequantize_grouped(q, s))
    assert np.abs(w2 - w).max() < np.abs(w).max() / 100  # 8-bit grouped: <1% of range
    # 3D (experts): per-expert groups
    we = np.random.default_rng(1).normal(size=(4, 256, 32)).astype(np.float32)
    qe, se = quantize_grouped(we, group_size=128)
    assert qe.shape == we.shape and se.shape == (4, 2, 32)
    np.testing.assert_allclose(np.asarray(dequantize_grouped(qe, se)), we, atol=0.04)


def test_init_inference_api():
    """deepspeed.init_inference parity: dict config with mp_size/dtype knobs."""
    cfg = gpt2_cfg(**TINY)
    engine = ds.init_inference(cfg, config={"dtype": "float32", "max_out_tokens": 64})
    ids = np.zeros((1, 4), dtype=np.int32)
    logits = engine(ids)
    assert logits.shape == (1, 4, cfg.vocab_size)


# --------------------------------------------------------------- HF conversion policies
transformers = pytest.importorskip("transformers")
torch = pytest.importorskip("torch")


def _logits_close(hf_model, ids, atol=2e-3):
    from deepspeed_tpu.module_inject import convert_hf_model
    from deepspeed_tpu.parallel.mesh import set_global_mesh
    set_global_mesh(None)  # earlier tests may leave a multi-device mesh active
    cfg, params = convert_hf_model(hf_model)
    cfg.dtype = jnp.float32
    ours = CausalLM(cfg).apply({"params": params}, jnp.asarray(ids))
    with torch.no_grad():
        theirs = hf_model(torch.tensor(ids)).logits.float().numpy()
    np.testing.assert_allclose(np.asarray(ours), theirs, atol=atol, rtol=1e-3)


def test_hf_gpt2_conversion():
    hf = transformers.GPT2LMHeadModel(transformers.GPT2Config(
        vocab_size=96, n_positions=64, n_embd=32, n_layer=2, n_head=4,
        resid_pdrop=0.0, embd_pdrop=0.0, attn_pdrop=0.0))
    hf.eval()
    ids = np.random.default_rng(4).integers(0, 96, size=(2, 10))
    _logits_close(hf, ids)


def test_hf_bloom_conversion():
    hf = transformers.BloomForCausalLM(transformers.BloomConfig(
        vocab_size=96, hidden_size=32, n_layer=2, n_head=4,
        hidden_dropout=0.0, attention_dropout=0.0))
    hf.eval()
    ids = np.random.default_rng(5).integers(0, 96, size=(2, 10))
    _logits_close(hf, ids)


def test_hf_opt_conversion():
    hf = transformers.OPTForCausalLM(transformers.OPTConfig(
        vocab_size=96, hidden_size=32, num_hidden_layers=2, num_attention_heads=4,
        ffn_dim=64, max_position_embeddings=64, dropout=0.0, word_embed_proj_dim=32))
    hf.eval()
    ids = np.random.default_rng(6).integers(0, 96, size=(2, 10))
    _logits_close(hf, ids)


def test_hf_llama_conversion():
    hf = transformers.LlamaForCausalLM(transformers.LlamaConfig(
        vocab_size=96, hidden_size=32, num_hidden_layers=2, num_attention_heads=4,
        num_key_value_heads=2, intermediate_size=64, max_position_embeddings=64))
    hf.eval()
    ids = np.random.default_rng(7).integers(0, 96, size=(2, 10))
    _logits_close(hf, ids)


def test_hf_generate_through_engine():
    """End-to-end reference flow: HF torch model → init_inference → generate."""
    hf = transformers.GPT2LMHeadModel(transformers.GPT2Config(
        vocab_size=96, n_positions=64, n_embd=32, n_layer=2, n_head=4,
        resid_pdrop=0.0, embd_pdrop=0.0, attn_pdrop=0.0))
    hf.eval()
    engine = ds.init_inference(hf, config={"dtype": "float32", "max_out_tokens": 64})
    ids = np.random.default_rng(8).integers(0, 96, size=(1, 6)).astype(np.int32)
    out = engine.generate(ids, max_new_tokens=5)
    with torch.no_grad():
        hf_out = hf.generate(torch.tensor(ids), max_new_tokens=5, do_sample=False)
    np.testing.assert_array_equal(out, hf_out.numpy())


def test_hf_gptj_conversion():
    hf = transformers.GPTJForCausalLM(transformers.GPTJConfig(
        vocab_size=96, n_positions=64, n_embd=32, n_layer=2, n_head=4,
        rotary_dim=4, resid_pdrop=0.0, embd_pdrop=0.0, attn_pdrop=0.0))
    hf.eval()
    ids = np.random.default_rng(7).integers(0, 96, size=(2, 10))
    _logits_close(hf, ids)


def test_hf_mistral_conversion():
    hf = transformers.MistralForCausalLM(transformers.MistralConfig(
        vocab_size=96, hidden_size=32, num_hidden_layers=2, num_attention_heads=4,
        num_key_value_heads=2, intermediate_size=64, max_position_embeddings=64,
        sliding_window=64, attention_dropout=0.0))
    hf.eval()
    ids = np.random.default_rng(8).integers(0, 96, size=(2, 10))
    _logits_close(hf, ids)


def test_hf_qwen2_conversion():
    hf = transformers.Qwen2ForCausalLM(transformers.Qwen2Config(
        vocab_size=96, hidden_size=32, num_hidden_layers=2, num_attention_heads=4,
        num_key_value_heads=2, intermediate_size=64, max_position_embeddings=64,
        attention_dropout=0.0, tie_word_embeddings=False))
    hf.eval()
    ids = np.random.default_rng(9).integers(0, 96, size=(2, 10))
    _logits_close(hf, ids)


def test_auto_tp_gpt_bigcode_conversion():
    """An architecture with NO named policy (gpt_bigcode: MQA + fused contiguous
    qkv) converts through the auto-TP generic policy with matching logits."""
    hf = transformers.AutoModelForCausalLM.from_config(
        transformers.AutoConfig.for_model(
            "gpt_bigcode", vocab_size=96, n_positions=64, n_embd=32, n_layer=2,
            n_head=4, multi_query=True, resid_pdrop=0.0, embd_pdrop=0.0,
            attn_pdrop=0.0))
    hf.eval()
    from deepspeed_tpu.module_inject.replace_module import HF_POLICIES
    assert hf.config.model_type not in HF_POLICIES
    ids = np.random.default_rng(7).integers(0, 96, size=(2, 10))
    _logits_close(hf, ids)


def test_auto_tp_serves_tp_sharded(eight_devices):
    """The auto-converted model serves tensor-parallel: logits on a tp=2 mesh
    match the single-device engine."""
    hf = transformers.AutoModelForCausalLM.from_config(
        transformers.AutoConfig.for_model(
            "gpt_bigcode", vocab_size=96, n_positions=64, n_embd=32, n_layer=2,
            n_head=4, multi_query=False, resid_pdrop=0.0, embd_pdrop=0.0,
            attn_pdrop=0.0))
    hf.eval()
    # the MHA fused-qkv (per-head interleaved) conversion must be numerically right,
    # not merely deterministic — compare against HF before the TP comparison
    _logits_close(hf, np.random.default_rng(8).integers(0, 96, size=(2, 10)))
    ids = np.zeros((1, 8), dtype=np.int32)
    e1 = ds.init_inference(hf, config={"dtype": "float32", "tensor_parallel": {"tp_size": 1},
                                       "max_out_tokens": 64})
    base = np.asarray(e1(ids))
    from deepspeed_tpu.parallel.mesh import set_global_mesh
    set_global_mesh(None)
    e2 = ds.init_inference(hf, config={"dtype": "float32", "tensor_parallel": {"tp_size": 2},
                                       "max_out_tokens": 64})
    sharded = np.asarray(e2(ids))
    np.testing.assert_allclose(sharded, base, atol=2e-4, rtol=1e-4)


def test_hf_gptneo_conversion():
    """Named GPT-Neo policy (reference containers/gptneo.py): separate bias-free
    q/k/v Linears, UNSCALED attention (sqrt(d_head) folded into q), alternating
    global/local layers — all-global here so no window clamp applies."""
    hf = transformers.GPTNeoForCausalLM(transformers.GPTNeoConfig(
        vocab_size=96, max_position_embeddings=64, hidden_size=32, num_layers=2,
        num_heads=4, attention_types=[[["global"], 2]], intermediate_size=64,
        resid_dropout=0.0, embed_dropout=0.0, attention_dropout=0.0))
    hf.eval()
    ids = np.random.default_rng(10).integers(0, 96, size=(2, 10))
    _logits_close(hf, ids)


def test_hf_gptneo_local_attention_clamps_and_matches():
    """The local-attention layout trap: local layers attend to the trailing
    window only, so conversion clamps max_seq_len to the window — inside it,
    logits must still match HF exactly."""
    hf = transformers.GPTNeoForCausalLM(transformers.GPTNeoConfig(
        vocab_size=96, max_position_embeddings=64, hidden_size=32, num_layers=2,
        num_heads=4, attention_types=[[["global", "local"], 1]], window_size=8,
        intermediate_size=64, resid_dropout=0.0, embed_dropout=0.0,
        attention_dropout=0.0))
    hf.eval()
    from deepspeed_tpu.module_inject import convert_hf_model
    cfg, _ = convert_hf_model(hf)
    assert cfg.max_seq_len == 8
    ids = np.random.default_rng(11).integers(0, 96, size=(2, 8))
    _logits_close(hf, ids)


def test_hf_gptneo_untied_head():
    """Untied GPT-Neo: the converted lm_head must actually be used (not silently
    shadowed by the tied wte.T path)."""
    hf = transformers.GPTNeoForCausalLM(transformers.GPTNeoConfig(
        vocab_size=96, max_position_embeddings=64, hidden_size=32, num_layers=2,
        num_heads=4, attention_types=[[["global"], 2]], intermediate_size=64,
        tie_word_embeddings=False, resid_dropout=0.0, embed_dropout=0.0,
        attention_dropout=0.0))
    hf.eval()
    from deepspeed_tpu.module_inject import convert_hf_model
    cfg, params = convert_hf_model(hf)
    assert not cfg.tie_word_embeddings and "lm_head" in params
    ids = np.random.default_rng(12).integers(0, 96, size=(2, 10))
    _logits_close(hf, ids)
