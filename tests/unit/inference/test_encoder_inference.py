"""Encoder injection parity: HF BERT / DistilBERT → EncoderLM, outputs matching
the torch modules (reference
``module_inject/containers/bert.py`` + ``distil_bert.py``)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import deepspeed_tpu as ds
from deepspeed_tpu.models.encoder import bert_cfg

torch = pytest.importorskip("torch")
transformers = pytest.importorskip("transformers")


def _bert(tiny=True):
    cfg = transformers.BertConfig(
        vocab_size=99, hidden_size=32, num_hidden_layers=2,
        num_attention_heads=4, intermediate_size=64,
        max_position_embeddings=48, type_vocab_size=2)
    m = transformers.BertModel(cfg)
    m.eval()
    return m


def _distilbert():
    cfg = transformers.DistilBertConfig(
        vocab_size=99, dim=32, n_layers=2, n_heads=4, hidden_dim=64,
        max_position_embeddings=48)
    m = transformers.DistilBertModel(cfg)
    m.eval()
    return m


def _ids(b=2, t=12, vocab=99, seed=0):
    rng = np.random.RandomState(seed)
    ids = rng.randint(0, vocab, size=(b, t)).astype(np.int32)
    mask = np.ones((b, t), np.int32)
    mask[0, t - 3:] = 0     # ragged: one padded sequence
    return ids, mask


class TestBertParity:
    def test_bert_matches_hf(self):
        m = _bert()
        ids, mask = _ids()
        tt = np.zeros_like(ids)
        tt[:, 6:] = 1
        with torch.no_grad():
            ref = m(input_ids=torch.tensor(ids.astype(np.int64)),
                    attention_mask=torch.tensor(mask.astype(np.int64)),
                    token_type_ids=torch.tensor(tt.astype(np.int64)))
        eng = ds.init_inference(model=m, config={"dtype": "float32"})
        hidden, pooled = eng.forward(ids, attention_mask=mask,
                                     token_type_ids=tt)
        # padded positions produce garbage on both sides — compare valid ones
        valid = mask.astype(bool)
        np.testing.assert_allclose(
            np.asarray(hidden)[valid],
            ref.last_hidden_state.numpy()[valid], rtol=2e-4, atol=2e-4)
        np.testing.assert_allclose(np.asarray(pooled),
                                   ref.pooler_output.numpy(),
                                   rtol=2e-4, atol=2e-4)

    def test_distilbert_matches_hf(self):
        m = _distilbert()
        ids, mask = _ids(seed=1)
        with torch.no_grad():
            ref = m(input_ids=torch.tensor(ids.astype(np.int64)),
                    attention_mask=torch.tensor(mask.astype(np.int64)))
        eng = ds.init_inference(model=m, config={"dtype": "float32"})
        hidden, pooled = eng.forward(ids, attention_mask=mask)
        assert pooled is None
        valid = mask.astype(bool)
        np.testing.assert_allclose(
            np.asarray(hidden)[valid],
            ref.last_hidden_state.numpy()[valid], rtol=2e-4, atol=2e-4)

    def test_bert_tp_sharded(self, eight_devices):
        """tp=4: column/row kernels physically sharded over the tensor axis;
        outputs equal to the tp=1 run."""
        m = _bert()
        ids, mask = _ids(seed=2)
        eng1 = ds.init_inference(model=m, config={"dtype": "float32"})
        h1, p1 = eng1.forward(ids, attention_mask=mask)
        eng4 = ds.init_inference(model=m, config={"dtype": "float32",
                                                  "tensor_parallel": {"tp_size": 4}})
        spec = eng4.params["layers_0"]["q_proj"]["kernel"].sharding.spec
        assert "tensor" in tuple(spec), spec
        h4, p4 = eng4.forward(ids, attention_mask=mask)
        valid = mask.astype(bool)
        np.testing.assert_allclose(np.asarray(h4)[valid], np.asarray(h1)[valid],
                                   rtol=2e-4, atol=2e-4)

    def test_fresh_config_serving(self):
        """EncoderConfig without weights: random init, forward runs, shapes HF-like."""
        cfg = bert_cfg(vocab_size=64, max_seq_len=32, n_embd=32, n_layer=2,
                       n_head=4)
        eng = ds.init_inference(model=cfg, config={"dtype": "float32"})
        ids, mask = _ids(vocab=64, seed=3)
        hidden, pooled = eng.forward(ids, attention_mask=mask)
        assert hidden.shape == (2, 12, 32)
        assert pooled.shape == (2, 32)
        assert np.isfinite(np.asarray(hidden)).all()


class TestEncoderInt8:
    def test_int8_close_to_fp_and_sharded(self, eight_devices):
        """dtype='int8': encoder matmul weights grouped-quantized at load (same
        GroupQuantizer analogue as the decoder engine), outputs close to fp,
        and the int8 payloads shard over the tensor axis at tp=2."""
        import jax.numpy as jnp
        m = _bert()
        ids, mask = _ids(seed=5)
        e_fp = ds.init_inference(model=m, config={"dtype": "float32"})
        h_fp, _ = e_fp.forward(ids, attention_mask=mask)

        e_q = ds.init_inference(model=m, config={
            "dtype": "int8", "tensor_parallel": {"tp_size": 2}})
        qnode = e_q.params["layers_0"]["q_proj"]["kernel"]
        assert isinstance(qnode, dict) and qnode["__int8_q__"].dtype == jnp.int8
        assert "tensor" in str(qnode["__int8_q__"].sharding.spec)
        h_q, _ = e_q.forward(ids, attention_mask=mask)

        valid = mask.astype(bool)
        a = np.asarray(h_fp)[valid]
        b = np.asarray(h_q)[valid]
        err = np.abs(b - a).mean() / (np.abs(a).mean() + 1e-9)
        assert err < 0.05, f"relative int8 error {err:.4f} too large"
