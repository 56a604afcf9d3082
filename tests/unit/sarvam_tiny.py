"""The tiny ``sarvam_mla`` model the CPU tests share: latent attention (4 heads
of 16 + 8 lanes against values of 16, a latent of 32, ``deepseek_yarn``), one
dense SwiGLU layer and then two layers of 8 SwiGLU experts, the top 2 by
sigmoid score + bias, beside a shared expert; an untied head."""

import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

ROPE = {"beta_fast": 32, "beta_slow": 1, "factor": 40, "mscale": 1, "mscale_all_dim": 1,
        "original_max_position_embeddings": 4096, "type": "deepseek_yarn"}
MODEL = dict(hidden_size=64, num_hidden_layers=3, vocab_size=256, num_attention_heads=4,
             kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
             intermediate_size=128, moe_intermediate_size=32, num_experts=8,
             num_experts_per_tok=2, num_shared_experts=1, first_k_dense_replace=1,
             routed_scaling_factor=2.5, rope_theta=10000, rope_scaling=ROPE,
             rms_norm_eps=1e-6)
PATTERN = "LFLELE"


def reference():
    """``benchmarks/chipbench/reference/sarvam_mla.py``, loaded by path."""
    path = os.path.join(REPO, "benchmarks", "chipbench", "reference", "sarvam_mla.py")
    spec = importlib.util.spec_from_file_location("chipbench_reference_sarvam_mla", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def config(dtype=jnp.float32, max_seq_len=64, **over):
    from deepspeed_tpu.models.causal_lm import sarvam_mla_cfg
    kw = {"init_std": 0.3, "out_init_std": 0.1, **MODEL, **over}
    return sarvam_mla_cfg(max_seq_len=max_seq_len, dtype=dtype, **kw)


def init(cfg, seed=0):
    """Seeded random parameters at ``init_std`` 0.3 (scores that spread, so
    that a wrong scale or a dropped rotary term shows), every norm's weight
    and every router's bias away from their initial values."""
    from deepspeed_tpu.models.causal_lm import CausalLM
    module = CausalLM(cfg)
    params = jax.jit(lambda key: module.init(
        {"params": key}, jnp.zeros((1, 8), jnp.int32))["params"])(
            jax.random.PRNGKey(seed))
    params = jax.tree_util.tree_map(lambda a: a, params)
    key = jax.random.PRNGKey(seed + 100)
    for i in range(cfg.n_layer):
        lp = params[f"layers_{i}"]
        lp["norm"]["scale"] = 1.0 + 0.3 * jax.random.normal(
            jax.random.fold_in(key, i), lp["norm"]["scale"].shape)
        if "kv_a_norm" in lp:
            lp["kv_a_norm"]["scale"] = 1.0 + 0.3 * jax.random.normal(
                jax.random.fold_in(key, 100 + i), lp["kv_a_norm"]["scale"].shape)
        if "moe" in lp:
            lp["moe"]["router_bias"] = 0.2 * jax.random.normal(
                jax.random.fold_in(key, 200 + i), lp["moe"]["router_bias"].shape)
    return module, params


def ids(n, seed=0, vocab=256, batch=1):
    return np.random.RandomState(seed).randint(1, vocab, (batch, n)).astype(np.int32)
