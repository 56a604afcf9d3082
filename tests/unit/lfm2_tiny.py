"""The tiny LFM2 the CPU tests share: both operators (gated short convolution,
attention at head 16), both feed-forwards (dense, sigmoid-and-bias experts),
four published layers = eight mixer layers."""

import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

MODEL = dict(hidden_size=64, num_hidden_layers=4,
             layer_types=["conv", "conv", "full_attention", "conv", "conv", "conv"],
             num_dense_layers=2, vocab_size=256, num_attention_heads=4,
             num_key_value_heads=2, intermediate_size=96, moe_intermediate_size=48,
             num_experts=8, num_experts_per_tok=2, conv_L_cache=3,
             norm_topk_prob=True, use_expert_bias=True, routed_scaling_factor=1.0,
             norm_eps=1e-5, rope_theta=1e6)
PATTERN = "CFCF*ECE"


def reference():
    """``benchmarks/chipbench/reference/lfm2_moe.py``, loaded by path."""
    path = os.path.join(REPO, "benchmarks", "chipbench", "reference", "lfm2_moe.py")
    spec = importlib.util.spec_from_file_location("chipbench_reference_lfm2_moe", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def config(dtype=jnp.float32, max_seq_len=64, **over):
    from deepspeed_tpu.models.causal_lm import lfm2_moe_cfg
    return lfm2_moe_cfg(max_seq_len=max_seq_len, dtype=dtype, init_std=0.3,
                        **{**MODEL, **over})


def init(cfg, seed=0):
    """Seeded random parameters at ``init_std`` 0.3 (a 64-wide router at std
    0.02 scores everything 0.5); the expert bias as wide as the scores' own
    spread and the learned norms of q and k away from one, so that a layer
    that drops either shows."""
    from deepspeed_tpu.models.causal_lm import CausalLM
    module = CausalLM(cfg)
    params = jax.jit(lambda key: module.init(
        {"params": key}, jnp.zeros((1, 8), jnp.int32))["params"])(
            jax.random.PRNGKey(seed))
    params = jax.tree_util.tree_map(lambda a: a, params)
    key = jax.random.PRNGKey(seed + 100)
    for i, lp in enumerate(params[f"layers_{j}"] for j in range(cfg.n_layer)):
        if "moe" in lp:
            lp["moe"]["router_bias"] = 0.3 * jax.random.normal(
                jax.random.fold_in(key, i), lp["moe"]["router_bias"].shape)
        for j, n in enumerate(("q_norm", "k_norm")):
            if n in lp:
                lp[n]["scale"] = 1.0 + 0.5 * jax.random.normal(
                    jax.random.fold_in(key, 100 + 2 * i + j), lp[n]["scale"].shape)
    return module, params


def ids(n, seed=0, vocab=256, batch=1):
    return np.random.RandomState(seed).randint(1, vocab, (batch, n)).astype(np.int32)
