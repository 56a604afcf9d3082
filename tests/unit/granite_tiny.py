"""The tiny Granite hybrid the CPU tests share: both mixers (Mamba-2 with ONE
state group serving all its heads, attention at head 16 without positions),
the gated feed-forward after each, the four multipliers away from their
defaults, the tied head: four published layers = eight mixer layers."""

import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

MODEL = dict(hidden_size=64, num_hidden_layers=4,
             layer_types=["mamba", "attention", "mamba", "mamba"], vocab_size=256,
             num_attention_heads=4, num_key_value_heads=2,
             shared_intermediate_size=96, mamba_n_heads=8, mamba_d_head=16,
             mamba_d_state=16, mamba_n_groups=1, mamba_d_conv=4, mamba_chunk_size=8,
             mamba_expand=2, embedding_multiplier=12, residual_multiplier=0.22,
             attention_multiplier=0.015625, logits_scaling=8, rms_norm_eps=1e-5)
PATTERN = "MF*FMFMF"
#: what every other family has: a model built with one of these in place of
#: MODEL's value has dropped that multiplier
DEFAULTS = dict(embedding_multiplier=1, residual_multiplier=1,
                attention_multiplier=16 ** -0.5, logits_scaling=1)


def reference():
    """``benchmarks/chipbench/reference/granite_hybrid.py``, loaded by path."""
    path = os.path.join(REPO, "benchmarks", "chipbench", "reference",
                        "granite_hybrid.py")
    spec = importlib.util.spec_from_file_location(
        "chipbench_reference_granite_hybrid", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def config(dtype=jnp.float32, max_seq_len=64, **over):
    from deepspeed_tpu.models.causal_lm import granite_hybrid_cfg
    kw = {"init_std": 0.3, **MODEL, **over}
    return granite_hybrid_cfg(max_seq_len=max_seq_len, dtype=dtype, **kw)


def init(cfg, seed=0):
    """Seeded random parameters at ``init_std`` 0.3: the scores of a 16-wide
    head under ``attention_multiplier`` 1/64 would all but vanish at 0.02, and
    no test could tell the multiplier from ``1 / sqrt(16)``. The norms'
    weights away from one, so that a layer that drops one shows."""
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
        if "mamba" in lp:
            lp["mamba"]["norm_w"] = 1.0 + 0.3 * jax.random.normal(
                jax.random.fold_in(key, 100 + i), lp["mamba"]["norm_w"].shape)
            lp["mamba"]["D"] = 1.0 + 0.3 * jax.random.normal(
                jax.random.fold_in(key, 200 + i), lp["mamba"]["D"].shape)
    return module, params


def ids(n, seed=0, vocab=256, batch=1):
    return np.random.RandomState(seed).randint(1, vocab, (batch, n)).astype(np.int32)
