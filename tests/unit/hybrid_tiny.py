"""The tiny hybrid the CPU tests share: every layer kind, grouped keys and
values, experts in a latent space, a share of the experts held."""

import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

MODEL = dict(hidden_size=64, hybrid_override_pattern="MEM*E", vocab_size=256,
             num_attention_heads=4, num_key_value_heads=2, head_dim=16,
             mamba_num_heads=8, mamba_head_dim=8, ssm_state_size=16, n_groups=2,
             conv_kernel=4, chunk_size=8, n_routed_experts=16, num_experts_per_tok=4,
             moe_intermediate_size=48, moe_shared_expert_intermediate_size=96,
             moe_latent_size=32, routed_scaling_factor=2.5, experts_held=[4, 8])


def reference():
    """``benchmarks/chipbench/reference/nemotron_h.py``, loaded by path."""
    path = os.path.join(REPO, "benchmarks", "chipbench", "reference", "nemotron_h.py")
    spec = importlib.util.spec_from_file_location("chipbench_reference_nemotron_h", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def config(dtype=jnp.float32, max_seq_len=64, **over):
    from deepspeed_tpu.models.causal_lm import nemotron_h_cfg
    return nemotron_h_cfg(max_seq_len=max_seq_len, dtype=dtype, **{**MODEL, **over})


def init(cfg, seed=0, router_scale=40.0, routed_scale=4.0):
    """Seeded random parameters. The router is scaled up so that the scores
    spread over (0, 1) as they do at the published width (a 64-wide router at
    std 0.02 scores everything 0.5 and the selection bias alone would choose),
    and the latent path likewise, so that the routed experts add as much as
    the shared one (at these widths they would add a thousandth of it)."""
    from deepspeed_tpu.models.causal_lm import CausalLM
    module = CausalLM(cfg)
    params = jax.jit(lambda key: module.init(
        {"params": key}, jnp.zeros((1, 8), jnp.int32))["params"])(
            jax.random.PRNGKey(seed))
    params = jax.tree_util.tree_map(lambda a: a, params)
    for name, lp in params.items():
        if isinstance(lp, dict) and "moe" in lp:
            lp["moe"]["router"] = lp["moe"]["router"] * router_scale
            for key in ("down", "experts_w1", "experts_w2", "up"):
                lp["moe"][key] = lp["moe"][key] * routed_scale
    return module, params


def ids(n, seed=0, vocab=256, batch=1):
    return np.random.RandomState(seed).randint(1, vocab, (batch, n)).astype(np.int32)
