"""The tiny Phi-4-mini-flash (SambaY) preset the CPU tests share: every kind of
layer of the family (Mamba-1, windowed, full and cross differential attention,
gated memory unit, a SwiGLU after each) at widths a CPU runs in seconds."""

import jax.numpy as jnp

from deepspeed_tpu.models.causal_lm import phi4flash_cfg

#: the benchmark configuration's ``rehearsal`` model: 8 layers S W S W S * G X
TINY = dict(hidden_size=64, num_hidden_layers=8, vocab_size=512,
            num_attention_heads=4, num_key_value_heads=2, intermediate_size=96,
            sliding_window=8, mamba_d_state=4, out_init_std=0.04)


def tiny_cfg(max_seq_len: int = 96, dtype=jnp.float32, **over):
    return phi4flash_cfg(max_seq_len=max_seq_len, dtype=dtype, **{**TINY, **over})
