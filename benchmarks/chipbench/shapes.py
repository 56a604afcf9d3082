"""Operations and bytes that a model step or a kernel call needs, from shapes.

The yardstick's own arithmetic: utilisations and roofline shares divide by
these, so they live with the benchmark and not with the program.
"""


def gpt2_params(n_layer: int, n_embd: int, vocab_size: int, n_positions: int) -> int:
    """Parameters of a GPT-2 model, embedding tables included (copied from
    ``GPT2Config.num_params``)."""
    d = n_embd
    return (vocab_size * d + n_positions * d
            + n_layer * (12 * d * d + 13 * d) + 2 * d)


def gpt2_train_flops_per_token(n_layer: int, n_embd: int, vocab_size: int,
                               n_positions: int, seq: int) -> float:
    """6 N + 12 L d s (copied from ``GPT2Config.flops_per_token``). N includes
    the embedding tables, whose lookup is no matmul (the tied head's is); the
    attention term is not halved for causality. Recomputation in the backward
    pass is not counted."""
    n = gpt2_params(n_layer, n_embd, vocab_size, n_positions)
    return 6.0 * n + 12.0 * n_layer * n_embd * seq


# ---- flash attention, causal, per kernel CALL over (batch*heads) sequences.
# s x s x d matmuls of each kernel: forward QK^T and PV (2); the dq kernel
# recomputes QK^T, then dP = dO V^T and dQ = dS K (3); the dkv kernel
# recomputes QK^T, then dV = P^T dO, dP = dO V^T and dK = dS^T Q (4). A causal
# mask leaves half of each.
FLASH_MATMULS = {"flash_fwd": 2, "flash_bwd_dq": 3, "flash_bwd_dkv": 4}
# (batch*heads, s, d) arrays each kernel reads and writes, 2 bytes an element
# (bf16): fwd q,k,v -> o; dq q,k,v,do -> dq; dkv q,k,v,do -> dk,dv. The
# per-row statistics (f32, s a head) are small beside them and left out.
FLASH_ARRAYS = {"flash_fwd": 4, "flash_bwd_dq": 5, "flash_bwd_dkv": 6}


def flash_call_flops(kernel: str, batch_heads: int, seq: int, d_head: int) -> float:
    return FLASH_MATMULS[kernel] * 2.0 * batch_heads * seq * seq * d_head / 2.0


def flash_call_bytes(kernel: str, batch_heads: int, seq: int, d_head: int) -> float:
    return FLASH_ARRAYS[kernel] * 2.0 * batch_heads * seq * d_head


def causal_lm_params(n_layer: int, n_embd: int, vocab_size: int) -> int:
    """Parameters of a BLOOM-shaped decoder (tied head, no position table,
    embedding layernorm): what one decode step reads."""
    d = n_embd
    return vocab_size * d + n_layer * (12 * d * d + 13 * d) + 4 * d


def kv_bytes_per_token(n_layer: int, n_head: int, d_head: int,
                       bytes_per_el: int = 2) -> int:
    return 2 * n_layer * n_head * d_head * bytes_per_el


def decode_step_bytes(n_layer: int, n_embd: int, n_head: int, vocab_size: int,
                      live_tokens: float, bytes_per_el: int = 2) -> float:
    """Bytes one decode step has to read: every weight once, and the keys and
    values of the live tokens of all slots."""
    return (causal_lm_params(n_layer, n_embd, vocab_size) * bytes_per_el
            + live_tokens * kv_bytes_per_token(n_layer, n_head,
                                               n_embd // n_head, bytes_per_el))
