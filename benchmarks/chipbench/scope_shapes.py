"""Operations of the matrix products that each declared scope of a GPT-2
training step holds, from shapes: the yardstick's own arithmetic for
``train_matmul_roofline_pct``. What ``train_mfu_pct`` counts (``shapes.py``:
6 N + 12 L d s a token), less the attention's ``12 L d s`` and the
parameters that are in no matrix product (biases, norms, the position table):
``6 x (12 L d^2 + d V)`` a token, a third of it forward and two thirds
backward. Recomputation is not counted."""

from typing import Dict

#: forward multiply-adds a token a layer, in units of ``d^2``: q/k/v ``d x 3d``,
#: the output projection ``d x d``, the MLP's two ``d x 4d``
LAYER_MATMULS = {"attn.qkv": 3, "attn.out": 1, "mlp.up": 4, "mlp.down": 4}
MATMUL_SCOPES = tuple(LAYER_MATMULS) + ("head",)


def gpt2_forward_matmul_flops_per_token(n_layer: int, n_embd: int,
                                        vocab_size: int) -> Dict[str, float]:
    """Forward operations a token of each scope's matrix products (2 a
    multiply-add); the head is the tied ``d x V`` product."""
    d = float(n_embd)
    out = {scope: 2.0 * n_layer * k * d * d for scope, k in LAYER_MATMULS.items()}
    out["head"] = 2.0 * d * vocab_size
    return out


def gpt2_train_matmul_flops_per_token(n_layer: int, n_embd: int,
                                      vocab_size: int) -> float:
    """``6 x (12 L d^2 + d V)``: forward, and twice that backward."""
    return 3.0 * sum(gpt2_forward_matmul_flops_per_token(
        n_layer, n_embd, vocab_size).values())
