"""Bytes and operations of one forward of an SDAR mixture-of-experts model
that generates by diffusion over blocks, from the configuration's ``model``
section (the keywords of the published ``sdar_moe`` config). The yardstick's
own arithmetic, beside ``shapes.py`` and ``hybrid_shapes.py``: roofline shares
divide by these, so they live with the benchmark and not with the program.
"""


def held_experts(model: dict) -> int:
    held = model.get("experts_held")
    return int(held[1]) if held else int(model["num_experts"])


def expert_params(model: dict) -> int:
    """One routed expert: gate, up and down matrices, no bias."""
    return 3 * int(model["hidden_size"]) * int(model["moe_intermediate_size"])


def layer_params_beside_experts(model: dict) -> int:
    """Of one published layer, what every forward reads whatever the routing:
    q/k/v/o, the two per-head norms of q and k, the layer's two norms and
    the router."""
    d, hd = int(model["hidden_size"]), int(model["head_dim"])
    q = int(model["num_attention_heads"]) * hd
    kv = int(model["num_key_value_heads"]) * hd
    return d * q + 2 * d * kv + q * d + 2 * hd + 2 * d + d * int(model["num_experts"])


def params_beside_experts(model: dict) -> int:
    """Every parameter a forward reads whatever the routing: the layers beside
    their experts, the final norm and the untied head. (Of the embedding a
    forward reads one row a position: counted by :func:`forward_bytes`.)"""
    d = int(model["hidden_size"])
    return (int(model["num_hidden_layers"]) * layer_params_beside_experts(model)
            + d + d * int(model["vocab_size"]))


def params_held(model: dict) -> int:
    """All parameters this stage holds, embedding and head included."""
    return (params_beside_experts(model)
            + int(model["num_hidden_layers"]) * held_experts(model) * expert_params(model)
            + int(model["hidden_size"]) * int(model["vocab_size"]))


def kv_bytes_per_token(model: dict, bytes_per_el: int = 2) -> int:
    return (2 * int(model["num_hidden_layers"]) * int(model["num_key_value_heads"])
            * int(model["head_dim"]) * bytes_per_el)


def moe_ffn_bytes(experts_touched: float, model: dict, bytes_per_el: int = 2) -> float:
    """Bytes the grouped expert kernel has to read: each touched expert's
    three matrices once (the rows it reads and writes are a few hundredths of
    that: left out)."""
    return experts_touched * expert_params(model) * bytes_per_el


def moe_ffn_flops(assignments: float, model: dict) -> float:
    """Three matmuls an assignment, 2 operations a multiply-add."""
    return assignments * 2.0 * expert_params(model)


def forward_bytes(model: dict, slots: int, experts_touched: float,
                  live_tokens: float, bytes_per_el: int = 2) -> float:
    """Bytes one forward of a block a slot has to move: every parameter beside
    the experts once, each touched expert once, the embedding rows of the
    block's positions, the committed keys and values read, and the block's
    own keys and values written and read back."""
    rows = slots * int(model["gen_block_length"])
    return (params_beside_experts(model) * bytes_per_el
            + moe_ffn_bytes(experts_touched, model, bytes_per_el)
            + rows * int(model["hidden_size"]) * bytes_per_el
            + (live_tokens + 2.0 * rows) * kv_bytes_per_token(model, bytes_per_el))
