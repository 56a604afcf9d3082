"""Bytes and operations of a decode step of an LFM2 mixture-of-experts model
(gated short convolutions and attention; a dense feed-forward and then
experts), from the configuration's ``model`` section (the keywords of the
published ``lfm2_moe`` config). The yardstick's own arithmetic, beside
``shapes.py``, ``hybrid_shapes.py`` and ``sdar_shapes.py``: roofline shares
divide by these, so they live with the benchmark and not with the program.
"""


def operators(model: dict) -> list:
    """``layer_types`` of the layers that are run: the first ``num_hidden_layers``."""
    return list(model["layer_types"][:int(model["num_hidden_layers"])])


def expert_layers(model: dict) -> int:
    return max(0, int(model["num_hidden_layers"]) - int(model["num_dense_layers"]))


def held_experts(model: dict) -> int:
    held = model.get("experts_held")
    return int(held[1]) if held else int(model["num_experts"])


def expert_params(model: dict) -> int:
    """One routed expert: gate, up and down matrices, no bias."""
    return 3 * int(model["hidden_size"]) * int(model["moe_intermediate_size"])


def conv_params(model: dict) -> int:
    """A gated short convolution: d x 3d in, the taps, d x d out."""
    d = int(model["hidden_size"])
    return d * 3 * d + int(model.get("conv_L_cache", 3)) * d + d * d


def attention_params(model: dict) -> int:
    """q/k/v/o and the two per-head norms of q and k."""
    d = int(model["hidden_size"])
    hd = d // int(model["num_attention_heads"])
    kv = int(model["num_key_value_heads"]) * hd
    return d * d + 2 * d * kv + d * d + 2 * hd


def dense_ffn_params(model: dict) -> int:
    return 3 * int(model["hidden_size"]) * int(model["intermediate_size"])


def router_params(model: dict) -> int:
    """The router's matrix and the expert bias."""
    return int(model["hidden_size"]) * int(model["num_experts"]) + int(model["num_experts"])


def params_beside_experts(model: dict) -> int:
    """Every parameter a decode step reads whatever the routing: each layer's
    operator and its two norms, the dense feed-forward or the router, the
    final norm, and the embedding, which is the tied head's matrix (of it as
    an embedding a step reads one row a sequence: left out)."""
    d = int(model["hidden_size"])
    dense = int(model["num_dense_layers"])
    per = {"conv": conv_params(model), "full_attention": attention_params(model)}
    return (sum(per[op] + 2 * d + (dense_ffn_params(model) if i < dense
                                   else router_params(model))
                for i, op in enumerate(operators(model)))
            + d + d * int(model["vocab_size"]))


def params_held(model: dict) -> int:
    """All parameters this stage holds (the embedding once: the head is tied)."""
    return params_beside_experts(model) \
        + expert_layers(model) * held_experts(model) * expert_params(model)


def kv_bytes_per_token(model: dict, bytes_per_el: int = 2) -> int:
    hd = int(model["hidden_size"]) // int(model["num_attention_heads"])
    return (2 * operators(model).count("full_attention")
            * int(model["num_key_value_heads"]) * hd * bytes_per_el)


def conv_state_bytes_per_slot(model: dict, bytes_per_el: int = 2) -> int:
    """One sequence's windows: the last ``conv_L_cache - 1`` products a conv layer."""
    return (operators(model).count("conv") * (int(model.get("conv_L_cache", 3)) - 1)
            * int(model["hidden_size"]) * bytes_per_el)


def moe_ffn_bytes(experts_touched: float, model: dict, bytes_per_el: int = 2) -> float:
    """Bytes the grouped expert kernel has to read: each touched expert's
    three matrices once (the rows it reads and writes are a few thousandths
    of that: left out)."""
    return experts_touched * expert_params(model) * bytes_per_el


def moe_ffn_flops(assignments: float, model: dict) -> float:
    """Three matmuls an assignment, 2 operations a multiply-add."""
    return assignments * 2.0 * expert_params(model)


def decode_step_bytes(model: dict, slots: int, experts_touched: float,
                      live_tokens: float, bytes_per_el: int = 2) -> float:
    """Bytes one decode step has to move: every parameter beside the routed
    experts once, each touched expert once, the convolutions' windows of
    every slot read and written, and the live keys and values read."""
    return (params_beside_experts(model) * bytes_per_el
            + moe_ffn_bytes(experts_touched, model, bytes_per_el)
            + 2.0 * slots * conv_state_bytes_per_slot(model, bytes_per_el)
            + live_tokens * kv_bytes_per_token(model, bytes_per_el))
