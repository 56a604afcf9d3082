"""Bytes and operations of a decode step of a hybrid of state-space, attention
and expert layers, from the configuration's ``model`` section (the keywords of
the published config). The yardstick's own arithmetic, beside ``shapes.py``:
roofline shares divide by these, so they live with the benchmark and not with
the program.
"""


def held_experts(model: dict) -> int:
    held = model.get("experts_held")
    return int(held[1]) if held else int(model["n_routed_experts"])


def expert_params(model: dict) -> int:
    """One routed expert: latent -> width -> latent, no bias."""
    return 2 * int(model["moe_latent_size"]) * int(model["moe_intermediate_size"])


def layer_params_beside_experts(model: dict, kind: str) -> int:
    """Parameters a decode step reads of one layer whatever the routing, its
    norm included: an expert layer's router, selection bias, latent
    projections and shared expert; a Mamba-2 or attention layer whole."""
    d = int(model["hidden_size"])
    if kind == "E":
        n, lat = int(model["n_routed_experts"]), int(model["moe_latent_size"])
        return (d * n + n + 2 * d * lat
                + 2 * d * int(model["moe_shared_expert_intermediate_size"]) + d)
    if kind == "*":
        q = int(model["num_attention_heads"]) * int(model["head_dim"])
        kv = int(model["num_key_value_heads"]) * int(model["head_dim"])
        return d * q + 2 * d * kv + q * d + d
    if kind == "M":
        h = int(model["mamba_num_heads"])
        inner = h * int(model["mamba_head_dim"])
        conv = inner + 2 * int(model["n_groups"]) * int(model["ssm_state_size"])
        return (d * (inner + conv + h) + (int(model["conv_kernel"]) + 1) * conv
                + 3 * h + inner + inner * d + d)
    raise ValueError(f"unknown layer kind {kind!r}")


def params_beside_experts(model: dict) -> int:
    """Every parameter a decode step reads whatever the routing: the layers
    beside their routed experts, the final norm and the untied head. (Of the
    embedding a step reads one row a sequence: left out.)"""
    d = int(model["hidden_size"])
    return (sum(layer_params_beside_experts(model, k)
                for k in model["hybrid_override_pattern"])
            + d + d * int(model["vocab_size"]))


def params_held(model: dict) -> int:
    """All parameters this share holds, embedding and head included."""
    n_e = model["hybrid_override_pattern"].count("E")
    return (params_beside_experts(model) + n_e * held_experts(model) * expert_params(model)
            + int(model["hidden_size"]) * int(model["vocab_size"]))


def ssm_state_bytes_per_slot(model: dict) -> int:
    """One sequence's recurrent state over the Mamba-2 layers, float32."""
    return (model["hybrid_override_pattern"].count("M") * int(model["mamba_num_heads"])
            * int(model["mamba_head_dim"]) * int(model["ssm_state_size"]) * 4)


def conv_state_bytes_per_slot(model: dict, bytes_per_el: int = 2) -> int:
    h = int(model["mamba_num_heads"])
    conv = h * int(model["mamba_head_dim"]) \
        + 2 * int(model["n_groups"]) * int(model["ssm_state_size"])
    return (model["hybrid_override_pattern"].count("M") * conv
            * (int(model["conv_kernel"]) - 1) * bytes_per_el)


def kv_bytes_per_token(model: dict, bytes_per_el: int = 2) -> int:
    return (2 * model["hybrid_override_pattern"].count("*")
            * int(model["num_key_value_heads"]) * int(model["head_dim"]) * bytes_per_el)


def moe_ffn_bytes(experts_touched: float, model: dict, bytes_per_el: int = 2) -> float:
    """Bytes the grouped expert kernel has to read: each touched expert once
    (the rows it reads and writes are a few hundredths of that: left out)."""
    return experts_touched * expert_params(model) * bytes_per_el


def moe_ffn_flops(assignments: float, model: dict) -> float:
    """Two matmuls an assignment, 2 operations a multiply-add."""
    return assignments * 2.0 * expert_params(model)


def ssm_step_bytes(slots: int, model: dict) -> float:
    """The recurrent state of every slot read and written once a step."""
    return 2.0 * slots * ssm_state_bytes_per_slot(model)


def decode_step_bytes(model: dict, slots: int, experts_touched: float,
                      live_tokens: float, bytes_per_el: int = 2) -> float:
    """Bytes one decode step has to move: every parameter beside the routed
    experts once, each touched expert once, the recurrent state of every slot
    read and written, the convolution's inputs likewise, and the live keys
    and values read."""
    return (params_beside_experts(model) * bytes_per_el
            + moe_ffn_bytes(experts_touched, model, bytes_per_el)
            + ssm_step_bytes(slots, model)
            + 2.0 * slots * conv_state_bytes_per_slot(model, bytes_per_el)
            + live_tokens * kv_bytes_per_token(model, bytes_per_el))
