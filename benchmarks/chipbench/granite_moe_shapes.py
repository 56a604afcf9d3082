"""Bytes and operations of a decode step of a Granite 4.0 hybrid WITH experts
(Mamba-2 and attention mixers; after each a softmax mixture of SwiGLU experts
beside a gated shared expert; a tied head), from the configuration's ``model``
section (the keywords of the published ``granitemoehybrid`` config, with
``experts_held`` the chip's share). The mixers' arithmetic is
``granite_shapes.py``'s. The yardstick's own arithmetic: roofline shares
divide by these, so they live with the benchmark and not with the program. A
configuration names the functions its readers call (its ``shapes`` section),
as it names its reference.
"""

from benchmarks.chipbench import granite_shapes as gs


def held_experts(model: dict) -> int:
    held = model.get("experts_held")
    return int(held[1]) if held else int(model["num_local_experts"])


def expert_params(model: dict) -> int:
    """One routed expert: gate, up and down matrices, no bias."""
    return 3 * int(model["hidden_size"]) * int(model["intermediate_size"])


def shared_params(model: dict) -> int:
    """The shared expert: gate, up and down matrices of its own width."""
    return 3 * int(model["hidden_size"]) * int(model["shared_intermediate_size"])


def router_params(model: dict) -> int:
    """The router's matrix over ALL experts, no bias."""
    return int(model["hidden_size"]) * int(model["num_local_experts"])


def expert_layer_params(model: dict, experts: int) -> int:
    """An expert layer that holds ``experts`` routed experts, its norm counted."""
    return (int(model["hidden_size"]) + router_params(model) + shared_params(model)
            + experts * expert_params(model))


def params_beside_experts(model: dict) -> int:
    """Every parameter a decode step reads whatever the routing: each
    published layer's mixer with its norm, its expert layer's norm, router
    and shared expert; the final norm; the embedding, which is the tied
    head's matrix (of it as an embedding a step reads a row a sequence: left
    out)."""
    d = int(model["hidden_size"])
    per = {"mamba": gs.mamba_params(model), "attention": gs.attention_params(model)}
    return (sum(per[kind] + d + expert_layer_params(model, 0)
                for kind in gs.mixers(model))
            + d + d * int(model["vocab_size"]))


def params(model: dict) -> int:
    """All parameters this chip holds (the embedding once: the head is tied)."""
    return params_beside_experts(model) \
        + len(gs.mixers(model)) * held_experts(model) * expert_params(model)


def moe_ffn_bytes(experts_touched: float, assignments: float, model: dict,
                  bytes_per_el: int = 2) -> float:
    """Bytes the grouped expert kernel has to move: each touched expert's
    three matrices once, and an assignment's row read (serving type) and its
    result written (float32)."""
    return (experts_touched * expert_params(model) * bytes_per_el
            + assignments * int(model["hidden_size"]) * (bytes_per_el + 4))


def moe_ffn_flops(assignments: float, model: dict) -> float:
    """Three matmuls an assignment, 2 operations a multiply-add."""
    return assignments * 2.0 * expert_params(model)


def moe_step_bytes(experts_touched: float, model: dict, bytes_per_el: int = 2) -> float:
    """Bytes of routed experts a step reads: ``experts_touched`` counts over
    all the expert layers of the step."""
    return experts_touched * expert_params(model) * bytes_per_el


def decode_step_bytes(model: dict, slots: int, live_tokens: float,
                      experts_touched: float, bytes_per_el: int = 2) -> float:
    """Bytes one decode step has to move: every parameter beside the routed
    experts once, each touched expert once (``experts_touched`` over all
    layers), the recurrent state and the convolutions' windows of every slot
    read and written, and the live keys and values read."""
    return (params_beside_experts(model) * bytes_per_el
            + moe_step_bytes(experts_touched, model, bytes_per_el)
            + gs.ssm_update_bytes(slots, model)
            + 2.0 * slots * gs.conv_state_bytes_per_slot(model, bytes_per_el)
            + live_tokens * gs.kv_bytes_per_token(model, bytes_per_el))
