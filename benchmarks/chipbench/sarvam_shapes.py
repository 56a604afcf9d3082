"""Bytes and operations of a ``sarvam_mla`` model's steps (latent attention
without a query latent; a dense SwiGLU in the first layers, then a
sigmoid-and-bias mixture of SwiGLU experts beside a shared expert; an untied
head), from the configuration's ``model`` section (the keywords of the
published config, with ``experts_held`` the chip's share and ``vocab_size``
the rows held). The yardstick's own arithmetic: roofline shares divide by
these, so they live with the benchmark and not with the program. A
configuration names the functions its readers call (its ``shapes`` section),
as it names its reference.

The attention is counted AS PUBLISHED: a cached row is ``kv_lora_rank +
qk_rope_head_dim`` lanes (576) for the operations, whatever zero lanes the
program stores behind it; the BYTES of a row are what is stored (``row_lanes``:
whole 128-lane tiles, 640), since those are what a step reads.
"""


def heads(model: dict) -> int:
    return int(model["num_attention_heads"])


def row_width(model: dict) -> int:
    """A cached row as published: the latent and the shared rotary key."""
    return int(model["kv_lora_rank"]) + int(model["qk_rope_head_dim"])


def row_lanes(model: dict) -> int:
    """A cached row as stored: whole 128-lane tiles."""
    return -(-row_width(model) // 128) * 128


def layers(model: dict) -> int:
    return int(model["num_hidden_layers"])


def expert_layers(model: dict) -> int:
    return layers(model) - int(model.get("first_k_dense_replace", 1))


def held_experts(model: dict) -> int:
    held = model.get("experts_held")
    return int(held[1]) if held else int(model["num_experts"])


def up_params(model: dict) -> int:
    """``kv_b_proj``: the keys' and the values' expansion of every head."""
    return int(model["kv_lora_rank"]) * heads(model) \
        * (int(model["qk_nope_head_dim"]) + int(model["v_head_dim"]))


def attention_params(model: dict) -> int:
    d, h = int(model["hidden_size"]), heads(model)
    q = int(model["qk_nope_head_dim"]) + int(model["qk_rope_head_dim"])
    return (d * h * q + d * row_width(model) + int(model["kv_lora_rank"])
            + up_params(model) + h * int(model["v_head_dim"]) * d)


def dense_ffn_params(model: dict) -> int:
    return 3 * int(model["hidden_size"]) * int(model["intermediate_size"])


def expert_params(model: dict) -> int:
    """One routed expert: gate, up and down matrices, no bias."""
    return 3 * int(model["hidden_size"]) * int(model["moe_intermediate_size"])


def shared_params(model: dict) -> int:
    return int(model.get("num_shared_experts", 1)) * expert_params(model)


def router_params(model: dict) -> int:
    """The router's matrix over ALL experts and its selection bias."""
    return (int(model["hidden_size"]) + 1) * int(model["num_experts"])


def params_beside_experts(model: dict) -> int:
    """Every parameter a decode step reads whatever the routing: each
    published layer's attention and its two norms, the dense layers'
    feed-forward, an expert layer's router and shared expert, the final norm
    and the untied head. (The embedding gives a row a sequence: left out.)"""
    d = int(model["hidden_size"])
    dense = layers(model) - expert_layers(model)
    return (layers(model) * (attention_params(model) + 2 * d)
            + dense * dense_ffn_params(model)
            + expert_layers(model) * (router_params(model) + shared_params(model))
            + d + d * int(model["vocab_size"]))


def params(model: dict) -> int:
    """All parameters this chip holds (embedding and head each once)."""
    return (params_beside_experts(model)
            + int(model["hidden_size"]) * int(model["vocab_size"])
            + expert_layers(model) * held_experts(model) * expert_params(model))


def latent_attn_bytes(live_tokens: float, slots: int, model: dict,
                      bytes_per_el: int = 2) -> float:
    """Bytes the absorbed attention of ONE decode step has to move, all
    layers: every live row as stored, once (scores and values read the same
    row), and ``kv_b_proj`` once a layer (the queries into the latent, the
    result out of it). Queries and outputs (``slots`` x heads x row) are
    activations that need not leave the chip's fast memory: left out."""
    return layers(model) * bytes_per_el * (
        live_tokens * row_lanes(model) + up_params(model))


def latent_attn_flops(live_tokens: float, slots: int, model: dict) -> float:
    """Operations of the same: a head's score against a row over the
    published ``row_width`` lanes and its values over the latent's, two a
    multiply-add, every head against every live row; and the two absorbing
    products a sequence."""
    per_row = heads(model) * (row_width(model) + int(model["kv_lora_rank"]))
    return layers(model) * 2.0 * (live_tokens * per_row + slots * up_params(model))


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


def decode_step_bytes(model: dict, slots: int, live_tokens: float,
                      experts_touched: float, bytes_per_el: int = 2) -> float:
    """Bytes one decode step has to move: every parameter beside the routed
    experts once, each touched expert once (``experts_touched`` over all
    layers), and every live latent row as stored. What the program moves
    besides (the chunk's dense view, gathered once a chunk) is no part of
    what a step HAS to move."""
    return (params_beside_experts(model) * bytes_per_el
            + experts_touched * expert_params(model) * bytes_per_el
            + live_tokens * layers(model) * row_lanes(model) * bytes_per_el)


def prefill_attn_flops(tokens: int, model: dict) -> float:
    """Operations of a prompt's causal attention, expanded, as published: a
    head's queries and keys ``qk_nope_head_dim + qk_rope_head_dim`` (192)
    wide, its values ``v_head_dim`` (128), half the square."""
    per_pair = int(model["qk_nope_head_dim"]) + int(model["qk_rope_head_dim"]) \
        + int(model["v_head_dim"])
    return layers(model) * heads(model) * per_pair * 2.0 * tokens * (tokens + 1) / 2.0
