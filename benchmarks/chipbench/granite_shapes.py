"""Bytes of a decode step of a Granite 4.0 hybrid without experts (Mamba-2 and
attention mixers, a gated feed-forward after each, a tied head), from the
configuration's ``model`` section (the keywords of the published
``granitemoehybrid`` config). The yardstick's own arithmetic, beside
``shapes.py``, ``hybrid_shapes.py``, ``sdar_shapes.py`` and ``lfm2_shapes.py``:
roofline shares divide by these, so they live with the benchmark and not with
the program.
"""


def mixers(model: dict) -> list:
    """``layer_types`` of the layers that are run: the first ``num_hidden_layers``."""
    return list(model["layer_types"][:int(model["num_hidden_layers"])])


def mamba_inner(model: dict) -> int:
    return int(model["mamba_n_heads"]) * int(model["mamba_d_head"])


def conv_dim(model: dict) -> int:
    """Channels of the convolution: x and the groups' B and C."""
    return mamba_inner(model) + 2 * int(model["mamba_n_groups"]) * int(model["mamba_d_state"])


def mamba_params(model: dict) -> int:
    """in_proj to [z | xBC | dt], the taps and their bias, dt_bias, A_log and D
    a head, the gated norm's weight, out_proj."""
    d, inner, h = int(model["hidden_size"]), mamba_inner(model), int(model["mamba_n_heads"])
    return (d * (inner + conv_dim(model) + h)
            + (int(model["mamba_d_conv"]) + 1) * conv_dim(model)
            + 3 * h + inner + inner * d)


def attention_params(model: dict) -> int:
    """q/k/v/o, no bias, no norm of q and k."""
    d = int(model["hidden_size"])
    hd = d // int(model["num_attention_heads"])
    return 2 * d * d + 2 * d * int(model["num_key_value_heads"]) * hd


def mlp_params(model: dict) -> int:
    return 3 * int(model["hidden_size"]) * int(model["shared_intermediate_size"])


def params(model: dict) -> int:
    """Every parameter (the embedding once: the head is tied): per published
    layer its mixer, its feed-forward and their two norms; the final norm."""
    d = int(model["hidden_size"])
    per = {"mamba": mamba_params(model), "attention": attention_params(model)}
    return (sum(per[kind] + mlp_params(model) + 2 * d for kind in mixers(model))
            + d + d * int(model["vocab_size"]))


def ssm_state_bytes_per_slot(model: dict, bytes_per_el: int = 4) -> int:
    """One sequence's recurrent state: heads x head size x state a Mamba layer, float32."""
    return (mixers(model).count("mamba") * mamba_inner(model)
            * int(model["mamba_d_state"]) * bytes_per_el)


def conv_state_bytes_per_slot(model: dict, bytes_per_el: int = 2) -> int:
    """One sequence's windows: the last ``mamba_d_conv - 1`` inputs a Mamba layer."""
    return (mixers(model).count("mamba") * (int(model["mamba_d_conv"]) - 1)
            * conv_dim(model) * bytes_per_el)


def kv_bytes_per_token(model: dict, bytes_per_el: int = 2) -> int:
    hd = int(model["hidden_size"]) // int(model["num_attention_heads"])
    return (2 * mixers(model).count("attention")
            * int(model["num_key_value_heads"]) * hd * bytes_per_el)


def ssm_update_bytes(slots: int, model: dict) -> int:
    """Bytes the one-token state update has to move, all layers: every slot's
    recurrent state read once and written once (its other operands, a few
    rows a slot, are thousandths of that: left out)."""
    return 2 * slots * ssm_state_bytes_per_slot(model)


def decode_step_bytes(model: dict, slots: int, live_tokens: float,
                      bytes_per_el: int = 2) -> float:
    """Bytes one decode step has to move: every parameter once (the tied
    table once: as the head; as an embedding a step reads a row a sequence),
    the recurrent state and the windows of every slot read and written, and
    the live keys and values read."""
    return (params(model) * bytes_per_el
            + ssm_update_bytes(slots, model)
            + 2.0 * slots * conv_state_bytes_per_slot(model, bytes_per_el)
            + live_tokens * kv_bytes_per_token(model, bytes_per_el))
