"""Parameters, bytes and operations of Phi-4-mini-flash (SambaY: Mamba-1 and
windowed differential attention in a self-decoder, ONE full-attention cache
that the cross-decoder's layers re-read, gated memory units), from the
configuration's ``model`` section (the keywords of the published ``phi4flash``
config and the family's defaults that ``assumed`` names). The yardstick's own
arithmetic, beside ``shapes.py`` and the other ``*_shapes.py``: roofline shares
divide by these, so they live with the benchmark and not with the program.
"""


def mixers(model: dict) -> list:
    """The mixer of every published layer, as the family's configuration
    class lays them out: ``mamba``, ``window``, ``full``, ``cross``, ``memory``."""
    n = int(model["num_hidden_layers"])
    half = n // 2
    out = []
    for l in range(n):
        if l % 2 == 0:
            out.append("mamba" if l <= half else "memory")
        else:
            out.append("window" if l < half else "full" if l == half + 1 else "cross")
    return out


def head_dim(model: dict) -> int:
    return int(model["hidden_size"]) // int(model["num_attention_heads"])


def d_inner(model: dict) -> int:
    return int(model.get("mamba_expand", 2)) * int(model["hidden_size"])


def d_state(model: dict) -> int:
    return int(model.get("mamba_d_state", 16))


def d_conv(model: dict) -> int:
    return int(model.get("mamba_d_conv", 4))


def dt_rank(model: dict) -> int:
    return -(-int(model["hidden_size"]) // 16)


def mamba_params(model: dict) -> int:
    """in_proj to [x | z], the taps and their bias, x_proj to [r | B | C],
    dt_proj with its bias, A_log, D, out_proj."""
    d, c, n, r = int(model["hidden_size"]), d_inner(model), d_state(model), dt_rank(model)
    return (d * 2 * c + (d_conv(model) + 1) * c + c * (r + 2 * n) + r * c + c
            + c * n + c + c * d)


def cross_attention_params(model: dict) -> int:
    """Wq and out_proj with their biases, four lambda vectors, subln."""
    d, hd = int(model["hidden_size"]), head_dim(model)
    return 2 * (d * d + d) + 4 * hd + 2 * hd


def attention_params(model: dict) -> int:
    """Wqkv (queries, and keys and values of ``num_key_value_heads``) and
    out_proj with their biases, four lambda vectors, subln."""
    d = int(model["hidden_size"])
    kv = int(model["num_key_value_heads"]) * head_dim(model)
    return cross_attention_params(model) + 2 * (d * kv + kv)


def memory_unit_params(model: dict) -> int:
    return 2 * int(model["hidden_size"]) * d_inner(model)


def mlp_params(model: dict) -> int:
    return 3 * int(model["hidden_size"]) * int(model["intermediate_size"])


def params(model: dict) -> int:
    """Every parameter (the embedding once: the head is tied): per published
    layer its mixer, its SwiGLU and two LayerNorms (weight and bias); the
    final LayerNorm."""
    d = int(model["hidden_size"])
    per = {"mamba": mamba_params(model), "window": attention_params(model),
           "full": attention_params(model), "cross": cross_attention_params(model),
           "memory": memory_unit_params(model)}
    return (sum(per[kind] + mlp_params(model) + 4 * d for kind in mixers(model))
            + 2 * d + d * int(model["vocab_size"]))


def kv_bytes_per_token(model: dict, bytes_per_el: int = 2) -> int:
    """The ONE full layer's keys and values: the whole model's growing cache."""
    return 2 * int(model["num_key_value_heads"]) * head_dim(model) * bytes_per_el


def ring_bytes_per_slot(model: dict, bytes_per_el: int = 2) -> int:
    """The windowed layers' rings: ``sliding_window`` rows of keys and of values."""
    return (mixers(model).count("window") * int(model["sliding_window"])
            * kv_bytes_per_token(model, bytes_per_el))


def state_bytes_per_slot(model: dict, bytes_per_el: int = 2) -> int:
    """The Mamba layers' recurrent state (float32) and their convolutions'
    last ``d_conv - 1`` inputs (serving type)."""
    c = d_inner(model)
    return mixers(model).count("mamba") * (
        c * d_state(model) * 4 + (d_conv(model) - 1) * c * bytes_per_el)


def shared_readers(model: dict) -> int:
    """Layers that attend the one cache in a decode step: the full layer and
    every cross layer."""
    kinds = mixers(model)
    return kinds.count("full") + kinds.count("cross")


def shared_attn_bytes(live_tokens: float, slots: int, model: dict,
                      bytes_per_el: int = 2) -> float:
    """Bytes a decode step's attention over the ONE cache has to move: the
    live rows (``live_tokens``: the tokens in all the slots' caches), once a
    reading layer."""
    return shared_readers(model) * live_tokens * kv_bytes_per_token(model, bytes_per_el)


def shared_attn_flops(live_tokens: float, slots: int, model: dict) -> float:
    """Operations of the same as published: a query head's scores over its
    key's ``head_dim`` lanes and its weights over the pair's ``2 head_dim``
    value, a multiply and an add each."""
    hd = head_dim(model)
    return (shared_readers(model) * live_tokens * int(model["num_attention_heads"])
            * 2 * (hd + 2 * hd))


def decode_step_bytes(model: dict, slots: int, live_tokens: float,
                      bytes_per_el: int = 2) -> float:
    """Bytes one decode step has to move: every parameter once (the tied
    table once, as the head), the live rows of the one cache once a reading
    layer, every slot's rings read, its recurrent state and windows read and
    written."""
    return (params(model) * bytes_per_el
            + shared_attn_bytes(live_tokens, slots, model, bytes_per_el)
            + slots * ring_bytes_per_slot(model, bytes_per_el)
            + 2.0 * slots * state_bytes_per_slot(model, bytes_per_el))


def scan_bytes(tokens: int, model: dict) -> float:
    """Bytes the selective scans of ONE prefill of ``tokens`` positions have
    to move, all Mamba layers: ``x`` and ``dt`` read and ``y`` written
    (channels wide), ``B`` and ``C`` read (state wide), float32."""
    return (mixers(model).count("mamba") * tokens
            * (3 * d_inner(model) + 2 * d_state(model)) * 4.0)


def scan_flops(tokens: int, model: dict) -> float:
    """Operations of the same: a state element a token takes its decay's
    product (the exponential not counted), the input's product and sum, the
    output's product and sum."""
    return (mixers(model).count("mamba") * tokens * d_inner(model) * d_state(model)
            * 6.0)


def cross_decoder_from(model: dict) -> int:
    """Index, in the program's tree (``layers_<i>``: a published layer is a
    mixer layer and a feed-forward layer), of the first layer after the one
    full-attention layer: where a prefill that stops early runs one position."""
    return 2 * (mixers(model).index("full") + 1)
