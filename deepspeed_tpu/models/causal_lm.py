"""Unified causal-LM family with KV-cache inference support.

The TPU-native analogue of the reference's kernel-backed model implementations
(``model_implementations/transformers/ds_{gpt,bloom,opt,megatron_gpt}.py`` +
``module_inject/containers/``): instead of 12 per-architecture injection containers, ONE
configurable transformer covers the families — positional scheme (learned/rotary/alibi),
parallel residual, GQA, gated MLP, pre/post-LN — and per-family constructors pin the knobs.

Two execution paths:
- ``forward(params, ids)``: full-sequence logits (training/scoring, flash/xla attention);
- ``prefill(params, ids)`` / ``decode_step(params, cache, tok)``: KV-cache serving path.
  The cache is head-major rows (``ops/paged_attention.heads_per_row``) feeding ``ops/attention/decode.py``'s fused
  kernel (reference hot loop ``softmax_context``, ``csrc/transformer/inference``).
"""

import dataclasses
from functools import partial
from typing import Any, Callable, Dict, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from ..observability import scope
from ..ops.attention.decode import (decode_attention, decode_attention_live,
                                    pack_queries, unpack_outputs)
from ..ops.attention.latent import (latent_decode_attention, rotate_pairs,
                                    yarn_inv_freq, yarn_mscale)
from ..ops.paged_attention import heads_per_row, kv_rows, latent_row_lanes
from ..ops.transformer.attention import xla_attention
from ..parallel.overlap import (RowParallelDense, chunked_expert_exchange,
                                get_overlap_config, moe_overlap_chunks,
                                raw_or_param)
from .base import Model
from .mamba1 import project, serving_values
from ..utils.jax_compat import shard_map


@dataclasses.dataclass
class CausalLMConfig:
    vocab_size: int = 50257
    max_seq_len: int = 2048
    n_embd: int = 768
    n_layer: int = 12
    n_head: int = 12
    n_kv_head: Optional[int] = None          # GQA; None → MHA
    d_ff: Optional[int] = None               # None → 4*n_embd
    pos_emb: str = "learned"                 # learned | rotary | alibi | none
    rotary_pct: float = 1.0                  # NeoX partial rotary
    rotary_base: float = 10000.0
    parallel_residual: bool = False          # NeoX/GPT-J
    gated_mlp: bool = False                  # LLaMA SwiGLU
    activation: str = "gelu"                 # gelu | relu | silu
    layernorm: str = "layernorm"             # layernorm | rmsnorm
    ln_eps: float = 1e-5
    embed_layernorm: bool = False            # BLOOM
    tie_word_embeddings: bool = True
    qkv_bias: bool = True
    mlp_bias: bool = True
    lm_head_bias: bool = False               # GPT-J ties nothing and biases the head
    dtype: Any = jnp.bfloat16
    init_std: float = 0.02
    # the matrices that write to the residual stream (``o_proj``, ``fc_out``, a
    # mixer's output projection); None = GPT-2's ``init_std / sqrt(2 n_layer)``
    out_init_std: Optional[float] = None
    name: str = "causal-lm"
    # MoE serving (reference ``ops/transformer/inference/moe_inference.py``): every
    # ``moe_layer_interval``-th layer's FFN is a gated expert mixture. 0 experts = dense.
    num_experts: int = 0
    moe_layer_interval: int = 2
    moe_top_k: int = 1
    # decode (t==1) routes via selected-expert weight GATHER instead of the all-expert
    # dispatch einsum — e× less FFN HBM traffic per step (reference builds dedicated MoE
    # inference ops for this hot loop, ``moe_inference.py:463``). False = always dispatch
    # (debug / parity testing).
    moe_decode_fastpath: bool = True
    # "pallas" = gather-fused kernel (weights stream HBM→MXU once);
    # "xla" = w[idx] gather + einsum (lets XLA pin small expert stacks in VMEM)
    moe_decode_impl: str = "pallas"
    # One MIXER a layer, chosen by a pattern string with a letter a layer
    # (the keys of :data:`LAYER_KINDS`, which says what each is);
    # every layer is then ``x + mixer(norm(x))``. None = the classic layer
    # (attention, then feed-forward). The sizes below are read only by the
    # mixers the pattern names.
    layer_pattern: Optional[str] = None
    head_dim_override: Optional[int] = None  # attention head size where != n_embd / n_head
    qk_norm: bool = False                    # RMSNorm of q and k per head, before the rotation
    # Granite's four scalars, each at what every other family has: the
    # embedding's rows are scaled by ``embedding_multiplier``, a mixer layer is
    # ``x + residual_multiplier * mixer(norm(x))``, attention's scores are
    # scaled by ``attention_multiplier`` (None = ``1 / sqrt(head_dim)``; read
    # by every attention path through :attr:`attn_scale` and nowhere else),
    # and the logits are divided by ``logits_scaling``
    embedding_multiplier: float = 1.0
    residual_multiplier: float = 1.0
    attention_multiplier: Optional[float] = None
    logits_scaling: float = 1.0
    # LATENT attention ("L" layers; ``ops/attention/latent.py``): every head's
    # keys and values are expanded from ONE normed latent of ``kv_lora_rank``
    # a token; a query and a key have ``qk_nope_head_dim`` lanes without a
    # position and ``qk_rope_head_dim`` rotated ones (the key's rotary part is
    # one for all heads), a value ``v_head_dim``. There is no query latent.
    # ``rope_yarn``: the numbers of a ``deepseek_yarn`` scaling, ``(factor,
    # original_max_position_embeddings, beta_fast, beta_slow, mscale,
    # mscale_all_dim)``, or None: plain rotary frequencies at ``rotary_base``
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    rope_yarn: Optional[Tuple[float, ...]] = None
    # what an "E" layer's experts are: "latent" (experts in a latent space
    # beside a shared expert, behind the sigmoid router with a selection
    # bias: ``moe/latent_moe.py``) or "gated" (SwiGLU experts of the full
    # width behind the router ``moe_router`` names, beside a gated shared
    # expert of ``moe_shared_width`` where that is not 0:
    # ``moe/gated_moe.py``)
    moe_kind: str = "latent"
    # the GATED mixture's router: "softmax" (probabilities over all experts,
    # the top k renormalised: no selection bias, no scaling) or
    # "sigmoid_bias" (``latent_moe.route``: sigmoid scores, the choice by
    # score + a selection bias, weights from the scores alone, normalised
    # over ``sum + moe_topk_eps`` and scaled by ``routed_scaling_factor``)
    moe_router: str = "softmax"
    moe_topk_eps: float = 1e-20
    # DIFFERENTIAL attention (Ye et al., arXiv 2410.05258, as SambaY has it):
    # every attention kind of the model ("*", "W", "X") takes its heads in
    # adjacent PAIRS, two softmax maps over one pair of key heads and one
    # 2 x head_dim value, subtracted under a learned lambda and normed
    # (:meth:`MixerLayer._diff_attention`); its biases are ``qkv_bias``'s.
    # ``sliding_window``: the keys a "W" layer's query sees, itself and the
    # ``sliding_window - 1`` before it
    diff_attention: bool = False
    sliding_window: int = 0
    # Mamba-1 ("S" layers; ``models/mamba1.py``): ``mamba1_d_inner`` channels,
    # ``ssm_state_size`` state lanes a channel, ``mamba1_dt_rank``,
    # ``conv_kernel`` taps. A gated memory unit ("G") is as wide
    mamba1_d_inner: int = 0
    mamba1_dt_rank: int = 0
    mamba_num_heads: int = 0
    mamba_head_dim: int = 0
    ssm_state_size: int = 0
    ssm_n_groups: int = 1
    conv_kernel: int = 4                     # "M" and "C": taps of the causal convolution
    ssm_chunk_size: int = 128
    n_routed_experts: int = 0                # the router's width
    experts_per_token: int = 0
    moe_expert_width: int = 0
    moe_shared_width: int = 0
    moe_latent_size: int = 0
    routed_scaling_factor: float = 1.0
    norm_topk_prob: bool = True
    # (first, count): the routed experts THIS program holds (expert
    # parallelism's share); None = all of them
    experts_held: Optional[Tuple[int, int]] = None
    # RANDOM weights only (an engine that is given none): after the seeded
    # init, level each expert layer's load on random tokens by its selection
    # bias, as training leaves it (``moe/latent_moe.py: level_expert_load``)
    level_random_experts: bool = False
    # RANDOM weights only: give every token id home experts that its routers
    # find behind a margin no rounding crosses (``moe/latent_moe.py:
    # home_random_routers``); False = routers as seeded
    home_random_routers: bool = False
    # Rows a GREEDY ``InferenceEngine.generate`` decodes at, at least (the
    # rows it adds hold nothing and are cut off). XLA rounds a row of a matmul
    # differently by how many rows the matmul has, so a deployment that wants
    # from ``generate`` the very tokens its serving chunk gives sets its slot
    # count here. None = the batch's own rows
    greedy_decode_rows: Optional[int] = None
    # GENERATION BY DIFFUSION OVER BLOCKS (0 = one token a step, left to
    # right). The model generates ``gen_block_length`` positions at a time:
    # a block starts as ``mask_token_id``, is run ``gen_denoising_steps``
    # times with full attention inside the block and attention to the blocks
    # before it, positions are unmasked between the runs in the order
    # ``gen_remasking`` says, and a last run on the finished block writes its
    # keys and values. The attention mask is then block-causal everywhere: key
    # ``j`` is seen by query ``i`` iff ``j // block <= i // block``. It is the
    # model that generates this way, so the serving scheduler and
    # ``InferenceEngine.generate`` read these fields and have no switch
    gen_block_length: int = 0
    gen_denoising_steps: int = 0
    gen_remasking: str = "sequential"
    gen_confidence_threshold: float = 0.9
    mask_token_id: int = 0

    VALID_MOE_DECODE_IMPLS = ("pallas", "xla")
    MOE_KINDS = ("latent", "gated")
    MOE_ROUTERS = ("softmax", "sigmoid_bias")
    REMASKING = ("sequential", "low_confidence_static", "low_confidence_dynamic")

    def __post_init__(self):
        if self.moe_kind not in self.MOE_KINDS:
            raise ValueError(f"moe_kind={self.moe_kind!r} is not one of "
                             f"{self.MOE_KINDS}")
        if self.moe_router not in self.MOE_ROUTERS:
            raise ValueError(f"moe_router={self.moe_router!r} is not one of "
                             f"{self.MOE_ROUTERS}")
        if self.gen_block_length:
            B, n = self.gen_block_length, self.gen_denoising_steps
            if B < 2 or n < 1 or B % n:
                raise ValueError(
                    f"gen_block_length={B} with gen_denoising_steps={n}: a block "
                    "of at least 2 positions, unmasked in equal parts over the "
                    "steps (the steps must divide the block)")
            if self.gen_remasking not in self.REMASKING:
                raise ValueError(f"gen_remasking={self.gen_remasking!r} is not "
                                 f"one of {self.REMASKING}")
            if not 0 <= self.mask_token_id < self.vocab_size:
                raise ValueError(f"mask_token_id={self.mask_token_id} lies "
                                 f"outside the vocabulary ({self.vocab_size})")
        # case-sensitive on purpose: 'XLA'/'Pallas'/'triton' must not silently
        # select the pallas path through a failed == "xla" comparison
        if self.moe_decode_impl not in self.VALID_MOE_DECODE_IMPLS:
            raise ValueError(
                f"moe_decode_impl={self.moe_decode_impl!r} is not one of "
                f"{self.VALID_MOE_DECODE_IMPLS}")
        if self.residual_multiplier != 1 and self.layer_pattern is None:
            raise ValueError(
                f"residual_multiplier={self.residual_multiplier} scales a mixer "
                "layer's branch: the classic layer (layer_pattern None) has none")
        if self.layer_pattern is not None:
            bad = sorted(set(self.layer_pattern) - set(PATTERN_KINDS))
            if bad or len(self.layer_pattern) != self.n_layer:
                raise ValueError(
                    f"layer_pattern={self.layer_pattern!r} must be {self.n_layer} "
                    f"letters of {PATTERN_KINDS}")
            self._check_streams()
            if self.experts_held is not None:
                first, count = (int(v) for v in self.experts_held)
                if not (0 <= first and count >= 1
                        and first + count <= self.n_routed_experts):
                    raise ValueError(
                        f"experts_held={self.experts_held} is no share of "
                        f"{self.n_routed_experts} experts")
                self.experts_held = (first, count)

    def _check_streams(self):
        """A layer that READS an earlier layer (:attr:`LayerKind.reads`) has one
        that writes what it reads before it; the paired kinds have pairs."""
        pattern = self.layer_pattern
        for i, k in enumerate(pattern):
            reads = LAYER_KINDS[k].reads
            if reads and not any(LAYER_KINDS[j].writes == reads for j in pattern[:i]):
                raise ValueError(
                    f"layer_pattern={pattern!r}: layer {i} is a {LAYER_KINDS[k].name} "
                    f"layer, which reads the {reads} of an earlier layer, and no "
                    "layer before it hands one on")
        paired = set(pattern) & {"W", "X"}
        if paired and not self.diff_attention:
            raise ValueError(f"layers {sorted(paired)} are differential attention's: "
                             "diff_attention must be set")
        if self.diff_attention:
            pairs, rows = self.n_head // 2, self.kv_heads // 2
            if self.n_head % 2 or self.kv_heads % 2 or pairs % rows:
                raise ValueError(
                    f"differential attention pairs adjacent heads: {self.n_head} "
                    f"query and {self.kv_heads} key heads are not whole pairs, "
                    "the query pairs a multiple of the key pairs")
        if "W" in pattern and self.sliding_window < 1:
            raise ValueError("a windowed layer needs sliding_window >= 1")

    def layer_kind(self, i: int) -> str:
        """``"A"`` for the classic layer (attention + feed-forward), else the
        pattern's letter, a key of :data:`LAYER_KINDS`: what the layer keeps
        between a sequence's tokens, what it holds and which mixer it runs
        are that entry's."""
        return "A" if self.layer_pattern is None else self.layer_pattern[i]

    @property
    def layer_kinds(self) -> str:
        return "".join(self.layer_kind(i) for i in range(self.n_layer))

    @property
    def layer_keeps(self) -> Tuple[str, ...]:
        """What each layer keeps between a sequence's tokens, a layer an
        entry (:attr:`LayerKind.keeps`): the one question the serve programs
        and the pool's movers ask of a layer's cache."""
        return tuple(LAYER_KINDS[k].keeps for k in self.layer_kinds)

    @property
    def kv_every_layer(self) -> bool:
        """Every layer keeps keys and values (what the prefix and slab
        movers, a suffix prefill and a speculative verify read)."""
        return all(LAYER_KINDS[k].keeps == "kv" for k in self.layer_kinds)

    @property
    def slot_state_layers(self) -> Tuple[str, ...]:
        """The names of the kinds of this model's layers that keep a
        PER-SLOT state, one array a slot that every token overwrites (empty:
        none does)."""
        return tuple(dict.fromkeys(
            LAYER_KINDS[k].name for k in self.layer_kinds
            if LAYER_KINDS[k].keeps == "state"))

    @property
    def cache_row_heads(self) -> int:
        """KV heads a cache row holds side by side (``init_cache`` and the
        pool ask): a differential pair where attention is differential (a
        row IS ``[k1 ; k2]``), else ``ops/paged_attention.heads_per_row``."""
        return 2 if self.diff_attention else heads_per_row(self.head_dim,
                                                           self.kv_heads)

    def mixer_depth(self, i: int) -> int:
        """Layer ``i``'s index among the layers that are no feed-forward
        ("F", "E"): the published layer index where every mixer is followed
        by one (differential attention's ``lambda_init`` reads it)."""
        return sum(1 for k in self.layer_kinds[:i] if k not in "FE")

    @property
    def prefill_stop(self) -> Optional[int]:
        """The layer from which a prefill needs ONE position a sequence: the
        last layer that keeps anything, where that is a full differential
        attention layer whose cache the layers after it re-read (they read
        other positions through that cache alone). None: every layer runs at
        every position."""
        kinds = self.layer_kinds
        last = max((i for i, keep in enumerate(self.layer_keeps)
                    if keep != "nothing"), default=None)
        if (last is None or kinds[last] != "*" or not self.diff_attention
                or not any(LAYER_KINDS[k].reads == "kv" for k in kinds[last + 1:])):
            return None
        return last

    @property
    def latent_layers(self) -> bool:
        """Some layer keeps latent rows (one row a token for all heads)."""
        return "latent" in self.layer_keeps

    @property
    def latent_row_width(self) -> int:
        """A latent row as published: the latent and the shared rotary key."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    def latent_rope(self) -> Tuple[np.ndarray, float]:
        """The ``qk_rope_head_dim / 2`` rotary frequencies of a latent layer
        and what its cos and sin are multiplied by (``m(factor, mscale) /
        m(factor, mscale_all_dim)`` under ``deepseek_yarn``, else 1)."""
        dim = self.qk_rope_head_dim
        if self.rope_yarn is None:
            return (1.0 / self.rotary_base
                    ** (np.arange(0, dim, 2, dtype=np.float32) / dim)), 1.0
        factor, original, fast, slow, mscale, mscale_all = self.rope_yarn
        return (yarn_inv_freq(dim, self.rotary_base, factor, int(original), fast,
                              slow),
                yarn_mscale(factor, mscale) / yarn_mscale(factor, mscale_all))

    @property
    def held_experts(self) -> Tuple[int, int]:
        return self.experts_held or (0, self.n_routed_experts)

    @property
    def conv_dim(self) -> int:
        return (self.mamba_num_heads * self.mamba_head_dim
                + 2 * self.ssm_n_groups * self.ssm_state_size)

    def is_moe_layer(self, i: int) -> bool:
        return self.num_experts > 0 and (i + 1) % self.moe_layer_interval == 0

    @property
    def head_dim(self) -> int:
        return self.head_dim_override or self.n_embd // self.n_head

    @property
    def kv_heads(self) -> int:
        return self.n_kv_head or self.n_head

    @property
    def attn_scale(self) -> float:
        """What the scores ``q . k`` are multiplied by before the softmax: the
        ONE place every attention path of the model takes it from."""
        if self.attention_multiplier is not None:
            return float(self.attention_multiplier)
        if self.kv_lora_rank:
            # a latent layer's queries and keys are nope + rope lanes wide, and
            # ``deepseek_yarn`` multiplies the scale by m(factor, mscale_all_dim)^2
            scale = (self.qk_nope_head_dim + self.qk_rope_head_dim) ** -0.5
            if self.rope_yarn is not None and self.rope_yarn[5]:
                scale *= yarn_mscale(self.rope_yarn[0], self.rope_yarn[5]) ** 2
            return float(scale)
        return 1.0 / float(np.sqrt(self.head_dim))

    @property
    def out_std(self) -> float:
        if self.out_init_std is not None:
            return float(self.out_init_std)
        return self.init_std / (2 * self.n_layer) ** 0.5

    @property
    def ffn_dim(self) -> int:
        return self.d_ff or 4 * self.n_embd

    def num_params(self) -> int:
        d, L, v = self.n_embd, self.n_layer, self.vocab_size
        if self.layer_pattern is not None:
            # a mixer layer: its mixer (the table's count) and its norm
            norm = d if self.layernorm == "rmsnorm" else 2 * d
            return (v * d + sum(LAYER_KINDS[k].params(self) + norm
                                for k in self.layer_pattern) + norm
                    + (0 if self.tie_word_embeddings else v * d))
        f = self.ffn_dim
        mlp = d * f * (3 if self.gated_mlp else 2)
        attn = d * d + 2 * d * self.kv_heads * self.head_dim + d * d
        n_moe = sum(1 for i in range(L) if self.is_moe_layer(i))
        moe_extra = n_moe * (self.num_experts - 1) * 2 * d * f  # experts replace the FFN
        return (v * d + L * (attn + mlp) + moe_extra +
                (0 if self.tie_word_embeddings else v * d))


# ------------------------------------------------------------------- layer kinds
@dataclasses.dataclass(frozen=True)
class LayerKind:
    """A row of :data:`LAYER_KINDS`: what a layer of that kind keeps between
    a sequence's tokens (``keeps``: ``"kv"`` keys and values, rows that grow
    with the sequence and live in pages; ``"latent"`` ONE row a token for all
    heads, in pages likewise, ``{"k"}`` alone
    (``ops/paged_attention.latent_row_lanes``); ``"state"`` one array a slot that
    every token overwrites, made by ``state(cfg, rows, dtype)``;
    ``"nothing"``, an empty cache), the parameters its mixer holds (``params(cfg)``, its norm
    not counted) and the method of :class:`MixerLayer` that runs it. ``ring``: the
    state is a ring of the last ``sliding_window`` rows of keys and values.
    ``writes`` / ``reads``: what the layer hands on to later layers of the same
    forward, and what it reads of an earlier one (``"kv"`` the cache of a layer
    that keeps keys and values, ``"memory"`` a state-space layer's scan output):
    the streams :class:`CausalLM` carries beside ``x``."""
    name: str
    keeps: str
    params: Optional[Callable] = None
    state: Optional[Callable] = None
    mixer: Optional[str] = None
    ring: bool = False
    writes: Optional[str] = None
    reads: Optional[str] = None


def _attention_params(cfg: CausalLMConfig) -> int:
    d, q = cfg.n_embd, cfg.n_head * cfg.head_dim
    if cfg.diff_attention:
        return _cross_attention_params(cfg) + 2 * _kv_proj_params(cfg)
    return (d * q + 2 * d * cfg.kv_heads * cfg.head_dim + q * d
            + (2 * cfg.head_dim if cfg.qk_norm else 0))


def _kv_proj_params(cfg: CausalLMConfig) -> int:
    width = cfg.kv_heads * cfg.head_dim
    return cfg.n_embd * width + (width if cfg.qkv_bias else 0)


def _cross_attention_params(cfg: CausalLMConfig) -> int:
    """Differential attention without keys and values of its own: the query
    and output projections, four lambda vectors, the norm over a pair."""
    d, q = cfg.n_embd, cfg.n_head * cfg.head_dim
    return (d * q + q * d + (q + d if cfg.qkv_bias else 0) + 4 * cfg.head_dim
            + 2 * cfg.head_dim)


def _mamba1_params(cfg: CausalLMConfig) -> int:
    d, c, n, rank = (cfg.n_embd, cfg.mamba1_d_inner, cfg.ssm_state_size,
                     cfg.mamba1_dt_rank)
    return (d * 2 * c + (cfg.conv_kernel + 1) * c + c * (rank + 2 * n)
            + rank * c + c + n * c + c + c * d)


def _gated_memory_params(cfg: CausalLMConfig) -> int:
    return 2 * cfg.n_embd * cfg.mamba1_d_inner


def _latent_attention_params(cfg: CausalLMConfig) -> int:
    d, h, rank = cfg.n_embd, cfg.n_head, cfg.kv_lora_rank
    nope, rope, v = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    return (d * h * (nope + rope) + d * (rank + rope) + rank
            + rank * h * (nope + v) + h * v * d)


def _mamba_params(cfg: CausalLMConfig) -> int:
    d, inner = cfg.n_embd, cfg.mamba_num_heads * cfg.mamba_head_dim
    return (d * (inner + cfg.conv_dim + cfg.mamba_num_heads)
            + (cfg.conv_kernel + 1) * cfg.conv_dim
            + 3 * cfg.mamba_num_heads + inner + inner * d)


def _short_conv_params(cfg: CausalLMConfig) -> int:
    d = cfg.n_embd
    return d * 3 * d + cfg.conv_kernel * d + d * d


def _experts_params(cfg: CausalLMConfig) -> int:
    d, n, held = cfg.n_embd, cfg.n_routed_experts, cfg.held_experts[1]
    if cfg.moe_kind == "gated":
        return (d * n + (n if cfg.moe_router == "sigmoid_bias" else 0)
                + held * 3 * d * cfg.moe_expert_width
                + 3 * d * cfg.moe_shared_width)
    return (d * n + n + 2 * d * cfg.moe_latent_size + 2 * d * cfg.moe_shared_width
            + held * 2 * cfg.moe_latent_size * cfg.moe_expert_width)


def _ffn_params(cfg: CausalLMConfig) -> int:
    d, f, mats = cfg.n_embd, cfg.ffn_dim, 3 if cfg.gated_mlp else 2
    return d * f * mats + ((mats - 1) * f + d if cfg.mlp_bias else 0)


def _mamba_state(cfg: CausalLMConfig, batch_size: int, dtype) -> Dict:
    """A state-space layer's state for ``batch_size`` sequences: the last
    ``conv_kernel - 1`` inputs of the convolution (serving type) and the
    recurrent state (float32)."""
    return {"conv": jnp.zeros((batch_size, cfg.conv_kernel - 1, cfg.conv_dim), dtype),
            "ssm": jnp.zeros((batch_size, cfg.mamba_num_heads, cfg.mamba_head_dim,
                              cfg.ssm_state_size), jnp.float32)}


def _mamba1_state(cfg: CausalLMConfig, batch_size: int, dtype) -> Dict:
    """A Mamba-1 layer's state: the convolution's last ``conv_kernel - 1``
    inputs (serving type) and the recurrent state, the channels on the lanes
    (float32)."""
    c = cfg.mamba1_d_inner
    return {"conv": jnp.zeros((batch_size, cfg.conv_kernel - 1, c), dtype),
            "ssm": jnp.zeros((batch_size, cfg.ssm_state_size, c), jnp.float32)}


def _window_state(cfg: CausalLMConfig, batch_size: int, dtype) -> Dict:
    """A windowed layer's ring: the last ``sliding_window`` tokens' keys and
    values in rows of a head pair, token ``t`` at row ``t mod sliding_window``
    (the model has no positions, so the order of the rows is free)."""
    shape = (batch_size, cfg.kv_heads // 2, cfg.sliding_window, 2 * cfg.head_dim)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def _short_conv_state(cfg: CausalLMConfig, batch_size: int, dtype) -> Dict:
    """A gated short convolution's state: the last ``conv_kernel - 1``
    products ``B * u`` (serving type), and nothing else."""
    return {"conv": jnp.zeros((batch_size, cfg.conv_kernel - 1, cfg.n_embd), dtype)}


#: the ONE place that says what each kind of layer is: ``init_cache``,
#: ``CausalLMConfig.num_params``, :class:`MixerLayer`, the serving executor
#: (``kv_every_layer``) and the scheduler's refusals (``slot_state_layers``)
#: read it, so a new kind is one entry and one method. "A" is the classic
#: layer (attention and feed-forward in one module) and stands in no pattern.
LAYER_KINDS: Dict[str, LayerKind] = {
    "A": LayerKind("classic", "kv"),
    "*": LayerKind("attention", "kv", _attention_params, mixer="_self_attention",
                   writes="kv"),
    "L": LayerKind("latent-attention", "latent", _latent_attention_params,
                   mixer="_latent"),
    "M": LayerKind("state-space", "state", _mamba_params, _mamba_state, "_mamba"),
    "S": LayerKind("selective-state-space", "state", _mamba1_params,
                   _mamba1_state, "_mamba1", writes="memory"),
    "C": LayerKind("short-convolution", "state", _short_conv_params,
                   _short_conv_state, "_short_conv"),
    "W": LayerKind("window-attention", "state", _attention_params, _window_state,
                   "_diff_attention", ring=True),
    "X": LayerKind("cross-attention", "nothing", _cross_attention_params,
                   mixer="_diff_attention", reads="kv"),
    "G": LayerKind("gated-memory", "nothing", _gated_memory_params,
                   mixer="_gated_memory", reads="memory"),
    "E": LayerKind("expert", "nothing", _experts_params, mixer="_experts"),
    "F": LayerKind("feed-forward", "nothing", _ffn_params, mixer="_ffn"),
}
PATTERN_KINDS = tuple(k for k in LAYER_KINDS if k != "A")
#: the ``keeps`` whose rows live in the pool's pages, behind a slot's page table
PAGED = ("kv", "latent")


# ---------------------------------------------------------------- family constructors
def gpt2_cfg(**kw) -> CausalLMConfig:
    return CausalLMConfig(pos_emb="learned", activation="gelu", name="gpt2", **kw)


def bloom_cfg(**kw) -> CausalLMConfig:
    """BLOOM (reference container ``module_inject/containers/bloom.py``): alibi positions,
    embedding layernorm, tied head."""
    kw.setdefault("pos_emb", "alibi")
    kw.setdefault("embed_layernorm", True)
    kw.setdefault("name", "bloom")
    return CausalLMConfig(**kw)


def opt_cfg(**kw) -> CausalLMConfig:
    kw.setdefault("pos_emb", "learned")
    kw.setdefault("activation", "relu")
    kw.setdefault("name", "opt")
    return CausalLMConfig(**kw)


def gptneox_cfg(**kw) -> CausalLMConfig:
    """GPT-NeoX (container ``gptneox.py``): rotary (partial), parallel residual."""
    kw.setdefault("pos_emb", "rotary")
    kw.setdefault("rotary_pct", 0.25)
    kw.setdefault("parallel_residual", True)
    kw.setdefault("tie_word_embeddings", False)
    kw.setdefault("name", "gpt-neox")
    return CausalLMConfig(**kw)


def gptj_cfg(**kw) -> CausalLMConfig:
    kw.setdefault("pos_emb", "rotary")
    kw.setdefault("rotary_pct", 0.25)
    kw.setdefault("parallel_residual", True)
    kw.setdefault("name", "gptj")
    return CausalLMConfig(**kw)


def llama_cfg(**kw) -> CausalLMConfig:
    kw.setdefault("pos_emb", "rotary")
    kw.setdefault("gated_mlp", True)
    kw.setdefault("activation", "silu")
    kw.setdefault("layernorm", "rmsnorm")
    kw.setdefault("qkv_bias", False)
    kw.setdefault("mlp_bias", False)
    kw.setdefault("tie_word_embeddings", False)
    kw.setdefault("ln_eps", 1e-6)
    kw.setdefault("name", "llama")
    return CausalLMConfig(**kw)


def nemotron_h_cfg(*, hidden_size, hybrid_override_pattern, vocab_size,
                   num_attention_heads, num_key_value_heads, head_dim,
                   mamba_num_heads, mamba_head_dim, ssm_state_size, n_groups,
                   conv_kernel, chunk_size, n_routed_experts, num_experts_per_tok,
                   moe_intermediate_size, moe_shared_expert_intermediate_size,
                   moe_latent_size, routed_scaling_factor, norm_topk_prob=True,
                   layer_norm_epsilon=1e-5, experts_held=None, **kw) -> CausalLMConfig:
    """Nemotron-H hybrids (``model_type: nemotron_h``): the keywords are the
    published config's. One mixer a layer from ``hybrid_override_pattern``
    ("M" Mamba-2, "*" attention with grouped keys and values, "E" latent
    mixture of experts with a shared expert, squared ReLU), RMSNorm, no bias
    but the convolution's, untied head. Set here and not published: no
    position encoding in attention (the family uses none; ``rope_theta`` is
    not read), the recurrent state in float32."""
    kw.setdefault("name", "nemotron-h")
    return CausalLMConfig(
        n_embd=hidden_size, n_layer=len(hybrid_override_pattern),
        layer_pattern=hybrid_override_pattern, vocab_size=vocab_size,
        n_head=num_attention_heads, n_kv_head=num_key_value_heads,
        head_dim_override=head_dim, pos_emb="none", layernorm="rmsnorm",
        ln_eps=layer_norm_epsilon, qkv_bias=False, mlp_bias=False,
        tie_word_embeddings=False, mamba_num_heads=mamba_num_heads,
        mamba_head_dim=mamba_head_dim, ssm_state_size=ssm_state_size,
        ssm_n_groups=n_groups, conv_kernel=conv_kernel, ssm_chunk_size=chunk_size,
        n_routed_experts=n_routed_experts, experts_per_token=num_experts_per_tok,
        moe_expert_width=moe_intermediate_size,
        moe_shared_width=moe_shared_expert_intermediate_size,
        moe_latent_size=moe_latent_size,
        routed_scaling_factor=float(routed_scaling_factor),
        norm_topk_prob=bool(norm_topk_prob),
        experts_held=None if experts_held is None else tuple(experts_held), **kw)


def sdar_moe_cfg(*, hidden_size, num_hidden_layers, vocab_size,
                 num_attention_heads, num_key_value_heads, head_dim, num_experts,
                 num_experts_per_tok, moe_intermediate_size, norm_topk_prob=True,
                 rms_norm_eps=1e-6, rope_theta=1e6, gen_block_length=4,
                 gen_denoising_steps=4, gen_remasking="sequential",
                 gen_confidence_threshold=0.9, mask_token_id=151669,
                 experts_held=None, **kw) -> CausalLMConfig:
    """SDAR mixtures of experts (``model_type: sdar_moe``): the keywords are the
    published config's, and ``gen_*`` / ``mask_token_id`` what the family's
    ``generate.py`` takes (the config gives none). A published layer is
    attention and then a mixture of experts, each ``x + f(rmsnorm(x))``: here
    the pair of mixer layers "*E", so ``n_layer`` is twice
    ``num_hidden_layers``. Attention: grouped keys and values, RMSNorm of q
    and k per head before a rotation over the whole head, no bias; experts:
    softmax router, top ``num_experts_per_tok`` renormalised, SwiGLU of width
    ``moe_intermediate_size``, no shared expert; RMSNorm, untied head. The
    mask is block-causal and generation is by diffusion over blocks
    (``CausalLMConfig.gen_block_length``)."""
    kw.setdefault("name", "sdar-moe")
    return CausalLMConfig(
        n_embd=hidden_size, n_layer=2 * int(num_hidden_layers),
        layer_pattern="*E" * int(num_hidden_layers), vocab_size=vocab_size,
        n_head=num_attention_heads, n_kv_head=num_key_value_heads,
        head_dim_override=head_dim, qk_norm=True, pos_emb="rotary",
        rotary_base=float(rope_theta), layernorm="rmsnorm", ln_eps=rms_norm_eps,
        qkv_bias=False, mlp_bias=False, tie_word_embeddings=False,
        moe_kind="gated", n_routed_experts=num_experts,
        experts_per_token=num_experts_per_tok,
        moe_expert_width=moe_intermediate_size,
        norm_topk_prob=bool(norm_topk_prob),
        experts_held=None if experts_held is None else tuple(experts_held),
        gen_block_length=int(gen_block_length),
        gen_denoising_steps=int(gen_denoising_steps), gen_remasking=gen_remasking,
        gen_confidence_threshold=float(gen_confidence_threshold),
        mask_token_id=int(mask_token_id), **kw)


def lfm2_moe_cfg(*, hidden_size, num_hidden_layers, layer_types, num_dense_layers,
                 vocab_size, num_attention_heads, num_key_value_heads,
                 intermediate_size, moe_intermediate_size, num_experts,
                 num_experts_per_tok, conv_L_cache=3, conv_bias=False,
                 use_expert_bias=True, norm_topk_prob=True,
                 routed_scaling_factor=1.0, norm_eps=1e-5, rope_theta=1e6,
                 experts_held=None, **kw) -> CausalLMConfig:
    """LFM2 mixtures of experts (``model_type: lfm2_moe``): the keywords are
    the published config's. A published layer is an operator and then a
    feed-forward, each ``x + f(rmsnorm(x))``: here a pair of mixer layers, "C"
    (gated short convolution of ``conv_L_cache`` taps, no bias, no
    activation) or "*" (grouped keys and values, RMSNorm of q and k per head
    before a rotation over the whole head, no bias) as ``layer_types`` says,
    then "F" (SwiGLU of width ``intermediate_size``) for the first
    ``num_dense_layers`` layers and "E" after them (sigmoid router, the top
    ``num_experts_per_tok`` of score + expert bias, weights from the scores
    over their sum + 1e-6, SwiGLU experts of width ``moe_intermediate_size``,
    no shared expert), so ``n_layer`` is twice ``num_hidden_layers``. The
    FIRST ``num_hidden_layers`` entries of ``layer_types`` are built (a
    pipeline's first stage where it is cut). RMSNorm, tied head. Set here and
    not published: the sigmoid score, the tied head, the 1e-6, the q/k norm's
    place (``benchmarks/chipbench/configs/lfm2-8b-a1b.json: assumed``)."""
    if conv_bias or not use_expert_bias:
        raise NotImplementedError(
            "lfm2_moe is built without a convolution bias and with the expert "
            f"bias (got conv_bias={conv_bias}, use_expert_bias={use_expert_bias})")
    kinds = {"conv": "C", "full_attention": "*"}
    n = int(num_hidden_layers)
    pattern = "".join(kinds[t] + ("F" if i < int(num_dense_layers) else "E")
                      for i, t in enumerate(layer_types[:n]))
    if len(pattern) != 2 * n:
        raise ValueError(f"layer_types names {len(layer_types)} layers, "
                         f"num_hidden_layers={n}")
    kw.setdefault("name", "lfm2-moe")
    return CausalLMConfig(
        n_embd=hidden_size, n_layer=2 * n, layer_pattern=pattern,
        vocab_size=vocab_size, n_head=num_attention_heads,
        n_kv_head=num_key_value_heads, qk_norm=True, pos_emb="rotary",
        rotary_base=float(rope_theta), layernorm="rmsnorm", ln_eps=norm_eps,
        qkv_bias=False, mlp_bias=False, gated_mlp=True, activation="silu",
        d_ff=intermediate_size, tie_word_embeddings=True,
        conv_kernel=int(conv_L_cache), moe_kind="gated",
        moe_router="sigmoid_bias", moe_topk_eps=1e-6,
        n_routed_experts=num_experts, experts_per_token=num_experts_per_tok,
        moe_expert_width=moe_intermediate_size,
        routed_scaling_factor=float(routed_scaling_factor),
        norm_topk_prob=bool(norm_topk_prob),
        experts_held=None if experts_held is None else tuple(experts_held), **kw)


def granite_hybrid_cfg(*, hidden_size, num_hidden_layers, layer_types, vocab_size,
                       num_attention_heads, num_key_value_heads,
                       shared_intermediate_size, mamba_n_heads, mamba_d_head,
                       mamba_d_state, mamba_n_groups, mamba_d_conv, mamba_chunk_size,
                       embedding_multiplier, residual_multiplier,
                       attention_multiplier, logits_scaling, mamba_expand=2,
                       mamba_conv_bias=True, mamba_proj_bias=False,
                       attention_bias=False, num_local_experts=0,
                       position_embedding_type="nope", hidden_act="silu",
                       normalization_function="rmsnorm", rms_norm_eps=1e-5,
                       tie_word_embeddings=True, intermediate_size=None,
                       num_experts_per_tok=0, rope_theta=None, rope_scaling=None,
                       max_position_embeddings=None,
                       model_type="granitemoehybrid", experts_held=None,
                       **kw) -> CausalLMConfig:
    """Granite 4.0 hybrids (``model_type: granitemoehybrid``), with experts
    and without: the keywords are the published config's. A published layer
    is a mixer and then a feed-forward, each ``x + residual_multiplier *
    f(rmsnorm(x))``: here a pair of mixer layers, "M" (Mamba-2: ``mamba_n_heads``
    heads of ``mamba_d_head``, ``mamba_n_groups`` groups of B and C, a
    convolution of ``mamba_d_conv`` taps with its bias) or "*" (grouped keys
    and values, no position encoding, no bias, scores scaled by
    ``attention_multiplier``) as ``layer_types`` says, and then, with
    ``num_local_experts`` 0, "F" (a SwiGLU of width
    ``shared_intermediate_size``), else "E": a softmax router over
    ``num_local_experts`` SwiGLU experts of width ``intermediate_size``, the
    top ``num_experts_per_tok`` renormalised, BESIDE a shared SwiGLU of width
    ``shared_intermediate_size`` under the same norm, their sum under one
    ``residual_multiplier`` (``moe/gated_moe.py``; ``experts_held = (first,
    count)`` is the share of the experts this program holds, None = all). So
    ``n_layer`` is twice ``num_hidden_layers``, and ``layer_types`` names
    exactly that many. The embedding's rows are
    scaled by ``embedding_multiplier`` and the tied head's logits divided by
    ``logits_scaling``. Set here and not published: the recurrent state in
    float32, ``dt`` not clamped. Taken and not read, so that the whole
    published config can be passed: without experts ``intermediate_size`` and
    ``num_experts_per_tok`` (the absent experts'), ``rope_theta`` and
    ``rope_scaling`` (no positions), ``max_position_embeddings`` (the caller's
    ``max_seq_len`` says what is served), ``model_type``. What it does not
    build it refuses."""
    n_experts, top_k = int(num_local_experts), int(num_experts_per_tok or 0)
    if n_experts and (intermediate_size is None or not 1 <= top_k <= n_experts):
        raise NotImplementedError(
            f"granitemoehybrid with num_local_experts={n_experts} needs the "
            "experts' width (intermediate_size) and 1 <= num_experts_per_tok <= "
            f"num_local_experts (got intermediate_size={intermediate_size}, "
            f"num_experts_per_tok={num_experts_per_tok})")
    refused = {
        "position_embedding_type": (position_embedding_type, "nope"),
        "mamba_proj_bias": (mamba_proj_bias, False),
        "attention_bias": (attention_bias, False),
        "mamba_conv_bias": (mamba_conv_bias, True),
        "hidden_act": (hidden_act, "silu"),
        "normalization_function": (normalization_function, "rmsnorm"),
        "mamba_expand * hidden_size": (mamba_expand * hidden_size,
                                       mamba_n_heads * mamba_d_head),
    }
    bad = {k: got for k, (got, built) in refused.items() if got != built}
    if bad:
        raise NotImplementedError(
            "granitemoehybrid is built without positions or projection "
            "biases, with the convolution's bias, SiLU, RMSNorm and an inner "
            f"width of heads x head size (got {bad})")
    kinds = {"mamba": "M", "attention": "*"}
    n = int(num_hidden_layers)
    if len(layer_types) != n or set(layer_types) - set(kinds):
        raise ValueError(f"layer_types names {len(layer_types)} layers of "
                         f"{sorted(set(layer_types))}, num_hidden_layers={n} of "
                         f"{sorted(kinds)}")
    kw.setdefault("name", "granite-hybrid")
    if n_experts:
        kw.update(moe_kind="gated", moe_router="softmax", norm_topk_prob=True,
                  n_routed_experts=n_experts, experts_per_token=top_k,
                  moe_expert_width=int(intermediate_size),
                  moe_shared_width=int(shared_intermediate_size))
    return CausalLMConfig(
        n_embd=hidden_size, n_layer=2 * n,
        layer_pattern="".join(kinds[t] + ("E" if n_experts else "F")
                              for t in layer_types),
        vocab_size=vocab_size, n_head=num_attention_heads,
        n_kv_head=num_key_value_heads, pos_emb="none", layernorm="rmsnorm",
        ln_eps=rms_norm_eps, qkv_bias=False, mlp_bias=False, gated_mlp=True,
        activation="silu", d_ff=shared_intermediate_size,
        tie_word_embeddings=bool(tie_word_embeddings),
        mamba_num_heads=mamba_n_heads, mamba_head_dim=mamba_d_head,
        ssm_state_size=mamba_d_state, ssm_n_groups=mamba_n_groups,
        conv_kernel=int(mamba_d_conv), ssm_chunk_size=int(mamba_chunk_size),
        embedding_multiplier=float(embedding_multiplier),
        residual_multiplier=float(residual_multiplier),
        attention_multiplier=float(attention_multiplier),
        logits_scaling=float(logits_scaling),
        experts_held=None if experts_held is None else tuple(experts_held), **kw)


def sarvam_mla_cfg(*, hidden_size, num_hidden_layers, vocab_size,
                   num_attention_heads, kv_lora_rank, qk_nope_head_dim,
                   qk_rope_head_dim, v_head_dim, intermediate_size,
                   moe_intermediate_size, num_experts, num_experts_per_tok,
                   num_shared_experts=1, first_k_dense_replace=1,
                   routed_scaling_factor=1.0, moe_router_enable_expert_bias=True,
                   norm_topk_prob=True, use_qk_norm=True, rope_theta=10000.0,
                   rope_scaling=None, rms_norm_eps=1e-6, hidden_act="silu",
                   tie_word_embeddings=False, q_head_dim=None, head_dim=None,
                   q_lora_rank=None, n_group=None, topk_group=None,
                   max_position_embeddings=None, attn_implementation=None,
                   default_theta=None, model_type="sarvam_mla",
                   experts_held=None, **kw) -> CausalLMConfig:
    """Sarvam's latent-attention mixtures (``model_type: sarvam_mla``;
    DeepSeek-V2's layer without a query latent): the keywords are the
    published config's. A published layer is attention and then a
    feed-forward, each ``x + f(rmsnorm(x))``: here the pair of mixer layers
    "L" (latent attention: ``num_attention_heads`` heads whose keys and
    values are expanded from one normed latent of ``kv_lora_rank`` a token,
    queries and keys of ``qk_nope_head_dim`` lanes and ``qk_rope_head_dim``
    rotated ones, the key's rotary part one for all heads, values of
    ``v_head_dim``, no bias) and then "F" (a SwiGLU of ``intermediate_size``)
    for the first ``first_k_dense_replace`` layers and "E" after them: sigmoid
    scores, the top ``num_experts_per_tok`` of score + expert bias, weights
    from the scores over their sum times ``routed_scaling_factor``, SwiGLU
    experts of ``moe_intermediate_size`` beside ``num_shared_experts`` shared
    ones (one SwiGLU of that many widths, added unscaled). So ``n_layer`` is
    twice ``num_hidden_layers``. Rotary positions are ``deepseek_yarn``'s
    (``rope_scaling``) or plain; RMSNorm; untied head as published. Set here
    and not published: ``use_qk_norm`` is the RMSNorm over the latent, the
    sigmoid score and the 1e-20 under the weights' sum (the family's
    ``noaux_tc``), rotary pairs ``(2i, 2i + 1)``
    (``benchmarks/chipbench/configs/sarvam-105b.json: assumed``). Taken and
    not read: ``max_position_embeddings`` (the caller's ``max_seq_len``),
    ``attn_implementation``, ``default_theta``, ``model_type``. What it does
    not build it refuses."""
    rope = dict(rope_scaling or {})
    kind = rope.get("type", rope.get("rope_type"))
    refused = {
        "q_lora_rank": (q_lora_rank, None),
        "n_group": (n_group if n_group not in (0, 1) else None, None),
        "topk_group": (topk_group if topk_group not in (0, 1) else None, None),
        "rope_scaling.type": (kind, "deepseek_yarn" if rope else None),
        "hidden_act": (hidden_act, "silu"),
        "use_qk_norm": (bool(use_qk_norm), True),
        "moe_router_enable_expert_bias": (bool(moe_router_enable_expert_bias), True),
        "q_head_dim": (q_head_dim or qk_nope_head_dim + qk_rope_head_dim,
                       qk_nope_head_dim + qk_rope_head_dim),
        "head_dim": (head_dim or kv_lora_rank + qk_rope_head_dim,
                     kv_lora_rank + qk_rope_head_dim),
    }
    bad = {k: got for k, (got, built) in refused.items() if got != built}
    if bad:
        raise NotImplementedError(
            "sarvam_mla is built without a query latent and without expert "
            "groups, with deepseek_yarn or plain rotary positions, SiLU, the "
            "latent's norm, the expert bias, queries of nope + rope lanes and "
            f"a cached row of rank + rope lanes (got {bad})")
    n, dense = int(num_hidden_layers), int(first_k_dense_replace)
    yarn = None
    if rope:
        yarn = (float(rope["factor"]),
                float(rope["original_max_position_embeddings"]),
                float(rope.get("beta_fast", 32)), float(rope.get("beta_slow", 1)),
                float(rope.get("mscale", 1)), float(rope.get("mscale_all_dim", 0)))
    kw.setdefault("name", "sarvam-mla")
    return CausalLMConfig(
        n_embd=hidden_size, n_layer=2 * n,
        layer_pattern="".join("L" + ("F" if i < dense else "E") for i in range(n)),
        vocab_size=vocab_size, n_head=num_attention_heads,
        head_dim_override=v_head_dim, kv_lora_rank=int(kv_lora_rank),
        qk_nope_head_dim=int(qk_nope_head_dim),
        qk_rope_head_dim=int(qk_rope_head_dim), v_head_dim=int(v_head_dim),
        rope_yarn=yarn, pos_emb="rotary", rotary_base=float(rope_theta),
        layernorm="rmsnorm", ln_eps=rms_norm_eps, qkv_bias=False, mlp_bias=False,
        gated_mlp=True, activation="silu", d_ff=intermediate_size,
        tie_word_embeddings=bool(tie_word_embeddings), moe_kind="gated",
        moe_router="sigmoid_bias", n_routed_experts=num_experts,
        experts_per_token=num_experts_per_tok,
        moe_expert_width=moe_intermediate_size,
        moe_shared_width=int(num_shared_experts) * int(moe_intermediate_size),
        routed_scaling_factor=float(routed_scaling_factor),
        norm_topk_prob=bool(norm_topk_prob),
        experts_held=None if experts_held is None else tuple(experts_held), **kw)


def phi4flash_cfg(*, hidden_size, num_hidden_layers, vocab_size,
                  num_attention_heads, num_key_value_heads, intermediate_size,
                  sliding_window, mb_per_layer=2, mamba_d_state=16, mamba_d_conv=4,
                  mamba_expand=2, mamba_dt_rank="auto", mamba_conv_bias=True,
                  mamba_proj_bias=False, layer_norm_eps=1e-5, hidden_act="silu",
                  tie_word_embeddings=True, mlp_bias=False, lm_head_bias=False,
                  attention_bias=True, embd_pdrop=0.0, resid_pdrop=0.0,
                  attention_dropout=0.0, max_position_embeddings=None,
                  model_type="phi4flash", **kw) -> CausalLMConfig:
    """Phi-4-mini-flash (``model_type: phi4flash``; SambaY, arXiv 2507.06607):
    the keywords are the published config's and, from ``mamba_d_state`` to
    ``attention_bias``, the defaults of the family's configuration class. A
    published layer is a mixer and then a SwiGLU, each ``x + f(layernorm(x))``:
    here a pair of mixer layers, ``n_layer`` twice ``num_hidden_layers``. The
    mixer of published layer ``l`` of ``n``, as the family's class lays them
    out (``n % 4 == 0``): even ``l`` up to ``n / 2`` "S" (Mamba-1:
    ``mamba_expand * hidden_size`` channels, ``mamba_d_state`` state lanes,
    ``mamba_dt_rank`` = ``ceil(hidden_size / 16)``, ``mamba_d_conv`` taps with
    a bias), odd ``l`` below ``n / 2`` "W" (differential attention over the
    ``sliding_window`` keys up to the query's own), ``l = n / 2 + 1`` "*"
    (differential attention over every key: the ONE cache that grows), odd
    ``l`` from ``n / 2 + 3`` "X" (differential cross-attention: queries of its
    own over layer ``n / 2 + 1``'s keys and values), even ``l`` from ``n / 2 +
    2`` "G" (gated memory unit on the scan output of layer ``n / 2``, the last
    Mamba). No position encoding anywhere; LayerNorm with bias; tied head.
    Set here and not published: float32 recurrent state
    (``benchmarks/chipbench/configs/phi-4-mini-flash-reasoning.json:
    assumed``). Taken and not read: ``max_position_embeddings`` (the caller's
    ``max_seq_len``), ``model_type``. What it does not build it refuses."""
    refused = {
        "mb_per_layer": (mb_per_layer, 2),
        "embd_pdrop": (float(embd_pdrop), 0.0),
        "resid_pdrop": (float(resid_pdrop), 0.0),
        "attention_dropout": (float(attention_dropout), 0.0),
        "hidden_act": (hidden_act, "silu"),
        "mamba_conv_bias": (bool(mamba_conv_bias), True),
        "mamba_proj_bias": (bool(mamba_proj_bias), False),
        "mlp_bias": (bool(mlp_bias), False),
        "lm_head_bias": (bool(lm_head_bias), False),
        "num_hidden_layers % 4": (int(num_hidden_layers) % 4, 0),
    }
    bad = {k: got for k, (got, built) in refused.items() if got != built}
    if bad:
        raise NotImplementedError(
            "phi4flash is built with a Mamba layer every second layer "
            "(mb_per_layer 2), without dropout, with SiLU, the convolution's "
            "bias and no other bias in a Mamba layer, the SwiGLU or the head, "
            f"and a depth that fours divide (got {bad})")
    n = int(num_hidden_layers)
    half = n // 2

    def mixer(l: int) -> str:
        if l % 2 == 0:
            return "S" if l <= half else "G"
        return "W" if l < half else "*" if l == half + 1 else "X"

    rank = (-(-int(hidden_size) // 16) if mamba_dt_rank == "auto"
            else int(mamba_dt_rank))
    kw.setdefault("name", "phi4flash")
    return CausalLMConfig(
        n_embd=hidden_size, n_layer=2 * n,
        layer_pattern="".join(mixer(l) + "F" for l in range(n)),
        vocab_size=vocab_size, n_head=num_attention_heads,
        n_kv_head=num_key_value_heads, pos_emb="none", layernorm="layernorm",
        ln_eps=layer_norm_eps, qkv_bias=bool(attention_bias), mlp_bias=False,
        gated_mlp=True, activation="silu", d_ff=intermediate_size,
        tie_word_embeddings=bool(tie_word_embeddings), diff_attention=True,
        sliding_window=int(sliding_window),
        mamba1_d_inner=int(mamba_expand) * int(hidden_size), mamba1_dt_rank=rank,
        ssm_state_size=int(mamba_d_state), conv_kernel=int(mamba_d_conv), **kw)


FAMILIES = {
    "gpt2": gpt2_cfg, "bloom": bloom_cfg, "opt": opt_cfg,
    "gpt_neox": gptneox_cfg, "gptj": gptj_cfg, "llama": llama_cfg,
}


# ----------------------------------------------------------------------- positional
def alibi_slopes(n_head: int) -> np.ndarray:
    """BLOOM alibi slope schedule (geometric in powers of 2)."""
    closest = 2 ** int(np.floor(np.log2(n_head)))
    base = 2.0 ** (-(2.0 ** -(np.log2(closest) - 3)))
    slopes = base ** np.arange(1, closest + 1)
    if closest < n_head:
        extra_base = 2.0 ** (-(2.0 ** -(np.log2(2 * closest) - 3)))
        extra = extra_base ** np.arange(1, 2 * (n_head - closest) + 1, 2)
        slopes = np.concatenate([slopes, extra])
    return slopes.astype(np.float32)


def rotate_half(x):
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([-x2, x1], axis=-1)


def apply_rotary(x, positions, base: float, pct: float):
    """x: (b, t, h, d); positions: (b, t). Reference kernel:
    ``csrc/transformer/inference/csrc/apply_rotary_pos_emb.cu``."""
    d = x.shape[-1]
    rot = int(d * pct) // 2 * 2
    if rot == 0:
        return x
    x_rot, x_pass = x[..., :rot], x[..., rot:]
    inv_freq = 1.0 / (base ** (np.arange(0, rot, 2, dtype=np.float32) / rot))
    freqs = positions[..., None].astype(jnp.float32) * inv_freq[None, None]  # (b,t,rot/2)
    emb = jnp.concatenate([freqs, freqs], axis=-1)[:, :, None, :]            # (b,t,1,rot)
    cos, sin = jnp.cos(emb), jnp.sin(emb)
    x_rot = x_rot.astype(jnp.float32)
    out = x_rot * cos + rotate_half(x_rot) * sin
    return jnp.concatenate([out.astype(x.dtype), x_pass], axis=-1)


def embed_rows(cfg: CausalLMConfig, wte, ids):
    """The embedding's rows in the model's type, scaled by
    ``embedding_multiplier`` where the family has one."""
    x = wte[ids]
    if cfg.embedding_multiplier != 1:
        x = x * cfg.embedding_multiplier
    return x.astype(cfg.dtype)


def scale_logits(cfg: CausalLMConfig, logits):
    return logits if cfg.logits_scaling == 1 else logits / cfg.logits_scaling


def _norm(cfg: CausalLMConfig, name: str):
    if cfg.layernorm == "rmsnorm":
        return nn.RMSNorm(epsilon=cfg.ln_eps, dtype=jnp.float32, name=name)
    return nn.LayerNorm(epsilon=cfg.ln_eps, dtype=jnp.float32, name=name)


def _act(cfg: CausalLMConfig):
    return {"gelu": partial(nn.gelu, approximate=True), "relu": nn.relu,
            "silu": nn.silu}[cfg.activation]


# ----------------------------------------------------------------------- modules
class QuantDense(nn.Module):
    """Drop-in for ``nn.Dense`` at column-parallel quantizable sites
    (qkv / fc_in / gate / up).

    Parameter tree (``kernel``/``bias``, fp32) is identical to ``nn.Dense`` —
    checkpoints and the training path don't change. At serve time the engine
    may swap ``kernel`` for a quant node; the projection then runs through the
    fused dequant-matmul kernel (``ops/quantizer/fused_matmul.py``) so
    int8/int4 bytes are what streams from HBM on the decode hot path."""
    features: int
    use_bias: bool = True
    dtype: Any = jnp.float32
    kernel_init: Any = nn.initializers.lecun_normal()
    bias_init: Any = nn.initializers.zeros
    site: str = "wq.dense"

    @nn.compact
    def __call__(self, x):
        kernel = raw_or_param(self, "kernel", self.kernel_init,
                               (x.shape[-1], self.features))
        bias = (self.param("bias", self.bias_init, (self.features,),
                           jnp.float32) if self.use_bias else None)
        from ..ops.quantizer import is_quant_node, quant_dense_apply
        if is_quant_node(kernel):
            return quant_dense_apply(x, kernel, bias, self.dtype,
                                     parallel="column", site=self.site)
        y = x.astype(self.dtype) @ kernel.astype(self.dtype)
        return y if bias is None else y + bias.astype(self.dtype)


class RoundedDense(nn.Module):
    """``nn.Dense``'s parameter tree (``kernel``, ``bias``) with every
    rounding to the serving type an explicit op (``mamba1.project``): the
    product accumulated in float32 and rounded, the bias added and the sum
    rounded again, whatever the compiler fuses around it. The result is the
    serving type's. For the layers whose serving chunk and ``generate`` loop
    must round alike on the chip (PERF.md section 6, PR 59)."""
    features: int
    use_bias: bool = True
    dtype: Any = jnp.float32
    kernel_init: Any = nn.initializers.lecun_normal()

    @nn.compact
    def __call__(self, x):
        kernel = self.param("kernel", self.kernel_init, (x.shape[-1], self.features),
                            jnp.float32)
        y = project(x, kernel, self.dtype)
        if self.use_bias:
            bias = self.param("bias", nn.initializers.zeros, (self.features,),
                              jnp.float32)
            y = serving_values(y + bias.astype(self.dtype).astype(jnp.float32),
                               self.dtype)
        return y.astype(self.dtype)


class _ExpertWeights(nn.Module):
    """Param holder producing the same tree as the training ``moe.experts.Experts``
    module (``moe_experts/{w1,b1,w2,b2}``) so trained checkpoints map 1:1; the routing
    math lives in the caller where it can be vmapped over token chunks. ``w1``/``w2``
    may come back as quant nodes at serve time (see :func:`raw_or_param`)."""
    num_experts: int
    d_model: int
    d_ff: int
    init_std: float

    @nn.compact
    def __call__(self):
        e, d, f = self.num_experts, self.d_model, self.d_ff
        init = nn.initializers.normal(self.init_std)
        return (raw_or_param(self, "w1", init, (e, d, f)),
                self.param("b1", nn.initializers.zeros, (e, f), jnp.float32),
                raw_or_param(self, "w2", init, (e, f, d)),
                self.param("b2", nn.initializers.zeros, (e, d), jnp.float32))


class CausalLMLayer(nn.Module):
    config: CausalLMConfig
    is_moe: bool = False

    def _attn_proj(self, x):
        cfg = self.config
        hd, hk = cfg.head_dim, cfg.kv_heads
        with scope("attn.qkv"):
            q = QuantDense(cfg.n_head * hd, use_bias=cfg.qkv_bias, dtype=cfg.dtype,
                           kernel_init=nn.initializers.normal(cfg.init_std),
                           site="wq.q_proj", name="q_proj")(x)
            k = QuantDense(hk * hd, use_bias=cfg.qkv_bias, dtype=cfg.dtype,
                           kernel_init=nn.initializers.normal(cfg.init_std),
                           site="wq.k_proj", name="k_proj")(x)
            v = QuantDense(hk * hd, use_bias=cfg.qkv_bias, dtype=cfg.dtype,
                           kernel_init=nn.initializers.normal(cfg.init_std),
                           site="wq.v_proj", name="v_proj")(x)
        b, t = x.shape[:2]
        with scope("attn.heads"):
            return (q.reshape(b, t, cfg.n_head, hd), k.reshape(b, t, hk, hd),
                    v.reshape(b, t, hk, hd))

    def _mlp(self, h):
        cfg = self.config
        act = _act(cfg)
        init = nn.initializers.normal(cfg.init_std)
        proj_init = nn.initializers.normal(cfg.out_std)
        if cfg.gated_mlp:
            with scope("mlp.up"):
                gate = QuantDense(cfg.ffn_dim, use_bias=cfg.mlp_bias,
                                  dtype=cfg.dtype, kernel_init=init,
                                  site="wq.gate_proj", name="gate_proj")(h)
                up = QuantDense(cfg.ffn_dim, use_bias=cfg.mlp_bias, dtype=cfg.dtype,
                                kernel_init=init, site="wq.up_proj",
                                name="up_proj")(h)
            with scope("mlp.act"):
                h = act(gate) * up
        else:
            with scope("mlp.up"):
                h = QuantDense(cfg.ffn_dim, use_bias=cfg.mlp_bias, dtype=cfg.dtype,
                               kernel_init=init, site="wq.fc_in", name="fc_in")(h)
            with scope("mlp.act"):
                h = act(h)
        # row-parallel TP site: lowers to the chunked matmul-reduce-scatter
        # ring when comm_overlap is active (plain matmul + GSPMD allreduce
        # otherwise); parameter tree identical to nn.Dense
        with scope("mlp.down"):
            return RowParallelDense(cfg.n_embd, use_bias=cfg.mlp_bias,
                                    dtype=cfg.dtype, kernel_init=proj_init,
                                    span="tp.fc_out", name="fc_out")(h)

    # prefill tokens are routed in chunks of this size: the one-hot dispatch/combine
    # tensors are (C, e, C) per chunk — linear total memory/flops in token count instead
    # of the quadratic (s, e, s) a whole-sequence no-drop dispatch would build
    MOE_CHUNK = 256

    def _moe_mlp(self, h):
        """Gated expert-mixture FFN for serving (reference ``moe_inference.py``: gating +
        einsum dispatch in the decode path). Eval-mode gating: deterministic, no token drop
        (chunked dispatch with capacity = chunk size — routing is per-token, so chunking
        does not change results; the reference's inference MoE has no capacity dropping
        either), experts sharded over the ``expert`` axis."""
        from ..moe.sharded_moe import TopKGate
        from ..parallel.mesh import AXIS_EXPERT, get_global_mesh
        cfg = self.config
        b, t, d = h.shape
        s = b * t
        x = h.reshape(s, d)
        wg = self.param("moe_gate", nn.initializers.normal(cfg.init_std),
                        (d, cfg.num_experts), jnp.float32)
        gate = TopKGate(k=cfg.moe_top_k, drop_tokens=False, use_rts=False,
                        top2_2nd_expert_sampling=False)
        # bind expert weights ONCE at this scope (params: moe_experts/{w1,b1,w2,b2}, same
        # tree as the training Experts module), then route with pure math — safe to vmap
        w1, b1, w2, b2 = _ExpertWeights(cfg.num_experts, d, cfg.ffn_dim, cfg.init_std,
                                        name="moe_experts")()
        act = _act(cfg)
        cdtype = cfg.dtype
        mesh = get_global_mesh()
        expert_sharded = mesh is not None and mesh.size(AXIS_EXPERT) > 1
        from ..ops.quantizer import dequantize_node, is_quant_node
        quant_experts = is_quant_node(w1) or is_quant_node(w2)
        if (quant_experts and t == 1 and cfg.moe_decode_fastpath
                and not expert_sharded and cfg.num_experts > cfg.moe_top_k):
            # quantized decode fast path: gather the SELECTED experts'
            # int8/int4 bytes from HBM (2-4x less weight traffic than a bf16
            # gather), dequantize only the gathered slices. Same dispatch-time
            # impl re-validation as the fp fastpath below; both impl spellings
            # route here (the quant gather IS the xla-style gather, and the
            # pallas kernel's BlockSpec streaming doesn't apply to packed
            # payloads yet)
            if cfg.moe_decode_impl not in CausalLMConfig.VALID_MOE_DECODE_IMPLS:
                raise ValueError(
                    f"moe_decode_impl={cfg.moe_decode_impl!r} is not one of "
                    f"{CausalLMConfig.VALID_MOE_DECODE_IMPLS}")
            from ..moe.sharded_moe import topk_select
            from ..ops.moe import moe_decode_ffn_quant
            k = cfg.moe_top_k
            with scope("moe.router"):
                logits = x.astype(jnp.float32) @ wg.astype(jnp.float32)
                idx, gw = topk_select(logits, k)
            with scope("moe.rows"):
                xk = x.astype(cdtype)
                if k > 1:
                    xk = jnp.repeat(xk, k, axis=0)
            with scope("moe.experts"):
                y = moe_decode_ffn_quant(xk, idx.reshape(-1), w1, b1, w2, b2, act)
            with scope("moe.rows"):
                out = jnp.einsum("bk,bkm->bm", gw, y.reshape(b, k, d))
                return out.reshape(b, t, d).astype(h.dtype)
        if quant_experts:
            # dispatch path (prefill / expert-sharded / fastpath off): every
            # expert's FFN runs, so collapse the nodes here — XLA fuses the
            # dequant into the consuming einsum's operand read. On the XLA
            # fallback backend the engine hoists this out of compiled decode
            # bodies (decode_fns); on the FUSED backend the nodes reach this
            # point inside the loop body and the step streams bf16-equivalent
            # expert bytes (XLA LICM makes the dequant a loop constant at
            # best) — t==1 here means that regression is live, so say so
            if t == 1:
                from ..ops.quantizer import fused_backend_active
                if fused_backend_active():
                    from ..utils.logging import log_dist
                    log_dist(
                        "weight_quant[moe_experts]: quantized experts on the "
                        "decode DISPATCH path (expert-sharded or fastpath "
                        "off) — dequantized in the loop body, no weight-"
                        "stream win; consider weight_quant.exclude for "
                        "expert FFNs in this topology", ranks=[0])
            w1 = dequantize_node(w1) if is_quant_node(w1) else w1
            w2 = dequantize_node(w2) if is_quant_node(w2) else w2
        if (t == 1 and cfg.moe_decode_fastpath and not expert_sharded
                and cfg.num_experts > cfg.moe_top_k):
            # decode fast path: a (b, 1, d) step touches at most b*k experts; the
            # gather-fused kernel streams just those experts' weights instead of
            # running every expert's FFN on a mostly-zero dispatch tensor. Routing
            # semantics shared with the dispatch path via topk_select (parity pinned
            # in tests/unit/moe/test_moe_decode.py).
            from ..moe.sharded_moe import topk_select
            from ..ops.moe import moe_decode_ffn, moe_decode_ffn_xla
            k = cfg.moe_top_k
            with scope("moe.router"):
                logits = x.astype(jnp.float32) @ wg.astype(jnp.float32)   # (b, e)
                idx, gw = topk_select(logits, k)                          # (b, k) ×2
            with scope("moe.rows"):
                xk = x.astype(cdtype)
                if k > 1:
                    xk = jnp.repeat(xk, k, axis=0)                        # (b*k, d)
            # dispatch-time re-validation: configs mutated after construction
            # (engine plumbing) must not silently fall through to pallas
            if cfg.moe_decode_impl not in CausalLMConfig.VALID_MOE_DECODE_IMPLS:
                raise ValueError(
                    f"moe_decode_impl={cfg.moe_decode_impl!r} is not one of "
                    f"{CausalLMConfig.VALID_MOE_DECODE_IMPLS}")
            ffn = (moe_decode_ffn_xla if cfg.moe_decode_impl == "xla"
                   else moe_decode_ffn)
            with scope("moe.experts"):
                y = ffn(xk, idx.reshape(-1),
                        w1.astype(cdtype), b1.astype(cdtype),
                        w2.astype(cdtype), b2.astype(cdtype), act)
            with scope("moe.rows"):
                out = jnp.einsum("bk,bkm->bm", gw, y.reshape(b, k, d))
                return out.reshape(b, t, d).astype(h.dtype)

        def expert_fn(expert_in):                       # (e, c, m) → (e, c, m)
            hh = jnp.einsum("ecm,emf->ecf", expert_in, w1.astype(cdtype)) + \
                b1[:, None, :].astype(cdtype)
            hh = act(hh)
            return jnp.einsum("ecf,efm->ecm", hh, w2.astype(cdtype)) + \
                b2[:, None, :].astype(cdtype)

        def gating(tokens):                             # pure math, safe under vmap
            _, combine, dispatch, _ = gate(wg, tokens, train=False, rng=None)
            return combine, dispatch

        e = cfg.num_experts
        chunk = min(s, self.MOE_CHUNK)
        pad = (-s) % chunk
        xc = jnp.pad(x, ((0, pad), (0, 0))).reshape(-1, chunk, d)     # (n, C, m)
        n = xc.shape[0]
        with scope("moe.router"):
            combine, dispatch = jax.vmap(gating)(xc)                  # (n, C, e, cap)
        cap = combine.shape[-1]          # == chunk for top-1, 2*chunk for top-2 (no-drop)
        with scope("moe.rows"):
            expert_in = jnp.einsum("nsec,nsm->encm", dispatch.astype(jnp.float32),
                                   xc.astype(jnp.float32)).astype(cdtype)
            expert_in = expert_in.reshape(e, n * cap, d)
        if expert_sharded:
            # capacity-chunked exchange when comm_overlap is active: each
            # chunk's token-major → expert-major a2a overlaps the previous
            # chunk's expert FFN (bitwise-exact — per-token FFN, whole combine)
            n_chunks = moe_overlap_chunks(get_overlap_config(),
                                          mesh.size(AXIS_EXPERT), n * cap)
            expert_out = chunked_expert_exchange(
                expert_in, expert_fn,
                mesh.sharding(P(AXIS_EXPERT, None, None)), n_chunks,
                site="moe.decode_a2a")
        else:
            with scope("moe.experts"):
                expert_out = expert_fn(expert_in)                     # (e, n*cap, m)
        with scope("moe.rows"):
            expert_out = expert_out.reshape(e, n, cap, d)
            out = jnp.einsum("nsec,encm->nsm", combine.astype(jnp.float32),
                             expert_out.astype(jnp.float32))
            out = out.reshape(-1, d)[:s]
            return out.reshape(b, t, d).astype(h.dtype)

    @nn.compact
    def __call__(self, x, positions, cache: Optional[Dict] = None,
                 cache_len: Optional[jnp.ndarray] = None,
                 prefix_fill: bool = False, block_step: bool = False,
                 attn_mask=None):
        """x: (b, t, d). With ``cache`` given (decode): t==1, attention against the cache.
        With ``prefix_fill`` (static): suffix prefill at a nonzero cache offset —
        ``cache`` already holds a restored prompt-prefix KV slab in rows
        ``[0, cache_len)``, the t suffix tokens write their K/V at rows
        ``cache_len + i`` and attend over prefix + suffix (the prefix-cache hit
        path: the prefix's prefill compute is skipped entirely).
        Returns (y, new_cache_kv or None)."""
        cfg = self.config
        with scope("norm"):
            h_in = _norm(cfg, "ln_attn")(x).astype(cfg.dtype)
        attn_out, new_kv = self._attention(h_in, positions, cache, cache_len,
                                           prefix_fill, block_step, attn_mask)

        mlp = self._moe_mlp if self.is_moe else self._mlp
        if cfg.parallel_residual:
            with scope("norm"):
                h_mlp = _norm(cfg, "ln_mlp")(x).astype(cfg.dtype)
            with scope("residual"):
                y = x + attn_out
            mlp_out = mlp(h_mlp)
            with scope("residual"):
                y = y + mlp_out
        else:
            with scope("residual"):
                x = x + attn_out
            with scope("norm"):
                h_mlp = _norm(cfg, "ln_mlp")(x).astype(cfg.dtype)
            mlp_out = mlp(h_mlp)
            with scope("residual"):
                y = x + mlp_out
        return y, new_kv

    def _attention(self, h_in, positions, cache, cache_len, prefix_fill,
                   block_step=False, attn_mask=None):
        """Causal self-attention on the normed input, in one of its cache
        modes: the whole sequence (without a cache, or with one to fill: a
        prefill), one token against the dense cache (decode; a served slot's
        cache is the chunk's dense view of its pages), a prefill at a cache
        offset (``prefix_fill``); returns the projected output and the
        layer's new keys and values (or None).

        A model that generates by diffusion over blocks
        (``cfg.gen_block_length``) has one more, ``block_step``: ``t`` is
        one block or several in a row, their keys and values are written at
        rows ``[cache_len, cache_len + t)`` of the dense cache (which must
        hold them: ``_cache_update`` clamps) and every query of the ``j``-th
        block sees rows ``[0, cache_len + (j + 1) * block)``: the committed
        blocks, the blocks before its own and its own. Its
        whole-sequence modes take the block-causal mask, or ``attn_mask`` (t,
        t) bool where the caller lays several copies of a sequence side by
        side (``InferenceEngine.forward``)."""
        cfg = self.config
        b, t, _ = h_in.shape
        q, k, v = self._attn_proj(h_in)
        with scope("attn.heads"):
            if cfg.qk_norm:
                q = nn.RMSNorm(epsilon=cfg.ln_eps, dtype=jnp.float32,
                               name="q_norm")(q).astype(cfg.dtype)
                k = nn.RMSNorm(epsilon=cfg.ln_eps, dtype=jnp.float32,
                               name="k_norm")(k).astype(cfg.dtype)
            if cfg.pos_emb == "rotary":
                q = apply_rotary(q, positions, cfg.rotary_base, cfg.rotary_pct)
                k = apply_rotary(k, positions, cfg.rotary_base, cfg.rotary_pct)

        slopes = (jnp.asarray(alibi_slopes(cfg.n_head))
                  if cfg.pos_emb == "alibi" else None)
        scale = cfg.attn_scale           # every path below is handed this one
        if cache is not None:
            # the KV heads a row of this cache holds (heads_per_row made it)
            r = cache["k"].shape[-1] // cfg.head_dim

        new_kv = None
        if block_step:
            if cache is None or slopes is not None:
                raise NotImplementedError(
                    "a block step runs on the dense cache view, without alibi")
            with scope("attn.heads"):
                k_hm = kv_rows(k, r)
            with scope("kv.append"):
                k_cache = _cache_update(cache["k"], k_hm, cache_len)
            with scope("attn.heads"):
                v_hm = kv_rows(v, r)
            with scope("kv.append"):
                v_cache = _cache_update(cache["v"], v_hm, cache_len)
            new_kv = {"k": k_cache, "v": v_cache}
            o = _block_decode(q, k_cache, v_cache, cache_len, cfg.gen_block_length,
                              scale)
        elif cache is not None and t == 1:
            # decode: append to cache (head-major), fused decode kernel
            with scope("attn.heads"):
                k_hm = kv_rows(k, r)             # (b, hk / r, 1, r * d)
                v_hm = kv_rows(v, r)
            with scope("kv.append"):
                k_cache = _cache_update(cache["k"], k_hm, cache_len)
                v_cache = _cache_update(cache["v"], v_hm, cache_len)
            new_kv = {"k": k_cache, "v": v_cache}
            with scope("attn.core"):
                o = _sharded_decode(q[:, 0], k_cache, v_cache, cache_len + 1,
                                    alibi=slopes, scale=scale)[:, None]
        elif cache is not None and prefix_fill and cfg.gen_block_length:
            raise NotImplementedError(
                "a prefill at a cache offset is causal: a model that generates "
                "by blocks has no prefix hits and no speculative verify")
        elif cache is not None and prefix_fill:
            # suffix prefill at offset cache_len: scatter suffix K/V into rows
            # [cache_len, cache_len + t) (OOB pad rows drop), attend each suffix
            # query over every cache row at position <= its own
            with scope("attn.heads"):
                k_hm = kv_rows(k, r)             # (b, hk / r, t, r * d)
                v_hm = kv_rows(v, r)

            def put(c, n, i):
                return c.at[:, i, :].set(n.astype(c.dtype))

            with scope("kv.append"):
                idx = cache_len[:, None] + jnp.arange(t)[None]        # (b, t)
                k_cache = jax.vmap(put)(cache["k"], k_hm, idx)
                v_cache = jax.vmap(put)(cache["v"], v_hm, idx)
            new_kv = {"k": k_cache, "v": v_cache}
            with scope("attn.core"):
                o = _prefix_attention_xla(q, k_cache, v_cache, cache_len, slopes,
                                          scale)
        else:
            if cache is not None and attn_mask is not None:
                raise NotImplementedError("attn_mask is for a forward without a cache")
            with scope("attn.core"):
                o = _bias_attention(q, k, v, slopes, cfg.gen_block_length or 1,
                                    attn_mask, scale)
            if cache is not None:
                # prefill: write the prompt's K/V (post-rotary) into the fixed cache
                T = cache["k"].shape[2]
                with scope("attn.heads"):
                    k_hm = kv_rows(k, r)
                    v_hm = kv_rows(v, r)
                pad = ((0, 0), (0, 0), (0, T - t), (0, 0))
                with scope("kv.append"):
                    new_kv = {"k": jnp.pad(k_hm, pad).astype(cache["k"].dtype),
                              "v": jnp.pad(v_hm, pad).astype(cache["v"].dtype)}
        with scope("attn.heads"):
            o = o.reshape(b, t, cfg.n_head * cfg.head_dim)
        proj_init = nn.initializers.normal(cfg.out_std)
        with scope("attn.out"):
            attn_out = RowParallelDense(cfg.n_embd, use_bias=cfg.mlp_bias,
                                        dtype=cfg.dtype, kernel_init=proj_init,
                                        span="tp.o_proj", name="o_proj")(o)
        return attn_out, new_kv


@dataclasses.dataclass
class LayerCall:
    """What :class:`MixerLayer` hands the method that runs its mixer beside
    the normed input: the arguments of its own call. ``carried`` holds what
    earlier layers of this forward handed on (:attr:`LayerKind.writes`);
    ``last_at`` (b,) is set for the ONE layer at which a prefill stops
    (:attr:`CausalLMConfig.prefill_stop`): the mixer writes its cache for
    every position and gives its output at that position alone."""
    positions: Any
    cache: Optional[Dict]
    cache_len: Any
    prefix_fill: bool
    seq_lens: Any
    block_step: bool
    attn_mask: Any
    carried: Dict
    last_at: Any = None


def _take_rows(x, at):
    """``x`` (b, t, ...) at position ``at`` (b,) of each row: ``(b, 1, ...)``."""
    return x[jnp.arange(x.shape[0]), at][:, None]


class MixerLayer(CausalLMLayer):
    """A layer of ONE mixer, ``x + residual_multiplier * mixer(norm(x))`` (the
    multiplier 1 but for Granite); ``kind`` is the letter of the
    configuration's pattern, a key of :data:`LAYER_KINDS`, whose entry names
    the method that runs the mixer (``mixer(h, call) -> (out, new cache)``)
    and says what the layer keeps, hands on and reads; ``index`` is the
    layer's place in the pattern. ``seq_lens`` (b,) are the real lengths of
    right-padded rows in a prefill or a block step: a recurrence must not run
    over the padding that a causal mask forgives, and an expert layer routes
    it nowhere. ``carried`` and ``last_at``: :class:`LayerCall`."""
    kind: str = "*"
    index: int = 0

    @nn.compact
    def __call__(self, x, positions, cache: Optional[Dict] = None,
                 cache_len: Optional[jnp.ndarray] = None,
                 prefix_fill: bool = False, seq_lens=None,
                 block_step: bool = False, attn_mask=None, carried=None,
                 last_at=None):
        cfg = self.config
        entry = LAYER_KINDS[self.kind]
        with scope("norm"):
            h = _norm(cfg, "norm")(x).astype(cfg.dtype)
        if entry.keeps == "state" and prefix_fill:
            raise NotImplementedError(
                f"a {entry.name} layer cannot resume at a cache offset: its "
                "state after the prefix was not kept (no prefix hits, no "
                "speculative verify on a model with such layers)")
        out, new = getattr(self, entry.mixer)(h, LayerCall(
            positions, cache, cache_len, prefix_fill, seq_lens, block_step,
            attn_mask, {} if carried is None else carried, last_at))
        if entry.keeps == "nothing":
            new = None if cache is None else {}
        if last_at is not None:
            with scope("residual"):
                x = _take_rows(x, last_at)
        with scope("residual"):
            if cfg.residual_multiplier != 1:
                # one rounding to the stream's type, not one a factor and one a sum
                out = cfg.residual_multiplier * out.astype(jnp.float32)
                return (x + out).astype(x.dtype), new
            return x + out.astype(x.dtype), new

    def _latent_attention(self, h_in, positions, cache, cache_len, attn_mask):
        """Latent attention on the normed input (``ops/attention/latent.py``
        has the row and the absorbed form). A token's query is ``n_head`` x
        (nope | rope) lanes; ``kv_a_proj`` gives its latent, normed, and the
        ONE rotary key all heads share: the row the cache keeps, ``{"k": (b,
        1, T, lanes)}``, zero lanes behind it. One token against the cache
        (decode) is ABSORBED, scope ``attn.latent``: ``kv_b_k`` takes the
        queries into the latent, the rows are read once for scores and values
        (:func:`latent_decode_attention`), ``kv_b_v`` expands the result. A
        whole sequence (prefill, forward) is EXPANDED, scope
        ``attn.latent_expand``: keys ``[k^n_i ; k^r]`` and values a head from
        the latent, then causal attention (:func:`_latent_attention_core`);
        with a cache to fill only the rows are returned. ``kv_b_k`` and
        ``kv_b_v`` are ``kv_b_proj``'s two halves a head, kept apart."""
        cfg = self.config
        b, t, _ = h_in.shape
        H, rank = cfg.n_head, cfg.kv_lora_rank
        nope, rope, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
        init = nn.initializers.normal(cfg.init_std)
        with scope("attn.qkv"):
            q = QuantDense(H * (nope + rope), use_bias=False, dtype=cfg.dtype,
                           kernel_init=init, site="wq.q_proj", name="q_proj")(h_in)
            kva = QuantDense(rank + rope, use_bias=False, dtype=cfg.dtype,
                             kernel_init=init, site="wq.kv_a_proj",
                             name="kv_a_proj")(h_in)
        w_uk = self.param("kv_b_k", init, (rank, H, nope), jnp.float32).astype(cfg.dtype)
        w_uv = self.param("kv_b_v", init, (rank, H, dv), jnp.float32).astype(cfg.dtype)
        lanes = latent_row_lanes(rank + rope)
        with scope("attn.heads"):
            q = q.reshape(b, t, H, nope + rope)
            c = nn.RMSNorm(epsilon=cfg.ln_eps, dtype=jnp.float32,
                           name="kv_a_norm")(kva[..., :rank]).astype(cfg.dtype)
            inv_freq, mscale = cfg.latent_rope()
            q_n = q[..., :nope]
            q_r = rotate_pairs(q[..., nope:], positions, inv_freq, mscale)
            k_r = rotate_pairs(kva[:, :, None, rank:], positions, inv_freq, mscale)
            row = jnp.concatenate(
                [c, k_r[:, :, 0], jnp.zeros((b, t, lanes - rank - rope), c.dtype)],
                axis=-1)[:, None]                            # (b, 1, t, lanes)
        scale = cfg.attn_scale
        new = None
        if cache is not None and t == 1:
            with scope("kv.append"):
                rows = _cache_update(cache["k"], row, cache_len)
            new = {"k": rows}
            with scope("attn.latent"):
                q_abs = jnp.einsum("bhn,lhn->bhl", q_n[:, 0], w_uk)
                q_row = jnp.concatenate(
                    [q_abs, q_r[:, 0],
                     jnp.zeros((b, H, lanes - rank - rope), q_abs.dtype)],
                    axis=-1).astype(rows.dtype)
                o = latent_decode_attention(q_row, rows, cache_len + 1, scale)
                o = jnp.einsum("bhl,lhv->bhv", o[..., :rank].astype(cfg.dtype),
                               w_uv)[:, None]
        else:
            with scope("attn.latent_expand"):
                k_n = jnp.einsum("btl,lhn->bthn", c, w_uk)
                v = jnp.einsum("btl,lhv->bthv", c, w_uv)
                k = jnp.concatenate(
                    [k_n, jnp.broadcast_to(k_r, (b, t, H, rope))], axis=-1)
                q = jnp.concatenate([q_n, q_r], axis=-1)
            with scope("attn.core"):
                o = _latent_attention_core(q, k, v, attn_mask, scale)
            if cache is not None:
                T = cache["k"].shape[2]
                with scope("kv.append"):
                    new = {"k": jnp.pad(row, ((0, 0), (0, 0), (0, T - t), (0, 0)))
                           .astype(cache["k"].dtype)}
        with scope("attn.heads"):
            o = o.reshape(b, t, H * dv)
        with scope("attn.out"):
            out = RowParallelDense(cfg.n_embd, use_bias=False, dtype=cfg.dtype,
                                   kernel_init=nn.initializers.normal(cfg.out_std),
                                   span="tp.o_proj", name="o_proj")(o)
        return out, new

    def _self_attention(self, h, call):
        if self.config.diff_attention:
            return self._diff_attention(h, call)
        return self._attention(h, call.positions, call.cache, call.cache_len,
                               call.prefix_fill, call.block_step, call.attn_mask)

    def _latent(self, h, call):
        if call.prefix_fill or call.block_step:
            raise NotImplementedError(
                "a latent-attention layer has no prefill at a cache offset "
                "and no block step: the rows it keeps are latents, which "
                "only the one-token decode attends in absorbed form (no "
                "prefix hits, no speculative verify on a model with such "
                "layers)")
        return self._latent_attention(h, call.positions, call.cache,
                                      call.cache_len, call.attn_mask)

    def _read(self, call, what: str, like):
        """What an earlier layer of this forward handed on under ``what``
        (:attr:`LayerKind.reads`). A layer initialised alone (a segment of
        ``causal_lm_segments``) reads ``like()``: its parameters do not
        depend on it."""
        if what not in call.carried:
            if self.is_initializing():
                return like()
            raise ValueError(
                f"layer {self.index} is a {LAYER_KINDS[self.kind].name} layer: it "
                f"reads the {what} an earlier layer of the same forward hands "
                "on, and none did in this call")
        return call.carried[what]

    def _diff_attention(self, h_in, call):
        """Differential attention on the normed input, the model's three
        attention kinds. Heads come in adjacent PAIRS: query pair ``i`` is
        heads ``(2i, 2i + 1) = (q1, q2)``, key pair ``j = i // (pairs / rows)``
        is KV heads ``(2j, 2j + 1) = (k1, k2)``, and ``V = [v1 ; v2]``; ``a1 =
        softmax(q1 k1^T s) V``, ``a2 = softmax(q2 k2^T s) V`` under one mask,
        and the pair's output is ``(1 - lam0) * RMSNorm(a1 - lam * a2)`` with
        ``lam = exp(lq1 . lk1) - exp(lq2 . lk2) + lam0`` and ``lam0 = 0.8 - 0.6
        exp(-0.3 depth)``. A cache row holds one key PAIR side by side
        (``cache_row_heads``), so with ``q1' = [q1 ; 0]`` and ``q2' = [0 ;
        q2]`` the two maps are two ordinary query heads of ``2 head_dim``
        lanes over that row (the zero lanes add exact zeros to the scores):
        the decode kernel, the flash kernel and XLA's products as they are.

        - "*" keeps keys and values in pages and hands its cache on
          (``carried["kv"]``): the dense rows and each sequence's length
          where there is a cache and ONE query a sequence (decode; the
          layer a prefill stops at, ``call.last_at``), else the sequence's
          own keys and values;
        - "X" has queries alone and attends what "*" handed on;
        - "W" sees the ``sliding_window`` keys up to its own: a ring of that
          many rows a slot, token ``t`` at row ``t mod window`` (no
          positions, so a softmax over the rows in any order is the same).
          A prefill attends under the band and leaves each sequence's last
          rows in the ring, by ``seq_lens``.

        Scopes: ``attn.shared`` the attention over the one cache, ``attn.window``
        the ring's write and attention and a prefill's band, ``attn.diff`` the
        combination."""
        cfg, kind = self.config, self.kind
        b, t, _ = h_in.shape
        hd, H = cfg.head_dim, cfg.n_head
        rows, lanes = cfg.kv_heads // 2, 2 * cfg.head_dim
        cache, W = call.cache, cfg.sliding_window
        if call.prefix_fill or call.block_step or call.attn_mask is not None:
            raise NotImplementedError(
                "differential attention has the whole-sequence, the prefill and "
                "the one-token modes: no prefill at a cache offset, no block "
                "step, no mask of the caller's")
        init = nn.initializers.normal(cfg.init_std)

        def dense(width, name, init=init):
            return RoundedDense(width, use_bias=cfg.qkv_bias, dtype=cfg.dtype,
                                kernel_init=init, name=name)

        with scope("attn.qkv"):
            h_q = h_in if call.last_at is None else _take_rows(h_in, call.last_at)
            q = dense(H * hd, "q_proj")(h_q)
            if kind != "X":
                k = dense(2 * rows * hd, "k_proj")(h_in)
                v = dense(2 * rows * hd, "v_proj")(h_in)
        lam_init = nn.initializers.normal(0.1)
        lq1, lk1, lq2, lk2 = (self.param(f"lambda_{n}", lam_init, (hd,), jnp.float32)
                              for n in ("q1", "k1", "q2", "k2"))
        subln = self.param("subln", nn.initializers.ones, (lanes,), jnp.float32)
        tq = q.shape[1]
        with scope("attn.heads"):
            qp = pair_queries(q.reshape(b, tq, H, hd))           # (b, tq, H, lanes)
            if kind != "X":
                k = k.reshape(b, t, rows, lanes)
                v = v.reshape(b, t, rows, lanes)
        scale = cfg.attn_scale
        one_query = cache is not None and tq == 1
        new = None
        if kind == "W" and one_query:
            with scope("attn.window"):
                at = call.cache_len % W
                ring_k = _cache_update(cache["k"], k.transpose(0, 2, 1, 3), at)
                ring_v = _cache_update(cache["v"], v.transpose(0, 2, 1, 3), at)
                new = {"k": ring_k, "v": ring_v}
                o = _sharded_decode(qp[:, 0], ring_k, ring_v,
                                    jnp.minimum(call.cache_len + 1, W),
                                    scale=scale)[:, None]
        elif kind == "W":
            with scope("attn.window"):
                o = _band_attention(qp, k, v, W, scale)
                if cache is not None:
                    lens = (jnp.full((b,), t, jnp.int32) if call.seq_lens is None
                            else call.seq_lens)
                    new = {"k": _ring_rows(k, lens, W).astype(cache["k"].dtype),
                           "v": _ring_rows(v, lens, W).astype(cache["v"].dtype)}
        elif kind == "*" and one_query and t == 1:
            with scope("kv.append"):
                k_cache = _cache_update(cache["k"], k.transpose(0, 2, 1, 3),
                                        call.cache_len)
                v_cache = _cache_update(cache["v"], v.transpose(0, 2, 1, 3),
                                        call.cache_len)
            new = {"k": k_cache, "v": v_cache}
            call.carried["kv"] = dict(new, lens=call.cache_len + 1)
            with scope("attn.shared"):
                o = _sharded_decode(qp[:, 0], k_cache, v_cache, call.cache_len + 1,
                                    scale=scale)[:, None]
        elif kind == "*":
            if cache is not None:
                T = cache["k"].shape[2]
                pad = ((0, 0), (0, 0), (0, T - t), (0, 0))
                with scope("kv.append"):
                    new = {"k": jnp.pad(k.transpose(0, 2, 1, 3), pad)
                           .astype(cache["k"].dtype),
                           "v": jnp.pad(v.transpose(0, 2, 1, 3), pad)
                           .astype(cache["v"].dtype)}
            if call.last_at is not None:
                # the layer a prefill stops at: one query a sequence over
                # the rows it has just written, as a decode step reads them
                call.carried["kv"] = dict(new, lens=call.seq_lens)
                with scope("attn.shared"):
                    o = _sharded_decode(qp[:, 0], new["k"], new["v"], call.seq_lens,
                                        scale=scale)[:, None]
            else:
                call.carried["kv"] = {"k": k, "v": v}
                with scope("attn.shared"):
                    o = _band_attention(qp, k, v, None, scale)
        else:
            shared = self._read(call, "kv", lambda: {
                "k": jnp.zeros((b, tq, rows, lanes), cfg.dtype),
                "v": jnp.zeros((b, tq, rows, lanes), cfg.dtype)})
            with scope("attn.shared"):
                if "lens" in shared:
                    if tq != 1:
                        raise NotImplementedError(
                            "a cross-attention layer attends the cache's rows "
                            "one query a sequence")
                    o = _sharded_decode(qp[:, 0], shared["k"], shared["v"],
                                        shared["lens"], scale=scale)[:, None]
                else:
                    o = _band_attention(qp, shared["k"], shared["v"], None, scale)
        with scope("attn.diff"):
            lam0 = 0.8 - 0.6 * float(np.exp(-0.3 * cfg.mixer_depth(self.index)))
            lam = (jnp.exp(jnp.sum(lq1 * lk1)) - jnp.exp(jnp.sum(lq2 * lk2))
                   + lam0).astype(jnp.float32)
            o = o.reshape(b, tq, H // 2, 2, lanes).astype(jnp.float32)
            diff = o[:, :, :, 0] - lam * o[:, :, :, 1]
            diff = diff * jax.lax.rsqrt(
                jnp.mean(diff * diff, axis=-1, keepdims=True) + cfg.ln_eps)
            o = serving_values((1.0 - lam0) * diff * subln, cfg.dtype).astype(
                cfg.dtype).reshape(b, tq, H * hd)
        with scope("attn.out"):
            out = dense(cfg.n_embd, "o_proj", nn.initializers.normal(cfg.out_std))(o)
        return out, new

    def _mamba1(self, h, call):
        from .mamba1 import Mamba1Mixer
        cfg = self.config
        out, new, memory = Mamba1Mixer(
            d_model=cfg.n_embd, d_inner=cfg.mamba1_d_inner,
            state_size=cfg.ssm_state_size, dt_rank=cfg.mamba1_dt_rank,
            conv_kernel=cfg.conv_kernel, dtype=cfg.dtype, init_std=cfg.init_std,
            out_std=cfg.out_std, name="mamba")(
                h, cache=call.cache, seq_lens=call.seq_lens)
        call.carried["memory"] = memory
        return out, new

    def _gated_memory(self, h, call):
        """The gated memory unit: ``W_out (silu(W_in u_t) * m_t)`` with
        ``m_t`` the scan output that the last Mamba-1 layer before it handed
        on, at the same position. It keeps nothing between tokens."""
        cfg = self.config
        c = cfg.mamba1_d_inner
        w_in = self.param("in_proj", nn.initializers.normal(cfg.init_std),
                          (cfg.n_embd, c), jnp.float32)
        w_out = self.param("out_proj", nn.initializers.normal(cfg.out_std),
                           (c, cfg.n_embd), jnp.float32)
        memory = self._read(call, "memory",
                            lambda: jnp.zeros(h.shape[:2] + (c,), jnp.float32))
        with scope("gmu.gate"):
            gate = jax.nn.silu(project(h, w_in, cfg.dtype))
            gated = serving_values(gate * memory, cfg.dtype).astype(cfg.dtype)
        with scope("gmu.out"):
            return project(gated, w_out, cfg.dtype).astype(cfg.dtype), None

    def _mamba(self, h, call):
        from .mamba2 import Mamba2Mixer
        cfg = self.config
        return Mamba2Mixer(
            d_model=cfg.n_embd, num_heads=cfg.mamba_num_heads,
            head_dim=cfg.mamba_head_dim, state_size=cfg.ssm_state_size,
            n_groups=cfg.ssm_n_groups, conv_kernel=cfg.conv_kernel,
            chunk_size=cfg.ssm_chunk_size, eps=cfg.ln_eps, dtype=cfg.dtype,
            init_std=cfg.init_std, out_std=cfg.out_std, name="mamba")(
                h, cache=call.cache, seq_lens=call.seq_lens)

    def _short_conv(self, h, call):
        from .short_conv import ShortConvMixer
        cfg = self.config
        return ShortConvMixer(
            d_model=cfg.n_embd, conv_kernel=cfg.conv_kernel, dtype=cfg.dtype,
            init_std=cfg.init_std, out_std=cfg.out_std, name="conv")(
                h, cache=call.cache, seq_lens=call.seq_lens)

    def _ffn(self, h, call):
        return self._mlp(h), None

    def _experts(self, h, call):
        cfg = self.config
        seq_lens = call.seq_lens
        valid = None
        if seq_lens is not None and h.shape[1] > 1:
            with scope("moe.plan"):
                valid = jnp.arange(h.shape[1])[None, :] < seq_lens[:, None]
        shared = dict(
            d_model=cfg.n_embd, n_routed=cfg.n_routed_experts,
            top_k=cfg.experts_per_token, expert_width=cfg.moe_expert_width,
            scale=cfg.routed_scaling_factor, norm_topk=cfg.norm_topk_prob,
            experts_held=cfg.held_experts, dtype=cfg.dtype,
            init_std=cfg.init_std, out_std=cfg.out_std, name="moe")
        if cfg.moe_kind == "gated":
            from ..moe.gated_moe import GatedMoE
            moe = GatedMoE(router=cfg.moe_router, topk_eps=cfg.moe_topk_eps,
                           shared_width=cfg.moe_shared_width, **shared)
        else:
            from ..moe.latent_moe import LatentMoE
            moe = LatentMoE(shared_width=cfg.moe_shared_width,
                            latent=cfg.moe_latent_size, **shared)
        out, stats = moe(h, valid)
        self.sow("stats", "moe_counts", stats)
        return out, None


def make_layer(cfg: CausalLMConfig, i: int, **kw):
    """The module of layer ``i``: the classic layer, or the mixer its letter names."""
    kind = cfg.layer_kind(i)
    if kind == "A":
        return CausalLMLayer(cfg, is_moe=cfg.is_moe_layer(i), **kw)
    return MixerLayer(cfg, kind=kind, index=i, **kw)


def block_causal_mask(t: int, block: int):
    """(t, t) bool: key ``j`` is seen by query ``i`` iff ``j // block <= i // block``."""
    pos = np.arange(t) // block
    return pos[None, :] <= pos[:, None]


def _bias_attention(q, k, v, slopes, mask_block: int, attn_mask, scale):
    """Full-sequence causal attention, optionally with per-head alibi slopes.
    ``mask_block`` > 1 makes the mask block-causal (:func:`block_causal_mask`);
    ``attn_mask`` (t, t) bool replaces the mask altogether (XLA path);
    ``scale`` multiplies the scores on whichever path is taken.

    The alibi bias rides INSIDE the Pallas flash kernel (no (h, t, s) bias tensor in
    HBM — the reference fuses the same bias into ``softmax_kernels.cu``). Lengths
    that are not ``flash_eligible`` — the 8..128-token serving prompt buckets,
    anything not a multiple of 128 — take the XLA einsum path, with or without
    alibi: ``_block_sizes`` would hand Mosaic sub-tile blocks there."""
    from ..ops.attention.flash import flash_attention
    from ..ops.transformer.attention import flash_eligible, xla_attention
    if k.shape[2] != q.shape[2]:  # GQA prefill: broadcast kv heads to query heads
        g = q.shape[2] // k.shape[2]
        k = jnp.repeat(k, g, axis=2)
        v = jnp.repeat(v, g, axis=2)
    t = q.shape[1]
    if attn_mask is not None or (mask_block > 1 and not (
            flash_eligible(t) and t % mask_block == 0)):
        if slopes is not None:
            raise NotImplementedError("alibi with a mask that is not causal")
        if attn_mask is None:
            attn_mask = jnp.asarray(block_causal_mask(t, mask_block))
        return xla_attention(q, k, v, causal=False, mask=attn_mask[None, None],
                             softmax_scale=scale)
    if flash_eligible(t):
        return flash_attention(q, k, v, causal=True, alibi_slopes=slopes,
                               softmax_scale=scale, mask_block=mask_block)
    if slopes is None:
        return xla_attention(q, k, v, causal=True, softmax_scale=scale)
    return _alibi_attention_xla(q, k, v, slopes, scale)


def pair_queries(q):
    """Differential attention's queries for cache rows that hold a key PAIR
    side by side: ``q`` ``(..., h, d)`` -> ``(..., h, 2 d)``, an even head in
    the first ``d`` lanes, an odd one in the last, exact zeros elsewhere, so
    that each scores its own key of the pair and weighs the pair's whole
    ``2 d`` value."""
    *lead, h, d = q.shape
    q = q.reshape(*lead, h // 2, 2, 1, d)
    own = jnp.eye(2, dtype=bool).reshape(2, 2, 1)
    return jnp.where(own, q, 0).reshape(*lead, h, 2 * d)


def _band_attention(q, k, v, window, scale):
    """Whole-sequence causal attention of ``q`` (b, t, h, d) over rows ``k``,
    ``v`` (b, t, rows, d), ``h / rows`` query heads a row; ``window``: a query
    sees the ``window`` keys up to its own (None: all of them). The flash
    kernel at a ``flash_eligible`` length of whole-tile heads (under a window
    in blocks of about its size, so that what lies behind the band is
    skipped), else XLA's products under the mask."""
    from ..ops.attention.flash import flash_attention
    from ..ops.transformer.attention import flash_eligible, xla_attention
    g = q.shape[2] // k.shape[2]
    k = jnp.repeat(k, g, axis=2)
    v = jnp.repeat(v, g, axis=2)
    t = q.shape[1]
    if flash_eligible(t) and q.shape[-1] % 128 == 0:
        if window is None:
            return flash_attention(q, k, v, causal=True, softmax_scale=scale)
        block = min(1024, max(128, 1 << (int(window).bit_length() - 1)))
        return flash_attention(q, k, v, causal=True, softmax_scale=scale,
                               block_q=block, block_k=block, window=int(window))
    if window is None:
        return xla_attention(q, k, v, causal=True, softmax_scale=scale)
    i, j = np.arange(t)[:, None], np.arange(t)[None, :]
    band = jnp.asarray((j <= i) & (j > i - window))
    return xla_attention(q, k, v, causal=False, mask=band[None, None],
                         softmax_scale=scale)


def _ring_rows(x, seq_lens, window: int):
    """The ring a right-padded prompt leaves: of ``x`` (b, t, rows, d) each
    sequence's last ``min(len, window)`` valid positions, position ``p`` at
    ring row ``p mod window``, as ``(b, rows, window, d)`` (rows no position
    of the sequence maps to are zeros, and the length masks them)."""
    t = x.shape[1]
    row = jnp.arange(window)[None]                              # (1, window)
    last = seq_lens[:, None] - 1 - row
    pos = row + (last // window) * window
    got = jnp.take_along_axis(x, jnp.clip(pos, 0, t - 1)[:, :, None, None], axis=1)
    return jnp.where((last >= 0)[:, :, None, None], got, 0).transpose(0, 2, 1, 3)


def _latent_attention_core(q, k, v, attn_mask, scale):
    """Whole-sequence causal attention of a latent layer's EXPANDED heads:
    queries and keys ``(b, t, h, nope + rope)``, values ``(b, t, h, v)``. At a
    ``flash_eligible`` length, values of whole 128-lane tiles, the flash kernel,
    which takes queries and keys wider than its values where both are: 192-lane
    queries and keys are padded to 256 with zero lanes (a zero lane adds
    nothing to a score). Else, and under ``attn_mask``, XLA's products."""
    from ..ops.attention.flash import flash_attention
    from ..ops.transformer.attention import flash_eligible, xla_attention
    if attn_mask is not None:
        return xla_attention(q, k, v, causal=False, mask=attn_mask[None, None],
                             softmax_scale=scale)
    if not flash_eligible(q.shape[1]) or v.shape[-1] % 128:
        return xla_attention(q, k, v, causal=True, softmax_scale=scale)
    pad = ((0, 0),) * 3 + ((0, -q.shape[-1] % 128),)
    return flash_attention(jnp.pad(q, pad), jnp.pad(k, pad), v, causal=True,
                           softmax_scale=scale)


def _alibi_attention_xla(q, k, v, slopes, scale=None):
    """XLA reference path for alibi attention (short/unaligned sequences; also the
    numerical reference the flash-alibi kernel is tested against, which is why
    it has the kernel's own default for ``scale``; the model always hands its
    ``attn_scale``)."""
    if scale is None:
        scale = q.shape[-1] ** -0.5
    t, s = q.shape[1], k.shape[1]
    rows = jnp.arange(t)[:, None]
    cols = jnp.arange(s)[None, :]
    bias = slopes[:, None, None] * (cols - rows)[None].astype(jnp.float32)
    logits = jnp.einsum("bthd,bshd->bhts", q, k).astype(jnp.float32) * scale
    logits = logits + bias[None]
    causal = jnp.tril(jnp.ones((t, s), dtype=bool))
    logits = jnp.where(causal[None, None], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    return jnp.einsum("bhts,bshd->bthd", probs, v)


def _prefix_attention_xla(q, k_cache, v_cache, offset, slopes, scale):
    """Suffix-prefill attention: queries at global positions ``offset + i``
    over the full KV cache (restored prefix rows + just-written suffix rows),
    masked ``key_pos <= query_pos`` — the t×T generalisation of
    ``decode_attention_xla``'s 1×T shape. fp32 softmax like every other
    XLA attention path here; rows beyond ``offset + t - 1`` (stale slab pad /
    unwritten) are masked out by construction.

    q: (b, t, h, d); k_cache/v_cache: (b, hk / r, T, r * d), the queries
    packed to their rows (``pack_queries``); offset: (b,)."""
    b, t, h, d = q.shape
    hk, T = k_cache.shape[1], k_cache.shape[2]
    r = k_cache.shape[3] // d
    g = h // hk
    q5 = pack_queries(q, r, hk).reshape(b, t, hk, g, r * d).astype(jnp.float32)
    s = jnp.einsum("btkgd,bkTd->bkgtT", q5,
                   k_cache.astype(jnp.float32)) * scale
    q_pos = offset[:, None] + jnp.arange(t)[None]                  # (b, t)
    k_pos = jnp.arange(T)
    if slopes is not None:
        rel = (k_pos[None, None, :] - q_pos[:, :, None]).astype(jnp.float32)
        s = s + slopes.reshape(1, hk, g, 1, 1) * rel[:, None, None]
    mask = k_pos[None, None, :] <= q_pos[:, :, None]               # (b, t, T)
    s = jnp.where(mask[:, None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgtT,bkTd->btkgd", p, v_cache.astype(jnp.float32))
    return unpack_outputs(o.reshape(b, t, h, r * d), r, hk).astype(q.dtype)


def _block_decode(q, k_cache, v_cache, lens, block: int, scale=None):
    """Attention of whole blocks of queries against the cache: ``q`` (b, t, h,
    d), ``t`` a multiple of ``block``; every query of a sequence's ``j``-th
    block sees the same rows ``[0, lens + (j + 1) * block)`` (the committed
    blocks, the blocks before its own and its own), so the ``t`` queries of a
    key head's ``g`` query heads are ``t x g`` rows of ONE group in
    ``decode_attention``'s ``(b, h_kv, g, d)`` operand, a block's rows in a
    row: the decode kernel, with no mask of its own, given a length a block
    (one block: the one-length kernel)."""
    b, t, h, d = q.shape
    hk = k_cache.shape[1] * (k_cache.shape[3] // d)    # KV heads, r a cache row
    g = h // hk
    with scope("attn.heads"):
        rows = q.reshape(b, t, hk, g, d).transpose(0, 2, 1, 3, 4).reshape(
            b, hk * t * g, d)
    with scope("attn.core"):
        ends = lens[:, None] + block * jnp.arange(1, t // block + 1)[None]   # (b, blocks)
        o = _sharded_decode(rows, k_cache, v_cache, ends, scale=scale)
    with scope("attn.heads"):
        return o.reshape(b, hk, t, g, d).transpose(0, 2, 1, 3, 4).reshape(b, t, h, d)


def _cache_update(cache, new, cache_len):
    """cache: (b, hk, T, d) rows (``ops/paged_attention.heads_per_row``); new:
    (b, hk, t, d), ``t`` = 1 for a decode step and the block for a block step;
    write at per-sequence position.

    One ``dynamic_update_slice`` a sequence with the sequence's index static:
    each is one in-place write on a loop's carry, a position at or past ``T``
    clamped to the last row. The batched forms cost more on the chip: a
    vmapped update is a scatter that the TPU compiler expands into a serial
    loop over the sequences (seven small ops an iteration, twice a layer a
    step), and a native scatter gives the cache a layout that a Mosaic
    ``decode_attention`` cannot read, so the whole cache is copied back every
    step (PERF.md, PR 30)."""
    new = new.astype(cache.dtype)
    for i in range(cache.shape[0]):
        cache = jax.lax.dynamic_update_slice(
            cache, new[i:i + 1], (i, 0, cache_len[i], 0))
    return cache


def _sharded_decode(q, k_cache, v_cache, lens, alibi=None, *, scale):
    """One-token attention over the cache, under ``shard_map`` over the batch
    and tensor axes where a mesh has them (pallas is opaque to SPMD, and a
    shard's own longest length is all the live-rows form need walk). ALiBi
    takes the served XLA form with the heads' slopes, which travel as a
    per-head input sharded over the tensor axis, so that each shard sees its
    own heads' slopes; everything else the decode kernel's entry."""
    from ..parallel.mesh import AXIS_TENSOR, BATCH_AXES, get_global_mesh
    b, h, d = q.shape
    mesh = get_global_mesh()

    def attend(q, k_cache, v_cache, lens, slopes=None):
        if slopes is None:
            return decode_attention(q, k_cache, v_cache, lens, scale)
        return decode_attention_live(q, k_cache, v_cache, lens, scale, slopes)

    operands = (q, k_cache, v_cache, lens) + (
        () if alibi is None else (jnp.asarray(alibi),))
    if mesh is not None:
        batch_axes = tuple(ax for ax in BATCH_AXES if mesh.size(ax) > 1)
        bsz = int(np.prod([mesh.size(ax) for ax in batch_axes])) if batch_axes else 1
        tp = mesh.size(AXIS_TENSOR)
        use_tp = tp > 1 and h % tp == 0 and k_cache.shape[1] % tp == 0
        manual = set(batch_axes) | ({AXIS_TENSOR} if use_tp else set())
        if manual and b % max(bsz, 1) == 0:
            tpax = AXIS_TENSOR if use_tp else None
            qspec = P(batch_axes or None, tpax, None)
            cspec = P(batch_axes or None, tpax, None, None)
            specs = (qspec, cspec, cspec, P(batch_axes or None), P(tpax))
            return shard_map(attend, mesh=mesh.mesh, axis_names=manual,
                             in_specs=specs[:len(operands)], out_specs=qspec,
                             check_vma=False)(*operands)
    return attend(*operands)


class CausalLM(nn.Module):
    config: CausalLMConfig

    @nn.compact
    def __call__(self, input_ids, positions=None, caches=None, cache_lens=None,
                 logits_positions=None, prefix_fill=False, seq_lens=None,
                 block_step=False, attn_mask=None):
        """``seq_lens`` (b,): the real lengths of right-padded rows of a
        prefill, for layers whose state a padded token would advance, and of
        a block step, whose padding the expert layers leave out.

        ``block_step`` (static; a model with ``gen_block_length``): the ``t``
        inputs are one block a sequence or several in a row, run against the
        dense caches at offset ``cache_lens`` (``CausalLMLayer._attention``),
        the first ``seq_lens`` of them real. ``attn_mask``
        (t, t) bool: the whole mask of a forward without caches.

        ``logits_positions`` (b,) or (b, n): compute the LM head ONLY at these sequence
        positions (serving prefill needs just each prompt's last valid token — for a
        250k vocab at t=512 this removes ~99.8% of the head matmul and the (b, t, V)
        fp32 logits buffer; reference parity: ds_inference reads final-token logits).
        Returns (b, 1, V) logits in that mode.

        ``prefix_fill`` (static): suffix prefill at cache offset ``cache_lens``
        — the caches already hold a restored prompt-prefix KV slab; the caller
        must pass ``positions = cache_lens + arange(t)`` so rotary/learned
        embeddings see global positions."""
        cfg = self.config
        b, t = input_ids.shape
        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(t)[None], (b, t))
        wte = self.param("wte", nn.initializers.normal(cfg.init_std),
                         (cfg.vocab_size, cfg.n_embd), jnp.float32)
        with scope("embed"):
            x = embed_rows(cfg, wte, input_ids)
            if cfg.pos_emb == "learned":
                wpe = self.param("wpe", nn.initializers.normal(cfg.init_std),
                                 (cfg.max_seq_len, cfg.n_embd), jnp.float32)
                x = x + jnp.take(wpe, positions, axis=0).astype(cfg.dtype)
            if cfg.embed_layernorm:
                x = _norm(cfg, "ln_embed")(x).astype(cfg.dtype)

        # what a later layer may read of an earlier one, carried beside ``x``
        # (:attr:`LayerKind.writes`): the memory of the last Mamba-1 layer,
        # the cache of the last layer that keeps keys and values
        carried = {}
        # a prefill (a cache to fill, logits at one position a sequence)
        # stops at ``stop``: that layer writes its cache for every position,
        # and from its output on only the position the logits are read at
        # runs, of ``x`` and of the memory
        stop = None
        if (caches is not None and t > 1 and seq_lens is not None
                and logits_positions is not None and logits_positions.ndim == 1
                and not prefix_fill and not block_step):
            stop = cfg.prefill_stop
        new_caches = []
        for i in range(cfg.n_layer):
            layer_cache = None if caches is None else caches[i]
            # only a layer of one mixer is told the rows' real lengths
            extra = ({} if cfg.layer_kind(i) == "A"
                     else {"seq_lens": seq_lens, "carried": carried})
            if block_step or attn_mask is not None:
                extra.update(block_step=block_step, attn_mask=attn_mask)
            if i == stop:
                extra.update(last_at=logits_positions)
            x, new_kv = make_layer(cfg, i, name=f"layers_{i}")(
                x, positions, cache=layer_cache, cache_len=cache_lens,
                prefix_fill=prefix_fill, **extra)
            if i == stop and "memory" in carried:
                with scope("residual"):
                    carried["memory"] = _take_rows(carried["memory"],
                                                   logits_positions)
            new_caches.append(new_kv)

        with scope("head"):
            x = _norm(cfg, "ln_f")(x)
            # (a prefill that stopped left ``x`` the one position already)
            if stop is None and logits_positions is not None \
                    and logits_positions.ndim == 2:
                x = jnp.take_along_axis(x, logits_positions[..., None], axis=1)
            elif stop is None and logits_positions is not None:
                x = x[jnp.arange(b), logits_positions][:, None]    # (b, 1, d)
            if cfg.tie_word_embeddings:
                logits = x.astype(jnp.float32) @ wte.T
            else:
                logits = nn.Dense(cfg.vocab_size, use_bias=cfg.lm_head_bias,
                                  dtype=jnp.float32,
                                  kernel_init=nn.initializers.normal(cfg.init_std),
                                  name="lm_head")(x.astype(jnp.float32))
            logits = scale_logits(cfg, logits)
        if caches is None:
            return logits
        return logits, new_caches


# ----------------------------------------------------------- segmented (offload_param)
def _norm_mod(cfg: CausalLMConfig):
    """Top-level (unnamed) norm module for standalone segment apply."""
    if cfg.layernorm == "rmsnorm":
        return nn.RMSNorm(epsilon=cfg.ln_eps, dtype=jnp.float32)
    return nn.LayerNorm(epsilon=cfg.ln_eps, dtype=jnp.float32)


def causal_lm_segments(cfg: CausalLMConfig, layers_per_group: int = 2):
    """Decompose :class:`CausalLM` into host-streamable :class:`~.base.Segment` slices.

    The segment parameter trees use the SAME top-level keys as the monolithic
    ``CausalLM.init`` tree (``wte``/``wpe``/``ln_embed``/``layers_i``/``ln_f``/``lm_head``)
    so checkpoints interchange between the streamed and the resident engines. Tied
    embeddings put ``wte`` in the last segment's ``param_keys`` (shared, not re-initialised);
    its gradient accumulates contributions from both ends, exactly like the monolithic
    backward.

    Reference: sub_group partitioning of ZeRO-3 params
    (``runtime/zero/stage3.py`` ``sub_group_size``,
    ``partitioned_param_coordinator.py:239`` fetch order).
    """
    from .base import Segment
    from .gpt2 import cross_entropy_loss
    segs = []

    def _positions(ids):
        b, t = ids.shape
        return jnp.broadcast_to(jnp.arange(t)[None], (b, t))

    # ---- embed -----------------------------------------------------------------
    embed_keys = ["wte"]
    if cfg.pos_emb == "learned":
        embed_keys.append("wpe")
    if cfg.embed_layernorm:
        embed_keys.append("ln_embed")

    def embed_init(rng):
        init = nn.initializers.normal(cfg.init_std)
        p = {"wte": init(jax.random.fold_in(rng, 0),
                         (cfg.vocab_size, cfg.n_embd), jnp.float32)}
        if cfg.pos_emb == "learned":
            p["wpe"] = init(jax.random.fold_in(rng, 1),
                            (cfg.max_seq_len, cfg.n_embd), jnp.float32)
        if cfg.embed_layernorm:
            p["ln_embed"] = _norm_mod(cfg).init(
                jax.random.fold_in(rng, 2),
                jnp.zeros((1, 1, cfg.n_embd), jnp.float32))["params"]
        return tuple(p[k] for k in embed_keys)

    def embed_apply(p, batch, rng):
        p = dict(zip(embed_keys, p))
        ids = batch["input_ids"]
        with scope("embed"):
            x = embed_rows(cfg, p["wte"], ids)
            if cfg.pos_emb == "learned":
                x = x + jnp.take(p["wpe"], _positions(ids), axis=0).astype(cfg.dtype)
            if cfg.embed_layernorm:
                x = _norm_mod(cfg).apply({"params": p["ln_embed"]}, x).astype(cfg.dtype)
            return x

    segs.append(Segment(name="embed", kind="first",
                        param_keys=tuple(embed_keys), init_keys=tuple(embed_keys),
                        init_fn=embed_init, apply_fn=embed_apply))

    # ---- layer groups ----------------------------------------------------------
    # One shared apply/init FUNCTION OBJECT per (is_moe flags) signature: segments with
    # the same layer composition then present jax.jit with the same callable AND the
    # same arg structure, so a 48-layer model compiles its interior group once, not 24×.
    _group_fns = {}

    def _fns_for(flags, first):
        # ``flags``: per layer (kind, is_moe); a layer's module depends on
        # nothing else, so ``first`` (a layer index of that signature) builds it
        if flags not in _group_fns:
            def group_init(rng, flags=flags):
                x = jnp.zeros((1, 4, cfg.n_embd), cfg.dtype)
                pos = jnp.zeros((1, 4), jnp.int32)
                return tuple(
                    make_layer(cfg, first + j).init(
                        {"params": jax.random.fold_in(rng, j)}, x, pos)["params"]
                    for j in range(len(flags)))

            def group_apply(p, x, batch, rng, flags=flags):
                pos = _positions(batch["input_ids"])
                for j, layer_params in enumerate(p):
                    x, _ = make_layer(cfg, first + j).apply(
                        {"params": layer_params}, x, pos)
                return x

            _group_fns[flags] = (group_init, group_apply)
        return _group_fns[flags]

    for lo in range(0, cfg.n_layer, layers_per_group):
        hi = min(lo + layers_per_group, cfg.n_layer)
        keys = tuple(f"layers_{i}" for i in range(lo, hi))
        # (differential attention's lambda_init reads the layer's depth)
        flags = tuple((cfg.layer_kind(i), cfg.is_moe_layer(i))
                      + ((cfg.mixer_depth(i),) if cfg.diff_attention else ())
                      for i in range(lo, hi))
        group_init, group_apply = _fns_for(flags, lo)
        segs.append(Segment(name=f"layers[{lo}:{hi}]", kind="mid", param_keys=keys,
                            init_keys=keys, init_fn=group_init,
                            apply_fn=group_apply))

    # ---- final norm + head + loss ----------------------------------------------
    final_init_keys = ["ln_f"] if cfg.tie_word_embeddings else ["ln_f", "lm_head"]
    final_param_keys = ["ln_f", "wte"] if cfg.tie_word_embeddings \
        else ["ln_f", "lm_head"]

    def final_init(rng):
        p = {"ln_f": _norm_mod(cfg).init(
            jax.random.fold_in(rng, 0),
            jnp.zeros((1, 1, cfg.n_embd), jnp.float32))["params"]}
        if not cfg.tie_word_embeddings:
            head = {"kernel": nn.initializers.normal(cfg.init_std)(
                jax.random.fold_in(rng, 1),
                (cfg.n_embd, cfg.vocab_size), jnp.float32)}
            if cfg.lm_head_bias:
                head["bias"] = jnp.zeros((cfg.vocab_size,), jnp.float32)
            p["lm_head"] = head
        return tuple(p[k] for k in final_init_keys)

    def final_apply(p, x, batch, rng):
        p = dict(zip(final_param_keys, p))
        with scope("head"):
            x = _norm_mod(cfg).apply({"params": p["ln_f"]}, x)
            if cfg.tie_word_embeddings:
                logits = x.astype(jnp.float32) @ p["wte"].T
            else:
                logits = x.astype(jnp.float32) @ p["lm_head"]["kernel"]
                if cfg.lm_head_bias:
                    logits = logits + p["lm_head"]["bias"]
            logits = scale_logits(cfg, logits)
        ids = batch["input_ids"]
        with scope("loss"):
            labels = batch.get("labels")
            if labels is None:
                labels = jnp.concatenate(
                    [ids[:, 1:],
                     jnp.full((ids.shape[0], 1), -100, dtype=ids.dtype)], axis=1)
            return cross_entropy_loss(logits, labels)

    segs.append(Segment(name="final", kind="last",
                        param_keys=tuple(final_param_keys),
                        init_keys=tuple(final_init_keys),
                        init_fn=final_init, apply_fn=final_apply))
    return segs


# ----------------------------------------------------------------------- bundles
def causal_lm_model(cfg: CausalLMConfig, sample_seq_len: Optional[int] = None,
                    layers_per_group: int = 2) -> Model:
    """Training/scoring bundle (loss over shifted labels). ``layers_per_group`` sets the
    granularity of the offload_param streaming decomposition (see
    :func:`causal_lm_segments`)."""
    from .gpt2 import cross_entropy_loss
    module = CausalLM(cfg)
    t = sample_seq_len or min(cfg.max_seq_len, 1024)

    def init_fn(rng):
        sample = jnp.zeros((1, t), dtype=jnp.int32)
        return module.init({"params": rng}, sample)["params"]

    def loss_fn(params, batch, rng):
        ids = batch["input_ids"]
        logits = module.apply({"params": params}, ids)
        with scope("loss"):
            labels = batch.get("labels")
            if labels is None:
                labels = jnp.concatenate(
                    [ids[:, 1:],
                     jnp.full((ids.shape[0], 1), -100, dtype=ids.dtype)], axis=1)
            return cross_entropy_loss(logits, labels)

    def apply_fn(params, batch, rng=None):
        ids = batch["input_ids"] if isinstance(batch, dict) else batch
        return module.apply({"params": params}, ids)

    return Model(loss_fn=loss_fn, init_fn=init_fn, apply_fn=apply_fn,
                 param_specs=None, name=cfg.name,
                 flops_per_sample=6.0 * cfg.num_params() * t,
                 segments=causal_lm_segments(cfg, layers_per_group))


def init_cache(cfg: CausalLMConfig, batch_size: int, max_len: Optional[int] = None,
               dtype=None, kv_shape=None):
    """One cache a layer, typed by what the layer's kind keeps
    (:data:`LAYER_KINDS`): fixed-capacity head-major keys and values in rows
    of ``r`` heads, ``(batch_size, kv_heads / r, T, r * head_dim)``
    (``ops/paged_attention.heads_per_row`` says ``r``; ``kv_shape`` where the
    caller lays them out otherwise: the paged pool's pages), the kind's
    per-slot state for ``batch_size`` sequences (``{"conv", "ssm"}``
    state-space, ``{"conv"}`` short convolution, ``{"k", "v"}`` a windowed
    layer's ring), an empty dict for a layer
    that keeps nothing; a latent layer's one row a token ``{"k": (batch_size,
    1, T, lanes)}`` (``ops/paged_attention.latent_row_lanes``; under
    ``kv_shape`` its first and third extents)."""
    T = max_len or cfg.max_seq_len
    dtype = dtype or cfg.dtype
    r = cfg.cache_row_heads
    shape = kv_shape or (batch_size, cfg.kv_heads // r, T, r * cfg.head_dim)
    out = []
    for kind in cfg.layer_kinds:
        entry = LAYER_KINDS[kind]
        if entry.keeps == "kv":
            out.append({"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)})
        elif entry.keeps == "latent":
            # one row a token for all heads, where a "kv" layer has hk rows
            out.append({"k": jnp.zeros(
                (shape[0], 1, shape[2], latent_row_lanes(cfg.latent_row_width)),
                dtype)})
        else:
            out.append(entry.state(cfg, batch_size, dtype) if entry.state else {})
    return out


def causal_lm_param_specs(params, tensor_axis: str = "tensor") -> Any:
    """Megatron TP rules for :class:`CausalLM` params (the sharding the reference's
    ``ReplaceWithTensorSlicing`` performs on qkv/mlp weights, ``module_inject/replace_module.py:25``)."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(params)

    def spec_for(path_str: str, ndim: int):
        col = ("q_proj", "k_proj", "v_proj", "fc_in", "gate_proj", "up_proj")
        row = ("o_proj", "fc_out")
        if "/moe_experts/" in path_str:
            # expert dim over the expert axis (reference EP serving: experts split across
            # ranks at load, ``moe_inference.py``)
            from ..parallel.mesh import AXIS_EXPERT
            return P(AXIS_EXPERT, *([None] * (ndim - 1)))
        if path_str.endswith("moe_gate"):
            return P(*([None] * ndim))
        if any(f"/{n}/" in path_str or path_str.endswith(f"{n}/kernel") for n in col):
            if path_str.endswith("kernel"):
                return P(None, tensor_axis)
            if path_str.endswith("bias"):
                return P(tensor_axis)
        if any(f"/{n}/" in path_str for n in row):
            if path_str.endswith("kernel"):
                return P(tensor_axis, None)
            return P(*([None] * ndim)) if ndim else P()
        if path_str.endswith("wte") or path_str.endswith("lm_head/kernel"):
            return P(tensor_axis, None) if path_str.endswith("wte") else P(None, tensor_axis)
        return P(*([None] * ndim)) if ndim else P()

    specs = []
    for path, leaf in flat:
        path_str = "/".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in path)
        specs.append(spec_for(path_str, getattr(leaf, "ndim", 0)))
    return jax.tree_util.tree_unflatten(treedef, specs)
