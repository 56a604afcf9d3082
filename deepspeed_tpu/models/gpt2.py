"""GPT-2 family — flagship causal-LM model.

TPU-first re-design of the model class the reference optimises (Megatron GPT-2 is DeepSpeed's
canonical workload; see reference ``tests/model/Megatron_GPT2`` and the inference containers
``module_inject/containers/gpt2.py``). Design choices for XLA/TPU:

- ``nn.scan`` over a single Block definition: ONE traced layer body and ONE parameter
  stack (every leaf of ``h`` stacked on axis 0, the ``layers`` axis) regardless of depth,
  which keeps tracing flat and gives checkpoints, ZeRO and pipeline stages one layout.
  The COMPILED body is one only where a mesh axis shards a layer's parameters or its
  carry: there the loop stays a ``while``, which bounds how many gathered layers are
  alive at once. Where the parameters are whole on the device the loop is lowered
  unrolled (``unroll_layer_loop``): no ``while``, so no stack of saved activations.
- optional ``jax.checkpoint`` (remat) per layer — the analogue of the reference's activation
  checkpointing (``runtime/activation_checkpointing/checkpointing.py``).
- bf16 compute / fp32 params via the engine's dtype policy; softmax and layernorm run fp32.
- attention is pluggable (``ops/transformer/attention.py``): xla | flash (Pallas) | ring
  (sequence-parallel Pallas).
- weight-tied LM head (wte used for output projection), GPT-2 initialisation scheme.
"""

import dataclasses
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..observability import get_tracer, scope
from ..ops.attention.flash import (FLASH_LSE_NAME, FLASH_OUT_NAME, FLASH_QKV_NAME,
                                   flash_attention_qkv)
from ..ops.transformer.attention import flash_reads_fused_qkv, get_attention_impl
from .base import Model


@dataclasses.dataclass
class GPT2Config:
    vocab_size: int = 50257
    n_positions: int = 1024
    n_embd: int = 768
    n_layer: int = 12
    n_head: int = 12
    dropout: float = 0.0
    dtype: Any = jnp.bfloat16          # compute dtype
    remat: bool = False
    # full: keep a layer's input, recompute the rest. dots: keep the matmul outputs too,
    # and the flash kernel's output and log-sum-exp, which no matmul gives back, so no
    # kernel runs twice; the two cost 2*b*t*n_embd + 4*b*n_head*t bytes a layer
    remat_policy: str = "full"
    scan_layers: bool = True
    attention_impl: str = "auto"       # flash kernel on TPU, xla attention elsewhere
    init_std: float = 0.02
    # Separate q/k/v projections instead of the fused c_attn. Required for in-stage
    # tensor parallelism: separate (d, d) kernels shard their last dim into whole head
    # groups, so the SAME global parameter layout is exact at every tp degree (a fused
    # (d, 3d) kernel sharded contiguously would mix q/k/v columns per shard, making the
    # model's meaning depend on tp — a silent checkpoint-portability hazard).
    split_qkv: bool = False
    # >0: compute the training loss with the chunked-vocab CE (online logsumexp
    # over vocab chunks of this size, runtime/zero/tiling.py) instead of
    # materialising (b, t, V) logits — the long-sequence memory knob (a 32k×50k
    # logits buffer alone is 6.6 GB fp32)
    vocab_chunk: int = 0

    @property
    def head_dim(self) -> int:
        return self.n_embd // self.n_head

    def flops_per_token(self) -> float:
        # 6ND training-flops rule + attention quadratic term
        n = self.num_params()
        return 6.0 * n + 12.0 * self.n_layer * self.n_embd * self.n_positions

    def num_params(self) -> int:
        d, L, v, t = self.n_embd, self.n_layer, self.vocab_size, self.n_positions
        return v * d + t * d + L * (12 * d * d + 13 * d) + 2 * d


# Preset sizes used by BASELINE configs (125M..13B follow GPT-3 table).
GPT2_PRESETS = {
    "gpt2-125m": dict(n_embd=768, n_layer=12, n_head=12),
    "gpt2-350m": dict(n_embd=1024, n_layer=24, n_head=16),
    "gpt2-760m": dict(n_embd=1536, n_layer=24, n_head=16),
    "gpt2-1.3b": dict(n_embd=2048, n_layer=24, n_head=16),
    "gpt2-2.7b": dict(n_embd=2560, n_layer=32, n_head=32),
    "gpt2-6.7b": dict(n_embd=4096, n_layer=32, n_head=32),
    "gpt2-13b": dict(n_embd=5120, n_layer=40, n_head=40),
}


def gpt2_config(preset: str, **overrides) -> GPT2Config:
    kw = dict(GPT2_PRESETS[preset])
    kw.update(overrides)
    return GPT2Config(**kw)


class Block(nn.Module):
    config: GPT2Config

    @nn.compact
    def __call__(self, x, deterministic: bool = True):
        cfg = self.config
        attn = get_attention_impl(cfg.attention_impl)
        with scope("norm"):
            h = nn.LayerNorm(dtype=jnp.float32, name="ln_1")(x).astype(cfg.dtype)
        drop = 0.0 if deterministic else cfg.dropout
        b, t, _ = h.shape
        fused = not cfg.split_qkv and flash_reads_fused_qkv(
            cfg.attention_impl, t, cfg.n_head, cfg.head_dim, drop)
        with scope("attn.qkv"):
            if cfg.split_qkv:
                q = nn.Dense(cfg.n_embd, dtype=cfg.dtype, name="q_attn",
                             kernel_init=nn.initializers.normal(cfg.init_std))(h)
                k = nn.Dense(cfg.n_embd, dtype=cfg.dtype, name="k_attn",
                             kernel_init=nn.initializers.normal(cfg.init_std))(h)
                v = nn.Dense(cfg.n_embd, dtype=cfg.dtype, name="v_attn",
                             kernel_init=nn.initializers.normal(cfg.init_std))(h)
            else:
                qkv = nn.Dense(3 * cfg.n_embd, dtype=cfg.dtype, name="c_attn",
                               kernel_init=nn.initializers.normal(cfg.init_std))(h)
                if not fused:
                    q, k, v = jnp.split(qkv, 3, axis=-1)
        if fused:
            # the kernels read q, k, v where c_attn wrote them: no split, no copy
            with scope("attn.core"):
                o = flash_attention_qkv(qkv, cfg.n_head, causal=True)
        else:
            with scope("attn.heads"):
                q = q.reshape(b, t, cfg.n_head, cfg.head_dim)
                k = k.reshape(b, t, cfg.n_head, cfg.head_dim)
                v = v.reshape(b, t, cfg.n_head, cfg.head_dim)
            drop_rng = None if drop == 0.0 else self.make_rng("dropout")
            with scope("attn.core"):
                o = attn(q, k, v, causal=True, dropout_rate=drop, dropout_rng=drop_rng)
            with scope("attn.heads"):
                o = o.reshape(b, t, cfg.n_embd)
        # scaled init on residual-writing projections (GPT-2 scheme)
        proj_init = nn.initializers.normal(cfg.init_std / (2 * cfg.n_layer) ** 0.5)
        with scope("attn.out"):
            o = nn.Dense(cfg.n_embd, dtype=cfg.dtype, name="c_proj",
                         kernel_init=proj_init)(o)
        with scope("residual"):
            o = nn.Dropout(cfg.dropout, deterministic=deterministic)(o)
            x = x + o

        with scope("norm"):
            h = nn.LayerNorm(dtype=jnp.float32, name="ln_2")(x).astype(cfg.dtype)
        with scope("mlp.up"):
            h = nn.Dense(4 * cfg.n_embd, dtype=cfg.dtype, name="c_fc",
                         kernel_init=nn.initializers.normal(cfg.init_std))(h)
        with scope("mlp.act"):
            h = nn.gelu(h, approximate=True)
        with scope("mlp.down"):
            h = nn.Dense(cfg.n_embd, dtype=cfg.dtype, name="mlp_c_proj",
                         kernel_init=proj_init)(h)
        with scope("residual"):
            h = nn.Dropout(cfg.dropout, deterministic=deterministic)(h)
            return x + h


# ------------------------------------------------------- manual tensor parallelism
def _manual_layer_norm(p, x, eps: float = 1e-6):
    """fp32 layernorm matching ``nn.LayerNorm(dtype=jnp.float32)`` numerics
    (flax ``_compute_stats``: var = E[x²] − E[x]², clamped at 0)."""
    x32 = x.astype(jnp.float32)
    mean = jnp.mean(x32, axis=-1, keepdims=True)
    mean2 = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    var = jnp.maximum(0.0, mean2 - jnp.square(mean))
    mul = jax.lax.rsqrt(var + eps) * p["scale"].astype(jnp.float32)
    return (x32 - mean) * mul + p["bias"].astype(jnp.float32)


def _tp_conjugate_ops(axis: str):
    """Megatron's f/g conjugate operators (megatron/mpu ``copy_to_model_parallel`` /
    ``reduce_from_model_parallel``), defined via custom_vjp so the backward
    collectives are EXPLICIT: under ``shard_map(check_vma=False)`` the raw ``psum``
    transposes to another psum, which double-counts replicated cotangents.

    - ``f``: identity forward, psum backward — enters a column-parallel region
      (the replicated input's cotangent sums each shard's contribution);
    - ``g``: psum forward, identity backward — exits a row-parallel region
      (the summed output's cotangent is already replicated).
    """
    @jax.custom_vjp
    def f(x):
        return x

    f.defvjp(lambda x: (x, None), lambda _, ct: (jax.lax.psum(ct, axis),))

    @jax.custom_vjp
    def g(x):
        return jax.lax.psum(x, axis)

    g.defvjp(lambda x: (jax.lax.psum(x, axis), None), lambda _, ct: (ct,))
    return f, g


def block_tp_apply(cfg: GPT2Config, tp: int, axis: str,
                   sp_axis: Optional[str] = None):
    """Megatron-style manual-collective Block forward for use INSIDE a ``shard_map``
    whose manual axes include ``axis`` (reference 3D parallelism: TP inside pipeline
    stages, ``runtime/pipe/topology.py:243``; column/row classification as in
    ``module_inject/replace_module.py:25``).

    The caller passes the LOCAL parameter shard: q/k/v + fc kernels column-sharded
    (last dim, whole head groups), o/mlp projections row-sharded (first dim); the
    f/g conjugate pair brackets each col→row sandwich — the two collectives per
    block that Megatron inserts. Exactly equal to the replicated ``Block``
    (``split_qkv=True``, dropout off) at any tp degree.

    With ``sp_axis`` the activations additionally arrive SEQUENCE-SHARDED
    (pipe×tensor×seq 4D): dense/LN math is per-token so only the attention
    changes — local heads attend over K/V all-gathered along the seq axis
    (grouped collectives; see ``allgather_attention_local``).

    Returns ``fn(params_local, x, rng) -> y``.
    """
    if not (cfg.split_qkv):
        raise AssertionError("tensor-parallel Block needs split_qkv=True (see GPT2Config)")
    if not (cfg.n_head % tp == 0):
        raise AssertionError((cfg.n_head, tp))
    if not (cfg.dropout == 0.0):
        raise AssertionError("TP stage_fn does not implement attention dropout")
    h_local = cfg.n_head // tp
    dt = cfg.dtype
    f_op, g_op = _tp_conjugate_ops(axis)

    def dense(p, x):
        return x @ p["kernel"].astype(dt) + p["bias"].astype(dt)

    # honor cfg.attention_impl like the replicated Block does, with the manual-region
    # constraint that only impls with a local (non-shard_map) form are usable
    impl = cfg.attention_impl
    if callable(impl) or impl in ("ring", "ulysses"):
        raise NotImplementedError(
            f"attention_impl={impl!r} has no manual-TP form inside the 1F1B "
            "shard_map — use 'auto', 'xla', or 'flash' for TP pipeline bodies")

    def attention(q, k, v):
        if sp_axis is not None:
            from ..ops.attention.ring import allgather_attention_local
            return allgather_attention_local(q, k, v, causal=True,
                                             axis_name=sp_axis)
        from ..ops.transformer.attention import resolves_to_flash, xla_attention
        if resolves_to_flash(impl, q.shape[1]):
            from ..ops.attention.flash import flash_attention_local
            return flash_attention_local(q, k, v, causal=True)
        return xla_attention(q, k, v, causal=True)

    def apply(p, x, rng=None):
        b, t, _ = x.shape
        h = f_op(_manual_layer_norm(p["ln_1"], x).astype(dt))
        q = dense(p["q_attn"], h).reshape(b, t, h_local, cfg.head_dim)
        k = dense(p["k_attn"], h).reshape(b, t, h_local, cfg.head_dim)
        v = dense(p["v_attn"], h).reshape(b, t, h_local, cfg.head_dim)
        o = attention(q, k, v).reshape(b, t, h_local * cfg.head_dim)
        # row-parallel projection: local partial matmul, g = psum-fwd/identity-bwd;
        # bias is added once, after the reduction
        o = g_op(o @ p["c_proj"]["kernel"].astype(dt)) + p["c_proj"]["bias"].astype(dt)
        x = x + o
        h = f_op(_manual_layer_norm(p["ln_2"], x).astype(dt))
        h = nn.gelu(dense(p["c_fc"], h), approximate=True)
        h = g_op(h @ p["mlp_c_proj"]["kernel"].astype(dt)) \
            + p["mlp_c_proj"]["bias"].astype(dt)
        return x + h

    return apply


# TP sharding roles of Block parameters (consumed by PipelineModule.param_specs):
# column-parallel kernels shard their LAST dim (outputs = whole head groups / ffn
# slices) and take their bias with them; row-parallel kernels shard their FIRST
# weight dim (inputs), bias replicated.
BLOCK_TP_COL = ("q_attn", "k_attn", "v_attn", "c_fc")
BLOCK_TP_ROW = ("c_proj", "mlp_c_proj")


def block_sp_apply(cfg: GPT2Config, sp: int, axis: str):
    """Sequence-parallel Block forward for use INSIDE a ``shard_map`` whose manual
    axes include ``axis`` (pipe×seq: context parallelism inside 1F1B pipeline
    stages — beyond the reference, whose SP story is absent).

    Activations arrive SEQUENCE-SHARDED ``(b, t/S, d)``; parameters are the full
    replicated Block tree (dense/LN work is per-token, so local chunks need no
    collectives) and attention all-gathers K/V over the seq axis
    (:func:`~...ops.attention.ring.allgather_attention_local` — grouped
    collectives, NOT the ppermute ring, because pipeline stage activity is
    staggered; see that function's docstring). Exactly equal to the replicated
    ``Block`` (dropout off) at any seq degree.

    Returns ``fn(params, x_local, rng) -> y_local``.
    """
    if not (cfg.split_qkv):
        raise AssertionError("seq-parallel Block needs split_qkv=True (see GPT2Config)")
    if not (cfg.dropout == 0.0):
        raise AssertionError("SP stage_fn does not implement attention dropout")
    dt = cfg.dtype

    def dense(p, x):
        return x @ p["kernel"].astype(dt) + p["bias"].astype(dt)

    def apply(p, x, rng=None):
        from ..ops.attention.ring import allgather_attention_local
        b, tl, _ = x.shape
        h = _manual_layer_norm(p["ln_1"], x).astype(dt)
        q = dense(p["q_attn"], h).reshape(b, tl, cfg.n_head, cfg.head_dim)
        k = dense(p["k_attn"], h).reshape(b, tl, cfg.n_head, cfg.head_dim)
        v = dense(p["v_attn"], h).reshape(b, tl, cfg.n_head, cfg.head_dim)
        o = allgather_attention_local(q, k, v, causal=True, axis_name=axis)
        o = o.reshape(b, tl, cfg.n_embd)
        o = dense(p["c_proj"], o)
        x = x + o
        h = _manual_layer_norm(p["ln_2"], x).astype(dt)
        h = nn.gelu(dense(p["c_fc"], h), approximate=True)
        h = dense(p["mlp_c_proj"], h)
        return x + h

    return apply


def _pin_batch_sharding(x):
    """Pin ``(b, t, d)`` activations to batch sharding over the present batch
    axes. The ZeRO-sharded embedding/layernorm params otherwise hand GSPMD
    conflicting sharding preferences for the layer carry (an fsdp-sharded
    feature dim vs the batch-sharded inputs), and it resolves them with an
    "Involuntary full rematerialization" replicate-reshard (the same failure
    mode — and fix — as ``moe/layer.py``'s token pinning). No-op without an
    installed mesh. Only called from the module-level ``GPT2`` forward, never
    from ``Block`` — the pipe engine wraps ``Block`` in manual shard_map
    regions where these axis names are not GSPMD-visible."""
    from ..parallel.mesh import AXIS_SEQ, BATCH_AXES, get_global_mesh
    mesh = get_global_mesh()
    if mesh is None:
        return x
    axes = tuple(ax for ax in BATCH_AXES if mesh.size(ax) > 1)
    if not axes and mesh.size(AXIS_SEQ) <= 1:
        return x
    # the seq dim keeps its context-parallel sharding (Ulysses) — pinning it
    # to replicated would itself conflict with the attention's a2a layout
    return jax.lax.with_sharding_constraint(
        x, mesh.sharding(mesh.batch_spec(extra_dims=x.ndim - 1,
                                         shard_seq_dim=1)))


def _pin_replicated(w):
    """Pin a parameter to full replication at a USE site. The embedding gather
    reads the whole ``wte`` row-wise; letting GSPMD keep the table's ZeRO/TP
    sharding on the gather operand makes the gather OUTPUT inherit a sharded
    feature dim, which then full-remats against the batch-sharded carry. The
    table is all-gathered for the row gather either way — pinning just makes
    the output sharding unconstrained instead of conflicting."""
    from ..parallel.mesh import get_global_mesh
    mesh = get_global_mesh()
    if mesh is None:
        return w
    return jax.lax.with_sharding_constraint(
        w, mesh.sharding(P(*([None] * w.ndim))))


def unroll_layer_loop() -> bool:
    """Is the scan over layers lowered with every trip in straight-line code (no
    ``while``)? Exactly when no mesh axis that can shard a layer's parameters or its
    carry is larger than 1: no mesh, or one whose only axis above 1 is ``data``. A
    ``while`` stacks every activation its backward needs in ONE ``(n_layer, ...)`` array
    a kind, written a layer by ``dynamic_update_slice`` and sliced (and, in front of a
    Mosaic kernel, copied) back out: 12.6 of the 170.3 ms of the 125M step (PERF.md
    section 6, PR 54). Straight-line code keeps each in a buffer of its own. Under a
    sharding axis the ``while`` is what keeps the scheduler from hoisting every layer's
    all-gather to the front, so there it stays, trip by trip. Depth has no bound of its
    own: cold compile and program size grow by ~2.5-2.9 s and ~10 MB a layer whatever
    the width (24 layers at d 2048 for a described v5e: 5.7 -> 69.6 s, 13 -> 227 MB),
    and a depth whose parameters fit one chip unsharded keeps both small beside a run."""
    from ..parallel.mesh import AXIS_DATA, MESH_AXES, get_global_mesh
    mesh = get_global_mesh()
    return mesh is None or all(mesh.size(ax) == 1 for ax in MESH_AXES if ax != AXIS_DATA)


class GPT2(nn.Module):
    config: GPT2Config

    @nn.compact
    def __call__(self, input_ids, deterministic: bool = True,
                 return_hidden: bool = False):
        """``return_hidden``: return ``(final hidden states, wte)`` instead of
        logits — the chunked-vocab CE path consumes these to avoid the
        ``(b, t, V)`` logits buffer (6.6 GB at seq 32k × vocab 50k)."""
        cfg = self.config
        b, t = input_ids.shape
        wte = self.param("wte", nn.initializers.normal(cfg.init_std),
                         (cfg.vocab_size, cfg.n_embd), jnp.float32)
        wpe = self.param("wpe", nn.initializers.normal(cfg.init_std),
                         (cfg.n_positions, cfg.n_embd), jnp.float32)
        with scope("embed"):
            x = _pin_replicated(wte)[input_ids].astype(cfg.dtype) + \
                wpe[:t][None].astype(cfg.dtype)
            x = nn.Dropout(cfg.dropout, deterministic=deterministic)(x)
            x = _pin_batch_sharding(x)

        block = Block
        if cfg.remat:
            policy = None
            if cfg.remat_policy == "dots":
                policies = jax.checkpoint_policies
                policy = policies.save_from_both_policies(
                    policies.dots_with_no_batch_dims_saveable,
                    policies.save_only_these_names(
                        FLASH_OUT_NAME, FLASH_LSE_NAME, FLASH_QKV_NAME))
            block = nn.remat(Block, prevent_cse=False, static_argnums=(2,), policy=policy)
        if cfg.scan_layers:
            # an initialisation saves no activation: its program stays one body (unrolled,
            # the 125M's loaded 1.5 s slower from the compile cache and gained nothing)
            unrolled = False
            if not self.is_initializing():
                unrolled = unroll_layer_loop()
                phase = get_tracer().current()
                if phase is not None:   # the set-up phase a program is traced under says which
                    phase.set(layer_loop="unrolled" if unrolled else "scan",
                              layers=cfg.n_layer)
            x, _ = nn.scan(
                lambda mdl, carry, _: (
                    _pin_batch_sharding(mdl(carry, deterministic)), None),
                variable_axes={"params": 0},
                split_rngs={"params": True, "dropout": True},
                length=cfg.n_layer,
                unroll=cfg.n_layer if unrolled else 1,
                metadata_params={nn.PARTITION_NAME: "layers"},
            )(block(cfg, name="h"), x, None)
        else:
            for i in range(cfg.n_layer):
                x = _pin_batch_sharding(block(cfg, name=f"h_{i}")(x, deterministic))

        with scope("head"):
            x = nn.LayerNorm(dtype=jnp.float32, name="ln_f")(x)
            if return_hidden:
                return x, wte
            # Tied LM head. bf16 operands + fp32 MXU accumulation: full-rate matmul (an
            # fp32 matmul runs at ~1/4 MXU rate and this is ~25% of model FLOPs),
            # fp32-accurate logits.
            return jax.lax.dot_general(
                x.astype(cfg.dtype), wte.astype(cfg.dtype),
                dimension_numbers=(((2,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)


def cross_entropy_loss(logits, labels, ignore_index: int = -100):
    """Next-token CE in fp32 with label masking."""
    vocab = logits.shape[-1]
    mask = labels != ignore_index
    safe_labels = jnp.where(mask, labels, 0)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, safe_labels[..., None], axis=-1)[..., 0]
    nll = (logz - gold) * mask
    return nll.sum() / jnp.maximum(mask.sum(), 1)


def cross_entropy_loss_sp(logits, labels, axis_name: str,
                          ignore_index: int = -100):
    """Sequence-parallel CE: this shard's (sum, valid-count) contributions are
    psum'd over ``axis_name`` before the ratio, so unequal masked-token counts
    per shard (e.g. the final -100 living on the last shard) stay exact. For use
    INSIDE a shard_map manual over the seq axis (the 1F1B sp tail).

    The sum rides the ``g`` conjugate op (psum forward, IDENTITY backward): under
    ``check_vma=False`` a raw psum transposes to another psum, which would scale
    every upstream cotangent by the seq degree (the same trap the Megatron f/g
    ops exist for)."""
    _, g_op = _tp_conjugate_ops(axis_name)
    mask = labels != ignore_index
    safe_labels = jnp.where(mask, labels, 0)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, safe_labels[..., None], axis=-1)[..., 0]
    nll = ((logz - gold) * mask).sum()
    total = g_op(nll)
    count = jax.lax.psum(mask.sum(), axis_name)   # integer: no cotangent path
    return total / jnp.maximum(count, 1)


def gpt2_model(config: GPT2Config, sample_seq_len: Optional[int] = None,
               sample_batch_size: int = 1) -> Model:
    """Build a :class:`Model` for the engine: batch = {"input_ids": (B, T)} with optional
    "labels" (defaults to shifted input_ids)."""
    module = GPT2(config)
    t = sample_seq_len or config.n_positions

    def init_fn(rng):
        sample = jnp.zeros((sample_batch_size, t), dtype=jnp.int32)
        return module.init({"params": rng, "dropout": rng}, sample)["params"]

    def _shift_labels(batch):
        ids = batch["input_ids"]
        if "labels" in batch:
            return batch["labels"]
        return jnp.concatenate(
            [ids[:, 1:], jnp.full((ids.shape[0], 1), -100, dtype=ids.dtype)], axis=1)

    def loss_fn(params, batch, rng):
        if config.vocab_chunk:
            from ..runtime.zero.tiling import chunked_vocab_cross_entropy
            hidden, wte = module.apply({"params": params}, batch["input_ids"],
                                       deterministic=False,
                                       rngs={"dropout": rng},
                                       return_hidden=True)
            with scope("loss"):
                return chunked_vocab_cross_entropy(hidden, wte, _shift_labels(batch),
                                                   chunk=config.vocab_chunk,
                                                   compute_dtype=config.dtype)
        logits = module.apply({"params": params}, batch["input_ids"],
                              deterministic=False, rngs={"dropout": rng})
        with scope("loss"):
            return cross_entropy_loss(logits, _shift_labels(batch))

    def apply_fn(params, batch, rng=None):
        ids = batch["input_ids"] if isinstance(batch, dict) else batch
        return module.apply({"params": params}, ids, deterministic=True)

    return Model(
        loss_fn=loss_fn,
        init_fn=init_fn,
        apply_fn=apply_fn,
        param_specs=None,  # filled per-mesh by gpt2_param_specs
        flops_per_sample=config.flops_per_token() * t,
        name=f"GPT2(L{config.n_layer},d{config.n_embd})",
    )


def gpt2_param_specs(params, tensor_axis: str = "tensor") -> Any:
    """Megatron-style TP PartitionSpecs by parameter path.

    Column-parallel: qkv and mlp-in kernels shard their output dim; row-parallel: attn/mlp out
    projections shard their input dim; embeddings shard the vocab dim. XLA inserts the
    all-reduces the reference does manually via ``LinearAllreduce`` (``module_inject/layers.py``).
    """
    flat = jax.tree_util.tree_flatten_with_path(params)[0]
    treedef = jax.tree_util.tree_structure(params)

    def spec_for(path_str: str, ndim: int):
        lead = [None] * (ndim - 2)
        if "c_attn" in path_str or "c_fc" in path_str:
            if path_str.endswith("kernel"):
                return P(*lead, None, tensor_axis)
            return P(*([None] * (ndim - 1)), tensor_axis)
        if ("c_proj" in path_str or "mlp_c_proj" in path_str) and path_str.endswith("kernel"):
            return P(*lead, tensor_axis, None)
        if path_str.endswith("wte"):
            return P(tensor_axis, None)
        return P(*([None] * ndim)) if ndim else P()

    specs = []
    for path, leaf in flat:
        path_str = "/".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in path)
        specs.append(spec_for(path_str, getattr(leaf, "ndim", 0)))
    return jax.tree_util.tree_unflatten(treedef, specs)
