"""Block-sparse attention — Pallas TPU kernel driven by SparsityConfig layouts.

TPU-native replacement for the reference's Triton block-sparse attention
(``deepspeed/ops/sparse_attention/matmul.py`` SDD/DSD kernels + ``softmax.py``, consumed
by ``sparse_self_attention.py``): the pattern library
(``ops/sparse_attention/sparsity_config.py`` here) produces a ``(heads, nb, nb)`` block
layout; this kernel computes attention only over active blocks.

Design: the flash-attention structure (online softmax, per-q-block streaming) with the
k-block loop replaced by a walk over a per-(head, q-block) table of ACTIVE k-block
indices. The tables are host-precomputed from the (static) layout and enter the kernel
via scalar prefetch (SMEM), so each grid cell runs a data-dependent-length ``fori_loop``
over exactly its nonzero blocks — compute and HBM traffic scale with layout density,
not t². The backward walks the transposed table for dk/dv (which q-blocks attend to
this k-block), recomputing probabilities from the saved logsumexp like the flash
backward.

Within-block elementwise causality applies on top of the block mask when the pattern is
unidirectional (the layouts are block-granular; diagonal blocks need the elementwise
triangle).
"""

import functools
from typing import Dict, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...utils.device import pallas_interpret as _interpret
from ..sparse_attention.sparsity_config import SparsityConfig, layout_to_dense_mask

NEG_INF = -1e30


# ------------------------------------------------------------------ layout tables
def build_tables(layout: np.ndarray) -> Dict[str, np.ndarray]:
    """Active-block index tables from a (H, nb, nb) 0/1 layout.

    ``fwd_idx[h, qi, n]`` = n-th active k-block for q-block ``qi`` (pad 0),
    ``fwd_cnt[h, qi]`` = number of active k-blocks; ``bwd_*`` the transpose
    (q-blocks per k-block).
    """
    layout = np.asarray(layout) != 0
    h, nb, _ = layout.shape
    fwd_cnt = layout.sum(axis=2).astype(np.int32)
    bwd_cnt = layout.sum(axis=1).astype(np.int32)
    max_f = max(1, int(fwd_cnt.max()))
    max_b = max(1, int(bwd_cnt.max()))
    fwd_idx = np.zeros((h, nb, max_f), np.int32)
    bwd_idx = np.zeros((h, nb, max_b), np.int32)
    for hi in range(h):
        for qi in range(nb):
            nz = np.nonzero(layout[hi, qi])[0]
            fwd_idx[hi, qi, :len(nz)] = nz
        for ki in range(nb):
            nz = np.nonzero(layout[hi, :, ki])[0]
            bwd_idx[hi, ki, :len(nz)] = nz
    return {"fwd_idx": fwd_idx, "fwd_cnt": fwd_cnt,
            "bwd_idx": bwd_idx, "bwd_cnt": bwd_cnt}


# ------------------------------------------------------------------ forward kernel
def _fwd_kernel(idx_ref, cnt_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
                *, scale, causal, block, n_heads):
    q = q_ref[0].astype(jnp.float32)                  # (block, d)
    bq, d = q.shape
    i = pl.program_id(0)
    j = pl.program_id(1)
    h_idx = jax.lax.rem(i, n_heads)
    rows = j * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, block), 0)
    nnz = cnt_ref[h_idx, j]

    def body(n, carry):
        m, l, acc = carry
        kb = idx_ref[h_idx, j, n]
        k_blk = k_ref[0, pl.ds(kb * block, block), :].astype(jnp.float32)
        v_blk = v_ref[0, pl.ds(kb * block, block), :].astype(jnp.float32)
        s = jax.lax.dot_general(q, k_blk, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            cols = kb * block + jax.lax.broadcasted_iota(jnp.int32, (bq, block), 1)
            s = jnp.where(cols <= rows, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[:, None])
        l_new = l * alpha + jnp.sum(p, axis=-1)
        acc_new = acc * alpha[:, None] + jax.lax.dot_general(
            p, v_blk, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        return m_new, l_new, acc_new

    m0 = jnp.full((bq,), NEG_INF, jnp.float32)
    l0 = jnp.zeros((bq,), jnp.float32)
    acc0 = jnp.zeros((bq, d), jnp.float32)
    m, l, acc = jax.lax.fori_loop(0, nnz, body, (m0, l0, acc0))
    l_safe = jnp.where(l > 0, l, 1.0)
    o_ref[0] = (acc / l_safe[:, None]).astype(o_ref.dtype)
    lse = jnp.where(l > 0, m + jnp.log(l_safe), NEG_INF)
    lse_ref[0, 0] = jnp.broadcast_to(lse[None, :], (8, bq))


def _bs_fwd(q3, k3, v3, fwd_idx, fwd_cnt, scale, causal, block, n_heads):
    bh, t, d = q3.shape
    nq = t // block
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(bh, nq),
        in_specs=[
            pl.BlockSpec((1, block, d), lambda i, j, *_: (i, j, 0)),
            pl.BlockSpec((1, t, d), lambda i, j, *_: (i, 0, 0)),
            pl.BlockSpec((1, t, d), lambda i, j, *_: (i, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block, d), lambda i, j, *_: (i, j, 0)),
            pl.BlockSpec((1, 1, 8, block), lambda i, j, *_: (i, j, 0, 0)),
        ],
    )
    o3, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, causal=causal, block=block,
                          n_heads=n_heads),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((bh, t, d), q3.dtype),
            jax.ShapeDtypeStruct((bh, nq, 8, block), jnp.float32),
        ],
        name="block_sparse_fwd",
        interpret=_interpret(),
    )(fwd_idx, fwd_cnt, q3, k3, v3)
    return o3, lse[:, :, 0, :].reshape(bh, t)


# ------------------------------------------------------------------ backward kernels
def _bwd_dq_kernel(idx_ref, cnt_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                   delta_ref, dq_ref, *, scale, causal, block, n_heads):
    q = q_ref[0].astype(jnp.float32)
    do = do_ref[0].astype(jnp.float32)
    lse = lse_ref[0, 0, 0]
    delta = delta_ref[0, 0, 0]
    bq, d = q.shape
    i = pl.program_id(0)
    j = pl.program_id(1)
    h_idx = jax.lax.rem(i, n_heads)
    rows = j * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, block), 0)
    nnz = cnt_ref[h_idx, j]

    def body(n, dq):
        kb = idx_ref[h_idx, j, n]
        k_blk = k_ref[0, pl.ds(kb * block, block), :].astype(jnp.float32)
        v_blk = v_ref[0, pl.ds(kb * block, block), :].astype(jnp.float32)
        s = jax.lax.dot_general(q, k_blk, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            cols = kb * block + jax.lax.broadcasted_iota(jnp.int32, (bq, block), 1)
            s = jnp.where(cols <= rows, s, NEG_INF)
        p = jnp.exp(s - lse[:, None])
        dp = jax.lax.dot_general(do, v_blk, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta[:, None]) * scale
        return dq + jax.lax.dot_general(ds, k_blk, (((1,), (0,)), ((), ())),
                                        preferred_element_type=jnp.float32)

    dq = jax.lax.fori_loop(0, nnz, body, jnp.zeros((bq, d), jnp.float32))
    dq_ref[0] = dq.astype(dq_ref.dtype)


def _bwd_dkv_kernel(idx_ref, cnt_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                    delta_ref, dk_ref, dv_ref, *, scale, causal, block, n_heads):
    k_blk = k_ref[0].astype(jnp.float32)              # (block, d)
    v_blk = v_ref[0].astype(jnp.float32)
    bk, d = k_blk.shape
    i = pl.program_id(0)
    kb = pl.program_id(1)
    h_idx = jax.lax.rem(i, n_heads)
    cols = kb * bk + jax.lax.broadcasted_iota(jnp.int32, (block, bk), 1)
    nnz = cnt_ref[h_idx, kb]

    def body(n, carry):
        dk, dv = carry
        qb = idx_ref[h_idx, kb, n]
        q_blk = q_ref[0, pl.ds(qb * block, block), :].astype(jnp.float32)
        do_blk = do_ref[0, pl.ds(qb * block, block), :].astype(jnp.float32)
        lse_blk = lse_ref[0, qb, 0]
        delta_blk = delta_ref[0, qb, 0]
        s = jax.lax.dot_general(q_blk, k_blk, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            rows = qb * block + jax.lax.broadcasted_iota(jnp.int32, (block, bk), 0)
            s = jnp.where(cols <= rows, s, NEG_INF)
        p = jnp.exp(s - lse_blk[:, None])
        dv_new = dv + jax.lax.dot_general(p, do_blk, (((0,), (0,)), ((), ())),
                                          preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do_blk, v_blk, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta_blk[:, None]) * scale
        dk_new = dk + jax.lax.dot_general(ds, q_blk, (((0,), (0,)), ((), ())),
                                          preferred_element_type=jnp.float32)
        return dk_new, dv_new

    dk0 = jnp.zeros((bk, d), jnp.float32)
    dv0 = jnp.zeros((bk, d), jnp.float32)
    dk, dv = jax.lax.fori_loop(0, nnz, body, (dk0, dv0))
    dk_ref[0] = dk.astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)


def _bs_bwd(q3, k3, v3, o3, lse, do3, tables, scale, causal, block, n_heads):
    bh, t, d = q3.shape
    nq = t // block
    delta = jnp.sum(do3.astype(jnp.float32) * o3.astype(jnp.float32), axis=-1)
    lse_b = jnp.broadcast_to(lse.reshape(bh, nq, 1, block), (bh, nq, 8, block))
    delta_b = jnp.broadcast_to(delta.reshape(bh, nq, 1, block), (bh, nq, 8, block))

    dq_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(bh, nq),
        in_specs=[
            pl.BlockSpec((1, block, d), lambda i, j, *_: (i, j, 0)),
            pl.BlockSpec((1, t, d), lambda i, j, *_: (i, 0, 0)),
            pl.BlockSpec((1, t, d), lambda i, j, *_: (i, 0, 0)),
            pl.BlockSpec((1, block, d), lambda i, j, *_: (i, j, 0)),
            pl.BlockSpec((1, 1, 8, block), lambda i, j, *_: (i, j, 0, 0)),
            pl.BlockSpec((1, 1, 8, block), lambda i, j, *_: (i, j, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, block, d), lambda i, j, *_: (i, j, 0)),
    )
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, causal=causal, block=block,
                          n_heads=n_heads),
        grid_spec=dq_spec,
        out_shape=jax.ShapeDtypeStruct((bh, t, d), q3.dtype),
        name="block_sparse_bwd_dq",
        interpret=_interpret(),
    )(tables["fwd_idx"], tables["fwd_cnt"], q3, k3, v3, do3, lse_b, delta_b)

    dkv_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(bh, nq),
        in_specs=[
            pl.BlockSpec((1, t, d), lambda i, j, *_: (i, 0, 0)),
            pl.BlockSpec((1, block, d), lambda i, j, *_: (i, j, 0)),
            pl.BlockSpec((1, block, d), lambda i, j, *_: (i, j, 0)),
            pl.BlockSpec((1, t, d), lambda i, j, *_: (i, 0, 0)),
            pl.BlockSpec((1, nq, 8, block), lambda i, j, *_: (i, 0, 0, 0)),
            pl.BlockSpec((1, nq, 8, block), lambda i, j, *_: (i, 0, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block, d), lambda i, j, *_: (i, j, 0)),
            pl.BlockSpec((1, block, d), lambda i, j, *_: (i, j, 0)),
        ],
    )
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=scale, causal=causal, block=block,
                          n_heads=n_heads),
        grid_spec=dkv_spec,
        out_shape=[
            jax.ShapeDtypeStruct((bh, t, d), k3.dtype),
            jax.ShapeDtypeStruct((bh, t, d), v3.dtype),
        ],
        name="block_sparse_bwd_dkv",
        interpret=_interpret(),
    )(tables["bwd_idx"], tables["bwd_cnt"], q3, k3, v3, do3, lse_b, delta_b)
    return dq, dk, dv


# ------------------------------------------------------------------ custom vjp core
@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8, 9, 10))
def _bs_core(q3, k3, v3, fwd_idx, fwd_cnt, bwd_idx, bwd_cnt,
             scale, causal, block, n_heads):
    o3, _ = _bs_fwd(q3, k3, v3, fwd_idx, fwd_cnt, scale, causal, block, n_heads)
    return o3


def _bs_core_fwd(q3, k3, v3, fwd_idx, fwd_cnt, bwd_idx, bwd_cnt,
                 scale, causal, block, n_heads):
    o3, lse = _bs_fwd(q3, k3, v3, fwd_idx, fwd_cnt, scale, causal, block, n_heads)
    return o3, (q3, k3, v3, o3, lse, fwd_idx, fwd_cnt, bwd_idx, bwd_cnt)


def _bs_core_bwd(scale, causal, block, n_heads, res, do3):
    q3, k3, v3, o3, lse, fwd_idx, fwd_cnt, bwd_idx, bwd_cnt = res
    tables = {"fwd_idx": fwd_idx, "fwd_cnt": fwd_cnt,
              "bwd_idx": bwd_idx, "bwd_cnt": bwd_cnt}
    dq, dk, dv = _bs_bwd(q3, k3, v3, o3, lse, do3, tables, scale, causal, block,
                         n_heads)
    zeros = lambda x: jnp.zeros_like(x)
    return dq, dk, dv, zeros(fwd_idx), zeros(fwd_cnt), zeros(bwd_idx), zeros(bwd_cnt)


_bs_core.defvjp(_bs_core_fwd, _bs_core_bwd)


# ------------------------------------------------------------------ public ops
def block_sparse_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                           layout: np.ndarray, block: int,
                           causal: bool = False,
                           softmax_scale: Optional[float] = None) -> jnp.ndarray:
    """q/k/v ``(b, t, h, d)`` with a (h, nb, nb) block ``layout`` → ``(b, t, h, d)``.

    ``causal`` applies the elementwise triangle on top of the block mask (use with
    unidirectional layouts). Rows whose layout is empty produce zeros.
    """
    b, t, h, d = q.shape
    if not (k.shape == q.shape and v.shape == q.shape):
        raise AssertionError("self-attention only")
    layout = np.asarray(layout)
    if not (layout.shape[0] == h):
        raise AssertionError((layout.shape, h))
    if not (layout.shape[1] * block == t):
        raise AssertionError(f"layout covers {layout.shape[1] * block} positions, inputs have {t}")
    scale = softmax_scale if softmax_scale is not None else 1.0 / float(np.sqrt(d))
    tables = build_tables(layout)

    def to3(x):
        return x.transpose(0, 2, 1, 3).reshape(b * h, t, d)

    o3 = _bs_core(to3(q), to3(k), to3(v),
                  jnp.asarray(tables["fwd_idx"]), jnp.asarray(tables["fwd_cnt"]),
                  jnp.asarray(tables["bwd_idx"]), jnp.asarray(tables["bwd_cnt"]),
                  scale, causal, block, h)
    return o3.reshape(b, h, t, d).transpose(0, 2, 1, 3)


def block_sparse_attention_reference(q, k, v, layout, block, causal=False,
                                     softmax_scale=None):
    """Dense-masked XLA ground truth (tests + feature fallback)."""
    from ..transformer.attention import xla_attention
    mask = layout_to_dense_mask(np.asarray(layout), block)      # (h, t, t)
    return xla_attention(q, k, v, causal=causal, mask=jnp.asarray(mask)[None],
                         softmax_scale=softmax_scale)


def make_sparse_attention_impl(config: SparsityConfig):
    """Bind a :class:`SparsityConfig` into a flash-signature attention impl
    (the ``SparseSelfAttention`` role, reference
    ``ops/sparse_attention/sparse_self_attention.py``): layouts are built and cached
    per sequence length."""
    layouts: Dict[int, np.ndarray] = {}

    def impl(q, k, v, causal=True, mask=None, softmax_scale=None,
             dropout_rate=0.0, dropout_rng=None):
        from ..transformer.attention import xla_attention
        uni = getattr(config, "attention", "bidirectional") == "unidirectional"
        if mask is not None or dropout_rate > 0.0 or q.shape[1] != k.shape[1]:
            # features the kernel doesn't cover: keep the SPARSITY PATTERN (dense
            # mask from the layout) and fall back to the masked XLA path — falling
            # back to dense attention would silently change the architecture
            t, s = q.shape[1], k.shape[1]
            if s not in layouts:
                layouts[s] = config.make_layout(s)
            lmask = jnp.asarray(layout_to_dense_mask(layouts[s],
                                                     config.block))[None]
            lmask = lmask[:, :, -t:, :]  # decode: q covers the cache tail
            if mask is not None:
                user = mask[:, None, None, :] if mask.ndim == 2 else mask
                lmask = jnp.logical_and(lmask, user.astype(bool))
            return xla_attention(q, k, v, causal=causal or uni, mask=lmask,
                                 softmax_scale=softmax_scale,
                                 dropout_rate=dropout_rate,
                                 dropout_rng=dropout_rng)
        t = q.shape[1]
        if t not in layouts:
            layouts[t] = config.make_layout(t)
        return block_sparse_attention(q, k, v, layouts[t], config.block,
                                      causal=causal or uni,
                                      softmax_scale=softmax_scale)

    return impl
