"""Attention kernel tests — numerical equivalence vs the jnp reference, the pattern of the
reference's ``tests/unit/ops/`` kernel-vs-torch comparisons."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops.attention import (decode_attention, decode_attention_live,
                                         decode_attention_xla, flash_attention,
                                         ring_attention)
from deepspeed_tpu.ops.transformer.attention import xla_attention
from deepspeed_tpu.parallel.mesh import MeshSpec, set_global_mesh


def _qkv(rng, b, t, h, d, dtype=np.float32):
    return tuple(jnp.asarray(rng.normal(size=(b, t, h, d)).astype(dtype))
                 for _ in range(3))


# ------------------------------------------------------------------------ flash
# (t, block_q, block_k) whose diagonal blocks are walked in sub-tiles: two or more forward
# strips (512) and backward strips (256) in ONE block a head, in a 2-block and in a 3-block
# grid, and unequal blocks (the diagonal enters a block at an offset: masked whole)
SUB_TILED = [(1024, 1024, 1024), (2048, 1024, 1024), (1536, 512, 512), (1024, 512, 1024)]


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("t,block", [(128, 64), (96, 64), (64, 128), (1024, 1024),
                                     (2048, 1024)])
def test_flash_matches_xla(causal, t, block):
    rng = np.random.default_rng(0)
    q, k, v = _qkv(rng, 2 if t < 1024 else 1, t, 4 if t < 1024 else 2, 32)
    o1 = flash_attention(q, k, v, causal=causal, block_q=block, block_k=block)
    o2 = xla_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), rtol=1e-5, atol=1e-5)


def _grads(fn, q, k, v, w):
    """d/d(q, k, v) of sum(fn * w): a cotangent that differs from row to row, so a
    strip written to the wrong rows cannot pass."""
    return jax.grad(lambda *a: (fn(*a).astype(jnp.float32) * w).sum(),
                    argnums=(0, 1, 2))(q, k, v)


@pytest.mark.parametrize("alibi", [False, True])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("t,block_q,block_k", SUB_TILED)
def test_flash_sub_tiled_matches_xla(t, block_q, block_k, dtype, alibi):
    """Forward and all three gradients where the diagonal block is walked in strips."""
    from deepspeed_tpu.models.causal_lm import _alibi_attention_xla, alibi_slopes
    rng = np.random.default_rng(11)
    h = 2
    q, k, v = (x.astype(dtype) for x in _qkv(rng, 1, t, h, 32))
    w = jnp.asarray(rng.normal(size=(1, t, h, 32)).astype(np.float32))
    slopes = jnp.asarray(alibi_slopes(h)) if alibi else None

    def flash(*a):
        return flash_attention(*a, causal=True, alibi_slopes=slopes,
                               block_q=block_q, block_k=block_k)

    def ref(*a):
        a = tuple(x.astype(jnp.float32) for x in a)
        if alibi:
            return _alibi_attention_xla(*a, slopes)
        return xla_attention(*a, causal=True)

    tol = dict(rtol=1e-4, atol=2e-5) if dtype == jnp.float32 else dict(rtol=4e-2, atol=4e-2)
    o1 = flash(q, k, v)
    assert o1.dtype == dtype
    np.testing.assert_allclose(np.asarray(o1, np.float32), np.asarray(ref(q, k, v)), **tol)
    for a, b in zip(_grads(flash, q, k, v, w), _grads(ref, q, k, v, w)):
        np.testing.assert_allclose(np.asarray(a, np.float32), np.asarray(b, np.float32),
                                   **tol)


def test_flash_causal_work_share():
    """The share of the score square the kernels form, from the strips they walk."""
    from deepspeed_tpu.ops.attention.flash import causal_work_share
    # the benchmark's shape: one 1024 block a head, d_head 64
    assert causal_work_share(1024, backward=True) <= 0.63      # 4 strips of 256
    assert causal_work_share(1024) <= 0.75                     # 2 strips of 512
    assert causal_work_share(1024, causal=False) == 1.0
    assert causal_work_share(1024, causal=False, backward=True) == 1.0
    # a block no strip divides, or unequal blocks, is formed whole
    assert causal_work_share(128, 64, 64) == 0.75
    assert causal_work_share(1024, 512, 1024) == 1.0
    # long sequences: the grid-level skip does the rest, towards the triangle's half
    assert 0.5 < causal_work_share(16384, backward=True) < causal_work_share(4096) < 0.6


def test_flash_bf16():
    rng = np.random.default_rng(2)
    q, k, v = _qkv(rng, 1, 128, 2, 32)
    q, k, v = (x.astype(jnp.bfloat16) for x in (q, k, v))
    o1 = flash_attention(q, k, v, causal=True)
    o2 = xla_attention(q, k, v, causal=True)
    assert o1.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(o1, dtype=np.float32),
                               np.asarray(o2, dtype=np.float32), rtol=3e-2, atol=3e-2)


@pytest.mark.parametrize("t,block", [(128, 64), (256, 128)])
def test_flash_alibi_matches_xla(t, block):
    """Alibi bias fused inside the kernel vs the XLA bias-matrix reference."""
    from deepspeed_tpu.models.causal_lm import _alibi_attention_xla, alibi_slopes
    rng = np.random.default_rng(7)
    h = 4
    q, k, v = _qkv(rng, 2, t, h, 32)
    slopes = jnp.asarray(alibi_slopes(h))
    o1 = flash_attention(q, k, v, causal=True, alibi_slopes=slopes,
                         block_q=block, block_k=block)
    o2 = _alibi_attention_xla(q, k, v, slopes)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), rtol=1e-5, atol=1e-5)


def test_flash_alibi_sharded_heads(eight_devices):
    """Slopes shard over the TP axis: each shard must see exactly its heads' slopes."""
    from deepspeed_tpu.models.causal_lm import _alibi_attention_xla, alibi_slopes
    set_global_mesh(MeshSpec({"tensor": 4, "data": 2}, eight_devices))
    try:
        rng = np.random.default_rng(9)
        h = 8
        q, k, v = _qkv(rng, 2, 128, h, 16)
        slopes = jnp.asarray(alibi_slopes(h))
        o1 = jax.jit(lambda *a: flash_attention(*a, causal=True, alibi_slopes=slopes,
                                                block_q=64, block_k=64))(q, k, v)
        o2 = _alibi_attention_xla(q, k, v, slopes)
        np.testing.assert_allclose(np.asarray(o1), np.asarray(o2),
                                   rtol=1e-5, atol=1e-5)
    finally:
        set_global_mesh(None)


# (heads, d_head) by the branch they take: heads read in place from (b, t, h*d), several a
# 128-lane block or one; or, where lane tiles cannot address a head, the (b*h, t, d) call
HEAD_SHAPES = [(4, 64), (4, 32), (2, 128), (3, 64), (2, 48)]
HEAD_IDS = ["two-a-block", "four-a-block", "one-a-block", "odd-count-folded", "d48-folded"]


def test_heads_a_block_by_shape():
    from deepspeed_tpu.ops.attention.flash import heads_a_block
    assert [heads_a_block(h, d) for h, d in HEAD_SHAPES] == [2, 4, 1, 0, 0]
    assert heads_a_block(12, 64) == 2 and heads_a_block(32, 128) == 1   # GPT-2, BLOOM
    assert heads_a_block(16, 256) == 1 and heads_a_block(1, 64) == 0


def _flat_reference(kind, slopes, t):
    from deepspeed_tpu.models.causal_lm import _alibi_attention_xla, block_causal_mask
    if kind == "alibi":
        return lambda q, k, v: _alibi_attention_xla(q, k, v, slopes)
    if kind == "mask_block":
        mask = jnp.asarray(block_causal_mask(t, 4))[None, None]
        return lambda q, k, v: xla_attention(q, k, v, causal=False, mask=mask)
    return lambda q, k, v: xla_attention(q, k, v, causal=True)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("block", [128, 1024], ids=["two-blocks", "one-block"])
@pytest.mark.parametrize("kind", ["causal", "alibi", "mask_block"])
@pytest.mark.parametrize("h,d", HEAD_SHAPES, ids=HEAD_IDS)
def test_flash_head_layouts_match_xla(h, d, kind, block, dtype):
    """Forward and dq/dk/dv on every branch of the operand layout, under a cotangent
    that differs from row to row and from head to head: a head read from, or written
    to, another head's lanes cannot pass."""
    from deepspeed_tpu.models.causal_lm import alibi_slopes
    rng = np.random.default_rng(21)
    t = 256
    q, k, v = (x.astype(dtype) for x in _qkv(rng, 2, t, h, d))
    w = jnp.asarray(rng.normal(size=(2, t, h, d)).astype(np.float32))
    # slopes of a power-of-two count (the closed form) cut to h heads
    slopes = jnp.asarray(alibi_slopes(4)[:h]) if kind == "alibi" else None

    def flash(*a):
        return flash_attention(*a, causal=True, alibi_slopes=slopes, block_q=block,
                               block_k=block, mask_block=4 if kind == "mask_block" else 1)

    want = _flat_reference(kind, slopes, t)

    def ref(*a):
        return want(*(x.astype(jnp.float32) for x in a))

    tol = dict(rtol=1e-4, atol=2e-5) if dtype == jnp.float32 else dict(rtol=4e-2, atol=4e-2)
    o1 = flash(q, k, v)
    assert o1.dtype == dtype and o1.shape == (2, t, h, d)
    np.testing.assert_allclose(np.asarray(o1, np.float32), np.asarray(ref(q, k, v)), **tol)
    for a, b in zip(_grads(flash, q, k, v, w), _grads(ref, q, k, v, w)):
        np.testing.assert_allclose(np.asarray(a, np.float32), np.asarray(b, np.float32),
                                   **tol)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("h,d", HEAD_SHAPES[:3], ids=HEAD_IDS[:3])
def test_flash_fused_qkv_matches_split(h, d, dtype):
    """One (b, t, 3*h*d) operand read at q | k | v's lane offsets against split +
    ``flash_attention``: the same output, and the same gradient of the operand."""
    from deepspeed_tpu.ops.attention.flash import flash_attention_qkv
    rng = np.random.default_rng(22)
    b, t = 2, 256
    qkv = jnp.asarray(rng.normal(size=(b, t, 3 * h * d)).astype(np.float32)).astype(dtype)
    w = jnp.asarray(rng.normal(size=(b, t, h * d)).astype(np.float32))

    def split(x):
        q, k, v = (y.reshape(b, t, h, d) for y in jnp.split(x, 3, axis=-1))
        return flash_attention(q, k, v, causal=True).reshape(b, t, h * d)

    def fused(x):
        return flash_attention_qkv(x, h, causal=True)

    tol = dict(rtol=1e-4, atol=2e-5) if dtype == jnp.float32 else dict(rtol=4e-2, atol=4e-2)
    got, want = fused(qkv), split(qkv)
    assert got.dtype == dtype and got.shape == (b, t, h * d)
    np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(want, np.float32),
                               **tol)
    g1, g2 = (jax.grad(lambda x: (fn(x).astype(jnp.float32) * w).sum())(qkv)
              for fn in (fused, split))
    assert g1.shape == qkv.shape and g1.dtype == dtype
    np.testing.assert_allclose(np.asarray(g1, np.float32), np.asarray(g2, np.float32), **tol)


# (h, d, b, t): GPT-2's head shape (two heads a block, six lane groups a row), the 1.3B
# configuration's (one a block), a sequence of two q and two kv blocks of 1024, and two
# lane groups of two kv blocks each
BIASED_SHAPES = [(12, 64, 2, 256), (4, 128, 2, 256), (2, 64, 1, 2048), (4, 64, 2, 2048)]
BIASED_IDS = ["gpt2-heads", "one-head-a-block", "several-blocks", "groups-of-blocks"]


def _split_path(b, t, h, d):
    def split(x, bias=None):
        x = x if bias is None else x + bias
        q, k, v = (y.reshape(b, t, h, d) for y in jnp.split(x, 3, axis=-1))
        return flash_attention(q, k, v, causal=True).reshape(b, t, h * d)
    return split


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("h,d,b,t", BIASED_SHAPES, ids=BIASED_IDS)
def test_flash_fused_qkv_under_the_projections_bias(h, d, b, t, dtype):
    """What ``Block`` runs: ``flash_attention_qkv(qkv0 + c, h)`` against
    ``flash_attention`` on ``split(qkv0 + c)``: the output, the gradient of ``qkv0``
    (dq | dk | dv, the split path's three joined) and that of the bias (its sum over
    the rows)."""
    from deepspeed_tpu.ops.attention.flash import flash_attention_qkv
    rng = np.random.default_rng(24)
    qkv0 = jnp.asarray(rng.normal(size=(b, t, 3 * h * d)).astype(np.float32)).astype(dtype)
    bias = jnp.asarray(rng.normal(size=(3 * h * d,)).astype(np.float32)).astype(dtype)
    w = jnp.asarray(rng.normal(size=(b, t, h * d)).astype(np.float32))
    split = _split_path(b, t, h, d)

    def fused(x, c):
        return flash_attention_qkv(x + c, h, causal=True)

    def grads(fn, *a):
        return jax.grad(lambda x, c: (fn(x, c).astype(jnp.float32) * w).sum(),
                        argnums=(0, 1))(*a)

    tol = dict(rtol=1e-4, atol=2e-5) if dtype == jnp.float32 else dict(rtol=4e-2, atol=4e-2)
    got = fused(qkv0, bias)
    assert got.dtype == dtype and got.shape == (b, t, h * d)
    # the reference adds and differentiates in float32: a bf16 sum over b*t rows is no
    # yardstick for the bias's gradient
    qkv32, bias32 = qkv0.astype(jnp.float32), bias.astype(jnp.float32)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(split(qkv32, bias32)), **tol)
    (dx, dc), (dx_want, dc_want) = grads(fused, qkv0, bias), grads(split, qkv32, bias32)
    assert dx.shape == qkv0.shape and dx.dtype == dtype
    assert dc.shape == bias.shape and dc.dtype == dtype
    np.testing.assert_allclose(np.asarray(dx, np.float32), np.asarray(dx_want), **tol)
    size = max(1.0, float(jnp.abs(dc_want).max()))
    np.testing.assert_allclose(np.asarray(dc, np.float32) / size,
                               np.asarray(dc_want) / size, **tol)
    # the same kernels on the same values
    same_dx, same_dc = grads(split, qkv0, bias)
    assert np.array_equal(np.asarray(dx, np.float32), np.asarray(same_dx, np.float32))
    assert np.array_equal(np.asarray(dc, np.float32), np.asarray(same_dc, np.float32))


def test_flash_fused_qkv_without_causality():
    """Every q block reaches every kv block (two q and two kv blocks of 1024)."""
    from deepspeed_tpu.ops.attention.flash import flash_attention_qkv
    rng = np.random.default_rng(27)
    b, t, h, d = 1, 2048, 2, 64
    qkv = jnp.asarray(rng.normal(size=(b, t, 3 * h * d)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(b, t, h * d)).astype(np.float32))

    def split(x):
        q, k, v = (y.reshape(b, t, h, d) for y in jnp.split(x, 3, axis=-1))
        return flash_attention(q, k, v, causal=False).reshape(b, t, h * d)

    g1, g2 = (jax.grad(lambda x: (fn(x) * w).sum())(qkv) for fn in (
        lambda x: flash_attention_qkv(x, h, causal=False), split))
    assert np.array_equal(np.asarray(g1), np.asarray(g2))


def test_flash_fused_qkv_under_a_batch_sharded_mesh_with_its_bias(eight_devices):
    """The bias is whole on every shard of the batch; its gradient is summed over them."""
    from deepspeed_tpu.ops.attention.flash import flash_attention_qkv
    set_global_mesh(MeshSpec({"fsdp": 4, "data": 2}, eight_devices))
    try:
        rng = np.random.default_rng(26)
        b, t, h, d = 8, 128, 2, 64
        qkv0 = jnp.asarray(rng.normal(size=(b, t, 3 * h * d)).astype(np.float32))
        bias = jnp.asarray(rng.normal(size=(3 * h * d,)).astype(np.float32))
        w = jnp.asarray(rng.normal(size=(b, t, h * d)).astype(np.float32))

        def ref(x, c):
            q, k, v = (y.reshape(b, t, h, d) for y in jnp.split(x + c, 3, axis=-1))
            return xla_attention(q, k, v, causal=True).reshape(b, t, h * d)

        def fused(x, c):
            return flash_attention_qkv(x + c, h, causal=True)

        def grads(fn):
            return jax.jit(jax.grad(lambda x, c: (fn(x, c) * w).sum(),
                                    argnums=(0, 1)))(qkv0, bias)

        assert "shard_map" in str(jax.make_jaxpr(fused)(qkv0, bias))
        for a, want in zip(grads(fused), grads(ref)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(want), rtol=1e-4,
                                       atol=1e-4)
    finally:
        set_global_mesh(None)


def test_flash_fused_qkv_refuses_heads_outside_lane_tiles():
    from deepspeed_tpu.ops.attention.flash import flash_attention_qkv
    with pytest.raises(ValueError, match="lane tiles"):
        flash_attention_qkv(jnp.zeros((1, 128, 3 * 3 * 64), jnp.float32), 3)


def test_flash_fused_qkv_under_a_batch_sharded_mesh(eight_devices):
    """The fused entry goes through the same per-shard wrapper as ``flash_attention``:
    batch axes manual, the lanes whole."""
    from deepspeed_tpu.ops.attention.flash import flash_attention_qkv
    set_global_mesh(MeshSpec({"fsdp": 4, "data": 2}, eight_devices))
    try:
        rng = np.random.default_rng(23)
        h, d = 2, 64
        qkv = jnp.asarray(rng.normal(size=(8, 128, 3 * h * d)).astype(np.float32))
        q, k, v = (y.reshape(8, 128, h, d) for y in jnp.split(qkv, 3, axis=-1))
        jaxpr = jax.make_jaxpr(lambda x: flash_attention_qkv(x, h))(qkv)
        assert "shard_map" in str(jaxpr)
        got = jax.jit(lambda x: flash_attention_qkv(x, h))(qkv)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(xla_attention(q, k, v, causal=True).reshape(
                8, 128, h * d)), rtol=1e-5, atol=1e-5)
    finally:
        set_global_mesh(None)


@pytest.mark.parametrize("impl,t,h,d,drop,tensor,want", [
    ("flash", 128, 12, 64, 0.0, 1, True),
    ("flash", 128, 12, 64, 0.1, 1, False),     # dropout is XLA's
    ("flash", 128, 3, 64, 0.0, 1, False),      # heads outside lane tiles
    ("flash", 128, 12, 64, 0.0, 2, False),     # a tensor axis would cut q | k | v
    ("xla", 1024, 12, 64, 0.0, 1, False),
    ("auto", 1024, 12, 64, 0.0, 1, False),     # auto is XLA off the TPU
], ids=["flash", "dropout", "odd-heads", "tensor-axis", "xla", "auto-on-cpu"])
def test_which_projections_reach_the_kernels_fused(eight_devices, impl, t, h, d, drop,
                                                  tensor, want):
    from deepspeed_tpu.ops.transformer.attention import flash_reads_fused_qkv
    set_global_mesh(MeshSpec({"tensor": tensor, "data": 8 // tensor}, eight_devices))
    try:
        assert flash_reads_fused_qkv(impl, t, h, d, drop) is want
    finally:
        set_global_mesh(None)


def test_flash_fallbacks():
    """Masks/dropout route to the XLA path (feature parity guard)."""
    rng = np.random.default_rng(3)
    q, k, v = _qkv(rng, 2, 32, 2, 16)
    mask = jnp.asarray(rng.integers(0, 2, size=(2, 32)).astype(bool))
    o1 = flash_attention(q, k, v, causal=False, mask=mask)
    o2 = xla_attention(q, k, v, causal=False, mask=mask)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), rtol=1e-6)


# ------------------------------------------------------------------------ ring
@pytest.mark.parametrize("causal", [True, False])
def test_ring_matches_xla(eight_devices, causal):
    set_global_mesh(MeshSpec({"seq": 4, "data": 2}, eight_devices))
    rng = np.random.default_rng(4)
    q, k, v = _qkv(rng, 2, 64, 2, 16)
    o1 = jax.jit(lambda *a: ring_attention(*a, causal=causal))(q, k, v)
    o2 = xla_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), rtol=1e-5, atol=1e-5)


def test_ring_grads_match_xla(eight_devices):
    set_global_mesh(MeshSpec({"seq": 8}, eight_devices))
    rng = np.random.default_rng(5)
    q, k, v = _qkv(rng, 1, 64, 2, 16)
    g1 = jax.jit(jax.grad(lambda *a: ring_attention(*a, causal=True).sum(),
                          argnums=(0, 1, 2)))(q, k, v)
    g2 = jax.grad(lambda *a: xla_attention(*a, causal=True).sum(),
                  argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5)


def test_ring_falls_back_without_seq_axis(eight_devices):
    set_global_mesh(MeshSpec({"data": 8}, eight_devices))
    rng = np.random.default_rng(6)
    q, k, v = _qkv(rng, 1, 64, 2, 16)
    o1 = ring_attention(q, k, v, causal=True)
    o2 = xla_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), rtol=1e-5, atol=1e-5)


# ------------------------------------------------------------------------ decode
# KV caches are head-major (b, h_kv, T, d) — the layout the kernel operates on.
@pytest.mark.parametrize("h,hk", [(8, 8), (8, 2)])  # MHA and GQA
def test_decode_matches_reference(h, hk):
    rng = np.random.default_rng(7)
    b, d, T = 3, 16, 64
    q = jnp.asarray(rng.normal(size=(b, h, d)).astype(np.float32))
    kc = jnp.asarray(rng.normal(size=(b, hk, T, d)).astype(np.float32))
    vc = jnp.asarray(rng.normal(size=(b, hk, T, d)).astype(np.float32))
    lens = jnp.asarray([1, 33, 64], dtype=jnp.int32)
    o1 = decode_attention(q, kc, vc, lens, block_k=16)
    o2 = decode_attention_xla(q, kc, vc, lens)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), rtol=1e-5, atol=1e-6)


def test_decode_respects_cache_len():
    """Entries past cache_len must not influence the output."""
    rng = np.random.default_rng(8)
    b, h, d, T = 1, 4, 16, 32
    q = jnp.asarray(rng.normal(size=(b, h, d)).astype(np.float32))
    kc = jnp.asarray(rng.normal(size=(b, h, T, d)).astype(np.float32))
    vc = jnp.asarray(rng.normal(size=(b, h, T, d)).astype(np.float32))
    lens = jnp.asarray([7], dtype=jnp.int32)
    o1 = decode_attention(q, kc, vc, lens, block_k=8)
    # poison the invalid region
    kc2 = kc.at[:, :, 7:].set(999.0)
    vc2 = vc.at[:, :, 7:].set(-999.0)
    o2 = decode_attention(q, kc2, vc2, lens, block_k=8)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), rtol=1e-6)


# ------------------------------------------- the served XLA form: live rows, in blocks
def _live_case(rng, T, r, lens, b=None, hk=2, group=2, dtype=jnp.bfloat16):
    """Queries and a cache of ``T`` rows of ``r`` KV heads of ``128 // r``
    lanes: ``hk`` rows, ``group`` query heads a KV head."""
    d = 128 // r
    b = len(lens) if b is None else b
    h = hk * r * group
    q = jnp.asarray(rng.normal(size=(b, h, d)), dtype)
    k, v = (jnp.asarray(rng.normal(size=(b, hk, T, r * d)), dtype) for _ in range(2))
    return q, k, v, h


@pytest.mark.parametrize("T", [576, 96, 2048])
@pytest.mark.parametrize("r", [1, 2], ids=["d128", "d64-rows-of-two"])
@pytest.mark.parametrize("alibi", [False, True], ids=["plain", "alibi"])
@pytest.mark.parametrize("n_lens", [0, 2], ids=["lens-b", "lens-b2"])
def test_live_rows_decode_attention_is_the_whole_cap_form(T, r, alibi, n_lens):
    """``decode_attention_live`` (blocks up to the batch's longest length, an
    online softmax) against the plain whole-cap ``decode_attention_xla``: with
    and without ALiBi, a head a row and two, one length a sequence and two
    runs, lengths of 0 and of ``T``, one under and one over a block's edge."""
    from deepspeed_tpu.models.causal_lm import alibi_slopes
    from deepspeed_tpu.ops.attention.decode import live_block
    B = live_block(T)
    assert T % B == 0 and (B % 16 == 0 or B == T)
    one = [0, B - 1, min(B + 1, T), T, 1, T // 2]
    rng = np.random.default_rng(T + r)
    q, k, v, h = _live_case(rng, T, r, one)
    lens = jnp.asarray(one, jnp.int32)
    if n_lens:      # a second run that sees up to a block more (a block step's shape)
        lens = jnp.stack([lens, jnp.minimum(lens + B // 2, T)], axis=1)
    slopes = jnp.asarray(alibi_slopes(h)) if alibi else None
    got = decode_attention_live(q, k, v, lens, None, slopes)
    want = decode_attention_xla(q, k, v, lens, None, slopes)
    assert got.shape == q.shape and got.dtype == q.dtype
    np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(want, np.float32),
                               rtol=2e-2, atol=2e-3)
    if not n_lens:      # a sequence that sees no row attends nothing
        assert not np.asarray(got, np.float32)[0].any()


@pytest.mark.parametrize("alibi", [False, True], ids=["plain", "alibi"])
@pytest.mark.parametrize("r", [1, 2], ids=["d128", "d64-rows-of-two"])
def test_live_rows_form_in_float32_agrees_to_rounding(r, alibi):
    """The same comparison where nothing rounds to bf16: 1e-5."""
    from deepspeed_tpu.models.causal_lm import alibi_slopes
    rng = np.random.default_rng(r)
    lens = [3, 64, 65, 200, 576]
    q, k, v, h = _live_case(rng, 576, r, lens, dtype=jnp.float32)
    slopes = jnp.asarray(alibi_slopes(h)) if alibi else None
    got = decode_attention_live(q, k, v, jnp.asarray(lens), None, slopes)
    want = decode_attention_xla(q, k, v, jnp.asarray(lens), None, slopes)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("alibi", [False, True], ids=["plain", "alibi"])
@pytest.mark.parametrize("r", [1, 2], ids=["d128", "d64-rows-of-two"])
def test_a_sequences_output_is_bit_equal_whatever_the_cap_is(r, alibi):
    """What serving parity leans on, (i): the same sequence in a cache of 576
    and of 2048 rows, walked in equal blocks, gives the same BITS (the rows
    past its length differ between the two caches)."""
    from deepspeed_tpu.models.causal_lm import alibi_slopes
    rng = np.random.default_rng(10 + r)
    lens = jnp.asarray([70, 129, 300], jnp.int32)
    q, k, v, h = _live_case(rng, 576, r, lens)
    slopes = jnp.asarray(alibi_slopes(h)) if alibi else None
    wide = [jnp.concatenate([a, jnp.asarray(rng.normal(size=a.shape[:2] + (2048 - 576,)
                                                         + a.shape[3:]), a.dtype)], axis=2)
            for a in (k, v)]
    small = decode_attention_live(q, k, v, lens, None, slopes, block=64)
    large = decode_attention_live(q, *wide, lens, None, slopes, block=64)
    np.testing.assert_array_equal(np.asarray(small, np.float32), np.asarray(large, np.float32))


@pytest.mark.parametrize("alibi", [False, True], ids=["plain", "alibi"])
@pytest.mark.parametrize("r", [1, 2], ids=["d128", "d64-rows-of-two"])
def test_a_sequences_output_is_bit_equal_whatever_the_trip_count_is(r, alibi):
    """(ii): a sequence alone, and beside one EIGHT blocks longer (whose
    length sets the batch's trip count): the same bits, under ``jit`` too."""
    from deepspeed_tpu.models.causal_lm import alibi_slopes
    T, B = 2048, 128
    rng = np.random.default_rng(20 + r)
    q, k, v, h = _live_case(rng, T, r, [0, 0])
    slopes = jnp.asarray(alibi_slopes(h)) if alibi else None
    short = B + 5
    fn = jax.jit(functools.partial(decode_attention_live, block=B))
    alone = fn(q[:1], k[:1], v[:1], jnp.asarray([short]), None, slopes)
    beside = fn(q, k, v, jnp.asarray([short, short + 8 * B]), None, slopes)
    np.testing.assert_array_equal(np.asarray(alone, np.float32)[0],
                                  np.asarray(beside, np.float32)[0])


def test_live_rows_form_does_not_read_past_the_longest_length():
    """Rows past the batch's longest length are never read: poisoned with
    NaN they change nothing (the whole-cap form multiplies them by zero and
    gives NaN)."""
    rng = np.random.default_rng(30)
    q, k, v, _ = _live_case(rng, 576, 1, [70, 90])
    lens = jnp.asarray([70, 90], jnp.int32)
    clean = decode_attention_live(q, k, v, lens)
    from deepspeed_tpu.ops.attention.decode import live_block
    edge = -(-90 // live_block(576)) * live_block(576)
    k2, v2 = (a.at[:, :, edge:].set(jnp.nan) for a in (k, v))
    np.testing.assert_array_equal(np.asarray(clean, np.float32),
                                  np.asarray(decode_attention_live(q, k2, v2, lens), np.float32))
    assert np.isnan(np.asarray(decode_attention_xla(q, k2, v2, lens), np.float32)).all()


def test_the_served_form_never_converts_the_whole_cache():
    """The StableHLO of the served form at BLOOM's shape: a ``while`` whose
    body converts ONE block of K and of V to float32; no float32 tensor has
    the cache's 576 rows of 128 lanes (the whole-cap form's first op makes
    two), and what is float32 with 576 columns is the scores' bias alone."""
    import re
    from deepspeed_tpu.models.causal_lm import alibi_slopes
    from deepspeed_tpu.ops.attention.decode import live_block
    b, h, T, d = 2, 32, 576, 128
    args = (jax.ShapeDtypeStruct((b, h, d), jnp.bfloat16),
            jax.ShapeDtypeStruct((b, h, T, d), jnp.bfloat16),
            jax.ShapeDtypeStruct((b, h, T, d), jnp.bfloat16),
            jax.ShapeDtypeStruct((b,), jnp.int32))
    slopes = jnp.asarray(alibi_slopes(h))
    live = jax.jit(lambda *a: decode_attention_live(*a, None, slopes)).lower(*args).as_text()
    whole = jax.jit(lambda *a: decode_attention_xla(*a, None, slopes)).lower(*args).as_text()
    cache_f32 = re.compile(r"tensor<[0-9x]*576x128xf32>")
    assert len(cache_f32.findall(whole)) >= 2 and not cache_f32.findall(live)
    assert "stablehlo.while" in live and "stablehlo.while" not in whole
    assert f"tensor<{b}x{h}x{live_block(T)}x{d}xf32>" in live
    with_576 = set(re.findall(r"tensor<[0-9x]*576[0-9x]*xf32>", live))
    assert with_576 <= {f"tensor<{b}x{h}x1x576xf32>", f"tensor<{b}x1x1x576xf32>",
                        "tensor<576xf32>"}, with_576


def test_an_alibi_models_decode_chunk_lowers_with_no_mosaic_kernel(monkeypatch):
    """What the benchmark's ``check_routes`` will see of BLOOM's chunk: the
    live-rows form is XLA's, ONE function with its ``while`` that every layer
    calls inside the chunk's loop, and no Mosaic kernel, with the interpreter
    off as on the chip."""
    import re
    from deepspeed_tpu.analysis.lowered import mosaic_calls
    from deepspeed_tpu.inference.decode_fns import (build_paged_decode_chunk,
                                                    make_slot_select_fn)
    from deepspeed_tpu.models.causal_lm import CausalLM, bloom_cfg, init_cache
    from deepspeed_tpu.ops.attention import decode
    monkeypatch.setattr(decode, "_interpret", lambda: False)
    cfg = bloom_cfg(n_layer=2, n_embd=64, n_head=4, vocab_size=128, dtype=jnp.bfloat16)
    slots, cap, page, pages = 2, 96, 16, 13
    module = CausalLM(cfg)
    params = jax.eval_shape(lambda: module.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"])
    caches = jax.eval_shape(lambda: init_cache(
        cfg, slots, cap, kv_shape=(pages, cfg.kv_heads, page, cfg.head_dim)))
    fn = build_paged_decode_chunk(module, lambda p: p,
                                  make_slot_select_fn(False, 1.0, 0, 1.0), 4, kv_cap=cap)
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)
    text = jax.jit(fn).lower(
        params, i32(slots, 1), caches, i32(slots, cap // page), i32(slots),
        jax.ShapeDtypeStruct((slots,), jnp.bool_), i32(slots), i32(slots), i32(slots),
        i32(slots), jax.ShapeDtypeStruct((2,), jnp.uint32)).as_text()
    assert mosaic_calls(text) == [] and "tpu_custom_call" not in text
    # the form is a jitted function: traced and lowered ONCE for all layers
    # (a while's body traced a layer cost BLOOM's chunk 6.5 s of set-up on
    # the chip's host: PERF.md section 6, PR 51), called once a layer
    assert text.count("stablehlo.while") == 2
    assert len(re.findall(r"call @decode_attention_live\b", text)) == cfg.n_layer
    assert len(re.findall(r"func\.func private @decode_attention_live\b", text)) == 1


# ----------------------------------------------------------- rows of several KV heads
def _per_head_softmax(q, k, v, row_len):
    """The plain thing, a head at a time on the UNPACKED cache: ``q`` (b, h,
    d), ``k``/``v`` (b, hk, T, d), ``row_len`` (b, h) rows a query head sees."""
    b, h, d = q.shape
    hk, T = k.shape[1], k.shape[2]
    out = np.zeros((b, h, d))
    for i in range(b):
        for j in range(h):
            n = int(row_len[i, j])
            s = k[i, j // (h // hk), :n] @ q[i, j] / np.sqrt(d)
            w = np.exp(s - s.max())
            out[i, j] = (w / w.sum()) @ v[i, j // (h // hk), :n]
    return out


PACKED = [(64, 8, 4), (64, 12, 1), (32, 4, 2), (64, 1, 8), (128, 4, 8)]


#: ``cache_len`` as (b,), (b, 1) and (b, 2); one query head a KV head has no two runs
PACKED_LENS = [(d, hk, g, n) for d, hk, g in PACKED for n in (0, 1, 2) if n < 2 or g % 2 == 0]


@pytest.mark.parametrize("path", ["xla", "mosaic-interpret"])
@pytest.mark.parametrize("d,hk,g,n_lens", PACKED_LENS, ids=[
    f"d{d}-hk{hk}-g{g}-lens-b{n or ''}" for d, hk, g, n in PACKED_LENS])
def test_packed_row_decode_attention_is_the_per_head_softmax(d, hk, g, n_lens, path):
    """A cache whose rows hold ``r`` KV heads side by side, read by packed
    queries, gives every head its own softmax over its own keys: against the
    per-head numpy form on the unpacked cache, with one length a sequence and
    with the runs of a block step, through the XLA path and the Mosaic body."""
    from deepspeed_tpu.ops.paged_attention import heads_per_row, kv_rows
    r = heads_per_row(d, hk)
    assert r == {(64, 8): 2, (64, 12): 2, (32, 4): 4, (64, 1): 1, (128, 4): 1}[d, hk]
    b, T = 3, 64
    h = hk * g
    rng = np.random.default_rng(d + hk + g)
    q = rng.normal(size=(b, h, d)).astype(np.float32)
    k = rng.normal(size=(b, T, hk, d)).astype(np.float32)
    v = rng.normal(size=(b, T, hk, d)).astype(np.float32)
    lens = np.array([[1, 9], [33, 40], [60, 64]], np.int32)[:, :max(n_lens, 1)]
    # a head's g rows are n equal runs, run j seeing lens[:, j] rows
    row_len = np.tile(np.repeat(lens, g // lens.shape[1], axis=1), (1, hk))
    want = _per_head_softmax(q, k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3), row_len)
    kc, vc = kv_rows(jnp.asarray(k), r), kv_rows(jnp.asarray(v), r)
    assert kc.shape == (b, hk // r, T, r * d)
    fn = decode_attention_xla if path == "xla" else functools.partial(
        decode_attention, block_k=16)
    got = fn(jnp.asarray(q), kc, vc, jnp.asarray(lens[:, 0] if n_lens == 0 else lens))
    assert got.shape == (b, h, d)
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("d,hk,g", PACKED, ids=[f"d{d}-hk{hk}-g{g}" for d, hk, g in PACKED])
def test_packed_queries_and_outputs_round_trip_exactly(d, hk, g):
    from deepspeed_tpu.ops.attention.decode import pack_queries, unpack_outputs
    from deepspeed_tpu.ops.paged_attention import heads_per_row
    r = heads_per_row(d, hk)
    rng = np.random.default_rng(d)
    q = jnp.asarray(rng.normal(size=(2, 3, hk * g, d)), jnp.bfloat16)
    packed = pack_queries(q, r, hk // r)
    if r == 1:
        assert packed is q and unpack_outputs(q, r, hk) is q
        return
    assert packed.shape == (2, 3, hk * g, r * d)
    np.testing.assert_array_equal(np.asarray(unpack_outputs(packed, r, hk // r), np.float32),
                                  np.asarray(q, np.float32))
    # a query head of KV head p * r + j lies in lanes [j * d, (j + 1) * d), zeros elsewhere
    lanes = np.asarray(packed, np.float32).reshape(2, 3, hk // r, r, g, r, d)
    for j in range(r):
        for other in range(r):
            block = lanes[:, :, :, j, :, other]
            assert (block != 0).all() if other == j else not block.any()
    # a select and a sum, nothing else: lane-offset slices joined by a stack gave
    # wrong lanes once compiled for the TPU (kernel_checks.check_decode holds it there)
    for fn, x in ((pack_queries, q), (unpack_outputs, packed)):
        used = {e.primitive.name for e in jax.make_jaxpr(
            lambda x: fn(x, r, hk // r))(x).jaxpr.eqns}
        assert not used & {"slice", "concatenate", "pad", "gather", "dynamic_slice"}, used


# ------------------------------------------------------------------------ model integration
def test_gpt2_flash_matches_xla_loss(eight_devices):
    from deepspeed_tpu.models.gpt2 import GPT2Config, gpt2_model
    rng = np.random.default_rng(9)
    ids = rng.integers(0, 128, size=(2, 64)).astype(np.int32)
    losses = {}
    for impl in ("xla", "flash"):
        cfg = GPT2Config(vocab_size=128, n_positions=64, n_embd=32, n_layer=2, n_head=2,
                         dropout=0.0, dtype=jnp.float32, attention_impl=impl,
                         scan_layers=False)
        model = gpt2_model(cfg, sample_seq_len=64)
        params = model.init_fn(jax.random.PRNGKey(0))
        losses[impl] = float(model.loss_fn(params, {"input_ids": ids},
                                           jax.random.PRNGKey(1)))
    np.testing.assert_allclose(losses["flash"], losses["xla"], rtol=1e-5)


# ------------------------------------------------------------------------ ulysses
@pytest.mark.parametrize("causal", [True, False])
def test_ulysses_matches_xla(eight_devices, causal):
    from deepspeed_tpu.ops.attention.ulysses import ulysses_attention
    set_global_mesh(MeshSpec({"seq": 4, "data": 2}, eight_devices))
    rng = np.random.default_rng(14)
    q, k, v = _qkv(rng, 2, 64, 4, 16)  # 4 heads / seq axis 4 -> 1 head per device
    o1 = jax.jit(lambda *a: ulysses_attention(*a, causal=causal))(q, k, v)
    o2 = xla_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), rtol=1e-5, atol=1e-5)


def test_ulysses_grads_match_xla(eight_devices):
    from deepspeed_tpu.ops.attention.ulysses import ulysses_attention
    set_global_mesh(MeshSpec({"seq": 4, "data": 2}, eight_devices))
    rng = np.random.default_rng(15)
    q, k, v = _qkv(rng, 1, 64, 4, 16)
    g1 = jax.jit(jax.grad(lambda *a: ulysses_attention(*a, causal=True).sum(),
                          argnums=(0, 1, 2)))(q, k, v)
    g2 = jax.grad(lambda *a: xla_attention(*a, causal=True).sum(),
                  argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5)


def test_ulysses_head_indivisible_falls_back_to_ring(eight_devices):
    """3 heads on a 4-way seq axis: the Ulysses constraint fails, ring takes over —
    result still matches dense attention."""
    from deepspeed_tpu.ops.attention.ulysses import ulysses_attention
    set_global_mesh(MeshSpec({"seq": 4, "data": 2}, eight_devices))
    rng = np.random.default_rng(16)
    q, k, v = _qkv(rng, 2, 64, 3, 16)
    o1 = jax.jit(lambda *a: ulysses_attention(*a, causal=True))(q, k, v)
    o2 = xla_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), rtol=1e-5, atol=1e-5)
