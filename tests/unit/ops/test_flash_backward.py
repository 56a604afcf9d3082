"""The flash backward pair (``flash_bwd_dq``, which makes ``delta`` from ``do`` and ``o``
and hands it on, and ``flash_bwd_dkv``) against the XLA attention's gradients, on every
branch of the operand layout. A file of its own, beside ``test_attention.py``: the cases
compile three interpreted kernels each, and a file is what a test worker is given."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops.transformer.attention import xla_attention

# (heads, d_head) by the branch they take, as ``test_attention.py: HEAD_SHAPES``
HEAD_SHAPES = [(4, 64), (4, 32), (2, 128), (3, 64), (2, 48)]
HEAD_IDS = ["two-a-block", "four-a-block", "one-a-block", "odd-count-folded", "d48-folded"]


def _qkv(rng, b, t, h, d):
    return tuple(jnp.asarray(rng.normal(size=(b, t, h, d)).astype(np.float32))
                 for _ in range(3))


# (batch, t, block_q, block_k) of the backward pair's gradient test: one block a head;
# two q and two kv blocks, so ``delta``'s block stays put over the kv axis while dq is
# summed (and a causal call skips one pass); q blocks half the kv blocks' size, where
# the diagonal enters a block at an offset
GRAD_BLOCKS = {"one-block": (2, 256, 256, 256), "two-by-two": (2, 256, 128, 128),
               "unequal": (2, 256, 64, 128)}
DTYPE_IDS = {jnp.float32: "f32", jnp.bfloat16: "bf16"}
# the two cases test_flash_grads_match_xla had and test_flash_alibi_grads_match_xla's:
# 128 tokens in blocks of 64, two float32 heads of 16 (the folded call)
_GRAD_CASES = [pytest.param(2, 16, kind, (1, 128, 64, 64), "split", jnp.float32,
                            id=f"two-of-16-{kind}-blocks-of-64-split-f32")
               for kind in ("causal", "full", "alibi")] + [
    pytest.param(h, d, kind, shape, entry, dtype,
                 id=f"{head}-{kind}-{blocks}-{entry}-{DTYPE_IDS[dtype]}")
    for (h, d), head in zip(HEAD_SHAPES, HEAD_IDS)
    for kind in ("causal", "full", "alibi", "mask_block")
    for blocks, shape in GRAD_BLOCKS.items() for entry in ("split", "fused")
    for dtype in DTYPE_IDS
    # one operand q | k | v is the flat layout's
    if entry == "split" or head in HEAD_IDS[:3]]


def _backward_pair_errors(h, d, kind, shape, entry, dtype):
    """``_flash_bwd``'s (dq, dk, dv) against the XLA attention's ``jax.vjp`` in
    float32, each as its largest error over the gradient's largest entry. The
    cotangent differs from entry to entry and the values sit at 1.5, so ``o`` is far
    from zero and ``delta`` (the rows' sums of ``do * o``) is large beside ``dp``: a
    ``delta`` that is wrong, or another row's or head's, cannot pass."""
    from deepspeed_tpu.models.causal_lm import (_alibi_attention_xla, alibi_slopes,
                                                block_causal_mask)
    from deepspeed_tpu.ops.attention import flash
    b, t, bq, bk = shape
    rng = np.random.default_rng(31)
    q, k, v = (x.astype(dtype) for x in _qkv(rng, b, t, h, d))
    v = (v.astype(jnp.float32) + 1.5).astype(dtype)
    do = jnp.asarray(rng.normal(size=(b, t, h, d)).astype(np.float32)).astype(dtype)
    slopes = jnp.asarray(alibi_slopes(4)[:h]) if kind == "alibi" else None
    mask_block = 4 if kind == "mask_block" else 1
    if kind == "alibi":
        def ref(q, k, v):
            return _alibi_attention_xla(q, k, v, slopes)
    elif kind == "mask_block":
        def ref(q, k, v):
            return xla_attention(q, k, v, causal=False,
                                 mask=jnp.asarray(block_causal_mask(t, 4))[None, None])
    else:
        ref = functools.partial(xla_attention, causal=kind != "full")
    want = jax.vjp(ref, *(x.astype(jnp.float32) for x in (q, k, v)))[1](
        do.astype(jnp.float32))

    if flash.heads_a_block(h, d):
        def lay(x):
            return x.reshape(b, t, h * d)

        def back(x):
            return x.reshape(b, t, h, d)
    else:
        def lay(x):
            return x.transpose(0, 2, 1, 3).reshape(b * h, t, d)

        def back(x):
            return x.reshape(b, h, t, d).transpose(0, 2, 1, 3)
    fused = entry == "fused"
    operands = [lay(x) for x in (q, k, v)]
    if fused:
        operands = [jnp.concatenate(operands, axis=-1)] * 3
    static = (None if slopes is None else flash._slopes_tiles(slopes), fused, d,
              d ** -0.5, kind != "full", bq, bk, mask_block)
    o, lse = flash._flash_fwd(*operands, *static)
    got = flash._flash_bwd(*operands, o, lse, lay(do), *static)
    errors = []
    for a, w in zip(got, want):
        assert a.dtype == dtype and a.shape == o.shape
        a, w = np.asarray(back(a), np.float32), np.asarray(w)
        errors.append(float(np.abs(a - w).max() / np.abs(w).max()))
    return errors


def _grad_tol(dtype):
    return 2e-5 if dtype == jnp.float32 else 2e-2


@pytest.mark.parametrize("h,d,kind,shape,entry,dtype", _GRAD_CASES)
def test_flash_backward_pair_matches_xla(h, d, kind, shape, entry, dtype):
    """dq, dk and dv of the two backward kernels on every branch of the operand
    layout, alone in a block or over several, with q, k and v apart or in one fused
    operand."""
    assert max(_backward_pair_errors(h, d, kind, shape, entry, dtype)) < _grad_tol(dtype)


@pytest.mark.parametrize("dtype", DTYPE_IDS, ids=DTYPE_IDS.values())
def test_a_backward_pair_whose_delta_is_zero_fails_by_far(monkeypatch, dtype):
    """The planted fault: with the kernel's ``delta`` forced to zero, dq and dk (dv
    does not read it) miss the reference by more than ten times the tolerance."""
    from deepspeed_tpu.ops.attention import flash
    monkeypatch.setattr(flash, "_head_row_sums",
                        lambda x, hh, d: jnp.zeros((x.shape[0], 1), x.dtype))
    dq, dk, dv = _backward_pair_errors(4, 64, "causal", GRAD_BLOCKS["two-by-two"], "fused",
                                       dtype)
    assert min(dq, dk) > 10 * _grad_tol(dtype) > dv
