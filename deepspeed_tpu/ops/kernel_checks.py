"""Compiled-kernel parity checks — ONE source of shapes and tolerances.

Shared by the real-TPU test lane (``tests/unit/ops/test_kernels_tpu.py``)
and ``chip_smoke.py``, which runs the whole table on the chip, so they
cannot drift: a Mosaic regression that fails one fails both identically. Each check
compiles the Pallas kernel (no interpret mode) and compares against its XLA
reference; thresholds are per-check, matched to the check's dtype.
"""

from functools import partial
from typing import Dict, Iterable, Optional, Tuple

import numpy as np


def _err(a, b) -> float:
    import jax.numpy as jnp
    return float(jnp.max(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32))))


def _flash_fwd_err(q, k, v) -> float:
    import jax
    from .attention.flash import flash_attention
    from .transformer.attention import xla_attention
    o1 = jax.jit(lambda *a: flash_attention(*a, causal=True))(q, k, v)
    return _err(o1, xla_attention(q, k, v, causal=True))


def check_flash_fwd() -> float:
    import jax.numpy as jnp
    rng = np.random.RandomState(0)
    return _flash_fwd_err(*(jnp.asarray(rng.standard_normal((2, 1024, 4, 64)),
                                        jnp.float32) for _ in range(3)))


def _flash_train_qkv():
    """q, k, v at the training benchmark's own attention shape (``gpt2-125m.seq1k``:
    seq 1024, d_head 64, bf16): one 1024 block a head, which the kernels walk in
    sub-tiles — the path a 512-token check never reaches."""
    import jax.numpy as jnp
    rng = np.random.RandomState(1)
    return tuple(jnp.asarray(rng.standard_normal((2, 1024, 4, 64)), jnp.bfloat16)
                 for _ in range(3))


def check_flash_fwd_bf16() -> float:
    return _flash_fwd_err(*_flash_train_qkv())


def check_flash_bwd() -> float:
    import jax
    import jax.numpy as jnp
    from .attention.flash import flash_attention
    from .transformer.attention import xla_attention
    q, k, v = _flash_train_qkv()
    g1 = jax.jit(jax.grad(lambda *a: flash_attention(
        *a, causal=True).astype(jnp.float32).sum(), argnums=(0, 1, 2)))(q, k, v)
    g2 = jax.jit(jax.grad(lambda *a: xla_attention(
        *a, causal=True).astype(jnp.float32).sum(), argnums=(0, 1, 2)))(q, k, v)
    return max(_err(a, b) for a, b in zip(g1, g2))


def check_flash_fused_qkv() -> float:
    """The fused projection (b, t, 3*h*d) read as ONE operand at the training shape
    (12 heads of 64, two a block) against split + ``flash_attention``: the output
    and the gradient of the operand."""
    import jax
    import jax.numpy as jnp
    from .attention.flash import flash_attention, flash_attention_qkv
    b, t, h, d = 2, 1024, 12, 64
    rng = np.random.RandomState(7)
    qkv = jnp.asarray(rng.standard_normal((b, t, 3 * h * d)), jnp.bfloat16)
    w = jnp.asarray(rng.standard_normal((b, t, h * d)), jnp.float32)

    def split(x):
        q, k, v = (y.reshape(b, t, h, d) for y in jnp.split(x, 3, axis=-1))
        return flash_attention(q, k, v, causal=True).reshape(b, t, h * d)

    def fused(x):
        return flash_attention_qkv(x, h, causal=True)

    def grad(fn):
        return jax.jit(jax.grad(lambda x: (fn(x).astype(jnp.float32) * w).sum()))(qkv)

    return max(_err(jax.jit(fused)(qkv), jax.jit(split)(qkv)),
               _err(grad(fused), grad(split)))


def check_flash_alibi() -> float:
    import jax
    import jax.numpy as jnp
    from ..models.causal_lm import _alibi_attention_xla, alibi_slopes
    from .attention.flash import flash_attention
    rng = np.random.RandomState(2)
    q, k, v = (jnp.asarray(rng.standard_normal((2, 512, 4, 64)), jnp.bfloat16)
               for _ in range(3))
    slopes = jnp.asarray(alibi_slopes(4))
    o1 = jax.jit(lambda *a: flash_attention(*a, causal=True,
                                            alibi_slopes=slopes))(q, k, v)
    return _err(o1, _alibi_attention_xla(q, k, v, slopes))


def check_decode() -> float:
    import jax
    import jax.numpy as jnp
    from .attention.decode import decode_attention, decode_attention_xla
    rng = np.random.RandomState(0)
    b, h, hk, d, T = 4, 16, 4, 128, 2048
    q = jnp.asarray(rng.standard_normal((b, h, d)), jnp.bfloat16)
    kc = jnp.asarray(rng.standard_normal((b, hk, T, d)), jnp.bfloat16)
    vc = jnp.asarray(rng.standard_normal((b, hk, T, d)), jnp.bfloat16)
    lens = jnp.asarray(rng.randint(100, T, size=(b,)), jnp.int32)
    o1 = jax.jit(decode_attention)(q, kc, vc, lens)
    err = _err(o1, decode_attention_xla(q, kc, vc, lens))
    # rows of two d 64 heads (``paged_attention.heads_per_row``; XLA's path on
    # the chip) at lfm2-8b-a1b.conv32's shape, against a head a row: what the
    # CPU cannot hold, how the chip's compiler lowers the packing of the queries
    from .paged_attention import kv_rows
    b, h, hk, d = 32, 32, 8, 64
    q = jnp.asarray(rng.standard_normal((b, h, d)), jnp.bfloat16)
    k, v = (jnp.asarray(rng.standard_normal((b, T, hk, d)), jnp.bfloat16)
            for _ in range(2))
    lens = jnp.asarray(rng.randint(40, T, size=(b,)), jnp.int32)
    rows = jax.jit(decode_attention)(q, kv_rows(k, 2), kv_rows(v, 2), lens)
    return max(err, _err(rows, decode_attention_xla(q, kv_rows(k, 1), kv_rows(v, 1), lens)))


def check_decode_live() -> float:
    """The served XLA form (``decode_attention_live``: blocks up to the batch's
    longest length, an online softmax) against the plain whole-cap form, as
    the chip's compiler lowers both: at bloom-7b1's shape with ALiBi, and at
    rows of two d 64 heads (lfm2-8b-a1b.conv32's) against a head a row, with
    lengths on, one under and one over a block's edge. And what serving
    parity leans on: a sequence's bits do not move with the batch's trip
    count (its neighbour one block long, then the whole cap)."""
    import jax.numpy as jnp
    from ..models.causal_lm import alibi_slopes
    from .attention.decode import (decode_attention_live, decode_attention_xla,
                                   live_block)
    from .paged_attention import kv_rows
    rng = np.random.RandomState(0)
    b, h, d, T = 2, 32, 128, 576
    B = live_block(T)
    q = jnp.asarray(rng.standard_normal((b, h, d)), jnp.bfloat16)
    kc, vc = (jnp.asarray(rng.standard_normal((b, h, T, d)), jnp.bfloat16)
              for _ in range(2))
    slopes = jnp.asarray(alibi_slopes(h))
    live = lambda *a: decode_attention_live(*a, None, slopes)   # jitted itself
    err = 0.0
    for lens in ([B - 1, B + 1], [B, 3 * B + 7], [69, T]):
        lens = jnp.asarray(lens, jnp.int32)
        err = max(err, _err(live(q, kc, vc, lens),
                            decode_attention_xla(q, kc, vc, lens, None, slopes)))
    short = live(q, kc, vc, jnp.asarray([70, 75], jnp.int32))
    beside = live(q, kc, vc, jnp.asarray([70, T], jnp.int32))
    if not bool(jnp.array_equal(short[0], beside[0])):
        return float("inf")
    b, h, hk, d, T = 32, 32, 8, 64, 2048
    B = live_block(T)
    q = jnp.asarray(rng.standard_normal((b, h, d)), jnp.bfloat16)
    k, v = (jnp.asarray(rng.standard_normal((b, T, hk, d)), jnp.bfloat16)
            for _ in range(2))
    edges = [B - 1, B, B + 1, 2 * B - 1, 2 * B + 1, 1, T, 405]
    lens = jnp.asarray(edges + list(rng.randint(40, 1100, size=b - len(edges))), jnp.int32)
    rows = decode_attention_live(q, kv_rows(k, 2), kv_rows(v, 2), lens)
    return max(err, _err(rows, decode_attention_xla(q, kv_rows(k, 1), kv_rows(v, 1), lens)))


def check_block_sparse() -> float:
    import jax
    import jax.numpy as jnp
    from .attention.block_sparse import (block_sparse_attention,
                                         block_sparse_attention_reference)
    from .sparse_attention import FixedSparsityConfig
    rng = np.random.RandomState(0)
    cfg = FixedSparsityConfig(num_heads=4, block=128, num_local_blocks=2)
    layout = np.asarray(cfg.make_layout(1024))
    q, k, v = (jnp.asarray(rng.standard_normal((2, 1024, 4, 128)), jnp.bfloat16)
               for _ in range(3))
    o = jax.jit(lambda *a: block_sparse_attention(
        *a, layout=layout, block=128, causal=True))(q, k, v)
    return _err(o, block_sparse_attention_reference(q, k, v, layout, 128,
                                                    causal=True))


def check_moe_decode_ffn() -> float:
    import jax
    import jax.numpy as jnp
    from .moe.decode_ffn import moe_decode_ffn, moe_decode_ffn_xla
    rng = np.random.RandomState(3)
    e, d, f, n = 8, 768, 3072, 4
    x = jnp.asarray(rng.standard_normal((n, d)), jnp.bfloat16)
    w1 = jnp.asarray(rng.standard_normal((e, d, f)) * d ** -0.5, jnp.bfloat16)
    b1 = jnp.asarray(rng.standard_normal((e, f)) * 0.02, jnp.bfloat16)
    w2 = jnp.asarray(rng.standard_normal((e, f, d)) * f ** -0.5, jnp.bfloat16)
    b2 = jnp.asarray(rng.standard_normal((e, d)) * 0.02, jnp.bfloat16)
    idx = jnp.asarray(rng.randint(0, e, size=(n,)), jnp.int32)
    act = jax.nn.gelu
    o1 = jax.jit(lambda *a: moe_decode_ffn(*a, act=act))(x, idx, w1, b1, w2, b2)
    return _err(o1, moe_decode_ffn_xla(x, idx, w1, b1, w2, b2, act))


def check_moe_grouped_ffn(tokens: int = 32, held: int = 128, of: int = 512,
                          k: int = 22, lat: int = 1024, f: int = 2688,
                          gated: bool = False) -> float:
    """The grouped expert kernel at the serving benchmark's shapes (128 held
    experts of 512, 22 a token, latent 1024 -> 2688 -> 1024, bf16, squared
    ReLU): 32 tokens is a decode step of 32 slots (tiles of 16 rows), 512 a
    prompt bucket (tiles of 32); with ``gated`` the three-matrix SiLU form.
    Against the ``jax.numpy`` form on the same plan, over the rows of the
    LIVE tiles: a tile past the last real one is neither fetched nor written
    (the kernel's cost follows what is held, not the worst case the shapes
    allow), so its rows hold whatever the memory held and nothing reads
    them. The reference copies a tile's expert, so it walks the live tiles a
    GiB of copies at a time."""
    import jax
    import jax.numpy as jnp
    from ..moe.latent_moe import relu2
    from .moe.grouped_ffn import (dispatch_plan, grouped_ffn, grouped_ffn_xla,
                                  tile_rows)
    rng = np.random.RandomState(6)
    idx = jnp.asarray(np.stack([rng.permutation(of)[:k] for _ in range(tokens)]),
                      jnp.int32)
    z = jnp.asarray(rng.standard_normal((tokens, lat)), jnp.bfloat16)
    w1 = jax.random.normal(jax.random.PRNGKey(1), (held, lat, f), jnp.bfloat16) * lat ** -0.5
    w2 = jax.random.normal(jax.random.PRNGKey(2), (held, f, lat), jnp.bfloat16) * f ** -0.5
    wg = jax.random.normal(jax.random.PRNGKey(3), (held, lat, f), jnp.bfloat16) \
        * lat ** -0.5 if gated else None
    act = jax.nn.silu if gated else relu2
    tm = tile_rows(tokens * k, of)
    plan = jax.jit(partial(dispatch_plan, first=0, count=held, tm=tm))(idx)
    x_rows, te, tv = z[plan["row_token"]], plan["tile_expert"], plan["tile_valid"]
    got = jax.jit(partial(grouped_ffn, act=act, tm=tm))(x_rows, te, tv, w1, w2,
                                                        w_gate=wg)
    live = int(tv.sum())
    per = max(1, 2 ** 30 // ((2 + gated) * lat * f * 2))
    want = jax.jit(partial(grouped_ffn_xla, act=act, tm=tm))
    err = 0.0
    for t in range(0, live, per):
        n = min(per, live - t)
        rows = slice(t * tm, (t + n) * tm)
        err = max(err, _err(got[rows], want(x_rows[rows], te[t:t + n], tv[t:t + n],
                                            w1, w2, w_gate=wg)))
    return err


def _check_qmm(bits: int, m: int) -> float:
    """Fused dequant GEMM at a 7B projection's shape (k = n = 4096, group
    128): ``m`` = 8 is the decode regime (one row block), 512 the m-blocked
    prefill regime. Error is relative to the output's scale (|y| ~ sqrt(k))."""
    import jax
    import jax.numpy as jnp
    from .quantizer.fused_matmul import (_block_config, quantized_matmul,
                                         quantized_matmul_xla)
    from .quantizer.quant import pack_int4, quantize_grouped
    rng = np.random.RandomState(5 + bits)
    k = n = 4096
    x = jnp.asarray(rng.standard_normal((m, k)), jnp.bfloat16)
    w = jnp.asarray(rng.standard_normal((k, n)), jnp.float32)
    q, s = quantize_grouped(w, group_size=128, bits=bits)
    if bits == 4:
        q = pack_int4(q, s.shape[-2])
    if _block_config(m, k, n, bits, 128, interpret=False) is None:
        raise RuntimeError(f"quantized_matmul would route m={m} bits={bits} "
                           "to its XLA fallback — nothing to check")
    y = jax.jit(lambda *a: quantized_matmul(*a, bits=bits,
                                            out_dtype=jnp.float32))(x, q, s)
    ref = quantized_matmul_xla(x, q, s, bits=bits, out_dtype=jnp.float32)
    return _err(y, ref) / float(np.sqrt(k))


# name → (check fn, max-abs-err tolerance for the check's dtype/shape)
KERNEL_CHECKS: Dict[str, Tuple] = {
    "flash_fwd": (check_flash_fwd, 0.02),       # fp32
    "flash_fwd_bf16": (check_flash_fwd_bf16, 0.05),  # bf16, training shape
    "flash_bwd": (check_flash_bwd, 0.05),       # bf16 grads, training shape
    "flash_fused_qkv": (check_flash_fused_qkv, 0.05),  # bf16 out + grad, training shape
    "flash_alibi": (check_flash_alibi, 0.05),   # bf16
    "decode": (check_decode, 0.03),             # bf16
    "decode_live": (check_decode_live, 0.03),   # bf16; XLA's served form
    "block_sparse": (check_block_sparse, 0.03),  # bf16
    "moe_decode_ffn": (check_moe_decode_ffn, 0.03),  # bf16
    # bf16 operands, f32 accumulation and f32 out on both sides: the kernel
    # and the reference make the same MXU products per row (the chip read 0.0,
    # PR 27), so the tolerance is a bf16 step of an output near 4, not a model
    "moe_grouped_ffn_decode": (check_moe_grouped_ffn, 0.03),
    "moe_grouped_ffn_prefill": (partial(check_moe_grouped_ffn, tokens=512), 0.03),
    # sarvam-105b.doc4k32's prefill: 4,096 tokens x 8, 16 held of 128 (256
    # rows an expert: tiles of 128), gated 4096 x 2048 in two width blocks,
    # ~1 tile in 7 live
    "moe_grouped_ffn_long_prefill": (partial(
        check_moe_grouped_ffn, tokens=4096, held=16, of=128, k=8, lat=4096,
        f=2048, gated=True), 0.03),
    # bf16 activations x dequantized weights, f32 accumulate; relative to
    # the output scale sqrt(k): the kernel rounds w to bf16 before the dot,
    # the reference keeps it f32
    "qmm_int8_decode": (partial(_check_qmm, bits=8, m=8), 0.02),
    "qmm_int8_prefill": (partial(_check_qmm, bits=8, m=512), 0.02),
    "qmm_int4_decode": (partial(_check_qmm, bits=4, m=8), 0.02),
    "qmm_int4_prefill": (partial(_check_qmm, bits=4, m=512), 0.02),
}


#: checks of a served XLA form: they compile NO Mosaic kernel (``chip_smoke.py``'s
#: gate holds every other check to at least one)
XLA_FORMS = frozenset({"decode_live"})


def run_kernel_checks(names: Optional[Iterable[str]] = None) -> Dict[str, float]:
    """Run the named checks (all by default); returns {name: max_abs_err}.
    Raises RuntimeError listing every check whose error exceeds its tolerance."""
    errs, bad = {}, {}
    for name in (names or KERNEL_CHECKS):
        fn, tol = KERNEL_CHECKS[name]
        errs[name] = fn()
        if not (errs[name] < tol):      # NaN-safe
            bad[name] = (errs[name], tol)
    if bad:
        raise RuntimeError(f"kernel checks FAILED (err, tol): {bad}")
    return errs
