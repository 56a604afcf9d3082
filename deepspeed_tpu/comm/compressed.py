"""Sign-compressed (1-bit) allreduce with error feedback.

Behavioural equivalent of reference ``deepspeed/runtime/comm/nccl.py``
(``NcclBackend.compressed_allreduce:52``) / ``comm/mpi.py``: each worker ships only the
SIGN of its (error-compensated) tensor plus one L1 scale, cutting collective volume
~32× for the momentum exchange of the 1-bit optimizers.

TPU-native realisation: an in-graph collective for use inside ``shard_map`` over a mesh
axis. Signs are bit-packed into uint8 lanes (8 signs/byte) so the ``all_gather`` actually
moves 1 bit per element over ICI; unpack + scale-weighted mean reconstructs the
compressed average. Error feedback (worker residual carried to the next step) preserves
convergence (1-bit Adam paper, Tang et al. 2021).
"""

from typing import Tuple

import jax
import jax.numpy as jnp

from ..observability import scope


def _pack_bits(bits: jnp.ndarray) -> jnp.ndarray:
    """(n,) bool -> (ceil(n/8),) uint8 bitmask."""
    n = bits.shape[0]
    pad = (-n) % 8
    b = jnp.pad(bits.astype(jnp.uint8), (0, pad)).reshape(-1, 8)
    weights = (2 ** jnp.arange(8, dtype=jnp.uint8))
    return jnp.sum(b * weights, axis=1).astype(jnp.uint8)


def _unpack_bits(packed: jnp.ndarray, n: int) -> jnp.ndarray:
    """(m,) uint8 -> (n,) bool."""
    bits = (packed[:, None] >> jnp.arange(8, dtype=jnp.uint8)[None, :]) & 1
    return bits.reshape(-1)[:n].astype(bool)


def sign_compress(x: jnp.ndarray,
                  error: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Error-compensated 1-bit compression (unpacked): returns
    ``(compressed, new_error)`` with ``compressed + new_error == x + error`` exactly.
    Shared by the 1-bit optimizers (momentum compression) and the wire collective."""
    c = x + error
    scale = jnp.mean(jnp.abs(c))
    compressed = jnp.where(c >= 0, scale, -scale)
    return compressed, c - compressed


def compress_signs(x: jnp.ndarray,
                   error: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray,
                                                jnp.ndarray]:
    """Error-compensated 1-bit compression of a flat fp32 tensor, bit-packed for the
    wire. Returns ``(packed_signs uint8, scale, new_error)`` with
    ``decompress(packed, scale) + new_error == x + error`` exactly.
    """
    c = x + error
    scale = jnp.mean(jnp.abs(c))
    signs = c >= 0
    new_error = c - jnp.where(signs, scale, -scale)
    return _pack_bits(signs), scale, new_error


# ------------------------------------------------ intN blockwise (EQuARX)
# The multi-bit siblings of the sign collective above (EQuARX, arxiv
# 2506.17615): per-block absmax scales instead of one global L1 scale, an
# int4/int8/int16 payload instead of packed signs — 7.8x/3.9x/2x wire
# reduction at graded fidelity, with the SAME error-feedback contract as
# sign_compress so the widths compose with (rather than replace) each other:
# transmitted + new_error == x + error. bits=8 is the original EQuARX wire
# used by the DP gradient sync; the fused quantized ring
# (``parallel/qring.py``) selects the width via ``comm_overlap.chunk_bits``.

#: Supported quantized-wire widths (``comm_overlap.chunk_bits``).
WIRE_BITS = (4, 8, 16)

_WIRE_QMAX = {4: 7.0, 8: 127.0, 16: 32767.0}


def intn_wire_nbytes(n_elems: int, block: int = 256, bits: int = 8) -> int:
    """Exact wire footprint of one compressed tensor: carrier payload (int4
    nibble-packed into int8, int8, or int16 — always over the block-padded
    length) plus one fp32 scale per block. This is the SAME arithmetic the
    jaxpr schema pass (``analysis/collectives.py``) recovers from the operand
    avals, so spans recorded with it cross-check exactly."""
    n_pad = -(-n_elems // block) * block
    payload = {4: n_pad // 2, 8: n_pad, 16: 2 * n_pad}[bits]
    return payload + (n_pad // block) * 4


def intn_blockwise_compress(flat: jnp.ndarray, block: int = 256,
                            bits: int = 8) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(n,) f32 → (carrier, scales f32 (n_pad/block,)); symmetric absmax per
    block (``scale = absmax/qmax``, zero blocks get scale 1). Carrier: int8
    (n_pad,) for bits=8, int16 (n_pad,) for bits=16, adjacent-pair
    nibble-packed int8 (n_pad/2,) for bits=4 (``block`` must be even)."""
    qmax = _WIRE_QMAX[bits]
    n = flat.shape[0]
    pad = (-n) % block
    fb = jnp.pad(flat, (0, pad)).reshape(-1, block)
    amax = jnp.max(jnp.abs(fb), axis=1, keepdims=True)
    scale = jnp.where(amax > 0, amax / qmax, 1.0)
    q = jnp.clip(jnp.round(fb / scale), -qmax, qmax)
    if bits == 16:
        return q.astype(jnp.int16).reshape(-1), scale[:, 0]
    q = q.astype(jnp.int8).reshape(-1)
    if bits == 4:
        # two nibbles per byte, adjacent pairs (n_pad is even: block is);
        # arithmetic >> sign-extends on unpack, same idiom as quant.pack_int4
        half = q.reshape(-1, 2)
        q = ((half[:, 1] << 4) | (half[:, 0] & 0xF)).astype(jnp.int8)
    return q, scale[:, 0]


def intn_blockwise_decompress(q: jnp.ndarray, scales: jnp.ndarray, n: int,
                              block: int = 256, bits: int = 8) -> jnp.ndarray:
    """Inverse of :func:`intn_blockwise_compress` (drops the pad)."""
    if bits == 4:
        lo = ((q << 4) >> 4).astype(jnp.int8)
        hi = (q >> 4).astype(jnp.int8)
        q = jnp.stack([lo, hi], axis=1).reshape(-1)
    fb = q.reshape(-1, block).astype(jnp.float32) * scales[:, None]
    return fb.reshape(-1)[:n]


def int8_blockwise_compress(flat: jnp.ndarray, block: int = 256
                            ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(n,) f32 → (q int8 (n_pad,), scales f32 (n_pad/block,)); the bits=8
    specialisation of :func:`intn_blockwise_compress` (kept as the named
    EQuARX wire the 1-bit machinery composes with)."""
    return intn_blockwise_compress(flat, block, 8)


def int8_blockwise_decompress(q: jnp.ndarray, scales: jnp.ndarray, n: int,
                              block: int = 256) -> jnp.ndarray:
    """Inverse of :func:`int8_blockwise_compress` (drops the pad)."""
    return intn_blockwise_decompress(q, scales, n, block, 8)


def quantized_allreduce(x: jnp.ndarray, error: jnp.ndarray, axis_name: str,
                        block: int = 256, bits: int = 8
                        ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    with scope("comm.quantized_allreduce"):
        return _quantized_allreduce(x, error, axis_name, block, bits)


def _quantized_allreduce(x: jnp.ndarray, error: jnp.ndarray, axis_name: str,
                         block: int = 256, bits: int = 8
                         ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Error-compensated intN blockwise mean over ``axis_name`` (call inside
    ``shard_map``); returns ``(replicated quantized mean, new local error)``.
    ``bits`` selects the wire width (:data:`WIRE_BITS`; default int8 = the
    original EQuARX wire).

    Two-phase, EQuARX-shaped, so per-worker wire volume stays O(n) at any
    world size (a naive gather-then-sum moves ``(W-1)·n`` — MORE than fp32
    beyond W≈8):

    1. **reduce-scatter phase**: each worker quantizes its contribution and
       ``all_to_all``s int8 chunk ``p`` (+ its scales) to worker ``p``, which
       dequantizes and sums its owned chunk in fixed rank order
       (deterministic);
    2. **gather phase**: the owned mean chunk is RE-quantized to int8 and
       ``all_gather``ed, so the wire stays 8-bit both ways.

    Both quantization stages are error-fed-back: phase 1 into this worker's
    residual everywhere, phase 2 (whose error is shared by construction) into
    the OWNED chunk's residual scaled by ``W`` — the next round's mean dilutes
    it back by ``1/W``, preserving the cumulative-transmission EF contract
    shared with :func:`compress_signs`.

    Non-finite inputs (fp16 overflow) are zeroed BEFORE quantization so a
    single inf cannot poison the int8 cast or the residual — the caller
    detects overflow from the pre-quantization values and skips the step.

    Collective volume per worker per phase: ``(W-1)/W ·
    intn_wire_nbytes(n)`` (intN payload + fp32 block scales) — at block=256
    that is ~7.8x/3.9x/2x under the full-precision ring allreduce
    (``8n·(W-1)/W``) for bits=4/8/16.
    """
    shape = x.shape
    flat = x.reshape(-1).astype(jnp.float32)
    err = error.reshape(-1).astype(jnp.float32)
    c = flat + err
    c = jnp.where(jnp.isfinite(c), c, 0.0)
    n = flat.shape[0]
    W = jax.lax.psum(1, axis_name)
    if W == 1:
        q, scales = intn_blockwise_compress(c, block, bits)
        deq = intn_blockwise_decompress(q, scales, n, block, bits)
        return deq.reshape(shape), (c - deq).reshape(shape)

    # pad so payload AND scale vectors split evenly across the W ranks
    n_pad = -((-n) // (block * W)) * (block * W)
    cp = jnp.pad(c, (0, n_pad - n))
    q, scales = intn_blockwise_compress(cp, block, bits)  # carrier, (n_pad/block,)
    chunk = n_pad // W
    bpc = (n_pad // block) // W                     # scale blocks per chunk
    # phase 1: rank p ends holding every rank's chunk p (intN on the wire;
    # the carrier splits evenly: chunk is a block multiple and block is even)
    qx = jax.lax.all_to_all(q.reshape(W, -1), axis_name, 0, 0, tiled=True)
    sx = jax.lax.all_to_all(scales.reshape(W, bpc), axis_name, 0, 0,
                            tiled=True)
    part = jax.vmap(
        lambda qq, ss: intn_blockwise_decompress(qq, ss, chunk, block, bits)
    )(qx, sx)
    mean_chunk = jnp.sum(part, axis=0) / W
    # phase 2: re-quantize the owned mean chunk, gather carrier + scales
    q2, s2 = intn_blockwise_compress(mean_chunk, block, bits)
    deq_chunk = intn_blockwise_decompress(q2, s2, chunk, block, bits)
    qg = jax.lax.all_gather(q2, axis_name, axis=0, tiled=True)
    sg = jax.lax.all_gather(s2, axis_name, axis=0, tiled=True)
    mean = intn_blockwise_decompress(qg, sg, n, block, bits)
    # error feedback: phase-1 everywhere, phase-2 at the owned chunk ×W
    r = cp - intn_blockwise_decompress(q, scales, n_pad, block, bits)
    idx = jax.lax.axis_index(axis_name)
    r = jax.lax.dynamic_update_slice(
        r, jax.lax.dynamic_slice(r, (idx * chunk,), (chunk,))
        + W * (mean_chunk - deq_chunk), (idx * chunk,))
    return mean.reshape(shape), r[:n].reshape(shape)


def compressed_allreduce(x: jnp.ndarray, error: jnp.ndarray,
                         axis_name: str) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """1-bit mean over ``axis_name`` (call inside ``shard_map``); returns
    ``(mean of compressed worker tensors, new local error)``.

    Collective volume: n/8 bytes of signs + 4 bytes of scale per worker (vs 4n bytes
    for a full fp32 allreduce).
    """
    shape = x.shape
    flat = x.reshape(-1).astype(jnp.float32)
    err = error.reshape(-1).astype(jnp.float32)
    packed, scale, new_error = compress_signs(flat, err)
    gathered = jax.lax.all_gather(packed, axis_name)      # (W, n/8) uint8
    scales = jax.lax.all_gather(scale, axis_name)         # (W,)
    n = flat.shape[0]
    signs = jax.vmap(lambda p: _unpack_bits(p, n))(gathered)  # (W, n) bool
    avg = jnp.mean(jnp.where(signs, scales[:, None], -scales[:, None]), axis=0)
    return avg.reshape(shape), new_error.reshape(shape)
