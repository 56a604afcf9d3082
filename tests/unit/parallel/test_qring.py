"""Fused quantized collective-matmul ring suite (8-virtual-CPU-device mesh).

Pins the contract of ``parallel/qring.py``: the dequant-GEMM ring with an fp
(lossless) wire agrees with the monolithic-psum quantized ground truth to the
last ulp for int8 AND nibble-packed int4 weight slabs (summation order is the
only difference); the intN wire (chunk_bits in {4, 8, 16}) is bounded and
monotone in width, carries error feedback ACROSS ring steps within a
dispatch (threading the residual over repeated dispatches converges the mean
output), and zeroes non-finite values on the wire (overflow gate) so one
poisoned shard's contribution is dropped, never propagated. Wire bytes are
machine-cross-checked: the recorded span, the closed form
``analysis.collectives.qring_wire_bytes``, and the jaxpr ppermute-operand sum
must agree to the byte. Runs inside the tier-1 window (``qring`` marker,
rank 5 in ``TIER1_BUDGETS_S``).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from deepspeed_tpu.analysis.collectives import (crosscheck_findings,
                                                qring_wire_bytes)
from deepspeed_tpu.comm.compressed import (intn_blockwise_compress,
                                           intn_blockwise_decompress,
                                           intn_wire_nbytes)
from deepspeed_tpu.ops.quantizer import (dequantize_grouped, make_quant_node,
                                         pack_int4, quant_dense_apply,
                                         quantize_grouped, unpack_int4)
from deepspeed_tpu.parallel import qring
from deepspeed_tpu.parallel.mesh import AXIS_TENSOR, MeshSpec, set_global_mesh
from deepspeed_tpu.parallel.overlap import OverlapConfig, overlap_scope
from deepspeed_tpu.utils.comms_logging import collective_spans
from deepspeed_tpu.utils.jax_compat import shard_map

pytestmark = pytest.mark.qring


def _build_slab(rng, k, n, bits, group=8):
    """Quantize a random (k, n) weight into a (carrier, scales) slab plus the
    DEQUANTIZED fp matrix — the monolithic ground truth must run over the
    same quantized values or weight-quant error would masquerade as ring
    error."""
    w = (rng.standard_normal((k, n)) * 0.5).astype(np.float32)
    q, s = quantize_grouped(jnp.asarray(w), group_size=group, bits=bits)
    if bits == 4:
        q = pack_int4(q, k // group)
        wd = dequantize_grouped(unpack_int4(q, k // group), s)
    else:
        wd = dequantize_grouped(q, s)
    return q, s, np.asarray(wd)


def _rs_ring(mesh, bits, wire_bits, bidir, quant_block=16, site=None):
    def body(a, b, c):
        out, _ = qring.fused_quant_matmul_reduce_scatter(
            a, b, c, AXIS_TENSOR, bits=bits, wire_bits=wire_bits,
            quant_block=quant_block, bidirectional=bidir, site=site)
        return out
    return shard_map(body, mesh=mesh.mesh, axis_names={AXIS_TENSOR},
                     in_specs=(P(None, AXIS_TENSOR), P(AXIS_TENSOR, None),
                               P(AXIS_TENSOR, None)),
                     out_specs=P(AXIS_TENSOR, None), check_vma=False)


# ------------------------------------------------------------- wire codec
@pytest.mark.parametrize("bits", [4, 8, 16])
def test_intn_codec_roundtrip_and_wire_bytes(bits):
    rng = np.random.default_rng(bits)
    n, block = 100, 16                       # deliberately NOT block-aligned
    flat = jnp.asarray(rng.standard_normal(n) * 3.0, jnp.float32)
    carrier, scales = intn_blockwise_compress(flat, block, bits)
    back = intn_blockwise_decompress(carrier, scales, n, block, bits)
    assert back.shape == (n,)
    # symmetric round-to-nearest: per-element error <= scale/2 of its block
    err = np.abs(np.asarray(back) - np.asarray(flat))
    bound = np.repeat(np.asarray(scales), block)[:n] * 0.5 + 1e-6
    assert (err <= bound).all()
    # the wire-bytes closed form IS the materialized carrier+scales footprint
    assert intn_wire_nbytes(n, block, bits) == \
        np.asarray(carrier).nbytes + np.asarray(scales).nbytes
    # zero blocks must not divide by zero (scale 1 contract)
    z_carrier, z_scales = intn_blockwise_compress(
        jnp.zeros((n,), jnp.float32), block, bits)
    assert np.asarray(z_scales).min() == 1.0
    np.testing.assert_array_equal(
        np.asarray(intn_blockwise_decompress(z_carrier, z_scales, n, block,
                                             bits)), 0.0)


# ----------------------------------------------------- reduce-scatter ring
@pytest.mark.parametrize("tp", [2, 4])
@pytest.mark.parametrize("bits", [8, 4])
@pytest.mark.parametrize("bidir", [False, True])
def test_fused_ring_fp_wire_last_ulp_vs_monolithic(tp, bits, bidir,
                                                   eight_devices):
    """The fused ring with a lossless wire IS the monolithic-psum quantized
    path up to cross-shard summation order — the 'int8 last-ulp' acceptance
    row, for int8 and nibble-packed int4 weight slabs at tp=2/4."""
    mesh = MeshSpec({"tensor": tp}, eight_devices[:tp])
    rng = np.random.default_rng(tp * 10 + bits)
    m, k, n = 8, 32, 12                       # n even: bidir column split
    x = rng.standard_normal((m, k)).astype(np.float32)
    q, s, wd = _build_slab(rng, k, n, bits)
    mono = x @ wd
    out = np.asarray(_rs_ring(mesh, bits, None, bidir)(x, q, s))
    np.testing.assert_allclose(out, mono, rtol=1e-5, atol=1e-5)


def test_ef_residual_across_dispatches_converges(eight_devices):
    """Error feedback across ring steps: threading the residual through
    repeated dispatches makes the MEAN output converge toward the true
    product (the error telescopes), far below the single-shot wire error —
    the contract shared with comm/compressed.py's quantized allreduce."""
    mesh = MeshSpec({"tensor": 2}, eight_devices[:2])
    rng = np.random.default_rng(11)
    m, k, n = 8, 32, 12
    x = rng.standard_normal((m, k)).astype(np.float32)
    q, s, wd = _build_slab(rng, k, n, 8)
    mono = x @ wd

    def body(a, b, c, r):
        return qring.fused_quant_matmul_reduce_scatter(
            a, b, c, AXIS_TENSOR, bits=8, wire_bits=8, quant_block=16,
            bidirectional=False, residual=r)
    f = shard_map(body, mesh=mesh.mesh, axis_names={AXIS_TENSOR},
                  in_specs=(P(None, AXIS_TENSOR), P(AXIS_TENSOR, None),
                            P(AXIS_TENSOR, None), P(AXIS_TENSOR)),
                  out_specs=(P(AXIS_TENSOR, None), P(AXIS_TENSOR)),
                  check_vma=False)
    f = jax.jit(f)                        # one trace, 48 cheap dispatches
    r = jnp.zeros((2 * (m // 2) * n,), jnp.float32)
    outs = []
    for _ in range(48):
        out, r = f(x, q, s, r)
        outs.append(np.asarray(out))
    single = np.linalg.norm(outs[0] - mono)
    mean48 = np.linalg.norm(np.mean(outs, axis=0) - mono)
    assert np.isfinite(np.asarray(r)).all()
    assert mean48 < 0.2 * single


def test_overflow_gate_zeroes_poisoned_wire_contribution(eight_devices):
    """A non-finite partial is zeroed ON THE WIRE (same gate as
    comm/compressed.py): with the quantized wire only the poisoned shard's
    OWN output block (whose contribution is added locally, never wired) stays
    non-finite; the fp wire propagates it into every block it visits."""
    tp = 4
    mesh = MeshSpec({"tensor": tp}, eight_devices[:tp])
    rng = np.random.default_rng(13)
    m, k, n = 8, 32, 12
    x = rng.standard_normal((m, k)).astype(np.float32)
    q, s, _ = _build_slab(rng, k, n, 8)
    s = np.asarray(s).copy()
    gpp = s.shape[0] // tp                   # scale groups per shard
    s[gpp:2 * gpp] = np.inf                  # poison shard 1's slab only
    s = jnp.asarray(s)
    m_blk = m // tp
    bad = slice(1 * m_blk, 2 * m_blk)        # rows block owned by shard 1

    out_q = np.asarray(_rs_ring(mesh, 8, 8, False)(x, q, s))
    assert not np.isfinite(out_q[bad]).all()
    finite_rows = np.ones(m, dtype=bool)
    finite_rows[bad] = False
    assert np.isfinite(out_q[finite_rows]).all()

    out_fp = np.asarray(_rs_ring(mesh, 8, None, False)(x, q, s))
    assert not np.isfinite(out_fp[finite_rows]).all()


# ----------------------------------------------------------- allgather ring
@pytest.mark.parametrize("bidir", [False, True])
def test_fused_allgather_matmul_parity(bidir, eight_devices):
    """fp wire: bit-exact vs the dense product (row blocks are independent
    matmuls over unchanged operands); int8 wire: bounded one-shot error —
    the carrier is forwarded VERBATIM so hops never compound it."""
    tp = 4
    mesh = MeshSpec({"tensor": tp}, eight_devices[:tp])
    rng = np.random.default_rng(17)
    m_loc, k, n = 3, 32, 12
    x = rng.standard_normal((tp * m_loc, k)).astype(np.float32)
    q, s, wd = _build_slab(rng, k, n, 8)
    mono = x @ wd

    def mk(wb):
        def body(a, b, c):
            out, _ = qring.fused_quant_allgather_matmul(
                a, b, c, AXIS_TENSOR, bits=8, wire_bits=wb, quant_block=16,
                bidirectional=bidir)
            return out
        return shard_map(body, mesh=mesh.mesh, axis_names={AXIS_TENSOR},
                         in_specs=(P(AXIS_TENSOR, None), P(None, None),
                                   P(None, None)),
                         out_specs=P(None, None), check_vma=False)

    # bit-exact against the same row-block matmuls done by XLA directly
    # (numpy's BLAS sums in another order, so ``mono`` is last-ulp only)
    blocks = np.concatenate([
        np.asarray(jnp.dot(jnp.asarray(x[i * m_loc:(i + 1) * m_loc]),
                           jnp.asarray(wd),
                           preferred_element_type=jnp.float32))
        for i in range(tp)])
    out_fp = np.asarray(mk(None)(x, q, s))
    np.testing.assert_array_equal(out_fp, blocks)
    np.testing.assert_allclose(out_fp, mono, rtol=1e-5, atol=1e-5)
    out8 = np.asarray(mk(8)(x, q, s))
    assert np.linalg.norm(out8 - mono) / np.linalg.norm(mono) < 0.05


# ------------------------------------------- quant_dense_apply row routing
def test_quant_dense_apply_routes_ring_and_bytes_crosscheck(eight_devices):
    """The serving entry: row-parallel quant nodes route through the fused
    quantized ring exactly when comm_overlap is active — the span flips
    monolithic all_reduce <-> overlapped reduce_scatter, the jaxpr grows/
    loses its ppermutes, and at every chunk_bits the recorded ring bytes
    equal the ``qring_wire_bytes`` closed form; int8/fp32 ring bytes <= 0.3
    at tp=4 (the acceptance ratio), machine-checked end to end by the
    analysis pass. This is ALSO the chunk_bits {4, 8, 16} virtual-mesh
    sweep: each width runs the full serving path with its own error band
    (monotone: wider wire, smaller error) and its own byte accounting."""
    tp = 4
    mesh = MeshSpec({"tensor": tp}, eight_devices[:tp])
    set_global_mesh(mesh)
    rng = np.random.default_rng(19)
    k, n, qb = 32, 256, 64
    w = (rng.standard_normal((k, n)) * 0.5).astype(np.float32)
    q, s = quantize_grouped(jnp.asarray(w), group_size=8, bits=8)
    node = make_quant_node(q, s, 8)
    x = jnp.asarray(rng.standard_normal((2, 6, k)), jnp.float32)
    m = 2 * 6

    collective_spans.reset()
    y_mono = quant_dense_apply(x, node, None, jnp.float32, parallel="row",
                               site="t.row")
    mono_spans = collective_spans.summary()
    assert mono_spans["t.row.monolithic"]["op"] == "all_reduce"
    assert "t.row" not in mono_spans

    ring_bytes = {}
    for cb in (4, 8, 16):
        collective_spans.reset()
        with overlap_scope(OverlapConfig(enabled=True, chunk_bits=cb,
                                         quant_block=qb)):
            y = quant_dense_apply(x, node, None, jnp.float32, parallel="row",
                                  site="t.row")
        spans = collective_spans.summary()
        assert spans["t.row"]["op"] == "reduce_scatter"
        assert spans["t.row"]["overlapped"]
        assert spans["t.row.gather"]["op"] == "all_gather"
        ring_bytes[cb] = spans["t.row"]["bytes_per_call"]
        assert ring_bytes[cb] == qring_wire_bytes(m, n, tp, wire_bits=cb,
                                                  block=qb)
        rel = (np.linalg.norm(np.asarray(y) - np.asarray(y_mono))
               / np.linalg.norm(np.asarray(y_mono)))
        assert rel < {4: 0.5, 8: 0.05, 16: 1e-3}[cb]
    fp_bytes = qring_wire_bytes(m, n, tp, wire_bits=None, block=qb)
    assert ring_bytes[8] / fp_bytes <= 0.3
    assert ring_bytes[4] < ring_bytes[8] < ring_bytes[16]

    # routing is a structural property, not just a span: ppermute in the
    # jaxpr iff the overlap scope is active
    def f_on(xx):
        with overlap_scope(OverlapConfig(enabled=True, quant_block=qb)):
            return quant_dense_apply(xx, node, None, jnp.float32,
                                     parallel="row")

    def f_off(xx):
        with overlap_scope(OverlapConfig(enabled=False)):
            return quant_dense_apply(xx, node, None, jnp.float32,
                                     parallel="row")
    assert "ppermute" in str(jax.make_jaxpr(f_on)(x))
    assert "ppermute" not in str(jax.make_jaxpr(f_off)(x))
    set_global_mesh(None)


def test_crosscheck_pass_agrees_with_span_and_closed_form(eight_devices):
    """Three-way byte agreement on the raw ring primitive: recorded span ==
    closed form == jaxpr ppermute-operand accounting (zero error findings
    from the collective-schema pass) for the int8 AND int4 wires."""
    tp = 4
    mesh = MeshSpec({"tensor": tp}, eight_devices[:tp])
    rng = np.random.default_rng(23)
    m, k, n, qb = 8, 32, 12, 16
    x = rng.standard_normal((m, k)).astype(np.float32)
    q, s, _ = _build_slab(rng, k, n, 8)
    for wb in (8, 4):
        site = f"lint.qring_w{wb}"
        collective_spans.reset()
        res = crosscheck_findings(_rs_ring(mesh, 8, wb, True, qb, site=site),
                                  (x, q, s), site_prefixes=("lint.",),
                                  target=site)
        assert not [f for f in res.findings if f.severity == "error"], \
            [f.message for f in res.findings]
        rec = collective_spans.summary()[site]["bytes_total"]
        assert rec == qring_wire_bytes(m, n, tp, wire_bits=wb, block=qb)
