"""Decomposed collectives for comm-compute overlap on the TP/MoE hot paths.

Monolithic ``lax.all_gather`` / ``lax.psum`` around a tensor-parallel matmul
serialize ICI traffic behind the MXU: the chip computes, then it communicates.
The T3 line of work (arxiv 2401.16677) and XLA's own ``collective_matmul`` pass
show the same matmul decomposed into ``tp`` ring steps hides most of the
collective: while chunk ``i`` transfers over ICI, chunk ``i-1`` multiplies.
This module provides those decomposed primitives plus the config plumbing that
turns them on behind the ``"comm_overlap"`` config block:

- :func:`chunked_allgather_matmul` — ``all_gather(x) @ w`` as a ``ppermute``
  ring; each output row-block is produced by exactly one matmul over unchanged
  operands, so it is **bit-identical** to the monolithic form.
- :func:`chunked_matmul_reduce_scatter` — ``psum_scatter(x @ w)`` as a ring of
  (block matmul + accumulate) steps; the cross-shard summation order is the
  ring visit order, so results match the monolithic form up to fp summation
  order (exact in integer/exact-representable cases; last-ulp in bf16/fp32).
- bidirectional variants of both (chunks travel both ICI directions at once —
  half the serial latency, both links busy).
- :func:`row_parallel_dense_apply` / :class:`RowParallelDense` — GSPMD-callable
  row-parallel dense (the ``o_proj``/``fc_out`` allreduce sites) that lowers to
  matmul-reduce-scatter + all-gather inside a ``shard_map`` when overlap is
  enabled, with an exact-numerics monolithic fallback otherwise.
- :func:`chunked_expert_exchange` — the MoE dispatch/combine a2a split into
  capacity chunks so each chunk's ICI exchange overlaps the previous chunk's
  expert FFN (bitwise-exact: the FFN is per-token and the combine einsum stays
  whole).

The quantized-collective half of the config block (``quantized_allreduce``,
EQuARX-style intN blockwise psum for DP gradient sync, arxiv 2506.17615) lives
in ``comm/compressed.py`` next to the 1-bit machinery it composes with; the
engine consumes it directly. The composition of BOTH halves — the ppermute
ring with a quantized wire payload and a dequant-GEMM per ring step (serving
TP decode over quantized weights) — lives in ``parallel/qring.py``.

Every decomposed/monolithic call site records a trace-time bytes-on-wire span
(``utils.comms_logging.collective_spans``) so MonitorMaster and the tests
can report collective volume and overlap ratio.
"""

import contextlib
import dataclasses
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from ..observability import scope
from ..utils.comms_logging import record_collective
from ..utils.jax_compat import shard_map
from .mesh import AXIS_PIPE, AXIS_SEQ, AXIS_TENSOR, BATCH_AXES, get_global_mesh


# --------------------------------------------------------------------- config
@dataclasses.dataclass(frozen=True)
class OverlapConfig:
    """Parsed ``"comm_overlap"`` config block.

    - ``enabled``: master switch; everything below is inert without it.
    - ``collective_matmul``: decomposed (chunked, ppermute-ring) TP matmuls +
      chunked MoE dispatch/combine.
    - ``quantized_allreduce``: intN blockwise-scaled DP gradient sync with
      error feedback (plain-DP regime only; see ``runtime/engine.py``).
    - ``chunk_bits``: wire width of the quantized collectives — the intN
      payload of the fused quantized ring (``parallel/qring.py``) and the DP
      gradient sync. One of {4, 8, 16} (int4 nibble-packed / EQuARX int8 /
      int16); anything else is a loud error, never a silent clamp.
    - ``bidirectional``: ring chunks travel both ICI directions.
    - ``quant_block``: elements per absmax scale block of the quantized
      collectives (even, >= 8 — int4 packs two wire elements per byte).
    - ``moe_chunks``: target chunk count for the MoE a2a pipeline.
    """
    enabled: bool = False
    collective_matmul: bool = True
    quantized_allreduce: bool = False
    chunk_bits: int = 8
    bidirectional: bool = True
    quant_block: int = 256
    moe_chunks: int = 4

    def __post_init__(self):
        from ..comm.compressed import WIRE_BITS
        if self.chunk_bits not in WIRE_BITS:
            raise ValueError(
                f"comm_overlap.chunk_bits={self.chunk_bits} unsupported — the "
                f"quantized wire is blockwise-scaled intN with N in "
                f"{sorted(WIRE_BITS)} (int4 nibble-packed / EQuARX int8 / "
                "int16); widths are validated, not clamped")
        if self.quant_block < 8 or self.quant_block % 2:
            raise ValueError(
                f"comm_overlap.quant_block={self.quant_block} invalid "
                "(even, >= 8)")

    @property
    def matmul_active(self) -> bool:
        return self.enabled and self.collective_matmul


def resolve_overlap_config(raw) -> OverlapConfig:
    """Accepts None | dict | pydantic model | OverlapConfig."""
    if raw is None:
        return OverlapConfig()
    if isinstance(raw, OverlapConfig):
        return raw
    if hasattr(raw, "model_dump"):
        raw = raw.model_dump()
    elif hasattr(raw, "dict") and not isinstance(raw, dict):
        raw = raw.dict()
    fields = {f.name for f in dataclasses.fields(OverlapConfig)}
    unknown = set(raw) - fields
    if unknown:
        raise ValueError(f"unknown comm_overlap keys: {sorted(unknown)} "
                         f"(known: {sorted(fields)})")
    return OverlapConfig(**raw)


_OVERLAP_CONFIG: OverlapConfig = OverlapConfig()


def set_overlap_config(cfg: Optional[OverlapConfig]):
    global _OVERLAP_CONFIG
    _OVERLAP_CONFIG = cfg if cfg is not None else OverlapConfig()


def get_overlap_config() -> OverlapConfig:
    return _OVERLAP_CONFIG


@contextlib.contextmanager
def overlap_scope(cfg: Optional[OverlapConfig]):
    """Install ``cfg`` for the duration of a trace. Used by the compiled-step
    builders (``inference/decode_fns.py``) so each engine's compiled bodies
    trace with THAT engine's overlap setting regardless of ambient global
    state (engines with different settings coexist in one process)."""
    if cfg is None:
        yield
        return
    prev = get_overlap_config()
    set_overlap_config(cfg)
    try:
        yield
    finally:
        set_overlap_config(prev)


# ------------------------------------------------------- ring primitives
# All primitives below run INSIDE a shard_map whose manual axes include
# ``axis_name``. Chunk count == axis size: one ring step per shard, the
# granularity at which XLA's latency-hiding scheduler can slide each ppermute
# under the neighbouring chunk's matmul.

def _ring_perm(W: int, step: int = 1):
    return [(p, (p + step) % W) for p in range(W)]


def _record_ring(site, op, per_shard_bytes, axis_name, overlapped):
    """Trace-time span for a ring primitive; ``site=None`` skips (the caller
    — e.g. ``row_parallel_dense_apply`` — is recording at its own level)."""
    if site is not None:
        W = jax.lax.psum(1, axis_name)
        record_collective(site, op, (W - 1) * per_shard_bytes, W,
                          overlapped=overlapped)


@scope("comm.allgather_matmul_monolithic")
def allgather_matmul_monolithic(x, w, axis_name, *, site=None):
    """Exact-numerics fallback: ``all_gather(x, tiled) @ w``."""
    _record_ring(site, "all_gather", x.size * x.dtype.itemsize, axis_name,
                 overlapped=False)
    g = jax.lax.all_gather(x, axis_name, axis=0, tiled=True)
    return g @ w


@scope("comm.matmul_reduce_scatter_monolithic")
def matmul_reduce_scatter_monolithic(x, w, axis_name, *, site=None):
    """Exact-numerics fallback: ``psum_scatter(x @ w, scatter dim 0, tiled)``."""
    W = jax.lax.psum(1, axis_name)
    _record_ring(site, "reduce_scatter",
                 x.shape[0] // W * w.shape[1] * jnp.result_type(x, w).itemsize,
                 axis_name, overlapped=False)
    return jax.lax.psum_scatter(x @ w, axis_name, scatter_dimension=0,
                                tiled=True)


@scope("comm.chunked_allgather_matmul")
def chunked_allgather_matmul(x, w, axis_name, *, bidirectional: bool = True,
                             site=None):
    """``all_gather(x, axis=0, tiled) @ w`` as a ppermute ring.

    ``x``: this shard's ``(m_loc, k)`` row block (sharded over ``axis_name``);
    ``w``: ``(k, n)`` local operand. Returns ``(W*m_loc, n)``.

    Step ``s`` multiplies the row block that arrived on the ring while step
    ``s-1``'s block was multiplying — the transfer hides under the MXU. Each
    output row block is one matmul over unchanged operands: bit-identical to
    the monolithic form.
    """
    W = jax.lax.psum(1, axis_name)
    if W == 1:
        return x @ w
    _record_ring(site, "all_gather", x.size * x.dtype.itemsize, axis_name,
                 overlapped=True)
    idx = jax.lax.axis_index(axis_name)
    m_loc, n = x.shape[0], w.shape[1]
    out = jnp.zeros((W * m_loc, n), dtype=jnp.result_type(x.dtype, w.dtype))

    def write(out, block, src):
        y = block @ w
        return jax.lax.dynamic_update_slice(out, y, (src * m_loc, 0))

    if not bidirectional:
        cur = x
        for s in range(W):
            out = write(out, cur, (idx - s) % W)
            if s != W - 1:
                cur = jax.lax.ppermute(cur, axis_name, _ring_perm(W, 1))
        return out

    fwd = bwd = x
    out = write(out, x, idx)
    for s in range(1, W // 2 + 1):
        fwd = jax.lax.ppermute(fwd, axis_name, _ring_perm(W, 1))
        out = write(out, fwd, (idx - s) % W)
        if s <= (W - 1) // 2:
            bwd = jax.lax.ppermute(bwd, axis_name, _ring_perm(W, -1))
            out = write(out, bwd, (idx + s) % W)
    return out


@scope("comm.chunked_matmul_reduce_scatter")
def chunked_matmul_reduce_scatter(x, w, axis_name, *,
                                  bidirectional: bool = True, site=None):
    """``psum_scatter(x @ w, scatter dim 0, tiled)`` as a compute/accumulate ring.

    ``x``: ``(m, k)`` local operand (each shard holds its partial-sum
    contribution, e.g. the row-parallel activation slice); ``w``: ``(k, n)``.
    ``m`` must divide by the axis size. Returns ``(m/W, n)``: shard ``p`` ends
    holding row block ``p`` fully summed.

    Row block ``b``'s accumulator starts at shard ``b+1`` and travels the ring;
    at each step the shard adds its own partial for the block just as the next
    hop's transfer begins — the ICI hop hides under the block matmul. The
    cross-shard sum runs in ring-visit order (fp summation order differs from
    the monolithic psum by at most last-ulp; exact for exactly-representable
    sums).
    """
    W = jax.lax.psum(1, axis_name)
    if W == 1:
        return x @ w
    idx = jax.lax.axis_index(axis_name)
    m, k = x.shape
    if m % W != 0:
        # must survive python -O: dynamic_slice CLAMPS out-of-range block
        # starts, so an unguarded ragged m would silently double-sum rows
        raise ValueError(
            f"chunked_matmul_reduce_scatter: m={m} not divisible by "
            f"axis size {W} — pad rows first (see row_parallel_dense_apply)")
    m_blk = m // W
    _record_ring(site, "reduce_scatter",
                 m_blk * w.shape[1] * jnp.result_type(x, w).itemsize,
                 axis_name, overlapped=True)

    def partial(b, ww):
        rows = jax.lax.dynamic_slice(x, (b * m_blk, 0), (m_blk, k))
        return rows @ ww

    if not bidirectional or w.shape[1] % 2:
        acc = partial((idx - 1) % W, w)
        for s in range(1, W):
            acc = jax.lax.ppermute(acc, axis_name, _ring_perm(W, 1))
            acc = acc + partial((idx - 1 - s) % W, w)
        return acc

    # bidirectional: column halves travel opposite ring directions, using both
    # ICI links each step at half the per-step payload
    h = w.shape[1] // 2
    wa, wb = w[:, :h], w[:, h:]
    acc_a = partial((idx - 1) % W, wa)
    acc_b = partial((idx + 1) % W, wb)
    for s in range(1, W):
        acc_a = jax.lax.ppermute(acc_a, axis_name, _ring_perm(W, 1))
        acc_a = acc_a + partial((idx - 1 - s) % W, wa)
        acc_b = jax.lax.ppermute(acc_b, axis_name, _ring_perm(W, -1))
        acc_b = acc_b + partial((idx + 1 + s) % W, wb)
    return jnp.concatenate([acc_a, acc_b], axis=1)


# --------------------------------------------- GSPMD-callable row-parallel dense
def _overlap_dense_eligible(mesh, b, t, k, cfg: OverlapConfig):
    if mesh is None or not cfg.matmul_active:
        return False, (), 1
    tp = mesh.size(AXIS_TENSOR)
    if tp <= 1 or k % tp or mesh.size(AXIS_SEQ) > 1 or mesh.size(AXIS_PIPE) > 1:
        return False, (), 1
    batch_axes = tuple(ax for ax in BATCH_AXES if mesh.size(ax) > 1)
    bsz = int(np.prod([mesh.size(ax) for ax in batch_axes])) if batch_axes else 1
    if batch_axes and b % bsz:
        return False, (), 1
    # chunking needs at least one row per ring step after batch sharding
    if (b // max(bsz, 1)) * t < tp:
        return False, (), 1
    return True, batch_axes, tp


def row_parallel_dense_apply(x, kernel, bias, dtype, *, site: str = "tp.row_dense"):
    """Row-parallel dense ``y = x @ kernel + bias`` with comm-compute overlap.

    ``x``: ``(b, t, k)`` activations; ``kernel``: ``(k, n)`` sharded
    ``P(tensor, None)`` by the model's param specs; ``bias``: ``(n,)`` or None.

    When the overlap config is active and shapes divide, lowers to a
    ``shard_map`` over {batch axes} ∪ {tensor}: local rows × local kernel slice
    through :func:`chunked_matmul_reduce_scatter`, then a tiled all-gather of
    the (small, d_model-wide) row blocks — replacing the monolithic allreduce
    GSPMD would insert, with the heavy matmul overlapping the scatter ring.
    Falls back to the plain (GSPMD-collective) matmul otherwise — numerics of
    the two paths agree (summation-order-exact for the gather, last-ulp for
    the scatter; pinned by ``tests/unit/parallel/test_overlap.py``).
    """
    cfg = get_overlap_config()
    mesh = get_global_mesh()
    b, t, k = x.shape
    n = kernel.shape[-1]
    x = x.astype(dtype)
    kernel = kernel.astype(dtype)
    ok, batch_axes, tp = _overlap_dense_eligible(mesh, b, t, k, cfg)
    if not ok:
        if mesh is not None and mesh.size(AXIS_TENSOR) > 1:
            record_collective(site + ".monolithic", "all_reduce",
                              b * t * n * jnp.dtype(dtype).itemsize,
                              mesh.size(AXIS_TENSOR), overlapped=False)
        y = x @ kernel
        return y if bias is None else y + bias.astype(dtype)

    bsz = int(np.prod([mesh.size(ax) for ax in batch_axes])) if batch_axes else 1
    m_loc = (b // bsz) * t
    pad = (-m_loc) % tp
    # decomposed allreduce = reduce-scatter (overlapped under the matmul;
    # span recorded by the primitive under ``site``) + tiled all-gather of the
    # small row blocks, recorded here: (W-1) blocks of (m/W)·n on the wire
    record_collective(site + ".gather", "all_gather",
                      (tp - 1) * ((m_loc + pad) // tp) * n
                      * jnp.dtype(dtype).itemsize,
                      tp, overlapped=False)
    # NOTE on autodiff: the kernel's in_spec leaves the batch axes unmentioned
    # (replicated); shard_map's transpose psums its cotangent over those axes
    # itself, so no explicit conjugate op is needed here (adding one would
    # double-count — pinned by the TP×DP grad parity test).
    def body(x_l, w_l):
        bl, tl, kl = x_l.shape
        x2 = x_l.reshape(bl * tl, kl)
        if pad:
            x2 = jnp.pad(x2, ((0, pad), (0, 0)))
        y_loc = chunked_matmul_reduce_scatter(
            x2, w_l, AXIS_TENSOR, bidirectional=cfg.bidirectional, site=site)
        y = jax.lax.all_gather(y_loc, AXIS_TENSOR, axis=0, tiled=True)
        if pad:
            y = y[:bl * tl]
        return y.reshape(bl, tl, -1)

    xspec = P(batch_axes or None, None, AXIS_TENSOR)
    manual = set(batch_axes) | {AXIS_TENSOR}
    y = shard_map(body, mesh=mesh.mesh, axis_names=manual,
                  in_specs=(xspec, P(AXIS_TENSOR, None)),
                  out_specs=P(batch_axes or None, None, None),
                  check_vma=False)(x, kernel)
    return y if bias is None else y + bias.astype(dtype)


# flax module mirroring nn.Dense's parameter tree (kernel/bias names, fp32
# params, compute-dtype cast) so swapping it into a model changes NOTHING about
# checkpoints — only how the row-parallel matmul lowers.
import flax.linen as nn  # noqa: E402  (after jax; mirrors models/* import order)


def raw_or_param(mdl: nn.Module, name: str, init_fn, shape):
    """Declare a weight at init; RAW-fetch it at apply.

    The serving engine replaces quantizable kernels with quant nodes
    (``{__int8_q__|__int4_q__, *_scale__}`` — ``ops/quantizer``). Those must
    flow through flax untouched: ``self.param`` re-runs the initializer under
    ``eval_shape`` and zips leaf shapes, which a packed int4 payload
    (``(k//2, n)``) fails. Raw scope access skips that validation; the fp
    (training/unquantized) tree is bit-identical either way. Shared by every
    quantizable projection module (:class:`RowParallelDense` here,
    ``QuantDense``/``_ExpertWeights`` in ``models/causal_lm.py``)."""
    if mdl.is_initializing() or not mdl.has_variable("params", name):
        return mdl.param(name, init_fn, shape, jnp.float32)
    return mdl.scope.get_variable("params", name)


class RowParallelDense(nn.Module):
    """Drop-in for ``nn.Dense`` at row-parallel TP sites (o_proj / fc_out).

    At serve time the engine may replace ``kernel`` with a quant node
    (``ops/quantizer``): when ``comm_overlap`` is active the projection then
    runs the fused quantized ring (``parallel/qring.py``) — a dequant-GEMM
    per ring step over the shard's whole packed slab (group boundaries never
    cross the wire, only fp accumulator chunks do), with the ring payload
    itself quantized to ``chunk_bits``. Ineligible shapes (or overlap off)
    keep the PR-5 fused kernel + monolithic psum."""
    features: int
    use_bias: bool = True
    dtype: Any = jnp.float32
    kernel_init: Callable = nn.initializers.lecun_normal()
    bias_init: Callable = nn.initializers.zeros
    span: str = "tp.row_dense"

    @nn.compact
    def __call__(self, x):
        kernel = raw_or_param(self, "kernel", self.kernel_init,
                              (x.shape[-1], self.features))
        bias = (self.param("bias", self.bias_init, (self.features,), jnp.float32)
                if self.use_bias else None)
        squeeze = x.ndim == 2
        if squeeze:
            x = x[:, None]
        from ..ops.quantizer import is_quant_node, quant_dense_apply
        if is_quant_node(kernel):
            y = quant_dense_apply(x, kernel, bias, self.dtype, parallel="row",
                                  site=self.span)
        else:
            y = row_parallel_dense_apply(x, kernel, bias, self.dtype,
                                         site=self.span)
        return y[:, 0] if squeeze else y


# ----------------------------------------------------------- MoE a2a pipeline
def moe_overlap_chunks(cfg: OverlapConfig, expert_parallel: int, cap: int) -> int:
    """Chunk count for the MoE dispatch/combine exchange: the largest divisor
    of ``cap`` not exceeding ``cfg.moe_chunks`` (1 = no chunking)."""
    if not cfg.matmul_active or expert_parallel <= 1 or cap <= 1:
        return 1
    target = max(1, min(cfg.moe_chunks, cap))
    for c in range(target, 1, -1):
        if cap % c == 0:
            return c
    return 1


@scope("comm.chunked_expert_exchange")
def chunked_expert_exchange(expert_in, expert_fn, sharding, n_chunks: int,
                            *, site: str = "moe.a2a"):
    """Run the expert exchange + FFN in ``n_chunks`` capacity slices.

    ``expert_in``: ``(e, c, m)`` token-major dispatch tensor; ``sharding``:
    the ``P(expert, ...)`` NamedSharding constraint that lowers to the
    all-to-all; ``expert_fn``: per-token expert FFN. Chunk ``i+1``'s layout
    exchange overlaps chunk ``i``'s FFN under XLA's async collectives. The FFN
    is per-token and slices are disjoint, so the concatenated result is
    bitwise-identical to the unchunked exchange.
    """
    e, c, m = expert_in.shape
    n_ranks = None
    mesh = get_global_mesh()
    if mesh is not None:
        from .mesh import AXIS_EXPERT
        n_ranks = mesh.size(AXIS_EXPERT)
    # full payload regardless of chunking: n_chunks exchanges move the same
    # total bytes as the monolithic exchange — recording one chunk's slice
    # would understate the overlap config's traffic by n_chunks in the A/B
    record_collective(site, "all_to_all",
                      expert_in.size * expert_in.dtype.itemsize,
                      n_ranks or 1, overlapped=n_chunks > 1)
    if n_chunks <= 1:
        expert_in = jax.lax.with_sharding_constraint(expert_in, sharding)
        out = expert_fn(expert_in)
        return jax.lax.with_sharding_constraint(out, sharding)
    cs = c // n_chunks
    outs = []
    for i in range(n_chunks):
        sl = jax.lax.with_sharding_constraint(
            expert_in[:, i * cs:(i + 1) * cs, :], sharding)
        yo = expert_fn(sl)
        outs.append(jax.lax.with_sharding_constraint(yo, sharding))
    return jnp.concatenate(outs, axis=1)
