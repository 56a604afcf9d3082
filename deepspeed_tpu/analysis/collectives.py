"""Collective-schema pass: bytes-on-wire accounted from the jaxpr itself.

``CollectiveSpans`` (``utils/comms_logging.py``) records each decomposed
collective call site's modeled wire volume at trace time — but the recording
is hand-written per site, which is exactly how the PR 3 "last-call overwrite"
undercount happened (n_layer traces at one site overwrote instead of
summing). This pass closes the loop: it walks the traced program's jaxpr,
statically accounts bytes-on-wire for every *explicit* collective primitive
(``ppermute``/``all_gather``/``reduce_scatter``/``psum``/``all_to_all`` —
shapes x dtype x ring factor), and cross-checks the total against what the
spans recorded during the same trace. A site that under- or over-records by
any margin fails the pass, forever.

Accounting convention (per-worker bytes, ring algorithms — the same
convention ``parallel/overlap.py`` records):

==================  ====================================================
primitive           wire bytes per worker
==================  ====================================================
ppermute            operand nbytes (each worker forwards its buffer once)
all_gather          (W - 1) x operand (per-shard) nbytes
reduce_scatter      (W - 1) x output (per-shard) nbytes
psum                2 (W - 1) / W x operand nbytes (ring allreduce)
all_to_all          (W - 1) / W x operand nbytes
==================  ====================================================

GSPMD-*implicit* collectives (a ``with_sharding_constraint`` that lowers to
an a2a, the monolithic-psum fallback's allreduce) never appear in the jaxpr
— sites recorded with those ops are excluded from the exact cross-check and
surfaced as ``info`` findings instead (documented limitation; their volume
is not checked statically).

Quantized wires need no special convention: the ppermute rule sums ALL
operand avals, so a fused-quantized-ring hop (``parallel/qring.py``) —
one intN carrier (int4 packs two elements per int8 byte, so the aval IS the
wire footprint) plus one fp32 scale vector per block — is accounted from
shapes x dtypes exactly like any fp hop. :func:`qring_wire_bytes` is the
closed form of that int-chunk arithmetic; the qring lint lane asserts the
recorded span, this closed form, and the jaxpr sum agree to the byte.
"""

from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from .jaxpr_passes import subjaxprs
from .report import Finding, PassResult, SEVERITY_ERROR, SEVERITY_INFO

#: collective primitives with static wire accounting
COLLECTIVE_PRIMS = ("ppermute", "all_gather", "reduce_scatter", "psum",
                    "all_to_all")

#: span ops that are GSPMD-implicit (absent from the jaxpr)
IMPLICIT_SPAN_OPS = ("all_reduce",)


def _aval_nbytes(aval) -> int:
    shape = getattr(aval, "shape", None)
    dtype = getattr(aval, "dtype", None)
    if shape is None or dtype is None:
        return 0
    return int(np.prod(shape, dtype=np.int64)) * np.dtype(dtype).itemsize


def _axes_size(axis_names, axis_env: Dict[str, int]) -> Optional[int]:
    names = axis_names if isinstance(axis_names, (tuple, list)) \
        else (axis_names,)
    size = 1
    for name in names:
        if name not in axis_env:
            return None
        size *= axis_env[name]
    return size


def _eqn_wire_bytes(eqn, axis_env: Dict[str, int]) -> Optional[int]:
    """Per-worker wire bytes for one collective eqn; None when the axis size
    is unknown (collective outside any recorded mesh context)."""
    name = eqn.primitive.name
    in_bytes = sum(_aval_nbytes(v.aval) for v in eqn.invars
                   if hasattr(v, "aval"))
    out_bytes = sum(_aval_nbytes(v.aval) for v in eqn.outvars)
    if name == "ppermute":
        return in_bytes
    if name == "all_gather":
        W = eqn.params.get("axis_size") or _axes_size(
            eqn.params.get("axis_name", ()), axis_env)
        return None if W is None else (W - 1) * in_bytes
    if name == "reduce_scatter":
        W = eqn.params.get("axis_size") or _axes_size(
            eqn.params.get("axis_name", ()), axis_env)
        return None if W is None else (W - 1) * out_bytes
    if name == "psum":
        W = _axes_size(eqn.params.get("axes", ()), axis_env)
        return None if W is None else int(2 * (W - 1) * in_bytes / W)
    if name == "all_to_all":
        W = _axes_size(eqn.params.get("axis_name", ()), axis_env)
        return None if W is None else int((W - 1) * in_bytes / W)
    return None


def collective_accounting(fn_or_jaxpr, args=()) -> List[Dict[str, Any]]:
    """Every explicit collective in the program, with modeled wire bytes.

    Returns records ``{"primitive", "wire_bytes", "axis_env", "shape"}`` in
    program order; ``wire_bytes`` is None when the enclosing axis size could
    not be resolved (reported by the cross-check as an error — an unaccounted
    collective is exactly what the pass exists to catch).
    """
    import jax
    if hasattr(fn_or_jaxpr, "eqns"):
        jaxpr = fn_or_jaxpr
    elif hasattr(fn_or_jaxpr, "jaxpr"):
        jaxpr = fn_or_jaxpr.jaxpr
    else:
        jaxpr = jax.make_jaxpr(fn_or_jaxpr)(*args).jaxpr
    records: List[Dict[str, Any]] = []

    def walk(jx, axis_env: Dict[str, int]):
        for eqn in jx.eqns:
            sub_env = axis_env
            mesh = eqn.params.get("mesh")
            if mesh is not None and hasattr(mesh, "shape"):
                sub_env = dict(axis_env)
                sub_env.update(dict(mesh.shape))
            if eqn.primitive.name in COLLECTIVE_PRIMS:
                shapes = [tuple(getattr(v.aval, "shape", ()))
                          for v in eqn.invars if hasattr(v, "aval")]
                records.append({
                    "primitive": eqn.primitive.name,
                    "wire_bytes": _eqn_wire_bytes(eqn, axis_env),
                    "axis_env": dict(axis_env),
                    "shape": shapes[0] if shapes else (),
                })
            for sub in subjaxprs(eqn):
                walk(sub, sub_env)

    walk(jaxpr, {})
    return records


def qring_wire_bytes(m: int, n: int, W: int, *, wire_bits: Optional[int] = 8,
                     block: int = 256, bidirectional: bool = True) -> int:
    """Closed-form per-worker bytes-on-wire of ONE fused quantized
    matmul-reduce-scatter dispatch (``parallel/qring.py``) — the intN-chunk
    wire arithmetic under this pass's ppermute convention.

    ``m``: padded flattened local token count (rows entering the ring; must
    divide by ``W``); ``n``: output features. Each serial step ppermutes one
    ``(m/W, n_dir)`` accumulator chunk as an intN carrier + one fp32 scale
    per ``block`` elements over the block-padded flat length
    (``comm.compressed.intn_wire_nbytes``); bidirectional rings make
    ``2 (W-1)`` half-width hops, unidirectional ``W-1`` full-width ones.
    ``wire_bits=None`` models the fp32 wire (the ground-truth lane).

    The qring span records this same number at trace time and the jaxpr
    side re-derives it from the ppermute operand avals — three independent
    computations that the lint lane and ``test_qring.py`` require to
    agree exactly, so bytes-on-wire claims are never hand-computed.
    """
    from ..comm.compressed import intn_wire_nbytes
    m_blk = m // W
    bidir = bidirectional and n % 2 == 0
    n_dir = n // 2 if bidir else n
    hop = (m_blk * n_dir * 4 if wire_bits is None
           else intn_wire_nbytes(m_blk * n_dir, block, wire_bits))
    return (W - 1) * (2 if bidir else 1) * hop


def _span_delta(before: Dict[str, Dict], after: Dict[str, Dict]
                ) -> Dict[str, Dict]:
    """Per-site recorded-bytes delta between two ``CollectiveSpans.summary()``
    snapshots (``bytes_total`` accumulates across traces)."""
    delta = {}
    for site, rec in after.items():
        prev = before.get(site, {}).get("bytes_total", 0)
        d = rec["bytes_total"] - prev
        if d or site not in before:
            delta[site] = dict(rec, bytes_total=d)
    return delta


def crosscheck_findings(fn, args, *, spans=None,
                        site_prefixes: Optional[Sequence[str]] = None,
                        target: str = "collectives") -> PassResult:
    """Trace ``fn(*args)``; assert jaxpr-accounted wire bytes == span-recorded
    wire bytes for the explicit-collective sites touched by the trace.

    ``spans``: the :class:`~deepspeed_tpu.utils.comms_logging.CollectiveSpans`
    instance the traced sites record into (defaults to the process-global
    one). ``site_prefixes`` names the sites the caller EXPECTS the trace to
    record — it shapes the report, not the arithmetic: the byte equation is
    always program-wide (the jaxpr side cannot be filtered by site, so a
    filtered recorded-side would manufacture false mismatches), and any
    explicit-op site recorded OUTSIDE the expected prefixes is surfaced as
    its own ``info`` finding.
    """
    import jax
    from ..utils.comms_logging import collective_spans
    spans = spans if spans is not None else collective_spans
    before = spans.summary()
    closed = jax.make_jaxpr(fn)(*args)
    delta = _span_delta(before, spans.summary())

    records = collective_accounting(closed)
    result = PassResult("collective_schema", target, checked=len(records))

    unaccounted = [r for r in records if r["wire_bytes"] is None]
    for r in unaccounted:
        result.findings.append(Finding(
            "collective_schema", SEVERITY_ERROR, target,
            f"collective {r['primitive']} over {r['shape']} has no "
            "resolvable axis size — unaccounted wire traffic",
            {"primitive": r["primitive"]}))

    implicit = {s: r for s, r in delta.items()
                if r.get("op") in IMPLICIT_SPAN_OPS}
    for s, r in implicit.items():
        result.findings.append(Finding(
            "collective_schema", SEVERITY_INFO, f"{target}/{s}",
            f"site records GSPMD-implicit op {r['op']!r} "
            f"({r['bytes_total']} bytes) — not statically checkable from "
            "the jaxpr; covered by bench A/B lanes",
            {"op": r["op"], "bytes": r["bytes_total"]}))

    if site_prefixes is not None:
        unexpected = [s for s in delta
                      if s not in implicit
                      and not any(s.startswith(p) for p in site_prefixes)]
        for s in unexpected:
            result.findings.append(Finding(
                "collective_schema", SEVERITY_INFO, f"{target}/{s}",
                f"trace also recorded site {s!r} outside the expected "
                f"prefixes {tuple(site_prefixes)} — its bytes participate "
                "in the program-wide cross-check below",
                {"bytes": delta[s]["bytes_total"]}))

    modeled = sum(r["wire_bytes"] for r in records
                  if r["wire_bytes"] is not None)
    recorded = sum(r["bytes_total"] for s, r in delta.items()
                   if s not in implicit)
    if modeled != recorded:
        result.findings.append(Finding(
            "collective_schema", SEVERITY_ERROR, target,
            f"bytes-on-wire mismatch: jaxpr accounts {modeled} but "
            f"CollectiveSpans recorded {recorded} for sites "
            f"{sorted(s for s in delta if s not in implicit)} — a call site "
            "under/over-records (the PR 3 last-call-overwrite class)",
            {"modeled": int(modeled), "recorded": int(recorded),
             "sites": {s: int(r["bytes_total"]) for s, r in delta.items()
                       if s not in implicit}}))
    return result
