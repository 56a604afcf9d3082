"""Per-request latency attribution: span tree → named-phase decomposition.

The PR 10 tracer records *what* happened to a request (``request`` →
``attempt{replica}`` → ``replica_request`` → ``queue_wait`` /
``serving.admit`` {``serving.prefix_lookup``, ``serving.page_table``,
``serving.restore_prefix``, ``serving.prefill`` | ``serving.suffix_prefill``} /
``decode_chunk×N`` / ``retire``); this module answers *where the time went*: every completed
request's end-to-end latency is decomposed into a fixed set of named phases
whose sum equals the e2e latency **by construction** (the phases partition the
root span's wall window — the tested identity is sum(phases) == e2e within
1%, the slack covering only float accumulation):

- ``queue``    — admission-queue wait: the ``queue_wait`` spans plus any
  uncovered time before the first replica-side work begins (router-level
  queueing happens before an ``attempt`` span exists);
- ``admission`` — admission-time work: prefix-cache trie lookups and whatever
  else of ``serving.admit`` no narrower span names (queue pop, operand
  placement outside the prefill, prefix insert);
- ``kv_restore`` — page-table acquire / bind (copy-on-write included) and
  prefix-slab restore at an admission;
- ``prefill``  — the prefill span: operand placement, dispatch, the first
  token's fetch;
- ``decode``   — decode-chunk compute (the slot-batch dispatches this request
  participated in);
- ``gap``      — inter-chunk scheduling gap: time inside the serving window
  covered by no span (co-batch waits, pump latency, harvest);
- ``retry_lost`` — every second spent inside an abandoned lane: the full
  subtree of any evicted/failed ``attempt`` and any ``replica_request``
  force-closed ``state=abandoned`` when its replica was killed. This is the
  serving-pipeline analogue of T3's attribution-of-overlap argument — the
  tail is usually not "decode got slow" but "a whole lane was thrown away".

Spans here are the tracer's finished-span dicts (``ts``/``dur`` in wall µs).
The flight recorder feeds each completed trace through :func:`attribute` and
aggregates rows into the "where did the p99 go" breakdown
(:func:`phase_breakdown`): phase *shares* at p50 vs p99 — the BENCH JSON's
answer to why the tail is shaped the way it is.
"""

from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

#: the fixed phase vocabulary, in classification-priority order (earlier
#: phases claim overlapping wall time first; ``gap`` is the residual)
PHASES = ("queue", "admission", "kv_restore", "prefill", "decode",
          "gap", "retry_lost")

#: registry tags for the per-phase histograms (declared in ``schema.TAGS``)
PHASE_TAGS = {
    "queue": "latency/phase/queue_ms",
    "admission": "latency/phase/admission_ms",
    "kv_restore": "latency/phase/kv_restore_ms",
    "prefill": "latency/phase/prefill_ms",
    "decode": "latency/phase/decode_ms",
    "gap": "latency/phase/gap_ms",
    "retry_lost": "latency/phase/retry_lost_ms",
}

E2E_TAG = "latency/e2e_ms"

#: span names that root a request-scoped trace (``request`` = router front
#: door; ``replica_request`` roots the single-scheduler path)
ROOT_NAMES = ("request", "replica_request")

#: attempt outcomes / lane states that mark a subtree as thrown-away work
_FAILED_ATTEMPT_OUTCOMES = ("evicted", "dispatch_error", "error")
_FAILED_LANE_STATES = ("abandoned", "evicted")

Interval = Tuple[float, float]

#: tracer span name → phase (spans with other names only move ``first_work``)
_NAME_TO_PHASE = {"queue_wait": "queue", "serving.admit": "admission",
                  "serving.prefix_lookup": "admission",
                  "serving.page_table": "kv_restore",
                  "serving.restore_prefix": "kv_restore",
                  "serving.prefill": "prefill",
                  "serving.suffix_prefill": "prefill",
                  "decode_chunk": "decode"}


def _merge(intervals: Sequence[Interval]) -> List[Interval]:
    """Sorted disjoint union."""
    out: List[Interval] = []
    for lo, hi in sorted(intervals):
        if hi <= lo:
            continue
        if out and lo <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], hi))
        else:
            out.append((lo, hi))
    return out


def _subtract(intervals: Sequence[Interval],
              covered: Sequence[Interval]) -> List[Interval]:
    """``intervals`` minus ``covered`` (both sorted disjoint)."""
    out: List[Interval] = []
    for lo, hi in intervals:
        cur = lo
        for clo, chi in covered:
            if chi <= cur:
                continue
            if clo >= hi:
                break
            if clo > cur:
                out.append((cur, clo))
            cur = max(cur, chi)
            if cur >= hi:
                break
        if cur < hi:
            out.append((cur, hi))
    return out


def _length(intervals: Sequence[Interval]) -> float:
    return sum(hi - lo for lo, hi in intervals)


def _clamp(lo: float, hi: float, t0: float, t1: float) -> Optional[Interval]:
    lo, hi = max(lo, t0), min(hi, t1)
    return (lo, hi) if hi > lo else None


def find_root(spans: Sequence[Dict]) -> Optional[Dict]:
    """The request-scoped root span of a finished trace: a parentless span
    named ``request`` (router front door preferred) or ``replica_request``
    (single-scheduler path). None when the trace is not request-shaped."""
    roots = [s for s in spans
             if not s.get("parent_id") and s.get("name") in ROOT_NAMES]
    if not roots:
        return None
    for s in roots:
        if s["name"] == "request":
            return s
    return roots[0]


def _failed_subtree_ids(spans: Sequence[Dict]) -> set:
    """Span ids belonging to thrown-away lanes: evicted/errored ``attempt``
    subtrees and ``state=abandoned``/``evicted`` ``replica_request`` subtrees
    (a killed replica's force-closed lane), including every descendant."""
    seeds = []
    for s in spans:
        name = s.get("name")
        if name != "attempt" and name != "replica_request":
            continue
        attrs = s.get("attrs") or {}
        if name == "attempt" \
                and attrs.get("outcome") in _FAILED_ATTEMPT_OUTCOMES:
            seeds.append(s)
        elif name == "replica_request" \
                and attrs.get("state") in _FAILED_LANE_STATES:
            seeds.append(s)
    if not seeds:
        return set()    # healthy trace: skip the child-map build entirely
    children: Dict[str, List[Dict]] = {}
    for s in spans:
        pid = s.get("parent_id")
        if pid:
            children.setdefault(pid, []).append(s)
    failed = set()
    stack = list(seeds)
    while stack:
        s = stack.pop()
        sid = s.get("span_id")
        if sid in failed:
            continue
        failed.add(sid)
        stack.extend(children.get(sid, ()))
    return failed


def attribute(spans: Sequence[Dict]) -> Optional[Dict]:
    """Decompose one finished trace into the named phases.

    Returns an attribution row ``{"trace_id", "request_id", "state", "e2e_ms",
    "phases": {phase: ms}, "tokens", "attempts", "retried"}`` or None when the
    trace has no request root. The phases PARTITION the root window, so
    ``sum(phases.values()) == e2e_ms`` up to float accumulation — the
    attribution identity the tests pin."""
    root = find_root(spans)
    if root is None:
        return None
    t0 = float(root["ts"])
    t1 = t0 + float(root["dur"])
    failed = _failed_subtree_ids(spans)

    by_phase: Dict[str, List[Interval]] = {p: [] for p in PHASES}
    first_work = t1
    for s in spans:
        if s is root:
            continue
        iv = _clamp(float(s["ts"]), float(s["ts"]) + float(s["dur"]), t0, t1)
        if iv is None:
            continue
        if failed and s.get("span_id") in failed:
            by_phase["retry_lost"].append(iv)
            continue
        phase = _NAME_TO_PHASE.get(s.get("name"))
        if phase is None:
            if s.get("name") == "replica_request":
                first_work = min(first_work, iv[0])
            continue
        by_phase[phase].append(iv)
        first_work = min(first_work, iv[0])

    # priority-ordered disjoint coverage: a restore second is a restore
    # second even though the prefill span covers it too. Empty phases are
    # skipped — this runs once per completed request on the serving host.
    # ``admission`` claims last: ``serving.admit`` covers the whole admission
    # (lookup, page table, prefill, prefix insert), so what no narrower span
    # names inside it is admission work.
    priority = ("retry_lost", "kv_restore", "queue", "decode", "prefill",
                "admission")
    covered: List[Interval] = []
    phases_ms = {p: 0.0 for p in PHASES}
    for phase in priority:
        if not by_phase[phase]:
            continue
        ivs = _subtract(_merge(by_phase[phase]), covered)
        phases_ms[phase] = _length(ivs) / 1e3
        covered = _merge(list(covered) + ivs)

    # residual: uncovered time before the first replica-side work is router
    # queueing (no span exists for it — the attempt hasn't been dispatched);
    # uncovered time after it is inter-chunk scheduling gap
    uncovered = _subtract([(t0, t1)], covered)
    for lo, hi in uncovered:
        pre = min(hi, max(lo, first_work))
        phases_ms["queue"] += (pre - lo) / 1e3
        phases_ms["gap"] += (hi - pre) / 1e3

    attrs = root.get("attrs") or {}
    return {
        "trace_id": root.get("trace_id"),
        "request_id": attrs.get("request_id"),
        "state": attrs.get("state"),
        "e2e_ms": (t1 - t0) / 1e3,
        "phases": phases_ms,
        "tokens": attrs.get("tokens"),
        "attempts": attrs.get("attempts", 1),
        "retried": attrs.get("retried", 0),
        "failed_lanes": len(failed),
    }


def phase_breakdown(rows: Sequence[Dict]) -> Dict:
    """The "where did the p99 go" aggregate: phase *shares* of e2e at p50 vs
    p99. The p50 group is the typical half (e2e <= median), the p99 group the
    tail (e2e >= p99, at least the slowest request); each group's share is
    sum(phase) / sum(e2e) over its members, so shares sum to ~1 per group."""
    rows = [r for r in rows if r and r.get("e2e_ms")]
    if not rows:
        return {"requests": 0, "e2e_ms_p50": None, "e2e_ms_p99": None,
                "p50_shares": None, "p99_shares": None}
    e2es = np.asarray([r["e2e_ms"] for r in rows], dtype=float)
    p50, p99 = float(np.percentile(e2es, 50)), float(np.percentile(e2es, 99))
    p50_rows = [r for r in rows if r["e2e_ms"] <= p50] or rows
    p99_rows = [r for r in rows if r["e2e_ms"] >= p99] \
        or [max(rows, key=lambda r: r["e2e_ms"])]

    def shares(group):
        total = sum(r["e2e_ms"] for r in group)
        if total <= 0:
            return {p: 0.0 for p in PHASES}
        return {p: sum(r["phases"][p] for r in group) / total for p in PHASES}

    return {"requests": len(rows), "e2e_ms_p50": p50, "e2e_ms_p99": p99,
            "p50_shares": shares(p50_rows), "p99_shares": shares(p99_rows)}
