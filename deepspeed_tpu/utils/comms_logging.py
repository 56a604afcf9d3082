"""Communication op logger with algorithmic/bus bandwidth math.

Behavioural equivalent of reference ``deepspeed/utils/comms_logging.py`` (``CommsLogger:58``,
``calc_bw_log:25``). On TPU, collectives inside jit are scheduled by XLA and invisible to
Python; this logger covers the eager comm facade (checkpoint resharding, host syncs) and is also
fed estimated volumes by the engine for in-graph collectives.
"""

import math
from typing import Dict

from .logging import logger


def get_caller_func(frame_depth: int = 3) -> str:
    import sys
    return sys._getframe(frame_depth).f_code.co_name


def calc_bw_log(comm_op: str, size_bytes: int, duration_s: float, n_ranks: int):
    """Returns (msg_size_bytes, algbw_Gbps, busbw_Gbps).

    Bus-bandwidth correction factors follow the standard ring-collective accounting the
    reference uses: allreduce busbw = algbw * 2(n-1)/n; all_gather/reduce_scatter = (n-1)/n.
    """
    duration_s = max(duration_s, 1e-12)
    n = max(n_ranks, 1)
    if comm_op in ("all_reduce", "allreduce", "all_to_all_single", "all_to_all"):
        tput = size_bytes / duration_s
        busbw = tput * (2 * (n - 1) / n)
    elif comm_op in ("all_gather", "allgather", "all_gather_into_tensor",
                     "reduce_scatter", "reduce_scatter_tensor"):
        size_bytes = size_bytes * n
        tput = size_bytes / duration_s
        busbw = tput * ((n - 1) / n)
    else:  # send/recv/broadcast/reduce/barrier
        tput = size_bytes / duration_s
        busbw = tput
    return size_bytes, tput * 8 / 1e9, busbw * 8 / 1e9


class CommsLogger:
    """Per-op record of counts/volumes/latencies; ``log_all`` prints a summary table."""

    def __init__(self, config=None):
        if config is not None:
            self.enabled = config.enabled
            self.verbose = config.verbose
            self.prof_all = config.prof_all
            self.prof_ops = list(config.prof_ops)
            self.debug = config.debug
        else:
            self.enabled = False
            self.verbose = False
            self.prof_all = True
            self.prof_ops = []
            self.debug = False
        self.comms_dict: Dict[str, Dict[int, list]] = {}

    def configure(self, config):
        self.enabled = config.enabled
        self.verbose = config.verbose
        self.prof_all = config.prof_all
        self.prof_ops = list(config.prof_ops)
        self.debug = config.debug

    def should_profile(self, op_name: str) -> bool:
        if not self.enabled:
            return False
        return self.prof_all or op_name in self.prof_ops

    def append(self, raw_name: str, record_name: str, latency_s: float, msg_size: int,
               n_ranks: int = 1):
        msg_size, algbw, busbw = calc_bw_log(raw_name, msg_size, latency_s, n_ranks)
        rec = self.comms_dict.setdefault(record_name, {})
        if msg_size in rec:
            rec[msg_size][0] += 1
            rec[msg_size][1].append(latency_s)
            rec[msg_size][2].append(algbw)
            rec[msg_size][3].append(busbw)
        else:
            rec[msg_size] = [1, [latency_s], [algbw], [busbw]]
        if self.verbose:
            logger.info(f"comm op: {record_name} | time(ms): {latency_s*1000:.2f} | "
                        f"msg size: {_fmt_size(msg_size)} | algbw(Gbps): {algbw:.2f} | "
                        f"busbw(Gbps): {busbw:.2f}")

    def log_all(self, print_log: bool = True, show_straggler: bool = False):
        lines = [f"{'Comm. Op':<20}{'Message Size':<20}{'Count':<10}"
                 f"{'Total Latency(ms)':<20}{'Avg Latency(ms)':<20}"
                 f"{'tput_avg (Gbps)':<20}{'busbw_avg (Gbps)':<20}"]
        for record_name, sizes in sorted(self.comms_dict.items()):
            lines.append(record_name)
            for size, (count, lats, algs, buss) in sorted(sizes.items()):
                total_lat = sum(lats) * 1000
                avg_lat = total_lat / count
                lines.append(f"{'':<20}{_fmt_size(size):<20}{count:<10}"
                             f"{total_lat:<20.2f}{avg_lat:<20.2f}"
                             f"{sum(algs)/count:<20.2f}{sum(buss)/count:<20.2f}")
        out = "\n".join(lines)
        if print_log:
            logger.info("\n" + out)
        return out


class CollectiveSpans:
    """Trace-time bytes-on-wire accounting for IN-GRAPH collectives by call site.

    XLA-scheduled collectives are invisible to Python timers, but their wire
    volume is a static function of shapes — each decomposed/monolithic call
    site (``parallel/overlap.py``, engine grad sync) records its per-dispatch
    payload when the enclosing computation TRACES. ``summary()`` therefore
    reports per-trace estimates (one record per compiled call site, not per
    step); ``overlap_ratio`` is the fraction of recorded bytes moved by
    overlap-scheduled (chunked ring / pipelined a2a) collectives. Consumed by
    MonitorMaster events and ``tests/unit/parallel/test_overlap.py``.
    """

    def __init__(self):
        self._spans: Dict[str, Dict] = {}

    def reset(self):
        self._spans.clear()

    def record(self, site: str, comm_op: str, size_bytes: int, n_ranks: int,
               overlapped: bool):
        rec = self._spans.setdefault(
            site, {"op": comm_op, "traces": 0, "bytes_per_call": 0,
                   "bytes_total": 0, "n_ranks": n_ranks,
                   "overlapped": bool(overlapped)})
        rec["traces"] += 1
        rec["bytes_per_call"] = int(size_bytes)
        # ACCUMULATE: n_layer traced calls at one site (e.g. every layer's
        # o_proj) must sum, not overwrite, or totals underreport by ~n_layer
        rec["bytes_total"] += int(size_bytes)
        rec["n_ranks"] = int(n_ranks)
        rec["overlapped"] = bool(overlapped)

    def summary(self) -> Dict[str, Dict]:
        return {k: dict(v) for k, v in self._spans.items()}

    def total_bytes(self) -> int:
        return spans_total_bytes(self._spans)

    def overlapped_bytes(self) -> int:
        return spans_overlapped_bytes(self._spans)

    def overlap_ratio(self) -> float:
        return spans_overlap_ratio(self._spans)


def spans_total_bytes(spans: Dict[str, Dict]) -> int:
    return sum(v["bytes_total"] for v in spans.values())


def spans_overlapped_bytes(spans: Dict[str, Dict]) -> int:
    return sum(v["bytes_total"] for v in spans.values() if v["overlapped"])


def spans_overlap_ratio(spans: Dict[str, Dict]) -> float:
    total = spans_total_bytes(spans)
    return (spans_overlapped_bytes(spans) / total) if total else 0.0


collective_spans = CollectiveSpans()


def record_collective(site: str, comm_op: str, size_bytes: int, n_ranks: int,
                      overlapped: bool = False):
    collective_spans.record(site, comm_op, size_bytes, n_ranks, overlapped)


def _fmt_size(num_bytes: float) -> str:
    if num_bytes == 0:
        return "0 B"
    units = ["B", "KB", "MB", "GB", "TB"]
    k = int(math.floor(math.log(max(num_bytes, 1), 1024)))
    k = min(k, len(units) - 1)
    return f"{num_bytes / (1024 ** k):.2f} {units[k]}"
