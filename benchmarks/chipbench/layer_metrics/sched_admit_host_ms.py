"""Host milliseconds of one admission: the program's ``serving.admit`` span
(queue pop to the slot being live: prefix lookup, page table, operand
placement, the prefill's dispatch, the first token's fetch, prefix insert)
minus the device-busy time inside it; the median over the traced admissions.
Earlier lines give it by prefill bucket and by hit or miss, and the medians
of the spans under it."""

from benchmarks.chipbench import program_spans as ps
from benchmarks.chipbench.harness import say

NAME = "sched_admit_host_ms"
UNIT = "ms"
LAYER = "serve scheduler"
MOVES = "ttft_p50_ms"
KINDS = ("serve_closed",)

PARTS = ("serving.prefix_lookup", "serving.page_table", "serving.place_inputs",
         "serving.dispatch", "serving.fetch", "serving.scatter_prefill",
         "serving.prefix_insert")


def read(ctx):
    if not ctx.trace_reduced or not ctx.trace_reduced["devices"]:
        return None            # no device plane: nothing to take busy time from
    spans = ps.in_window(ctx)
    admits = ps.named(spans, "serving.admit")
    if not admits:
        return None
    red = ctx.trace_reduced
    host = [ps.host_s(red, a) for a in admits]
    groups = {}
    for a, host_s in zip(admits, host):
        pre = (ps.inside(spans, a, "serving.prefill")
               + ps.inside(spans, a, "serving.suffix_prefill"))
        bucket = pre[0].stats.get("bucket") if pre else None
        kind = "hit" if int(a.stats.get("prefix_len", 0) or 0) > 0 else "miss"
        groups.setdefault((kind, bucket), []).append(host_s)
    for (kind, bucket), vals in sorted(groups.items(), key=str):
        say(f"admissions, {kind}, prefill bucket {bucket}: {len(vals)}, host "
            f"{ps.fmt(ps.median_ms(vals))} ms median")
    for part in PARTS:
        vals = [p.end - p.start for a in admits for p in ps.inside(spans, a, part)]
        if vals:
            say(f"under serving.admit: {part} {ps.fmt(ps.median_ms(vals))} ms "
                f"median over {len(vals)}")
    waits = [float(a.stats["queue_wait_ms"]) for a in admits
             if "queue_wait_ms" in a.stats]
    if waits:
        say(f"queue wait before the {len(waits)} admissions: "
            f"{ps.fmt(ps.median_ms([w * 1e-3 for w in waits]))} ms median")
    return ps.median_ms(host)
