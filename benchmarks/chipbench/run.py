#!/usr/bin/env python3
"""One command runs one cell of ``BENCHMARK.json`` once:

    python benchmarks/chipbench/run.py --workload <name> --seed <n> \
        --seconds <s> --trace <0|1>

It finds the chips the cell asks for or exits 2 and prints no result (there is
no CPU fallback), builds weights and inputs from ``--seed``, warms up every
program the cell's traffic uses (set-up), measures for ``--seconds`` seconds,
checks the outputs and prints ONE JSON object as the last line of its
standard output: ``correct``, ``attempted``, ``failed``, ``metrics``,
``device`` and, traced, ``breakdown``. ``--trace 0`` reports the cell's
end-to-end metrics; ``--trace 1`` arms ``jax.profiler`` for a few seconds of
the window and reports its per-layer metrics.

``--rehearse-cpu`` is a separate, labelled rehearsal at tiny widths on the CPU
backend: the same control flow, no device number (every time and rate is
left out; counts stay).

Every run compares the program with the plain float32 reference its
configuration names, outside the window, as part of ``correct``.

JAX is touched in this process only; nothing is started.
"""

import time

_T0 = time.monotonic()          # process start, as nearly as python can say

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.chipbench import registry  # noqa: E402
from benchmarks.chipbench import trace_reduce as tr  # noqa: E402
from benchmarks.chipbench.harness import Context, Refused, say  # noqa: E402


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse-cpu", action="store_true")
    return ap.parse_args(argv)


def claim_devices(chips: int, rehearse: bool):
    """Import jax on the platform this run is for; the cell's chips or
    :class:`Refused`."""
    if rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        flags = os.environ.get("XLA_FLAGS", "")
        if "--xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + f" --xla_force_host_platform_device_count={chips}")
    import jax
    try:
        devs = jax.devices()
    except RuntimeError as e:
        raise Refused(f"JAX found no device: {e}")
    on_tpu = devs[0].platform == "tpu"
    if rehearse and on_tpu:
        raise Refused("--rehearse-cpu on a TPU host: run without it")
    if not rehearse and not on_tpu:
        raise Refused(f"no TPU visible to JAX (platform {devs[0].platform!r}): "
                      "nothing was run; --rehearse-cpu is the labelled rehearsal")
    if len(devs) < chips:
        raise Refused(f"the cell asks for {chips} chip(s), JAX sees {len(devs)}")
    return jax, devs[:chips]


def set_compile_cache(jax) -> str:
    """The program's own ``enable_compile_cache`` places the persistent cache
    (the tree allows one setter): where ``JAX_COMPILATION_CACHE_DIR`` says,
    else ``.jax_cache/`` at the root of the checkout, a fixed path. Small
    programs are cached as well, so that a second run compiles nothing."""
    from deepspeed_tpu.utils.device import enable_compile_cache
    path = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def device_block(devs, ctx) -> dict:
    """The device as JAX reports it. ``memory_peak_bytes`` of the fullest chip
    is what its allocator held at most (``peak_bytes_in_use``: weights,
    optimizer state, caches) plus what the runtime had reserved at most for
    running programs (``peak_bytes_reserved``: a step's activations and other
    temporaries live there and not in the allocator's count); the two parts
    are given beside it under keys of their own. Read as the window closed."""
    stats = ctx.memory_at_close or [d.memory_stats() or {} for d in devs]
    full = max(stats, key=lambda st: int(st.get("peak_bytes_in_use", 0))
               + int(st.get("peak_bytes_reserved", 0)))
    in_use = int(full.get("peak_bytes_in_use", 0))
    reserved = int(full.get("peak_bytes_reserved", 0))
    out = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs), "memory_peak_bytes": in_use + reserved,
           "memory_peak_in_use_bytes": in_use,
           "memory_peak_reserved_bytes": reserved}
    if ctx.trace_reduced is not None and not ctx.rehearse:
        out["busy_s"] = tr.device_busy_s(ctx.trace_reduced)
        out["window_s"] = tr.window_s(ctx.trace_reduced)
    return out


def layer_metrics(ctx, bench, dirs) -> dict:
    """Run the reader of every per-layer metric this cell reports; a reader
    that finds nothing to read returns ``None`` and the metric is left out."""
    out = {}
    for entry in registry.metrics_of(bench, "per_layer", ctx.cell["name"]):
        mod = registry.load_module("layer_metrics", entry["name"], dirs)
        if ctx.kind_name not in mod.KINDS:
            continue
        value = mod.read(ctx)
        if value is not None:
            out[entry["name"]] = {"value": float(value), "unit": entry["unit"]}
    return out


def main(argv=None) -> int:
    args = parse(argv)
    bench = registry.load_benchmark(ROOT)
    dirs = registry.search_dirs(bench, ROOT)
    cell = registry.cell_of(bench, args.workload)
    with open(registry.config_file_of(bench, cell["config"], ROOT)) as f:
        config = json.load(f)
    traffic = registry.load_json("traffic", cell["traffic"], dirs)
    if args.rehearse_cpu:
        config = registry.rehearsal_view(config)
        traffic = registry.rehearsal_view(traffic)
    kind = registry.load_module("traffic_kinds", traffic["kind"], dirs)
    seconds = args.seconds if args.seconds is not None else float(bench["run_seconds"])

    try:
        jax, devs = claim_devices(int(cell["chips"]), args.rehearse_cpu)
    except Refused as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 2
    if args.rehearse_cpu:
        say("REHEARSAL: tiny widths on the CPU backend, Pallas in interpret "
            "mode; no line below is a device number")
    say(f"platform={devs[0].platform} device_kind={devs[0].device_kind} "
        f"devices={len(devs)} cell={cell['name']} config={cell['config']} "
        f"traffic={cell['traffic']} kind={traffic['kind']} seed={args.seed} "
        f"seconds={seconds} trace={args.trace}")
    cache = set_compile_cache(jax)
    say(f"compile cache: {cache}")

    from benchmarks.chipbench.probe import Probe
    scratch = tempfile.mkdtemp(prefix="chipbench_")
    dump_dir = os.path.join(scratch, "ir")
    os.makedirs(dump_dir)
    jax.config.update("jax_dump_ir_to", dump_dir)
    ctx = Context(cell=cell, config=config, traffic=traffic,
                  kind_name=traffic["kind"], seed=args.seed, seconds=seconds,
                  trace=bool(args.trace), rehearse=args.rehearse_cpu,
                  devices=devs, probe=Probe(dump_dir), t0=_T0,
                  trace_dir=os.path.join(scratch, "trace"), dirs=dirs)
    try:
        result = kind.run(ctx)
        ctx.result = result
        if ctx.trace and ctx.trace_path:
                ctx.trace_reduced = tr.reduce_trace(ctx.trace_path)
        compiles = ctx.probe.compiles_between(result.window[0], result.window[1])
        say(f"programs compiled or loaded inside the window: {compiles} "
            f"{ctx.probe.lowered_between(result.window[0], result.window[1])}")
        if compiles:
            result.reasons.append(f"{compiles} program(s) compiled inside the window")
        setup_s = result.window[0] - _T0
        say(f"set-up {setup_s:.3f} s, of which compiling "
            f"{ctx.probe.compile_seconds(_T0, result.window[0]):.3f} s (cache hits "
            f"{ctx.probe.hits}, misses {ctx.probe.misses})")
        if args.trace:
            metrics = layer_metrics(ctx, bench, dirs)
        else:
            values = dict(result.end_to_end, setup_s=setup_s)
            metrics = {m["name"]: {"value": float(values[m["name"]]), "unit": m["unit"]}
                       for m in registry.metrics_of(bench, "end_to_end", cell["name"])
                       if values.get(m["name"]) is not None}
        device = device_block(devs, ctx)
        if args.rehearse_cpu:      # a CPU time is never written under a metric's name
            metrics = {k: v for k, v in metrics.items() if k in result.counts_only}
        for r in result.reasons:
            say(f"NOT CORRECT: {r}")
        line = {"correct": not result.reasons, "attempted": result.attempted,
                "failed": result.failed, "metrics": metrics, "device": device}
        if args.trace and ctx.trace_reduced is not None and not args.rehearse_cpu:
                line["breakdown"] = tr.breakdown(ctx.trace_reduced)
        sys.stdout.flush()
        print(json.dumps(line), flush=True)
        return 0
    finally:
        shutil.rmtree(scratch, ignore_errors=True)


if __name__ == "__main__":
    sys.exit(main())
