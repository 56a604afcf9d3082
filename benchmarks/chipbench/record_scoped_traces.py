#!/usr/bin/env python3
"""Record the two small traces ``tests/unit/chipbench/test_chipbench_scopes.py``
reads, on a chip:

    chiprun -- python3 benchmarks/chipbench/record_scoped_traces.py chiprun_out/scoped_traces

``train_125m_scoped_8steps.xplane.pb.gz``: eight steps of ``gpt2-125m.seq1k``'s
engine at its real widths, all inside the ``chipbench.window`` span.
``decode_tiny_scoped.xplane.pb.gz``: ``bloom-7b1``'s scheduler at the
configuration's REHEARSAL widths (2 layers of d 128: a trace of a few hundred
kilobytes, no number of it means anything), two callers, a few chunks. The
engine and the scheduler are built by the traffic kinds' own builders, so the
traces hold what a run of the cell holds: the program's spans and the
declared scopes of its ops. Copy the two files to
``tests/unit/chipbench/testdata/`` by hand; nothing else reads this script."""

import gzip
import os
import shutil
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import numpy as np  # noqa: E402

from benchmarks.chipbench import registry  # noqa: E402
from benchmarks.chipbench.harness import Context, say  # noqa: E402


def context(bench, dirs, workload: str, devices, rehearse: bool, scratch: str):
    cell = registry.cell_of(bench, workload)
    with open(registry.config_file_of(bench, cell["config"], ROOT)) as f:
        import json
        config = json.load(f)
    traffic = registry.load_json("traffic", cell["traffic"], dirs)
    if rehearse:
        config, traffic = registry.rehearsal_view(config), registry.rehearsal_view(traffic)
    return Context(cell=cell, config=config, traffic=traffic, kind_name=traffic["kind"],
                   seed=36, seconds=0.0, trace=True, rehearse=False, devices=devices,
                   probe=None, t0=time.monotonic(),
                   trace_dir=os.path.join(scratch, workload), dirs=dirs)


def keep(ctx, out_dir: str, name: str) -> None:
    path = os.path.join(out_dir, name)
    with open(ctx.trace_path, "rb") as src, gzip.open(path, "wb", compresslevel=9) as dst:
        shutil.copyfileobj(src, dst)
    say(f"{name}: {os.path.getsize(path)} bytes")


def train(bench, dirs, devices, scratch, out_dir):
    import jax
    ctx = context(bench, dirs, "gpt2-125m.seq1k", devices, False, scratch)
    kind = registry.load_module("traffic_kinds", "train", dirs)
    engine = kind.build_engine(ctx)
    tr = ctx.traffic
    stream = kind.BatchStream(1, int(ctx.config["train"]["micro_batch_per_chip"]),
                              int(tr["sequence_length"]), tr["stream"]["motif_tokens"],
                              tr["stream"]["motif_vocab"])
    for _ in range(3):
        loss = engine.train_batch(stream.next())
    jax.block_until_ready(loss)
    ctx.start_trace()
    for _ in range(8):
        loss = engine.train_batch(stream.next())
    jax.block_until_ready(loss)
    ctx.stop_trace()
    keep(ctx, out_dir, "train_125m_scoped_8steps.xplane.pb.gz")


def decode(bench, dirs, devices, scratch, out_dir):
    ctx = context(bench, dirs, "bloom-7b1.chat", devices, True, scratch)
    kind = registry.load_module("traffic_kinds", "serve_closed", dirs)
    cfg, _, sched = kind.build_scheduler(ctx)
    rng = np.random.default_rng(0)

    def serve(n_requests, tokens):
        for _ in range(n_requests):
            sched.submit(rng.integers(1, cfg.vocab_size, size=9).astype(np.int32),
                         max_new_tokens=tokens)
        sched.run()

    serve(2, 6)
    ctx.start_trace()
    serve(2, 22)
    ctx.stop_trace()
    keep(ctx, out_dir, "decode_tiny_scoped.xplane.pb.gz")


def main(argv) -> int:
    out_dir = os.path.abspath(argv[1] if len(argv) > 1 else "chiprun_out/scoped_traces")
    os.makedirs(out_dir, exist_ok=True)
    import jax
    devices = jax.devices()[:1]
    if devices[0].platform != "tpu":
        print("record_scoped_traces: no TPU visible to JAX", file=sys.stderr)
        return 2
    bench = registry.load_benchmark(ROOT)
    dirs = registry.search_dirs(bench, ROOT)
    scratch = tempfile.mkdtemp(prefix="scoped_traces_")
    try:
        train(bench, dirs, devices, scratch, out_dir)
        decode(bench, dirs, devices, scratch, out_dir)
    finally:
        shutil.rmtree(scratch, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
