"""The six ``program_span`` readers on a synthesised trace: annotations opened
by hand under ``jax.profiler`` on the CPU backend, with the stats the program
gives its spans, and device ops laid under them by the test (a CPU trace has no
device plane). Where the spans are absent every reader gives ``None``."""

import glob
import os
import sys
import time
import types

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmarks.chipbench import program_spans as ps  # noqa: E402
from benchmarks.chipbench import registry  # noqa: E402
from benchmarks.chipbench import trace_reduce as tr  # noqa: E402

BENCH = registry.load_benchmark(REPO)
DIRS = registry.search_dirs(BENCH, REPO)
NEW = ("sched_admit_host_ms", "sched_fetch_idle_ms_per_step", "decode_wasted_step_pct",
       "delivery_stalled_pct", "train_host_ms_per_step", "setup_engine_init_s")


def _reader(name):
    return registry.load_module("layer_metrics", name, DIRS)


def _record(tmp_path, body):
    import jax
    from jax.profiler import TraceAnnotation as TA
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with TA("chipbench.window"):
            body(TA)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"), recursive=True)
    return path


def _serve_script(TA):
    """Two steps: step 1 admits a miss (bucket 8) and runs a chunk of two
    streams, step 2 admits a hit (bucket 16) and runs a chunk of two."""
    for step, (rid, prefix_len, bucket, prog) in enumerate(
            [(11, 0, 8, "serving.prefill"), (12, 320, 16, "serving.suffix_prefill")], 1):
        with TA("chipbench.step"):
            with TA("serving.step", step=step, queue_depth=1, active_slots=1):
                with TA("serving.sweep"):
                    pass
                with TA("serving.admit", request_id=rid, queue_wait_ms=0.25 * step,
                        prompt_tokens=5) as admit:
                    with TA("serving.prefix_lookup", hit=int(prefix_len > 0),
                            matched_tokens=prefix_len):
                        time.sleep(0.001)
                    with TA("serving.page_table", op="acquire", pages_fresh=2,
                            pages_shared=0, cow=0):
                        time.sleep(0.001)
                    with TA(prog, request_id=rid, bucket=bucket, tokens=5,
                            prefix_len=prefix_len):
                        with TA("serving.place_inputs", program="prefill"):
                            time.sleep(0.002)
                        with TA("serving.dispatch", program="prefill"):
                            time.sleep(0.001)
                        with TA("serving.fetch", program="prefill"):
                            time.sleep(0.010)
                    admit.set_metadata(slot=0, prefix_len=prefix_len, outcome="ok")
                with TA("serving.decode_chunk", chunk=step, active_slots=2,
                        request_ids="11 12", slot_steps_run=16) as chunk:
                    with TA("serving.place_inputs", program="decode_chunk"):
                        time.sleep(0.002)
                    with TA("serving.dispatch", program="decode_chunk"):
                        time.sleep(0.001)
                    with TA("serving.fetch", program="decode_chunk"):
                        time.sleep(0.020)
                    chunk.set_metadata(tokens_kept=16 - 3 * step, deliveries=2,
                                       stalled_deliveries=step - 1)
                with TA("serving.harvest", finished=0):
                    time.sleep(0.001)
                with TA("serving.telemetry"):
                    time.sleep(0.001)


def _ctx(path, devices, **kw):
    red = tr.reduce_trace(path)
    assert red["window"][1] > red["window"][0]
    red["devices"] = devices(ps.load(path)) if devices else []
    base = dict(trace_path=path, trace_reduced=red, t0=0.0, dirs=DIRS,
                config={"serve": {"chunk_size": 8, "max_seq_len": 576}},
                traffic=registry.load_json("traffic", "chat", DIRS),
                result=types.SimpleNamespace(window=(100.0, 151.0), counters={}))
    base.update(kw)
    return types.SimpleNamespace(**base)


def _busy_first_half_of_fetches(spans):
    """The device runs through the first half of every ``serving.fetch`` and
    through the whole of every ``serving.dispatch``; idle elsewhere."""
    ops = []
    for sp in spans:
        if sp.name == "serving.fetch":
            ops.append(("fusion.1", sp.start, (sp.start + sp.end) / 2))
        elif sp.name == "serving.dispatch":
            ops.append(("fusion.2", sp.start, sp.end))
    return [{"id": 0, "ops": sorted(ops, key=lambda o: o[1]), "asyncs": [], "programs": []}]


@pytest.fixture(scope="module")
def serve_trace(tmp_path_factory):
    return _record(tmp_path_factory.mktemp("serve"), _serve_script)


@pytest.fixture(scope="module")
def empty_trace(tmp_path_factory):
    return _record(tmp_path_factory.mktemp("empty"), lambda TA: time.sleep(0.002))


def test_program_spans_are_read_with_their_stats_in_start_order(serve_trace):
    spans = ps.load(serve_trace)
    assert [s.name for s in spans if s.name in ("serving.step", "serving.decode_chunk")] \
        == ["serving.step", "serving.decode_chunk"] * 2
    chunk = ps.named(spans, "serving.decode_chunk")[1]
    assert chunk.stats == {"chunk": 2, "active_slots": 2, "request_ids": "11 12",
                           "slot_steps_run": 16, "tokens_kept": 10, "deliveries": 2,
                           "stalled_deliveries": 1}
    assert [s.name for s in ps.inside(spans, chunk)] \
        == ["serving.place_inputs", "serving.dispatch", "serving.fetch"]
    admit = ps.named(spans, "serving.admit")[0]
    assert admit.stats["outcome"] == "ok" and admit.stats["queue_wait_ms"] == 0.25
    assert not any(s.name.startswith("chipbench.") for s in spans)
    assert ps.total(ps.named(spans, "serving.decode_chunk"), "slot_steps_run") == 32


def test_counts_from_the_chunk_spans(serve_trace, capsys):
    ctx = _ctx(serve_trace, None)
    assert _reader("decode_wasted_step_pct").read(ctx) \
        == pytest.approx(100.0 * (32 - 23) / 32)
    assert _reader("delivery_stalled_pct").read(ctx) == pytest.approx(100.0 * 1 / 4)
    out = capsys.readouterr().out
    assert "23 tokens kept of 32 slot-steps in 2 chunks" in out
    # chat's cycle of lengths: 1339 decode tokens in 1424 slot-steps
    assert "1339 decode tokens in 1424 slot-steps: 5.97 % wasted" in out
    assert "1 of 4 deliveries in 2 chunks" in out


def test_admission_host_time_leaves_out_the_device_busy_part(serve_trace, capsys):
    ctx = _ctx(serve_trace, _busy_first_half_of_fetches)
    spans = ps.in_window(ctx)
    want = []
    for a in ps.named(spans, "serving.admit"):
        busy = sum((s.end - s.start) / (2 if s.name == "serving.fetch" else 1)
                   for s in ps.inside(spans, a)
                   if s.name in ("serving.fetch", "serving.dispatch"))
        want.append((a.end - a.start - busy) * 1e3)
    got = _reader("sched_admit_host_ms").read(ctx)
    assert got == pytest.approx(sum(want) / 2, rel=1e-6) and 4.0 < got < 60.0
    out = capsys.readouterr().out
    assert "admissions, miss, prefill bucket 8: 1" in out
    assert "admissions, hit, prefill bucket 16: 1" in out
    for part in ("serving.page_table", "serving.prefix_lookup", "serving.place_inputs",
                 "serving.fetch"):
        assert f"under serving.admit: {part}" in out
    assert "queue wait before the 2 admissions: 0.375 ms median" in out


def test_idle_under_the_chunks_fetch_and_its_four_neighbours(serve_trace, capsys):
    ctx = _ctx(serve_trace, _busy_first_half_of_fetches)
    spans = ps.in_window(ctx)
    halves = [(f.end - f.start) / 2 * 1e3 for c in ps.named(spans, "serving.decode_chunk")
              for f in ps.inside(spans, c, "serving.fetch")]
    got = _reader("sched_fetch_idle_ms_per_step").read(ctx)
    assert got == pytest.approx(sum(halves) / 2, rel=1e-6) and got >= 10.0
    out = capsys.readouterr().out
    lines = {ln.split(":")[0].removeprefix("device idle under "): ln
             for ln in out.splitlines() if ln.startswith("device idle under")}
    assert list(lines) == ["serving.place_inputs", "serving.dispatch", "serving.fetch",
                           "serving.harvest", "serving.telemetry"]
    assert " 0.000 ms" in lines["serving.dispatch"]      # the device was busy there
    assert "over 2 steps" in lines["serving.harvest"]
    by_span = next(ln for ln in out.splitlines() if ln.startswith("idle gaps of 20 us"))
    assert by_span.split(": ", 1)[1].startswith("serving.fetch 0.0")   # the largest
    assert "serving.dispatch" not in by_span and "a program span names" in by_span


def test_train_host_time_is_the_median_train_step_span(tmp_path, capsys):
    def body(TA):
        for step in (41, 42, 43):
            with TA("chipbench.step"):
                with TA("train_step", step=step):
                    with TA("train.host_batch"):
                        time.sleep(0.002)
                    with TA("train.dispatch"):
                        time.sleep(0.001 * (step - 40))
                    with TA("train.bookkeeping"):
                        pass
    ctx = _ctx(_record(tmp_path, body), None)
    steps = ps.named(ps.in_window(ctx), "train_step")
    got = _reader("train_host_ms_per_step").read(ctx)
    assert got == pytest.approx(sorted(s.end - s.start for s in steps)[1] * 1e3)
    out = capsys.readouterr().out
    assert "3 train_step host spans in the traced window, steps 41..43" in out
    for part in ("train.host_batch", "train.dispatch", "train.bookkeeping"):
        assert f"under train_step: {part}" in out


def test_setup_engine_init_is_the_top_level_phases_before_the_window(monkeypatch, capsys):
    from deepspeed_tpu.observability import trace
    t = trace.Tracer()
    for name, parent, t0, t1, attrs in [
            ("setup.init_params", "setup.engine_init", 10.0, 12.0, {}),
            ("setup.engine_init", None, 9.0, 20.0, {}),
            ("setup.kv_pool", None, 20.0, 20.5, {"pool": "paged", "pages": 73}),
            ("setup.program", None, 21.0, 25.0, {"program": "prefill", "bucket": 8}),
            ("setup.build_train_step", None, 30.0, 33.0, {}),
            ("setup.program", None, 120.0, 121.0, {"program": "prefill", "bucket": 64})]:
        t._phases.append({"name": name, "t0": t0, "t1": t1, "parent": parent,
                          "attrs": attrs})
    monkeypatch.setattr(trace, "_tracer", t)
    ctx = types.SimpleNamespace(t0=5.0, result=types.SimpleNamespace(window=(100.0, 151.0)))
    assert _reader("setup_engine_init_s").read(ctx) == pytest.approx(11.0 + 0.5 + 3.0)
    out = capsys.readouterr().out
    assert "set-up phase setup.init_params (in setup.engine_init): 2.000 s" in out
    assert "setup.program total: 4.000 s over 1 first calls" in out   # not the one at 120 s
    assert "pool=paged pages=73" in out
    assert "setup.engine_init: its stages name 2.000 s of 11.000 s; 8.000 s follow" in out


@pytest.mark.parametrize("name", NEW)
def test_a_reader_gives_none_where_its_spans_are_absent(empty_trace, name, monkeypatch):
    """A program from before these spans (the parent commit): no span in the
    trace, no ``phases`` on its tracer."""
    from deepspeed_tpu.observability import trace
    monkeypatch.setattr(trace, "_tracer", types.SimpleNamespace())
    with_device = [{"id": 0, "ops": [("fusion.1", 0.0, 1.0)], "asyncs": [], "programs": []}]
    ctx = _ctx(empty_trace, lambda spans: with_device)
    assert ps.in_window(ctx) == []
    assert _reader(name).read(ctx) is None
    untraced = _ctx(empty_trace, None, trace_path=None, trace_reduced=None)
    assert _reader(name).read(untraced) is None


@pytest.mark.parametrize("name", NEW)
def test_a_new_reader_is_declared_as_its_file_says(name):
    entry = next(m for m in BENCH["per_layer"] if m["name"] == name)
    mod = _reader(name)
    assert entry["source"] == "program_span"
    assert (mod.NAME, mod.UNIT, mod.LAYER, mod.MOVES) \
        == (entry["name"], entry["unit"], entry["layer"], entry["moves"])
