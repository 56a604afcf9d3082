"""The one-clock readers of the chunk cycle (PR 55) on a recorded host trace
with device executions laid under it by the test (a CPU trace has no device
plane), the device plane shifted by -1.5, 0 and +1.5 ms against the host
plane: ``sched_chunk_gap_dev_ms`` and ``sched_chunk_turnaround_host_ms`` read
the same three times, the accepted ``sched_fetch_idle_ms_per_step`` moves by
the shift, and the printed causal bounds hold zero only at 0. ``host_pause_pct``
on a kept list, each reader on a program without the attributes, the three
entries of ``BENCHMARK.json``, and a labelled rehearsal whose kept trace holds
a planted collection as ``host.gc``."""

import glob
import json
import os
import subprocess
import sys
import time
import types

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from benchmarks.chipbench import chunk_cycles as cc  # noqa: E402
from benchmarks.chipbench import program_spans as ps  # noqa: E402
from benchmarks.chipbench import registry  # noqa: E402
from benchmarks.chipbench import trace_reduce as tr  # noqa: E402
from test_chipbench_spans import _ctx, _record  # noqa: E402

BENCH = registry.load_benchmark(REPO)
DIRS = registry.search_dirs(BENCH, REPO)
NEW = {"sched_chunk_gap_dev_ms": "device_trace",
       "sched_chunk_turnaround_host_ms": "program_span",
       "host_pause_pct": "program_span"}
CELLS = ["bloom-7b1.chat", "bloom-7b1.docqa", "nemotron-3-super-120b-a12b.conv32",
         "sdar-30b-a3b-chat.conv32", "lfm2-8b-a1b.conv32"]
CHUNKS = 6
ADMITTED = 3            # the chunk (1-based) with an admission before it
START_AFTER_S = 0.2e-3  # an execution starts this long after its dispatch does
END_BEFORE_S = 1.0e-3   # and ends this long before its fetch returns
TURNAROUND_MS = (None, 1.25, 6.5, 1.5, 1.0, 1.75)


def _reader(name):
    return registry.load_module("layer_metrics", name, DIRS)


def _script(TA):
    """Six steps of one chunk each, as the program's spans are; the third is
    preceded by an admission, whose prefill is dispatched as ``seq`` 3."""
    seq = 0
    time.sleep(0.003)       # room in the window for a device plane that lies early
    for chunk in range(1, CHUNKS + 1):
        with TA("chipbench.step"):
            with TA("serving.step", step=chunk, queue_depth=0, active_slots=2):
                if chunk == ADMITTED:
                    with TA("serving.admit", request_id=7, queue_wait_ms=0.1,
                            prompt_tokens=5):
                        seq += 1
                        with TA("serving.dispatch", program="prefill", seq=seq):
                            time.sleep(0.001)
                        with TA("serving.fetch", program="prefill", arrays=1):
                            time.sleep(0.004)
                with TA("serving.decode_chunk", chunk=chunk, active_slots=2,
                        request_ids="1 2", slot_steps_run=16) as span:
                    with TA("serving.place_inputs", program="decode_chunk", arrays=1):
                        time.sleep(0.0005)
                    seq += 1
                    with TA("serving.dispatch", program="decode_chunk", seq=seq):
                        time.sleep(0.001)
                    with TA("serving.fetch", program="decode_chunk", arrays=1):
                        time.sleep(0.012)
                    stats = dict(tokens_kept=16, deliveries=2, stalled_deliveries=0,
                                 fetch_wait_ms=12.0 + chunk)
                    if TURNAROUND_MS[chunk - 1] is not None:
                        stats.update(turnaround_ms=TURNAROUND_MS[chunk - 1],
                                     admit_ms=5.0 if chunk == ADMITTED else 0.0)
                    span.set_metadata(**stats)
                with TA("serving.harvest", finished=0):
                    time.sleep(0.0005)
                with TA("serving.telemetry"):
                    pass
    time.sleep(0.003)       # and for one that lies late


@pytest.fixture(scope="module")
def trace(tmp_path_factory):
    return _record(tmp_path_factory.mktemp("cycles"), _script)


@pytest.fixture(scope="module")
def old_trace(tmp_path_factory):
    """The parent's program: the same spans without ``seq`` and the cycle's
    three attributes."""
    def body(TA):
        for chunk in (1, 2, 3):
            with TA("chipbench.step"):
                with TA("serving.step", step=chunk, queue_depth=0, active_slots=2):
                    with TA("serving.decode_chunk", chunk=chunk, active_slots=2,
                            request_ids="1 2", slot_steps_run=16):
                        with TA("serving.dispatch", program="decode_chunk"):
                            time.sleep(0.001)
                        with TA("serving.fetch", program="decode_chunk", arrays=1):
                            time.sleep(0.004)
    return _record(tmp_path_factory.mktemp("old"), body)


def _device(shift_s=0.0):
    """A device plane for ``_ctx``: one execution a decode dispatch, from
    ``START_AFTER_S`` after its start to ``END_BEFORE_S`` before its fetch's
    return, the admission's prefill inside its own fetch; every time moved by
    ``shift_s``, as a trace's device plane lies off its host plane."""
    def build(spans):
        programs = []
        for d in ps.named(spans, "serving.dispatch"):
            fetch = next(f for f in ps.named(spans, "serving.fetch") if f.start >= d.end)
            programs.append((d.stats["program"], d.start + START_AFTER_S + shift_s,
                             fetch.end - END_BEFORE_S + shift_s))
        ops = [("fusion.1", s, e) for _, s, e in programs]
        return [{"id": 0, "ops": ops, "asyncs": [], "programs": programs}]
    return build


def _bounds(out):
    line = next(ln for ln in out.splitlines() if ln.startswith("host plane minus device"))
    lower = float(line.split("at least ")[1].split(" ms")[0])
    upper = float(line.split("at most ")[1].split(" ms")[0])
    return lower, upper, "zero lies between" in line


@pytest.mark.parametrize("shift_ms", [-1.5, 0.0, 1.5])
def test_the_one_clock_readers_do_not_move_with_the_planes_offset(trace, shift_ms, capsys):
    ctx = _ctx(trace, _device(shift_ms * 1e-3))
    level = _ctx(trace, _device())
    gap = _reader("sched_chunk_gap_dev_ms").read(ctx)
    out = capsys.readouterr().out
    turn = _reader("sched_chunk_turnaround_host_ms").read(ctx)
    # the device's gaps: four of the five (the prefill's execution lies in one)
    want = cc.device_gaps(level.trace_reduced)
    assert len(want) == CHUNKS - 2
    assert gap == pytest.approx(ps.median_ms(want), abs=1e-6) and gap > 1.0
    assert turn == 1.375                 # the median of 1.25, 1.5, 1.0, 1.75: 6.5 is left out
    lower, upper, zero = _bounds(out)
    assert lower == pytest.approx(-START_AFTER_S * 1e3 - shift_ms, abs=2e-3)
    assert upper == pytest.approx(END_BEFORE_S * 1e3 - shift_ms, abs=2e-3)
    assert zero is (shift_ms == 0.0)
    assert f"{CHUNKS} of {CHUNKS} serving.dispatch(program=decode_chunk) spans paired " \
           "BY ORDER" in out


def test_the_accepted_fetch_idle_reader_moves_by_the_shift(trace, capsys):
    old = _reader("sched_fetch_idle_ms_per_step")
    got = {ms: old.read(_ctx(trace, _device(ms * 1e-3))) for ms in (-1.5, 0.0, 1.5)}
    assert got[0.0] == pytest.approx(END_BEFORE_S * 1e3, abs=1e-3)
    assert got[-1.5] == pytest.approx(got[0.0] + 1.5, abs=0.15)     # by the shift
    assert got[1.5] < got[0.0] - 0.3     # and down, to what the dispatch leaves of it
    capsys.readouterr()


def test_the_lines_give_the_fetch_wait_and_what_the_runtime_adds(trace, capsys):
    ctx = _ctx(trace, _device())
    turn = _reader("sched_chunk_turnaround_host_ms").read(ctx)
    out = capsys.readouterr().out
    gap = ps.median_ms(cc.device_gaps(ctx.trace_reduced))
    assert f"fetch wait median {12.0 + 3.5:.3f} ms over {CHUNKS} chunks" in out
    assert f"turnaround over {CHUNKS - 2} chunks with no admission before them" in out
    assert "serving.dispatch on the host's clock, median ms by program: decode_chunk 1." in out
    assert f" over {CHUNKS}, prefill 1." in out and " over 1\n" in out
    assert f"device gap {gap:.3f} ms less host turnaround {turn:.3f} ms = " \
           f"{gap - turn:+.3f} ms that the runtime adds" in out


def test_pairing_by_order_survives_a_trace_that_opens_after_a_dispatch(trace):
    """The first execution's dispatch span began before the profiler did and
    is not in the trace; the execution is. A pairing by position alone would
    give every dispatch the execution before its own."""
    spans = ps.load(trace)
    first = cc.dispatches(spans)[0]
    late = [sp for sp in spans if not (sp.start <= first.start and sp.end >= first.end)
            and sp is not first]
    runs = cc.executions({"window": (0.0, 1e9),
                          "devices": _device()(spans)})
    assert len(runs) == CHUNKS and len(cc.dispatches(late)) == CHUNKS - 1
    found = cc.pair(late, runs)
    assert [int(c.dispatch.stats["seq"]) for c in found] == [2, 4, 5, 6, 7]
    assert [c.run for c in found] == runs[1:]
    assert all(c.dispatch.start <= c.run[0] and c.run[1] <= c.fetch.end for c in found)
    lower, upper = cc.offset_bounds(found)
    assert lower == pytest.approx(-START_AFTER_S, abs=2e-6)
    assert upper == pytest.approx(END_BEFORE_S, abs=2e-6)


def test_orders_that_come_apart_end_the_pairing(trace):
    spans = ps.load(trace)
    runs = cc.executions({"window": (0.0, 1e9), "devices": _device()(spans)})
    del runs[2]                      # an execution the device plane lost
    assert len(cc.pair(spans, runs)) == 2


@pytest.mark.parametrize("name", sorted(NEW))
def test_a_reader_gives_none_without_the_attributes_or_the_device(old_trace, trace, name,
                                                                 monkeypatch):
    from deepspeed_tpu.observability import trace as obs
    monkeypatch.setattr(obs, "_tracer", types.SimpleNamespace())   # no ``pauses``
    with_device = _ctx(old_trace, _device(), spans=[])
    assert ps.named(ps.in_window(with_device), "serving.decode_chunk")
    assert _reader(name).read(with_device) is None
    untraced = _ctx(old_trace, None, trace_path=None, trace_reduced=None, spans=[])
    assert _reader(name).read(untraced) is None
    # the change's program, rehearsed on the CPU: no device plane, no number
    monkeypatch.setattr(obs, "_tracer", obs.Tracer())
    assert _reader(name).read(_ctx(trace, None, spans=[])) is None


@pytest.mark.parametrize("name", sorted(NEW))
def test_a_new_reader_is_declared_as_its_file_says(name):
    entry = next(m for m in BENCH["per_layer"] if m["name"] == name)
    mod = _reader(name)
    assert (mod.NAME, mod.UNIT, mod.LAYER, mod.MOVES) == \
        (entry["name"], entry["unit"], entry["layer"], entry["moves"])
    assert (entry["layer"], entry["moves"], entry["better"]) == \
        ("serve scheduler", "tpot_mean_ms", "lower")
    assert entry["source"] == NEW[name] and mod.KINDS == ("serve_closed",)
    assert entry["workloads"] == CELLS             # not the two Granite cells


def test_the_three_entries_are_the_benchmarks_last():
    assert [m["name"] for m in BENCH["per_layer"][-3:]] == \
        ["sched_chunk_gap_dev_ms", "sched_chunk_turnaround_host_ms", "host_pause_pct"]
    for cell in CELLS:
        reports = {m["name"] for m in registry.metrics_of(BENCH, "per_layer", cell)}
        assert set(NEW) <= reports and "sched_fetch_idle_ms_per_step" in reports


def test_host_pause_pct_is_the_union_of_the_kept_pauses_inside_the_window(monkeypatch,
                                                                         capsys):
    from deepspeed_tpu.observability import trace as obs
    t = obs.Tracer()
    lo, hi = 100.0, 151.0
    t.record_pause("host.gc", 90.0, 90.3, ring=False, generation=2, collected=5)   # set-up
    t.record_pause("host.gc", 110.0, 110.2, ring=False, generation=2, collected=0)
    t.record_pause("host.gc", 120.0, 120.002, ring=False, generation=1, collected=3)
    # a stalled turnaround that holds the full collection: the collection counts once
    t.record_pause("host.stall", 109.95, 110.25, phase="turnaround", ms=300.0,
                   typical_ms=2.0, gc_ms=200.0)
    # a stall from inside one step into the next: 0.4 s of it between the steps
    t.record_pause("host.stall", 129.9, 130.5, phase="turnaround", ms=600.0,
                   typical_ms=2.0, gc_ms=0.0)
    t.record_pause("host.stall", 140.0, 141.0, phase="fetch", ms=1000.0,
                   typical_ms=170.0, gc_ms=0.0)
    t.record_pause("host.gc", 150.9, 151.3, ring=False, generation=2, collected=0)  # clipped
    monkeypatch.setattr(obs, "_tracer", t)
    steps = [("chipbench.step", 100.0, 130.0), ("chipbench.step", 130.4, 151.0)]
    ctx = types.SimpleNamespace(
        result=types.SimpleNamespace(window=(lo, hi)), spans=steps,
        trace_reduced={"devices": [{"id": 0}], "window": (0.0, 3.0)})
    got = _reader("host_pause_pct").read(ctx)
    # the collector's union 0.2 + 0.002 + 0.1 (clipped); each stall's time OVER its
    # typical, inside step(), less the collector's part: 0.298 - 0.2, 0.098 + 0.1, 0.83
    want = (0.302 + 0.098 + 0.198 + 0.83) / 51.0 * 100.0
    assert got == pytest.approx(want, rel=1e-9)
    out = capsys.readouterr().out
    assert "pause host.gc generation 2: 200.000 ms, 10.000 s into the window, " \
           "collected 0 (chipbench.step, in a stalled turnaround)" in out
    assert "pause host.stall phase fetch: 1000.000 ms where 170.000 is typical, " \
           "40.000 s into the window, 0.000 ms of it the collector's; of the 830.000 ms " \
           "over, 830.000 lie inside step() and 830.000 are counted" in out
    assert "of the 598.000 ms over, 198.000 lie inside step() and 198.000 are" in out
    assert "of the 298.000 ms over, 298.000 lie inside step() and 98.000 are" in out
    assert "generation 1: 1 in 2.000 ms, generation 2: 2 in 300.000 ms" in out
    assert "stalls 3 in 1900.000 ms; 0 pauses dropped" in out
    # a window without one reads 0, not None
    monkeypatch.setattr(obs, "_tracer", obs.Tracer())
    assert _reader("host_pause_pct").read(ctx) == 0.0


def test_a_rehearsal_keeps_a_planted_collection_in_its_trace_as_host_gc(tmp_path):
    """The cell as the driver runs it, at the rehearsal's widths, with a
    ``gc.collect()`` planted as the traced window opens. The harness removes
    its trace when it ends, so the run keeps it (``rmtree`` a no-op in the
    child, its ``TMPDIR`` this test's)."""
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env.update(JAX_PLATFORMS="cpu", BENCH_RUN="7", PYTHONPATH=REPO, TMPDIR=str(tmp_path))
    script = os.path.join(REPO, "benchmarks", "chipbench", "run.py")
    keep = ("import gc, runpy, shutil, sys; shutil.rmtree = lambda *a, **k: None; "
            "from benchmarks.chipbench import harness; "
            "start = harness.Context.start_trace; "
            "harness.Context.start_trace = lambda self: (start(self), gc.collect()); "
            f"sys.argv = [{script!r}] + sys.argv[1:]; "
            f"runpy.run_path({script!r}, run_name='__main__')")
    out = subprocess.run(
        [sys.executable, "-c", keep, "--workload", "bloom-7b1.chat", "--seed",
         "3000000019", "--seconds", "3", "--trace", "1", "--rehearse-cpu"], env=env,
        cwd=REPO, capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    last = json.loads(out.stdout.strip().splitlines()[-1])
    assert last["correct"] is True and last["failed"] == 0, out.stdout[-3000:]
    assert not set(NEW) & set(last["metrics"])          # a rehearsal: no number
    assert "pause host.gc generation 2: " in out.stdout
    assert "host pauses kept inside the " in out.stdout
    (path,) = glob.glob(os.path.join(str(tmp_path), "chipbench_*", "trace", "**",
                                     "*.xplane.pb"), recursive=True)
    events = [ev for plane in tr.load(path).planes if plane.name == "/host:CPU"
              for line in plane.lines for ev in line.events]
    window = next(ev for ev in events if ev.name == "chipbench.window")
    full = [ev for ev in events if ev.name == "host.gc"]
    assert full and all(dict(ev.stats)["generation"] == 2 for ev in full)
    assert any(window.start_ns <= ev.start_ns
               and ev.start_ns + ev.duration_ns <= window.start_ns + window.duration_ns
               for ev in full)
    chunks = ps.named(ps.load(path), "serving.decode_chunk")
    assert chunks and all("fetch_wait_ms" in sp.stats for sp in chunks)
    assert all("seq" in sp.stats for sp in ps.named(ps.load(path), "serving.dispatch"))
