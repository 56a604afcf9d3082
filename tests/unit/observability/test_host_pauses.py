"""Host pauses and the chunk cycle (PR 55): the one ``gc.callbacks`` hook, the
kept list ``tracer.pauses``, the scheduler's fetch wait and turnaround from
the stamps its spans take, a stalled chunk kept as ``host.stall``, and all of
it visible through the operator's capture (``configure_capture``).

CPU backend, tiny model: counts, attributes and nesting, never a time that
means anything on a chip.
"""

import gc
import glob
import inspect
import os
import sys

import numpy as np
import pytest

from deepspeed_tpu.inference.serving import telemetry as tel
from deepspeed_tpu.observability import metrics, schema, trace
from deepspeed_tpu.observability.trace import Tracer, get_tracer

pytestmark = pytest.mark.observability

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))
CHUNK = 4
TAGS = ("host/gc_pause_ms", "host/gc_collections_total", "host/stalls_total",
        "host/stall_ms_total", "serving/chunk_fetch_wait_ms",
        "serving/chunk_turnaround_ms")


@pytest.fixture(autouse=True)
def _fresh(monkeypatch):
    """A tracer of this test's own behind ``get_tracer()``, the hook in."""
    t = Tracer()
    monkeypatch.setattr(trace, "_tracer", t)
    trace.install_gc_hook()
    yield t
    trace.install_gc_hook()


def _engine(cap=256):
    import jax.numpy as jnp
    from deepspeed_tpu.inference.config import DeepSpeedInferenceConfig
    from deepspeed_tpu.inference.engine import InferenceEngine
    from deepspeed_tpu.models.causal_lm import gpt2_cfg
    return InferenceEngine(
        gpt2_cfg(vocab_size=96, max_seq_len=cap, n_embd=32, n_layer=2, n_head=4,
                 dtype=jnp.float32),
        DeepSpeedInferenceConfig(dtype="float32", max_out_tokens=cap))


def _scheduler(engine=None, **kw):
    from deepspeed_tpu.inference.serving import (ContinuousBatchingScheduler,
                                                 ServingConfig)
    return ContinuousBatchingScheduler(engine or _engine(), ServingConfig(
        slots=2, chunk_size=CHUNK, max_seq_len=256, **kw))


def _count(tag):
    snap = metrics.get_registry().snapshot().get(tag)
    return 0 if snap is None else snap.get("count", snap.get("value"))


# ------------------------------------------------------------------- the hook
class TestTheCollectorsHook:
    def test_it_installs_once_and_uninstalls(self):
        before = gc.callbacks.count(trace._gc_hook)
        assert before == 1
        trace.install_gc_hook()
        get_tracer()
        assert gc.callbacks.count(trace._gc_hook) == 1
        trace.uninstall_gc_hook()
        assert trace._gc_hook not in gc.callbacks
        get_tracer()                 # taken out by hand: nothing puts it back
        assert trace._gc_hook not in gc.callbacks
        n = _count("host/gc_collections_total")
        gc.collect()
        assert _count("host/gc_collections_total") == n
        trace.install_gc_hook()
        assert gc.callbacks.count(trace._gc_hook) == 1

    def test_the_first_get_tracer_of_a_process_installs_it(self, monkeypatch):
        trace.uninstall_gc_hook()
        monkeypatch.setattr(trace, "_gc_hooked", None)
        get_tracer()
        assert gc.callbacks.count(trace._gc_hook) == 1

    def test_a_forced_collection_lands_in_the_registry_and_the_kept_list(self, _fresh):
        n, c = _count("host/gc_pause_ms"), _count("host/gc_collections_total")
        junk = [[i] for i in range(1000)]
        for a, b in zip(junk, junk[1:]):
            a.append(b), b.append(a)
        del junk, a, b
        gc.collect()
        # (reading the registry allocates: a young collection may slip in)
        assert _count("host/gc_pause_ms") >= n + 1
        assert _count("host/gc_collections_total") >= c + 1
        (p,) = [p for p in _fresh.pauses if p["attrs"]["generation"] == 2]
        assert p["name"] == "host.gc" and p["t1"] > p["t0"]
        assert p["attrs"]["collected"] >= 1000
        assert set(p) == {"name", "t0", "t1", "attrs"}
        assert _fresh.spans == []            # never the ring

    def test_a_short_young_collection_is_counted_and_not_kept(self, _fresh, monkeypatch):
        monkeypatch.setattr(trace, "GC_KEEP_MS", 1e6)
        c = _count("host/gc_collections_total")
        gc.collect(0)
        assert _count("host/gc_collections_total") >= c + 1
        assert [p for p in _fresh.pauses if p["attrs"]["generation"] != 2] == []
        monkeypatch.setattr(trace, "GC_KEEP_MS", 0.0)     # "a pause of 1 ms and more"
        gc.collect(0)
        assert 0 in [p["attrs"]["generation"] for p in _fresh.pauses]

    def test_a_stand_in_tracer_without_the_list_breaks_nothing(self, monkeypatch, capsys):
        import types
        monkeypatch.setattr(trace, "_tracer", types.SimpleNamespace())
        gc.collect()
        assert "Exception ignored" not in capsys.readouterr().err


# ---------------------------------------------------------------- the kept list
class TestTheKeptList:
    def test_it_drops_oldest_counts_drops_and_leaves_the_phases_alone(self, monkeypatch):
        monkeypatch.setattr(trace, "MAX_PAUSES", 4)
        t = Tracer()
        with t.phase("setup.engine_init"):
            pass
        for i in range(7):
            t.record_pause("host.stall", float(i), i + 0.5, phase="fetch", ms=500.0,
                           typical_ms=1.0, gc_ms=0.0)
        assert [p["t0"] for p in t.pauses] == [3.0, 4.0, 5.0, 6.0]
        assert t.pauses_dropped == 3
        assert [p["name"] for p in t.phases] == ["setup.engine_init"]
        assert t.spans == [] and t.dropped == 0

    def test_a_stall_reaches_the_ring_while_the_tracer_is_enabled(self):
        t = Tracer().enable()
        t.record_pause("host.stall", 10.0, 10.5, phase="turnaround", ms=500.0,
                       typical_ms=2.0, gc_ms=0.0)
        t.record_pause("host.gc", 11.0, 11.1, ring=False, generation=2, collected=0)
        (s,) = t.spans
        assert (s["name"], s["cat"], s["parent_id"]) == ("host.stall", "host", None)
        assert s["dur"] == pytest.approx(0.5e6) and s["attrs"]["phase"] == "turnaround"
        assert [p["name"] for p in t.pauses] == ["host.stall", "host.gc"]

    def test_the_collectors_part_of_a_stretch_of_time(self):
        t = Tracer()
        t.record_pause("host.gc", 1.0, 1.2, ring=False, generation=2, collected=0)
        t.record_pause("host.stall", 1.0, 2.0, phase="fetch", ms=1000.0,
                       typical_ms=1.0, gc_ms=200.0)
        t.record_pause("host.gc", 3.0, 3.1, ring=False, generation=1, collected=0)
        assert t.gc_ms_between(0.0, 10.0) == pytest.approx(300.0)
        assert t.gc_ms_between(1.1, 3.05) == pytest.approx(150.0)
        assert t.gc_ms_between(1.3, 2.9) == 0.0


# ------------------------------------------------------------- the chunk cycle
def _run(sched, steps=10**6, at=None):
    n = 0
    while sched.busy and n < steps:
        sched.step()
        n += 1
        if at and n in at:
            at[n]()
    return n


class TestTheChunkCycle:
    @pytest.fixture(autouse=True)
    def _floor(self, monkeypatch):
        # this machine's hiccups under six test workers are no stall
        monkeypatch.setattr(tel, "STALL_FLOOR_MS", 300.0)

    def test_a_planted_stall_is_kept_with_its_phase(self, _fresh):
        sched = _scheduler()
        sched.submit(list(range(1, 9)), max_new_tokens=160)
        n = _count("host/stalls_total")
        _run(sched, at={tel.STALL_REFRESH_CHUNKS + 4:
                        lambda: sched.executor.stall_next(0.6)})
        (p,) = [p for p in _fresh.pauses if p["name"] == "host.stall"]
        # the hook sleeps before the dispatch: the host's turnaround, not the fetch
        assert p["attrs"]["phase"] == "turnaround"
        assert 600.0 <= p["attrs"]["ms"] < 900.0 and p["attrs"]["typical_ms"] < 100.0
        assert p["t1"] - p["t0"] == pytest.approx(p["attrs"]["ms"] * 1e-3, abs=1e-5)
        assert p["attrs"]["gc_ms"] == pytest.approx(
            _fresh.gc_ms_between(p["t0"], p["t1"]), abs=1e-3)
        assert sched.telemetry.stalls == 1
        assert _count("host/stalls_total") == n + 1
        assert sched.telemetry.snapshot()["host_stalls"] == 1

    def test_a_window_without_one_keeps_none(self, _fresh):
        sched = _scheduler()
        sched.submit(list(range(1, 9)), max_new_tokens=160)
        chunks = _count("serving/chunk_fetch_wait_ms")
        steps = _run(sched)
        assert [p for p in _fresh.pauses if p["name"] == "host.stall"] == []
        assert sched.telemetry.stalls == 0 and sched.telemetry.stall_ms == 0.0
        hist = sched.telemetry.chunk_ms
        assert hist["fetch"].count == steps > 2 * tel.STALL_REFRESH_CHUNKS
        assert hist["turnaround"].count == hist["fetch"].count - 1
        assert _count("serving/chunk_fetch_wait_ms") == chunks + hist["fetch"].count

    def test_a_collection_between_two_chunks_is_a_stall_with_its_gc_ms(self, _fresh,
                                                                       monkeypatch):
        monkeypatch.setattr(tel, "STALL_FLOOR_MS", 0.0)
        monkeypatch.setattr(tel, "STALL_MULTIPLE", {"fetch": 1e9, "turnaround": 1e9})
        sched = _scheduler()
        sched.submit(list(range(1, 9)), max_new_tokens=120)

        def collect():      # from here on every turnaround is "a stall"
            monkeypatch.setattr(tel, "STALL_MULTIPLE", {"fetch": 1e9, "turnaround": 0.0})
            gc.collect()

        _run(sched, steps=tel.STALL_REFRESH_CHUNKS + 6,
             at={tel.STALL_REFRESH_CHUNKS + 4: collect})
        first = next(p for p in _fresh.pauses if p["name"] == "host.stall")
        (full,) = [p for p in _fresh.pauses if p["attrs"].get("generation") == 2
                   and first["t0"] <= p["t0"] and p["t1"] <= first["t1"]]
        assert first["attrs"]["gc_ms"] == pytest.approx(
            (full["t1"] - full["t0"]) * 1e3, abs=2e-3)

    def test_the_fetch_phase_is_held_to_its_own_multiple(self, _fresh):
        t = tel.ServingTelemetry()
        now = 100.0
        for i in range(tel.STALL_REFRESH_CHUNKS):
            t.on_cycle(now + 0.001, now + 0.002, now + 0.202, prev_fetched=now)
            now += 0.21
        assert t.stalls == 0 and t._typical_ms["fetch"] == pytest.approx(200.0, rel=0.09)
        # 1.4 x the median: a long chunk; 0.5 s more: the runtime sat on the copy
        t.on_cycle(now + 0.001, now + 0.002, now + 0.282, prev_fetched=now)
        assert t.stalls == 0
        at = t.on_cycle(now + 0.301, now + 0.302, now + 1.002, prev_fetched=now + 0.3)
        assert at == {"fetch_wait_ms": 700.0, "turnaround_ms": 1.0, "admit_ms": 0.0}
        (p,) = _fresh.pauses
        assert (p["name"], p["attrs"]["phase"], p["attrs"]["ms"]) == \
            ("host.stall", "fetch", 700.0)
        assert (p["t0"], p["t1"]) == (now + 0.302, now + 1.002)
        assert t.stalls == 1      # and what it cost is the time over the median
        assert t.stall_ms == pytest.approx(700.0 - p["attrs"]["typical_ms"], abs=1e-2)

    def test_no_stall_is_called_before_the_first_median(self, _fresh):
        t = tel.ServingTelemetry()
        t.on_cycle(1.0, 1.001, 9.0, prev_fetched=0.5)       # a compile, say
        assert t.stalls == 0 and _fresh.pauses == []

    def test_an_admission_between_two_chunks_is_left_out_of_the_turnaround(self, _fresh):
        _fresh.enable()
        sched = _scheduler()
        sched.submit(list(range(1, 9)), max_new_tokens=40)
        _run(sched, steps=3)
        sched.submit([3, 4, 5, 6, 7], max_new_tokens=12)     # admitted inside a stream
        _run(sched)
        ring = sorted(_fresh.spans, key=lambda s: s["ts"])
        chunks = [s for s in ring if s["name"] == "serving.decode_chunk"]
        assert "turnaround_ms" not in chunks[0]["attrs"]     # nothing ran before it
        assert all({"fetch_wait_ms", "turnaround_ms", "admit_ms"} <= set(c["attrs"])
                   for c in chunks[1:])
        (late,) = [c for c in chunks[1:] if c["attrs"]["admit_ms"] > 0]
        before = chunks[chunks.index(late) - 1]

        def inside(outer, name):
            return [s for s in ring if s["name"] == name and s["ts"] >= outer["ts"]
                    and s["ts"] + s["dur"] <= outer["ts"] + outer["dur"] + 1]

        (fetch,) = inside(before, "serving.fetch")
        (dispatch,) = inside(late, "serving.dispatch")
        admits = [s for s in ring if s["name"] == "serving.admit"
                  and fetch["ts"] <= s["ts"] <= dispatch["ts"]]
        assert len(admits) == 1
        gap_ms = (dispatch["ts"] + dispatch["dur"] - fetch["ts"] - fetch["dur"]) * 1e-3
        assert late["attrs"]["admit_ms"] == pytest.approx(admits[0]["dur"] * 1e-3, abs=5e-3)
        assert late["attrs"]["turnaround_ms"] == pytest.approx(
            gap_ms - late["attrs"]["admit_ms"], abs=5e-3)
        assert late["attrs"]["fetch_wait_ms"] == pytest.approx(
            inside(late, "serving.fetch")[0]["dur"] * 1e-3, abs=5e-3)

    def test_an_empty_servers_wait_is_no_turnaround(self, _fresh):
        _fresh.enable()
        sched = _scheduler()
        sched.submit([1, 2, 3], max_new_tokens=6)
        _run(sched)
        sched.step()                     # nothing to do: the cycle is broken here
        sched.submit([4, 5, 6], max_new_tokens=6)
        _run(sched)
        chunks = [s for s in sorted(_fresh.spans, key=lambda s: s["ts"])
                  if s["name"] == "serving.decode_chunk"]
        firsts = [c for c in chunks if "turnaround_ms" not in c["attrs"]]
        assert len(firsts) == 2 and len(chunks) > 2

    def test_elapsed_and_the_fetch_wait_come_from_the_spans_stamps(self):
        from deepspeed_tpu.inference.serving.executor import ChunkedDecodeExecutor
        sched = _scheduler()
        sched.submit([1, 2, 3], max_new_tokens=8)
        sched.step()
        res = sched.executor.run_chunk(sched._toks, sched._lens, sched._active,
                                       sched._remaining, sched._eos, sched._seeds,
                                       sched._steps)
        dispatched, fetch_t0, fetched = res.stamps
        assert dispatched <= fetch_t0 <= fetched
        assert 0 < fetched - fetch_t0 <= res.elapsed
        for fn in (ChunkedDecodeExecutor.run_chunk, ChunkedDecodeExecutor._timed,
                   ChunkedDecodeExecutor._dispatch):
            assert "monotonic" not in inspect.getsource(fn), fn.__name__

    def test_a_dispatch_carries_the_running_count_of_programs(self, _fresh):
        _fresh.enable()
        sched = _scheduler()
        sched.submit([1, 2, 3], max_new_tokens=10)
        _run(sched)
        seqs = [s["attrs"]["seq"] for s in sorted(_fresh.spans, key=lambda s: s["ts"])
                if s["name"] == "serving.dispatch"]
        assert seqs == sorted(seqs) and len(set(seqs)) == len(seqs) >= 4
        assert seqs[-1] == sched.executor.pool.programs


# ------------------------------------------------------------- declared names
class TestTheSchema:
    def test_the_lint_knows_every_new_name(self):
        import ast
        from deepspeed_tpu.analysis.ast_rules import iter_span_names_from_tree
        assert schema.lint_emission_sites(REPO) == []
        for tag in TAGS:
            assert schema.resolve(tag) == tag
        seen = {}
        for rel in ("deepspeed_tpu/observability/trace.py",
                    "deepspeed_tpu/inference/serving/telemetry.py"):
            assert rel in schema.SPAN_MODULES and rel in schema.EMITTER_MODULES
            tags = {t for t, _ in schema.iter_emission_tags(os.path.join(REPO, rel))}
            with open(os.path.join(REPO, rel)) as f:
                names = {n for n, _ in iter_span_names_from_tree(ast.parse(f.read()))}
            seen[rel] = (tags, names)
        assert {"host/gc_pause_ms", "host/gc_collections_total"} <= seen[
            "deepspeed_tpu/observability/trace.py"][0]
        assert set(TAGS[2:]) <= seen["deepspeed_tpu/inference/serving/telemetry.py"][0]
        assert seen["deepspeed_tpu/observability/trace.py"][1] == {"host.gc"}
        assert seen["deepspeed_tpu/inference/serving/telemetry.py"][1] == {"host.stall"}

    def test_an_undeclared_host_tag_is_a_finding(self, tmp_path):
        bad = tmp_path / "emitter.py"
        bad.write_text("def f(registry):\n"
                       "    registry.histogram('host/gc_pause_ms').observe(1.0)\n"
                       "    registry.counter('host/gc_typo_total').inc()\n")
        tags = [t for t, _ in schema.iter_emission_tags(str(bad))]
        assert tags == ["host/gc_pause_ms", "host/gc_typo_total"]
        assert schema.resolve(tags[0]) and schema.resolve(tags[1]) is None

    @pytest.mark.parametrize("name,sinks,attrs", [
        ("host.gc", schema.PAUSE_GC, ("generation", "collected")),
        ("host.stall", schema.PAUSE_STALL, ("phase", "ms", "typical_ms", "gc_ms"))])
    def test_the_two_pauses_are_declared_with_their_attributes(self, name, sinks, attrs):
        assert schema.SPANS[name][:3] == (sinks, "serve scheduler", attrs)
        assert "host_pause_pct" in schema.SPANS[name][3]

    @pytest.mark.parametrize("name,reader", [
        ("serving.fetch", "sched_chunk_gap_dev_ms"),
        ("serving.dispatch", "sched_chunk_gap_dev_ms"),
        ("serving.decode_chunk", "sched_chunk_turnaround_host_ms")])
    def test_the_reads_column_names_the_new_readers(self, name, reader):
        assert reader in schema.SPANS[name][3]
        assert os.path.isfile(os.path.join(REPO, "benchmarks", "chipbench",
                                           "layer_metrics", reader + ".py"))


# ------------------------------------------------------- the operator's capture
def test_the_operators_capture_holds_the_collection_and_the_chunks_numbers(tmp_path):
    """``configure_capture`` / ``SIGUSR2``'s path (default profiler options,
    no harness): ``host.gc`` is an event of the host plane with its
    generation, and every chunk span carries the cycle's numbers as stats."""
    from deepspeed_tpu.observability import profiler
    if REPO not in sys.path:
        sys.path.insert(0, REPO)
    from benchmarks.chipbench import trace_reduce as tr
    sched = _scheduler()
    sched.submit(list(range(1, 9)), max_new_tokens=60)
    _run(sched, steps=2)
    cap = profiler.configure_capture(str(tmp_path), num_ticks=6, sigusr2=False)
    try:
        cap.arm()
        _run(sched, steps=3)
        gc.collect()
        _run(sched)
    finally:
        profiler.configure_capture(None)
    assert cap.captures == 1
    (path,) = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"), recursive=True)
    events = [ev for plane in tr.load(path).planes if plane.name == "/host:CPU"
              for line in plane.lines for ev in line.events]
    full = [dict(ev.stats) for ev in events if ev.name == "host.gc"]
    assert full and all(st["generation"] == 2 and "collected" in st for st in full)
    chunks = [dict(ev.stats) for ev in events if ev.name == "serving.decode_chunk"]
    assert len(chunks) >= 4
    assert all({"fetch_wait_ms", "turnaround_ms", "admit_ms"} <= set(st)
               for st in chunks)
    seqs = [dict(ev.stats)["seq"] for ev in events if ev.name == "serving.dispatch"]
    assert len(seqs) >= 4 and len(set(seqs)) == len(seqs)
    assert np.all(np.asarray([st["fetch_wait_ms"] for st in chunks]) > 0)
