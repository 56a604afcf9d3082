"""Observability spine (PR 10): tracer, bounded metrics registry, schema lint,
Prometheus exposition, cross-process trace join, overhead A/B smoke.

Everything here runs on the CPU backend in seconds — the lane is hoisted
second (after fault tolerance) in tier-1 collection.
"""

import importlib.util
import json
import os
import threading
import time
import urllib.request

import numpy as np
import pytest

from deepspeed_tpu.observability import schema
from deepspeed_tpu.observability.metrics import (Histogram, MetricsRegistry,
                                                 start_metrics_server)
from deepspeed_tpu.observability.profiler import ProfilerCapture
from deepspeed_tpu.observability.trace import SpanContext, Tracer, get_tracer

pytestmark = pytest.mark.observability

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


@pytest.fixture(autouse=True)
def _fresh_tracer():
    """The process tracer is global state like the mesh: never leak an enabled
    tracer (or its spans) into the next test."""
    t = get_tracer()
    t.disable()
    t.reset()
    yield t
    t.disable()
    t.reset()


def _small_engine(vocab=96, seq=64, slots=2, chunk=2, **kw):
    import jax.numpy as jnp

    from deepspeed_tpu.inference.config import DeepSpeedInferenceConfig
    from deepspeed_tpu.inference.engine import InferenceEngine
    from deepspeed_tpu.models.causal_lm import gpt2_cfg
    return InferenceEngine(
        gpt2_cfg(vocab_size=vocab, max_seq_len=seq, n_embd=32, n_layer=2,
                 n_head=4, dtype=jnp.float32),
        DeepSpeedInferenceConfig(dtype="float32", max_out_tokens=seq))


# ---------------------------------------------------------------- histograms
class TestHistogram:
    def test_percentiles_vs_numpy(self):
        rng = np.random.default_rng(0)
        for dist in (rng.lognormal(3.0, 1.0, 5000),
                     rng.uniform(0.5, 500.0, 5000),
                     rng.exponential(40.0, 5000)):
            h = Histogram()
            for v in dist:
                h.observe(float(v))
            for q in (50, 90, 95, 99):
                truth = float(np.percentile(dist, q))
                est = h.percentile(q)
                # log-bucket growth 1.08 bounds relative error per bucket;
                # interpolation keeps it well inside 10%
                assert abs(est - truth) / truth < 0.10, (q, est, truth)

    def test_bounded_memory_and_stats(self):
        h = Histogram()
        n_buckets = len(h.counts)
        for v in np.random.default_rng(1).lognormal(2, 2, 20000):
            h.observe(float(v))
        assert len(h.counts) == n_buckets          # fixed, forever
        assert h.count == 20000
        assert h.min is not None and h.max is not None
        assert h.min <= h.percentile(50) <= h.max

    def test_edge_values(self):
        h = Histogram()
        assert h.percentile(50) is None            # empty
        h.observe(0.0)                             # underflow bucket
        h.observe(-3.0)
        h.observe(1e12)                            # overflow bucket
        assert h.count == 3
        assert h.percentile(0) is not None
        assert h.percentile(100) == pytest.approx(1e12)


# ------------------------------------------------------------------ registry
class TestRegistry:
    def test_kinds_and_undeclared(self):
        r = MetricsRegistry()
        r.record("serving/completed_total", 3, 1)
        r.record("serving/completed_total", 7, 2)
        r.record("serving/queue_depth", 5, 2)
        r.record("serving/ttft_ms", 12.5, 1)
        snap = r.snapshot()
        assert snap["serving/completed_total"]["value"] == 7
        assert snap["serving/queue_depth"]["value"] == 5
        assert snap["serving/ttft_ms"]["count"] == 1
        with pytest.raises(KeyError):
            r.record("serving/not_a_declared_tag", 1.0)
        with pytest.raises(TypeError):
            r.gauge("serving/ttft_ms")             # kind mismatch

    def test_counter_monotone(self):
        r = MetricsRegistry()
        r.record("router/retried_total", 5, 1)
        r.record("router/retried_total", 2, 2)     # stale replay: no rewind
        assert r.snapshot()["router/retried_total"]["value"] == 5

    def test_feed_sums_counters_across_emitters(self):
        """N replicas each publish their OWN cumulative totals; per-emitter
        feeds must make /metrics the process TOTAL, not the max replica."""
        from deepspeed_tpu.observability.metrics import RegistryFeed
        r = MetricsRegistry()
        rep0, rep1 = RegistryFeed(r), RegistryFeed(r)
        rep0.record_events([("serving/completed_total", 5, 1)])
        rep1.record_events([("serving/completed_total", 3, 1)])
        rep0.record_events([("serving/completed_total", 6, 2)])   # +1
        assert r.snapshot()["serving/completed_total"]["value"] == 9
        # a FRESH emitter restarting at 0 keeps adding (no stale-freeze)
        rep2 = RegistryFeed(r)
        rep2.record_events([("serving/completed_total", 2, 1)])
        assert r.snapshot()["serving/completed_total"]["value"] == 11
        # gauges stay last-write-wins through the feed
        rep0.record_events([("serving/queue_depth", 7, 3)])
        assert r.snapshot()["serving/queue_depth"]["value"] == 7

    def test_monitor_is_one_export_backend(self):
        r = MetricsRegistry()
        events = []

        class FakeMonitor:
            enabled = True

            def write_events(self, evs):
                events.extend(evs)

        r.attach_monitor(FakeMonitor())
        r.record("router/queue_depth", 4.0, 9)
        assert events == [("router/queue_depth", 4.0, 9)]

    def test_prometheus_exposition_parses(self):
        r = MetricsRegistry()
        r.record("serving/completed_total", 11, 1)
        r.record("router/replica0/health", 0, 1)
        r.record("router/replica1/health", 2, 1)
        for v in (1.0, 10.0, 100.0):
            r.record("serving/ttft_ms", v, 1)
        text = r.prometheus_text()
        # minimal exposition-format parser: every non-comment line is
        # `name{labels} value` with a float value; TYPE lines declare kinds
        types = {}
        samples = []
        for line in text.strip().splitlines():
            if line.startswith("# TYPE"):
                _, _, name, kind = line.split()
                types[name] = kind
            elif not line.startswith("#"):
                head, val = line.rsplit(" ", 1)
                float(val)
                samples.append(head)
        assert types["serving_completed_total"] == "counter"
        assert types["serving_ttft_ms"] == "histogram"
        assert types["router_replica_health"] == "gauge"
        assert 'router_replica_health{replica="0"}' in samples
        assert 'router_replica_health{replica="1"}' in samples
        assert any(s.startswith("serving_ttft_ms_bucket{") for s in samples)
        assert "serving_ttft_ms_count" in samples

    def test_metrics_http_server(self):
        r = MetricsRegistry()
        r.record("serving/rejected_total", 2, 1)
        server = start_metrics_server(0, registry=r)
        try:
            url = f"http://127.0.0.1:{server.server_port}/metrics"
            body = urllib.request.urlopen(url, timeout=10).read().decode()
            assert "serving_rejected_total 2" in body
            with pytest.raises(urllib.error.HTTPError):
                urllib.request.urlopen(
                    f"http://127.0.0.1:{server.server_port}/nope", timeout=10)
        finally:
            server.shutdown()


# ----------------------------------------------------------------- tag lint
class TestTagSchemaLint:
    def test_every_emission_site_is_declared(self):
        problems = schema.lint_emission_sites(REPO)
        assert problems == [], (
            "undeclared metric tags at emission sites (declare them in "
            "observability/schema.py TAGS):\n" + "\n".join(problems))

    def test_lint_walks_real_sites(self):
        # the walker must actually SEE the known emitters — an empty walk
        # would pass the lint vacuously
        seen = set()
        for rel in schema.EMITTER_MODULES:
            for tag, _ in schema.iter_emission_tags(os.path.join(REPO, rel)):
                seen.add(schema.resolve(tag))
        for expect in ("serving/ttft_ms", "router/queue_depth",
                       "Train/Samples/train_loss", "Train/step_time_ms",
                       "router/replica{i}/health", "inference/ttft_ms"):
            assert expect in seen, f"lint walker missed {expect}"

    def test_lint_catches_a_drifted_tag(self, tmp_path):
        bad = tmp_path / "bad_emitter.py"
        bad.write_text(
            "def emit(monitor):\n"
            "    monitor.write_events([('serving/typo_total', 1.0, 0)])\n")
        tags = list(schema.iter_emission_tags(str(bad)))
        assert tags and tags[0][0] == "serving/typo_total"
        assert schema.resolve("serving/typo_total") is None

    def test_template_resolution(self):
        assert schema.resolve("router/replica7/health") \
            == "router/replica{i}/health"
        assert schema.resolve("router/replica*/outstanding") \
            == "router/replica{i}/outstanding"
        assert schema.kind_of("router/replica7/outstanding") == schema.GAUGE


# -------------------------------------------------------------------- tracer
def _chrome_check(events):
    """Perfetto/Chrome trace-event schema sanity: required keys, phases,
    numeric non-negative timestamps."""
    assert events, "no trace events"
    for e in events:
        assert e["ph"] in ("X", "M")
        assert isinstance(e["name"], str) and e["name"]
        assert isinstance(e["pid"], int) and isinstance(e["tid"], int)
        if e["ph"] == "X":
            assert float(e["ts"]) >= 0 and float(e["dur"]) >= 0
            assert "trace_id" in e["args"] and "span_id" in e["args"]
    assert any(e["ph"] == "M" and e["name"] == "process_name" for e in events)


class TestTracer:
    def test_disabled_is_noop(self):
        t = Tracer()
        assert t.begin("x") is None
        assert t.start_span("y", parent=None) is None
        with t.span("z", k=1) as s:
            # the profiler's annotation is always open; the ring has nothing
            assert s.open is None and s.trace_id is None
            s.set(n=2)
        assert s.t1 >= s.t0
        t.end_span(None)
        assert t.spans == []

    def test_nesting_and_chrome_export(self, tmp_path):
        t = Tracer().enable(pid_label="test")
        root = t.begin("request", attrs={"id": 7})
        child = t.start_span("prefill", parent=root)
        t.end_span(child)
        t.record_span("queue_wait", root, root.t0, time.monotonic())
        t.end_span(root)
        spans = t.spans
        by_name = {s["name"]: s for s in spans}
        assert by_name["prefill"]["parent_id"] == by_name["request"]["span_id"]
        assert by_name["queue_wait"]["parent_id"] \
            == by_name["request"]["span_id"]
        assert len({s["trace_id"] for s in spans}) == 1
        # children nest INSIDE the parent's interval
        req = by_name["request"]
        for s in ("prefill", "queue_wait"):
            assert by_name[s]["ts"] >= req["ts"] - 1
            assert (by_name[s]["ts"] + by_name[s]["dur"]
                    <= req["ts"] + req["dur"] + 1)
        path = str(tmp_path / "trace.json")
        n = t.export_chrome(path)
        doc = json.load(open(path))
        assert n == 3
        _chrome_check(doc["traceEvents"])

    def test_bounded_with_drop_count(self):
        t = Tracer(max_spans=10).enable()
        for i in range(25):
            with t.span(f"s{i}"):
                pass
        assert len(t.spans) == 10
        assert t.dropped == 15

    def test_cross_context_join(self):
        t = Tracer().enable()
        ctx = SpanContext("traceABC", "span123")
        s = t.begin("child_side", ctx=ctx)
        t.end_span(s)
        rec = t.spans[0]
        assert rec["trace_id"] == "traceABC"
        assert rec["parent_id"] == "span123"


# -------------------------------------------------- serving column end-to-end
class TestServingTracing:
    def test_request_spans_cover_the_column(self, tmp_path):
        from deepspeed_tpu.inference.serving import (
            ContinuousBatchingScheduler, ServingConfig)
        tracer = get_tracer().enable(pid_label="test-serving")
        sched = ContinuousBatchingScheduler(
            _small_engine(), ServingConfig(slots=2, chunk_size=2,
                                           max_seq_len=64))
        h = sched.submit([5, 6, 7], max_new_tokens=6)
        sched.run()
        assert h.state.value == "finished"
        spans = tracer.spans
        mine = [s for s in spans if s["trace_id"] == h.trace_id
                or (h.trace_id is None)]
        names = [s["name"] for s in spans]
        for expect in ("replica_request", "queue_wait", "serving.admit",
                       "serving.prefill", "decode_chunk", "retire"):
            assert expect in names, (expect, names)
        by_name = {}
        for s in spans:
            by_name.setdefault(s["name"], []).append(s)
        root = by_name["replica_request"][0]
        # single trace id across the whole request column; the batch-level
        # spans of a step() root a trace per step
        request_scoped = ("replica_request", "queue_wait", "serving.admit",
                          "serving.page_table", "serving.prefill",
                          "serving.scatter_prefill", "decode_chunk", "retire")
        assert all(s["trace_id"] == root["trace_id"] for s in spans
                   if s["name"] in request_scoped
                   and s["attrs"].get("op") != "release")
        steps = {s["trace_id"] for s in by_name["serving.step"]}
        assert root["trace_id"] not in steps
        assert all(s["trace_id"] in steps
                   for s in by_name["serving.decode_chunk"])
        # decode chunks nest under the request root
        for c in by_name["decode_chunk"]:
            assert c["parent_id"] == root["span_id"]
        # chunk spans carry per-chunk token counts summing to the decode total
        chunk_tokens = sum(c["args"]["tokens"] if "args" in c
                           else c["attrs"]["tokens"]
                           for c in by_name["decode_chunk"])
        assert chunk_tokens == len(h.tokens) - 1     # token 0 came from prefill
        _chrome_check(tracer.chrome_events())

    def test_router_retry_spans_join_by_trace_id(self):
        from deepspeed_tpu.inference.serving import (Router, RouterConfig,
                                                     ServingConfig)
        from deepspeed_tpu.inference.serving.chaos import (ChaosEvent,
                                                           ChaosSchedule)
        tracer = get_tracer().enable(pid_label="test-router")
        engines = [_small_engine()]
        engines.append(_small_engine())
        engines[1].params = engines[0].params
        cfg = RouterConfig(serving=ServingConfig(slots=2, chunk_size=2,
                                                 max_seq_len=64),
                           suspect_after_s=0.05, dead_after_s=0.15,
                           recover_after_s=30.0, max_attempts=4)
        router = Router(engines, cfg)
        chaos = ChaosSchedule([ChaosEvent(kind="kill", replica=1,
                                          when="busy")])
        handles = [router.submit(np.asarray([3 + i, 5, 9], np.int32),
                                 max_new_tokens=10, seed=i)
                   for i in range(4)]
        while router.busy:
            chaos.poll(router)
            router.step()
        assert all(h.state.value == "finished" for h in handles)
        retried = [h for h in handles if h.retried > 0]
        assert retried, "chaos kill produced no retry — test is vacuous"
        spans = tracer.spans
        rr = retried[0]
        mine = [s for s in spans if s["trace_id"] == rr._root_span] \
            if rr._root_span else None
        # find the request root through its attrs (root span ended at finalize)
        roots = [s for s in spans if s["name"] == "request"
                 and s["attrs"].get("request_id") == rr.id]
        assert len(roots) == 1
        tid = roots[0]["trace_id"]
        mine = [s for s in spans if s["trace_id"] == tid]
        attempts = [s for s in mine if s["name"] == "attempt"]
        assert len(attempts) >= 2, "retry must appear as a second attempt span"
        retry_attempts = [a for a in attempts if a["attrs"].get("retry")]
        assert retry_attempts, "retry attempt span missing retry attrs"
        ra = retry_attempts[0]
        assert ra["attrs"]["retry_replica_id"] == rr.replica_id
        assert ra["attrs"].get("retry_of") in {a["span_id"] for a in attempts}
        # both the killed replica's spans and the retry replica's spans are on
        # THIS trace: >= 2 replica_request roots parented to attempt spans
        rep_roots = [s for s in mine if s["name"] == "replica_request"]
        assert len(rep_roots) >= 2
        att_ids = {a["span_id"] for a in attempts}
        assert all(r["parent_id"] in att_ids for r in rep_roots)
        # per-chunk decode spans exist under the joined trace
        assert any(s["name"] == "decode_chunk" for s in mine)
        _chrome_check(tracer.chrome_events())

    def test_drain_commits_handed_off_spans(self):
        from deepspeed_tpu.inference.serving import (Router, RouterConfig,
                                                     ServingConfig)
        tracer = get_tracer().enable(pid_label="test-drain")
        router = Router([_small_engine()],
                        RouterConfig(serving=ServingConfig(
                            slots=1, chunk_size=2, max_seq_len=64)))
        router.submit([1, 2, 3], max_new_tokens=20)
        router.submit([4, 5, 6], max_new_tokens=20)
        router.step()                    # first request in flight
        specs = router.drain()
        assert specs, "nothing handed off — drain test is vacuous"
        roots = [s for s in tracer.spans if s["name"] == "request"]
        handed = [s for s in roots if s["attrs"].get("state") == "handed_off"]
        assert len(handed) == len(specs), \
            "handed-off requests' root spans must be committed at drain"

    def test_subprocess_trace_id_join(self):
        """Cross-process lane: a subprocess-hosted replica's spans come back
        over the JSONL pipe carrying the parent's trace id."""
        from deepspeed_tpu.inference.serving.subproc import SubprocessReplica
        tracer = get_tracer().enable(pid_label="parent")
        rep = SubprocessReplica(REPO, vocab_size=96, max_seq_len=64,
                                n_embd=32, n_layer=2, n_head=4, slots=2,
                                chunk_size=2)
        try:
            rep.wait_ready()
            root = tracer.begin("request", attrs={"request_id": 0})
            rep.submit(0, [4, 5, 6], max_new_tokens=6, trace_id=root.trace_id,
                       parent_span=root.span_id)
            toks = rep.wait_tokens(0, 6)
            assert len(toks) >= 1
            rep.stop()
            tracer.end_span(root)
            child_spans = rep.take_spans()
            assert child_spans, "child streamed no spans"
            # the request's own spans carry the parent's trace id; the rest
            # are the batch-level spans of the child's step() calls
            mine = [s for s in child_spans if s["trace_id"] == root.trace_id]
            assert all(s["name"].startswith(("serving.", "setup."))
                       for s in child_spans
                       if s["trace_id"] != root.trace_id)
            assert any(s["name"] == "replica_request"
                       and s["parent_id"] == root.span_id for s in mine)
            assert any(s["name"] == "decode_chunk" for s in mine)
            assert any(s["name"] == "serving.admit"
                       and s["attrs"]["request_id"] == 0 for s in mine)
            tracer.ingest(mine, pid_label="subproc-replica")
            events = tracer.chrome_events()
            _chrome_check(events)
            # two process lanes in one Perfetto file, one trace id
            procs = {e["args"]["name"] for e in events
                     if e["ph"] == "M" and e["name"] == "process_name"}
            assert {"parent", "subproc-replica"} <= procs
            xs = [e for e in events if e["ph"] == "X"]
            assert len({e["args"]["trace_id"] for e in xs}) == 1
        finally:
            if rep.alive:
                rep.sigkill()


# ------------------------------------------------------- telemetry migration
class TestTelemetryBounded:
    def test_snapshot_keys_identical_and_bounded(self):
        from deepspeed_tpu.inference.serving.telemetry import ServingTelemetry

        class H:
            ttft, tpot = 0.05, 0.002
            state = type("S", (), {"value": "finished"})

        t = ServingTelemetry()
        from deepspeed_tpu.inference.serving.scheduler import RequestState

        class Done:
            state = RequestState.FINISHED
            ttft, tpot = 0.05, 0.002

        nb = len(t.ttft_ms.counts)
        for _ in range(5000):
            t.on_finished(Done())
        assert len(t.ttft_ms.counts) == nb         # O(1): no per-request list
        assert not hasattr(t, "ttfts") and not hasattr(t, "tpots")
        snap = t.snapshot()
        for key in ("ttft_ms_p50", "ttft_ms_p95", "tpot_ms_p50",
                    "tpot_ms_p95", "completed", "tokens_per_sec"):
            assert key in snap
        assert snap["completed"] == 5000
        assert snap["ttft_ms_p50"] == pytest.approx(50.0, rel=0.10)
        assert snap["tpot_ms_p50"] == pytest.approx(2.0, rel=0.10)

    def test_router_telemetry_bounded(self):
        from deepspeed_tpu.inference.serving.router import RouterTelemetry
        rt = RouterTelemetry()
        assert not hasattr(rt, "ttfts") and not hasattr(rt, "tpots")
        assert rt.snapshot()["ttft_ms_p50"] is None


# ------------------------------------------------------------- profiler capture
class TestProfilerCapture:
    def test_capture_n_ticks(self, tmp_path):
        import jax
        import jax.numpy as jnp
        cap = ProfilerCapture(str(tmp_path / "prof"), num_ticks=2)
        cap.arm()
        f = jax.jit(lambda x: x * 2)
        for _ in range(4):
            np.asarray(f(jnp.ones(8)))
            cap.tick("step")
        assert not cap.active
        assert cap.captures == 1
        # jax profiler wrote its logdir
        assert any(os.scandir(str(tmp_path / "prof")))

    def test_sigusr2_arms(self, tmp_path):
        import signal
        cap = ProfilerCapture(str(tmp_path / "p2"), num_ticks=1)
        prev = cap.install_sigusr2()
        try:
            os.kill(os.getpid(), signal.SIGUSR2)
            time.sleep(0.05)
            assert cap.armed
        finally:
            signal.signal(signal.SIGUSR2, prev)
            cap.close()

    def test_module_tick_noop_without_capture(self):
        from deepspeed_tpu.observability import profiler as obs_profiler
        assert obs_profiler.get_capture() is None
        obs_profiler.tick("whatever")              # must be free + safe


# ------------------------------------------------------------ train-side spans
class TestTrainSpans:
    def test_train_step_and_monitor_events(self, tmp_path):
        import sys
        sys.path.insert(0, os.path.join(REPO, "tests", "unit"))
        import deepspeed_tpu as ds
        from simple_model import base_config, random_batches, simple_model
        tracer = get_tracer().enable(pid_label="test-train")
        events = []

        class FakeMonitor:
            enabled = True

            def write_events(self, evs):
                events.extend(evs)

        engine = ds.initialize(model=simple_model(hidden_dim=8),
                               config=base_config(batch_size=16))[0]
        engine.set_monitor(FakeMonitor())
        engine.train_batch(batch=random_batches(1, 16, 8)[0])
        names = [s["name"] for s in tracer.spans]
        assert "train_step" in names
        tags = {t for t, _, _ in events}
        assert "Train/Samples/train_loss" in tags
        assert "Train/step_time_ms" in tags
        assert "Train/tokens_per_sec" in tags
        # registry carries the same counters the monitor saw
        from deepspeed_tpu.observability.metrics import get_registry
        snap = get_registry().snapshot()
        assert "Train/step_time_ms" in snap
        assert snap["Train/step_time_ms"]["count"] >= 1


# ------------------------------------------------------------ loadgen --trace-out
class TestLoadgenTrace:
    def test_loadgen_trace_out(self, tmp_path, capsys):
        spec = importlib.util.spec_from_file_location(
            "serving_loadgen_trace", os.path.join(
                REPO, "benchmarks", "serving", "loadgen.py"))
        loadgen = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(loadgen)
        trace_path = str(tmp_path / "trace.json")
        rc = loadgen.main(["--smoke", "--trace-out", trace_path])
        assert rc == 0
        doc = json.load(open(trace_path))
        _chrome_check(doc["traceEvents"])
        out = capsys.readouterr().out.strip().splitlines()[-1]
        bench = json.loads(out)
        assert bench["trace"]["spans"] > 0         # the run really traced
        assert get_tracer().enabled is False       # and left tracing off


# --------------------------------------------------- chaos soak + acceptance
class TestChaosSoakTrace:
    def test_soak_trace_joins_kill_and_retry_and_metrics_match(
            self, tmp_path, capsys):
        """The PR-10 acceptance lane: one chaos-soak loadgen run emits a
        Perfetto-loadable trace in which a killed request's original-replica
        and retry-replica spans join on one trace id (with per-chunk decode
        spans on both lanes), and ``/metrics`` serves the same counters the
        BENCH JSON reports."""
        from deepspeed_tpu.observability.metrics import get_registry
        get_registry().reset()      # counters are monotone; isolate this run
        spec = importlib.util.spec_from_file_location(
            "serving_loadgen_soak", os.path.join(REPO, "benchmarks",
                                                 "serving", "loadgen.py"))
        loadgen = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(loadgen)
        trace_path = str(tmp_path / "soak_trace.json")
        rc = loadgen.main(["--smoke", "--replicas", "2", "--chaos",
                           "kill:replica=1,when=busy", "--trace-out",
                           trace_path])
        out = capsys.readouterr().out.strip().splitlines()[-1]
        bench = json.loads(out)
        assert rc == 0
        detail = bench["detail"]
        assert detail["lost"] == 0 and detail["retried"] >= 1
        assert detail.get("parity_ok", True)

        doc = json.load(open(trace_path))
        _chrome_check(doc["traceEvents"])
        xs = [e for e in doc["traceEvents"] if e["ph"] == "X"]
        by_trace = {}
        for e in xs:
            by_trace.setdefault(e["args"]["trace_id"], []).append(e)
        # a killed-and-retried request: >= 2 attempt spans on ONE trace id,
        # the retry attempt stamped with the retry replica id, and decode
        # chunks present on the joined trace
        joined = None
        for tid, evs in by_trace.items():
            attempts = [e for e in evs if e["name"] == "attempt"]
            if len(attempts) >= 2 and any(a["args"].get("retry")
                                          for a in attempts):
                joined = (tid, evs, attempts)
                break
        assert joined is not None, \
            "no trace with a retry attempt — kill did not land or join broke"
        tid, evs, attempts = joined
        retry = [a for a in attempts if a["args"].get("retry")][0]
        assert "retry_replica_id" in retry["args"]
        assert any(e["name"] == "decode_chunk" for e in evs)
        assert any(e["name"] == "replica_request"
                   and e["args"].get("state") == "abandoned"
                   for e in evs), "killed replica's lane missing"
        assert any(e["name"] == "replica_request"
                   and e["args"].get("state") == "finished"
                   for e in evs), "retry replica's lane missing"

        # /metrics serves the same counters the BENCH JSON reports
        server = start_metrics_server(0)
        try:
            url = f"http://127.0.0.1:{server.server_port}/metrics"
            body = urllib.request.urlopen(url, timeout=10).read().decode()
        finally:
            server.shutdown()
        metrics = {}
        for line in body.strip().splitlines():
            if not line.startswith("#"):
                head, val = line.rsplit(" ", 1)
                metrics[head] = float(val)
        assert metrics["router_completed_total"] == detail["completed"]
        assert metrics["router_retried_total"] == detail["retried"]
        assert metrics["router_evicted_total"] == detail["evicted"]
