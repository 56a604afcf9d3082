"""Tail-latency flight recorder lane (PR 14).

Covers the diagnostic layer over the PR 10 spine: per-request latency
attribution (phase partition + the sum==e2e identity), tail-sampling
retention under bounded budgets, the EWMA+MAD anomaly detector (trip →
flight dump + profiler arming), the chaos-soak acceptance criterion (every
retried/evicted/shed/deadline-missed request keeps its full span tree; the
injected stall trips the detector and the dump carries the evidence), the
cross-process kill→retry tail capture over a real subprocess, the
``/statusz``/``/healthz`` status plane + ``ds-tpu-top``, and loadgen
``--flight-out``.
"""

import importlib.util
import json
import os
import signal
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from deepspeed_tpu.observability import attribution
from deepspeed_tpu.observability.anomaly import (AnomalyConfig,
                                                 AnomalyDetector,
                                                 install_detector)
from deepspeed_tpu.observability.flight import (FlightConfig, FlightRecorder,
                                                get_recorder)
from deepspeed_tpu.observability.metrics import (get_registry,
                                                 start_metrics_server)
from deepspeed_tpu.observability.trace import get_tracer

pytestmark = pytest.mark.observability

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


@pytest.fixture(autouse=True)
def _fresh_obs():
    """Tracer, recorder, and detector are process globals: never leak an
    enabled one (or its sinks/monitors) into the next test."""
    t = get_tracer()
    t.disable()
    t.reset()
    t._sinks.clear()
    yield t
    rec = get_recorder()
    if rec is not None:
        rec.detach()
    install_detector(None)
    reg = get_registry()
    reg._monitors = [m for m in reg._monitors
                     if not isinstance(m, AnomalyDetector)]
    t.disable()
    t.reset()
    t._sinks.clear()


def _small_engine(vocab=96, seq=64):
    import jax.numpy as jnp

    from deepspeed_tpu.inference.config import DeepSpeedInferenceConfig
    from deepspeed_tpu.inference.engine import InferenceEngine
    from deepspeed_tpu.models.causal_lm import gpt2_cfg
    return InferenceEngine(
        gpt2_cfg(vocab_size=vocab, max_seq_len=seq, n_embd=32, n_layer=2,
                 n_head=4, dtype=jnp.float32),
        DeepSpeedInferenceConfig(dtype="float32", max_out_tokens=seq))


def _span(name, trace_id, span_id, parent_id, ts_ms, dur_ms, attrs=None,
          cat="serving"):
    return {"name": name, "cat": cat, "trace_id": trace_id,
            "span_id": span_id, "parent_id": parent_id, "ts": ts_ms * 1e3,
            "dur": dur_ms * 1e3, "pid": "test", "tid": "test",
            "attrs": attrs or {}}


def _request_trace(tid="t1", e2e_ms=100.0, state="finished", retried=0,
                   attempts=1, request_id=0):
    """A minimal healthy request tree: root + queue_wait + prefill + chunk."""
    return [
        _span("queue_wait", tid, "s2", "s1", 0, 10),
        _span("serving.prefill", tid, "s3", "s1", 10, 20),
        _span("decode_chunk", tid, "s4", "s1", 30, e2e_ms - 30),
        _span("request", tid, "s1", None, 0, e2e_ms,
              attrs={"request_id": request_id, "state": state,
                     "retried": retried, "attempts": attempts, "tokens": 8}),
    ]


# --------------------------------------------------------------- attribution
class TestAttribution:
    def test_phase_partition_synthetic(self):
        tid = "trace1"
        spans = [
            _span("request", tid, "root", None, 0, 100,
                  attrs={"request_id": 7, "state": "finished", "tokens": 9}),
            _span("replica_request", tid, "rr", "att", 10, 88,
                  attrs={"state": "finished"}),
            _span("attempt", tid, "att", "root", 10, 88),
            _span("queue_wait", tid, "q", "rr", 10, 8),
            _span("serving.prefix_lookup", tid, "lk", "rr", 18, 2),
            _span("serving.prefill", tid, "pf", "rr", 20, 20),
            _span("serving.restore_prefix", tid, "rs", "pf", 20, 6),
            _span("decode_chunk", tid, "c1", "rr", 40, 20),
            _span("decode_chunk", tid, "c2", "rr", 70, 20),
        ]
        row = attribution.attribute(spans)
        ph = row["phases"]
        # uncovered [0,10) before the first replica-side work = router queue
        assert ph["queue"] == pytest.approx(10 + 8)
        assert ph["admission"] == pytest.approx(2)
        assert ph["kv_restore"] == pytest.approx(6)
        assert ph["prefill"] == pytest.approx(14)       # 20 minus the restore
        assert ph["decode"] == pytest.approx(40)
        assert ph["retry_lost"] == pytest.approx(0)
        # [60,70) inter-chunk + [90,100) tail
        assert ph["gap"] == pytest.approx(20)
        assert sum(ph.values()) == pytest.approx(row["e2e_ms"])
        assert row["request_id"] == 7 and row["state"] == "finished"

    def test_abandoned_lane_is_retry_lost(self):
        tid = "trace2"
        spans = [
            _span("request", tid, "root", None, 0, 100,
                  attrs={"request_id": 1, "state": "finished", "retried": 1,
                         "attempts": 2}),
            # first attempt: evicted — its whole subtree is thrown-away work
            _span("attempt", tid, "a1", "root", 0, 40,
                  attrs={"outcome": "evicted"}),
            _span("replica_request", tid, "rr1", "a1", 0, 40,
                  attrs={"state": "abandoned"}),
            _span("decode_chunk", tid, "c1", "rr1", 10, 20),
            # retry attempt: clean lane
            _span("attempt", tid, "a2", "root", 45, 55,
                  attrs={"retry": True, "retry_of": "a1"}),
            _span("replica_request", tid, "rr2", "a2", 45, 55,
                  attrs={"state": "finished"}),
            _span("serving.prefill", tid, "pf", "rr2", 45, 15),
            _span("decode_chunk", tid, "c2", "rr2", 60, 40),
        ]
        row = attribution.attribute(spans)
        ph = row["phases"]
        assert ph["retry_lost"] == pytest.approx(40)
        assert ph["prefill"] == pytest.approx(15)
        assert ph["decode"] == pytest.approx(40)
        # [40,45): between the eviction and the retry's replica-side work —
        # the request is back in the router queue, so it reads as queue wait
        assert ph["queue"] == pytest.approx(5)
        assert ph["gap"] == pytest.approx(0)
        assert sum(ph.values()) == pytest.approx(row["e2e_ms"])

    def test_identity_on_real_run(self):
        """Acceptance: phase decomposition sums to e2e within 1% for every
        request of a real scheduler run, and decode time is attributed."""
        from deepspeed_tpu.inference.serving import (
            ContinuousBatchingScheduler, ServingConfig)
        tracer = get_tracer().enable(pid_label="attr-test")
        rec = FlightRecorder(FlightConfig(sample_every=1)).attach(tracer)
        sched = ContinuousBatchingScheduler(
            _small_engine(), ServingConfig(slots=2, chunk_size=2,
                                           max_seq_len=64))
        handles = [sched.submit([3 + i, 5, 9], max_new_tokens=6)
                   for i in range(5)]
        sched.run()
        assert all(h.state.value == "finished" for h in handles)
        rows = list(rec.rows)
        assert len(rows) == len(handles)
        for row in rows:
            total = sum(row["phases"].values())
            assert abs(total - row["e2e_ms"]) <= 0.01 * row["e2e_ms"] + 1e-6
            assert row["phases"]["decode"] > 0

    def test_breakdown_shares(self):
        rows = [attribution.attribute(_request_trace(f"t{i}", e2e_ms=100.0,
                                                     request_id=i))
                for i in range(10)]
        rows.append(attribution.attribute(
            _request_trace("slowT", e2e_ms=1000.0, request_id=99)))
        bd = attribution.phase_breakdown(rows)
        assert bd["requests"] == 11
        assert bd["e2e_ms_p99"] > bd["e2e_ms_p50"]
        for group in ("p50_shares", "p99_shares"):
            assert set(bd[group]) == set(attribution.PHASES)
            assert sum(bd[group].values()) == pytest.approx(1.0, abs=1e-6)


# ------------------------------------------------------------ tail retention
class TestRetention:
    def _feed(self, rec, spans):
        for s in spans:
            rec.on_span(s)

    def test_tail_classes_retained(self):
        rec = FlightRecorder(FlightConfig(sample_every=0))
        self._feed(rec, _request_trace("a", state="expired", request_id=1))
        self._feed(rec, _request_trace("b", state="failed", request_id=2))
        self._feed(rec, _request_trace("c", state="shed", request_id=3))
        self._feed(rec, _request_trace("d", retried=1, request_id=4))
        self._feed(rec, _request_trace("e", request_id=5))   # healthy: row only
        reasons = {r["attribution"]["request_id"]: r["reason"]
                   for r in rec.retained}
        assert reasons == {1: "expired", 2: "failed", 3: "shed", 4: "retried"}
        assert len(rec.rows) == 5

    def test_abandoned_lane_marks_evicted(self):
        rec = FlightRecorder(FlightConfig(sample_every=0))
        tid = "k1"
        spans = [
            _span("replica_request", tid, "rr1", "a1", 0, 40,
                  attrs={"state": "abandoned"}),
            _span("request", tid, "root", None, 0, 100,
                  attrs={"request_id": 1, "state": "finished"}),
        ]
        self._feed(rec, spans)
        assert [r["reason"] for r in rec.retained] == ["evicted"]

    def test_slow_retention_is_adaptive(self):
        cfg = FlightConfig(sample_every=0, warmup_requests=10,
                           slow_p95_mult=3.0)
        rec = FlightRecorder(cfg)
        for i in range(30):
            self._feed(rec, _request_trace(f"f{i}", e2e_ms=10.0,
                                           request_id=i))
        assert not rec.retained                  # uniform family: nothing slow
        self._feed(rec, _request_trace("slow", e2e_ms=500.0, request_id=900))
        assert [r["reason"] for r in rec.retained] == ["slow"]
        # adaptive: a uniformly slower family does NOT retain (bar follows)
        rec2 = FlightRecorder(cfg)
        for i in range(30):
            self._feed(rec2, _request_trace(f"g{i}", e2e_ms=500.0,
                                            request_id=i))
        assert not rec2.retained

    def test_shed_storm_does_not_collapse_slow_bar(self):
        """Instant (e2e≈0) shed roots must not enter the e2e family: a shed
        storm would otherwise drag the windowed p95 to ~0 and mass-retain
        every healthy request as 'slow'."""
        cfg = FlightConfig(sample_every=0, warmup_requests=10)
        rec = FlightRecorder(cfg)
        for i in range(30):
            self._feed(rec, _request_trace(f"h{i}", e2e_ms=100.0,
                                           request_id=i))
        bar_before = rec.stats()["slow_bar_ms"]
        for i in range(200):            # the storm: 0-duration shed roots
            self._feed(rec, [_span("request", f"sh{i}", "r", None, 0, 0,
                                   attrs={"request_id": 1000 + i,
                                          "state": "shed"})])
        assert rec.stats()["slow_bar_ms"] == pytest.approx(bar_before)
        self._feed(rec, _request_trace("ok", e2e_ms=110.0, request_id=2000))
        reasons = [r["reason"] for r in rec.retained]
        assert "slow" not in reasons    # healthy traffic still healthy
        # the storm retains as shed, bounded by the trace budget (drop-oldest)
        assert reasons.count("shed") == len(reasons) \
            == rec.config.max_retained_traces
        assert rec.retained_evicted == 200 - rec.config.max_retained_traces

    def test_uniform_sample(self):
        rec = FlightRecorder(FlightConfig(sample_every=10))
        for i in range(20):
            self._feed(rec, _request_trace(f"s{i}", request_id=i))
        assert [r["reason"] for r in rec.retained] == ["sample", "sample"]

    def test_retention_budget_bounded(self):
        cfg = FlightConfig(sample_every=0, max_retained_traces=5,
                           max_retained_spans=1000)
        rec = FlightRecorder(cfg)
        for i in range(20):
            self._feed(rec, _request_trace(f"x{i}", state="failed",
                                           request_id=i))
        assert len(rec.retained) == 5
        assert rec.retained_spans <= cfg.max_retained_spans
        assert rec.retained_evicted == 15
        # drop-oldest: the survivors are the newest
        kept = sorted(r["attribution"]["request_id"] for r in rec.retained)
        assert kept == list(range(15, 20))

    def test_open_trace_bound(self):
        rec = FlightRecorder(FlightConfig(max_open_traces=4))
        for i in range(10):       # child spans whose roots never arrive
            rec.on_span(_span("decode_chunk", f"open{i}", f"c{i}", "rr", 0, 1))
        assert len(rec._open) == 4
        assert rec.open_dropped == 6


# ------------------------------------------------------------------- anomaly
class TestAnomalyDetector:
    def test_trip_on_outlier_and_cooldown(self):
        det = AnomalyDetector(AnomalyConfig(min_obs=8, threshold=8.0,
                                            cooldown_s=3600.0,
                                            watch=("serving/tpot_ms",)))
        rng = np.random.default_rng(0)
        now = 1000.0
        for v in rng.normal(5.0, 0.3, 40):
            assert det.observe("serving/tpot_ms", float(v), now=now) is None
        trip = det.observe("serving/tpot_ms", 250.0, now=now)
        assert trip is not None
        assert trip["signal"] == "serving/tpot_ms"
        assert trip["value"] == 250.0
        assert trip["threshold"] == 8.0
        assert trip["score"] > 8.0
        # rate-limited: a second outlier inside the cooldown is suppressed
        assert det.observe("serving/tpot_ms", 260.0, now=now + 1) is None
        assert det.trips == 1 and det.suppressed == 1

    def test_counter_stream_scored_on_delta(self):
        det = AnomalyDetector(AnomalyConfig(min_obs=8, threshold=8.0,
                                            watch=("router/retried_total",)))
        now = 0.0
        for i in range(20):                       # flat cumulative: delta 0
            det.observe("router/retried_total", 0.0, now=now)
        trip = det.observe("router/retried_total", 6.0, now=now)   # retry burst
        assert trip is not None and trip["value"] == 6.0
        # the huge cumulative total itself must never be the scored quantity
        assert det._state["router/retried_total"].ewma < 1.0

    def test_trip_dumps_and_arms_profiler(self, tmp_path):
        from deepspeed_tpu.observability.profiler import (configure_capture,
                                                          get_capture)
        rec = FlightRecorder(FlightConfig(sample_every=1),
                             dump_path=str(tmp_path / "f.json"))
        for s in _request_trace("warm", request_id=0):
            rec.on_span(s)
        configure_capture(str(tmp_path / "prof"), num_ticks=4, sigusr2=False)
        try:
            det = AnomalyDetector(
                AnomalyConfig(min_obs=4, threshold=8.0,
                              watch=("serving/tpot_ms",)),
                recorder=rec)
            for _ in range(10):
                det.observe("serving/tpot_ms", 5.0, now=0.0)
            trip = det.observe("serving/tpot_ms", 500.0, now=0.0)
            assert trip is not None
            assert get_capture().armed       # XLA capture armed for next ticks
            autos = list(tmp_path.glob("f.auto*.json"))
            assert len(autos) == 1
            doc = json.load(open(autos[0]))
            assert doc["otherData"]["reason"] == "anomaly:serving/tpot_ms"
            anomalies = doc["otherData"]["anomalies"]
            assert anomalies and anomalies[-1]["signal"] == "serving/tpot_ms"
            journal = doc["otherData"]["journal"]
            assert any(e["kind"] == "anomaly" for e in journal)
        finally:
            configure_capture(None)

    def test_registry_monitor_path(self):
        """Attached as a registry monitor, the detector sees emissions without
        touching the emitters."""
        det = AnomalyDetector(AnomalyConfig(min_obs=4, threshold=8.0,
                                            watch=("serving/tpot_ms",)))
        reg = get_registry()
        reg.attach_monitor(det)
        try:
            for _ in range(10):
                reg.record("serving/tpot_ms", 5.0)
            reg.record("serving/tpot_ms", 500.0)
            assert det.trips == 1
        finally:
            reg.detach_monitor(det)


# ------------------------------------------------------------------- SIGUSR1
class TestSigusr1:
    def test_sigusr1_requests_dump(self, tmp_path):
        tracer = get_tracer().enable(pid_label="usr1")
        rec = FlightRecorder(FlightConfig(sample_every=1),
                             dump_path=str(tmp_path / "fl.json"))
        rec.attach(tracer)
        prev = rec.install_sigusr1()
        try:
            os.kill(os.getpid(), signal.SIGUSR1)
            assert rec._dump_requested
            # the next committed span performs the dump (the serve loop
            # commits spans constantly)
            root = tracer.begin("request", attrs={"request_id": 0})
            tracer.end_span(root)
            autos = list(tmp_path.glob("fl.auto*.json"))
            assert len(autos) == 1
            assert json.load(open(autos[0]))["otherData"]["reason"] \
                == "sigusr1"
        finally:
            signal.signal(signal.SIGUSR1, prev)


# ---------------------------------------------------- chaos soak acceptance
class TestChaosSoakFlight:
    def test_soak_retains_all_tail_classes_and_stall_trips(self, tmp_path):
        """The PR 14 acceptance lane: a bursty kill+stall+surge soak where
        (1) EVERY retried/evicted/shed/deadline-missed request keeps its full
        span tree inside the bounded budget, (2) the injected stall trips the
        anomaly detector, and (3) the dump carries the stalled decode_chunk
        span, the triggering signal name/value/threshold, and the coincident
        control-plane decisions (health transitions in the journal)."""
        from deepspeed_tpu.inference.serving import (Router, RouterConfig,
                                                     ServingConfig)
        from deepspeed_tpu.inference.serving.chaos import (ChaosEvent,
                                                           ChaosSchedule)
        tracer = get_tracer().enable(pid_label="soak")
        engines = [_small_engine(), _small_engine()]
        engines[1].params = engines[0].params
        cfg = RouterConfig(serving=ServingConfig(slots=2, chunk_size=2,
                                                 max_seq_len=64),
                           suspect_after_s=0.05, dead_after_s=0.15,
                           recover_after_s=30.0, max_attempts=4)
        router = Router(engines, cfg)
        rng = np.random.default_rng(0)

        def prompt(n):
            return rng.integers(1, 90, size=n).astype(np.int32)

        # phase A — warm both replicas: every prefill-bucket/chunk compile is
        # paid BEFORE the detector attaches, so its EWMA/MAD learn the
        # steady-state family, not compile transients
        warm = [router.submit(prompt(int(rng.integers(3, 9))),
                              max_new_tokens=8) for _ in range(10)]
        while router.busy:
            router.step()
        assert all(h.state.value == "finished" for h in warm)
        # phase B — attach the recorder + detector and feed them steady
        # completions: the recorder's adaptive slow bar and the detector's
        # EWMA/MAD both learn the compile-free steady family, so the stall's
        # victims read as slow/anomalous against the real baseline
        rec = FlightRecorder(
            FlightConfig(sample_every=0, warmup_requests=8,
                         max_retained_traces=32, max_retained_spans=5000),
            dump_path=str(tmp_path / "soak.json")).attach(tracer)
        det = AnomalyDetector(
            AnomalyConfig(min_obs=6, threshold=8.0, cooldown_s=0.2,
                          watch=("serving/tpot_ms", "router/tpot_ms")),
            recorder=rec)
        install_detector(det)
        get_registry().attach_monitor(det)
        steady = [router.submit(prompt(int(rng.integers(3, 9))),
                                max_new_tokens=8) for _ in range(8)]
        while router.busy:
            router.step()
        assert all(h.state.value == "finished" for h in steady)
        assert det.trips == 0, "steady traffic must not trip the detector"

        chaos = ChaosSchedule([
            ChaosEvent(kind="kill", replica=1, when="busy"),
            ChaosEvent(kind="stall", replica=0, when="busy", duration=0.5),
            ChaosEvent(kind="surge", at=0.0, duration=1.0, mult=2.0),
        ])
        soak = [router.submit(prompt(int(rng.integers(3, 9))),
                              max_new_tokens=10, seed=i) for i in range(6)]
        burst = [(prompt(int(rng.integers(3, 9))), 10 + i) for i in range(4)]
        # one deadline the queue cannot meet: a post-admission deadline miss
        # (slo_admission is OFF here so the request is ADMITTED and expires)
        soak.append(router.submit(prompt(4), max_new_tokens=8,
                                  deadline_s=0.003))
        # one infeasible-SLO shed at the front door: flip SLO admission on
        # for exactly this submission (the estimator is warm from phase A/B)
        from deepspeed_tpu.inference.serving.router import AdmissionShedError
        router.config.slo_admission = True
        with pytest.raises(AdmissionShedError):
            router.submit(prompt(4), max_new_tokens=8, deadline_s=1e-4)
        router.config.slo_admission = False
        while router.busy or burst:
            chaos.poll(router)
            if burst and chaos.load_multiplier() > 1.0:
                p, seed = burst.pop(0)           # the surge window bursts
                soak.append(router.submit(p, max_new_tokens=10, seed=seed))
            elif burst and chaos.events[2].fired \
                    and chaos.load_multiplier() == 1.0:
                burst.pop(0)                     # surge window closed: drain
            router.step()
        assert chaos.exhausted, "kill/stall/surge must all have fired"

        done = [h for h in soak if h.state.value == "finished"]
        retried = [h for h in soak if h.retried > 0 or h.evictions > 0]
        expired = [h for h in soak if h.state.value == "expired"]
        assert retried, "kill produced no retried request — vacuous soak"
        assert expired, "deadline request did not expire — vacuous soak"
        assert len(done) + len(expired) == len(soak)

        # (1) 100% tail retention inside the bounded budget
        retained_ids = {r["attribution"]["request_id"]
                        for r in rec.retained}
        for h in retried + expired:
            assert h.id in retained_ids, \
                f"tail request {h.id} ({h.state.value}) lost its span tree"
        reasons = {r["reason"] for r in rec.retained}
        assert "shed" in reasons, "the shed decision left no retained trace"
        assert rec.retained_spans <= rec.config.max_retained_spans
        assert len(rec.retained) <= rec.config.max_retained_traces

        # (2) the stall tripped the detector on a latency stream
        assert det.trips >= 1
        assert any(t["signal"] in ("serving/tpot_ms", "router/tpot_ms")
                   for t in det.recent)

        # (3) the dump carries the evidence
        path = rec.dump(reason="soak_end")
        doc = json.load(open(path))
        xs = [e for e in doc["traceEvents"] if e["ph"] == "X"]
        stalled = [e for e in xs if e["name"] == "decode_chunk"
                   and e["dur"] >= 0.35e6]
        assert stalled, "stalled decode_chunk span missing from the bundle"
        trips = doc["otherData"]["anomalies"]
        assert trips and all(k in trips[-1] for k in
                             ("signal", "value", "threshold", "score"))
        journal = doc["otherData"]["journal"]
        kinds = {e["kind"] for e in journal}
        assert "replica_health" in kinds, "kill left no health transitions"
        assert "shed" in kinds, "shed decision missing from the journal"
        # abandoned lane in the bundle, joined to a retry attempt
        assert any(e["name"] == "replica_request"
                   and e["args"].get("state") == "abandoned" for e in xs)
        assert any(e["name"] == "attempt" and e["args"].get("retry")
                   for e in xs)


# --------------------------------------------- cross-process tail capture
class TestCrossProcessTailCapture:
    def test_subprocess_kill_retry_lane_in_dump(self, tmp_path):
        """Real-SIGKILL tail capture: the killed child's abandoned lane
        (state=abandoned) and the retry attempt join by trace id inside the
        flight dump."""
        from deepspeed_tpu.inference.serving.subproc import SubprocessReplica
        from deepspeed_tpu.utils.fault_injection import FaultSpec, fault_env
        tracer = get_tracer().enable(pid_label="parent")
        rec = FlightRecorder(FlightConfig(sample_every=0),
                             dump_path=str(tmp_path / "xp.json"))
        rec.attach(tracer)
        dims = dict(vocab_size=96, max_seq_len=64, n_embd=32, n_layer=2,
                    n_head=4, slots=2, chunk_size=2)
        prompt = [4, 5, 6]
        budget = 20
        # pace the child's chunks (same trick as the prefix-cache SIGKILL
        # lane): an unpaced child streams every token between two parent
        # polls and the mid-decode kill has nothing to land on
        env = fault_env([("serving.decode_chunk",
                          FaultSpec(kind="delay", delay_s=0.05))], seed=3)
        rep_a = SubprocessReplica(REPO, env=env, **dims)
        rep_b = None
        try:
            rep_a.wait_ready()
            root = tracer.begin("request", attrs={"request_id": 0})
            att1 = tracer.start_span("attempt", root,
                                     attrs={"replica": 0, "attempt": 1})
            rep_a.submit(0, prompt, max_new_tokens=budget,
                         trace_id=att1.trace_id, parent_span=att1.span_id)
            streamed = rep_a.wait_tokens(0, 2)
            assert len(streamed) >= 2 and not rep_a.done(0), \
                "child finished before the kill — pacing fault did not hold"
            rep_a.sigkill()                      # real SIGKILL mid-decode
            tracer.ingest(rep_a.take_spans(), pid_label="subproc-a")
            closed = rep_a.abandon_open_lanes(tracer)
            assert closed == [0]
            # idempotent + bounded: the context is consumed, a second call
            # must not re-emit abandoned spans
            assert rep_a._trace_ctx == {}
            assert rep_a.abandon_open_lanes(tracer) == []
            tracer.end_span(att1, attrs={"outcome": "evicted",
                                         "evicted_from_replica": 0})
            # checkpointless retry on a fresh subprocess replica: re-prefill
            # prompt + streamed prefix under a linked attempt span
            streamed = rep_a.tokens(0)
            att2 = tracer.start_span("attempt", root,
                                     attrs={"replica": 1, "attempt": 2,
                                            "retry": True,
                                            "retry_of": att1.span_id})
            rep_b = SubprocessReplica(REPO, **dims)
            rep_b.wait_ready()
            rep_b.submit(0, list(prompt) + streamed,
                         max_new_tokens=budget - len(streamed),
                         trace_id=att2.trace_id, parent_span=att2.span_id)
            rep_b.wait_tokens(0, budget - len(streamed))
            assert rep_b.done(0)
            rep_b.stop()
            tracer.ingest(rep_b.take_spans(), pid_label="subproc-b")
            tracer.end_span(att2, attrs={"outcome": "finished"})
            tracer.end_span(root, attrs={"state": "finished", "retried": 1,
                                         "attempts": 2,
                                         "tokens": budget})
            # the root commit finalized the trace: retained as a tail class
            assert [r["reason"] for r in rec.retained] == ["retried"]
            path = rec.dump(reason="test")
            doc = json.load(open(path))
            xs = [e for e in doc["traceEvents"] if e["ph"] == "X"]
            assert len({e["args"]["trace_id"] for e in xs}) == 1
            abandoned = [e for e in xs if e["name"] == "replica_request"
                         and e["args"].get("state") == "abandoned"]
            assert abandoned, "killed lane missing from the dump"
            retry = [e for e in xs if e["name"] == "attempt"
                     and e["args"].get("retry")]
            assert retry and retry[0]["args"]["retry_of"] == att1.span_id
            # both process lanes made it into the bundle
            assert any(e["name"] == "decode_chunk" for e in xs)
            row = rec.retained[0]["attribution"]
            assert row["phases"]["retry_lost"] > 0
        finally:
            for rep in (rep_a, rep_b):
                if rep is not None and rep.alive:
                    rep.sigkill()


# ------------------------------------------------------------- status plane
class TestStatusPlane:
    def _router(self):
        from deepspeed_tpu.inference.serving import (Router, RouterConfig,
                                                     ServingConfig)
        return Router([_small_engine()],
                      RouterConfig(serving=ServingConfig(
                          slots=2, chunk_size=2, max_seq_len=64)))

    def test_statusz_and_healthz(self):
        from deepspeed_tpu.inference.serving.server import (
            make_health_provider, make_status_provider)
        router = self._router()
        h = router.submit([1, 2, 3], max_new_tokens=4)
        router.step()
        server = start_metrics_server(
            0, status_provider=make_status_provider(router),
            health_provider=make_health_provider(router))
        try:
            base = f"http://127.0.0.1:{server.server_port}"
            doc = json.loads(urllib.request.urlopen(
                base + "/statusz", timeout=10).read().decode())
            assert doc["kind"] == "router"
            assert doc["replicas"][0]["health"] == "live"
            assert "degradation_rung" in doc and "counters" in doc
            resp = urllib.request.urlopen(base + "/healthz", timeout=10)
            ready = json.loads(resp.read().decode())
            assert resp.status == 200 and ready["ready"] is True
            assert ready["live_replicas"] == 1
            # drain closes admission: /healthz flips to 503 not-ready
            router.begin_drain()
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(base + "/healthz", timeout=10)
            assert ei.value.code == 503
            body = json.loads(ei.value.read().decode())
            assert body["ready"] is False and body["live"] is True
            # /metrics stays served beside the status plane
            text = urllib.request.urlopen(
                base + "/metrics", timeout=10).read().decode()
            assert "router_queue_depth" in text
        finally:
            h.cancel()
            server.shutdown()

    def test_healthz_without_provider_is_liveness(self):
        server = start_metrics_server(0)
        try:
            resp = urllib.request.urlopen(
                f"http://127.0.0.1:{server.server_port}/healthz", timeout=10)
            assert resp.status == 200
            assert json.loads(resp.read().decode())["live"] is True
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(
                    f"http://127.0.0.1:{server.server_port}/statusz",
                    timeout=10)
            assert ei.value.code == 404
        finally:
            server.shutdown()

    def test_ds_tpu_top_once(self, capsys):
        from deepspeed_tpu.inference.serving.server import (
            make_health_provider, make_status_provider)
        from deepspeed_tpu.observability import top
        router = self._router()
        server = start_metrics_server(
            0, status_provider=make_status_provider(router),
            health_provider=make_health_provider(router))
        try:
            rc = top.main(["--once", "--port", str(server.server_port)])
            assert rc == 0
            out = capsys.readouterr().out
            assert "replicas:" in out and "live" in out
            assert "rung=HEALTHY" in out
        finally:
            server.shutdown()

    def test_ds_tpu_top_unreachable(self, capsys):
        from deepspeed_tpu.observability import top
        rc = top.main(["--once", "--port", "1"])   # nothing listens there
        assert rc == 1
        assert "unreachable" in capsys.readouterr().out


# ---------------------------------------------------------------- loadgen
class TestLoadgenFlight:
    def _loadgen(self):
        spec = importlib.util.spec_from_file_location(
            "serving_loadgen_flight", os.path.join(REPO, "benchmarks",
                                                   "serving", "loadgen.py"))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    def test_flight_out_bundle_attribution_and_jsonl(self, tmp_path, capsys):
        """One smoke run covers the --flight-out surface: the bundle, the
        JSON's attribution detail, AND the --jsonl-metrics mirror (per-request
        latency/e2e_ms + latency/phase/* rows, no telemetry double-write)."""
        loadgen = self._loadgen()
        path = str(tmp_path / "bundle.json")
        rc = loadgen.main(["--smoke", "--flight-out", path,
                           "--jsonl-metrics", str(tmp_path)])
        assert rc == 0
        out = capsys.readouterr().out.strip().splitlines()[-1]
        bench = json.loads(out)
        # the detail carries the schema-checked p50-vs-p99 breakdown
        bd = bench["detail"]["attribution"]
        assert bd["requests"] > 0
        for group in ("p50_shares", "p99_shares"):
            assert set(bd[group]) == set(attribution.PHASES)
            assert sum(bd[group].values()) == pytest.approx(1.0, abs=1e-6)
        assert bench["flight"]["path"] == path
        doc = json.load(open(path))
        assert doc["otherData"]["kind"] == "flight_bundle"
        assert doc["otherData"]["reason"] == "end_of_run"
        assert get_tracer().enabled is False
        # jsonl mirror: attribution rows landed, telemetry tags only once
        tags = {}
        for line in open(tmp_path / "loadgen.jsonl"):
            t = json.loads(line)["tag"]
            tags[t] = tags.get(t, 0) + 1
        assert tags.get("latency/e2e_ms", 0) > 0
        assert tags.get("latency/phase/decode_ms", 0) > 0
        assert tags.get("serving/ttft_ms", 0) == tags["latency/e2e_ms"]
