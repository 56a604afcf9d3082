"""The one span API (PR 25): a scoped ``tracer.span`` lands in a profiler
trace with its attributes as event stats and, while the tracer is enabled, in
the ring under the same name; the serve step, the train step and the set-up
name their phases; nothing about it waits for the device.

The serving tests read a real ``jax.profiler`` trace of a tiny configuration
on the CPU backend (host plane only: counts and nesting, never a time).
"""

import glob
import os
import threading

import numpy as np
import pytest

from deepspeed_tpu.observability import schema
from deepspeed_tpu.observability.trace import Tracer, get_tracer

pytestmark = pytest.mark.observability

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))
CHUNK = 4


@pytest.fixture(autouse=True)
def _fresh_tracer():
    t = get_tracer()
    t.disable()
    t.reset()
    yield t
    t.disable()
    t.reset()


# ------------------------------------------------------------------ the API
class TestSpanApi:
    def test_scoped_spans_nest_under_the_threads_open_span(self):
        t = Tracer().enable()
        with t.span("outer", step=1) as outer:
            with t.span("inner", k=2) as inner:
                assert t.current() is inner
            assert t.current() is outer
        assert t.current() is None
        by = {s["name"]: s for s in t.spans}
        assert by["inner"]["parent_id"] == by["outer"]["span_id"]
        assert by["inner"]["trace_id"] == by["outer"]["trace_id"]
        assert by["outer"]["attrs"] == {"step": 1} and by["inner"]["attrs"] == {"k": 2}

    def test_parent_places_a_span_on_a_requests_trace(self):
        t = Tracer().enable()
        root = t.begin("replica_request", attrs={"request_id": 7})
        with t.span("serving.step"):
            with t.span("serving.admit", parent=root, request_id=7):
                with t.span("serving.prefill", request_id=7):
                    pass
            with t.span("serving.decode_chunk"):
                pass
        t.end_span(root)
        by = {s["name"]: s for s in t.spans}
        assert by["serving.admit"]["parent_id"] == by["replica_request"]["span_id"]
        assert by["serving.prefill"]["trace_id"] == by["replica_request"]["trace_id"]
        assert by["serving.decode_chunk"]["trace_id"] == by["serving.step"]["trace_id"]
        assert by["serving.step"]["trace_id"] != by["replica_request"]["trace_id"]

    def test_set_adds_attributes_known_at_the_end_and_stamps_are_monotonic(self):
        t = Tracer().enable()
        with t.span("serving.decode_chunk", chunk=3) as sp:
            sp.set(tokens_kept=5, deliveries=2)
        assert sp.t0 <= sp.t1
        (rec,) = t.spans
        assert rec["attrs"] == {"chunk": 3, "tokens_kept": 5, "deliveries": 2}
        assert rec["dur"] == pytest.approx((sp.t1 - sp.t0) * 1e6, abs=1.0)

    def test_an_exception_is_recorded_and_propagates(self):
        t = Tracer().enable()
        with pytest.raises(KeyError):
            with t.span("x"):
                raise KeyError("boom")
        assert t.spans[0]["attrs"]["error"] == "KeyError"
        assert t.current() is None

    def test_a_span_on_another_thread_takes_the_callers_span_as_parent(self):
        t = Tracer().enable()
        with t.span("serving.decode_chunk") as chunk:
            parent = t.current()

            def worker():
                with t.span("serving.fetch", parent=parent):
                    pass
            th = threading.Thread(target=worker)
            th.start()
            th.join(10)
            assert not th.is_alive()
        by = {s["name"]: s for s in t.spans}
        assert by["serving.fetch"]["parent_id"] == by["serving.decode_chunk"]["span_id"]
        assert chunk.span_id == by["serving.decode_chunk"]["span_id"]

    @pytest.mark.parametrize("enabled", [False, True])
    def test_phases_are_kept_whether_or_not_the_tracer_is_enabled(self, enabled):
        t = Tracer()
        if enabled:
            t.enable()
        with t.phase("setup.engine_init"):
            with t.phase("setup.init_params"):
                with t.span("not.a.phase"):
                    pass
        with t.phase("setup.program", program="prefill", bucket=8):
            pass
        got = [(p["name"], p["parent"]) for p in t.phases]
        assert got == [("setup.init_params", "setup.engine_init"),
                       ("setup.engine_init", None), ("setup.program", None)]
        assert t.phases[2]["attrs"] == {"program": "prefill", "bucket": 8}
        assert all(p["t1"] >= p["t0"] for p in t.phases)
        assert (len(t.spans) == 4) is enabled and (len(t.spans) == 0) is not enabled


# -------------------------------------------------------- the declared names
class TestSpanSchema:
    def test_every_span_site_is_declared_and_every_declared_span_has_a_site(self):
        from deepspeed_tpu.analysis.ast_rules import iter_span_names_from_tree
        import ast
        assert schema.lint_emission_sites(REPO) == []
        seen = set()
        for rel in schema.SPAN_MODULES:
            with open(os.path.join(REPO, rel)) as f:
                seen |= {n for n, _ in iter_span_names_from_tree(ast.parse(f.read()))}
        assert seen == set(schema.SPANS), (seen ^ set(schema.SPANS))

    def test_an_undeclared_span_name_is_a_finding(self, tmp_path):
        from deepspeed_tpu.analysis.ast_rules import EmissionTagRule, run_ast_rules
        (tmp_path / "site.py").write_text(
            "def f(tracer):\n"
            "    with tracer.span('serving.admit'):\n"
            "        with tracer.span('serving.typo'):\n"
            "            pass\n")
        rule = EmissionTagRule(schema.resolve, (), resolve_span=schema.resolve_span,
                               span_modules=("site.py",))
        res = run_ast_rules(str(tmp_path), [rule], paths=("site.py",))
        assert [f.details["tag"] for f in res.findings] == ["serving.typo"]

    @pytest.mark.parametrize("name", sorted(schema.SPANS))
    def test_every_span_names_a_layer_of_the_benchmark_and_what_reads_it(self, name):
        import json
        sinks, layer, attrs, reads = schema.SPANS[name]
        bench = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
        assert sinks in (schema.BOTH, schema.RING, schema.PHASE, schema.PAUSE_GC,
                         schema.PAUSE_STALL)
        assert layer in {m["layer"] for m in bench["per_layer"]}
        assert reads and isinstance(attrs, tuple)
        assert (sinks == schema.PHASE) == name.startswith("setup.")
        # the second kept list: host pauses (PR 55)
        assert (sinks in (schema.PAUSE_GC, schema.PAUSE_STALL)) == name.startswith("host.")
        perf = open(os.path.join(REPO, "PERF.md")).read()
        assert f"`{name}`" in perf, f"PERF.md section 3 has no row for {name}"

    def test_no_annotate_beside_a_span_and_one_host_clock(self):
        import re
        for rel in ("deepspeed_tpu/inference/serving/scheduler.py",
                    "deepspeed_tpu/inference/serving/executor.py",
                    "deepspeed_tpu/runtime/engine.py"):
            src = open(os.path.join(REPO, rel)).read()
            assert not re.search(r"\bannotate\(", src), rel
            if "serving" in rel:        # spans, elapsed and first_token_at: one clock
                assert "perf_counter" not in src, rel


# ------------------------------------------------ the serve step, in a trace
def _engine():
    import jax.numpy as jnp
    from deepspeed_tpu.inference.config import DeepSpeedInferenceConfig
    from deepspeed_tpu.inference.engine import InferenceEngine
    from deepspeed_tpu.models.causal_lm import gpt2_cfg
    return InferenceEngine(
        gpt2_cfg(vocab_size=96, max_seq_len=64, n_embd=32, n_layer=2, n_head=4,
                 dtype=jnp.float32),
        DeepSpeedInferenceConfig(dtype="float32", max_out_tokens=64))


def _scheduler(engine):
    from deepspeed_tpu.inference.serving import (ContinuousBatchingScheduler,
                                                 ServingConfig)
    from deepspeed_tpu.inference.serving.prefix_cache import PrefixCacheConfig
    return ContinuousBatchingScheduler(engine, ServingConfig(
        slots=2, chunk_size=CHUNK, max_seq_len=64,
        prefix_cache=PrefixCacheConfig(min_hit_tokens=4, min_insert_tokens=4,
                                       insert_on="prefill")))


def _events(trace_dir):
    """The program's spans in the trace's host plane, ``(name, start, end,
    stats, thread)`` by start time: the benchmark's own reading of them."""
    import sys
    if REPO not in sys.path:
        sys.path.insert(0, REPO)
    from benchmarks.chipbench import program_spans
    (path,) = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    return program_spans.load(path)


def _under(events, outer, name=None):
    return [e for e in events if e is not outer and e[4] == outer[4]
            and e[1] >= outer[1] and e[2] <= outer[2] and (name is None or e[0] == name)]


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """A two-caller script under ``jax.profiler`` with the tracer ON: request
    A (a prefix hit, 7 tokens: 1 + 4 + 2, so its last chunk is partial) is
    running when B (a miss, 10 tokens) is admitted, so B's prefill lands
    inside A's stream. The document was served once before, for the hit."""
    import jax
    tracer = get_tracer()
    tracer.disable()
    tracer.reset()
    sched = _scheduler(_engine())
    doc = list(range(1, 20))
    sched.submit(doc, max_new_tokens=6)
    sched.run()
    before = sched.telemetry.snapshot()
    tracer.enable(pid_label="spans")
    trace_dir = str(tmp_path_factory.mktemp("xplane"))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    try:
        a = sched.submit(doc + [7, 8, 9], max_new_tokens=7)
        sched.step()
        b = sched.submit([5, 6, 7, 9, 11], max_new_tokens=10)
        sched.run()
    finally:
        jax.profiler.stop_trace()
    ring = tracer.spans
    tracer.disable()
    tracer.reset()
    after = sched.telemetry.snapshot()
    return {"events": _events(trace_dir), "ring": ring, "a": a, "b": b,
            "counters": {k: after[k] - before[k] for k in
                         ("decode_slot_steps", "tokens_total", "deliveries",
                          "deliveries_stalled")}}


TABLE_B = {
    "serving.step": ("step", "queue_depth", "active_slots"),
    "serving.sweep": (),
    "serving.admit": ("request_id", "queue_wait_ms", "prompt_tokens", "prefix_len",
                      "slot", "outcome", "programs"),
    "serving.prefix_lookup": ("hit", "matched_tokens"),
    "serving.page_table": ("op", "pages_fresh", "pages_shared", "cow"),
    "serving.prefill": ("request_id", "bucket", "tokens", "prefix_len"),
    "serving.suffix_prefill": ("request_id", "bucket", "tokens", "prefix_len"),
    "serving.decode_chunk": ("chunk", "active_slots", "request_ids", "slot_steps_run",
                             "attn_rows", "tokens_kept", "deliveries",
                             "stalled_deliveries", "fetch_wait_ms"),
    "serving.place_inputs": ("program",),
    "serving.dispatch": ("program", "seq"),
    "serving.fetch": ("program",),
    "serving.scatter_prefill": (),
    "serving.prefix_insert": (),
    "serving.harvest": ("finished",),
    "serving.telemetry": (),
}


class TestServeStepInATrace:
    @pytest.mark.parametrize("name", sorted(TABLE_B))
    def test_the_span_is_in_the_xplane_with_its_attributes_as_stats(self, served, name):
        found = [e for e in served["events"] if e[0] == name]
        assert found, f"no {name} event in the trace"
        for e in found:
            assert set(TABLE_B[name]) <= set(e[3]), (name, e[3])
        assert set(TABLE_B[name]) <= set(schema.SPANS[name][2])

    @pytest.mark.parametrize("name", sorted(TABLE_B))
    def test_the_ring_holds_the_same_name_with_the_same_attributes(self, served, name):
        ring = [s for s in served["ring"] if s["name"] == name]
        xplane = [e for e in served["events"] if e[0] == name]
        assert len(ring) == len(xplane) > 0
        for s, e in zip(sorted(ring, key=lambda s: s["ts"]), xplane):
            assert set(s["attrs"]) == set(e[3])
            for k, v in e[3].items():
                assert str(s["attrs"][k]) == str(v), (name, k)

    def test_spans_nest_as_the_table_says(self, served):
        ev = served["events"]
        steps = [e for e in ev if e[0] == "serving.step"]
        for name in ("serving.sweep", "serving.admit", "serving.decode_chunk",
                     "serving.harvest", "serving.telemetry"):
            for e in (x for x in ev if x[0] == name):
                assert any(s[1] <= e[1] and e[2] <= s[2] for s in steps), name
        for admit in (e for e in ev if e[0] == "serving.admit"):
            names = {e[0] for e in _under(ev, admit)}
            assert {"serving.prefix_lookup", "serving.page_table",
                    "serving.prefix_insert"} <= names
            assert names & {"serving.prefill", "serving.suffix_prefill"}
        for outer in ("serving.prefill", "serving.suffix_prefill", "serving.decode_chunk"):
            for e in (x for x in ev if x[0] == outer):
                inner = [x[0] for x in _under(ev, e)
                         if x[0] in ("serving.place_inputs", "serving.dispatch",
                                     "serving.fetch")]
                assert inner == ["serving.place_inputs", "serving.dispatch",
                                 "serving.fetch"], (outer, inner)
        # a release is a page-table edit of the harvest, an acquire of an admission
        ops = {(e[3]["op"], o[0]) for o in ev if o[0] in ("serving.admit", "serving.harvest")
               for e in _under(ev, o, "serving.page_table")}
        assert ops == {("bind", "serving.admit"), ("acquire", "serving.admit"),
                       ("release", "serving.harvest")}

    def test_a_hit_and_a_miss_are_told_apart_by_attributes(self, served):
        by_id = {e[3]["request_id"]: e for e in served["events"] if e[0] == "serving.admit"}
        hit, miss = by_id[served["a"].id], by_id[served["b"].id]
        assert hit[3]["prefix_len"] == 19 and miss[3]["prefix_len"] == 0
        assert hit[3]["outcome"] == miss[3]["outcome"] == "ok"
        # a miss dispatches its prefill and, after the stamp, the scatter; this
        # hit's match ends inside a page, so a page is copied before its prefill
        assert (miss[3]["programs"], hit[3]["programs"]) == (2, 2)
        for admit in (hit, miss):
            assert len(_under(served["events"], admit, "serving.dispatch")) == 1
        (suffix,) = _under(served["events"], hit, "serving.suffix_prefill")
        (whole,) = _under(served["events"], miss, "serving.prefill")
        assert (suffix[3]["bucket"], suffix[3]["tokens"], suffix[3]["prefix_len"]) == (8, 3, 19)
        assert (whole[3]["bucket"], whole[3]["tokens"], whole[3]["request_id"]) \
            == (8, 5, served["b"].id)

    def test_tokens_kept_is_what_the_handles_hold_and_waste_is_the_hand_count(self, served):
        chunks = [e[3] for e in served["events"] if e[0] == "serving.decode_chunk"]
        a, b = served["a"], served["b"]
        assert (len(a.tokens), len(b.tokens)) == (7, 10)
        kept = sum(c["tokens_kept"] for c in chunks)
        assert kept == (len(a.tokens) - 1) + (len(b.tokens) - 1)
        run = sum(c["slot_steps_run"] for c in chunks)
        by_hand = sum(CHUNK * -(-(n - 1) // CHUNK) - (n - 1) for n in (7, 10))
        assert run - kept == by_hand == 5
        assert all(c["slot_steps_run"] == CHUNK * c["active_slots"] for c in chunks)
        # A ends inside its second chunk: that chunk kept 2 of its 4 steps for A
        ids = [str(c["request_ids"]).split() for c in chunks]
        assert ids == [[str(a.id)], [str(b.id), str(a.id)], [str(b.id)], [str(b.id)]]
        assert [c["tokens_kept"] for c in chunks] == [4, 6, 4, 1]

    def test_a_prefill_inside_a_stream_stalls_that_streams_next_delivery(self, served):
        chunks = [e[3] for e in served["events"] if e[0] == "serving.decode_chunk"]
        # chunk 2 delivers to A (B was prefilled since A's first delivery) and
        # to B (its own prefill does not stall it)
        assert [c["deliveries"] for c in chunks] == [1, 2, 1, 1]
        assert [c["stalled_deliveries"] for c in chunks] == [0, 1, 0, 0]

    def test_the_registry_counters_agree_with_the_span_sums(self, served):
        chunks = [e[3] for e in served["events"] if e[0] == "serving.decode_chunk"]
        c = served["counters"]
        assert c["decode_slot_steps"] == sum(x["slot_steps_run"] for x in chunks)
        assert c["tokens_total"] == sum(x["tokens_kept"] for x in chunks)
        assert c["deliveries"] == sum(x["deliveries"] for x in chunks)
        assert c["deliveries_stalled"] == sum(x["stalled_deliveries"] for x in chunks)
        from deepspeed_tpu.observability.metrics import get_registry
        snap = get_registry().snapshot()
        for tag in ("serving/decode_slot_steps_total", "serving/decode_tokens_kept_total",
                    "serving/deliveries_total", "serving/deliveries_stalled_total"):
            assert schema.kind_of(tag) == schema.COUNTER and tag in snap

    def test_request_scoped_ring_spans_carry_the_request_id(self, served):
        a = served["a"]
        root = next(s for s in served["ring"] if s["name"] == "replica_request"
                    and s["attrs"]["request_id"] == a.id)
        mine = [s for s in served["ring"] if s["trace_id"] == root["trace_id"]]
        names = {s["name"] for s in mine}
        assert {"queue_wait", "serving.admit", "serving.suffix_prefill", "decode_chunk",
                "retire"} <= names
        for s in mine:
            if s["name"] in ("serving.admit", "serving.suffix_prefill", "decode_chunk"):
                assert s["attrs"]["request_id"] == a.id
        # the ring's first-token stamp is the end of the prefill span
        pre = next(s for s in mine if s["name"] == "serving.suffix_prefill")
        tracer = get_tracer()
        assert tracer.ts_us(a.first_token_at) == pytest.approx(pre["ts"] + pre["dur"], abs=2.0)

    def test_the_first_call_of_each_program_is_a_setup_phase(self, served):
        progs = [(p["attrs"]["program"], p["attrs"]["bucket"])
                 for p in get_tracer().phases if p["name"] == "setup.program"]
        assert {("prefill", 32), ("decode_chunk", CHUNK), ("suffix_prefill", 8),
                ("prefill", 8)} <= set(progs)
        assert len(progs) == len(set(progs)), "a program's first call was kept twice"
        names = [p["name"] for p in get_tracer().phases]
        assert "setup.inference_engine_init" in names and "setup.kv_pool" in names
        parents = {p["name"]: p["parent"] for p in get_tracer().phases}
        assert parents["setup.place_params"] == "setup.inference_engine_init"


class TestWatchdogKeepsTheSpanOnTheCallersThread:
    def test_children_on_the_worker_nest_under_the_callers_chunk_span(self):
        from deepspeed_tpu.inference.serving import (ContinuousBatchingScheduler,
                                                     ServingConfig)
        tracer = get_tracer().enable()
        sched = ContinuousBatchingScheduler(_engine(), ServingConfig(
            slots=2, chunk_size=CHUNK, max_seq_len=64, chunk_deadline_s=60.0))
        h = sched.submit([3, 4, 5], max_new_tokens=6)
        sched.run()
        assert h.state.value == "finished"
        spans = tracer.spans
        chunk = next(s for s in spans if s["name"] == "serving.decode_chunk")
        assert chunk["tid"] == threading.current_thread().name
        kids = [s for s in spans if s["parent_id"] == chunk["span_id"]]
        assert [s["name"] for s in sorted(kids, key=lambda s: s["ts"])] \
            == ["serving.place_inputs", "serving.dispatch", "serving.fetch"]
        assert {s["tid"] for s in kids if s["name"] != "serving.place_inputs"} \
            == {"ds-serve-chunk-watchdog"}


class TestAttnRowsIsTheRowsAStepsAttentionWalks:
    def test_the_chunk_span_carries_the_longest_length_in_whole_blocks(self):
        """Two requests of known lengths: ``attn_rows`` of every
        ``serving.decode_chunk`` span is the longest slot's length at
        dispatch plus the chunk, rounded up to ``live_block(cap)``, at most
        the cap; a released slot's length no longer counts."""
        import jax.numpy as jnp
        from deepspeed_tpu.inference.config import DeepSpeedInferenceConfig
        from deepspeed_tpu.inference.engine import InferenceEngine
        from deepspeed_tpu.inference.serving import (ContinuousBatchingScheduler,
                                                     ServingConfig)
        from deepspeed_tpu.models.causal_lm import gpt2_cfg
        from deepspeed_tpu.ops.attention.decode import live_block
        cap = 512
        B = live_block(cap)
        assert B == 64
        engine = InferenceEngine(
            gpt2_cfg(vocab_size=96, max_seq_len=cap, n_embd=32, n_layer=1, n_head=4,
                     dtype=jnp.float32),
            DeepSpeedInferenceConfig(dtype="float32", max_out_tokens=cap))
        sched = ContinuousBatchingScheduler(engine, ServingConfig(
            slots=2, chunk_size=CHUNK, max_seq_len=cap))
        tracer = get_tracer().enable()
        rng = np.random.default_rng(0)
        long = sched.submit(rng.integers(1, 90, 250).tolist(), max_new_tokens=9)
        short = sched.submit(rng.integers(1, 90, 30).tolist(), max_new_tokens=17)
        sched.run()
        assert (len(long.tokens), len(short.tokens)) == (9, 17)
        chunks = [s["attrs"] for s in tracer.spans if s["name"] == "serving.decode_chunk"]
        # both run two chunks (250 + 4 -> 256, 254 + 4 -> 320: over a block's
        # edge), then the short one alone from 38 and 42 rows
        assert [c["active_slots"] for c in chunks] == [2, 2, 1, 1]
        assert [c["attn_rows"] for c in chunks] == [4 * B, 5 * B, B, B]
        assert all(c["attn_rows"] <= cap for c in chunks)


# ------------------------------------------------------------ the train step
def _train_engine():
    import sys
    sys.path.insert(0, os.path.join(REPO, "tests", "unit"))
    import deepspeed_tpu as ds
    from simple_model import base_config, random_batches, simple_model
    engine = ds.initialize(model=simple_model(hidden_dim=8),
                           config=base_config(batch_size=16))[0]
    return engine, random_batches(4, 16, 8)


class TestTrainStepWithoutTheObserverEffect:
    @pytest.mark.parametrize("enabled", [False, True])
    def test_train_batch_never_waits_for_the_device(self, enabled, monkeypatch):
        """Tracer on and off dispatch the same number of ``train_batch`` calls
        before the first block on a loss: all of them."""
        import jax
        engine, batches = _train_engine()
        engine.train_batch(batch=batches[0])        # compile outside the count
        blocked = []
        real = jax.block_until_ready
        monkeypatch.setattr(jax, "block_until_ready",
                            lambda x: blocked.append(1) or real(x))
        tracer = get_tracer()
        if enabled:
            tracer.enable()
        losses = [engine.train_batch(batch=b) for b in batches[1:]]
        assert blocked == [], "train_batch blocked on the device"
        assert len(losses) == 3
        real(losses[-1])
        names = [s["name"] for s in tracer.spans]
        assert (names.count("train_step") == 3) is enabled
        if enabled:
            steps = [s for s in tracer.spans if s["name"] == "train_step"]
            assert [s["attrs"]["step"] for s in steps] == [2, 3, 4]
            for s in steps:
                kids = [k["name"] for k in sorted(tracer.spans, key=lambda k: k["ts"])
                        if k["parent_id"] == s["span_id"]]
                assert kids == ["train.host_batch", "train.dispatch", "train.bookkeeping"]
            assert "grad_sync" not in names

    def test_no_block_until_ready_in_train_batch_depends_on_the_tracer(self):
        import ast
        src = open(os.path.join(REPO, "deepspeed_tpu/runtime/engine.py")).read()
        fn = next(n for n in ast.walk(ast.parse(src))
                  if isinstance(n, ast.FunctionDef) and n.name == "train_batch")
        assert "block_until_ready" not in ast.get_source_segment(src, fn)

    def test_modeled_collective_bytes_ride_the_step_span(self):
        import deepspeed_tpu as ds
        from deepspeed_tpu.models import GPT2Config, gpt2_model
        import jax
        if len(jax.devices()) < 2:
            pytest.skip("needs two devices for a gradient sync")
        tracer = get_tracer().enable()
        model = gpt2_model(GPT2Config(vocab_size=128, n_positions=32, n_embd=32,
                                      n_layer=1, n_head=2), sample_seq_len=32)
        engine = ds.initialize(model=model, config={
            "train_batch_size": 16, "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
            "comm_overlap": {"quantized_allreduce": True}})[0]
        ids = np.random.default_rng(0).integers(0, 128, size=(16, 32), dtype=np.int32)
        engine.train_batch(batch={"input_ids": ids})
        step = next(s for s in tracer.spans if s["name"] == "train_step")
        assert not any(s["name"] == "grad_sync" for s in tracer.spans)
        if engine._comm_spans:
            assert step["attrs"]["bytes_on_wire"] > 0
            assert 0.0 <= step["attrs"]["overlap_ratio"] <= 1.0

    def test_phases_hold_engine_init_with_the_tracer_off(self):
        tracer = get_tracer()
        assert not tracer.enabled
        n0 = len(tracer.phases)
        engine, batches = _train_engine()
        engine.train_batch(batch=batches[0])
        new = tracer.phases[n0:]
        parents = {p["name"]: p["parent"] for p in new}
        assert parents["setup.engine_init"] is None
        for child in ("setup.mesh", "setup.init_params", "setup.init_optimizer",
                      "setup.place_state"):
            assert parents[child] == "setup.engine_init", child
        assert parents["setup.build_train_step"] is None
        init = next(p for p in new if p["name"] == "setup.engine_init")
        assert sum(p["t1"] - p["t0"] for p in new if p["parent"] == "setup.engine_init") \
            <= init["t1"] - init["t0"]
        assert tracer.spans == []
