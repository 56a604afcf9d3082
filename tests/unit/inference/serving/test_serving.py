"""Serving subsystem tests: continuous batching, slot recycling, backpressure,
deadlines/cancellation, fault retry, telemetry, loadgen smoke.

The acceptance lane for the serving tentpole: ≥3 staggered unequal-length
requests through the scheduler with (1) token parity against per-request
``generate``, (2) a later-arriving request admitted into a slot freed mid-flight,
(3) queue-full submissions rejected with backpressure rather than dropped.
"""

import importlib.util
import json
import os

import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu as ds
from deepspeed_tpu.inference.engine import InferenceEngine
from deepspeed_tpu.inference.serving import (ContinuousBatchingScheduler,
                                             QueueFullError, RequestState,
                                             ServingConfig)
from deepspeed_tpu.inference.serving.kv_pool import NULL_PAGE, PagedKVPool
from deepspeed_tpu.models.causal_lm import gpt2_cfg
from deepspeed_tpu.utils import fault_injection as fi
from tests.unit import hybrid_tiny as ht

pytestmark = pytest.mark.serving

TINY = dict(vocab_size=96, max_seq_len=64, n_embd=32, n_layer=2, n_head=4,
            dtype=jnp.float32)
CAP = 32


@pytest.fixture(scope="module")
def engine():
    return InferenceEngine(gpt2_cfg(**TINY), ds.inference.DeepSpeedInferenceConfig(
        dtype="float32", max_out_tokens=CAP))


def _prompts(seed=0, sizes=(8, 5, 3)):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, TINY["vocab_size"], size=s).astype(np.int32)
            for s in sizes]


# --------------------------------------------------------------- acceptance
def test_continuous_batching_integration(engine):
    """Three staggered unequal-length requests; slot recycling mid-flight;
    backpressure; token parity with per-request generate."""
    p0, p1, p2 = _prompts(0)
    sched = ContinuousBatchingScheduler(engine, ServingConfig(
        slots=2, chunk_size=3, max_queue=2, max_seq_len=CAP))

    h0 = sched.submit(p0, max_new_tokens=7)       # finishes first
    h1 = sched.submit(p1, max_new_tokens=12)      # long-running
    sched.step()                                  # both admitted + one chunk
    assert h0.state == h1.state == RequestState.RUNNING
    # both slots now spoken for; the queue bound (2) backpressures extras
    hq1 = sched.submit(p2, max_new_tokens=2)
    hq2 = sched.submit(p2, max_new_tokens=2)
    with pytest.raises(QueueFullError) as ei:
        sched.submit(p2, max_new_tokens=2)
    assert ei.value.retry_after > 0
    # rejected ≠ dropped: the two accepted queue entries are intact
    assert sched.queue_depth == 2
    hq1.cancel()
    hq2.cancel()

    # stagger: step until h0 completes, h1 must still be decoding
    steps = 0
    while not h0.done and steps < 50:
        sched.step()
        steps += 1
    assert h0.state == RequestState.FINISHED
    assert h1.state == RequestState.RUNNING

    # late arrival lands in the slot h0 freed, while h1 keeps decoding
    h2 = sched.submit(p2, max_new_tokens=6)
    sched.step()
    assert h2.state == RequestState.RUNNING
    assert h2.slot == h0.slot
    sched.run()
    assert h1.state == h2.state == RequestState.FINISHED

    for h, p, m in ((h0, p0, 7), (h1, p1, 12), (h2, p2, 6)):
        ref = engine.generate(p[None, :], max_new_tokens=m)
        np.testing.assert_array_equal(h.result(), ref[0, p.size:])
        assert h.finish_reason == "length"
        assert h.ttft is not None and h.ttft > 0

    # after resubmission the previously-rejected workload is served fine
    h3 = sched.submit(p2, max_new_tokens=2)
    sched.run()
    assert h3.state == RequestState.FINISHED


def test_eos_finish_matches_generate(engine):
    """A request hitting its per-request EOS mid-chunk stops there, emits the
    EOS, and matches generate's trimmed output."""
    (p0,) = _prompts(3, sizes=(6,))
    ref = engine.generate(p0[None, :], max_new_tokens=8)
    eos = int(ref[0, p0.size + 2])               # third generated token
    ref_eos = engine.generate(p0[None, :], max_new_tokens=8, eos_token_id=eos)
    sched = ContinuousBatchingScheduler(engine, ServingConfig(
        slots=2, chunk_size=3, max_seq_len=CAP))
    h = sched.submit(p0, max_new_tokens=8, eos_token_id=eos)
    sched.run()
    assert h.finish_reason == "eos"
    assert h.tokens[-1] == eos
    np.testing.assert_array_equal(h.result(), ref_eos[0, p0.size:])


# ------------------------------------------------------------------ kv pool
def test_kv_pool_recycling_zero_fills(engine):
    """Released pages go back to the free list as they are (rows at or past
    ``cache_len`` are masked, and the next prefill's scatter overwrites whole
    pages); a released slot's recurrent state, which no length masks, comes
    back zeroed."""
    pool = PagedKVPool(engine.model_config, slots=2, cap=CAP, page_size=8,
                       dtype=engine.dtype)
    a, b = pool.acquire(), pool.acquire()
    assert (a, b) == (0, 1) and pool.acquire() is None
    assert pool.occupancy == 1.0 and pool.free_pages == 0
    pages = pool.table_row(1).copy()
    assert np.all(pages != NULL_PAGE) and len(set(pages)) == pool.max_pages
    # dirty slot 1: its pages, and nobody else's, hold the rows
    dirty = [{"k": jnp.ones((1,) + c["k"].shape[1:2] + (CAP,) + c["k"].shape[3:]),
              "v": jnp.ones((1,) + c["v"].shape[1:2] + (CAP,) + c["v"].shape[3:])}
             for c in pool.caches]
    pool.scatter_prefill(1, dirty)
    k0 = np.asarray(pool.caches[0]["k"])
    assert np.all(k0[pages] == 1.0) and np.all(k0[pool.table_row(0)] == 0.0)
    pool.release(1)
    assert pool.free_slots == 1 and pool.free_pages == pool.max_pages
    assert np.all(pool.table_row(1) == NULL_PAGE)
    # released slot is recyclable, its pages with it; a shorter prefill's
    # scatter pads to whole pages, so no row of the last tenant survives
    assert pool.acquire(tokens=10) == 1
    reused = pool.table_row(1)[:2]
    assert set(reused) <= set(pages)
    short = [{"k": jnp.full_like(d["k"][:, :, :10], 2.0),
              "v": jnp.full_like(d["v"][:, :, :10], 2.0)} for d in dirty]
    pool.scatter_prefill(1, short)
    rows = np.asarray(pool.caches[0]["k"])[reused].transpose(1, 0, 2, 3) \
        .reshape(k0.shape[1], -1, k0.shape[3])
    assert np.all(rows[:, :10] == 2.0) and np.all(rows[:, 10:] == 0.0)
    pool.release(0)
    with pytest.raises(ValueError):
        pool.release(0)
    # the state pool: a hybrid model's per-slot state is cleared on release
    hybrid = PagedKVPool(ht.config(), slots=2, cap=CAP, page_size=8)
    assert hybrid.state_nbytes > 0
    slot = hybrid.acquire()
    hybrid.caches = [c if "k" in c else {key: jnp.ones_like(x) for key, x in c.items()}
                     for c in hybrid.caches]
    hybrid.release(slot)
    for c in hybrid.caches:
        for key in set(c) - {"k", "v"}:
            x = np.asarray(c[key].astype(jnp.float32))
            assert np.all(x[slot] == 0.0) and np.all(x[1 - slot] == 1.0)


# ------------------------------------------------- deadlines / cancellation
def test_deadline_and_cancellation(engine):
    p0, p1, _ = _prompts(1)
    sched = ContinuousBatchingScheduler(engine, ServingConfig(
        slots=1, chunk_size=2, max_seq_len=CAP))
    # queued request with an already-expired deadline never runs
    h_dead = sched.submit(p0, max_new_tokens=4, deadline_s=0.0)
    sched.step()
    assert h_dead.state == RequestState.EXPIRED
    assert h_dead.finish_reason == "deadline"
    # in-flight cancellation keeps partial tokens and frees the slot
    h = sched.submit(p1, max_new_tokens=20)
    sched.step()
    assert h.state == RequestState.RUNNING
    got = len(h.tokens)
    assert got >= 1
    h.cancel()
    sched.step()
    assert h.state == RequestState.CANCELLED
    assert len(h.tokens) >= got
    assert sched.executor.pool.free_slots == 1
    # the freed slot serves the next request normally
    h2 = sched.submit(p0, max_new_tokens=3)
    sched.run()
    assert h2.state == RequestState.FINISHED


def test_admission_validation(engine):
    # small default budget so the max_new_tokens=0 case cannot be masked by the
    # capacity check silently rejecting a substituted default
    sched = ContinuousBatchingScheduler(engine, ServingConfig(
        slots=1, chunk_size=2, max_seq_len=CAP, default_max_new_tokens=4))
    with pytest.raises(ValueError):
        sched.submit(np.arange(CAP, dtype=np.int32))          # prompt > max
    with pytest.raises(ValueError):
        sched.submit(np.arange(8, dtype=np.int32), max_new_tokens=CAP)
    with pytest.raises(ValueError):
        sched.submit(np.arange(4, dtype=np.int32) % 8, max_new_tokens=0)
    assert sched.queue_depth == 0                 # nothing was enqueued


# -------------------------------------------------------------- fault retry
def test_transient_prefill_fault_is_retried(engine):
    fi.reset_faults()
    p0 = _prompts(2, sizes=(5,))[0]
    sched = ContinuousBatchingScheduler(engine, ServingConfig(
        slots=1, chunk_size=2, max_seq_len=CAP, retry_base_delay=0.001))
    ref = engine.generate(p0[None, :], max_new_tokens=4)
    with fi.inject("serving.prefill", fi.FaultSpec(kind="io_error",
                                                   max_faults=1)):
        h = sched.submit(p0, max_new_tokens=4)
        sched.run()
    assert fi.faults_fired("serving.prefill") == 1
    assert h.state == RequestState.FINISHED
    np.testing.assert_array_equal(h.result(), ref[0, p0.size:])
    fi.reset_faults()


def test_exhausted_prefill_retries_fail_request_not_loop(engine):
    """When the retry budget runs out the request fails — but the slot is
    reclaimed and the scheduler keeps serving."""
    fi.reset_faults()
    p0 = _prompts(6, sizes=(4,))[0]
    sched = ContinuousBatchingScheduler(engine, ServingConfig(
        slots=1, chunk_size=2, max_seq_len=CAP, transient_retries=1,
        retry_base_delay=0.001))
    with fi.inject("serving.prefill", fi.FaultSpec(kind="io_error",
                                                   max_faults=5)):
        h_bad = sched.submit(p0, max_new_tokens=3)
        sched.step()
    assert h_bad.state == RequestState.CANCELLED
    assert h_bad.finish_reason == "error"
    assert sched.executor.pool.free_slots == 1        # slot reclaimed
    h_ok = sched.submit(p0, max_new_tokens=3)
    sched.run()
    assert h_ok.state == RequestState.FINISHED
    fi.reset_faults()


def test_non_transient_dispatch_failure_reaches_the_caller(engine):
    """Only transient faults (I/O errors, a chunk deadline, the chaos kill)
    are absorbed. Anything else out of a dispatch — on the chip a Mosaic
    refusal or an HBM RESOURCE_EXHAUSTED, here an injected RuntimeError —
    would fail every later dispatch the same way: the in-flight requests are
    failed AND the error propagates out of ``step()``, so a server whose
    every dispatch fails cannot exit 0."""
    fi.reset_faults()
    p0 = _prompts(8, sizes=(4,))[0]
    sched = ContinuousBatchingScheduler(engine, ServingConfig(
        slots=2, chunk_size=2, max_seq_len=CAP, transient_retries=1,
        retry_base_delay=0.001))
    h_decode = sched.submit(p0, max_new_tokens=6)
    with fi.inject("serving.decode_chunk", fi.FaultSpec(
            kind="io_error", exc_type=RuntimeError, max_faults=5)):
        with pytest.raises(RuntimeError, match="injected fault"):
            sched.step()
    assert h_decode.state == RequestState.CANCELLED
    assert h_decode.finish_reason == "error"
    assert fi.faults_fired("serving.decode_chunk") == 1      # never retried
    h_prefill = sched.submit(p0, max_new_tokens=3)
    with fi.inject("serving.prefill", fi.FaultSpec(
            kind="io_error", exc_type=RuntimeError, max_faults=5)):
        with pytest.raises(RuntimeError, match="injected fault"):
            sched.step()
    assert h_prefill.state == RequestState.CANCELLED
    assert h_prefill.finish_reason == "error"
    fi.reset_faults()


def test_exhausted_decode_retries_fail_inflight_keep_serving(engine):
    """An unrecoverable decode chunk fails every in-flight request (the donated
    pool buffers cannot be trusted), but the pool is rebuilt and the scheduler
    keeps serving new requests."""
    fi.reset_faults()
    p0 = _prompts(7, sizes=(4,))[0]
    sched = ContinuousBatchingScheduler(engine, ServingConfig(
        slots=2, chunk_size=2, max_seq_len=CAP, transient_retries=1,
        retry_base_delay=0.001))
    with fi.inject("serving.decode_chunk", fi.FaultSpec(kind="io_error",
                                                        max_faults=5)):
        h_bad = sched.submit(p0, max_new_tokens=6)
        sched.step()
    assert h_bad.state == RequestState.CANCELLED
    assert h_bad.finish_reason == "error"
    assert sched.executor.pool.free_slots == 2        # pool rebuilt, all free
    ref = engine.generate(p0[None, :], max_new_tokens=4)
    h_ok = sched.submit(p0, max_new_tokens=4)
    sched.run()
    assert h_ok.state == RequestState.FINISHED
    np.testing.assert_array_equal(h_ok.result(), ref[0, p0.size:])
    fi.reset_faults()


def test_serve_stdin_streams_and_isolates_bad_lines(engine):
    """deepspeed-serve's stdin loop: streams results as requests finish and
    fails a malformed line alone instead of killing the server."""
    import io

    from deepspeed_tpu.inference.serving import server as srv
    sched = ContinuousBatchingScheduler(engine, ServingConfig(
        slots=2, chunk_size=3, max_seq_len=CAP))
    inp = io.StringIO(
        '{"prompt": [1, 2, 3, 4], "max_new_tokens": 3}\n'
        "this is not json\n"
        '{"prompt": [], "max_new_tokens": 3}\n'
        '{"prompt": [5, 6, 7], "max_new_tokens": 2}\n')
    out = io.StringIO()
    snap = srv._serve_stdin(sched, out=out, inp=inp)
    lines = [json.loads(x) for x in out.getvalue().strip().splitlines()]
    errors = [ln for ln in lines if "error" in ln]
    results = [ln for ln in lines if "error" not in ln]
    assert len(errors) == 2                       # bad json + empty prompt
    assert len(results) == 2
    assert all(r["state"] == "finished" and len(r["tokens"]) > 0
               for r in results)
    assert snap["completed"] == 2


# ----------------------------------------------------- sampling determinism
def test_sampling_independent_of_co_batching(engine):
    """A sampled request's tokens depend only on its own seed — not on slot
    placement or co-batched traffic (per-slot key streams)."""
    p0, p1, _ = _prompts(4)
    sampling = dict(do_sample=True, temperature=0.9, top_k=0, top_p=1.0)
    alone = ContinuousBatchingScheduler(engine, ServingConfig(
        slots=2, chunk_size=3, max_seq_len=CAP, **sampling))
    ha = alone.submit(p0, max_new_tokens=6, seed=7)
    alone.run()
    crowd = ContinuousBatchingScheduler(engine, ServingConfig(
        slots=2, chunk_size=3, max_seq_len=CAP, **sampling))
    hb_other = crowd.submit(p1, max_new_tokens=9, seed=3)   # takes slot 0
    hb = crowd.submit(p0, max_new_tokens=6, seed=7)         # slot 1 this time
    crowd.run()
    assert ha.slot != hb.slot
    np.testing.assert_array_equal(ha.result(), hb.result())
    assert hb_other.state == RequestState.FINISHED


# ---------------------------------------------------------------- telemetry
def test_telemetry_jsonl_events(engine, tmp_path):
    from deepspeed_tpu.config.config import MonitorConfig
    from deepspeed_tpu.monitor import MonitorMaster
    master = MonitorMaster(MonitorConfig(jsonl_monitor={
        "enabled": True, "output_path": str(tmp_path), "job_name": "serve"}))
    sched = ContinuousBatchingScheduler(engine, ServingConfig(
        slots=2, chunk_size=3, max_seq_len=CAP), monitor=master)
    p0, p1, _ = _prompts(5)
    sched.submit(p0, max_new_tokens=4)
    sched.submit(p1, max_new_tokens=3)
    sched.run()
    path = os.path.join(str(tmp_path), "serve.jsonl")
    tags = {json.loads(line)["tag"] for line in open(path)}
    assert {"serving/ttft_ms", "serving/tpot_ms", "serving/queue_depth",
            "serving/slot_occupancy", "serving/tokens_per_sec",
            "serving/completed_total"} <= tags
    snap = sched.telemetry.snapshot()
    assert snap["completed"] == 2 and snap["tokens_total"] >= 5
    assert snap["ttft_ms_p50"] > 0


# ------------------------------------------------------------ loadgen smoke
@pytest.mark.slow   # duplicate of the slow bench smokes' entry-path coverage;
# demoted in PR 19 to pay for test_prefix_tier.py inside serving_family's
# tier-1 share (tests/conftest.py TIER1_BUDGETS_S rank 3)
def test_loadgen_smoke(capsys):
    repo = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))))
    spec = importlib.util.spec_from_file_location(
        "serving_loadgen", os.path.join(repo, "benchmarks", "serving",
                                        "loadgen.py"))
    loadgen = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(loadgen)
    rc = loadgen.main(["--smoke"])
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0
    assert out["metric"] == "serving_tokens_per_sec" and out["value"] > 0
    assert out["detail"]["completed"] == 6
    assert out["detail"]["all_finished"]
