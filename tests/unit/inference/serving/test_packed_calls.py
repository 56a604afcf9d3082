"""One packed operand in, one packed result out (PR 28): every program the
scheduler calls takes its per-call control state as ONE host-built int32 array
and hands back what the host reads as ONE int32 array.

- the packed chunk gives token for token what the unpacked call gave (the
  unpacked call is built here from ``decode_fns``' builders, which keep their
  signatures, and placed and fetched array by array as ``run_chunk`` did);
- the ``arrays`` attribute of ``serving.place_inputs`` / ``serving.fetch``
  counts the crossings: 1 and 1 for a chunk, 2 and 1 for a prefill;
- a miss's prefill allocates no result (PR 50): it is handed the last miss's
  batch-1 cache, donated, and writes its own over it, every leaf aliased; the
  scatter follows the first token's stamp; ``serving.admit`` counts
  ``programs``; a slot that held a longer request serves the next as
  ``engine.generate`` does; a failed admission rebuilds the pool only if its
  buffers are gone, whether the failure shows at the dispatch or at the fetch;
- the lowered programs keep what the benchmark's readers find them by: their
  names, and the padded prompt as ``@main``'s first int32 argument of rank 2.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu as ds
from deepspeed_tpu.inference.decode_fns import build_paged_decode_chunk
from deepspeed_tpu.inference.engine import InferenceEngine
from deepspeed_tpu.inference.serving import (ContinuousBatchingScheduler,
                                             ServingConfig)
from deepspeed_tpu.inference.serving import executor as ex_mod
from deepspeed_tpu.inference.serving.executor import ChunkResult
from deepspeed_tpu.inference.serving.prefix_cache import PrefixCacheConfig
from deepspeed_tpu.models.causal_lm import gpt2_cfg
from deepspeed_tpu.analysis.donation import assert_all_donated
from deepspeed_tpu.inference.serving.scheduler import RequestState
from deepspeed_tpu.observability.trace import get_tracer
from deepspeed_tpu.utils import fault_injection as fi
from benchmarks.chipbench.probe import first_int_arg_shape
from tests.unit import granite_tiny as gt
from tests.unit import hybrid_tiny as ht
from tests.unit import lfm2_tiny as lt

pytestmark = pytest.mark.serving

CAP, CHUNK, SLOTS = 64, 4, 2
DENSE = dict(vocab_size=96, max_seq_len=CAP, n_embd=32, n_layer=2, n_head=4,
             dtype=jnp.float32)


@pytest.fixture(scope="module")
def engines():
    conf = ds.inference.DeepSpeedInferenceConfig(dtype="float32",
                                                 max_out_tokens=CAP)
    return {False: InferenceEngine(gpt2_cfg(**DENSE), conf),
            True: InferenceEngine(ht.config(max_seq_len=CAP), conf, seed=3)}


@pytest.fixture(autouse=True)
def _fresh_tracer():
    t = get_tracer()
    t.disable()
    t.reset()
    yield t
    t.disable()
    t.reset()


def _scheduler(engine, sample, prefix=False):
    sampling = dict(do_sample=True, temperature=0.9) if sample else {}
    return ContinuousBatchingScheduler(engine, ServingConfig(
        slots=SLOTS, chunk_size=CHUNK, max_seq_len=CAP, max_queue=8,
        kv_page_size=8, prefix_cache=PrefixCacheConfig(enabled=prefix),
        **sampling))


def _unpacked_run_chunk(ex):
    """``run_chunk`` as it was before the packing: the builder's function
    jitted as it stands, every operand placed and every output fetched as an
    array of its own."""
    fn = jax.jit(build_paged_decode_chunk(
        ex.engine.module, ex.engine._dequant, ex._slot_select, ex.chunk_size,
        kv_cap=ex.cap, with_stats=ex.with_stats), donate_argnums=(2,))

    def run_chunk(toks, lens, active, remaining, eos_ids, seeds, steps):
        out = fn(ex.engine.params, jnp.asarray(toks, jnp.int32).reshape(-1, 1),
                 ex.pool.caches, jnp.asarray(ex.pool.page_table),
                 jnp.asarray(lens, jnp.int32),
                 jnp.asarray(active, bool), jnp.asarray(remaining, jnp.int32),
                 jnp.asarray(eos_ids, jnp.int32), jnp.asarray(seeds, jnp.int32),
                 jnp.asarray(steps, jnp.int32), ex._base_key)
        ex.pool.caches = out[2]
        buf, toks_d, lens_d, active_d, remaining_d, steps_d, *stats = (
            np.asarray(x) for i, x in enumerate(out) if i != 2)
        return ChunkResult(buf=buf, toks=toks_d, lens=lens_d, active=active_d,
                           remaining=remaining_d, steps=steps_d, elapsed=0.0,
                           moe=stats[0] if stats else None)

    return run_chunk


def _serve(sched, vocab):
    rng = np.random.default_rng(5)
    prompts = [rng.integers(1, vocab, size=n).astype(np.int32)
               for n in (5, 13, 16, 9)]
    # four requests through two slots, ending anywhere in a chunk; the second
    # stops at an EOS it is sure to draw late or never (both are compared)
    handles = [sched.submit(p, max_new_tokens=6 + 3 * i, seed=11 + i,
                            eos_token_id=7 if i == 1 else None)
               for i, p in enumerate(prompts)]
    sched.run()
    return handles


@pytest.mark.parametrize("experts", [False, True], ids=["dense", "experts"])
@pytest.mark.parametrize("sample", [False, True], ids=["greedy", "sampled"])
def test_the_packed_chunk_gives_the_unpacked_calls_tokens(engines, sample,
                                                          experts):
    engine = engines[experts]
    vocab = engine.model_config.vocab_size
    packed = _scheduler(engine, sample)
    chunks = []
    run = packed.executor.run_chunk
    packed.executor.run_chunk = lambda *a: chunks.append(run(*a)) or chunks[-1]
    got = _serve(packed, vocab)
    plain = _scheduler(engine, sample)
    plain.executor.run_chunk = _unpacked_run_chunk(plain.executor)
    want = _serve(plain, vocab)
    for g, w in zip(got, want):
        assert g.state == w.state and g.finish_reason == w.finish_reason
        assert list(g.tokens) == list(w.tokens)
        assert len(g.tokens) >= 2
    assert (packed.telemetry.moe_assignments, packed.telemetry.moe_experts_touched) \
        == (plain.telemetry.moe_assignments, plain.telemetry.moe_experts_touched)
    assert (packed.telemetry.moe_assignments > 0) is experts
    # the harvest reads the fields it read before, with the types it read
    res = chunks[0]
    assert res.buf.shape == (SLOTS, CHUNK) and res.toks.shape == (SLOTS, 1)
    assert res.active.dtype == np.bool_ and res.active.shape == (SLOTS,)
    for field in (res.buf, res.toks, res.lens, res.remaining, res.steps):
        assert field.dtype == np.int32
    assert (res.moe is not None) is experts
    if experts:
        assert res.moe.shape == (2,) and res.moe.dtype == np.int32


def _crossings(ring, program):
    return {name: [s["attrs"]["arrays"] for s in ring
                   if s["name"] == name and s["attrs"]["program"] == program]
            for name in ("serving.place_inputs", "serving.fetch")}


@pytest.mark.parametrize("experts", [False, True], ids=["dense", "experts"])
def test_a_chunk_crosses_once_each_way_and_a_prefill_twice_in_once_out(
        engines, experts):
    tracer = get_tracer().enable()
    _serve(_scheduler(engines[experts], False),
           engines[experts].model_config.vocab_size)
    ring = list(tracer.spans)
    chunk, prefill = _crossings(ring, "decode_chunk"), _crossings(ring, "prefill")
    assert len(chunk["serving.fetch"]) >= 4 and len(prefill["serving.fetch"]) == 4
    assert set(chunk["serving.place_inputs"]) == {1}
    assert set(chunk["serving.fetch"]) == {1}
    assert set(prefill["serving.place_inputs"]) == {2}
    assert set(prefill["serving.fetch"]) == {1}


@pytest.mark.parametrize("shared_tokens,cow", [(16, 0), (13, 1)],
                         ids=["whole-pages", "copy-on-write"])
def test_a_prefix_hit_crosses_as_a_prefill_does_and_keeps_its_tokens(
        engines, shared_tokens, cow):
    """The suffix prefill's packed operand carries the slot's page-table row,
    whether the match ends on a page boundary (two pages of 8 bound as they
    are) or inside a page (the boundary page copied first): two arrays in,
    one out, and the tokens of a request served without the cache."""
    tracer = get_tracer().enable()
    shared = np.arange(1, shared_tokens + 1, dtype=np.int32)
    prompts = [np.concatenate([shared, np.asarray(tail, np.int32)])
               for tail in ([40, 41, 42], [50, 51])]
    tokens = {}
    for prefix in (True, False):
        sched = _scheduler(engines[False], False, prefix=prefix)
        handles = []
        for p in prompts:
            handles.append(sched.submit(p, max_new_tokens=5))
            sched.run()
        tokens[prefix] = [list(h.tokens) for h in handles]
        if prefix:
            assert handles[1].prefix_hit_tokens == shared_tokens
            assert sched.executor.pool.cow_copies_total == cow
    assert tokens[True] == tokens[False]
    hit = _crossings(list(tracer.spans), "suffix_prefill")
    assert hit == {"serving.place_inputs": [2], "serving.fetch": [1]}


@pytest.mark.parametrize("experts", [False, True], ids=["dense", "experts"])
def test_the_lowered_programs_keep_their_names_and_the_prompt_leads(engines,
                                                                    experts):
    """What the benchmark's readers find the programs by: ``jit_<name>`` in
    the trace and the dump, and ``first_int_arg_shape`` = the prefill's
    ``1 x bucket``: no other int32 operand of rank 2 or more before the
    padded prompt. (A prefix hit needs keys and values in every layer, so
    the hybrid pattern has no suffix prefill.)"""
    ex = _scheduler(engines[experts], False).executor
    ids = jax.ShapeDtypeStruct((1, 16), jnp.int32)
    vec = lambda n: jax.ShapeDtypeStruct((n,), jnp.int32)  # noqa: E731
    table = ex.pool.max_pages
    params, key = ex.engine.params, ex._base_key
    cases = {
        "prefill": (ex._prefill_fn(16),
                    (params, ex._one_cache(), ids, vec(2), key)),
        "decode_chunk": (
            ex._chunk_fn(),
            (params, jax.ShapeDtypeStruct((SLOTS, ex_mod.CTL_COLS + table),
                                          jnp.int32), ex.pool.caches, key)),
    }
    if ex.kv_every_layer:
        cases["suffix_prefill"] = (
            ex._suffix_prefill_fn_paged(16),
            (params, ex.pool.caches, ids, vec(ex_mod.PRE_COLS + table), key))
    for name, (fn, args) in cases.items():
        text = fn.lower(*args).as_text()
        assert f"module @jit_{name} " in text, name
        shape = first_int_arg_shape(text)
        if name == "decode_chunk":
            assert shape == f"{SLOTS}x{ex_mod.CTL_COLS + table}"
        else:
            assert shape == "1x16", (name, shape)


def _under(ring, span):
    """The spans of ``ring`` below ``span``, at any depth."""
    ids, out = {span["span_id"]}, []
    for s in sorted(ring, key=lambda s: s["ts"]):
        if s["parent_id"] in ids:
            ids.add(s["span_id"])
            out.append(s)
    return out


@pytest.mark.parametrize("experts", [False, True], ids=["dense", "experts"])
def test_a_miss_prefill_writes_its_cache_over_the_last_ones_and_allocates_none(
        engines, experts):
    tracer = get_tracer().enable()
    sched = _scheduler(engines[experts], False)
    ex = sched.executor
    first = ex._one_cache()
    _serve(sched, engines[experts].model_config.vocab_size)
    ring = list(tracer.spans)
    admits = [s for s in ring if s["name"] == "serving.admit"]
    assert [a["attrs"]["programs"] for a in admits] == [2, 2, 2, 2]
    for a in admits:
        below = _under(ring, a)
        assert [s["attrs"]["program"] for s in below
                if s["name"] == "serving.dispatch"] == ["prefill"]
        # the scatter is a program of the pool's own, after the stamp
        prefill, scatter = (next(s for s in below if s["name"] == name)
                            for name in ("serving.prefill", "serving.scatter_prefill"))
        assert scatter["ts"] >= prefill["ts"] + prefill["dur"]
    # the batch-1 cache is operand 1, donated, and every leaf of it (keys and
    # values, and the hybrid's per-slot state) is aliased to a result: the
    # first one's buffers went into the admissions, one stands after them
    leaves = jax.tree_util.tree_leaves(ex._one_cache())
    assert all(a.is_deleted() for a in jax.tree_util.tree_leaves(first))
    assert not any(a.is_deleted() for a in leaves)
    args = (ex.engine.params, ex._one_cache(), jnp.zeros((1, 16), jnp.int32),
            jnp.zeros((2,), jnp.int32), ex._base_key)
    audit = assert_all_donated(ex._prefill_fn(16), args, target="serve_prefill")
    assert audit.checked == len(leaves) == (6 if experts else 4)
    assert audit.findings == []


def _engine_of(kind):
    conf = ds.inference.DeepSpeedInferenceConfig(dtype="float32",
                                                 max_out_tokens=CAP)
    cfg = {"kv": lambda: gpt2_cfg(**DENSE), "granite": gt.config,
           "lfm2": lt.config}[kind]()
    return InferenceEngine(cfg, conf, seed=3)


@pytest.mark.parametrize("kind", ["kv", "granite", "lfm2"])
def test_a_slot_that_held_a_longer_request_serves_the_next_as_generate_does(kind):
    """A slot whose pages held another request's rows (here one that filled
    every page of the pool to the cap) and whose batch-1 cache held that
    request's too: the next request reads none of them, its state is written
    whole."""
    engine = _engine_of(kind)
    rng = np.random.default_rng(9)
    vocab = engine.model_config.vocab_size
    long, short = (rng.integers(1, vocab, size=n).astype(np.int32)
                   for n in (40, 5))
    sched = ContinuousBatchingScheduler(engine, ServingConfig(
        slots=1, chunk_size=CHUNK, max_seq_len=CAP, kv_page_size=8,
        prefix_cache=PrefixCacheConfig(enabled=False)))
    first = sched.submit(long, max_new_tokens=CAP - long.size)
    sched.run()
    assert len(first.tokens) == CAP - long.size and sched.executor.pool.free_pages == 8
    dirty = [float(jnp.abs(c["k"][1:]).min(axis=(1, 2, 3)).max())
             for c in sched.executor.pool.caches if "k" in c]
    assert dirty and min(dirty) > 0          # every page but the null one was written
    second = sched.submit(short, max_new_tokens=12)
    sched.run()
    for prompt, h in ((long, first), (short, second)):
        alone = engine.generate(prompt[None], max_new_tokens=len(h.tokens))
        assert list(h.tokens) == [int(t) for t in alone[0, prompt.size:]]


class _Poisoned:
    """A result whose fetch fails: how a device error of an asynchronous
    dispatch reaches the host."""

    def __array__(self, *args, **kwargs):
        raise OSError("the device failed; the fetch says so")


@pytest.mark.parametrize("when", ["before-dispatch", "in-dispatch", "in-fetch"])
@pytest.mark.parametrize("hit", [False, True], ids=["miss", "hit"])
def test_a_failed_admission_rebuilds_the_pool_only_if_its_buffers_are_gone(
        engines, hit, when, monkeypatch):
    """One rule for a hit and a miss, read off the buffers the dispatch was
    handed: a ``serving.prefill`` fault fires before any dispatch and a miss's
    prefill is never handed the pool, so the other stream, the pool and the
    cached prefix stay; a hit's suffix prefill that dies after the donation,
    at its dispatch or only at the fetch of its result, took the pool's
    buffers with it: no second try runs on them, the in-flight requests fail,
    the pool is rebuilt and the scheduler keeps serving."""
    fi.reset_faults()
    engine = engines[False]
    consumed = hit and when != "before-dispatch"
    sched = ContinuousBatchingScheduler(engine, ServingConfig(
        slots=SLOTS, chunk_size=CHUNK, max_seq_len=CAP, kv_page_size=8,
        transient_retries=0 if when == "before-dispatch" else 1,
        retry_base_delay=0.001, prefix_cache=PrefixCacheConfig(enabled=hit)))
    ex = sched.executor
    shared = np.arange(1, 17, dtype=np.int32)
    done = sched.submit(np.concatenate([shared, [40, 41]]).astype(np.int32),
                        max_new_tokens=3)
    sched.run()                                   # warms, and fills the cache
    running = sched.submit(np.arange(50, 59, dtype=np.int32), max_new_tokens=12)
    sched.step()
    assert running.state == RequestState.RUNNING and done.state == RequestState.FINISHED
    pool = ex.pool
    victim_prompt = np.concatenate([shared, [60, 61, 62]]).astype(np.int32)
    if when == "before-dispatch":
        with fi.inject("serving.prefill", fi.FaultSpec(kind="io_error",
                                                       max_faults=1)):
            victim = sched.submit(victim_prompt, max_new_tokens=4)
            sched.step()
        assert fi.faults_fired("serving.prefill") == 1
    else:
        dispatch, tries = ex._dispatch, []

        def dies(fn, args, program, *rest):
            out, returned_at = dispatch(fn, args, program, *rest)
            if not program.endswith("prefill"):
                return out, returned_at
            tries.append(program)
            if when == "in-dispatch":
                raise OSError("the dispatch died with its operands in it")
            return (_Poisoned(),) + tuple(out[1:]), returned_at

        monkeypatch.setattr(ex, "_dispatch", dies)
        victim = sched.submit(victim_prompt, max_new_tokens=4)
        sched.step()
        monkeypatch.undo()
        # a pool that went with the first try is not handed to a second
        assert len(tries) == (1 if consumed else 2)
    assert victim.state == RequestState.CANCELLED and victim.finish_reason == "error"
    assert pool.consumed is consumed
    assert (ex.pool is pool) is not consumed
    if consumed:
        assert running.state == RequestState.CANCELLED
        assert ex.pool.free_slots == SLOTS
    else:
        assert ex.pool.free_slots == SLOTS - 1
        sched.run()
        alone = engine.generate(running.prompt[None], max_new_tokens=12)
        assert list(running.tokens) == [int(t) for t in alone[0, running.prompt.size:]]
    again = sched.submit(victim_prompt, max_new_tokens=4)
    sched.run()
    assert again.state == RequestState.FINISHED
    # the cached prefix lived in the old pool's pages: it went with them
    assert again.prefix_hit_tokens == (16 if hit and not consumed else 0)
    alone = engine.generate(victim_prompt[None], max_new_tokens=4)
    assert list(again.tokens) == [int(t) for t in alone[0, victim_prompt.size:]]
    fi.reset_faults()
