"""Speculative decoding tests: proposer units, accept-rule exactness, greedy
bit-identity across hit/miss/retry/drain/migration, rejection-sampling
distribution preservation, and the rollback edge cases (COW boundary-page
rejection, EOS inside a speculated block, cap-edge window truncation,
speculation x prefix-cache hit, mid-verify chaos kill -> bit-exact retry on a
survivor).

The greedy assertions are all EXACT token equality against non-speculative
decode: every emitted token is a verify-pass argmax, so bit-identity is
structural (see ``inference.speculative``) — these tests pin that the
threading through executor/scheduler/router preserves it under every recovery
path the serving column has.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu as ds
from deepspeed_tpu.inference.engine import InferenceEngine
from deepspeed_tpu.inference.serving import (ContinuousBatchingScheduler,
                                             PrefixCacheConfig, RequestState,
                                             Router, RouterConfig,
                                             ServingConfig)
from deepspeed_tpu.inference.speculative import (NgramProposer,
                                                 SpeculativeConfig,
                                                 accept_tokens, greedy_accept,
                                                 make_proposer)
from deepspeed_tpu.models.causal_lm import gpt2_cfg
from deepspeed_tpu.utils import fault_injection as fi

pytestmark = pytest.mark.speculative

TINY = dict(vocab_size=96, max_seq_len=64, n_embd=32, n_layer=2, n_head=4,
            dtype=jnp.float32)
CAP = 48


@pytest.fixture(scope="module")
def engine():
    return InferenceEngine(
        gpt2_cfg(**TINY),
        ds.inference.DeepSpeedInferenceConfig(dtype="float32",
                                              max_out_tokens=CAP))


@pytest.fixture(scope="module")
def engines(engine):
    e1 = InferenceEngine(
        gpt2_cfg(**TINY),
        ds.inference.DeepSpeedInferenceConfig(dtype="float32",
                                              max_out_tokens=CAP),
        params=engine.params)
    return [engine, e1]


def _sched(engine, speculate=True, cache=False, **over):
    kw = dict(slots=2, chunk_size=3, max_seq_len=CAP, retry_base_delay=0.001,
              kv_page_size=8, speculate=speculate, spec_k=4,
              prefix_cache=(PrefixCacheConfig(min_hit_tokens=4,
                                              min_insert_tokens=4,
                                              insert_on="prefill")
                            if cache else None))
    kw.update(over)
    return ContinuousBatchingScheduler(engine, ServingConfig(**kw))


def _ref(engine, prompt, max_new, **kw):
    out = np.asarray(engine.generate(prompt[None, :], max_new_tokens=max_new,
                                     **kw))
    return out[0, prompt.size:]


def _rep_prompt(rng, unit=4, reps=4, tail=0):
    """Repetitive-suffix prompt: the n-gram proposer's home turf."""
    u = rng.integers(0, TINY["vocab_size"], size=unit).astype(np.int32)
    p = np.tile(u, reps)
    if tail:
        p = np.concatenate([p, rng.integers(0, TINY["vocab_size"],
                                            size=tail).astype(np.int32)])
    return p


# -------------------------------------------------------------- proposer units
def test_ngram_proposer_longest_most_recent_match():
    p = NgramProposer(ngram_max=3, ngram_min=1)
    # stream ...[7,8]...[7,8]... ends in [7,8]: latest earlier occurrence of
    # the 2-gram is at index 4, its continuation is [9, 1]
    ctx = np.array([7, 8, 1, 2, 7, 8, 9, 1, 7, 8], np.int32)
    np.testing.assert_array_equal(p.propose(ctx, 2), [9, 1])
    # k truncates the continuation
    np.testing.assert_array_equal(p.propose(ctx, 1), [9])
    # longest match wins: [2,7,8] (3-gram) occurs earlier -> its continuation
    ctx3 = np.array([2, 7, 8, 5, 0, 2, 7, 8], np.int32)
    np.testing.assert_array_equal(p.propose(ctx3, 2), [5, 0])


def test_ngram_proposer_no_match_and_edge():
    p = NgramProposer(ngram_max=4, ngram_min=1)
    assert p.propose(np.array([1, 2, 3, 4], np.int32), 4).size == 0
    assert p.propose(np.array([5], np.int32), 4).size == 0
    assert p.propose(np.array([], np.int32), 4).size == 0
    # suffix-adjacent match with empty continuation falls through to a
    # shorter n rather than proposing nothing: [3,3,3] -> 1-gram 3 matches
    # at index 1 with continuation [3]
    np.testing.assert_array_equal(
        p.propose(np.array([3, 3, 3], np.int32), 2), [3])


def test_spec_config_validation():
    with pytest.raises(ValueError):
        SpeculativeConfig(k=0)
    with pytest.raises(ValueError):
        SpeculativeConfig(proposer="magic")
    with pytest.raises(ValueError):
        SpeculativeConfig(ngram_min=3, ngram_max=2)
    with pytest.raises(ValueError):
        make_proposer(SpeculativeConfig(proposer="draft_model"))


def test_greedy_accept_unit():
    assert greedy_accept(np.array([1, 2, 3]), np.array([1, 2, 3, 9])) == 3
    assert greedy_accept(np.array([1, 5, 3]), np.array([1, 2, 3, 9])) == 1
    assert greedy_accept(np.array([4]), np.array([1, 2])) == 0
    assert greedy_accept(np.zeros(0), np.array([1])) == 0


def test_accept_tokens_greedy_emits_argmax_stream():
    # logits argmax along the window: [2, 0, 1]; draft [2, 0, 5] accepts 2
    # and corrects position 2 to the argmax there
    V = 6
    logits = np.full((3, V), -10.0, np.float32)
    logits[0, 2] = logits[1, 0] = logits[2, 1] = 0.0
    emitted, acc = accept_tokens(np.array([2, 0], np.int32), logits,
                                 sampling=(False, 1.0, 0, 1.0),
                                 base_key=jax.random.PRNGKey(0), seed=0,
                                 step0=0)
    assert (emitted, acc) == ([2, 0, 1], 2)
    emitted, acc = accept_tokens(np.array([2, 5], np.int32), logits,
                                 sampling=(False, 1.0, 0, 1.0),
                                 base_key=jax.random.PRNGKey(0), seed=0,
                                 step0=0)
    assert (emitted, acc) == ([2, 0], 1)


def test_rejection_sampling_preserves_target_distribution():
    """Monte Carlo over per-slot seeds: the first emitted token of a
    speculated position is distributed EXACTLY as the target softmax,
    point-mass draft or not — the rejection-sampling identity
    p(x)·1 + (1-p(x))·p(y)/(1-p(x)) = p(y)."""
    rng = np.random.default_rng(5)
    V = 6
    logits = (rng.normal(size=(2, V)) * 1.5).astype(np.float32)
    target = np.exp(logits[0] - logits[0].max())
    target = target / target.sum()
    draft = np.array([int(np.argmax(target))], np.int32)   # likeliest token
    base_key = jax.random.PRNGKey(0)
    counts = np.zeros(V)
    N = 1500
    for seed in range(N):
        emitted, _ = accept_tokens(draft, logits,
                                   sampling=(True, 1.0, 0, 1.0),
                                   base_key=base_key, seed=seed, step0=0)
        counts[emitted[0]] += 1
    tv = 0.5 * np.abs(counts / N - target).sum()
    assert tv < 0.05, f"TV distance {tv:.3f} vs target distribution"
    # and an unlikely draft too: acceptance is rare, residual must cover
    draft2 = np.array([int(np.argmin(target))], np.int32)
    counts2 = np.zeros(V)
    for seed in range(N):
        emitted, _ = accept_tokens(draft2, logits,
                                   sampling=(True, 1.0, 0, 1.0),
                                   base_key=base_key, seed=seed, step0=0)
        counts2[emitted[0]] += 1
    tv2 = 0.5 * np.abs(counts2 / N - target).sum()
    assert tv2 < 0.05, f"TV distance {tv2:.3f} vs target distribution"


# --------------------------------------------------- scheduler-level parity
def test_greedy_parity_spec_vs_plain_vs_generate(engine):
    """Greedy speculative output is bit-identical to non-speculative decode,
    and both to ``engine.generate``, token for token, for repetitive
    (high-acceptance) and random (dry-proposer) prompts co-batched together."""
    rng = np.random.default_rng(3)
    prompts = [_rep_prompt(rng), _rep_prompt(rng, unit=3, reps=4, tail=2),
               rng.integers(0, 96, size=7).astype(np.int32)]
    maxn = (14, 10, 8)
    want = [_ref(engine, p, m) for p, m in zip(prompts, maxn)]
    for speculate in (False, True):
        sched = _sched(engine, speculate=speculate)
        hs = [sched.submit(p, max_new_tokens=m)
              for p, m in zip(prompts, maxn)]
        sched.run()
        assert all(h.state == RequestState.FINISHED for h in hs)
        for h, w in zip(hs, want):
            np.testing.assert_array_equal(h.result(), w)
    # speculation actually sped something up: fewer verify rounds than tokens
    snap = sched.telemetry.snapshot()
    assert snap["spec_accepted"] > 0
    assert snap["spec_passes_per_token"] < 1.0


def test_spec_telemetry_counters_and_snapshot(engine):
    sched = _sched(engine)
    rng = np.random.default_rng(9)
    sched.submit(_rep_prompt(rng), max_new_tokens=10)
    sched.run()
    snap = sched.telemetry.snapshot()
    assert snap["spec_rounds"] > 0
    assert snap["spec_proposed"] >= snap["spec_accepted"] >= 0
    assert 0.0 <= snap["spec_acceptance_rate"] <= 1.0
    assert snap["spec_tokens"] > 0
    # registry feed saw the declared serving/spec_* tags (schema-linted)
    sn = sched.telemetry.spec
    assert sn.rounds == snap["spec_rounds"]


def test_sampled_spec_deterministic_per_seed(engine):
    """Sampled speculative decode is deterministic per request seed and
    independent of co-batching — two runs with the same seeds agree."""
    rng = np.random.default_rng(21)
    p0, p1 = _rep_prompt(rng), rng.integers(0, 96, size=6).astype(np.int32)
    outs = []
    for _ in range(2):
        sched = _sched(engine, do_sample=True, temperature=1.0)
        h0 = sched.submit(p0, max_new_tokens=9, seed=7)
        h1 = sched.submit(p1, max_new_tokens=6, seed=11)
        sched.run()
        outs.append((h0.result(), h1.result()))
    np.testing.assert_array_equal(outs[0][0], outs[1][0])
    np.testing.assert_array_equal(outs[0][1], outs[1][1])


# ------------------------------------------------------- rollback edge cases
class _WrongProposer:
    """Adversarial draft: always proposes a token the verify argmax cannot
    match (deterministically wrong), forcing a rejection every round."""
    deterministic = True

    def propose(self, context, k):
        return np.full(k, (int(context[-1]) + 1) % TINY["vocab_size"],
                       np.int32)


def test_rejection_on_cow_boundary_page(engine):
    """A rejected verify window whose rows live on the COW'd boundary page of
    a prefix-cache hit: the rewind is a cache_len no-op (stale rows stay
    masked), the COW copy is not disturbed, and the stream stays bit-exact."""
    rng = np.random.default_rng(17)
    shared = rng.integers(0, 96, size=20).astype(np.int32)
    # 21-token prompt, page_size 8: a hit binds pages 0-1 shared and COWs
    # page 2 (rows 16..23); decode starts at row 21, so the first verify
    # windows land INSIDE the COW boundary page
    prompt = np.concatenate([shared,
                             rng.integers(0, 96, size=1).astype(np.int32)])
    ref = _ref(engine, prompt, 6)
    sched = _sched(engine, cache=True)
    sched.proposer = _WrongProposer()     # every round rejects at position 0
    h_warm = sched.submit(prompt, max_new_tokens=6)
    sched.run()
    np.testing.assert_array_equal(h_warm.result(), ref)
    h_hit = sched.submit(prompt, max_new_tokens=6)
    sched.run()
    assert h_hit.prefix_hit_tokens > 0                  # real cache hit
    assert sched.executor.pool.cow_copies_total >= 1    # real COW boundary
    snap = sched.telemetry.snapshot()
    assert snap["spec_accepted"] == 0                   # every round rejected
    assert snap["spec_proposed"] > 0
    np.testing.assert_array_equal(h_hit.result(), ref)


class _OracleProposer:
    """Drafts the TRUE greedy continuation (precomputed reference): every
    round is a full accept, so an EOS anywhere past the prefill token is
    guaranteed to land inside an accepted speculated block."""
    deterministic = True

    def __init__(self, full):
        self.full = np.asarray(full, np.int32)   # prompt + reference tokens

    def propose(self, context, k):
        t = int(np.asarray(context).size)
        return self.full[t:t + k]


def test_eos_inside_speculated_block(engine):
    """EOS emitted in the middle of an accepted block truncates the block at
    the EOS (inclusive) and finishes the request exactly like non-speculative
    decode with the same EOS."""
    rng = np.random.default_rng(31)   # seed picked for a non-constant stream
    prompt = rng.integers(0, 96, size=12).astype(np.int32)
    ref10 = _ref(engine, prompt, 10)
    # EOS must differ from the prefill token (ref10[0]) or the request ends
    # before any verify round; the first later token that differs works —
    # generate() and the scheduler both stop at its FIRST occurrence.
    eos = int(next(t for t in ref10[1:] if t != ref10[0]))
    ref = _ref(engine, prompt, 10, eos_token_id=eos)
    assert ref.size < 10                  # EOS really truncates the stream
    sched = _sched(engine)
    sched.proposer = _OracleProposer(np.concatenate([prompt, ref10]))
    h = sched.submit(prompt, max_new_tokens=10, eos_token_id=eos)
    sched.run()
    assert h.finish_reason == "eos" and h.tokens[-1] == eos
    np.testing.assert_array_equal(h.result(), ref)
    assert sched.telemetry.spec.accepted > 0   # the block path actually ran


def test_cap_edge_truncation_of_proposal_window(engine):
    """A request whose budget runs to the KV cap: near the edge the per-slot
    proposal window truncates (possibly to zero — a plain decode step through
    the same compiled shape) and the output still bit-matches the
    non-speculative stream all the way to the length finish."""
    rng = np.random.default_rng(33)
    max_new = 8
    prompt = np.tile(rng.integers(0, 96, size=4).astype(np.int32),
                     (CAP - max_new) // 4)          # prompt + max_new == CAP
    assert prompt.size + max_new == CAP
    ref = _ref(engine, prompt, max_new)
    sched = _sched(engine)
    h = sched.submit(prompt, max_new_tokens=max_new)
    sched.run()
    assert h.state == RequestState.FINISHED and h.finish_reason == "length"
    np.testing.assert_array_equal(h.result(), ref)


def test_spec_prefix_cache_hit_parity(engine):
    """Speculation x prefix-cache hit: the hit skips prefill, speculation
    accelerates decode, and the output is bit-identical to the cold miss and
    to non-speculative decode."""
    rng = np.random.default_rng(41)
    shared = rng.integers(0, 96, size=16).astype(np.int32)
    prompt = np.concatenate([shared, _rep_prompt(rng, unit=3, reps=2)])
    ref = _ref(engine, prompt, 8)
    sched = _sched(engine, cache=True)
    h_miss = sched.submit(prompt, max_new_tokens=8)
    sched.run()
    h_hit = sched.submit(prompt, max_new_tokens=8)
    sched.run()
    assert h_miss.prefix_hit_tokens == 0 and h_hit.prefix_hit_tokens > 0
    np.testing.assert_array_equal(h_miss.result(), ref)
    np.testing.assert_array_equal(h_hit.result(), ref)


# ------------------------------------------- router: retry / drain / migrate
def _router(engines, **over):
    serving = over.pop("serving", None) or ServingConfig(
        slots=2, chunk_size=3, max_seq_len=CAP, retry_base_delay=0.001,
        kv_page_size=8, speculate=True, spec_k=4,
        prefix_cache=PrefixCacheConfig(min_hit_tokens=4, min_insert_tokens=4,
                                       insert_on="prefill"))
    rcfg = RouterConfig(serving=serving, suspect_after_s=0.04,
                        dead_after_s=0.12, recover_after_s=30.0,
                        breaker_threshold=2, max_attempts=4,
                        retry_base_delay=0.001)
    for k, v in over.items():
        setattr(rcfg, k, v)
    return Router(engines, rcfg)


def test_retry_after_kill_spec(engines):
    """Mid-decode replica kill with speculation on: checkpointless retry
    re-derives identical drafts from the carried prefix (deterministic
    proposer), so the final stream is bit-identical, lost == 0."""
    import time
    router = _router(engines)
    rng = np.random.default_rng(19)
    p = _rep_prompt(rng, unit=4, reps=3)
    h = router.submit(p, max_new_tokens=12)
    victim = None
    t0 = time.monotonic()
    while not h.done and time.monotonic() - t0 < 60:
        if victim is None and h.inner is not None and len(h.inner.tokens) >= 2:
            victim = router.replicas[h.replica_id]
            victim.kill()
        router.step()
    assert h.state.value == "finished" and h.retried >= 1
    np.testing.assert_array_equal(h.result(), _ref(engines[0], p, 12))
    assert router.snapshot()["lost"] == 0


def test_drain_handoff_spec(engines):
    """Graceful drain with speculation on: hand-off specs continue bit-exactly
    on a fresh (also speculating) router."""
    router = _router(engines)
    rng = np.random.default_rng(23)
    ps = [_rep_prompt(rng, unit=3, reps=2),
          rng.integers(0, 96, size=4).astype(np.int32),
          _rep_prompt(rng, unit=4, reps=2)]
    hs = [router.submit(p, max_new_tokens=12) for p in ps]
    router.step()
    router.begin_drain()
    specs = router.drain()
    assert len(specs) == len(hs) and router.snapshot()["lost"] == 0
    router2 = _router(engines)
    hs2 = {s["id"]: router2.submit(np.asarray(s["prompt"], np.int32),
                                   max_new_tokens=s["max_new_tokens"])
           for s in specs}
    router2.run()
    for h, p in zip(hs, ps):
        h2 = hs2[h.id]
        assert h2.state.value == "finished"
        full = np.concatenate([h.result(), h2.result()])
        np.testing.assert_array_equal(full, _ref(engines[0], p, 12))


def test_autoscale_migration_spec(engines):
    """Scale-down retire mid-flight with speculation on: the migrated
    request's final stream is bit-identical, lost == 0."""
    import time
    router = _router(engines, retire_grace_s=0.05)
    rng = np.random.default_rng(29)
    p = _rep_prompt(rng, unit=4, reps=3, tail=2)
    h = router.submit(p, max_new_tokens=14)
    t0 = time.monotonic()
    retired = False
    while not h.done and time.monotonic() - t0 < 60:
        if not retired and h.inner is not None and len(h.inner.tokens) >= 2:
            router.begin_retire(h.replica_id)
            retired = True
        router.step()
    assert retired and h.state.value == "finished"
    np.testing.assert_array_equal(h.result(), _ref(engines[0], p, 14))
    assert router.snapshot()["lost"] == 0


def test_mid_verify_chaos_kill_bit_exact_retry(engines):
    """A fault injected at the ``serving.spec_verify`` seam exhausts one
    replica's retry budget mid-verify; the router's checkpointless retry
    finishes the request bit-exactly on a survivor, lost == 0."""
    import time
    fi.reset_faults()
    serving = ServingConfig(
        slots=2, chunk_size=3, max_seq_len=CAP, transient_retries=1,
        retry_base_delay=0.001, kv_page_size=8,
        speculate=True, spec_k=4)
    router = _router(engines, serving=serving)
    rng = np.random.default_rng(31)
    p = _rep_prompt(rng, unit=4, reps=3)
    # let two verify rounds commit, then fail the next dispatch twice —
    # exactly the per-replica budget (transient_retries=1 -> 2 attempts)
    with fi.inject("serving.spec_verify",
                   fi.FaultSpec(kind="io_error", after_n=2, max_faults=2)):
        h = router.submit(p, max_new_tokens=12)
        t0 = time.monotonic()
        while not h.done and time.monotonic() - t0 < 60:
            router.step()
    assert fi.faults_fired("serving.spec_verify") == 2
    assert h.state.value == "finished" and h.retried >= 1
    np.testing.assert_array_equal(h.result(), _ref(engines[0], p, 12))
    assert router.snapshot()["lost"] == 0
    fi.reset_faults()
