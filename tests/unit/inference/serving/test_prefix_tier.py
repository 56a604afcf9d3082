"""Tiered prefix cache (host-RAM rung) + fleet KV-economy routing tests:
device->host spill / host->device promote lifecycle, per-rung budget refusal,
promote-path bit-exact greedy parity, prefix-aware dispatch beating
affinity-only on a cold-replica trace, digest-gossip staleness tolerance, and
the mid-promote chaos kill (the restore->suffix-prefill window with the kill
landing between a host-rung restore and the suffix prefill).

The tier's contract mirrors the device rung's: slab rows are verbatim KV a
full prefill wrote, round-tripped through host numpy unchanged, so greedy
output is bit-identical across hit / promote / miss / retry.
"""

import time
import types

import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu as ds
from deepspeed_tpu.inference.engine import InferenceEngine
from deepspeed_tpu.inference.serving import (ChaosEvent, ChaosSchedule,
                                             ContinuousBatchingScheduler,
                                             PrefixCache, PrefixCacheConfig,
                                             Router, RouterConfig,
                                             ServingConfig)
from deepspeed_tpu.inference.serving.prefix_cache import (DIGEST_LADDER,
                                                          match_from_digests,
                                                          prefix_digest,
                                                          slab_bytes)
from deepspeed_tpu.models.causal_lm import gpt2_cfg

pytestmark = pytest.mark.prefix_cache

TINY = dict(vocab_size=96, max_seq_len=64, n_embd=32, n_layer=2, n_head=4,
            dtype=jnp.float32)
CAP = 48


@pytest.fixture(scope="module")
def engine():
    return InferenceEngine(gpt2_cfg(**TINY), ds.inference.DeepSpeedInferenceConfig(
        dtype="float32", max_out_tokens=CAP))


@pytest.fixture(scope="module")
def engines(engine):
    e1 = InferenceEngine(gpt2_cfg(**TINY), ds.inference.DeepSpeedInferenceConfig(
        dtype="float32", max_out_tokens=CAP), params=engine.params)
    return [engine, e1]


def _fake_slab(rows=8, hk=2, d=4, fill=1.0, layers=2):
    return [{"k": jnp.full((hk, rows, d), fill, jnp.float32),
             "v": jnp.full((hk, rows, d), -fill, jnp.float32)}
            for _ in range(layers)]


def _toks(*ids):
    return np.asarray(ids, np.int32)


def _tier_cfg(one, device_slabs=2, host_slabs=4, **over):
    kw = dict(max_bytes=device_slabs * one, host_tier_bytes=host_slabs * one,
              min_hit_tokens=1, min_insert_tokens=1)
    kw.update(over)
    return PrefixCacheConfig(**kw)


# ------------------------------------------------------- spill/promote lifecycle
def test_spill_on_eviction_and_promote_on_lookup():
    one = slab_bytes(_fake_slab())
    pc = PrefixCache(_tier_cfg(one, device_slabs=2))
    pa, pb, pc_ = _toks(1, 1, 1), _toks(2, 2, 2), _toks(3, 3, 3)
    pc.insert(pa, _fake_slab())
    pc.insert(pb, _fake_slab())
    pc.insert(pc_, _fake_slab())             # evicts LRU a -> spills to host
    assert pc.entries == 2 and pc.evicted == 1
    assert pc.spills == 1 and pc.host_entries == 1
    assert pc.host_bytes == one and pc.total_bytes == 2 * one
    # host-rung hit == promote: same matched depth, slab now host numpy
    m, e = pc.lookup(_toks(1, 1, 1, 9))
    assert m == 3 and e is not None
    assert e.pages is None and isinstance(e.slab[0]["k"], np.ndarray)
    assert pc.promotions == 1
    # device-rung hit is NOT a promote
    m, e = pc.lookup(_toks(2, 2, 2, 9))
    assert m == 3 and pc.promotions == 1
    # re-inserting the spilled path upgrades host -> device (no duplicate);
    # the upgrade displaces the device LRU (c), which spills in turn
    pc.insert(pa, _fake_slab())
    assert pc.lookup(_toks(1, 1, 1, 9))[0] == 3
    assert pc.promotions == 1                # pa is a device hit again
    assert pc.host_entries == 1 and pc.spills == 2
    s = pc.stats()
    for k in ("spills", "spill_skipped", "promotions", "host_evicted",
              "host_entries", "spilled_bytes", "host_max_bytes"):
        assert k in s


def test_clear_drops_both_rungs_drop_device_keeps_host():
    one = slab_bytes(_fake_slab())
    pc = PrefixCache(_tier_cfg(one, device_slabs=1))
    pc.insert(_toks(1, 1, 1), _fake_slab())
    pc.insert(_toks(2, 2, 2), _fake_slab())  # a spills
    assert pc.host_entries == 1 and pc.entries == 1
    # drop_device models a pool rebuild: device rung vanishes WITHOUT
    # spilling (the pool is poisoned), independent host slabs survive
    pc.drop_device()
    assert pc.entries == 0 and pc.total_bytes == 0
    assert pc.host_entries == 1
    assert pc.lookup(_toks(1, 1, 1, 9))[0] == 3      # promote still possible
    pc.clear()                               # process death: everything gone
    assert pc.host_entries == 0 and pc.host_bytes == 0
    assert pc.lookup(_toks(1, 1, 1, 9)) == (0, None)


# ------------------------------------------------------------- budget refusal
def test_tier_off_means_plain_drop():
    one = slab_bytes(_fake_slab())
    pc = PrefixCache(PrefixCacheConfig(max_bytes=one, host_tier_bytes=0,
                                       min_hit_tokens=1, min_insert_tokens=1))
    pc.insert(_toks(1, 1, 1), _fake_slab())
    pc.insert(_toks(2, 2, 2), _fake_slab())
    assert pc.evicted == 1 and pc.spills == 0 and pc.host_entries == 0
    assert pc.lookup(_toks(1, 1, 1, 9)) == (0, None)


def test_host_budget_refuses_oversized_slab_and_lru_evicts():
    one = slab_bytes(_fake_slab())
    # host rung smaller than one slab: the spill is refused, not truncated
    pc = PrefixCache(_tier_cfg(one, device_slabs=1, host_tier_bytes=one - 1))
    pc.insert(_toks(1, 1, 1), _fake_slab())
    pc.insert(_toks(2, 2, 2), _fake_slab())
    assert pc.spill_skipped == 1 and pc.host_entries == 0
    # host rung holding exactly one slab: the second spill LRU-drops the first
    pc2 = PrefixCache(_tier_cfg(one, device_slabs=1, host_slabs=1))
    pc2.insert(_toks(1, 1, 1), _fake_slab())
    pc2.insert(_toks(2, 2, 2), _fake_slab())     # a -> host
    pc2.insert(_toks(3, 3, 3), _fake_slab())     # b -> host, a host-evicted
    assert pc2.spills == 2 and pc2.host_evicted == 1
    assert pc2.host_entries == 1 and pc2.host_bytes == one
    assert pc2.lookup(_toks(1, 1, 1, 9)) == (0, None)
    assert pc2.lookup(_toks(2, 2, 2, 9))[0] == 3


def test_paged_entry_without_gather_hook_cannot_spill():
    one = slab_bytes(_fake_slab())
    pc = PrefixCache(_tier_cfg(one, device_slabs=1))
    released = []
    pc.page_release = released.append
    assert pc.page_gather is None
    assert pc.insert_pages(_toks(1, 1, 1), np.asarray([0, 1]), one)
    pc.insert(_toks(2, 2, 2), _fake_slab())
    # no dense copy exists to keep: the eviction falls back to a plain drop
    # (and still decrefs the pages through the owner's release hook)
    assert pc.spill_skipped == 1 and pc.host_entries == 0
    assert len(released) == 1


# --------------------------------------------------- promote greedy parity e2e
def _tiered_sched(engine, device_bytes, host_bytes=1 << 20, **over):
    kw = dict(slots=2, chunk_size=2, max_seq_len=CAP, retry_base_delay=0.001,
              kv_page_size=4,
              prefix_cache=PrefixCacheConfig(
                  max_bytes=device_bytes, host_tier_bytes=host_bytes,
                  min_hit_tokens=4, min_insert_tokens=4,
                  insert_on="prefill"))
    kw.update(over)
    return ContinuousBatchingScheduler(engine, ServingConfig(**kw))


def test_promote_hit_bit_exact_end_to_end(engine):
    """Evict -> spill -> promote on the real paged serving path: the promoted
    request's greedy stream must equal the cache-off per-request generate,
    token for token, and the tier counters must tell the truth."""
    rng = np.random.default_rng(31)
    shared = rng.integers(0, 96, size=16).astype(np.int32)
    other = rng.integers(0, 96, size=16).astype(np.int32)

    def p(base):
        return np.concatenate([base,
                               rng.integers(0, 96, size=4).astype(np.int32)])

    # 20-token prompt -> 5 pages * 4 rows * 512 B/row = 10 KiB; a 12 KiB
    # device budget holds exactly one entry, so the second insert evicts
    sched = _tiered_sched(engine, device_bytes=12 * 1024)
    pa = p(shared)
    h = sched.submit(pa, max_new_tokens=4)
    sched.run()
    assert h.prefix_hit_tokens == 0
    h = sched.submit(p(other), max_new_tokens=4)
    sched.run()
    pc = sched.prefix_cache
    assert pc.spills >= 1 and pc.host_entries >= 1
    # the spilled prefix now hits from the HOST rung: a promote restore
    pa2 = p(shared)
    h = sched.submit(pa2, max_new_tokens=6)
    sched.run()
    assert h.prefix_hit_tokens >= 16
    assert pc.promotions >= 1
    ref = np.asarray(engine.generate(pa2[None, :], max_new_tokens=6))
    np.testing.assert_array_equal(h.result(), ref[0, pa2.size:])
    rep = sched.prefix_cache_report()
    assert rep["spills"] >= 1 and rep["promotions"] >= 1
    assert rep["spilled_bytes"] > 0


# ---------------------------------------------- prefix-aware dispatch routing
def _router(engines, **over):
    serving = over.pop("serving", None) or ServingConfig(
        slots=2, chunk_size=3, max_seq_len=CAP, retry_base_delay=0.001,
        prefix_cache=PrefixCacheConfig(min_hit_tokens=4, min_insert_tokens=4,
                                       insert_on="prefill"))
    rcfg = RouterConfig(serving=serving, suspect_after_s=0.04,
                        dead_after_s=0.12, recover_after_s=30.0,
                        breaker_threshold=2, max_attempts=4,
                        retry_base_delay=0.001)
    for k, v in over.items():
        setattr(rcfg, k, v)
    return Router(engines, rcfg)


def _warm(router, prompt, n=1):
    r0 = router.replicas[0]
    for _ in range(n):
        h = r0.submit(prompt, max_new_tokens=2)
        while not h.done:
            r0.step()


def test_prefix_aware_beats_affinity_on_cold_replica(engines):
    """Many-tenant trace (no session locality): affinity-only dispatch
    scatters a shared prefix onto the cold replica; prefix-aware dispatch
    concentrates it on the replica whose cache holds it."""
    rng = np.random.default_rng(37)
    shared = rng.integers(0, 96, size=16).astype(np.int32)

    def prompt():
        return np.concatenate([shared,
                               rng.integers(0, 96, size=4).astype(np.int32)])

    # A: affinity-only (sessions unique -> pure least-outstanding): the
    # concurrent burst spreads, so the cold replica eats avoidable misses
    ra = _router(engines)
    _warm(ra, prompt())
    hs = [ra.submit(prompt(), max_new_tokens=3, session=f"t{i}")
          for i in range(3)]
    while any(not h.done for h in hs):
        ra.step()
    assert ra.replicas[1].scheduler.prefix_cache.misses >= 1
    assert any(h.prefix_hit_tokens == 0 for h in hs)

    # B: prefix-aware: the same burst (bounded by the holder's 2 slots so
    # capacity never forces a spill-over) routes every request to the warm
    # replica and hits
    rb = _router(engines, prefix_aware_routing=True,
                 prefix_route_load_weight=4.0)
    _warm(rb, prompt())
    hs = [rb.submit(prompt(), max_new_tokens=3, session=f"t{i}")
          for i in range(2)]
    while any(not h.done for h in hs):
        rb.step()
    assert all(h.prefix_hit_tokens > 0 for h in hs)
    assert all(h.replica_id == 0 for h in hs)
    assert rb.replicas[1].scheduler.prefix_cache.entries == 0
    assert rb.telemetry.prefix_routed >= 2
    assert rb.telemetry.prefix_saved_tokens >= 2 * 16
    snap = rb.snapshot()
    assert snap["kv_economy"]["enabled"]
    assert snap["kv_economy"]["fleet_hit_rate"] > 0


def test_load_weight_spills_over_when_holder_is_busy(engines):
    """The saved-vs-load tradeoff: with the default (stronger) load weight a
    deeply-queued cache holder loses to an idle cold replica — prefix-aware
    routing must not convoy everything onto one hot replica."""
    router = _router(engines, prefix_aware_routing=True,
                     prefix_route_load_weight=32.0)
    rng = np.random.default_rng(41)
    shared = rng.integers(0, 96, size=16).astype(np.int32)

    def prompt():
        return np.concatenate([shared,
                               rng.integers(0, 96, size=4).astype(np.int32)])

    _warm(router, prompt())
    # burst: the first request takes the warm replica; 16 saved tokens do not
    # outweigh 32 * 1 outstanding, so the second goes to the idle replica
    h0 = router.submit(prompt(), max_new_tokens=3, session="t0")
    h1 = router.submit(prompt(), max_new_tokens=3, session="t1")
    while not (h0.done and h1.done):
        router.step()
    assert h0.replica_id == 0 and h1.replica_id == 1


# ------------------------------------------------- gossip staleness tolerance
def test_match_from_digests_ladder():
    pc = PrefixCache(PrefixCacheConfig(min_hit_tokens=1, min_insert_tokens=1))
    rng = np.random.default_rng(43)
    prefix = rng.integers(0, 96, size=40).astype(np.int32)
    pc.insert(prefix, _fake_slab(rows=40))
    digests = pc.digest_report()
    # ladder points <= 40 are advertised (16 and 32)
    assert prefix_digest(prefix, 16) in digests
    assert prefix_digest(prefix, 32) in digests
    # deepest shared ladder point, capped at len(prompt)-1
    probe = np.concatenate([prefix, _toks(1, 2)])
    assert match_from_digests(probe, digests) == 32
    assert match_from_digests(prefix[:17], digests) == 16
    assert match_from_digests(prefix[:16], digests) == 0    # usable = 15
    cold = rng.integers(0, 96, size=40).astype(np.int32)
    assert match_from_digests(cold, digests) == 0
    # stale/absent/garbage gossip degrades to 0, never raises
    assert match_from_digests(probe, None) == 0
    assert match_from_digests(probe, []) == 0
    assert match_from_digests(probe, ["junk", "16:feedface"]) == 0
    assert set(DIGEST_LADDER) == {16, 32, 64, 128, 256, 512}


def test_expected_saved_tolerates_bad_heartbeats(engines):
    """The router's dispatch probe must degrade to 0 on absent, stale-empty,
    or garbage gossip — a malformed heartbeat field can cost routing quality
    but never an exception on the submit path."""
    router = _router(engines, prefix_aware_routing=True)
    prompt = np.arange(20, dtype=np.int32)

    def hosted_stub(hb):
        # hosted replicas have no in-process prefix cache; the probe falls
        # through to the heartbeat's gossiped digests
        return types.SimpleNamespace(
            scheduler=types.SimpleNamespace(prefix_cache=None), hb=hb)

    assert router._expected_saved(hosted_stub(None), prompt) == 0
    assert router._expected_saved(hosted_stub("garbage"), prompt) == 0
    assert router._expected_saved(hosted_stub({}), prompt) == 0
    assert router._expected_saved(hosted_stub({"cache": None}), prompt) == 0
    assert router._expected_saved(hosted_stub({"cache": "bogus"}), prompt) == 0
    assert router._expected_saved(
        hosted_stub({"cache": {"digests": ["junk"]}}), prompt) == 0
    # and a genuine digest advertises real savings
    good = {"cache": {"digests": [prefix_digest(prompt, 16)]}}
    assert router._expected_saved(hosted_stub(good), prompt) == 16
    # in-process probe: a broken peek degrades to 0 the same way
    broken = types.SimpleNamespace(scheduler=types.SimpleNamespace(
        prefix_cache=types.SimpleNamespace(
            peek=lambda p: (_ for _ in ()).throw(RuntimeError("boom")))))
    assert router._expected_saved(broken, prompt) == 0


# --------------------------------------------------------- mid-promote chaos
def test_chaos_kill_mid_promote_retry_parity(engines):
    """`kill:when=restore` against a HOST-rung promote: the kill lands between
    the host->device restore and the suffix prefill. The retry must land on
    the survivor and finish bit-exact, lost == 0 — the donation-consumed
    restore must never leak a half-promoted slot into the stream."""
    serving = ServingConfig(
        slots=2, chunk_size=2, max_seq_len=CAP, retry_base_delay=0.001,
        kv_page_size=4,
        prefix_cache=PrefixCacheConfig(
            max_bytes=12 * 1024, host_tier_bytes=1 << 20,
            min_hit_tokens=4, min_insert_tokens=4, insert_on="prefill"))
    router = _router(engines, serving=serving)
    rng = np.random.default_rng(47)
    shared = rng.integers(0, 96, size=16).astype(np.int32)
    other = rng.integers(0, 96, size=16).astype(np.int32)

    def p(base):
        return np.concatenate([base,
                               rng.integers(0, 96, size=4).astype(np.int32)])

    # pin a session so the churn all lands on one replica: insert A, then B
    # (the 12 KiB device rung holds one ~10 KiB entry, so A spills to host)
    for base in (shared, other):
        h = router.submit(p(base), max_new_tokens=2, session="s")
        while not h.done:
            router.step()
    pinned = router._affinity["s"]
    pc = router.replicas[pinned].scheduler.prefix_cache
    assert pc.spills >= 1 and pc.host_entries >= 1
    # arm the restore-kill on the pinned replica; the next same-session
    # request hits the HOST rung, so the consumed hook fires mid-promote
    chaos = ChaosSchedule([ChaosEvent(kind="kill", replica=pinned,
                                      when="restore")])
    pa = p(shared)
    h = router.submit(pa, max_new_tokens=6, session="s")
    t0 = time.monotonic()
    while not h.done and time.monotonic() - t0 < 60:
        chaos.poll(router)
        router.step()
    assert chaos.exhausted, "restore-kill never fired (no promote admission)"
    assert pc.promotions >= 1
    assert h.state.value == "finished" and h.retried >= 1
    ref = np.asarray(engines[0].generate(pa[None, :], max_new_tokens=6))
    np.testing.assert_array_equal(h.result(), ref[0, pa.size:])
    snap = router.snapshot()
    assert snap["lost"] == 0
    assert snap["prefix_cache"]["spills"] >= 1
    assert snap["kv_economy"]["spills_total"] >= 1
