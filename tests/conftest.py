"""Test harness configuration.

The analogue of the reference's ``tests/unit/common.py`` distributed harness: where DeepSpeed
spawns N torch.multiprocessing workers with real NCCL over localhost (``common.py:87
DistributedExec``), the TPU framework runs multi-device tests single-process on a virtual
8-device CPU mesh (``xla_force_host_platform_device_count``) — XLA's deterministic compilation
makes this a faithful stand-in for sharding/collective semantics (SURVEY §4 'Implication').
"""

import os

# XLA_FLAGS must be set before the CPU backend initialises (backends initialise lazily, at
# first use, so setting it here — before any test touches a device — takes effect).
if "--xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                               " --xla_force_host_platform_device_count=8")
os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402
import pytest  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_threefry_partitionable", True)


# --------------------------------------------------------------- tier-1 budget
#: Wall-clock budget (seconds, CPU-host on the 8-device virtual mesh) per
#: ordered tier-1 lane inside the 870 s window (``timeout -k 10 870`` in
#: ROADMAP.md's tier-1 command). The collection ORDER is part of the
#: contract: lanes run strictly in rank order so an overrunning late lane
#: loses its OWN tail to the timeout, never an established earlier lane's.
#: Budgets are documented ceilings, not per-test enforcement — what is
#: enforced is (a) the table summing inside the window (checked at configure
#: time, so a new lane must take its budget from somewhere visible) and
#: (b) the collection order actually being rank-monotone
#: (``pytest_collection_finish`` below fails drift loudly).
TIER1_BUDGETS_S = {
    0: ("fault_tolerance", 120),   # subprocess SIGKILL rings + ckpt rewind
    1: ("observability", 40),      # pure-host tracing/metrics lane
    2: ("analysis", 70),           # contract passes over the real programs
    3: ("serving_family", 370),    # serving + router + prefix_cache + paged_kv
    #     + autoscale + host + net + speculative + prefix_tier: the
    #     compiled-dispatch block. PR 19's tiered-cache lane
    #     (test_prefix_tier.py, ~25 s) rides inside this share — paid for by
    #     demoting the duplicate plain-loadgen smoke to ``slow`` (the loadgen
    #     entry path stays covered by the slow bench smokes and the prefix/
    #     paged lanes' in-process run_load calls). PR 20 takes 60 s of this
    #     share for the qring lane — the family ran ~340 s at PR-19 HEAD, so
    #     the headroom was real, and the ring lanes are the suite's newest
    #     unvetted compile load.
    4: ("comm_overlap", 90),       # chunked-collective parity + bench smoke
    5: ("qring", 60),              # fused quantized ring: parity + EF + bytes
    6: ("weight_quant", 70),       # int4/int8 pack + fused-dequant parity
    7: ("unranked", 50),           # models, runtime units, everything else
}
TIER1_WINDOW_S = 870


def _tier1_rank(it) -> int:
    """Collection rank of one test item (lower runs earlier); the key both
    ``pytest_collection_modifyitems`` sorts by and the drift check audits."""
    if "test_fault_tolerance" in it.nodeid:
        return 0
    if it.get_closest_marker("observability") is not None:
        return 1                # fast lane: whole suite runs in seconds
    if it.get_closest_marker("analysis") is not None:
        return 2                # contract passes over the real programs
    if "inference/serving" in it.nodeid \
            or it.get_closest_marker("serving_router") is not None \
            or it.get_closest_marker("prefix_cache") is not None \
            or it.get_closest_marker("paged_kv") is not None \
            or it.get_closest_marker("serving_autoscale") is not None \
            or it.get_closest_marker("serving_host") is not None \
            or it.get_closest_marker("speculative") is not None:
        return 3
    if it.get_closest_marker("comm_overlap") is not None:
        return 4
    if it.get_closest_marker("qring") is not None:
        return 5
    if it.get_closest_marker("weight_quant") is not None:
        return 6
    return 7


def pytest_configure(config):
    total = sum(s for _, s in TIER1_BUDGETS_S.values())
    if total > TIER1_WINDOW_S:
        raise pytest.UsageError(
            f"tier-1 lane budgets sum to {total}s > the {TIER1_WINDOW_S}s "
            "window — a new lane must take its budget from an existing one "
            "(edit TIER1_BUDGETS_S in tests/conftest.py)")
    config.addinivalue_line(
        "markers", "slow: long-running convergence/perf lanes "
        "(deselect with -m 'not slow')")
    config.addinivalue_line(
        "markers", "serving: continuous-batching serving lane (scheduler, "
        "KV slot pool, chunked decode, loadgen smoke) — tier-1 fast lane")
    config.addinivalue_line(
        "markers", "serving_router: multi-replica router lane (health state "
        "machine, checkpointless retry, drain, chaos soak smoke) — tier-1 "
        "fast lane")
    config.addinivalue_line(
        "markers", "comm_overlap: comm-compute overlap parity lane (chunked "
        "collective matmuls, quantized allreduce) — tier-1 fast lane")
    config.addinivalue_line(
        "markers", "qring: fused quantized collective-matmul ring lane "
        "(fp-wire last-ulp parity vs monolithic psum, intN wire error "
        "bounds, EF-across-ring-steps convergence, overflow gate, "
        "chunk_bits sweep + byte crosscheck) — tier-1 fast lane")
    config.addinivalue_line(
        "markers", "weight_quant: weight-streaming quantized decode lane "
        "(int4 packing, fused dequant-matmul parity, audit) — tier-1 fast "
        "lane")
    config.addinivalue_line(
        "markers", "prefix_cache: radix prompt-prefix KV cache lane (trie "
        "semantics, LRU eviction, suffix prefill, hit-vs-miss greedy parity, "
        "restore-boundary chaos, subprocess SIGKILL retry) — tier-1 fast lane")
    config.addinivalue_line(
        "markers", "observability: tracing/metrics/profiler lane (span "
        "nesting + Perfetto schema, cross-process trace join, histogram "
        "percentiles, /metrics exposition, tag-schema lint, loadgen "
        "--trace-out) — tier-1 fast lane")
    config.addinivalue_line(
        "markers", "analysis: program-contract analyzer lane (donation "
        "audit, retrace lint, host-sync detector, loop-invariance pin, "
        "collective-schema cross-check, AST rules, ds-tpu-lint JSON smoke) "
        "— tier-1 fast lane")
    config.addinivalue_line(
        "markers", "paged_kv: paged KV memory lane (page allocator, refcount "
        "+ copy-on-write lifecycle, paged-attention kernel-vs-XLA parity, "
        "hit/miss/retry/drain/migration bit-exactness, page-bind chaos "
        "kill) — tier-1 fast lane")
    config.addinivalue_line(
        "markers", "serving_autoscale: elastic control plane lane "
        "(autoscaler scale-up/down, hysteresis, SLO admission shed-vs-"
        "expire, degradation ladder, drain-parity on scale-down, "
        "chaos-during-scale, loadgen schedule smoke) — tier-1 fast lane")
    config.addinivalue_line(
        "markers", "serving_host: process-parallel replica hosts lane "
        "(subproc protocol hello/quarantine/stop-ladder, HostedReplica "
        "router membership, ReplicaSupervisor restart storm + budget, "
        "chaos sig= grammar, real SIGKILL+respawn parity) — tier-1 fast "
        "lane")
    config.addinivalue_line(
        "markers", "serving_net: socket replica transport lane (frame codec "
        "roundtrip + CRC quarantine/resync, versioned hello + session "
        "resume, sever-evict-redial parity, net:* chaos grammar, partition/"
        "delay soak over real TCP children) — tier-1 fast lane")
    config.addinivalue_line(
        "markers", "speculative: speculative decoding lane (n-gram/draft "
        "proposers, one-pass verify, greedy bit-identity across hit/miss/"
        "retry/drain/migration, rejection-sampling exactness, rollback edge "
        "cases) — tier-1 fast lane")


def pytest_collection_modifyitems(config, items):
    """The fault-tolerance, serving, comm-overlap, and weight-quant lanes must
    land inside tier-1's wall-clock budget — the full suite can overrun it on
    CPU, and all of them sort late alphabetically ('tests/unit/runtime',
    'tests/unit/inference/serving', 'tests/unit/parallel',
    'tests/unit/ops/test_weight_quant'). Run lanes in ``_tier1_rank`` order
    (budgets: ``TIER1_BUDGETS_S``); relative order within a rank is
    unchanged."""
    if any(_tier1_rank(it) < 7 for it in items):
        items.sort(key=_tier1_rank)  # stable: preserves order within a rank


def pytest_collection_finish(session):
    """Fail collection-order drift LOUDLY: after every plugin has had its say,
    the final item order must still be rank-monotone — otherwise a reordering
    plugin (or a sort that silently stopped firing) would push an established
    lane past the tier-1 timeout and the first symptom would be a flaky
    timeout kill, not an explanation. (Run tier-1 with ``-p no:randomly``;
    this check is what turns a violation into a one-line diagnosis.)"""
    ranks = [_tier1_rank(it) for it in session.items]
    for i in range(1, len(ranks)):
        if ranks[i] < ranks[i - 1]:
            lane = TIER1_BUDGETS_S[ranks[i]][0]
            prev = TIER1_BUDGETS_S[ranks[i - 1]][0]
            raise pytest.UsageError(
                f"tier-1 collection-order drift: {session.items[i].nodeid} "
                f"(lane {lane!r}, rank {ranks[i]}) collected after "
                f"{session.items[i - 1].nodeid} (lane {prev!r}, rank "
                f"{ranks[i - 1]}) — lanes must run in TIER1_BUDGETS_S order "
                "or the window budget in tests/conftest.py is meaningless")


@pytest.fixture(autouse=True)
def _reset_global_mesh():
    """Tests that activate a mesh (engines, shard_map paths) must not leak it into
    later tests — the global mesh is process state, like the reference's cached process
    groups (``groups.py``)."""
    yield
    from deepspeed_tpu.parallel.mesh import set_global_mesh
    set_global_mesh(None)


@pytest.fixture
def eight_devices():
    devs = jax.devices()
    if len(devs) < 8:
        pytest.skip("needs 8 virtual devices")
    return devs[:8]


@pytest.fixture
def tmp_ckpt_dir(tmp_path):
    return str(tmp_path / "ckpt")
