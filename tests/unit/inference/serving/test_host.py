"""Process-parallel replica host tests: the subproc protocol hardening
(versioned hello, malformed-line quarantine, stop escalation ladder), the
HostedReplica router membership surface, the ReplicaSupervisor's
bounded-backoff restart semantics (storm -> budget exhaustion -> pinned DEAD
with survivors serving), chaos ``sig=`` grammar, the hosted /statusz +
ds-tpu-top surfaces, and ONE real end-to-end lane: two jax children behind the
router, a real SIGKILL mid-decode, supervised respawn, and bit-exact retry
parity against a parent-side reference engine (the determinism contract).

Protocol/supervision lanes run against STUB children (``cmd_override`` — a
python one-liner, no jax import) so the storm/ladder timing is fast and
deterministic; only the flagship lane pays real child boots.
"""

import json
import os
import sys
import time

import numpy as np
import pytest

from deepspeed_tpu.inference.serving import (ChaosSchedule, EngineReplica,
                                             HostConfig, HostedReplica,
                                             QueueFullError, ReplicaState,
                                             ReplicaSupervisor, Router,
                                             RouterConfig, ServingConfig,
                                             SupervisorConfig, parse_chaos)
from deepspeed_tpu.inference.serving.subproc import (PROTO_VERSION,
                                                     HostProtocolError,
                                                     SubprocessReplica)

pytestmark = pytest.mark.serving_host

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))))))

HELLO = json.dumps({"ready": True, "proto": PROTO_VERSION, "pid": 0,
                    "faults_armed": 0, "cap": 48, "max_prompt_len": 47,
                    "slots": 2})


def stub_cmd(body: str) -> list:
    """A child argv that speaks just enough protocol for parent-side lanes —
    no jax import, so these tests run in milliseconds."""
    return [sys.executable, "-c", body]


SLEEPER = stub_cmd(
    f"import sys, time; print('{HELLO}'); sys.stdout.flush(); time.sleep(600)")
TERM_IGNORER = stub_cmd(
    "import signal, sys, time; signal.signal(signal.SIGTERM, signal.SIG_IGN);"
    f" print('{HELLO}'); sys.stdout.flush(); time.sleep(600)")
INSTANT_EXIT = stub_cmd(f"print('{HELLO}')")


# ------------------------------------------------------------ chaos grammar
def test_chaos_sig_grammar():
    evs = parse_chaos("kill:replica=1,sig=TERM,when=busy;"
                      "kill:replica=2,sig=kill,at=1.0")
    assert [e.sig for e in evs] == ["TERM", "KILL"]
    with pytest.raises(ValueError, match="unknown kill signal"):
        parse_chaos("kill:replica=1,sig=HUP,at=1")
    with pytest.raises(ValueError, match="kill-only"):
        parse_chaos("stall:replica=1,sig=KILL,when=busy")
    with pytest.raises(ValueError, match="kill-only"):
        parse_chaos("revive:replica=1,sig=TERM,at=1")


def test_chaos_sig_ignored_for_in_process_replicas(monkeypatch):
    """sig= on an in-process replica keeps flag semantics (no real signal)."""
    calls = []

    class FakeReplica:
        id = 0
        running = 1

        class scheduler:
            class executor:
                chunk_warm = True

        def kill(self):
            calls.append("flag-kill")

    class FakeRouter:
        replicas = [FakeReplica()]

        def replica_by_id(self, rid):
            return self.replicas[0]

    chaos = ChaosSchedule(parse_chaos("kill:replica=0,sig=TERM,when=busy"))
    chaos.poll(FakeRouter())
    assert calls == ["flag-kill"]


# --------------------------------------------------- protocol: versioned hello
def test_hello_version_mismatch_raises():
    bad_hello = json.dumps({"ready": True, "proto": 99})
    rep = SubprocessReplica(REPO, cmd=stub_cmd(
        f"import sys, time; print('{bad_hello}'); sys.stdout.flush(); "
        "time.sleep(30)"))
    try:
        with pytest.raises(HostProtocolError, match="proto=99"):
            rep.wait_ready(timeout=30)
    finally:
        rep.stop(drain_s=0.2, term_s=0.2)


def test_hello_missing_proto_raises():
    legacy = json.dumps({"ready": True, "pid": 1})
    rep = SubprocessReplica(REPO, cmd=stub_cmd(
        f"import sys, time; print('{legacy}'); sys.stdout.flush(); "
        "time.sleep(30)"))
    try:
        with pytest.raises(HostProtocolError):
            rep.wait_ready(timeout=30)
    finally:
        rep.stop(drain_s=0.2, term_s=0.2)


# ------------------------------------------- protocol: malformed-line quarantine
def test_malformed_child_lines_quarantined_not_fatal():
    """Garbage on the child's stdout is counted + sampled; the hello after it
    still lands and the parent never crashes."""
    rep = SubprocessReplica(REPO, cmd=stub_cmd(
        "import sys, time;"
        "print('this is not json {{');"
        f"print('{HELLO}');"
        "print('more garbage ]]');"
        "sys.stdout.flush(); time.sleep(30)"))
    try:
        ready = rep.wait_ready(timeout=30)
        assert ready["proto"] == PROTO_VERSION
        t0 = time.monotonic()
        while rep.quarantined < 2 and time.monotonic() - t0 < 10:
            time.sleep(0.02)
        assert rep.quarantined == 2
        assert rep.quarantined_sample is not None
    finally:
        rep.stop(drain_s=0.2, term_s=0.2)


# ----------------------------------------------- protocol: stop escalation
def test_stop_escalates_to_sigterm_on_wedged_child():
    """A child that ignores its stdin (never drains) used to hang stop() for
    60s; the ladder now climbs to SIGTERM inside the drain deadline."""
    rep = SubprocessReplica(REPO, cmd=SLEEPER)
    rep.wait_ready(timeout=30)
    t0 = time.monotonic()
    rc = rep.stop(drain_s=0.3, term_s=5.0)
    assert time.monotonic() - t0 < 5.0
    assert rc == -15                      # died at the SIGTERM rung
    assert rep.escalations == 1


def test_stop_escalates_to_sigkill_on_term_immune_child():
    """SIGTERM-immune (or SIGSTOPped) children force the SIGKILL backstop."""
    rep = SubprocessReplica(REPO, cmd=TERM_IGNORER)
    rep.wait_ready(timeout=30)
    t0 = time.monotonic()
    rc = rep.stop(drain_s=0.3, term_s=0.3)
    assert time.monotonic() - t0 < 10.0
    assert rc == -9                       # SIGKILL rung
    assert rep.escalations == 2


def test_stop_on_sigstopped_child_terminates():
    """The regression the satellite names: a wedged (stopped) child must not
    hang the caller — SIGTERM cannot deliver while stopped, SIGKILL can."""
    import signal as _signal
    rep = SubprocessReplica(REPO, cmd=SLEEPER)
    rep.wait_ready(timeout=30)
    os.kill(rep.proc.pid, _signal.SIGSTOP)
    t0 = time.monotonic()
    rc = rep.stop(drain_s=0.3, term_s=0.3)
    assert time.monotonic() - t0 < 10.0
    assert rc == -9
    assert rep.escalations == 2


# --------------------------------------------------------- supervisor storm
def _stub_host(cmd, **cfg):
    return HostedReplica(HostConfig(repo_root=REPO, cmd_override=cmd,
                                    stop_drain_s=0.2, stop_term_s=0.2, **cfg))


@pytest.fixture(scope="module")
def survivor_engine():
    """One in-process survivor engine shared by the supervisor/statusz lanes
    (tier-1 window reclaim: three engine builds + XLA warms collapsed into
    one; every consumer drives disjoint requests or none at all)."""
    import jax.numpy as jnp

    import deepspeed_tpu as ds
    from deepspeed_tpu.inference.engine import InferenceEngine
    from deepspeed_tpu.models.causal_lm import gpt2_cfg
    return InferenceEngine(
        gpt2_cfg(vocab_size=96, max_seq_len=48, n_embd=32, n_layer=2,
                 n_head=4, dtype=jnp.float32),
        ds.inference.DeepSpeedInferenceConfig(dtype="float32",
                                              max_out_tokens=48))


def test_supervisor_restart_storm_budget_and_survivors(survivor_engine):
    """The restart-storm lane: a host whose child dies instantly respawns
    with GROWING backoff until the budget exhausts and the replica pins DEAD
    — while the router keeps serving every request on the in-process
    survivor, lost == 0."""
    engine = survivor_engine
    host = _stub_host(INSTANT_EXIT)
    rcfg = RouterConfig(
        serving=ServingConfig(slots=2, chunk_size=3, max_seq_len=48,
                              retry_base_delay=0.001),
        suspect_after_s=0.04, dead_after_s=0.12, recover_after_s=0.1,
        max_attempts=4)
    router = Router([engine, host], rcfg)
    sup = ReplicaSupervisor(router, SupervisorConfig(max_restarts=2,
                                                     backoff_base_s=0.05))
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, 96, size=5).astype(np.int32) for _ in range(4)]
    handles = [router.submit(p, max_new_tokens=5) for p in prompts]
    t0 = time.monotonic()
    while time.monotonic() - t0 < 30:
        sup.step()
        router.step()
        if not router.busy and sup.state[1].pinned:
            break
    st = sup.state[1]
    assert st.pinned and 1 in sup.pinned
    assert st.restarts == 2 == sup.restarts_total
    # exponential: each wait doubles the previous
    assert st.backoffs == sorted(st.backoffs)
    assert len(st.backoffs) >= 2 and st.backoffs[1] == 2 * st.backoffs[0]
    assert router.replica_state(1) == ReplicaState.DEAD
    # a pinned replica stays dead: no further respawns on later sweeps
    sup.step()
    assert sup.restarts_total == 2
    # the survivor served everything
    assert all(h.state.value == "finished" for h in handles)
    assert router.snapshot()["lost"] == 0
    ref = engine.generate(prompts[0][None, :], max_new_tokens=5)
    np.testing.assert_array_equal(handles[0].result(),
                                  np.asarray(ref)[0, prompts[0].size:])
    host.close()


def test_supervisor_report_and_statusz_top_surfaces(survivor_engine):
    """/statusz carries child pid + restart count per hosted replica and the
    supervisor block; ds-tpu-top renders both."""
    from deepspeed_tpu.inference.serving.server import make_status_provider
    from deepspeed_tpu.observability.top import render
    engine = survivor_engine
    host = _stub_host(SLEEPER)
    host.wait_ready()
    router = Router([engine, host], RouterConfig(
        serving=ServingConfig(slots=2, chunk_size=3, max_seq_len=48)))
    sup = ReplicaSupervisor(router)
    sup.step()
    doc = make_status_provider(router, supervisor=sup)()
    hosted_row = [r for r in doc["replicas"] if r["id"] == 1][0]
    assert hosted_row["pid"] == host.child_pid
    assert hosted_row["restarts"] == 0
    assert "pid" not in [r for r in doc["replicas"] if r["id"] == 0][0]
    assert doc["hosts"]["restarts_total"] == 0
    frame = render(doc)
    assert f"pid={host.child_pid}" in frame
    assert "hosts: restarts=0" in frame
    host.close()


def test_detach_closes_hosted_child(survivor_engine):
    """Retiring a hosted replica must not leak its child process."""
    engine = survivor_engine
    host = _stub_host(SLEEPER)
    host.wait_ready()
    router = Router([engine, host], RouterConfig(
        serving=ServingConfig(slots=2, chunk_size=3, max_seq_len=48)))
    assert host.alive
    router.begin_retire(1, grace_s=0.5)
    t0 = time.monotonic()
    while 1 not in router.retired and time.monotonic() - t0 < 10:
        router.step()
    assert 1 in router.retired
    t0 = time.monotonic()
    while host._rep.proc.poll() is None and time.monotonic() - t0 < 10:
        time.sleep(0.02)
    assert host._rep.proc.poll() is not None


# ------------------------------------------------------------ flagship lane
@pytest.fixture(scope="module")
def live_hosts():
    """Two REAL jax children (boot cost paid once for the module)."""
    cfg = HostConfig(vocab_size=96, max_seq_len=64, n_embd=32, n_layer=2,
                     n_head=4, slots=2, chunk_size=2, repo_root=REPO)
    hosts = [HostedReplica(cfg) for _ in range(2)]
    for h in hosts:
        h.wait_ready(timeout=300)
    yield hosts
    for h in hosts:
        h.close()


def test_hosted_router_sigkill_respawn_parity(live_hosts):
    """The end-to-end acceptance in one lane: real children behind the
    router, heartbeats/hb metadata flowing, a garbage line quarantined by the
    child mid-run, a real SIGKILL mid-decode via the chaos sig grammar, the
    supervisor respawning the child, every request completing with lost == 0
    and the retried ones bit-identical to the parent reference engine."""
    hosts = live_hosts
    rcfg = RouterConfig(suspect_after_s=0.5, dead_after_s=1.5,
                        recover_after_s=0.3, max_attempts=4)
    router = Router(hosts, rcfg)
    sup = ReplicaSupervisor(router, SupervisorConfig(max_restarts=3,
                                                     backoff_base_s=0.2))
    chaos = ChaosSchedule(parse_chaos("kill:replica=1,sig=KILL,when=busy"))
    # a malformed parent->child line is quarantined by the child, not fatal
    hosts[0]._rep.proc.stdin.write("NOT JSON AT ALL {{\n")
    hosts[0]._rep.proc.stdin.flush()
    rng = np.random.default_rng(9)
    reqs = [(rng.integers(0, 96, size=5).astype(np.int32), 12)
            for _ in range(8)]
    handles, pending = [], list(reqs)
    t0 = time.monotonic()
    while (pending or router.busy) and time.monotonic() - t0 < 180:
        chaos.poll(router)
        sup.step()
        while pending:
            p, m = pending[0]
            try:
                handles.append(router.submit(p, max_new_tokens=m))
                pending.pop(0)
            except QueueFullError:
                break
        router.step()
    assert chaos.exhausted, "the SIGKILL never fired"
    assert all(h.state.value == "finished" for h in handles)
    assert router.snapshot()["lost"] == 0
    retried = sum(h.retried for h in handles)
    assert retried >= 1
    ref = hosts[0].engine            # lazily-built parent twin (determinism)
    for h, (p, m) in zip(handles, reqs):
        np.testing.assert_array_equal(
            h.result(),
            np.asarray(ref.generate(p[None, :], max_new_tokens=m))[0, p.size:])
    # heartbeat metadata flowed (rss for the supervisor's telemetry sweep)
    hb = hosts[0].hb
    assert hb is not None and hb.get("rss_bytes", 0) > 0
    assert hosts[0].pipe_lag_ms() is not None
    # the child-side quarantine registered and did not kill the replica
    t1 = time.monotonic()
    while hosts[0]._rep.child_quarantined < 1 and time.monotonic() - t1 < 10:
        time.sleep(0.02)
    assert hosts[0]._rep.child_quarantined >= 1
    # the supervisor respawned the killed child; drive it back through the
    # RECOVERING warm probe with an overflow burst and require LIVE
    t1 = time.monotonic()
    probes = []
    while time.monotonic() - t1 < 120:
        sup.step()
        router.step()
        if router.replica_state(1) == ReplicaState.LIVE:
            break
        # offer probe traffic only once the respawned child can actually take
        # one (hello landed, slots free): probes offered during its boot
        # window just drain into the healthy replica and starve the half-open
        # slot
        r1 = router.replica_by_id(1)
        if (router.replica_state(1) == ReplicaState.RECOVERING
                and r1 is not None and r1.available > 0
                and router.queue_depth == 0 and len(probes) < 64):
            for _ in range(4):
                try:
                    probes.append(router.submit(
                        rng.integers(0, 96, size=4).astype(np.int32),
                        max_new_tokens=4))
                except QueueFullError:
                    break
    assert sup.restarts_total >= 1
    assert router.replica_state(1) == ReplicaState.LIVE
    t1 = time.monotonic()
    while router.busy and time.monotonic() - t1 < 60:
        router.step()
    assert all(h.state.value == "finished" for h in probes)
    assert router.snapshot()["lost"] == 0


def test_hosted_stall_is_real_sigstop(live_hosts):
    """Chaos stall against a hosted replica SIGSTOPs the child: heartbeats go
    silent, the pipe-silence watchdog ages it to SUSPECT, and SIGCONT brings
    it back to LIVE."""
    hosts = live_hosts
    rcfg = RouterConfig(suspect_after_s=0.2, dead_after_s=5.0)
    router = Router(hosts, rcfg)
    chaos = ChaosSchedule(parse_chaos("stall:replica=0,at=0.0,s=0.8"))
    chaos.poll(router)
    assert chaos.exhausted
    saw_suspect = False
    t0 = time.monotonic()
    while time.monotonic() - t0 < 10:
        router.step()
        if router.replica_state(0) == ReplicaState.SUSPECT:
            saw_suspect = True
        if saw_suspect and router.replica_state(0) == ReplicaState.LIVE:
            break
        time.sleep(0.01)
    assert saw_suspect, "SIGSTOP silence never aged the replica"
    assert router.replica_state(0) == ReplicaState.LIVE, \
        "SIGCONT did not bring the replica back"
