"""Socket replica transport tests: the frame codec (length-prefix + CRC,
malformed-frame quarantine/resync), the child-side :class:`ChildSocketIO`
session contract (versioned hello, session-token resume vs fresh, badline
refusal on proto drift, per-hello ``cancel_all``), the parent-side
:class:`SocketReplicaLink` reconnect machine (sever -> bounded-backoff redial
-> resume), write-side backpressure, the ``net:`` chaos grammar, and the real
end-to-end lanes: a 3-replica framed-TCP fleet surviving a partition + delay
+ real SIGKILL storm with lost == 0 and bit-exact retry parity, plus the
respawn-vs-redial split (a dead CHILD respawns, a dead CONNECTION redials).

Codec/protocol lanes run against in-process :class:`ChildSocketIO` instances
(no jax import, no child boot) so they run in milliseconds; only the fleet
lanes pay real child boots — once, through a module-scoped fixture.
"""

import json
import os
import socket
import struct
import threading
import time
import zlib

import numpy as np
import pytest

from deepspeed_tpu.inference.serving import (ChaosSchedule, FrameDecoder,
                                             HostConfig, NetConfig,
                                             QueueFullError, ReplicaState,
                                             ReplicaSupervisor, Router,
                                             RouterConfig, SocketHostedReplica,
                                             SocketReplicaLink,
                                             SupervisorConfig, encode_frame,
                                             parse_chaos)
from deepspeed_tpu.inference.serving.net import MAGIC, MAX_FRAME, ChildSocketIO
from deepspeed_tpu.inference.serving.subproc import PROTO_VERSION

pytestmark = pytest.mark.serving_net

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))))))

READY = {"ready": True, "proto": PROTO_VERSION, "pid": 0, "faults_armed": 0,
         "cap": 48, "max_prompt_len": 47, "slots": 2}


# ------------------------------------------------------------------ frame codec
def test_frame_roundtrip_across_arbitrary_splits():
    """Frames survive any TCP segmentation: the decoder reassembles byte-wise,
    3-byte-wise, and all-at-once feeds identically."""
    payloads = [json.dumps({"i": i, "blob": "x" * (7 * i)}).encode()
                for i in range(5)]
    wire = b"".join(encode_frame(p) for p in payloads)
    for step in (1, 3, len(wire)):
        dec = FrameDecoder()
        out = []
        for off in range(0, len(wire), step):
            out.extend(dec.feed(wire[off:off + step]))
        assert out == payloads
        assert dec.frames == len(payloads)
        assert dec.quarantined == 0


def test_garbage_before_magic_is_quarantined_then_resyncs():
    dec = FrameDecoder()
    good = encode_frame(b'{"ok": 1}')
    out = dec.feed(b"HTTP/1.1 200 OK\r\n\r\n" + good)
    assert out == [b'{"ok": 1}']
    assert dec.quarantined >= 1          # counts resync EVENTS, not bytes
    assert dec.quarantined_sample is not None


def test_corrupt_crc_is_a_detected_loss_not_a_misparse():
    """A bit-flipped payload fails the CRC: the frame is quarantined and the
    NEXT frame still decodes (resync by magic rescan)."""
    a = bytearray(encode_frame(b'{"seq": 1}'))
    a[-3] ^= 0x40                        # flip one payload bit
    b = encode_frame(b'{"seq": 2}')
    dec = FrameDecoder()
    out = dec.feed(bytes(a) + b)
    assert out == [b'{"seq": 2}']
    assert dec.quarantined >= 1


def test_oversize_length_header_resyncs():
    """A corrupted length field claiming > MAX_FRAME must not stall the
    stream waiting for bytes that never come."""
    bogus = (MAGIC + struct.pack(">I", MAX_FRAME + 1)
             + struct.pack(">I", zlib.crc32(b"")))
    good = encode_frame(b'{"after": true}')
    dec = FrameDecoder()
    out = dec.feed(bogus + good)
    assert out == [b'{"after": true}']
    assert dec.quarantined >= 1


def test_encode_frame_rejects_oversize_payload():
    with pytest.raises(ValueError, match="MAX_FRAME"):
        encode_frame(b"x" * (MAX_FRAME + 1))


# ------------------------------------------------------------ net chaos grammar
def test_chaos_net_grammar():
    evs = parse_chaos("net:replica=1,mode=partition,at=0.2,s=2;"
                      "net:replica=0,mode=delay=80,when=busy,s=1.5;"
                      "net:replica=2,mode=drop=0.3,at=0.1,s=1")
    assert [(e.mode, e.value) for e in evs] == [
        ("partition", 0.0), ("delay", 80.0), ("drop", 0.3)]
    with pytest.raises(ValueError, match="unknown net fault mode"):
        parse_chaos("net:replica=0,mode=teleport,at=0,s=1")
    with pytest.raises(ValueError, match="needs mode="):
        parse_chaos("net:replica=0,at=0,s=1")
    with pytest.raises(ValueError, match="net-only"):
        parse_chaos("kill:replica=0,mode=partition,when=busy")
    with pytest.raises(ValueError, match="positive"):
        parse_chaos("net:replica=0,mode=delay=0,at=0,s=1")
    with pytest.raises(ValueError, match="probability"):
        parse_chaos("net:replica=0,mode=drop=1.5,at=0,s=1")
    with pytest.raises(ValueError, match="malformed net fault value"):
        parse_chaos("net:replica=0,mode=delay=fast,at=0,s=1")


class _FakeRouter:
    def __init__(self, replica):
        self.replicas = [replica]

    def replica_by_id(self, rid):
        return self.replicas[0]


def test_chaos_net_requires_a_transport_seam():
    """net: against a replica with no socket link is a harness bug — loud
    ValueError, never a silently-skipped fault (the soak would pass
    vacuously)."""

    class NoSeam:
        id = 0

    chaos = ChaosSchedule(parse_chaos("net:replica=0,mode=partition,at=0,s=1"))
    with pytest.raises(ValueError, match="no network transport seam"):
        chaos.poll(_FakeRouter(NoSeam()))


def test_chaos_net_fires_into_the_seam():
    calls = []

    class Seam:
        id = 0

        def net_fault(self, mode, value, duration_s):
            calls.append((mode, value, duration_s))

    chaos = ChaosSchedule(parse_chaos("net:replica=0,mode=delay=40,at=0,s=1.5"))
    chaos.poll(_FakeRouter(Seam()))
    assert chaos.exhausted
    assert calls == [("delay", 40.0, 1.5)]


# --------------------------------------------- child transport (ChildSocketIO)
def _dial(port):
    s = socket.create_connection(("127.0.0.1", port), timeout=5.0)
    s.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
    return s


def _send(sock, obj):
    sock.sendall(encode_frame(json.dumps(obj).encode()))


def _recv_objs(sock, dec, want, timeout=10.0):
    """Read frames until ``want(objs)`` is satisfied or timeout."""
    objs = []
    sock.settimeout(0.2)
    t0 = time.monotonic()
    while not want(objs) and time.monotonic() - t0 < timeout:
        try:
            data = sock.recv(65536)
        except socket.timeout:
            continue
        if data == b"":
            break
        objs.extend(json.loads(p) for p in dec.feed(data))
    return objs


def test_child_socket_io_needs_exactly_one_wiring():
    with pytest.raises(ValueError, match="exactly one"):
        ChildSocketIO([], threading.Event())
    with pytest.raises(ValueError, match="exactly one"):
        ChildSocketIO([], threading.Event(), listen="127.0.0.1:0",
                      connect="127.0.0.1:1")


def test_child_hello_session_resume_and_proto_refusal():
    """The session contract end to end against a bare ChildSocketIO: the
    cached ready survives a pre-connection emit, a fresh hello gets
    resumed=False, the session token resumes, a wrong token is a fresh
    session, proto drift is refused with a badline frame, and every accepted
    hello synthesizes a cancel_all."""
    lines, term = [], threading.Event()
    io = ChildSocketIO(lines, term, listen="127.0.0.1:0")
    try:
        io.emit(READY)                   # no connection yet: cached + dropped
        assert io.dropped >= 1
        # --- fresh hello: ready re-emitted with session, resumed=False
        s = _dial(io.port)
        _send(s, {"hello": {"proto": PROTO_VERSION, "resume": None}})
        objs = _recv_objs(s, FrameDecoder(),
                          lambda o: any("ready" in m for m in o))
        ready = next(m for m in objs if "ready" in m)
        assert ready["proto"] == PROTO_VERSION
        assert ready["session"] == io.session
        assert ready["resumed"] is False
        # --- ping -> pong echoes the probe
        _send(s, {"ping": 7, "t": 123.5})
        objs = _recv_objs(s, FrameDecoder(),
                          lambda o: any("pong" in m for m in o))
        pong = next(m for m in objs if "pong" in m)
        assert pong["pong"] == 7 and pong["t"] == 123.5
        # --- JSON garbage in a VALID frame is the main loop's quarantine,
        # not the transport's: forwarded raw
        s.sendall(encode_frame(b"not json at all {{"))
        t0 = time.monotonic()
        while not any("not json" in ln for ln in lines) \
                and time.monotonic() - t0 < 10:
            time.sleep(0.02)
        assert any("not json" in ln for ln in lines)
        s.close()
        # --- resume with the session token
        s2 = _dial(io.port)
        _send(s2, {"hello": {"proto": PROTO_VERSION, "resume": io.session}})
        objs = _recv_objs(s2, FrameDecoder(),
                          lambda o: any("ready" in m for m in o))
        ready2 = next(m for m in objs if "ready" in m)
        assert ready2["resumed"] is True
        assert ready2["session"] == io.session     # one token per process
        s2.close()
        # --- a wrong token is a FRESH session, never a false resume
        s3 = _dial(io.port)
        _send(s3, {"hello": {"proto": PROTO_VERSION, "resume": "deadbeef"}})
        objs = _recv_objs(s3, FrameDecoder(),
                          lambda o: any("ready" in m for m in o))
        assert next(m for m in objs if "ready" in m)["resumed"] is False
        s3.close()
        # --- every accepted hello frees orphaned slots (appended before the
        # ready goes out, but poll anyway: the server thread owns the append)
        t0 = time.monotonic()
        while sum('"cancel_all"' in ln for ln in lines) < 3 \
                and time.monotonic() - t0 < 10:
            time.sleep(0.02)
        assert sum('"cancel_all"' in ln for ln in lines) == 3
        # --- proto drift: refused with a badline frame, then closed
        s4 = _dial(io.port)
        _send(s4, {"hello": {"proto": 99}})
        objs = _recv_objs(s4, FrameDecoder(),
                          lambda o: any("badline" in m for m in o))
        bad = next(m for m in objs if "badline" in m)
        assert bad["badline"] == "hello" and "99" in bad["error"]
        s4.close()
    finally:
        term.set()
        io.close()


def test_child_wire_quarantine_counts_resync_events():
    """Garbage BYTES (not a framed payload) hit the decoder's CRC/magic
    resync and count in the child's cumulative quarantine tally."""
    lines, term = [], threading.Event()
    io = ChildSocketIO(lines, term, listen="127.0.0.1:0")
    try:
        s = _dial(io.port)
        _send(s, {"hello": {"proto": PROTO_VERSION, "resume": None}})
        _recv_objs(s, FrameDecoder(), lambda o: any("ready" in m for m in o))
        s.sendall(b"\x00\x01raw tcp garbage, no magic, no frame\xff")
        _send(s, {"ping": 1, "t": 0.0})  # a good frame right after resync
        objs = _recv_objs(s, FrameDecoder(),
                          lambda o: any("pong" in m for m in o))
        assert any("pong" in m for m in objs)
        t0 = time.monotonic()
        while io.quarantined < 1 and time.monotonic() - t0 < 10:
            time.sleep(0.02)
        assert io.quarantined >= 1
        s.close()
    finally:
        term.set()
        io.close()


# ------------------------------------------- parent link (SocketReplicaLink)
def test_endpoint_link_hello_ping_submit_sever_resume():
    """The reconnect state machine against an in-process child transport:
    versioned hello with session capture, RTT probes, protocol v1 submit over
    the wire, then force-sever -> bounded-backoff redial -> session RESUME
    (same token, resumed verdict re-stamped by the new hello)."""
    lines, term = [], threading.Event()
    io = ChildSocketIO(lines, term, listen="127.0.0.1:0")
    link = None
    try:
        io.emit(READY)
        link = SocketReplicaLink(
            REPO, endpoint=f"127.0.0.1:{io.port}",
            net=NetConfig(ping_interval_s=0.05, connect_timeout_s=15.0,
                          redial_backoff_base_s=0.02))
        ready = link.wait_ready(timeout=30)
        assert ready["proto"] == PROTO_VERSION
        assert link.session == io.session
        assert link.resumed_last is False
        assert link.alive                # _RemoteProc: alive while not _gone
        # pings flow both ways: an RTT sample lands
        t0 = time.monotonic()
        while link.rtt_last_ms is None and time.monotonic() - t0 < 10:
            time.sleep(0.02)
        assert link.rtt_last_ms is not None and link.rtt_last_ms >= 0.0
        # a submit crosses as one protocol v1 object
        link.submit(7, np.array([4, 5, 6], dtype=np.int32), max_new_tokens=4,
                    seed=11)
        t0 = time.monotonic()
        sub = None
        while sub is None and time.monotonic() - t0 < 10:
            for ln in list(lines):
                try:
                    obj = json.loads(ln)
                except ValueError:
                    continue
                if obj.get("id") == 7:
                    sub = obj
            time.sleep(0.02)
        assert sub is not None
        assert sub["prompt"] == [4, 5, 6]
        assert sub["max_new_tokens"] == 4 and sub["seed"] == 11
        # --- sever: the verdict goes UNKNOWN, the redial resumes the session
        session0 = link.session
        link.force_sever("test-sever")
        t0 = time.monotonic()
        while (link.severed or link.reconnects < 1
               or link.resumed_last is None) \
                and time.monotonic() - t0 < 20:
            time.sleep(0.02)
        assert not link.severed
        assert link.reconnects >= 1 and link.sever_count >= 1
        assert link.resumed_last is True
        assert link.session == session0
        # the child synthesized a cancel_all for the orphaned connection
        assert sum('"cancel_all"' in ln for ln in lines) == 2
    finally:
        if link is not None:
            link.close()
        term.set()
        io.close()


def test_write_backpressure_bounds_the_out_buffer():
    """With no reachable peer the out-queue cannot drain: past
    write_buffer_max, submit raises QueueFullError instead of buffering
    unboundedly."""
    probe = socket.socket()
    probe.bind(("127.0.0.1", 0))
    port = probe.getsockname()[1]
    probe.close()                        # nothing listens here
    link = SocketReplicaLink(
        REPO, endpoint=f"127.0.0.1:{port}",
        net=NetConfig(connect_timeout_s=5.0, write_buffer_max=2048,
                      redial_backoff_base_s=0.02))
    try:
        prompt = np.zeros(200, dtype=np.int32)
        with pytest.raises(QueueFullError):
            for i in range(64):
                link.submit(i, prompt, max_new_tokens=4)
    finally:
        link.close()


# ------------------------------------------------------------------ fleet lanes
@pytest.fixture(scope="module")
def socket_fleet():
    """Three REAL jax children behind framed TCP (boot cost paid once)."""
    cfg = HostConfig(vocab_size=96, max_seq_len=64, n_embd=32, n_layer=2,
                     n_head=4, slots=2, chunk_size=2, repo_root=REPO)
    hosts = [SocketHostedReplica(cfg) for _ in range(3)]
    for h in hosts:
        h.wait_ready(timeout=300)
    yield hosts
    for h in hosts:
        h.close()


def _drive(host, handles, timeout=60.0):
    t0 = time.monotonic()
    while not all(h.done for h in handles) and time.monotonic() - t0 < timeout:
        host.step()
    return all(h.done for h in handles)


def test_socket_sever_evicts_resumes_and_joins_spans(socket_fleet):
    """One host, no router: a traced request completes over the socket with
    its child spans joining the parent trace; a mid-flight sever finalizes
    the open handle EVICTED with its streamed prefix; the link redials and
    RESUMES the same child session; a post-resume submit is served bit-exact
    against the parent reference engine."""
    from deepspeed_tpu.observability.trace import get_tracer
    h = socket_fleet[0]
    tracer = get_tracer().enable(pid_label="net-parent")
    try:
        rng = np.random.default_rng(21)
        prompt = rng.integers(0, 96, size=5).astype(np.int32)
        root = tracer.begin("request", attrs={"request_id": 0})
        done = h.submit(prompt, max_new_tokens=6, trace_ctx=root)
        assert _drive(h, [done])
        tracer.end_span(root)

        # child spans cross the socket asynchronously: keep harvesting until
        # the decode spans land, then require ONE joined trace id
        def _xs():
            return [e for e in tracer.chrome_events() if e["ph"] == "X"]
        t0 = time.monotonic()
        while not any(e["name"] == "decode_chunk" for e in _xs()) \
                and time.monotonic() - t0 < 20:
            h.step()
            time.sleep(0.02)
        xs = _xs()
        assert any(e["name"] == "decode_chunk" for e in xs), \
            "child decode spans never joined the parent trace"
        # the request's own spans carry ONE joined trace id; the child's
        # batch-level step() spans root a trace per step
        mine = [e for e in xs if e["args"]["trace_id"] == root.trace_id]
        assert any(e["name"] == "decode_chunk" for e in mine)
        assert any(e["name"] == "serving.admit" for e in mine)
        assert all(e["name"].startswith(("serving.", "setup.")) for e in xs
                   if e["args"]["trace_id"] != root.trace_id)
        # --- sever mid-flight: eviction with streamed prefixes
        session0 = h.session
        victim = h.submit(prompt, max_new_tokens=32)
        h.force_sever("test-sever")
        t0 = time.monotonic()
        while not victim.done and time.monotonic() - t0 < 30:
            h.step()
        assert victim.done
        assert victim.state.value == "evicted"
        # --- the reconnect machine resumes the SAME child session
        t0 = time.monotonic()
        while (h.severed or h.reconnects < 1 or h.resumed_last is None) \
                and time.monotonic() - t0 < 30:
            h.step()
            time.sleep(0.01)
        assert not h.severed
        assert h.reconnects >= 1
        assert h.resumed_last is True
        assert h.session == session0
        # --- post-resume service is bit-exact (checkpointless retry model)
        after = h.submit(prompt, max_new_tokens=6)
        assert _drive(h, [after])
        ref = h.engine
        np.testing.assert_array_equal(
            after.result(),
            np.asarray(ref.generate(prompt[None, :],
                                    max_new_tokens=6))[0, prompt.size:])
    finally:
        tracer.disable()


def test_socket_delay_jitter_no_false_kill(socket_fleet):
    """Latency below the SUSPECT threshold is jitter, not death: a 30ms
    inbound delay window must finish every request with zero evictions and
    both replicas LIVE."""
    hosts = socket_fleet[:2]
    router = Router(hosts, RouterConfig(suspect_after_s=0.5, dead_after_s=1.5,
                                        recover_after_s=0.3, max_attempts=4))
    sever0 = [getattr(h._rep, "sever_count", 0) for h in hosts]
    chaos = ChaosSchedule(parse_chaos("net:replica=1,mode=delay=30,at=0,s=1.5"))
    rng = np.random.default_rng(5)
    reqs = [(rng.integers(0, 96, size=4).astype(np.int32), 8)
            for _ in range(6)]
    handles, pending = [], list(reqs)
    t0 = time.monotonic()
    while (pending or router.busy) and time.monotonic() - t0 < 90:
        chaos.poll(router)
        while pending:
            p, m = pending[0]
            try:
                handles.append(router.submit(p, max_new_tokens=m))
                pending.pop(0)
            except QueueFullError:
                break
        router.step()
    assert chaos.exhausted
    assert all(h.state.value == "finished" for h in handles)
    snap = router.snapshot()
    assert snap["lost"] == 0 and snap["evicted"] == 0
    for rid in (0, 1):
        assert router.replica_state(rid) == ReplicaState.LIVE
    # delay never severed the connection (no redial storm behind the jitter)
    assert [getattr(h._rep, "sever_count", 0) for h in hosts] == sever0


def test_socket_fleet_partition_sigkill_soak(socket_fleet):
    """The flagship acceptance lane: 3 framed-TCP replicas under a storm
    mixing a real network partition (replica 1) with a real SIGKILL
    (replica 2). Every request completes, lost == 0, retried work is
    bit-exact against the parent reference — and the recovery paths SPLIT:
    the partitioned child (process alive) heals by aging back through
    RECOVERING with ZERO respawns, while the killed child respawns through
    the supervisor with a fresh link dial."""
    hosts = socket_fleet
    # recover_after_s outlives the partition window: a RECOVERING probe into
    # a still-partitioned replica just bounces back to DEAD and burns a
    # retry attempt per bounce
    router = Router(hosts, RouterConfig(suspect_after_s=0.5, dead_after_s=1.5,
                                        recover_after_s=2.0, max_attempts=4))
    sup = ReplicaSupervisor(router, SupervisorConfig(max_restarts=3,
                                                     backoff_base_s=0.2))
    chaos = ChaosSchedule(parse_chaos(
        "net:replica=1,mode=partition,at=0.5,s=2.5;"
        "kill:replica=2,sig=KILL,when=busy"))
    rng = np.random.default_rng(13)
    reqs = [(rng.integers(0, 96, size=5).astype(np.int32), 10)
            for _ in range(10)]
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
    assert chaos.exhausted, "the partition/SIGKILL storm never fired"
    assert all(h.state.value == "finished" for h in handles)
    assert router.snapshot()["lost"] == 0
    assert sum(h.retried for h in handles) >= 1
    ref = hosts[0].engine
    for h, (p, m) in zip(handles, reqs):
        np.testing.assert_array_equal(
            h.result(),
            np.asarray(ref.generate(p[None, :],
                                    max_new_tokens=m))[0, p.size:])
    # drive both casualties back to LIVE through the RECOVERING warm probe
    # (the supervisor's backoff fires inside this loop and respawns the
    # SIGKILLed child; the partitioned one only needs its fault to expire)
    probes = []
    t1 = time.monotonic()
    while time.monotonic() - t1 < 120:
        sup.step()
        router.step()
        if all(router.replica_state(rid) == ReplicaState.LIVE
               for rid in (1, 2)):
            break
        for rid in (1, 2):
            r = router.replica_by_id(rid)
            if (router.replica_state(rid) == ReplicaState.RECOVERING
                    and r is not None and r.available > 0
                    and router.queue_depth == 0 and len(probes) < 64):
                for _ in range(4):
                    try:
                        probes.append(router.submit(
                            rng.integers(0, 96, size=4).astype(np.int32),
                            max_new_tokens=4))
                    except QueueFullError:
                        break
    for rid in (1, 2):
        assert router.replica_state(rid) == ReplicaState.LIVE, \
            f"replica {rid} never recovered"
    # respawn-vs-redial: the killed CHILD respawned, the partitioned one
    # did not (its process never died — the connection owned the outage)
    assert sup.restarts_total >= 1
    assert sup.state[2].restarts >= 1
    assert sup.state[1].restarts == 0
    t1 = time.monotonic()
    while router.busy and time.monotonic() - t1 < 60:
        router.step()
    assert all(h.state.value == "finished" for h in probes)
    assert router.snapshot()["lost"] == 0
    # the respawned child is a FRESH session (new process, new token);
    # the healed partition kept its connection-level counters sane
    assert hosts[2].resumed_last is False
    assert not hosts[1].severed and not hosts[2].severed


def test_a_link_reads_a_socket_numbered_past_selects_limit():
    """A process that holds more than 1024 descriptors (the NVMe tests leave
    that many open on a worker until they are collected) hands out socket
    numbers ``select`` refuses; the link polls, so it is served all the same."""
    import select
    import socket
    from deepspeed_tpu.inference.serving.net import _readable
    held = [open(os.devnull) for _ in range(1100)]
    a, b = socket.socketpair()
    try:
        assert a.fileno() >= 1024
        with pytest.raises(ValueError):
            select.select([a], [], [], 0)
        assert _readable((a,), 0.0) == []
        b.send(b"x")
        assert _readable((a, b), 1.0) == [a]
        b.close()
        assert _readable((a,), 1.0) == [a] and a.recv(8) == b"x" and a.recv(8) == b""
    finally:
        a.close()
        for f in held:
            f.close()
