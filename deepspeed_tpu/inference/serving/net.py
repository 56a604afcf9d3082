"""Socket-framed replica transport: protocol v1 over TCP.

PR 15's :mod:`.subproc` pipe proved the recovery model across a PROCESS
boundary; this module carries the same protocol v1 across a MACHINE boundary
— the ROADMAP's "TCP framing of protocol v1" item — without changing one
recovery semantic. Every JSONL line the pipe would carry rides inside a
length-prefixed frame::

    MAGIC(2) | length(4, big-endian) | crc32(4, big-endian) | payload

``MAGIC`` is ``0xD5`` + the wire version byte, so a drifted peer fails the
resync scan instead of mis-framing; ``length`` is bounded by
:data:`MAX_FRAME`; the CRC makes a corrupted frame a *detected* loss. The
quarantine contract is protocol v1's, verbatim: a bad frame (bad magic, bad
CRC, oversized header) is counted + sampled and the decoder RESYNCS to the
next magic — one bad frame loses one message, never the replica.

Both sides are nonblocking and deadline-driven (a ``select`` loop on the
parent, accept/dial threads on the child); neither ever blocks the serving
loop on the network.

**Hello + session tokens.** The parent opens every connection with
``{"hello": {"proto": 1, "resume": <token|null>}}``. The child mints one
session token per process (``os.urandom`` hex) and answers with the protocol
v1 ready line plus ``{"session": t, "resumed": bool}`` — ``resumed`` true iff
the parent's ``resume`` token matches, i.e. this is the SAME warm process
(engine built, caches hot) behind a redialed connection. A fresh token tells
the parent the process behind the endpoint was replaced: nothing it streamed
before survives. Either way the child cancels any orphaned in-flight work on
a new accept (the parent already evicted it — see below), so slots free
rather than leak.

**Sever semantics (the checkpointless-retry contract over TCP).** When the
connection severs — RST, FIN, or a chaos partition aging into DEAD — the
parent immediately evicts every in-flight request WITH its streamed token
prefix through the existing eviction path; the router's checkpointless retry
re-prefills ``prompt + prefix`` anywhere, bit-exact. The link then runs an
explicit reconnect state machine: CONNECTED -> SEVERED -> (bounded
exponential backoff redial) -> CONNECTED, resuming with the session token,
while the frozen heartbeat stamp ages the replica through the router's
LIVE->SUSPECT->DEAD machine. The supervisor's respawn arm stays process
scoped: a dead CHILD respawns, a dead CONNECTION redials — the
"respawn-or-redial" split.

**Write-side backpressure.** Outbound frames queue under a byte bound
(:attr:`NetConfig.write_buffer_max`); a submit that would exceed it raises
the scheduler's ``QueueFullError`` so the router's admission backpressure —
not an unbounded buffer — absorbs a slow link.

**Network chaos seam.** :meth:`SocketReplicaLink.net_fault` injects faults at
the transport seam (both directions, surviving redials until expiry):
``partition`` discards every byte both ways (silence -> SUSPECT -> DEAD ->
respawn-or-redial), ``delay`` sleeps the read path (heartbeat jitter that
must NOT false-kill below the SUSPECT threshold), ``drop`` corrupts inbound
bytes with seeded probability (CRC quarantine + resync exercised under
load). The chaos grammar (``net:replica=i,mode=...``) lives in :mod:`.chaos`.

``net/*`` telemetry (frames, reconnects, quarantined frames, RTT from
ping/pong frames, partition trips) is declared in ``observability.schema``
and emitted through a per-link :class:`~...observability.metrics.RegistryFeed`.
"""

import json
import os
import random
import select
import socket
import subprocess
import sys
import threading
import time
import zlib
from collections import deque
from dataclasses import dataclass
from typing import Dict, List, Optional

from ...observability.metrics import RegistryFeed
from ...utils.logging import logger
from .scheduler import QueueFullError
from .subproc import (PROTO_VERSION, HostProtocolError, SubprocessReplica,
                      refuse_chip_child)

#: frame sentinel: 0xD5 + wire version. Bumping the wire format bumps the
#: second byte, so an old peer's resync scan never mis-frames a new stream.
MAGIC = b"\xd5\x01"
_HEADER = 10                       # MAGIC(2) + length(4) + crc32(4)
#: hard bound on one frame's payload — a corrupted length field must never
#: stall the decoder waiting on gigabytes that are not coming
MAX_FRAME = 8 * 1024 * 1024


def encode_frame(payload: bytes) -> bytes:
    """One wire frame around ``payload`` (the JSONL line, encoded)."""
    if len(payload) > MAX_FRAME:
        raise ValueError(f"frame payload {len(payload)} exceeds MAX_FRAME "
                         f"{MAX_FRAME}")
    return (MAGIC + len(payload).to_bytes(4, "big")
            + (zlib.crc32(payload) & 0xFFFFFFFF).to_bytes(4, "big") + payload)


def _readable(socks, timeout_s: float) -> list:
    """Those of ``socks`` that can be read within ``timeout_s``. ``poll`` and
    not ``select``: a process that holds more than 1024 descriptors hands out
    socket numbers that ``select`` refuses outright (``filedescriptor out of
    range``), which severed a healthy link for ever. A closed socket raises
    ``ValueError`` here as it did there."""
    by_fd = {s.fileno(): s for s in socks}
    poller = select.poll()
    for fd in by_fd:
        poller.register(fd, select.POLLIN)
    return [by_fd[fd] for fd, _ in poller.poll(timeout_s * 1e3)]


class FrameDecoder:
    """Streaming frame decoder with the v1 quarantine contract: garbage
    between frames, a corrupted CRC, or an insane length is counted +
    sampled, then the scan RESYNCS at the next magic — decoding never stops
    and never raises on wire bytes."""

    def __init__(self):
        self._buf = bytearray()
        self.frames = 0                  # well-formed frames decoded
        self.quarantined = 0             # resync events (bad magic/CRC/len)
        self.quarantined_sample: Optional[str] = None

    def _quarantine(self, sample: bytes) -> None:
        self.quarantined += 1
        self.quarantined_sample = repr(sample[:80])

    def feed(self, data: bytes) -> List[bytes]:
        """Absorb ``data``; return every complete payload now decodable."""
        self._buf += data
        out: List[bytes] = []
        while True:
            idx = self._buf.find(MAGIC)
            if idx < 0:
                # no magic in the buffer: quarantine all but the tail byte
                # (which may be the first byte of a magic split across reads)
                if len(self._buf) > 1:
                    self._quarantine(bytes(self._buf[:-1]))
                    del self._buf[:-1]
                break
            if idx > 0:                  # garbage before the frame: resync
                self._quarantine(bytes(self._buf[:idx]))
                del self._buf[:idx]
            if len(self._buf) < _HEADER:
                break                    # header still arriving
            length = int.from_bytes(self._buf[2:6], "big")
            if length > MAX_FRAME:
                # corrupted length: skip this magic, rescan inside
                self._quarantine(bytes(self._buf[:_HEADER]))
                del self._buf[:2]
                continue
            if len(self._buf) < _HEADER + length:
                break                    # truncated so far: wait for bytes
            payload = bytes(self._buf[_HEADER:_HEADER + length])
            crc = int.from_bytes(self._buf[6:10], "big")
            if (zlib.crc32(payload) & 0xFFFFFFFF) != crc:
                # detected corruption: drop the magic, rescan — the real next
                # frame's own magic survives inside the corrupted span
                self._quarantine(payload[:80])
                del self._buf[:2]
                continue
            del self._buf[:_HEADER + length]
            self.frames += 1
            out.append(payload)
        return out


@dataclass
class NetConfig:
    """Transport knobs for one :class:`SocketReplicaLink`."""
    connect_timeout_s: float = 30.0    # first dial / bootstrap deadline
    redial_backoff_base_s: float = 0.05  # reconnect machine: base * 2^attempt
    redial_backoff_max_s: float = 2.0
    redial_window_s: float = 120.0     # severed this long -> the endpoint is
    #   gone (the supervisor's respawn arm takes over)
    ping_interval_s: float = 0.5       # RTT probe cadence (net/rtt_ms)
    write_buffer_max: int = 8 * 1024 * 1024   # outbound byte bound: past it,
    #   submit raises QueueFullError (backpressure, not an unbounded buffer)
    emit_interval_s: float = 0.25      # net/* telemetry cadence


class _NetFault:
    """One active transport fault (the chaos seam's state). ``value`` is
    milliseconds for ``delay``, a probability for ``drop``."""

    def __init__(self, mode: str, value: float, duration_s: float):
        self.mode = mode
        self.value = float(value)
        self.until = time.monotonic() + float(duration_s)
        self._rng = random.Random(0xC0FFEE)

    def active(self, now: Optional[float] = None) -> bool:
        return (time.monotonic() if now is None else now) < self.until

    def corrupt(self, data: bytes) -> bytes:
        """``drop`` mode: flip one byte with probability ``value`` per read —
        the CRC turns the flip into a detected, quarantined frame loss."""
        if data and self._rng.random() < self.value:
            i = self._rng.randrange(len(data))
            b = bytearray(data)
            b[i] ^= 0xFF
            return bytes(b)
        return data


class _RemoteProc:
    """Duck-typed ``Popen`` stand-in for an endpoint-dialed child the parent
    did not spawn: "process death" is the reconnect machine giving up (the
    redial window closing), and signals have nowhere to go — the connection
    is the only lever, which is exactly the stop ladder's new rung."""

    def __init__(self, link: "SocketReplicaLink"):
        self._link = link
        self.pid: Optional[int] = None     # stamped from the child's hello
        self.returncode: Optional[int] = None

    def poll(self) -> Optional[int]:
        if self._link._gone:
            self.returncode = 1
        return self.returncode

    def wait(self, timeout: Optional[float] = None) -> Optional[int]:
        return self.poll()

    def send_signal(self, sig) -> None:    # no local process: sever instead
        self._link.force_sever("signal-on-remote")


class SocketReplicaLink(SubprocessReplica):
    """Parent-side link to a socket-served child: the exact
    :class:`~.subproc.SubprocessReplica` surface (ready/hb/progress/spans/
    quarantine/stop-ladder), carried over framed TCP with an explicit
    reconnect state machine instead of a pipe.

    Three wirings:

    - ``endpoint=None, child_dials=False`` — spawn the child with
      ``--serve-socket --listen 0``, read the ``{"listening": port}``
      bootstrap line off its stdout, dial it;
    - ``endpoint=None, child_dials=True`` — bind an ephemeral listener and
      spawn the child with ``--serve-socket --connect host:port`` (the child
      runs the dial/backoff loop, the parent accepts);
    - ``endpoint="host:port"`` — dial an externally started child
      (``deepspeed-serve --replica-endpoint``); the "process" is a
      :class:`_RemoteProc` whose death is the redial window closing.
    """

    def __init__(self, repo_root: str, env: Optional[Dict[str, str]] = None,
                 prefix_cache: bool = False, cmd: Optional[List[str]] = None,
                 endpoint: Optional[str] = None, child_dials: bool = False,
                 net: Optional[NetConfig] = None, **dims):
        # NOTE: deliberately does NOT chain to SubprocessReplica.__init__ —
        # that constructor spawns a pipe child and a pipe pump. This one
        # recreates the same state surface, then runs sockets. Everything
        # protocol-shaped (wait_ready, abandon_open_lanes, take_spans,
        # tokens/done/wait_tokens, alive) is inherited unchanged.
        self.net = net or NetConfig()
        self.ready: Optional[Dict] = None
        self.hb: Optional[Dict] = None
        self.last_line_at: Optional[float] = None
        self.progress: Dict[int, Dict] = {}
        self.quarantined = 0
        self.quarantined_sample: Optional[str] = None
        self.child_quarantined = 0
        self.escalations = 0
        self._trace_ctx: Dict[int, tuple] = {}
        self.spans: "deque" = deque(maxlen=200_000)
        self.spans_dropped = 0
        self.summary: Optional[Dict] = None
        self._lock = threading.Lock()
        # ---------------------------------------------- reconnect machine
        self.severed = False           # state: CONNECTED(False) | SEVERED(True)
        self.sever_count = 0
        self.reconnects = 0            # successful redials (CONNECTED again)
        self.session: Optional[str] = None   # child's token, from its hello
        self.resumed_last: Optional[bool] = None  # last hello's resume verdict
        self.frames_sent = 0
        self.rtt_last_ms: Optional[float] = None
        self._gone = False             # endpoint mode: redial window closed
        self._closed = False
        self._stopping = False
        self._fault: Optional[_NetFault] = None
        self._decoder = FrameDecoder()
        self._outq: "deque" = deque()  # encoded frames awaiting the socket
        self._out_bytes = 0
        self._sock: Optional[socket.socket] = None
        self._listener: Optional[socket.socket] = None
        self._endpoint = endpoint
        self._child_dials = bool(child_dials)
        self._feed = RegistryFeed()
        self._ticks = 0
        self._last_emit = 0.0
        self._rtts: List[float] = []
        self._severed_at: Optional[float] = None
        # self-pipe: submit() runs on the router thread but the socket is
        # owned by the IO thread — without a wakeup, an enqueued frame sits
        # out the select timeout (up to 50ms) before hitting the wire, which
        # serialises straight into TTFT on slot-starved replicas
        self._wake_r, self._wake_w = socket.socketpair()
        self._wake_r.setblocking(False)
        self._wake_w.setblocking(False)
        # the wire lock serialises sendall across the IO thread and the
        # inline-flush fast path (submit's thread) — without it two drains
        # could interleave partial frames on the wire
        self._wire_lock = threading.Lock()

        if endpoint is not None:
            self.proc = _RemoteProc(self)
        else:
            if child_dials:
                self._listener = socket.socket(socket.AF_INET,
                                               socket.SOCK_STREAM)
                self._listener.setsockopt(socket.SOL_SOCKET,
                                          socket.SO_REUSEADDR, 1)
                self._listener.bind(("127.0.0.1", 0))
                self._listener.listen(4)
            jax_child = cmd is None     # a cmd override is a jax-free stub
            if cmd is None:
                cmd = [sys.executable, "-m",
                       "deepspeed_tpu.inference.serving.subproc",
                       "--serve-socket"]
                if child_dials:
                    port = self._listener.getsockname()[1]
                    cmd += ["--connect", f"127.0.0.1:{port}"]
                else:
                    cmd += ["--listen", "127.0.0.1:0"]
                for k, v in dims.items():
                    cmd += [f"--{k.replace('_', '-')}", str(v)]
                if prefix_cache:
                    cmd += ["--prefix-cache"]
            full_env = dict(os.environ)
            try:
                import jax
                full_env.setdefault(
                    "JAX_THREEFRY_PARTITIONABLE",
                    "1" if jax.config.jax_threefry_partitionable else "0")
            except Exception:
                pass
            if env:
                full_env.update(env)
            if jax_child:
                refuse_chip_child(full_env)
            self.proc = subprocess.Popen(
                cmd, cwd=repo_root, env=full_env, text=True,
                stdin=subprocess.DEVNULL, stdout=subprocess.PIPE,
                stderr=subprocess.DEVNULL)
        self._io = threading.Thread(target=self._io_loop, daemon=True)
        self._io.start()

    # ----------------------------------------------------------- connection
    def _bootstrap_port(self) -> Optional[int]:
        """Spawn-listen mode: the child prints ``{"listening": port}`` on its
        REAL stdout before any heavy import — the one line stdio still
        carries."""
        deadline = time.monotonic() + self.net.connect_timeout_s
        while time.monotonic() < deadline and not self._closed:
            if self.proc.poll() is not None:
                return None
            line = self.proc.stdout.readline()
            if not line:
                return None
            try:
                obj = json.loads(line)
            except ValueError:
                continue               # stray interpreter noise: skip
            if "listening" in obj:
                return int(obj["listening"])
        return None

    def _connect_once(self) -> Optional[socket.socket]:
        """One CONNECTED attempt: dial (or accept), then open with the hello
        frame carrying the resume token."""
        try:
            if self._child_dials:
                self._listener.settimeout(1.0)
                try:
                    s, _ = self._listener.accept()
                except socket.timeout:
                    return None
            else:
                host, port = self._addr
                s = socket.create_connection((host, port), timeout=5.0)
        except OSError:
            return None
        s.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        # the socket stays BLOCKING: reads are gated by select() and sends by
        # a bounded timeout — a nonblocking sendall can partial-write a frame
        # and desynchronize the stream, which the CRC would then quarantine
        # as a loss we inflicted on ourselves
        s.settimeout(5.0)
        try:
            hello = {"hello": {"proto": PROTO_VERSION, "resume": self.session}}
            s.sendall(encode_frame(json.dumps(hello).encode()))
        except OSError:
            try:
                s.close()
            except OSError:
                pass
            return None
        return s

    def _resolve_addr(self) -> bool:
        """Fill ``self._addr`` once (endpoint string or bootstrap port)."""
        if getattr(self, "_addr", None) is not None:
            return True
        if self._endpoint is not None:
            host, _, port = self._endpoint.rpartition(":")
            self._addr = (host or "127.0.0.1", int(port))
            return True
        if self._child_dials:
            self._addr = ("", 0)           # accept side: no dial target
            return True
        port = self._bootstrap_port()
        if port is None:
            return False
        # keep draining the child's stdout so a chatty interpreter can never
        # fill the pipe and wedge the child on a stray print
        threading.Thread(target=lambda: deque(self.proc.stdout, maxlen=0),
                         daemon=True).start()
        self._addr = ("127.0.0.1", port)
        return True

    # ------------------------------------------------------------- IO thread
    def _io_loop(self) -> None:
        self._addr = None
        if not self._resolve_addr():
            self._gone = True
            return
        attempt = 0
        first = True
        dial_started = time.monotonic()
        while not self._closed:
            if self.proc.poll() is not None and self._endpoint is None:
                return                 # child process died: supervisor's arm
            sock = self._connect_once()
            if sock is None:
                attempt += 1
                window = (self.net.connect_timeout_s if first
                          else self.net.redial_window_s)
                start = self._severed_at or dial_started
                if time.monotonic() - start > window:
                    self._gone = True  # reconnect machine gave up
                    return
                if not self._child_dials:
                    # bounded exponential backoff between dials
                    time.sleep(min(self.net.redial_backoff_max_s,
                                   self.net.redial_backoff_base_s
                                   * (2 ** min(attempt, 16))))
                continue
            attempt = 0
            with self._lock:
                self._sock = sock
                if not first:
                    self.reconnects += 1
                self.severed = False
                self._severed_at = None
            if not first:
                logger.warning("[net] link re-established "
                               f"(reconnect #{self.reconnects})")
            first = False
            self._serve_conn(sock)     # returns on sever
            if self._closed or self._stopping:
                return

    def _serve_conn(self, sock: socket.socket) -> None:
        last_ping = 0.0
        while not self._closed:
            now = time.monotonic()
            fault = self._fault
            if fault is not None and not fault.active(now):
                self._fault = fault = None
            # ---------------------------------------------------- write side
            if fault is not None and fault.mode == "partition":
                with self._lock:       # silence both ways: outbound discarded
                    self._outq.clear()
                    self._out_bytes = 0
            else:
                if now - last_ping >= self.net.ping_interval_s:
                    last_ping = now
                    self._enqueue({"ping": self._ticks, "t": now})
                try:
                    with self._wire_lock:
                        self._drain_outq(sock)
                except OSError:        # incl. a send timeout: the frame may
                    self._on_sever(sock, "send")   # be partial — sever, the
                    return             # peer's decoder resyncs on its CRC
            # ----------------------------------------------------- read side
            try:
                r = _readable((sock, self._wake_r), 0.05)
            except (OSError, ValueError):
                self._on_sever(sock, "select")
                return
            if self._wake_r in r:
                try:
                    while self._wake_r.recv(4096):
                        pass
                except (OSError, BlockingIOError):
                    pass
                if sock not in r:
                    continue           # loop back to the write side at once
            if sock in r:
                try:
                    data = sock.recv(65536)
                except socket.timeout:
                    data = None
                except OSError:
                    self._on_sever(sock, "recv")
                    return
                if data == b"":
                    self._on_sever(sock, "eof")
                    return
                if data:
                    fault = self._fault
                    if fault is not None and fault.active():
                        if fault.mode == "partition":
                            data = b""       # inbound silence
                        elif fault.mode == "delay":
                            time.sleep(min(fault.value / 1e3,
                                           max(0.0, fault.until
                                               - time.monotonic())))
                        elif fault.mode == "drop":
                            data = fault.corrupt(data)
                    if data:
                        for payload in self._decoder.feed(data):
                            self._handle_payload(payload)
            self._maybe_emit()

    def _on_sever(self, sock: socket.socket, why: str) -> None:
        try:
            sock.close()
        except OSError:
            pass
        with self._lock:
            if self._sock is sock:
                self._sock = None
            already = self.severed
            self.severed = True
            # the last hello's resume verdict is stale the moment the
            # connection dies: readers polling for the NEXT hello's verdict
            # (sever-resume probes) must see "unknown", not the old answer
            self.resumed_last = None
            if self._severed_at is None:
                self._severed_at = time.monotonic()
            if not already:
                self.sever_count += 1
        if not self._closed and not self._stopping:
            logger.warning(f"[net] connection severed ({why}); "
                           "reconnect machine engaged")

    def _handle_payload(self, payload: bytes) -> None:
        try:
            obj = json.loads(payload)
            if not isinstance(obj, dict):
                raise ValueError("frame payload is not an object")
        except (ValueError, UnicodeDecodeError):
            with self._lock:
                self.quarantined += 1
                self.quarantined_sample = repr(payload[:200])
            return
        with self._lock:
            self.last_line_at = time.monotonic()
            if "pong" in obj:
                t = obj.get("t")
                if isinstance(t, (int, float)):
                    rtt = max(0.0, (time.monotonic() - float(t)) * 1e3)
                    self.rtt_last_ms = rtt
                    self._rtts.append(rtt)
                return
            if "ready" in obj:
                self.ready = obj
                if obj.get("session"):
                    self.session = str(obj["session"])
                self.resumed_last = bool(obj.get("resumed"))
                if isinstance(self.proc, _RemoteProc):
                    self.proc.pid = obj.get("pid")
            elif "hb" in obj:
                obj["_rx_t"] = time.time()
                self.hb = obj
            elif "badline" in obj:
                self.child_quarantined += 1
            elif "summary" in obj:
                self.summary = obj["summary"]
            elif "spans" in obj:
                overflow = (len(self.spans) + len(obj["spans"])
                            - self.spans.maxlen)
                if overflow > 0:
                    self.spans_dropped += overflow
                self.spans.extend(obj["spans"])
            elif "id" in obj:
                rid = int(obj["id"])
                self.progress[rid] = obj
                if obj.get("done"):
                    self._trace_ctx.pop(rid, None)

    # ------------------------------------------------------------- telemetry
    def _maybe_emit(self) -> None:
        now = time.monotonic()
        if now - self._last_emit < self.net.emit_interval_s:
            return
        self._last_emit = now
        self._ticks += 1
        with self._lock:
            rtts, self._rtts = self._rtts, []
        events = [
            ("net/frames_total",
             float(self._decoder.frames + self.frames_sent), self._ticks),
            ("net/reconnects_total", float(self.reconnects), self._ticks),
            ("net/quarantined_frames_total",
             float(self._decoder.quarantined), self._ticks),
            ("net/partition_trips_total", float(self.sever_count),
             self._ticks),
        ]
        events += [("net/rtt_ms", r, self._ticks) for r in rtts]
        self._feed.record_events(events)

    # ------------------------------------------------------------ chaos seam
    def net_fault(self, mode: str, value: float, duration_s: float) -> None:
        """Inject a transport fault (chaos ``net:`` grammar): ``partition``
        (silence both ways), ``delay`` (``value`` ms added to the read path),
        ``drop`` (``value`` probability of corrupting a read — CRC quarantine
        + resync). Persists across redials until the window expires."""
        if mode not in ("partition", "delay", "drop"):
            raise ValueError(f"unknown net fault mode {mode!r}")
        self._fault = _NetFault(mode, value, duration_s)
        logger.warning(f"[net] fault injected: mode={mode} value={value} "
                       f"for {duration_s}s")

    def force_sever(self, why: str = "forced") -> None:
        """Drop the connection NOW (evict-then-redial path — the endpoint
        analogue of a kill)."""
        with self._lock:
            sock = self._sock
        if sock is not None:
            self._on_sever(sock, why)

    # ------------------------------------------------------------ frame send
    def _enqueue(self, obj: Dict, enforce_bound: bool = False) -> None:
        frame = encode_frame(json.dumps(obj).encode())
        with self._lock:
            if enforce_bound and (self._out_bytes + len(frame)
                                  > self.net.write_buffer_max):
                raise QueueFullError(0.25)
            self._outq.append(frame)
            self._out_bytes += len(frame)
        self._try_flush_inline()
        try:
            self._wake_w.send(b"\x00")     # rouse the IO thread mid-select
        except (OSError, BlockingIOError):
            pass                           # full pipe already guarantees a wake

    def _drain_outq(self, sock: socket.socket) -> None:
        """Send every queued frame, FIFO. Caller holds ``_wire_lock``; an
        OSError propagates with the in-flight frame still queued (the frame
        may be partial on the wire — the peer's CRC resync absorbs that)."""
        while True:
            with self._lock:
                if not self._outq:
                    return
                frame = self._outq[0]
            sock.sendall(frame)
            self.frames_sent += 1
            with self._lock:
                self._outq.popleft()
                self._out_bytes -= len(frame)

    def _try_flush_inline(self) -> None:
        """Opportunistic same-thread flush: a submit lands on the wire for
        one syscall instead of a cross-thread GIL handoff (which costs up to
        the switch interval per frame — it serialises straight into TTFT on
        slot-starved replicas). Skipped whenever the IO thread owns the wire,
        a fault is staged (partition semantics live in the IO loop), or the
        link is down — the wake pipe covers those."""
        if not self._wire_lock.acquire(blocking=False):
            return
        try:
            with self._lock:
                sock = self._sock
            if sock is None or self._fault is not None:
                return
            try:
                self._drain_outq(sock)
            except OSError:
                self._on_sever(sock, "send")
        finally:
            self._wire_lock.release()

    # ----------------------------------------- SubprocessReplica overrides
    def submit(self, rid: int, prompt, max_new_tokens: int, seed: int = 0,
               eos_token_id: Optional[int] = None,
               deadline_s: Optional[float] = None,
               trace_id: Optional[str] = None,
               parent_span: Optional[str] = None) -> None:
        req = {"id": int(rid), "prompt": [int(t) for t in prompt],
               "max_new_tokens": int(max_new_tokens), "seed": int(seed),
               "eos_token_id": eos_token_id}
        if deadline_s is not None:
            req["deadline_s"] = float(deadline_s)
        if trace_id:
            req["trace_id"] = trace_id
            req["parent_span"] = parent_span
            self._trace_ctx[int(rid)] = (trace_id, parent_span,
                                         time.monotonic())
        self._enqueue(req, enforce_bound=True)

    def cancel(self, rid: int) -> None:
        try:
            self._enqueue({"cmd": "cancel", "id": int(rid)})
        except QueueFullError:
            pass                       # a severed/backed-up link is already
        #   the stronger cancellation (the child cancels on re-hello)

    def sigkill(self) -> None:
        if isinstance(self.proc, _RemoteProc):
            self.force_sever("sigkill-on-remote")
            return
        super().sigkill()

    def stop(self, drain_s: float = 10.0, term_s: float = 5.0) -> int:
        """Stop escalation ladder over TCP: stop frame + drain deadline ->
        **connection close** (the new rung: a wedged link cannot hang the
        drain) -> SIGTERM grace -> SIGKILL. Endpoint links stop at the
        connection-close rung — there is no process to signal."""
        self._stopping = True
        if self.proc.poll() is None:
            self._enqueue({"cmd": "stop"})
            deadline = time.monotonic() + drain_s
            while time.monotonic() < deadline and self.proc.poll() is None:
                time.sleep(0.02)
            if self.proc.poll() is None:
                self.escalations += 1      # rung: close the connection
                self.force_sever("stop-ladder")
                deadline = time.monotonic() + min(1.0, term_s)
                while (time.monotonic() < deadline
                       and self.proc.poll() is None):
                    time.sleep(0.02)
            if self.proc.poll() is None \
                    and not isinstance(self.proc, _RemoteProc):
                self.escalations += 1              # rung: SIGTERM grace
                try:
                    self.proc.send_signal(15)
                except ProcessLookupError:
                    pass
                try:
                    self.proc.wait(timeout=term_s)
                except subprocess.TimeoutExpired:
                    self.escalations += 1          # rung: SIGKILL backstop
                    try:
                        self.proc.send_signal(9)
                    except ProcessLookupError:
                        pass
                    self.proc.wait(timeout=30)
        self.close()
        return self.proc.returncode

    def close(self) -> None:
        """Tear the link down (no process action): sockets closed, IO thread
        released."""
        self._closed = True
        with self._lock:
            sock, self._sock = self._sock, None
        for s in (sock, self._listener, self._wake_r, self._wake_w):
            if s is not None:
                try:
                    s.close()
                except OSError:
                    pass

    @property
    def fault_active(self) -> bool:
        """Whether a chaos net fault currently governs this link — the
        breaker's evidence that an outage is transport-level, not a wedged
        child."""
        fault = self._fault
        return bool(fault is not None and fault.active())

    @property
    def alive(self) -> bool:
        return self.proc.poll() is None


# ============================================================= child side
class ChildSocketIO:
    """The child's transport: accept (``--listen``) or dial (``--connect``)
    one parent connection at a time, decode frames into the protocol v1
    ``lines`` the child main loop already consumes, and frame every ``emit``
    back out.

    Session contract: one token per process. Each new connection must open
    with the parent's hello (proto checked — a drifted parent is refused with
    an error frame, not mis-parsed); the child answers with the cached ready
    line + ``session``/``resumed`` and synthesizes a ``cancel_all`` so work
    orphaned by the dead connection frees its slots (the parent already
    evicted it with prefixes)."""

    def __init__(self, lines: List[str], term: threading.Event,
                 listen: Optional[str] = None, connect: Optional[str] = None):
        if (listen is None) == (connect is None):
            raise ValueError("--serve-socket needs exactly one of "
                             "--listen or --connect")
        self.lines = lines
        self.term = term
        self.session = os.urandom(8).hex()
        self.dropped = 0               # emits with no live connection
        self.quarantined = 0           # wire-level resync events (decoder)
        self._ready_obj: Optional[Dict] = None
        self._resumed = False
        self._conn: Optional[socket.socket] = None
        self._lock = threading.Lock()
        self._srv: Optional[socket.socket] = None
        self._connect = connect
        self.port: Optional[int] = None
        if listen is not None:
            host, _, port = str(listen).rpartition(":")
            self._srv = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
            self._srv.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
            self._srv.bind((host or "0.0.0.0", int(port or listen)))
            self._srv.listen(4)
            self.port = self._srv.getsockname()[1]
            # bootstrap line on the REAL stdout, before any heavy import:
            # the spawning parent learns the ephemeral port from it
            print(json.dumps({"listening": self.port}), flush=True)
        threading.Thread(target=self._run, daemon=True).start()

    # ------------------------------------------------------------------ emit
    def emit(self, obj: Dict) -> None:
        if "ready" in obj:
            self._ready_obj = dict(obj)
            obj = {**obj, "session": self.session, "resumed": self._resumed}
        frame = encode_frame(json.dumps(obj).encode())
        with self._lock:
            conn = self._conn
        if conn is None:
            self.dropped += 1
            return
        try:
            conn.sendall(frame)
        except OSError:
            self.dropped += 1

    # ------------------------------------------------------------- transport
    def _run(self) -> None:
        backoff = 0.05
        dial_deadline = time.monotonic() + 120.0
        while not self.term.is_set():
            sock = None
            if self._srv is not None:
                self._srv.settimeout(0.5)
                try:
                    sock, _ = self._srv.accept()
                except socket.timeout:
                    continue
                except OSError:
                    return
            else:
                host, _, port = self._connect.rpartition(":")
                try:
                    sock = socket.create_connection(
                        (host or "127.0.0.1", int(port)), timeout=2.0)
                    backoff = 0.05
                    dial_deadline = time.monotonic() + 120.0
                except OSError:
                    if time.monotonic() > dial_deadline:
                        self.term.set()    # parent gone for good: drain+exit
                        return
                    time.sleep(backoff)
                    backoff = min(2.0, backoff * 2)
                    continue
            sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
            self._serve(sock)
            with self._lock:
                if self._conn is sock:
                    self._conn = None
            try:
                sock.close()
            except OSError:
                pass

    def _serve(self, sock: socket.socket) -> None:
        dec = FrameDecoder()
        hello_ok = False
        while not self.term.is_set():
            try:
                sock.settimeout(0.5)
                data = sock.recv(65536)
            except socket.timeout:
                continue
            except OSError:
                return
            if data == b"":
                return                 # parent hung up: await the next one
            q0 = dec.quarantined
            payloads = dec.feed(data)
            self.quarantined += dec.quarantined - q0
            for payload in payloads:
                try:
                    obj = json.loads(payload)
                    if not isinstance(obj, dict):
                        raise ValueError("not an object")
                except (ValueError, UnicodeDecodeError):
                    # protocol-level quarantine stays with the main loop:
                    # hand the raw line through as-is
                    self.lines.append(payload.decode("utf-8", "replace"))
                    continue
                if not hello_ok:
                    hello = obj.get("hello")
                    if not isinstance(hello, dict) \
                            or hello.get("proto") != PROTO_VERSION:
                        # a drifted (or absent) hello is refused, never
                        # mis-parsed — mirror of the parent's versioned check
                        try:
                            sock.sendall(encode_frame(json.dumps(
                                {"badline": "hello",
                                 "error": f"proto={hello.get('proto') if isinstance(hello, dict) else None!r}"
                                          f" != {PROTO_VERSION}"}).encode()))
                        except OSError:
                            pass
                        return
                    hello_ok = True
                    self._resumed = hello.get("resume") == self.session
                    with self._lock:
                        self._conn = sock
                    # free slots orphaned by the previous connection BEFORE
                    # the ready goes out: the parent has already evicted that
                    # work with prefixes, and a peer that has seen the ready
                    # may rely on the cancel having landed
                    self.lines.append(json.dumps({"cmd": "cancel_all"}))
                    if self._ready_obj is not None:
                        self.emit(self._ready_obj)   # re-adds session/resumed
                    continue
                if "ping" in obj:
                    self.emit({"pong": obj["ping"], "t": obj.get("t")})
                    continue
                self.lines.append(payload.decode("utf-8", "replace"))

    def close(self) -> None:
        with self._lock:
            conn, self._conn = self._conn, None
        for s in (conn, self._srv):
            if s is not None:
                try:
                    s.close()
                except OSError:
                    pass
