"""Proxy: the RSM client + replay engine (reference ``src/proxy/proxy.c``).

Leader side: every socket event the interposition shim reports (CONNECT /
SEND / CLOSE) is tagged with a cluster-wide connection id
(``node_id << 8 | counter`` — proxy.c:101-106), queued for the driver to
batch into the consensus step, and the shim's blocking ack is released only
once the entry is committed + applied (the spin at proxy.c:160, here a
``threading.Event``).

Follower side: committed events whose connection id originates at another
node are replayed into the local unmodified app over loopback TCP
(``do_action_connect/send/close``, proxy.c:373-439) — producing the
identical byte stream the leader's app consumed.

The shim ↔ driver wire protocol is defined in ``native/interpose.cpp``.
"""

from __future__ import annotations

import collections
import contextlib
import functools
import os
import socket
import struct
import sys
import threading
import time
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Tuple

from rdma_paxos_tpu.consensus.log import EntryType
from rdma_paxos_tpu.obs import trace as obs_trace
from rdma_paxos_tpu.obs.metrics import default_registry
from rdma_paxos_tpu.obs.trace import default_ring
from rdma_paxos_tpu.utils.net import close_listener

OP_HELLO, OP_CONNECT, OP_SEND, OP_CLOSE = 1, 2, 3, 4

# one-shot stderr warning latch for unverifiable quiesce barriers (the
# structured signal — quiesce_unknown trace event + counter — fires on
# every occurrence; the human-readable line only once per process)
_QUIESCE_UNKNOWN_WARNED = False


def spec_send_refused_dirty(etype: int, conn_id: int, replicated_conns,
                            proxy, app_dirty: bool) -> bool:
    """Shared intake-refusal quarantine policy (single source for BOTH
    runtimes — ClusterDriver and NodeDaemon — so they cannot drift).

    True iff refusing this event with -1 leaves a SPECULATIVE app
    diverged: the shim already delivered a SEND's bytes to the app
    (read() returns before the verdict), so a refused SEND on a
    replicated session means the app executed input that will never
    commit — the caller must set ``app_dirty`` before severing, exactly
    as failing in-flight events does."""
    return (etype == int(EntryType.SEND)
            and conn_id in replicated_conns
            and proxy is not None
            and proxy.spec_mode and not app_dirty)

_OP_TO_ETYPE = {
    OP_CONNECT: EntryType.CONNECT,
    OP_SEND: EntryType.SEND,
    OP_CLOSE: EntryType.CLOSE,
}


@dataclass
class PendingEvent:
    """One shim event awaiting commit (the blocked app thread's handle).

    Two completion surfaces: ``done`` (a threading.Event for in-process
    waiters) and an optional ``on_done`` callback the ProxyServer
    attaches to send the seq-tagged wire response — the pipelined-shim
    contract, where the link thread never blocks on a commit."""

    etype: EntryType
    conn_id: int
    payload: bytes
    done: threading.Event = field(default_factory=threading.Event)
    status: int = 0
    on_done: Optional[Callable[[int], None]] = None
    _cb_lock: threading.Lock = field(default_factory=threading.Lock)
    # creation timestamp (perf_counter): release-site instrumentation
    # measures intake→commit-release as the client-visible commit
    # latency (obs commit_latency_seconds histogram)
    t0: float = field(default_factory=time.perf_counter)

    def release(self, status: int = 0) -> None:
        self.status = status
        self.done.set()
        self._fire()

    def attach(self, cb: Callable[[int], None]) -> None:
        """Attach the wire-response callback (fires immediately if the
        event already completed — release/attach may race)."""
        with self._cb_lock:
            self.on_done = cb
        if self.done.is_set():
            self._fire()

    def _fire(self) -> None:
        with self._cb_lock:
            if not self.done.is_set() or self.on_done is None:
                return
            cb, self.on_done = self.on_done, None
        try:
            cb(self.status)
        except OSError:
            pass                     # link died: the shim fell back


class ProxyServer:
    """Unix-socket server the interposed app connects to.

    One thread per app link. The link thread only READS: each event is
    handed to the driver-provided ``on_event`` callback, and the
    seq-tagged response is written either immediately (pass-through /
    sever verdicts) or from whatever thread releases the PendingEvent
    once the entry commits — so many app threads can have events in
    flight concurrently (the reference's tailq-insert-then-spin split,
    ``proxy.c:114-160``). Per-fd event order is preserved end-to-end:
    the shim serializes writes under its send mutex and this server
    reads them in order into the driver's submit queue.

    A CONNECT's verdict decides the connection once: ``<0`` sever, ``0``
    track, ``1`` pass and forget: the shim drops the fd from its table
    and no event of it follows. The 1 is this server's own answer, for a
    peer that ``claim`` (``ReplayEngine.claim``) knows as the driver's
    own replay connection; ``on_event`` never sees such a connection.
    """

    def __init__(self, sock_path: str, node_id: int,
                 on_event: Callable[[int, int, bytes],
                                    Optional[PendingEvent]],
                 conn_ctr_start: int = 0, obs=None,
                 claim: Optional[Callable[[bytes], bool]] = None):
        self.sock_path = sock_path
        self.claim = claim
        # Observability facade (rdma_paxos_tpu.obs) — link threads
        # count wire events per op so replication throughput and shim
        # pressure export with every snapshot
        self.obs = obs
        # conn ids pack the origin into bits 24+ of an int32 log column
        # (M_CONN): an id >= 128 would flip the sign bit and break the
        # origin test ((conn >> 24) == host_id) everywhere downstream —
        # fail loudly here rather than hang that host's clients. Elastic
        # host ids grow monotonically, so long-lived deployments must
        # recycle ids below 128 (the reference packs node_id<<8 into an
        # int with the same kind of bound, proxy.c:101-106).
        if not 0 <= node_id < 128:
            raise ValueError(
                f"node_id {node_id} does not fit the conn-id origin "
                "field (int32 M_CONN allows 0..127)")
        self.node_id = node_id
        self.on_event = on_event
        # declared by the shim's HELLO (bit0 of its payload byte): the
        # app executes SPECULATIVELY on not-yet-committed input, holding
        # replies until commit (output commit). The driver needs this to
        # know that failing an inflight event (deposition) leaves the
        # app DIRTY — it consumed input that may never commit — and must
        # be quarantined until rebuilt from the committed store.
        self.spec_mode = False
        # namespaced start (elastic generations) so a restarted host's
        # fresh connection ids avoid ids its previous incarnation stamped
        # into carried-over log entries. The namespace is bounded (16
        # generations x 2^20 connections before wrap), so collisions are
        # rare, not impossible — the ReplayEngine treats a repeated
        # CONNECT for a known id as a stream RESET, which keeps a wrap
        # benign (M_GEN protects the ack path independently).
        self._conn_ctr = conn_ctr_start & 0xFFFFFF
        self.conn_of_fd: Dict[Tuple[int, int], int] = {}  # (link, fd) -> id
        if os.path.exists(sock_path):
            os.unlink(sock_path)
        self._srv = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
        self._srv.bind(sock_path)
        self._srv.listen(8)
        self._links: List[socket.socket] = []
        self._link_ctr = 0
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._accept_loop,
                                        daemon=True)
        self._thread.start()

    def next_conn_id(self) -> int:
        self._conn_ctr = (self._conn_ctr + 1) & 0xFFFFFF
        return (self.node_id << 24) | self._conn_ctr

    def _accept_loop(self) -> None:
        while not self._stop.is_set():
            try:
                link, _ = self._srv.accept()
            except OSError:
                return
            self._links.append(link)
            lid = self._link_ctr
            self._link_ctr += 1
            threading.Thread(target=self._serve_link, args=(link, lid),
                             daemon=True).start()

    def _recv_exact(self, sock: socket.socket, n: int) -> Optional[bytes]:
        buf = b""
        while len(buf) < n:
            chunk = sock.recv(n - len(buf))
            if not chunk:
                return None
            buf += chunk
        return buf

    def _serve_link(self, link: socket.socket, lid: int) -> None:
        wlock = threading.Lock()     # responses come from many threads

        def respond(seq: int, status: int) -> None:
            with wlock:
                link.sendall(struct.pack("<Ii", seq, status))

        try:
            while not self._stop.is_set():
                hdr = self._recv_exact(link, 13)
                if hdr is None:
                    return
                op, seq, fd, ln = struct.unpack("<BIiI", hdr)
                payload = self._recv_exact(link, ln) if ln else b""
                if payload is None:
                    return
                if self.obs is not None:
                    self.obs.metrics.inc("proxy_wire_events_total",
                                         replica=self.node_id, op=op)
                if op not in _OP_TO_ETYPE:       # HELLO / unknown
                    if op == OP_HELLO and payload:
                        self.spec_mode = bool(payload[0] & 1)
                    respond(seq, 0)
                    continue
                if op == OP_CONNECT:
                    if self.claim is not None and self.claim(payload):
                        respond(seq, 1)     # the driver's own: forgotten
                        continue
                    self.conn_of_fd[(lid, fd)] = self.next_conn_id()
                conn_id = self.conn_of_fd.get((lid, fd), 0)
                if op == OP_CLOSE:
                    self.conn_of_fd.pop((lid, fd), None)
                # handler returns: None => pass through (0);
                # int => immediate status (<0 severs the connection);
                # PendingEvent => respond when committed (the link
                # thread moves on to the next event immediately)
                ev = self.on_event(int(_OP_TO_ETYPE[op]), conn_id,
                                   payload)
                if isinstance(ev, PendingEvent):
                    ev.attach(functools.partial(respond, seq))
                elif isinstance(ev, int):
                    respond(seq, ev)
                else:
                    respond(seq, 0)
        except OSError:
            pass
        finally:
            try:
                link.close()
            except OSError:
                pass

    def close(self) -> None:
        self._stop.set()
        close_listener(self._srv, self._thread)
        for l in self._links:
            try:
                l.close()
            except OSError:
                pass
        if os.path.exists(self.sock_path):
            os.unlink(self.sock_path)


_DUMP_MAGIC = 0x52505353544F5231     # native/stablestore.cpp kMagic


def dump_records(blob: bytes):
    """-> (base, iterator over the records) of a ``StableStore.dump()``
    blob, parsed here (an optional 16-byte ``[magic, base]`` header,
    then ``[u32 length][record]`` after ``[u32 length][record]``): a
    joiner's app is fed the history from the blob that brought it,
    not read back out of the store record by record."""
    off = base = 0
    if len(blob) >= 16:
        magic, b = struct.unpack_from("<QQ", blob, 0)
        if magic == _DUMP_MAGIC:
            off, base = 16, b

    def records():
        at, end = off, len(blob)
        while at + 4 <= end:
            (n,) = struct.unpack_from("<I", blob, at)
            yield blob[at + 4:at + 4 + n]
            at += 4 + n
    return base, records()


def apply_record(replay: "ReplayEngine", rec: bytes) -> None:
    """One store record (1-byte etype + 4-byte little-endian conn id +
    payload) into the app: the single decoder of the layout."""
    replay.apply(rec[0], int.from_bytes(rec[1:5], "little"), rec[5:])


def replay_store_into(store, replay: "ReplayEngine",
                      start: int = 0, stop: Optional[int] = None,
                      cap: int = 1 << 20) -> None:
    """Replay the stable store's event history from record ``start``
    (up to ``stop``, default the store's end) into the local app
    (``proxy_apply_db_snapshot`` analog, ``proxy.c:306-339``).
    ``start=0`` rebuilds a FRESH app; a nonzero ``start`` delivers only
    the delta to a LIVE app that already executed the prefix (store
    streams are prefix-consistent: every store is a prefix of the
    committed event order). ``cap`` bounds one record's read buffer."""
    if replay is None:
        return
    base = getattr(store, "base", 0)
    if start < base:
        # records below base were compacted away; their effects must
        # already be covered by a restored app-state checkpoint
        start = base
    for i in range(start, len(store) if stop is None else stop):
        apply_record(replay, store.read(i, cap))
    replay.drain_responses()


class ReplayEngine:
    """Replays committed remote-origin events into the local app over
    loopback TCP (the follower half of the reference proxy).

    IN LOG ORDER ACROSS CONNECTIONS: each connection has a socket of its
    own, and an event-loop app that finds two of them readable serves
    them in ITS order (toyserver and redis: by slot or fd), not in the
    order they were written. Two clients writing one key would then
    leave the replicas with different values. So before bytes go to
    another connection than the last one, :meth:`apply` waits until the
    app has answered EVERY whole request written to the last one.

    What proves a write: the apps speak line protocols, a request a
    line and an answer a line, so a write owes as many answers as it
    holds newlines (a pipelining client's read is ONE log entry of many
    requests, and neighbouring entries of one connection reach here
    joined) and is proven when that many answer lines have been read
    off its socket: the answer to its LAST request, which an app that
    answers a line at a time, each with a ``write`` of its own, sends
    long after the first. The answers are read and dropped, which is
    also all the draining the sockets need. Before more bytes go to the
    SAME connection the engine waits likewise, unless the last write
    left a request unfinished (it did not end in a newline: a
    fragment; TCP keeps one connection's order), so that the answers
    owed are always ONE write's. Whole requests AHEAD of an unfinished
    one are owed like any others: bytes for another connection (two
    groups' streams into one app: within one log a request's fragments
    are neighbours) wait for them, and only the fragment, which nothing
    can answer yet, is left behind; its rest waits its turn like any
    other write. An answer is matched to the write it answers and to no
    other: a connection left with answers owed (below) is read empty
    before it is written to again, so what comes late is never taken
    for the proof of a later write.

    A write whose answers do not all come within ``ORDER_WAIT_S`` costs
    that once and is counted in ``order_timeouts``; the bytes that then
    go to another connection go unproven (counted: an unproven
    handoff), and there the order is the app's. An app that leaves
    ``GIVE_UP_AFTER`` writes in a row wholly unanswered (a sink:
    ``noreply``) is not waited for again until it does answer. One that
    ``GIVE_UP_AFTER`` times in a row answers, but with fewer lines than
    it was sent (memcached's ``set`` is two lines and one answer), does
    not speak a line a line: from then on ANY answer proves a write, as
    it does where a write holds one request, and every connection is
    read empty before it is written to.
    """

    # how long a write to another connection waits for the app's next
    # answer on the last one
    ORDER_WAIT_S = 0.05
    GIVE_UP_AFTER = 3
    # ORDER_WAIT_S as the kernel's receive timeout (a struct timeval),
    # not Python's: the socket stays blocking, so a wait for the app's
    # answers is ONE recv where they come together
    _RCVTIMEO = struct.pack("ll", 0, int(ORDER_WAIT_S * 1e6))
    # a port of ours is claimable from its bind until this long after
    # its socket's close (an app that accepts late reports a connection
    # closed already; a later client that draws the port is a client),
    # and of the never-claimed (an app without the shim reports no
    # connection) the oldest are forgotten past UNCLAIMED_MAX
    CLAIM_WAIT_S = 1.0
    UNCLAIMED_MAX = 1024
    _LOOPBACK = socket.inet_aton("127.0.0.1")

    def __init__(self, app_host: str, app_port: int):
        self.addr = (app_host, app_port)
        self.conns: Dict[int, socket.socket] = {}
        self._awaiting: Optional[socket.socket] = None  # last written to
        self._owed = 0              # answers its whole requests still owe
        self._whole = False         # the last write there ended a request
        self._by_line = True        # the app answers a line a request line
        # connections left with answers owed: they may still come
        self._stale: set = set()
        self._reply_bytes = 0       # app output read since the last drain
        # answers blocked for since the last take_answer_waits, and the
        # time blocked: the follower app's turn
        self._waits = 0
        self._wait_ns = 0
        self._sink = bytearray(65536)   # where that output is read to
        # waits in a row that timed out: on nothing at all, on too few
        self._unanswered = 0
        self._short = 0
        self.order_timeouts = 0
        # since the last take_replayed: writes that ended a request,
        # and order_timeouts as it stood then
        self._requests = 0
        self._timeouts_taken = 0
        # since the last take_answers: answers matched to the write
        # they answer, and writes that went to another connection while
        # the last one's was unproven
        self._answers = 0
        self._unproven = 0
        # local (ephemeral) ports of our replay sockets: the driver uses
        # these to recognize its own replayed connections arriving back
        # through the app's interposition shim. port -> None while its
        # socket is open, then the time of the close; read by the link
        # threads (``claim``), hence the lock
        self.local_ports: collections.OrderedDict = collections.OrderedDict()
        self._ports_lock = threading.Lock()

    def _connect(self, conn_id: int) -> socket.socket:
        # a CONNECT for an id we already track means the id wrapped
        # around (bounded namespaces); the new stream replaces the old
        # one — reset rather than interleave bytes into a stale socket
        old = self.conns.pop(conn_id, None)
        if old is not None:
            self._forget(old)
            self._shut(old)
        s = self._open()
        s.setsockopt(socket.SOL_SOCKET, socket.SO_RCVTIMEO, self._RCVTIMEO)
        self.conns[conn_id] = s
        return s

    def _open(self) -> socket.socket:
        s = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        # bind first so the local port is REGISTERED before the app can
        # possibly observe the connection: a hot-polling app accepts and
        # reports CONNECT to the driver concurrently with (even before)
        # our connect() returning, and the driver must never misclassify
        # our own replay connection as a client session. Registered until
        # the verdict (``claim``), not until the close: the app may
        # accept the connection after that, and its CONNECT is still ours
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
        with self._ports_lock:
            self.local_ports[port] = None
            if len(self.local_ports) > self.UNCLAIMED_MAX:
                self.local_ports.popitem(last=False)
        try:
            s.connect(self.addr)
        except OSError:
            with self._ports_lock:
                self.local_ports.pop(port, None)    # the app never saw it
            s.close()
            raise
        return s

    def _shut(self, s: socket.socket) -> None:
        port = s.getsockname()[1]
        with self._ports_lock:
            if port in self.local_ports:        # not claimed yet
                self.local_ports[port] = time.monotonic()
        s.close()

    def claim(self, peer: bytes) -> bool:
        """True, ONCE, for the peer of a connection of our own, as a
        CONNECT's payload names it (4 address bytes, the port in network
        byte order): the verdict 1 on what the app's shim reports."""
        if peer[:4] != self._LOOPBACK:
            return False
        with self._ports_lock:
            closed = self.local_ports.pop(
                int.from_bytes(peer[4:6], "big"), float("-inf"))
        return (closed is None
                or time.monotonic() - closed < self.CLAIM_WAIT_S)

    def _forget(self, s: socket.socket) -> None:
        """``s`` is to be closed: nothing is owed on it any more."""
        self._stale.discard(s)
        if self._awaiting is s:
            self._awaiting, self._owed = None, 0

    def _settle(self, wait: bool = True) -> None:
        """Read away the app's answers on the connection last written
        to, until none is owed: waiting for them (at most
        ``ORDER_WAIT_S`` each time the socket is empty) before bytes go
        to another connection, or only those that are there already."""
        s = self._awaiting
        if s is None or not self._owed:
            return
        sink = self._sink
        flags = 0 if wait else socket.MSG_DONTWAIT
        t0 = time.perf_counter_ns() if wait else 0
        got = 0
        try:
            while self._owed:
                n = s.recv_into(sink, 0, flags)
                if not n:                       # the app closed it
                    self._owed = 0
                    return
                got += n
                self._unanswered = 0
                self._reply_bytes += n
                lines = (sink.count(b"\n", 0, n) if self._by_line
                         else self._owed)
                self._answers += min(lines, self._owed)
                if lines > self._owed:
                    # more than a line a request: the rest of what it
                    # says may follow, and is not the next write's
                    self._stale.add(s)
                self._owed = max(self._owed - lines, 0)
            self._short = 0
        except BlockingIOError:
            if not wait:
                return                          # not waiting: still owed
            self.order_timeouts += 1
            if not got:                         # never answered
                self._unanswered += 1
            else:
                self._short += 1
                if self._short >= self.GIVE_UP_AFTER:
                    self._by_line = False
        except OSError:
            self._owed = 0
        finally:
            if wait:
                # the one place an answer is waited for, so the count
                # holds whoever calls (or wraps) ``apply``; answers
                # that never come were ``ORDER_WAIT_S`` blocked all the same
                self._waits += 1
                self._wait_ns += time.perf_counter_ns() - t0

    def _read_empty(self, s: socket.socket) -> None:
        """Drop what the app has said on ``s`` since it was last read:
        answers that came too late to prove their own write."""
        self._stale.discard(s)
        try:
            while True:
                n = s.recv_into(self._sink, 0, socket.MSG_DONTWAIT)
                self._reply_bytes += n
                if n < len(self._sink):
                    return
        except OSError:
            pass

    def apply(self, etype: int, conn_id: int, payload: bytes) -> None:
        if etype == int(EntryType.CONNECT):
            self._connect(conn_id)
        elif etype == int(EntryType.SEND):
            s = self.conns.get(conn_id)
            if s is None:       # joined mid-stream: open lazily
                s = self._connect(conn_id)
            last = self._awaiting
            if self._owed and (last is not s or self._whole):
                # an unfinished request has no answer to wait for, the
                # whole ones ahead of it have (a replica that follows
                # several groups is handed another group's operation
                # between one group's fragments)
                self._settle(wait=self._unanswered < self.GIVE_UP_AFTER)
                if self._owed:              # timed out, or a sink
                    self._stale.add(last)
                    self._unproven += last is not s
                    self._owed = 0
            if last is not s and (s in self._stale or not self._by_line):
                self._read_empty(s)
            s.sendall(payload)
            self._awaiting = s
            self._owed += payload.count(b"\n") if self._by_line else 1
            if self._owed > 1:
                # an app that answers a line at a time sends the first
                # answer and, by Nagle's algorithm, holds the others
                # until this end has acknowledged it, which this end,
                # with nothing to send, would put off for 40 ms: ask
                # for the ACKs at once (until this socket next sends;
                # the kernel then sends one whenever what was there has
                # been read), and the rest come in a few segments
                s.setsockopt(socket.IPPROTO_TCP, socket.TCP_QUICKACK, 1)
            self._whole = payload.endswith(b"\n")
            self._requests += self._whole
        elif etype == int(EntryType.CLOSE):
            s = self.conns.pop(conn_id, None)
            if s is not None:
                if self._awaiting is s:
                    self._settle()  # its last requests, before the EOF
                self._forget(s)
                self._shut(s)

    @contextlib.contextmanager
    def raw_conn(self):
        """Context manager: a passthrough-registered connection to the
        local app for OUT-OF-BAND operations (app checkpoint dump /
        restore). Bound before connecting so the driver always
        classifies it as our own (never replicates its traffic)."""
        s = self._open()
        try:
            yield s
        finally:
            self._shut(s)

    def barrier(self, probe_fn, timeout: float = 10.0) -> None:
        """PROCESSED-INPUT barrier: replay input is delivered over
        per-connection sockets asynchronously, so a single-threaded
        event-loop app may service a later out-of-band connection (e.g.
        a checkpoint dump) before draining replay bytes still buffered
        on other connections. ``probe_fn(sock)`` must issue a
        request/response roundtrip on ``sock`` and return only once it
        has observed the response to ITS OWN request (discarding any
        buffered responses to earlier replayed commands). A reply on a
        connection proves the app consumed every byte written to that
        connection before the probe (TCP ordering + in-order reads), so
        probing every replay connection proves all delivered records
        were consumed."""
        self._settle()
        for s in list(self.conns.values()):
            s.settimeout(timeout)
            try:
                probe_fn(s)
            finally:
                s.settimeout(None)

    # both address families: a dual-stack or v6-bound app's loopback
    # sockets appear in tcp6 (with v4-mapped peers), invisible to the
    # IPv4 table — scanning only /proc/net/tcp silently weakened the
    # barrier there (ADVICE.md #2)
    _PROC_TCP_PATHS = ("/proc/net/tcp", "/proc/net/tcp6")

    def _quiesce_unknown(self, reason: str) -> None:
        """The kernel-queue barrier could not be VERIFIED (unreadable
        /proc tables, failed ioctl with no compensating peer check):
        record it as unknown — never as quiescent. Logged once per
        process (stderr); traced/counted on every occurrence."""
        default_ring().record(obs_trace.QUIESCE_UNKNOWN, reason=reason)
        default_registry().inc("quiesce_unknown_total")
        global _QUIESCE_UNKNOWN_WARNED
        if not _QUIESCE_UNKNOWN_WARNED:
            _QUIESCE_UNKNOWN_WARNED = True
            print("ReplayEngine.quiesce: cannot verify kernel queues "
                  f"({reason}); treating as NOT quiescent — supply an "
                  "app_snapshot probe_fn for an exact barrier",
                  file=sys.stderr, flush=True)

    def quiesce(self, timeout: float = 5.0,
                settle_rounds: int = 3) -> bool:
        """Best-effort app-agnostic barrier (used when no probe hook is
        configured): wait until every replay connection's bytes have
        left BOTH kernel queues — our unsent send queue (TIOCOUTQ) and
        the app-side receive queue (via /proc/net/tcp{,6} rx_queue for
        the loopback peer socket) — over ``settle_rounds`` consecutive
        samples. NARROWS but does NOT close the race: bytes the app has
        read() into userspace buffers (or lines applied one at a time
        between lock releases) are invisible to kernel queues, so a
        checkpoint can still observe partially-applied input. Apps that
        can express a request/response no-op should supply the
        app_snapshot probe_fn, which is exact.

        Unverifiable is UNKNOWN, never 'empty' (the old behavior
        silently counted both a failed TIOCOUTQ ioctl and an unreadable
        /proc/net/tcp as empty, degrading the barrier to nothing on
        IPv6 loopback / non-Linux / sandboxed kernels — ADVICE.md #2):

        * no readable /proc/net/tcp{,6} table → return False (the
          app-side rx queue is unknowable);
        * TIOCOUTQ unsupported (e.g. sandboxed kernels) → degrade to
          requiring the peer-rx check to VERIFY every replay port (a
          matching row with rx_queue 0 in a readable table); if any
          port cannot be matched, return False.

        Both degradations log once per process and emit a
        ``quiesce_unknown`` trace event + counter so the weakened
        barrier is visible, and a returned False makes the caller
        abort the checkpoint instead of compacting records the
        checkpoint may not cover."""
        import fcntl
        import struct
        import termios
        import time as _time
        deadline = _time.monotonic() + timeout
        app_port = self.addr[1]
        quiet = 0
        while True:
            busy = False
            sendq_verified = True
            ports = {}
            n_conns = 0
            for s in list(self.conns.values()):
                n_conns += 1
                try:
                    out = struct.unpack(
                        "i", fcntl.ioctl(s.fileno(), termios.TIOCOUTQ,
                                         b"\x00" * 4))[0]
                except OSError:
                    # unknown, NOT empty: fall through to the peer-rx
                    # check, which must then verify this socket
                    sendq_verified = False
                    out = 0
                if out:
                    busy = True
                    break
                try:
                    ports[s.getsockname()[1]] = True
                except OSError:
                    pass
            if not busy and n_conns:
                # peer (app-side) sockets: local == app port, remote ==
                # one of our replay ports; rx_queue is hex field 4 after
                # the colon — same field layout in tcp and tcp6 (the
                # address is longer, the :port suffix parse is
                # identical)
                readable = 0
                matched = set()
                for proc in self._PROC_TCP_PATHS:
                    try:
                        with open(proc) as f:
                            lines = f.readlines()[1:]
                    except OSError:
                        continue     # this table unreadable
                    readable += 1
                    for ln in lines:
                        try:
                            parts = ln.split()
                            lport = int(parts[1].split(":")[1], 16)
                            rport = int(parts[2].split(":")[1], 16)
                            if lport == app_port and rport in ports:
                                rxq = int(parts[4].split(":")[1], 16)
                                matched.add(rport)
                                if rxq:
                                    busy = True
                                    break
                        except (IndexError, ValueError):
                            continue  # garbled row: not a verification
                    if busy:
                        break
                if readable == 0:
                    self._quiesce_unknown(
                        "no readable /proc/net/tcp{,6}")
                    return False
                if (not busy and not sendq_verified
                        and (len(matched) < n_conns
                             or len(ports) < n_conns)):
                    # the send queue was unverifiable AND at least one
                    # replay socket has no visible peer row: nothing
                    # proves its bytes were consumed
                    self._quiesce_unknown(
                        "TIOCOUTQ unsupported and peer rows missing "
                        f"({len(matched)}/{n_conns} verified)")
                    return False
            if not busy:
                quiet += 1
                if quiet >= settle_rounds:
                    return True
            else:
                quiet = 0
            if _time.monotonic() >= deadline:
                return False
            _time.sleep(0.002)

    def drain_responses(self) -> int:
        """The local app writes responses to replayed connections; nobody
        uses them (the reference's follower likewise discards app output
        — only the leader's app talks to real clients). ``apply`` reads
        each away before it writes to another connection, so what is
        left is the last connection's: read if it is there already, else
        by the next ``apply``. -> the bytes of app output read since the
        last call."""
        self._settle(wait=False)
        n, self._reply_bytes = self._reply_bytes, 0
        return n

    def take_answer_waits(self) -> Tuple[int, int]:
        """-> (answers :meth:`_settle` blocked for, nanoseconds blocked)
        since the last call: what of the replay pass was the app's turn
        and not this process's."""
        out = (self._waits, self._wait_ns)
        self._waits = self._wait_ns = 0
        return out

    def take_answers(self) -> Tuple[int, int]:
        """-> (answers read and matched to the write they answer,
        writes that went to another connection while the last one's
        was unproven) since the last call."""
        out = (self._answers, self._unproven)
        self._answers = self._unproven = 0
        return out

    def take_replayed(self) -> Tuple[int, int]:
        """-> (writes that ended a request, answers waited
        ``ORDER_WAIT_S`` for in vain) since the last call."""
        out = (self._requests, self.order_timeouts - self._timeouts_taken)
        self._requests, self._timeouts_taken = 0, self.order_timeouts
        return out

    def close(self) -> None:
        for s in self.conns.values():
            try:
                s.close()
            except OSError:
                pass
        self.conns.clear()
