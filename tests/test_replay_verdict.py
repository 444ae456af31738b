"""A connection the driver opened to its own app is classified ONCE.

The app's shim reports every accepted connection to the driver, and the
driver's proxy server answers its own replay (and checkpoint)
connections with 1, pass and forget, before any handler sees them. It
knows them by the loopback port ``ReplayEngine`` bound, and that
registration lives from the bind to that verdict, not to the socket's
close: an app that accepts late reports a connection the engine has
closed already, and it is still the driver's own (taken for a client's
it would be refused on a dirty follower, and on a leader its replayed
bytes would be replicated again). A verdict consumes it, so a client
never draws a 1; an app without the shim never reports, and what is
never claimed goes: ``CLAIM_WAIT_S`` after its close, or as the oldest
of ``UNCLAIMED_MAX``."""

import os
import queue
import socket
import struct
import threading

import pytest

from rdma_paxos_tpu.config import LogConfig, TimeoutConfig
from rdma_paxos_tpu.consensus.log import EntryType
from rdma_paxos_tpu.proxy.proxy import (
    OP_CONNECT, ProxyServer, ReplayEngine)
from rdma_paxos_tpu.runtime.driver import ClusterDriver

SEND, CONNECT, CLOSE = (int(EntryType.SEND), int(EntryType.CONNECT),
                        int(EntryType.CLOSE))
CFG = LogConfig(n_slots=64, slot_bytes=32, window_slots=16, batch_slots=8)
TO = TimeoutConfig(elec_timeout_low=1e9, elec_timeout_high=2e9)  # manual


@pytest.fixture()
def late_app():
    """An app that accepts only when asked to: the kernel completes the
    handshake in the listener's backlog meanwhile. ``report()`` accepts
    one connection and -> the payload of the CONNECT event a shim would
    send for it (peer address, peer port in network byte order)."""
    srv = socket.socket()
    srv.bind(("127.0.0.1", 0))
    srv.listen(64)
    srv.settimeout(10)
    accepted = []

    def report() -> bytes:
        c, (host, port) = srv.accept()
        accepted.append(c)
        return socket.inet_aton(host) + port.to_bytes(2, "big")
    report.port = srv.getsockname()[1]
    yield report
    for s in accepted + [srv]:
        s.close()


def test_a_connection_closed_before_it_is_accepted_is_still_ours(late_app):
    eng = ReplayEngine("127.0.0.1", late_app.port)
    eng.apply(CONNECT, 7, b"")
    eng.apply(CLOSE, 7, b"")            # back to back: nobody accepted yet
    assert not eng.conns
    peer = late_app()
    assert eng.claim(peer), "the registration died with the socket"
    assert not eng.claim(peer), "a verdict consumes it: once"
    assert not eng.local_ports
    eng.close()


def test_a_replaced_stream_and_a_checkpoint_connection_alike(late_app):
    """A CONNECT for a known id resets the stream; ``raw_conn`` is the
    checkpoint's connection: each port is claimable once, whether or not
    its socket still lives."""
    eng = ReplayEngine("127.0.0.1", late_app.port)
    eng.apply(CONNECT, 7, b"")
    eng.apply(CONNECT, 7, b"")          # the id wrapped: the first is closed
    with eng.raw_conn():
        pass                            # left before the app accepted it
    peers = [late_app() for _ in range(3)]
    assert len(set(peers)) == 3
    assert [eng.claim(p) for p in peers] == [True] * 3
    assert [eng.claim(p) for p in peers] == [False] * 3
    eng.close()


def test_only_a_loopback_peer_is_ours(late_app):
    """The port alone is not the peer: a client from another address
    with a registered port is a client, and its CONNECT consumes
    nothing."""
    eng = ReplayEngine("127.0.0.1", late_app.port)
    eng.apply(CONNECT, 7, b"")
    peer = late_app()
    assert not eng.claim(socket.inet_aton("10.1.2.3") + peer[4:])
    assert not eng.claim(b"")           # a shim that could not name it
    assert eng.claim(peer)
    eng.close()


def test_a_closed_port_is_ours_for_a_while_only(late_app, monkeypatch):
    """A port whose accept is never reported (an fd past the shim's
    table, a link that was down) must not wait for a later client: it
    is claimable while its socket is open and ``CLAIM_WAIT_S`` more."""
    clock = [1000.0]
    monkeypatch.setattr("rdma_paxos_tpu.proxy.proxy.time.monotonic",
                        lambda: clock[0])
    eng = ReplayEngine("127.0.0.1", late_app.port)
    for i in range(3):
        eng.apply(CONNECT, i, b"")
    still_open, in_time, late = (late_app() for _ in range(3))
    eng.apply(CLOSE, 1, b"")
    eng.apply(CLOSE, 2, b"")
    clock[0] += ReplayEngine.CLAIM_WAIT_S / 2
    assert eng.claim(in_time)
    clock[0] += ReplayEngine.CLAIM_WAIT_S
    assert not eng.claim(late), "a later client with that port is a client"
    assert eng.claim(still_open), "an open socket holds its port"
    assert not eng.local_ports
    eng.close()


def test_a_connection_the_app_never_saw_is_not_registered():
    with socket.socket() as s:          # a port nobody listens on
        s.bind(("127.0.0.1", 0))
        dead = s.getsockname()[1]
    eng = ReplayEngine("127.0.0.1", dead)
    with pytest.raises(OSError):
        eng.apply(CONNECT, 1, b"")
    with pytest.raises(OSError):
        with eng.raw_conn():
            pass
    assert not eng.local_ports and not eng.conns


def test_ten_thousand_pairs_without_the_shim_stay_bounded():
    """An app without the shim reports nothing, so nothing is ever
    claimed: the oldest registrations go, the newest stay. (The app
    hangs up first and with a reset, once the engine's connect has
    returned, and the engine's side waits for that, so that no pair
    leaves a port in TIME_WAIT behind for the tests that run beside
    this one.)"""
    srv = socket.socket()
    srv.bind(("127.0.0.1", 0))
    srv.listen(128)
    connected = queue.Queue()

    def serve():
        while True:
            try:
                c, _ = srv.accept()
            except OSError:
                return
            c.setsockopt(socket.SOL_SOCKET, socket.SO_LINGER,
                         struct.pack("ii", 1, 0))
            connected.get()
            c.close()
    t = threading.Thread(target=serve, daemon=True)
    t.start()
    eng = ReplayEngine("127.0.0.1", srv.getsockname()[1])
    most = 0
    for i in range(10_000):
        eng.apply(CONNECT, i, b"")
        connected.put(i)
        last = eng.conns[i].getsockname()[1]
        try:
            eng.conns[i].recv(1)
        except OSError:
            pass
        eng.apply(CLOSE, i, b"")
        most = max(most, len(eng.local_ports))
    assert most == ReplayEngine.UNCLAIMED_MAX == len(eng.local_ports)
    assert not eng.conns
    assert eng.claim(socket.inet_aton("127.0.0.1") + last.to_bytes(2, "big"))
    srv.close()
    t.join(5)


class Link:
    """A shim's end of a replica's link: events up, verdicts down."""

    def __init__(self, sock_path: str):
        self.sock = socket.socket(socket.AF_UNIX)
        self.sock.settimeout(10)
        self.sock.connect(sock_path)
        self.seq = 0

    def send(self, op: int, fd: int, payload: bytes) -> None:
        self.seq += 1
        self.sock.sendall(
            struct.pack("<BIiI", op, self.seq, fd, len(payload)) + payload)

    def verdict(self) -> int:
        seq, status = struct.unpack(
            "<Ii", self.sock.recv(8, socket.MSG_WAITALL))
        assert seq == self.seq
        return status

    def close(self) -> None:
        self.sock.close()


@pytest.fixture(scope="module")
def driver(tmp_path_factory):
    """Three replicas, replica 0 elected, no loop running. The apps are
    this module's listeners, so no shim reports behind the test's back:
    the test is the shim, on a link of its own to each replica."""
    srvs = []
    for _ in range(3):
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        s.listen(64)
        s.settimeout(10)
        srvs.append(s)
    workdir = str(tmp_path_factory.mktemp("verdict"))
    d = ClusterDriver(CFG, 3, timeout_cfg=TO, workdir=workdir,
                      app_ports=[s.getsockname()[1] for s in srvs])
    d.cluster.run_until_elected(0)
    d.step()
    assert d.leader() == 0
    links = [Link(os.path.join(workdir, "proxy%d.sock" % r))
             for r in range(3)]
    yield d, srvs, links
    for link in links:
        link.close()
    d.stop()
    for s in srvs:
        s.close()


@pytest.mark.parametrize("r, dirty", [(1, False), (2, True), (0, False)],
                         ids=["follower", "dirty_follower", "leader"])
def test_the_driver_answers_its_own_late_connection_with_1(driver, r, dirty):
    """``apply(CONNECT)`` then ``apply(CLOSE)`` back to back, the app
    accepts afterwards: not refused on a dirty follower, nothing
    replicated on a leader, no connection id spent; and the same port a
    second time is a client's, judged as a client's."""
    d, srvs, links = driver
    rt, link = d.runtimes[r], links[r]
    rt.app_dirty = dirty
    try:
        rt.replay.apply(CONNECT, (1 << 24) | 9, b"")
        rt.replay.apply(CLOSE, (1 << 24) | 9, b"")
        c, (host, port) = srvs[r].accept()
        c.close()
        peer = socket.inet_aton(host) + port.to_bytes(2, "big")
        link.send(OP_CONNECT, 40, peer)
        assert link.verdict() == 1
        assert not rt.proxy.conn_of_fd
        assert not rt.replicated_conns and not rt.inflight
        assert not d._submitq[r]
        assert not rt.replay.local_ports
        link.send(OP_CONNECT, 41, peer)
        if r == 0:                      # the leader replicates a client's
            for _ in range(50):
                if rt.proxy.conn_of_fd and not rt.inflight:
                    break
                d.step()
            assert link.verdict() == 0
            assert set(rt.proxy.conn_of_fd.values()) == rt.replicated_conns
        else:                           # a dirty app serves no client; a
            assert link.verdict() == (-1 if dirty else 0)   # follower's is local
            assert not rt.replicated_conns
    finally:
        rt.app_dirty = False


def test_the_proxy_server_asks_claim_before_any_handler(late_app, tmp_path):
    """What ``ClusterDriver`` and ``NodeDaemon`` both build on: a peer
    that ``claim`` takes draws 1 and reaches no handler, one it leaves
    is the handler's, with a connection id."""
    eng = ReplayEngine("127.0.0.1", late_app.port)
    seen = []
    srv = ProxyServer(str(tmp_path / "p.sock"), 3,
                      lambda *ev: seen.append(ev) or -1, claim=eng.claim)
    link = Link(srv.sock_path)
    try:
        eng.apply(CONNECT, 5, b"")
        eng.apply(SEND, 5, b"SET k v\n")
        eng.apply(CLOSE, 5, b"")        # waits ORDER_WAIT_S for no answer
        peer = late_app()
        link.send(OP_CONNECT, 9, peer)
        assert link.verdict() == 1 and not seen and not srv.conn_of_fd
        link.send(OP_CONNECT, 9, peer)
        assert link.verdict() == -1
        assert seen == [(CONNECT, (3 << 24) | 1, peer)]
        assert not eng.local_ports
    finally:
        link.close()
        srv.close()
        eng.close()
