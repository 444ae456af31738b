"""Speculative execution + output commit (the shim's default discipline).

The reference blocks the app thread inside ``read()`` until the event is
committed (proxy.c:160) — fine at µs commit latency, but at a host-loop's
ms-scale latency it caps a single-threaded app at one read-buffer per
commit RTT. The TPU-native redesign (``native/interpose.cpp``): reads are
forwarded asynchronously and the app executes immediately; its REPLIES are
held until the commit frontier covers every input forwarded before the
reply was produced. Externally the contract is unchanged — a client that
holds a reply knows its request committed.

These tests pin the two sides of that contract:

* the happy path — replies only ever reflect committed input (follower
  state equality, exactly-once), at full pipeline depth;
* mis-speculation — a deposed leader whose app consumed input that never
  committed is QUARANTINED (``app_dirty``): its clients are severed, new
  sessions are refused, and ``ClusterDriver.reset_app`` rebuilds the
  restarted app from the committed store, after which the diverged write
  is provably gone.
"""

import os
import socket
import struct
import subprocess
import time

import pytest

from rdma_paxos_tpu.config import LogConfig, TimeoutConfig
from rdma_paxos_tpu.runtime.driver import ClusterDriver

NATIVE = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "native")

CFG = LogConfig(n_slots=256, slot_bytes=128, window_slots=32, batch_slots=16)
PORTS = [7361, 7362, 7363]


@pytest.fixture(scope="module", autouse=True)
def build_native():
    subprocess.run(["make", "-C", NATIVE], check=True, capture_output=True)


def spawn_app(tmp_path, r, port):
    env = dict(os.environ)
    env["LD_PRELOAD"] = os.path.join(NATIVE, "interpose.so")
    env["RP_PROXY_SOCK"] = os.path.join(str(tmp_path), f"proxy{r}.sock")
    env.pop("RP_SPEC", None)          # default = speculative
    return subprocess.Popen([os.path.join(NATIVE, "toyserver"), str(port)],
                            env=env, stderr=subprocess.DEVNULL)


class Client:
    def __init__(self, port):
        self.s_port = port
        self.s = socket.create_connection(("127.0.0.1", port), timeout=10)
        self.f = self.s.makefile("rb")

    def cmd(self, line: str) -> bytes:
        self.s.sendall(line.encode() + b"\n")
        return self.f.readline().strip()

    def send_only(self, line: str) -> None:
        self.s.sendall(line.encode() + b"\n")

    def close(self):
        try:
            self.s.close()
        except OSError:
            pass


@pytest.fixture()
def stack(tmp_path):
    apps, driver = [], None
    try:
        driver = ClusterDriver(
            CFG, 3, workdir=str(tmp_path), app_ports=PORTS,
            timeout_cfg=TimeoutConfig(elec_timeout_low=0.3,
                                      elec_timeout_high=0.6))
        for r, port in enumerate(PORTS):
            apps.append(spawn_app(tmp_path, r, port))
        time.sleep(0.3)
        driver.run(period=0.002)
        deadline = time.time() + 60
        while driver.leader() < 0 and time.time() < deadline:
            time.sleep(0.05)
        assert driver.leader() >= 0, "no leader elected"
        yield driver, apps, tmp_path
    finally:
        if driver is not None:
            driver.stop()
        for a in apps:
            a.kill()
            a.wait()


def wait_kv(port, key, want, timeout=15.0):
    deadline = time.time() + timeout
    last = None
    while time.time() < deadline:
        try:
            c = Client(port)
            last = c.cmd(f"GET {key}")
            c.close()
            if last == want:
                return last
        except OSError:
            pass
        time.sleep(0.1)
    return last


def test_spec_mode_declared_and_replies_imply_commit(stack):
    driver, _apps, _tmp = stack
    lead = driver.leader()
    c = Client(PORTS[lead])
    # a deep pipeline of writes — the app executes speculatively, but
    # every reply we READ is an output-commit guarantee
    for i in range(40):
        assert c.cmd(f"SET k{i} v{i}") == b"+OK"
    c.close()
    # the shim declared itself speculative via HELLO
    assert driver.runtimes[lead].proxy.spec_mode
    # reply received => committed => must reach every follower
    for r in range(3):
        if r == lead:
            continue
        assert wait_kv(PORTS[r], "k39", b"v39") == b"v39", f"replica {r}"


def test_misspeculation_quarantine_and_reset(stack):
    driver, apps, tmp_path = stack
    lead = driver.leader()

    c = Client(PORTS[lead])
    assert c.cmd("SET committed yes") == b"+OK"
    for r in range(3):
        assert wait_kv(PORTS[r], "committed", b"yes") == b"yes"

    # isolate the leader, then feed it input that can never commit; the
    # speculative app EXECUTES it (that is the point of speculation)
    driver.cluster.partition([[lead], [r for r in range(3) if r != lead]])
    c.send_only("SET poison bad")

    # the majority side elects a new leader
    deadline = time.time() + 60
    while time.time() < deadline:
        nl = driver.leader()
        if nl >= 0 and nl != lead:
            break
        time.sleep(0.05)
    assert driver.leader() != lead, "no failover"

    # heal: the old leader hears the higher term, steps down, and its
    # un-committable inflight input marks the app dirty
    driver.cluster.heal()
    deadline = time.time() + 30
    while time.time() < deadline:
        if driver.runtimes[lead].app_dirty:
            break
        time.sleep(0.05)
    assert driver.runtimes[lead].app_dirty, "mis-speculation not flagged"

    # the poisoned client was severed (held reply dropped, never sent)
    c.s.settimeout(5)
    try:
        data = c.s.recv(64)
    except OSError:
        data = b""
    assert data == b"", "client of a mis-speculated event must be severed"
    c.close()

    # a dirty app refuses NEW sessions too (no stale/diverged reads)
    s = socket.create_connection(("127.0.0.1", PORTS[lead]), timeout=5)
    s.settimeout(5)
    try:
        s.sendall(b"GET committed\n")
        refused = s.recv(64) == b""
    except OSError:
        refused = True
    s.close()
    assert refused, "dirty app served a session"

    # operator path: restart the app fresh, rebuild from committed store
    apps[lead].kill()
    apps[lead].wait()
    apps[lead] = spawn_app(tmp_path, lead, PORTS[lead])
    time.sleep(0.3)
    driver.reset_app(lead)
    assert not driver.runtimes[lead].app_dirty

    # committed state survived; the diverged write is GONE
    assert wait_kv(PORTS[lead], "committed", b"yes") == b"yes"
    cchk = Client(PORTS[lead])
    assert cchk.cmd("GET poison") == b"-"
    cchk.close()

    # and the reset app resumes live replication from the new leader
    nl = driver.leader()
    cw = Client(PORTS[nl])
    assert cw.cmd("SET after reset-ok") == b"+OK"
    cw.close()
    assert wait_kv(PORTS[lead], "after", b"reset-ok") == b"reset-ok"


def test_refused_send_at_intake_quarantines_spec_app(stack):
    """A deposed leader with NO in-flight events is clean — but a
    surviving pre-deposition session that sends AFTER deposition has its
    bytes executed by the speculative app before intake refuses them
    (-1). That refusal must quarantine the app exactly like failing
    in-flight events does: otherwise the diverged app keeps serving
    stale local reads and serves clients again on re-election."""
    driver, _apps, _tmp = stack
    lead = driver.leader()

    c = Client(PORTS[lead])
    assert c.cmd("SET durable yes") == b"+OK"     # commits; inflight drains

    # depose the leader: partition it away, let the majority elect, heal
    driver.cluster.partition([[lead], [r for r in range(3) if r != lead]])
    deadline = time.time() + 60
    while time.time() < deadline:
        nl = driver.leader()
        if nl >= 0 and nl != lead:
            break
        time.sleep(0.05)
    assert driver.leader() != lead, "no failover"
    driver.cluster.heal()
    time.sleep(0.3)   # a few poll iterations under the healed mesh
    # no in-flight input was lost, so deposition alone leaves it clean
    assert not driver.runtimes[lead].app_dirty

    # the surviving session sends: spec app consumes, intake refuses
    c.send_only("SET sneaky bad")
    deadline = time.time() + 30
    while time.time() < deadline:
        if driver.runtimes[lead].app_dirty:
            break
        time.sleep(0.05)
    assert driver.runtimes[lead].app_dirty, (
        "refused-at-intake speculated SEND did not quarantine the app")
    c.close()


def test_driver_death_severs_without_fabricated_acks(stack):
    """The shim's driver-death discipline: replies held for input the
    dead driver never committed must NOT be released (that would
    fabricate +OK acks for lost writes — the output-commit violation
    round 5 found and fixed), and the diverged speculative app must
    serve nothing — not even new sessions — until replaced."""
    driver, _apps, _tmp = stack
    lead = driver.leader()
    c = Client(PORTS[lead])
    assert c.cmd("SET alive yes") == b"+OK"

    # an uncommittable write in flight (driver dies before stepping it)
    c.send_only("SET phantom write")
    driver.stop()

    # the held reply must never arrive: sever, not ack
    c.s.settimeout(5)
    try:
        data = c.s.recv(64)
    except OSError:
        data = b""
    assert data == b"", (
        "client received bytes after driver death: %r" % data)
    c.close()

    # the diverged app refuses NEW sessions too (a refused connect is
    # the strongest form of that refusal)
    try:
        s = socket.create_connection(("127.0.0.1", PORTS[lead]),
                                     timeout=5)
        s.settimeout(5)
        s.sendall(b"GET alive\n")
        refused = s.recv(64) == b""
        s.close()
    except OSError:
        refused = True
    assert refused, "diverged app served a session after driver death"


# ---------------------------------------------------------------------
# The CONNECT verdict decides a connection once (<0 sever, 0 track,
# 1 pass and forget): the shim against a driver's side of the link that
# the test plays itself, so what the link carries, and what waits for
# it, is seen exactly.
# ---------------------------------------------------------------------

OP_HELLO, OP_CONNECT, OP_SEND, OP_CLOSE = 1, 2, 3, 4


class FakeLink:
    """The driver's end of one shim link: reads events, answers only
    what the test tells it to."""

    def __init__(self, path):
        self.srv = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
        self.srv.bind(path)
        self.srv.listen(1)
        self.link = None

    def event(self, timeout=10.0):
        """-> (op, seq, fd, payload), or None if the link stays silent
        for ``timeout`` seconds."""
        if self.link is None:
            self.srv.settimeout(timeout)
            self.link, _ = self.srv.accept()
        self.link.settimeout(timeout)
        try:
            hdr = self.link.recv(13, socket.MSG_WAITALL)
        except socket.timeout:
            return None
        if len(hdr) < 13:
            return None
        op, seq, fd, ln = struct.unpack("<BIiI", hdr)
        payload = self.link.recv(ln, socket.MSG_WAITALL) if ln else b""
        return op, seq, fd, payload

    def answer(self, seq, status):
        self.link.sendall(struct.pack("<Ii", seq, status))

    def close(self):
        for s in (self.link, self.srv):
            if s is not None:
                s.close()


def hang_up(c):
    """``Client.close`` leaves the connection open while its reading
    file object lives: close both, so the app reads the end of it."""
    c.f.close()
    c.close()


def free_port():
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


@pytest.fixture()
def shimmed_app(tmp_path):
    """-> start(spec): toyserver under the shim, its link served by a
    ``FakeLink`` that has answered the HELLO."""
    made = []

    def start(spec):
        link = FakeLink(os.path.join(str(tmp_path), "proxy.sock"))
        port = free_port()
        env = dict(os.environ)
        env["LD_PRELOAD"] = os.path.join(NATIVE, "interpose.so")
        env["RP_PROXY_SOCK"] = os.path.join(str(tmp_path), "proxy.sock")
        env["RP_SPEC"] = spec
        app = subprocess.Popen(
            [os.path.join(NATIVE, "toyserver"), str(port)], env=env,
            stderr=subprocess.DEVNULL)
        made.extend([link.close, app.wait, app.kill])
        op, seq, _pid, flags = link.event()
        assert op == OP_HELLO and flags == bytes([spec == "1"])
        link.answer(seq, 0)
        deadline = time.time() + 10
        while True:
            try:
                return link, Client(port)
            except OSError:
                assert time.time() < deadline, "app never listened"
                time.sleep(0.02)
    yield start
    for undo in reversed(made):
        undo()


@pytest.mark.parametrize("spec", ["1", "0"])
def test_connect_answered_1_is_forgotten_by_the_shim(shimmed_app, spec):
    """No read of a forgotten connection is forwarded, no reply on it
    held, no close of it reported: the app's answer reaches the peer
    while the driver's side of the link says nothing at all."""
    link, c = shimmed_app(spec)
    op, seq, fd, info = link.event()
    assert op == OP_CONNECT
    assert int.from_bytes(info[4:6], "big") == c.s.getsockname()[1]
    link.answer(seq, 1)
    for i in range(20):             # nothing acks these: nothing waits
        assert c.cmd(f"SET k{i} v{i}") == b"+OK"
    assert c.cmd("GET k19") == b"v19"
    assert link.event(timeout=0.3) is None, "a read was forwarded"
    hang_up(c)
    assert link.event(timeout=0.5) is None, "the close was reported"
    # the fd number, reused by the next connection, is tracked afresh
    c2 = Client(c.s_port)
    op, seq, fd2, _ = link.event()
    assert (op, fd2) == (OP_CONNECT, fd)
    link.answer(seq, 0)
    c2.send_only("GET k0")
    assert link.event()[0] == OP_SEND
    hang_up(c2)


@pytest.mark.parametrize("spec", ["1", "0"])
def test_connect_answered_0_is_tracked_as_before(shimmed_app, spec):
    """Every read is an event, the reply waits for its ack (held by the
    shim, or the app held inside read()), the close is reported."""
    link, c = shimmed_app(spec)
    op, seq, fd, _ = link.event()
    assert op == OP_CONNECT
    link.answer(seq, 0)
    c.send_only("SET a 1")
    op, seq, efd, payload = link.event()
    assert (op, efd, payload) == (OP_SEND, fd, b"SET a 1\n")
    c.s.settimeout(0.3)
    with pytest.raises(socket.timeout):
        c.s.recv(16)                # no ack yet: no reply
    link.answer(seq, 0)
    c.s.settimeout(10)
    assert c.f.readline().strip() == b"+OK"
    hang_up(c)
    op, seq, efd, _ = link.event()
    assert (op, efd) == (OP_CLOSE, fd)
    link.answer(seq, 0)


def wire_events(driver, r):
    """-> {op: count} of the wire events replica ``r``'s link threads
    have taken in (``proxy_wire_events_total``)."""
    return {op: int(driver.obs.metrics.get("proxy_wire_events_total",
                                           replica=r, op=op))
            for op in (OP_HELLO, OP_CONNECT, OP_SEND, OP_CLOSE)}


def test_followers_report_nothing_of_the_replay(stack):
    """After N replicated SETs from three clients a follower's link has
    carried its HELLO and the CONNECTs of the driver's own connections,
    each answered 1, and nothing else: no read and no close of them.
    The replay still goes through ``replay.apply``, where the
    benchmark's must-fail controls stand, and the apps are equal."""
    driver, _apps, _tmp = stack
    lead = driver.leader()
    fols = [r for r in range(3) if r != lead]
    seen = {r: [] for r in fols}
    for r in fols:              # replaced as perfbench's `inject` does
        replay = driver.runtimes[r].replay

        def spy(etype, conn, payload, _apply=replay.apply, _r=r):
            seen[_r].append((etype, payload))
            return _apply(etype, conn, payload)
        replay.apply = spy
    n, clients = 60, [Client(PORTS[lead]) for _ in range(3)]
    for i in range(n):
        assert clients[i % 3].cmd(f"SET key{i} val{i}") == b"+OK"
    for c in clients:
        hang_up(c)
    sets = [b"SET key%d val%d" % (i, i) for i in range(n)]
    for r in fols:
        assert wait_kv(PORTS[r], f"key{n - 1}", f"val{n - 1}".encode())
        deadline = time.time() + 10     # the three CLOSEs, replayed
        while (sum(e == 4 for e, _ in seen[r]) < 3
               and time.time() < deadline):
            time.sleep(0.05)
        sent = b"".join(p for e, p in seen[r] if e == 3)
        assert sent.split(b"\n")[:-1] == sets, "an operation went round"
    for r in fols:
        ev = wire_events(driver, r)
        # the three replayed sessions, plus wait_kv's own (untouched by
        # this PR: a client's session on a follower stays tracked)
        assert ev[OP_HELLO] == 1
        ours = sum(e == 2 for e, _ in seen[r])
        assert ours == 3
        probes = ev[OP_CONNECT] - ours
        assert probes >= 1
        assert ev[OP_SEND] == probes, ev    # wait_kv's one GET each
        assert ev[OP_CLOSE] <= probes, ev
        assert not driver.runtimes[r].replay.local_ports
        assert len(driver.runtimes[r].proxy.conn_of_fd) <= probes
    for i in (0, n // 2, n - 1):
        vals = set()
        for p in PORTS:
            c = Client(p)
            vals.add(c.cmd(f"GET key{i}"))
            hang_up(c)
        assert vals == {f"val{i}".encode()}
    counts = set()
    for p in PORTS:
        c = Client(p)
        counts.add(c.cmd("COUNT"))
        hang_up(c)
    assert counts == {str(n).encode()}
