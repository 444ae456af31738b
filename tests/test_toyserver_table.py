"""``native/toyserver``'s string table: 131,072 slots as ever without an
argument, ``2^n`` with ``-s n``. The other arguments' meaning and the
protocol stand; a pipelined batch is answered a line a write, which a
client that asks for its ACKs at once does not wait 40 ms for, or with
``-j`` in ONE write, which no client waits for."""

import os
import socket
import subprocess
import time

import pytest

NATIVE = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "native")


@pytest.fixture(scope="module", autouse=True)
def build_native():
    subprocess.run(["make", "-C", NATIVE], check=True, capture_output=True)


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


@pytest.fixture()
def server():
    procs = []

    def start(*args):
        port = free_port()
        p = subprocess.Popen([os.path.join(NATIVE, "toyserver"), str(port),
                              *args], stderr=subprocess.DEVNULL)
        procs.append(p)
        for _ in range(100):
            try:
                return socket.create_connection(("127.0.0.1", port),
                                                timeout=10)
            except ConnectionRefusedError:
                assert p.poll() is None, "toyserver exited"
                time.sleep(0.02)
        raise AssertionError("toyserver never listened")
    yield start
    for p in procs:
        p.kill()
        p.wait()


def fill(sock, n):
    """``n`` SETs of new keys in batches; -> the replies."""
    f = sock.makefile("rb")
    out = []
    for at in range(0, n, 4096):
        upto = min(at + 4096, n)
        sock.sendall(b"".join(b"SET key:%012d v%d\n" % (i, i)
                              for i in range(at, upto)))
        out += [f.readline().strip() for _ in range(at, upto)]
    return out


def ask(sock, line):
    sock.sendall(line + b"\n")
    return sock.makefile("rb").readline().strip()


@pytest.mark.parametrize("args, slots", [((), 131072), (("-s", "8"), 256),
                                         (("-s", "18"), 262144),
                                         (("-t", "-s", "9"), 512),
                                         (("-j", "-s", "9"), 512),
                                         (("-s", "9", "-t", "-j"), 512)])
def test_the_table_holds_one_key_less_than_its_slots(server, args, slots):
    s = server(*args)
    replies = fill(s, slots + 10)
    assert replies[:slots - 1] == [b"+OK"] * (slots - 1)
    assert set(replies[slots - 1:]) == {b"-ERR full"}
    assert ask(s, b"COUNT") == b"%d" % (slots - 1)
    # what it holds is read back, an overwrite takes no slot
    assert ask(s, b"GET key:%012d" % (slots - 2)) == b"v%d" % (slots - 2)
    assert ask(s, b"SET key:%012d again" % 0) == b"+OK"
    assert ask(s, b"GET key:%012d" % 0) == b"again"
    assert ask(s, b"GET key:%012d" % (slots + 5)) == b"-"


def test_delete_and_reinsert_keep_probe_chains_in_a_sized_table(server):
    s = server("-s", "8")
    assert set(fill(s, 200)) == {b"+OK"}
    for i in range(0, 200, 2):
        assert ask(s, b"DEL key:%012d" % i) == b"+OK"
    assert ask(s, b"COUNT") == b"100"
    for i in range(200):
        want = b"-" if i % 2 == 0 else b"v%d" % i
        assert ask(s, b"GET key:%012d" % i) == want


@pytest.mark.parametrize("bad", [("-s",), ("-s", "7"), ("-s", "27"),
                                 ("-x",), ("-n",)])
def test_an_argument_it_does_not_know_is_refused(bad):
    p = subprocess.run([os.path.join(NATIVE, "toyserver"),
                        str(free_port()), *bad], capture_output=True,
                       timeout=10)
    assert p.returncode == 2


def batch_ms(sock, rounds=12, quickack=True):
    """Median time from a write of sixteen SETs to its sixteenth reply."""
    sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
    took = []
    for b in range(rounds):
        t0 = time.perf_counter()
        sock.sendall(b"".join(b"SET k%d-%d v\n" % (b, i)
                              for i in range(16)))
        got = 0
        while got < 16:
            if quickack:
                sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_QUICKACK, 1)
            got += sock.recv(65536).count(b"\n")
        took.append((time.perf_counter() - t0) * 1e3)
    return sorted(took)[rounds // 2]


def test_a_batch_is_answered_a_line_a_write(server):
    """Sixteen answers are sixteen writes: the second waits in Nagle's
    algorithm for the client's ACK of the first, which a client that has
    nothing to send delays (40 ms on Linux) unless it asks for its ACKs
    at once. The generators, the replay engine and the checks that
    pipeline do."""
    assert batch_ms(server()) < 10


@pytest.mark.parametrize("args", [("-j",), ("-j", "-t")])
def test_joined_answers_are_one_write(server, args):
    """``-j``: the answers to one read's requests leave in one write, as
    Redis's do, so nothing waits for an ACK whatever the client does;
    a batch's sixteen come in one segment."""
    s = server(*args)
    assert batch_ms(s, quickack=False) < 10
    s.sendall(b"".join(b"SET j%d v%d\n" % (i, i) for i in range(16)))
    time.sleep(0.2)
    assert s.recv(65536) == b"+OK\n" * 16


def test_joined_answers_keep_their_order(server):
    """A listing among other requests of one read: every line in its
    place, the listing's terminator before the next answer."""
    s = server("-j", "-s", "8")
    assert fill(s, 3) == [b"+OK"] * 3
    s.sendall(b"GET key:000000000001\nDUMPALL\nCOUNT\nGET nokey\n")
    f = s.makefile("rb")
    got = [f.readline().strip() for _ in range(7)]
    assert got[0] == b"v1" and got[4:] == [b".", b"3", b"-"]
    assert sorted(got[1:4]) == [b"key:%012d v%d" % (i, i) for i in range(3)]
