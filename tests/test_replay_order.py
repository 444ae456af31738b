"""Followers replay in LOG order across connections.

Each replicated connection is a socket of its own into the follower's
app, and an event-loop app that finds several readable serves them in
its own order. ``ReplayEngine`` therefore waits for the app's answer on
one connection before it writes to another: two clients that write one
key leave every replica with the same value."""

import selectors
import socket
import threading
import time

import pytest

from rdma_paxos_tpu.consensus.log import EntryType
from rdma_paxos_tpu.proxy.proxy import ReplayEngine

SEND, CONNECT, CLOSE = (int(EntryType.SEND), int(EntryType.CONNECT),
                        int(EntryType.CLOSE))


class LateApp(threading.Thread):
    """A single-threaded event-loop server that wakes late (``nap`` s
    before each look at its sockets) and serves the readable ones in
    REVERSE order of acceptance: whatever was written to two connections
    while it slept is served out of order. One ``+OK`` a line
    (``answers``), or nothing at all (a sink)."""

    def __init__(self, nap=0.01, answers=True):
        super().__init__(daemon=True)
        self.nap, self.answers = nap, answers
        self.srv = socket.socket()
        self.srv.bind(("127.0.0.1", 0))
        self.srv.listen(64)
        self.port = self.srv.getsockname()[1]
        self.served = []            # lines, in the order served
        self.stop = False
        self.start()

    def run(self):
        sel = selectors.DefaultSelector()
        sel.register(self.srv, selectors.EVENT_READ)
        conns, bufs = [], {}
        while not self.stop:
            time.sleep(self.nap)
            ready = {k.fileobj for k, _ in sel.select(timeout=0.05)}
            if self.srv in ready:
                c, _ = self.srv.accept()
                conns.append(c)
                bufs[c] = b""
                sel.register(c, selectors.EVENT_READ)
            for c in reversed(conns):
                if c not in ready:
                    continue
                try:
                    data = c.recv(65536)
                except OSError:
                    data = b""
                if not data:
                    sel.unregister(c)
                    conns.remove(c)
                    continue
                bufs[c] += data
                while b"\n" in bufs[c]:
                    line, bufs[c] = bufs[c].split(b"\n", 1)
                    self.served.append(line)
                    if self.answers:
                        try:
                            c.sendall(b"+OK\n")
                        except OSError:
                            pass


@pytest.fixture()
def late_app():
    apps = []

    def make(**kw):
        apps.append(LateApp(**kw))
        return apps[-1]
    yield make
    for a in apps:
        a.stop = True
        a.srv.close()


def test_connections_are_served_in_log_order(late_app):
    app = late_app()
    eng = ReplayEngine("127.0.0.1", app.port)
    conns = [101, 102, 103, 104]
    for c in conns:
        eng.apply(CONNECT, c, b"")
    sent = [b"SET k v%d" % i for i in range(60)]
    for i, line in enumerate(sent):
        eng.apply(SEND, conns[(i * 7) % 4], line + b"\n")
    deadline = time.time() + 10
    while len(app.served) < len(sent) and time.time() < deadline:
        time.sleep(0.01)
    assert app.served == sent
    assert eng.order_timeouts == 0
    # every answer but the last connection's was read away on the way
    assert eng.drain_responses() >= 4 * (len(sent) - 1)
    eng.close()


def test_a_run_on_one_connection_is_not_waited_for(late_app):
    """Bytes to the SAME connection need no answer in between: TCP keeps
    their order (a fragmented request is several writes and one line)."""
    app = late_app()
    eng = ReplayEngine("127.0.0.1", app.port)
    t0 = time.perf_counter()
    for part in (b"SET big ", b"aaaa", b"bbbb\n"):
        eng.apply(SEND, 7, part)
    assert time.perf_counter() - t0 < ReplayEngine.ORDER_WAIT_S
    eng.apply(SEND, 8, b"SET other 1\n")
    eng.apply(CLOSE, 8, b"")            # waits for its answer, then EOF
    assert app.served == [b"SET big aaaabbbb", b"SET other 1"]
    assert eng.order_timeouts == 0
    eng.close()


def test_an_app_that_never_answers_is_given_up_on(late_app):
    """A sink costs ``GIVE_UP_AFTER`` waits, not one a write."""
    app = late_app(nap=0.0, answers=False)
    eng = ReplayEngine("127.0.0.1", app.port)
    t0 = time.perf_counter()
    for i in range(40):
        eng.apply(SEND, 200 + i % 2, b"line %d\n" % i)
    took = time.perf_counter() - t0
    assert eng.order_timeouts == ReplayEngine.GIVE_UP_AFTER
    assert took < (ReplayEngine.GIVE_UP_AFTER + 2) * ReplayEngine.ORDER_WAIT_S
    assert eng.drain_responses() == 0
    eng.close()


def test_a_second_request_on_one_connection_waits_for_the_first(late_app):
    """At most ONE whole request is ever unanswered: were a connection
    written to twice in a row, the answer to its first request would
    pass for the second's, and the next connection's bytes could be
    served before it (the last operation of one dispatch and the first
    of the next are often one connection's)."""
    app = late_app(nap=0.0)
    eng = ReplayEngine("127.0.0.1", app.port)
    sent = []
    for i in range(60):
        conn = 10 if i % 3 else 9           # 9, 10, 10, 9, 10, 10, ...
        sent.append(b"line%d" % i)
        eng.apply(SEND, conn, sent[-1] + b"\n")
    eng.apply(CLOSE, 9, b"")
    eng.apply(CLOSE, 10, b"")
    deadline = time.time() + 10
    while len(app.served) < len(sent) and time.time() < deadline:
        time.sleep(0.01)
    assert app.served == sent
    assert eng.order_timeouts == 0
    eng.close()


def test_an_unfinished_request_is_not_waited_for(late_app):
    """An app that follows two groups is handed the other group's
    operation between one group's fragments: no answer can come for
    half a request, so none is waited for, and the rest of the request
    still waits for the answer to what went out in between."""
    app = late_app(nap=0.0)
    eng = ReplayEngine("127.0.0.1", app.port)
    t0 = time.perf_counter()
    eng.apply(SEND, 21, b"SET big aaaa")        # group A, fragment 1
    eng.apply(SEND, 22, b"SET other 1\n")       # group B, whole
    eng.apply(SEND, 21, b"bbbb\n")              # group A, the rest
    eng.apply(CLOSE, 21, b"")
    assert time.perf_counter() - t0 < ReplayEngine.ORDER_WAIT_S
    assert app.served == [b"SET other 1", b"SET big aaaabbbb"]
    assert eng.order_timeouts == 0
    eng.close()


class SlowApp(threading.Thread):
    """One connection at a time in its own thread: ``+OK`` to every
    line, ``delay`` s after it was read."""

    def __init__(self, delay):
        super().__init__(daemon=True)
        self.delay = delay
        self.srv = socket.socket()
        self.srv.bind(("127.0.0.1", 0))
        self.srv.listen(16)
        self.port = self.srv.getsockname()[1]
        self.start()

    def run(self):
        while True:
            try:
                c, _ = self.srv.accept()
            except OSError:
                return
            threading.Thread(target=self.serve, args=(c,),
                             daemon=True).start()

    def serve(self, c):
        try:
            while True:
                data = c.recv(65536)
                if not data:
                    return
                for _ in range(data.count(b"\n")):
                    time.sleep(self.delay)
                    c.sendall(b"+OK\n")
        except OSError:
            pass


@pytest.fixture()
def slow_app():
    app = SlowApp(0.005)
    yield app
    app.srv.close()


@pytest.mark.parametrize("wrapped", [False, True],
                         ids=["apply", "apply_wrapped"])
def test_answer_waits_are_counted_where_they_are_waited_for(slow_app,
                                                            wrapped):
    """n answers blocked for are n samples of at least the app's 5 ms
    each; the fault injection of ``perfbench`` replaces ``apply`` on the
    instance and the counts, kept in ``_settle``, survive it."""
    eng = ReplayEngine("127.0.0.1", slow_app.port)
    if wrapped:
        real, seen = eng.apply, []

        def faulty(etype, conn_id, payload):
            seen.append(conn_id)
            return real(etype, conn_id, payload)
        eng.apply = faulty
    assert eng.take_answer_waits() == (0, 0)
    n = 6
    for i in range(n + 1):      # the first write has nothing to wait for
        eng.apply(SEND, 300 + i % 2, b"SET k%d v\n" % i)
    waits, wait_ns = eng.take_answer_waits()
    assert waits == n and wait_ns >= n * 0.005 * 0.9e9
    assert eng.order_timeouts == 0
    assert eng.take_answer_waits() == (0, 0)    # handed back once
    if wrapped:
        assert len(seen) == n + 1
    eng.close()


def test_what_is_not_waited_for_is_not_counted(slow_app):
    eng = ReplayEngine("127.0.0.1", slow_app.port)
    # an unfinished request (``_whole`` false): bytes for another
    # connection go out at once, nothing can answer yet
    eng.apply(SEND, 401, b"SET big aaaa")
    eng.apply(SEND, 402, b"SET other ")
    # the run on one connection, and the drain's look without waiting
    eng.apply(SEND, 402, b"1\n")
    eng._settle(wait=False)
    eng.drain_responses()
    assert eng.take_answer_waits() == (0, 0)
    deadline = time.time() + 5
    while eng._owed and time.time() < deadline:
        time.sleep(0.01)
        eng._settle(wait=False)         # read once there, never waited for
    assert not eng._owed
    assert eng.take_answer_waits() == (0, 0)
    eng.close()


# ---------------------------------------------------------------------------
# writes that hold several requests (a pipelining client's read is ONE
# log entry): the proof is the answer to the LAST of them
# ---------------------------------------------------------------------------

class LineAtATimeApp(threading.Thread):
    """A single-threaded event-loop server that serves ONE line a
    connection a turn, the connections in REVERSE order of acceptance,
    and answers each line ``delay`` s after it served it with a send of
    its own: the first answer to a write of sixteen requests is out long
    before the last request is served, and whatever another connection
    holds by then is served in between."""

    def __init__(self, delay=0.001):
        super().__init__(daemon=True)
        self.delay = delay
        self.srv = socket.socket()
        self.srv.bind(("127.0.0.1", 0))
        self.srv.listen(64)
        self.port = self.srv.getsockname()[1]
        self.served = []            # lines, in the order served
        self.stop = False
        self.start()

    def run(self):
        sel = selectors.DefaultSelector()
        sel.register(self.srv, selectors.EVENT_READ)
        conns, bufs = [], {}
        while not self.stop:
            busy = any(b"\n" in b for b in bufs.values())
            for key, _ in sel.select(timeout=0 if busy else 0.05):
                c = key.fileobj
                if c is self.srv:
                    c, _ = self.srv.accept()
                    conns.append(c)
                    bufs[c] = b""
                    sel.register(c, selectors.EVENT_READ)
                    continue
                try:
                    data = c.recv(65536)
                except OSError:
                    data = b""
                if not data:
                    sel.unregister(c)
                    conns.remove(c)
                    continue
                bufs[c] += data
            for c in reversed(conns):
                if b"\n" not in bufs[c]:
                    continue
                line, bufs[c] = bufs[c].split(b"\n", 1)
                self.served.append(line)
                time.sleep(self.delay)
                try:
                    c.sendall(b"+OK\n")
                except OSError:
                    pass


@pytest.fixture()
def line_app():
    app = LineAtATimeApp()
    yield app
    app.stop = True
    app.srv.close()


def batch(conn, b, n=16):
    """Write ``b`` of connection ``conn``: ``n`` SETs of ONE key."""
    return [b"SET samekey c%d-b%d-%d" % (conn, b, i) for i in range(n)]


def test_a_write_of_sixteen_requests_is_proven_by_its_last_answer(line_app):
    """Two connections write one key, sixteen requests a write, in
    turn: the app must serve the log's order, whatever its own. (With
    the FIRST answer for a proof the second connection's write goes
    out while the app is at the first one's second line, and the
    fifteen answers left over prove that connection's next write
    before the app has seen it.)"""
    eng = ReplayEngine("127.0.0.1", line_app.port)
    eng.apply(CONNECT, 1, b"")
    eng.apply(CONNECT, 2, b"")
    sent, writes = [], 0
    for b in range(6):
        for conn in (1, 2):
            lines = batch(conn, b)
            sent += lines
            writes += 1
            eng.apply(SEND, conn, b"".join(ln + b"\n" for ln in lines))
    eng.apply(CLOSE, 1, b"")
    eng.apply(CLOSE, 2, b"")        # waits for the last write's answers
    assert line_app.served == sent
    assert eng.order_timeouts == 0
    # every answer was matched to its write, none was handed on unproven
    assert eng.take_answers() == (16 * writes, 0)
    assert eng.take_answers() == (0, 0)         # handed back once
    assert eng.take_replayed() == (writes, 0)
    waits, _ns = eng.take_answer_waits()
    assert waits == writes          # one wait a write, not one an answer
    assert eng.drain_responses() == 4 * 16 * writes
    eng.close()


def test_whole_requests_ahead_of_an_unfinished_one_are_waited_for(line_app):
    """A read that ends mid-request: fifteen whole requests and the head
    of a sixteenth. Another connection's entry follows it in the log:
    the fifteen are proven first, only the fragment (which nothing can
    answer) is left behind, and its rest waits for the other write."""
    eng = ReplayEngine("127.0.0.1", line_app.port)
    first, second = batch(1, 0), batch(2, 0)
    head = b"".join(ln + b"\n" for ln in first[:15]) + first[15][:9]
    eng.apply(SEND, 1, head)
    eng.apply(SEND, 2, b"".join(ln + b"\n" for ln in second))
    eng.apply(SEND, 1, first[15][9:] + b"\n")
    eng.apply(CLOSE, 1, b"")
    eng.apply(CLOSE, 2, b"")
    assert line_app.served == first[:15] + second + first[15:]
    assert eng.order_timeouts == 0
    assert eng.take_answers() == (32, 0)
    # the fragment's write ended no request
    assert eng.take_replayed() == (2, 0)
    eng.close()


def test_a_handoff_with_answers_owed_is_counted_unproven(late_app):
    """A sink answers nothing: every write to another connection goes
    out with the last one's request unproven, waited for or not."""
    app = late_app(nap=0.0, answers=False)
    eng = ReplayEngine("127.0.0.1", app.port)
    for i in range(6):
        eng.apply(SEND, 500 + i % 2, b"line %d\n" % i)
    assert eng.take_answers() == (0, 5)
    assert eng.order_timeouts == ReplayEngine.GIVE_UP_AFTER
    eng.close()


class ScriptedApp(SlowApp):
    """``SlowApp`` whose delay is the line's last word, in ms, and that
    keeps the order in which it FINISHED the lines."""

    def __init__(self):
        self.finished = []
        super().__init__(0)

    def serve(self, c):
        buf = b""
        try:
            while True:
                data = c.recv(65536)
                if not data:
                    return
                buf += data
                while b"\n" in buf:
                    line, buf = buf.split(b"\n", 1)
                    time.sleep(int(line.split()[-1]) / 1e3)
                    self.finished.append(line)
                    c.sendall(b"+OK\n")
        except OSError:
            pass


def test_an_answer_that_came_late_proves_no_later_write():
    """Connection 1's first answer takes longer than ``ORDER_WAIT_S``:
    that handoff goes unproven, and the answer arrives while the engine
    is elsewhere. It must not pass for the answer to connection 1's
    NEXT request (a slow one): connection 2's write after it waits for
    the real one."""
    app = ScriptedApp()
    late = int(ReplayEngine.ORDER_WAIT_S * 1e3) + 30
    eng = ReplayEngine("127.0.0.1", app.port)
    eng.apply(SEND, 1, b"SET a %d\n" % late)
    eng.apply(SEND, 2, b"SET b 0\n")        # times out on connection 1
    assert eng.order_timeouts == 1
    time.sleep(0.1)                         # the late answer is there
    eng.apply(SEND, 1, b"SET c 30\n")
    eng.apply(SEND, 2, b"SET d 0\n")        # after c is DONE, not before
    eng.apply(CLOSE, 1, b"")
    eng.apply(CLOSE, 2, b"")
    assert app.finished[-2:] == [b"SET c 30", b"SET d 0"]
    assert eng.order_timeouts == 1
    assert eng.take_answers() == (3, 1)     # b, c, d matched; a unproven
    assert eng.drain_responses() == 16      # the late one read and dropped
    eng.close()
    app.srv.close()


def test_an_app_that_answers_fewer_lines_than_it_is_sent_falls_back():
    """memcached's ``set`` is two lines and one answer: after
    ``GIVE_UP_AFTER`` writes in a row answered short, any answer proves
    a write, as before, and nothing waits ``ORDER_WAIT_S`` again."""

    class TwoLinesOneAnswer(SlowApp):
        def serve(self, c):
            try:
                while True:
                    data = c.recv(65536)
                    if not data:
                        return
                    for _ in range(data.count(b"\n") // 2):
                        c.sendall(b"STORED\r\n")
            except OSError:
                pass

    app = TwoLinesOneAnswer(0)
    eng = ReplayEngine("127.0.0.1", app.port)
    t0 = time.perf_counter()
    for i in range(40):
        eng.apply(SEND, 600 + i % 2, b"set k%d 0 0 1\r\nv\r\n" % i)
    took = time.perf_counter() - t0
    assert eng.order_timeouts == ReplayEngine.GIVE_UP_AFTER
    assert took < (ReplayEngine.GIVE_UP_AFTER + 2) * ReplayEngine.ORDER_WAIT_S
    answers, unproven = eng.take_answers()
    assert unproven == ReplayEngine.GIVE_UP_AFTER
    assert answers == 39                    # one a write, short or not
    eng.close()
    app.srv.close()
