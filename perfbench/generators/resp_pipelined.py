#!/usr/bin/env python3
"""Closed-loop PIPELINED SET load over TCP, in a process of its own.

``redis-benchmark -t set -c <conns> -P <pipeline> -r <keyspace>``'s
traffic: ``conns`` connections to one server, each writing ``pipeline``
``SET key:<12 digits> <value>`` in ONE ``send`` and writing the next
batch, whole, the moment the last reply of the batch is read. Keys are
uniform over ``keyspace``, so connections write the same keys. The
commands are Redis inline commands (25 bytes a SET at 3-byte values,
400 a batch of sixteen), which ``native/toyserver`` and a real Redis
both take.

The cell's apps answer a batch with ONE ``write``, as Redis does
(``native/toyserver -j``). A server that answers a line at a time, each
with a ``write`` of its own (``toyserver`` without ``-j``), meets Nagle's
algorithm: the second answer waits for this end's ACK of the first,
which a client with nothing to send delays 40 ms. So the child asks for
its ACKs at once (``TCP_QUICKACK`` after every write; the kernel then
sends one whenever the child has read what was there), which
redis-benchmark has no need to; against either server a batch's answers
are then read as they come, each stamped after the ``recv`` that
brought it.

Like ``resp_closed_loop.py`` (whose constants, sample file and parent
side this file imports) it is two things: run as a script it IS the
load generator (one thread, ``selectors``, non-blocking sockets; a
batch is stamped once before it is written, every reply after the
``recv`` that brought it, on ``CLOCK_MONOTONIC``; no JAX, nothing of the
program; ``P <n>`` every 100 ms, ``stop`` on stdin, ``grace_s`` for what
is outstanding, the sample to ``--out``); imported, ``build`` starts
that child and turns its sample file into the harness's ``Sample``.

One row an OPERATION: a request's latency is its batch's write to ITS
reply's read (redis-benchmark stamps a batch once and gives all sixteen
the last reply's time).

Everything comes from the seed: connection ``c`` draws the key and the
value of its ``i``-th SET from ``random.Random(f"p16:{seed}:{c}")``, one
number a SET (:func:`drawer`), so the parent rebuilds every key and
value from the rows' ``(conn, idx)`` alone (:func:`writes_of`).
"""

from __future__ import annotations

import array
import json
import os
import random
import selectors
import socket
import subprocess
import sys
import time

try:                        # imported by the harness
    from perfbench.generators import resp_closed_loop
except ImportError:         # run as the child, from this directory
    import resp_closed_loop

ALPHABET = resp_closed_loop.ALPHABET
PROGRESS_S = resp_closed_loop.PROGRESS_S
MAX_RECONNECTS = resp_closed_loop.MAX_RECONNECTS
# an operation that is UNRESOLVED (sent and never answered: the batch's
# connection was severed, or the grace ran out) may or may not have been
# applied; one that FAILED (an error reply) was not
OK, FAILED, UNRESOLVED = (resp_closed_loop.OK, resp_closed_loop.FAILED,
                          resp_closed_loop.UNRESOLVED)
REPLY = b"+OK\n"


def drawer(keyspace: int, value_bytes: int):
    """-> ``draw(rng) -> (key number, value)``, a stream's next SET: ONE
    number drawn (``rng.random()``: 53 bits, a keyspace times the values
    is 36 here), its high part the key, its low part the value's
    letters, looked up in a table of all of them (46,656 at three)."""
    letters = ALPHABET.encode()
    values = [b""]
    for _ in range(value_bytes):
        values = [v + letters[j:j + 1] for v in values
                  for j in range(len(letters))]
    n, total = len(values), keyspace * len(values)

    def draw(rng: random.Random):
        key, v = divmod(int(rng.random() * total), n)
        return key, values[v]
    return draw


def stream(seed: int, conn: int) -> random.Random:
    return random.Random(f"p16:{seed}:{conn}")


def key_of(number: int) -> bytes:
    return b"key:%012d" % number


# ---------------------------------------------------------------------------
# the child
# ---------------------------------------------------------------------------

class _Conn:
    __slots__ = ("cid", "sock", "rng", "next_i", "buf", "op", "left",
                 "t_reply", "reconnects")

    def __init__(self, cid, rng):
        self.cid, self.rng = cid, rng
        self.sock = None
        self.next_i = 0
        self.buf = b""
        self.op = -1            # row of the batch's next reply, -1 = idle
        self.left = 0           # replies of the batch still to come
        self.t_reply = 0.0
        self.reconnects = 0


def child_main(argv) -> int:
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, required=True)
    ap.add_argument("--params", required=True)      # the mix, as JSON
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--out", required=True)
    a = ap.parse_args(argv)
    p = json.loads(a.params)
    depth = int(p["pipeline"])
    draw = drawer(int(p["keyspace"]), int(p["value_bytes"]))
    oks = REPLY * depth
    unresolved = bytes([UNRESOLVED]) * depth
    zeros = array.array("d", [0.0]) * depth

    mono = time.monotonic
    sel = selectors.DefaultSelector()
    # one row per request, in the order sent
    op_conn, op_idx = array.array("i"), array.array("q")
    op_send, op_recv = array.array("d"), array.array("d")
    op_state = array.array("b")
    turnaround = array.array("d")   # last reply read -> next batch written
    cpu_t, cpu_s = array.array("d"), array.array("d")
    done = 0

    def connect(c: _Conn) -> None:
        s = socket.create_connection((a.host, a.port), timeout=30)
        s.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        s.setblocking(False)
        c.sock, c.buf = s, b""
        sel.register(s, selectors.EVENT_READ, c)

    def send_next(c: _Conn) -> None:
        """The next ``depth`` SETs, whole, in one ``send``."""
        i, rng = c.next_i, c.rng
        c.next_i += depth
        lines = [b"SET key:%012d %s\n" % draw(rng) for _ in range(depth)]
        c.op, c.left = len(op_send), depth
        op_conn.extend([c.cid] * depth)
        op_idx.extend(range(i, i + depth))
        op_recv.extend(zeros)
        op_state.frombytes(unresolved)
        t = mono()
        op_send.extend([t] * depth)
        if c.t_reply:
            turnaround.append(t - c.t_reply)
        try:
            c.sock.sendall(b"".join(lines))     # far below the buffer
            c.sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_QUICKACK, 1)
        except OSError:
            sever(c)

    def sever(c: _Conn) -> None:
        """What of the batch is unanswered may or may not have been
        applied (its rows stay UNRESOLVED); reconnect (bounded) and go
        on with the next batch."""
        c.op, c.left = -1, 0
        try:
            sel.unregister(c.sock)
        except (KeyError, ValueError):
            pass
        c.sock.close()
        c.sock = None
        c.t_reply = 0.0
        if c.reconnects < MAX_RECONNECTS and not stopping:
            c.reconnects += 1
            time.sleep(0.2)
            connect(c)
            send_next(c)

    def replies(c: _Conn, data: bytes, t: float) -> int:
        """The reply lines in ``data`` onto the batch's rows, all at
        ``t``; -> how many."""
        if not c.buf and len(data) <= 4 * c.left \
                and data == oks[:len(data)] and not len(data) % 4:
            n = len(data) // 4                  # nothing but +OK
            states = bytes([OK]) * n
        else:
            lines = (c.buf + data).split(b"\n")
            c.buf = lines.pop()
            lines = lines[:c.left]
            n = len(lines)
            states = bytes(OK if ln.strip() == b"+OK" else FAILED
                           for ln in lines)
        k = c.op
        op_recv[k:k + n] = array.array("d", [t]) * n
        op_state[k:k + n] = array.array("b", states)
        c.op += n
        c.left -= n
        return n

    conns = [_Conn(cid, stream(a.seed, cid))
             for cid in range(p["connections"])]
    stopping = False
    for c in conns:
        connect(c)
    os.set_blocking(sys.stdin.fileno(), False)
    sel.register(sys.stdin, selectors.EVENT_READ, None)
    for c in conns:
        send_next(c)

    out = sys.stdout
    next_progress = mono()
    deadline = None
    while True:
        now = mono()
        if now >= next_progress:
            tm = os.times()
            cpu_t.append(now)
            cpu_s.append(tm.user + tm.system)
            out.write("P %d\n" % done)
            out.flush()
            next_progress = now + PROGRESS_S
        if stopping and (all(c.op < 0 for c in conns) or now >= deadline):
            break
        for key, _ in sel.select(timeout=max(0.0, next_progress - mono())):
            c = key.data
            if c is None:
                try:
                    os.read(sys.stdin.fileno(), 4096)
                except BlockingIOError:
                    continue
                # "stop" or EOF (the parent died): either ends the load
                stopping, deadline = True, mono() + float(p["grace_s"])
                sel.unregister(sys.stdin)
                continue
            try:
                data = c.sock.recv(65536)
            except BlockingIOError:
                continue
            except OSError:
                data = b""
            if not data:
                sever(c)
                continue
            if c.op < 0:
                continue                # nothing is owed: not ours
            t = mono()
            done += replies(c, data, t)
            if not c.left:              # the batch's last reply is in
                c.op, c.t_reply = -1, t
                if not stopping:
                    send_next(c)

    tm = os.times()
    cpu_t.append(mono())
    cpu_s.append(tm.user + tm.system)
    for c in conns:
        if c.sock is not None:
            c.sock.close()
    header = dict(n_ops=len(op_send), n_turnaround=len(turnaround),
                  n_cpu=len(cpu_t),
                  reconnects=sum(c.reconnects for c in conns))
    tmp = a.out + ".tmp"
    with open(tmp, "wb") as f:
        f.write(json.dumps(header).encode() + b"\n")
        for arr in (op_conn, op_idx, op_send, op_recv, op_state,
                    turnaround, cpu_t, cpu_s):
            arr.tofile(f)
    os.replace(tmp, a.out)
    out.write("D %d\n" % done)
    out.flush()
    return 0


# ---------------------------------------------------------------------------
# the parent's side
# ---------------------------------------------------------------------------

def writes_of(p: dict, seed: int, conn, idx):
    """-> (key numbers, values) of the rows ``(conn[k], idx[k])``, each
    connection's stream drawn again from the seed up to its highest
    index."""
    top = {}
    for c, i in zip(conn, idx):
        if i > top.get(c, -1):
            top[c] = i
    draw = drawer(p["keyspace"], p["value_bytes"])
    drawn = {}
    for c, n in top.items():
        rng = stream(seed, c)
        drawn[c] = [draw(rng) for _ in range(n + 1)]
    pairs = [drawn[c][i] for c, i in zip(conn, idx)]
    return [k for k, _ in pairs], [v for _, v in pairs]


class Generator(resp_closed_loop.Generator):
    """``resp_closed_loop``'s parent side (the child's progress lines,
    its sample file's columns) round this file's child."""

    def start(self) -> None:
        self.proc = subprocess.Popen(
            [sys.executable, os.path.abspath(__file__),
             "--host", self.host, "--port", str(self.port),
             "--params", json.dumps(self.p),
             "--seed", str(self.ctx.seed), "--out", self.out],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE)
        self.ctx.children.append(self.proc)
        os.set_blocking(self.proc.stdout.fileno(), False)

    def stop(self, t_open: float, t_close: float):
        """Tell the child to stop, wait for it, build the Sample. Its
        ``acked`` is empty: the reference is the table of writes,
        ``sample.writes()`` -> columns ``conn``, ``key`` (numbers),
        ``value``, ``t_req``, ``t_rep``, ``state``, one row a SET since
        the generator started, values drawn again from the seed;
        ``sample.window`` is ``(t_open, t_close)``."""
        from perfbench.harness.sample import Sample
        try:
            self.proc.stdin.write(b"stop\n")
            self.proc.stdin.flush()
        except OSError:
            pass
        try:
            self.proc.wait(timeout=float(self.p["grace_s"]) + 30)
        except subprocess.TimeoutExpired:
            self.proc.kill()
            self.proc.wait()
            raise RuntimeError("load generator did not stop")
        if self.proc.returncode != 0:
            raise RuntimeError(
                f"load generator exited with {self.proc.returncode}")
        cols = resp_closed_loop.read_sample_file(self.out)
        n = cols["header"]["n_ops"]
        send, recv, state = cols["send"], cols["recv"], cols["state"]
        ok = sorted((k for k in range(n) if state[k] == OK),
                    key=recv.__getitem__)
        in_window = [k for k in ok if t_open <= recv[k] < t_close]
        sent_in = [k for k in range(n) if t_open <= send[k] < t_close]
        # the child's CPU time over the window, from its 100 ms samples
        ct, cs = cols["cpu_t"], cols["cpu_s"]

        def cpu_at(t):
            best = min(range(len(ct)), key=lambda j: abs(ct[j] - t))
            return ct[best], cs[best]
        (ta, ca), (tb, cb) = cpu_at(t_open), cpu_at(t_close)
        ta_w = sorted(cols["turnaround"])
        report = dict(
            generator_busy_share=(100.0 * (cb - ca) / (tb - ta)
                                  if tb > ta else None),
            generator_turnaround_p50_us=(
                1e6 * ta_w[len(ta_w) // 2] if ta_w else None),
            # the child ticks every 100 ms: a longer silence of its own
            # means this machine, not the system under test, stood still
            generator_longest_tick_gap_s=max(
                (b - a for a, b in zip(ct, ct[1:]) if t_open <= b
                 and a <= t_close), default=None),
            reconnects=cols["header"]["reconnects"])
        sample = Sample(
            completions=[recv[k] for k in in_window],
            latencies_ms=[(recv[k] - send[k]) * 1e3 for k in in_window],
            all_completions=[recv[k] for k in ok],
            attempted=len(sent_in),
            failed=sum(1 for k in sent_in if state[k] != OK),
            unresolved_total=sum(1 for k in range(n)
                                 if state[k] == UNRESOLVED),
            acked={}, report=report)

        def writes() -> dict:
            # built when the check asks for it: seconds of Python for a
            # million rows, in the process that also steps the group
            keys, values = writes_of(self.p, self.ctx.seed, cols["conn"],
                                     cols["idx"])
            return dict(conn=cols["conn"], key=keys, value=values,
                        t_req=send, t_rep=recv, state=state)
        sample.writes = writes
        sample.window = (t_open, t_close)
        return sample


def build(params: dict, deployment, ctx) -> Generator:
    return Generator(params, deployment, ctx)


if __name__ == "__main__":
    sys.exit(child_main(sys.argv[1:]))
