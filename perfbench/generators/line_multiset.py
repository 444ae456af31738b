#!/usr/bin/env python3
"""Closed-loop batched writes over TCP, in a process of its own.

``ssdb-bench``'s records (key ``k%010d``, value ``v%0100d``, each key
written once) sent in batches: ``conns`` connections to one server, each
with one ``multi_set k v k v ...`` of ``pairs`` pairs outstanding, the
next one written the moment the ``+OK <pairs>`` is read. One request is
one line, which ``native/toyssdb`` takes (SSDB's own block framing
carries the same words).

Like ``resp_closed_loop.py`` the file is two things: run as a script it
IS the load generator (one thread, ``selectors``, non-blocking sockets,
every request stamped before it is written and every reply after it is
read, on ``CLOCK_MONOTONIC``; no JAX, nothing of the program; ``P <n>``
every 100 ms, ``stop`` on stdin, ``--grace`` seconds for what is
outstanding, the sample to ``--out``); imported, ``build`` starts that
child and turns its sample file into the harness's ``Sample``.

Everything comes from the seed. Connection ``c``'s request ``j`` writes
the keys numbered ``c * 10**8 + j * pairs + p`` (``p`` below ``pairs``:
never repeated, unique across connections), and each value is its key's
number under a prefix drawn for the request from
``random.Random(f"multiset:{seed}:{c}")``, so no two values are alike
and the parent rebuilds every acknowledged request from ``(c, j)``
alone (:func:`request_pairs`). ``Sample.acked`` is the list of those
``(c, j)`` in the order acknowledged: the plain reference
(``perfbench/reference/multiset_dict.py``) is fed from it.
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

PROGRESS_S = 0.1
MAX_RECONNECTS = 5          # per connection, after the shim severs it
OK, FAILED, UNRESOLVED = 1, 2, 0
CONN_STRIDE = 10 ** 8       # key numbers a connection owns
PREFIX_BELOW = 10 ** 89     # a value's seeded prefix, above its key's number


def prefix_stream(seed: int, conn: int):
    rng = random.Random(f"multiset:{seed}:{conn}")
    while True:
        yield rng.randrange(PREFIX_BELOW) * 10 ** 10


def request_pairs(p: dict, conn: int, j: int, prefix: int) -> list:
    """-> [(key, value)] of connection ``conn``'s request ``j``."""
    kf, vf = p["key_format"].encode(), p["value_format"].encode()
    first = conn * CONN_STRIDE + j * p["pairs"]
    return [(kf % n, vf % (prefix + n))
            for n in range(first, first + p["pairs"])]


def request_line(pairs: list) -> bytes:
    return b"multi_set " + b" ".join(k + b" " + v for k, v in pairs) + b"\n"


# ---------------------------------------------------------------------------
# the child
# ---------------------------------------------------------------------------

class _Conn:
    __slots__ = ("cid", "sock", "prefixes", "next_j", "buf", "op",
                 "t_reply", "reconnects")

    def __init__(self, cid, prefixes):
        self.cid, self.prefixes = cid, prefixes
        self.sock = None
        self.next_j = 0
        self.buf = b""
        self.op = -1            # index into the op arrays, -1 = idle
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
    want_reply = b"+OK %d" % p["pairs"]

    mono = time.monotonic
    sel = selectors.DefaultSelector()
    # one row per request, in the order sent
    op_conn, op_idx = array.array("i"), array.array("q")
    op_send, op_recv = array.array("d"), array.array("d")
    op_state = array.array("b")
    turnaround = array.array("d")       # reply read -> next request written
    cpu_t, cpu_s = array.array("d"), array.array("d")
    done = 0

    def connect(c: _Conn) -> None:
        s = socket.create_connection((a.host, a.port), timeout=30)
        s.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        s.setblocking(False)
        c.sock, c.buf = s, b""
        sel.register(s, selectors.EVENT_READ, c)

    def send_next(c: _Conn) -> None:
        j = c.next_j
        c.next_j += 1
        line = request_line(request_pairs(p, c.cid, j, next(c.prefixes)))
        c.op = len(op_send)
        op_conn.append(c.cid)
        op_idx.append(j)
        op_recv.append(0.0)
        op_state.append(UNRESOLVED)
        t = mono()
        op_send.append(t)
        if c.t_reply:
            turnaround.append(t - c.t_reply)
        try:
            c.sock.sendall(line)        # far below the socket buffer
        except OSError:
            sever(c)

    def sever(c: _Conn) -> None:
        """The request in flight is lost: count it failed, reconnect
        (bounded) and go on with the next keys."""
        if c.op >= 0:
            op_state[c.op] = FAILED
            c.op = -1
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

    conns = [_Conn(cid, prefix_stream(a.seed, cid))
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
            c.buf += data
            while b"\n" in c.buf and c.op >= 0:
                line, c.buf = c.buf.split(b"\n", 1)
                t = mono()
                op_recv[c.op] = t
                op_state[c.op] = OK if line.strip() == want_reply else FAILED
                c.op, c.t_reply = -1, t
                done += 1
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

def acknowledged(p: dict, seed: int, acked: list):
    """The acknowledged requests ``[(conn, j)]``, in that order, each
    as its ``[(key, value)]``: what the plain reference is fed."""
    upto = {}
    for conn, j in acked:
        upto[conn] = max(upto.get(conn, -1), j)
    prefix = {}
    for conn, top in upto.items():
        stream = prefix_stream(seed, conn)
        for j in range(top + 1):
            prefix[conn, j] = next(stream)
    for conn, j in acked:
        yield request_pairs(p, conn, j, prefix[conn, j])


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
        """Tell the child to stop, wait for it, build the Sample."""
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
        return Sample(
            completions=[recv[k] for k in in_window],
            latencies_ms=[(recv[k] - send[k]) * 1e3 for k in in_window],
            all_completions=[recv[k] for k in ok],
            attempted=len(sent_in),
            failed=sum(1 for k in sent_in if state[k] != OK),
            unresolved_total=n - len(ok),
            acked=[(cols["conn"][k], cols["idx"][k]) for k in ok],
            report=report)


def build(params: dict, deployment, ctx) -> Generator:
    return Generator(params, deployment, ctx)


if __name__ == "__main__":
    sys.exit(child_main(sys.argv[1:]))
