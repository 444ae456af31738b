#!/usr/bin/env python3
"""YCSB core workloads over the Redis binding's commands, closed loop, in
a process of its own.

What ``ycsb load redis`` and ``ycsb run redis`` send for a workload of
reads and updates (``workloads/workloada`` with ``CoreWorkload``'s
defaults): the load phase inserts ``recordcount`` records, each ONE
``HMSET user<h> field0 <v> ... field9 <v>`` (the binding's ``insert``;
its ``ZADD`` into the scan index is left out: nothing scans), then every
connection draws operations until told to stop: a read is ``HGETALL
user<h>`` (``readallfields=true``), an update ``HMSET user<h> field<j>
<v>`` with ``j`` uniform (``writeallfields=false``). Keys are zipfian
over the records, the ranks scrambled by FNV-1a 64. The commands are
Redis inline commands; ``native/toyserver`` answers ``HGETALL`` on one
line.

Like ``resp_closed_loop.py`` the file is two things: run as a script it
IS the load generator (one thread, ``selectors``, every request stamped
before it is written and every reply after it is read, on
``CLOCK_MONOTONIC``; no JAX, nothing of the program); imported, ``build``
starts that child and turns its sample file into the harness's
``Sample``, with the table of operations the plain reference
(``perfbench/reference/ycsb_register.py``) is handed as ``sample.ops()``.

Everything comes from the seed: connection ``c`` draws its keys, kinds,
fields and values from ``random.Random(f"ycsb:{seed}:{c}")``, one
operation after the other, so the parent regenerates every written
value from the table's kinds alone. A value starts with its writer's
connection and index (``c<conn>i<index>f<field>.``): no two are equal.
"""

from __future__ import annotations

import array
import bisect
import json
import os
import random
import selectors
import socket
import subprocess
import sys
import time

PROGRESS_S = 0.1
MAX_RECONNECTS = 5          # per connection, after the shim severs it
# states of an operation; a write that is SEVERED or UNRESOLVED may or
# may not have been applied, one that FAILED (an error reply) was not
UNRESOLVED, OK, FAILED, SEVERED = 0, 1, 2, 3
INSERT, UPDATE, READ = 0, 1, 2
FNV_OFFSET, FNV_PRIME = 0xCBF29CE484222325, 0x100000001B3


def fnv1a64(n: int) -> int:
    h = FNV_OFFSET
    for _ in range(8):
        h = ((h ^ (n & 0xFF)) * FNV_PRIME) & 0xFFFFFFFFFFFFFFFF
        n >>= 8
    return h


def key_of(record: int) -> bytes:
    return b"user%d" % fnv1a64(record)


def field_of(j: int) -> bytes:
    return b"field%d" % j


class Keys:
    """Record numbers by popularity: rank ``i`` (probability proportional
    to ``1 / (i + 1) ** theta``) is the record with the ``i``-th smallest
    FNV-1a 64 hash, so the hot records are spread over the key space and
    the distribution over records is exactly zipfian."""

    def __init__(self, recordcount: int, theta: float):
        self.by_rank = sorted(range(recordcount), key=fnv1a64)
        total, self.cdf = 0.0, []
        for i in range(recordcount):
            total += 1.0 / (i + 1) ** theta
            self.cdf.append(total)
        self.total = total

    def draw(self, rng: random.Random) -> int:
        return self.by_rank[bisect.bisect_left(self.cdf,
                                               rng.random() * self.total)]


def value(rng: random.Random, conn: int, idx: int, field: int,
          nbytes: int) -> bytes:
    head = b"c%di%df%d." % (conn, idx, field)
    n = nbytes - len(head)
    return head + b"%0*x" % (n, rng.getrandbits(4 * n))


class Stream:
    """Connection ``conn``'s operations, in order: first its share of the
    load (records ``conn, conn + conns, ...``), then the mix."""

    def __init__(self, p: dict, seed: int, conn: int, keys: Keys):
        self.p, self.conn, self.keys = p, conn, keys
        self.rng = random.Random(f"ycsb:{seed}:{conn}")
        self.idx = 0
        self.to_load = list(range(conn, p["recordcount"],
                                  p["connections"]))[::-1]

    def next_insert(self):
        """-> (kind, record, field, {field: value}) or None: loaded."""
        if not self.to_load:
            return None
        rec = self.to_load.pop()
        vals = {j: value(self.rng, self.conn, self.idx, j,
                         self.p["fieldlength"])
                for j in range(self.p["fieldcount"])}
        self.idx += 1
        return INSERT, rec, -1, vals

    def next_mixed(self):
        rng = self.rng
        rec = self.keys.draw(rng)
        if rng.random() < self.p["readproportion"]:
            self.idx += 1
            return READ, rec, -1, {}
        j = rng.randrange(self.p["fieldcount"])
        vals = {j: value(rng, self.conn, self.idx, j,
                         self.p["fieldlength"])}
        self.idx += 1
        return UPDATE, rec, j, vals

    def replay(self, kind: int):
        """The parent's side: the operation of ``kind`` this stream sent
        next (the table says which kind it was)."""
        return self.next_insert() if kind == INSERT else self.next_mixed()


def request_line(kind: int, rec: int, vals: dict) -> bytes:
    if kind == READ:
        return b"HGETALL %s\n" % key_of(rec)
    return b"HMSET %s %s\n" % (key_of(rec), b" ".join(
        b"%s %s" % (field_of(j), v) for j, v in sorted(vals.items())))


# ---------------------------------------------------------------------------
# the child
# ---------------------------------------------------------------------------

class _Conn:
    __slots__ = ("cid", "sock", "stream", "buf", "op", "t_reply",
                 "reconnects")

    def __init__(self, cid, stream):
        self.cid, self.stream = cid, stream
        self.sock = None
        self.buf = b""
        self.op = -1            # index into the op arrays, -1 = idle
        self.t_reply = 0.0
        self.reconnects = 0


def child_main(argv) -> int:
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, required=True)
    ap.add_argument("--params", required=True)     # the mix, as JSON
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--out", required=True)
    a = ap.parse_args(argv)
    p = json.loads(a.params)

    mono = time.monotonic
    sel = selectors.DefaultSelector()
    # one row per request, in the order sent
    op_conn, op_kind = array.array("i"), array.array("b")
    op_rec, op_field = array.array("i"), array.array("i")
    op_send, op_recv = array.array("d"), array.array("d")
    op_state = array.array("b")
    op_reply = array.array("q")         # offset of a read's reply, or -1
    replies = []                        # the reads' reply lines
    reply_at = 0
    turnaround = array.array("d")       # reply read -> next request written
    cpu_t, cpu_s = array.array("d"), array.array("d")
    done = loaded = 0
    keys = Keys(p["recordcount"], p["zipfian_constant"])

    def connect(c: _Conn) -> None:
        s = socket.create_connection((a.host, a.port), timeout=30)
        s.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        s.setblocking(False)
        c.sock, c.buf = s, b""
        sel.register(s, selectors.EVENT_READ, c)

    def send_next(c: _Conn) -> None:
        nxt = c.stream.next_insert()
        if nxt is None:
            if loaded < p["recordcount"]:
                return          # the others are still loading: wait
            nxt = c.stream.next_mixed()
        kind, rec, field, vals = nxt
        line = request_line(kind, rec, vals)
        c.op = len(op_send)
        op_conn.append(c.cid)
        op_kind.append(kind)
        op_rec.append(rec)
        op_field.append(field)
        op_recv.append(0.0)
        op_state.append(UNRESOLVED)
        op_reply.append(-1)
        t = mono()
        op_send.append(t)
        if c.t_reply:
            turnaround.append(t - c.t_reply)
        try:
            c.sock.sendall(line)        # far below the socket buffer
        except OSError:
            sever(c)

    def sever(c: _Conn) -> None:
        """The request in flight may or may not have been applied;
        reconnect (bounded) and go on with the next operation."""
        if c.op >= 0:
            op_state[c.op] = SEVERED
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

    conns = [_Conn(cid, Stream(p, a.seed, cid, keys))
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
                stopping, deadline = True, mono() + p["grace_s"]
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
                k = c.op
                op_recv[k] = t
                kind = op_kind[k]
                if kind == READ and not line.startswith(b"-ERR"):
                    op_state[k] = OK
                    op_reply[k] = reply_at
                    replies.append(line)
                    reply_at += len(line) + 1
                elif kind != READ and line == b"+OK":
                    op_state[k] = OK
                    loaded += kind == INSERT
                else:
                    op_state[k] = FAILED
                    if kind == INSERT:
                        # an app without the hash commands, or a full
                        # table: there is no workload without the load
                        sys.stderr.write(
                            "resp_ycsb: the app answered an insert with "
                            "%r\n" % line[:80])
                        return 4
                c.op, c.t_reply = -1, t
                done += 1
                if stopping:
                    continue
                if kind == INSERT and loaded == p["recordcount"]:
                    for other in conns:     # the load is in: all start
                        if other.op < 0 and other.sock is not None:
                            send_next(other)
                else:
                    send_next(c)

    tm = os.times()
    cpu_t.append(mono())
    cpu_s.append(tm.user + tm.system)
    for c in conns:
        if c.sock is not None:
            c.sock.close()
    blob = b"".join(r + b"\n" for r in replies)
    header = dict(n_ops=len(op_send), n_turnaround=len(turnaround),
                  n_cpu=len(cpu_t), n_reply_bytes=len(blob),
                  reconnects=sum(c.reconnects for c in conns))
    tmp = a.out + ".tmp"
    with open(tmp, "wb") as f:
        f.write(json.dumps(header).encode() + b"\n")
        for arr in (op_conn, op_kind, op_rec, op_field, op_send, op_recv,
                    op_state, op_reply, turnaround, cpu_t, cpu_s):
            arr.tofile(f)
        f.write(blob)
    os.replace(tmp, a.out)
    out.write("D %d\n" % done)
    out.flush()
    return 0


# ---------------------------------------------------------------------------
# the parent's side
# ---------------------------------------------------------------------------

def read_sample_file(path: str) -> dict:
    with open(path, "rb") as f:
        header = json.loads(f.readline())
        n, nt, nc = header["n_ops"], header["n_turnaround"], header["n_cpu"]
        cols = {}
        for name, code, count in (("conn", "i", n), ("kind", "b", n),
                                  ("rec", "i", n), ("field", "i", n),
                                  ("send", "d", n), ("recv", "d", n),
                                  ("state", "b", n), ("reply", "q", n),
                                  ("turnaround", "d", nt),
                                  ("cpu_t", "d", nc), ("cpu_s", "d", nc)):
            arr = array.array(code)
            arr.fromfile(f, count)
            cols[name] = arr
        cols["blob"] = f.read(header["n_reply_bytes"])
    cols["header"] = header
    return cols


def operations(cols: dict, p: dict, seed: int) -> list:
    """The table the reference is handed: one dict an operation, in the
    order sent, with the values regenerated from the seed: ``key``,
    ``kind`` (``"write"`` or ``"read"``), ``fields`` (a write: what it
    set; a read: the reply line, unparsed, under ``reply``), ``t_req``,
    ``t_rep``, ``state``."""
    keys = Keys(p["recordcount"], p["zipfian_constant"])
    streams = {}
    blob, out = cols["blob"], []
    for k in range(cols["header"]["n_ops"]):
        conn, kind = cols["conn"][k], cols["kind"][k]
        if conn not in streams:
            streams[conn] = Stream(p, seed, conn, keys)
        drawn, rec, _field, vals = streams[conn].replay(kind)
        if (drawn, rec) != (kind, cols["rec"][k]):
            raise RuntimeError(
                f"operation {k}: the child sent kind {kind} on record "
                f"{cols['rec'][k]}, the seed gives {drawn} on {rec}")
        op = dict(key=key_of(rec), t_req=cols["send"][k],
                  t_rep=cols["recv"][k], state=cols["state"][k])
        if kind == READ:
            op["kind"] = "read"
            at = cols["reply"][k]
            op["reply"] = (blob[at:blob.index(b"\n", at)]
                           if at >= 0 else None)
        else:
            op["kind"] = "write"
            op["fields"] = {field_of(j): v for j, v in vals.items()}
        out.append(op)
    return out


class Generator:
    """Starts the child against the deployment's client endpoint."""

    def __init__(self, params: dict, deployment, ctx):
        self.p, self.ctx = params, ctx
        self.host, self.port = deployment.client_endpoint()
        self.out = os.path.join(ctx.workdir, "generator_sample.bin")
        self.proc = None
        self._done = 0
        self._tail = b""

    def start(self) -> None:
        self.proc = subprocess.Popen(
            [sys.executable, os.path.abspath(__file__),
             "--host", self.host, "--port", str(self.port),
             "--params", json.dumps(self.p),
             "--seed", str(self.ctx.seed), "--out", self.out],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE)
        self.ctx.children.append(self.proc)
        os.set_blocking(self.proc.stdout.fileno(), False)

    def completed(self) -> int:
        """Replies the child has read so far (drains its progress
        lines; never blocks)."""
        try:
            data = os.read(self.proc.stdout.fileno(), 1 << 16)
        except BlockingIOError:
            data = b""
        if data:
            lines = (self._tail + data).split(b"\n")
            self._tail = lines.pop()
            for ln in lines:
                if ln[:2] in (b"P ", b"D "):
                    self._done = int(ln[2:])
        if self.proc.poll() not in (None, 0):
            raise RuntimeError(
                f"load generator exited with {self.proc.returncode}")
        return self._done

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
        cols = read_sample_file(self.out)
        n = cols["header"]["n_ops"]
        send, recv, state = cols["send"], cols["recv"], cols["state"]
        in_window = [k for k in range(n)
                     if state[k] == OK and t_open <= recv[k] < t_close]
        sent_in = [k for k in range(n) if t_open <= send[k] < t_close]
        failed = sum(1 for k in sent_in if state[k] != OK)
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
            all_completions=sorted(recv[k] for k in range(n)
                                   if state[k] == OK),
            attempted=len(sent_in), failed=failed,
            unresolved_total=sum(1 for k in range(n) if state[k] != OK),
            acked={}, report=report)
        # the reference's table (built when the check asks for it: it is
        # a second of Python, and this process also steps an idle group)
        # and which of its rows the window holds
        sample.ops = lambda: operations(cols, self.p, self.ctx.seed)
        sample.in_window = in_window
        sample.keys = Keys(self.p["recordcount"],
                           self.p["zipfian_constant"])
        return sample


def build(params: dict, deployment, ctx) -> Generator:
    return Generator(params, deployment, ctx)


if __name__ == "__main__":
    sys.exit(child_main(sys.argv[1:]))
