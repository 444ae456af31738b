#!/usr/bin/env python3
"""Closed-loop SET load over TCP, in a process of its own.

``redis-benchmark -t set -c <conns> -P 1``'s traffic: ``conns``
connections to one server, each with one ``SET k<conn>-<i> <value>``
outstanding, the next one written the moment the ``+OK`` is read. The
commands are Redis inline commands, which ``native/toyserver`` and a
real Redis both take.

The file is two things:

* run as a script it IS the load generator: one thread, ``selectors``,
  non-blocking sockets, every request and reply stamped on
  ``CLOCK_MONOTONIC`` (system-wide, so the parent's window stamps are
  on the same clock). It imports nothing of JAX or of the program, so
  it may run beside the process that owns the chip, and its Python
  does not share that process's interpreter lock. It prints ``P <n>``
  (replies so far) every 100 ms, stops sending when it reads ``stop``
  on stdin, waits ``--grace`` seconds for what is outstanding, writes
  its sample to ``--out`` and exits;
* imported by the harness, ``build`` starts that child and turns its
  sample file into the harness's ``Sample``.

Values come from the seed: connection ``c`` draws its ``i``-th value
from ``random.Random(f"set:{seed}:{c}")``, so the parent rebuilds the
dict of what was acknowledged from the counts alone.
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

ALPHABET = "abcdefghijklmnopqrstuvwxyz0123456789"
PROGRESS_S = 0.1
MAX_RECONNECTS = 5          # per connection, after the shim severs it
OK, FAILED, UNRESOLVED = 1, 2, 0


def value_stream(seed: int, conn: int, value_bytes: int):
    rng = random.Random(f"set:{seed}:{conn}")
    while True:
        yield "".join(rng.choice(ALPHABET)
                      for _ in range(value_bytes)).encode()


def key_of(conn: int, i: int) -> bytes:
    return b"k%d-%d" % (conn, i)


# ---------------------------------------------------------------------------
# the child
# ---------------------------------------------------------------------------

class _Conn:
    __slots__ = ("cid", "sock", "values", "next_i", "buf", "op",
                 "t_reply", "reconnects")

    def __init__(self, cid, values):
        self.cid, self.values = cid, values
        self.sock = None
        self.next_i = 0
        self.buf = b""
        self.op = -1            # index into the op arrays, -1 = idle
        self.t_reply = 0.0
        self.reconnects = 0


def child_main(argv) -> int:
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, required=True)
    ap.add_argument("--conns", type=int, required=True)
    ap.add_argument("--value-bytes", type=int, required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--grace", type=float, required=True)
    ap.add_argument("--out", required=True)
    a = ap.parse_args(argv)

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
        i = c.next_i
        c.next_i += 1
        line = b"SET %s %s\n" % (key_of(c.cid, i), next(c.values))
        c.op = len(op_send)
        op_conn.append(c.cid)
        op_idx.append(i)
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
        (bounded) and go on with the next key."""
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

    conns = [_Conn(cid, value_stream(a.seed, cid, a.value_bytes))
             for cid in range(a.conns)]
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
                stopping, deadline = True, mono() + a.grace
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
                op_state[c.op] = OK if line.strip() == b"+OK" else FAILED
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

def read_sample_file(path: str) -> dict:
    with open(path, "rb") as f:
        header = json.loads(f.readline())
        n, nt, nc = header["n_ops"], header["n_turnaround"], header["n_cpu"]
        cols = {}
        for name, code, count in (("conn", "i", n), ("idx", "q", n),
                                  ("send", "d", n), ("recv", "d", n),
                                  ("state", "b", n),
                                  ("turnaround", "d", nt),
                                  ("cpu_t", "d", nc), ("cpu_s", "d", nc)):
            arr = array.array(code)
            arr.fromfile(f, count)
            cols[name] = arr
    cols["header"] = header
    return cols


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
             "--conns", str(self.p["connections"]),
             "--value-bytes", str(self.p["value_bytes"]),
             "--seed", str(self.ctx.seed),
             "--grace", str(self.p["grace_s"]),
             "--out", self.out],
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
        grace = float(self.p["grace_s"])
        try:
            self.proc.wait(timeout=grace + 30)
        except subprocess.TimeoutExpired:
            self.proc.kill()
            self.proc.wait()
            raise RuntimeError("load generator did not stop")
        if self.proc.returncode != 0:
            raise RuntimeError(
                f"load generator exited with {self.proc.returncode}")
        cols = read_sample_file(self.out)
        n = cols["header"]["n_ops"]
        acked, per_conn = {}, {}
        for k in range(n):
            if cols["state"][k] == OK:
                per_conn.setdefault(cols["conn"][k], set()).add(
                    cols["idx"][k])
        for conn, idxs in per_conn.items():
            vs = value_stream(self.ctx.seed, conn, self.p["value_bytes"])
            for i in range(max(idxs) + 1):
                v = next(vs)
                if i in idxs:
                    acked[key_of(conn, i)] = v
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
        return Sample(
            completions=[recv[k] for k in in_window],
            latencies_ms=[(recv[k] - send[k]) * 1e3 for k in in_window],
            all_completions=sorted(recv[k] for k in range(n)
                                   if state[k] == OK),
            attempted=len(sent_in), failed=failed,
            unresolved_total=sum(1 for k in range(n) if state[k] != OK),
            acked=acked, report=report)


def build(params: dict, deployment, ctx) -> Generator:
    return Generator(params, deployment, ctx)


if __name__ == "__main__":
    sys.exit(child_main(sys.argv[1:]))
