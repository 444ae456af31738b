#!/usr/bin/env python3
"""``resp_ycsb``'s workload through a cluster-aware client: what YCSB's
Redis binding does with ``redis.cluster=true``, where ``JedisCluster``
keeps a connection per master and sends every key to the master that
owns it.

The keyspace is split over G replication groups. Each of the mix's
``connections`` client threads holds one connection PER GROUP, to the
app of the replica that leads that group, opened at start (``Jedis``
pools lazily), and has ONE operation outstanding at a time, on the
connection of its key's group: the closed loop is the thread's, as in
YCSB, not the connection's. Everything else is ``resp_ycsb``'s and is
imported from it: the operations and their order from the seed
(``Stream``), the keys and their skew (``Keys``), the request lines, the
sample file, the table of operations the reference is handed.

Which group owns a record is NOT decided here. The deployment hands
over the program's own routing as a table, record -> group, and the
groups' endpoints (``deployment.group_of_key``, ``group_endpoints``);
the parent writes both into a plan file for the child. The child
counts completions per group and says so in the sample file's header;
the parent holds that against the table.

Run as a script it IS the load generator (one thread, ``selectors``, no
JAX, nothing of the program), like the other two.
"""

from __future__ import annotations

import array
import json
import os
import selectors
import socket
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if __name__ == "__main__":
    sys.path.insert(0, ROOT)

from perfbench.generators import resp_ycsb  # noqa: E402
from perfbench.generators.resp_ycsb import (  # noqa: E402
    FAILED, INSERT, MAX_RECONNECTS, OK, PROGRESS_S, READ, SEVERED,
    UNRESOLVED, Keys, Stream, key_of, request_line)


# ---------------------------------------------------------------------------
# the child
# ---------------------------------------------------------------------------

class _Thread:
    """One client thread: a stream of operations, a connection a group,
    one operation outstanding."""
    __slots__ = ("tid", "stream", "socks", "bufs", "op", "op_group",
                 "t_reply", "reconnects")

    def __init__(self, tid, stream, n_groups):
        self.tid, self.stream = tid, stream
        self.socks = [None] * n_groups
        self.bufs = [b""] * n_groups
        self.op = -1            # index into the op arrays, -1 = idle
        self.op_group = -1
        self.t_reply = 0.0
        self.reconnects = 0


def child_main(argv) -> int:
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--plan", required=True)    # endpoints and the table
    ap.add_argument("--params", required=True)  # the mix, as JSON
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--out", required=True)
    a = ap.parse_args(argv)
    p = json.loads(a.params)
    with open(a.plan) as f:
        plan = json.load(f)
    endpoints, table = plan["endpoints"], plan["table"]
    G = len(endpoints)

    mono = time.monotonic
    sel = selectors.DefaultSelector()
    op_conn, op_kind = array.array("i"), array.array("b")
    op_rec, op_field = array.array("i"), array.array("i")
    op_send, op_recv = array.array("d"), array.array("d")
    op_state = array.array("b")
    op_reply = array.array("q")
    replies = []
    reply_at = 0
    turnaround = array.array("d")
    cpu_t, cpu_s = array.array("d"), array.array("d")
    done = loaded = 0
    by_group = [0] * G
    keys = Keys(p["recordcount"], p["zipfian_constant"])
    stopping = False

    def connect(t: _Thread, g: int) -> None:
        s = socket.create_connection(tuple(endpoints[g]), timeout=30)
        s.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        s.setblocking(False)
        t.socks[g], t.bufs[g] = s, b""
        sel.register(s, selectors.EVENT_READ, (t, g))

    def send_next(t: _Thread) -> None:
        nxt = t.stream.next_insert()
        if nxt is None:
            if loaded < p["recordcount"]:
                return          # the others are still loading: wait
            nxt = t.stream.next_mixed()
        kind, rec, field, vals = nxt
        g = table[rec]
        line = request_line(kind, rec, vals)
        t.op, t.op_group = len(op_send), g
        op_conn.append(t.tid)
        op_kind.append(kind)
        op_rec.append(rec)
        op_field.append(field)
        op_recv.append(0.0)
        op_state.append(UNRESOLVED)
        op_reply.append(-1)
        now = mono()
        op_send.append(now)
        if t.t_reply:
            turnaround.append(now - t.t_reply)
        try:
            t.socks[g].sendall(line)    # far below the socket buffer
        except (OSError, AttributeError):
            sever(t, g)

    def sever(t: _Thread, g: int) -> None:
        """The request in flight on that connection may or may not have
        been applied; reconnect (bounded) and go on."""
        hit = t.op >= 0 and t.op_group == g
        if hit:
            op_state[t.op] = SEVERED
            t.op = t.op_group = -1
        s = t.socks[g]
        if s is not None:
            try:
                sel.unregister(s)
            except (KeyError, ValueError):
                pass
            s.close()
            t.socks[g] = None
        t.t_reply = 0.0
        if t.reconnects < MAX_RECONNECTS and not stopping:
            t.reconnects += 1
            time.sleep(0.2)
            connect(t, g)
            if hit:
                send_next(t)

    threads = [_Thread(tid, Stream(p, a.seed, tid, keys), G)
               for tid in range(p["connections"])]
    for t in threads:
        for g in range(G):
            connect(t, g)
    os.set_blocking(sys.stdin.fileno(), False)
    sel.register(sys.stdin, selectors.EVENT_READ, None)
    for t in threads:
        send_next(t)

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
        if stopping and (all(t.op < 0 for t in threads) or now >= deadline):
            break
        for key, _ in sel.select(timeout=max(0.0, next_progress - mono())):
            if key.data is None:
                try:
                    os.read(sys.stdin.fileno(), 4096)
                except BlockingIOError:
                    continue
                # "stop" or EOF (the parent died): either ends the load
                stopping, deadline = True, mono() + p["grace_s"]
                sel.unregister(sys.stdin)
                continue
            t, g = key.data
            try:
                data = t.socks[g].recv(65536)
            except BlockingIOError:
                continue
            except (OSError, AttributeError):
                data = b""
            if not data:
                sever(t, g)
                continue
            t.bufs[g] += data
            while b"\n" in t.bufs[g] and t.op >= 0 and t.op_group == g:
                line, t.bufs[g] = t.bufs[g].split(b"\n", 1)
                now = mono()
                k = t.op
                op_recv[k] = now
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
                        sys.stderr.write(
                            "resp_ycsb_cluster: the app answered an "
                            "insert with %r\n" % line[:80])
                        return 4
                by_group[g] += op_state[k] == OK
                t.op, t.op_group, t.t_reply = -1, -1, now
                done += 1
                if stopping:
                    continue
                if kind == INSERT and loaded == p["recordcount"]:
                    for other in threads:   # the load is in: all start
                        if other.op < 0:
                            send_next(other)
                else:
                    send_next(t)

    tm = os.times()
    cpu_t.append(mono())
    cpu_s.append(tm.user + tm.system)
    for t in threads:
        for s in t.socks:
            if s is not None:
                s.close()
    blob = b"".join(r + b"\n" for r in replies)
    header = dict(n_ops=len(op_send), n_turnaround=len(turnaround),
                  n_cpu=len(cpu_t), n_reply_bytes=len(blob),
                  reconnects=sum(t.reconnects for t in threads),
                  completions_by_group=by_group)
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

class Generator(resp_ycsb.Generator):
    """``resp_ycsb``'s parent, aimed at a group's leader's app for each
    group; the sample it hands back says which group owns which key and
    what share of the window's completions the busiest group took."""

    def __init__(self, params: dict, deployment, ctx):
        super().__init__(params, deployment, ctx)
        self.endpoints = [list(e) for e in deployment.group_endpoints()]
        self.table = [deployment.group_of_key(key_of(rec))
                      for rec in range(params["recordcount"])]
        self.plan = os.path.join(ctx.workdir, "generator_plan.json")

    def start(self) -> None:
        import subprocess
        with open(self.plan, "w") as f:
            json.dump(dict(endpoints=self.endpoints, table=self.table), f)
        self.proc = subprocess.Popen(
            [sys.executable, os.path.abspath(__file__),
             "--plan", self.plan, "--params", json.dumps(self.p),
             "--seed", str(self.ctx.seed), "--out", self.out],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE)
        self.ctx.children.append(self.proc)
        os.set_blocking(self.proc.stdout.fileno(), False)

    def stop(self, t_open: float, t_close: float):
        sample = super().stop(t_open, t_close)
        cols = resp_ycsb.read_sample_file(self.out)
        G = len(self.endpoints)
        in_window = [0] * G
        for k in sample.in_window:
            in_window[self.table[cols["rec"][k]]] += 1
        # the table's count of every acknowledged operation since the
        # start, beside the child's own (by the connection it used)
        by_table = [0] * G
        for k in range(cols["header"]["n_ops"]):
            if cols["state"][k] == OK:
                by_table[self.table[cols["rec"][k]]] += 1
        total = sum(in_window)
        sample.report.update(
            hot_group_ops_share=(100.0 * max(in_window) / total
                                 if total else None),
            window_completions_by_group=in_window,
            completions_by_group=cols["header"]["completions_by_group"],
            completions_by_table=by_table)
        sample.group_of = {key_of(rec): g
                           for rec, g in enumerate(self.table)}
        sample.n_groups = G
        return sample


def build(params: dict, deployment, ctx) -> Generator:
    return Generator(params, deployment, ctx)


if __name__ == "__main__":
    sys.exit(child_main(sys.argv[1:]))
