"""``interposed_app``'s deployment under clients that pipeline: a log
entry is sixteen requests, and connections write the same keys.

The deployment is ``interposed_app.Deployment`` (apps under the shim,
one ``ClusterDriver``, the followers' apps replayed to) but for the
app's arguments (``config["app"]["args"]``, after the port: its
table's size, and a read's answers joined into one write) and ``check``, which is a register
check: no client owns a key, two connections may write one at once,
and the log's order decides. All of it exact (limit 0), against the
plain reference ``perfbench/reference/set_register.py``:

* after a marker written through the leader's app has shown on every
  follower's (they replay in log order, so they then hold everything
  acknowledged before it), ``COUNT`` on every app (the followers' apart
  from the marker, each apart from the keep-alive's one key where that
  app says it holds it) lies between the keys an acknowledged ``SET``
  wrote and those plus the keys only unresolved ones wrote, and the
  three counts are equal;
* ``SAMPLE_KEYS`` seeded keys AND every key that two connections wrote
  within ``CONTENDED_S`` of each other inside the window (the later
  request written less than that after the earlier one's reply was
  read; at most ``CONTENDED_MAX`` of them, the earliest; the number is
  printed) are read back with ``GET`` from every app, the leader's
  first: each holds a value the reference admits (that of an
  acknowledged ``SET`` no acknowledged ``SET`` to the key strictly
  follows, or of an unresolved one), and the three apps answer alike.

Faults for the runs that show ``correct`` can come out false, each on
one follower's replay path: ``interposed_app``'s two
(``follower_drops_applies``, ``follower_alters_values``: they take a
write of many lines as it comes) and ``follower_swaps_same_key``: where
a ``SET`` follows, within ``SWAP_WITHIN_S``, another connection's
``SET`` of the same key, the follower's app ends as if it had applied
the two in the other order (the later one reaches it carrying the
earlier one's value): what an engine that let two connections' writes
overtake each other would leave behind.
"""

from __future__ import annotations

import collections
import os
import random
import socket
import subprocess
import time

from perfbench.deployments import interposed_app
from perfbench.generators.resp_pipelined import FAILED, OK, key_of
from perfbench.harness.keepalive import ABSENT, ask_count
from perfbench.reference import set_register as ref

SAMPLE_KEYS = 5000
CONTENDED_MAX = 5000
CONTENDED_S = 0.1
ASK_DEPTH = 64              # questions a write: the check pipelines too
FRONTIER_WAIT_S = 30
SWAP = "follower_swaps_same_key"
SWAP_WITHIN_S = 0.06


def app_sizes_its_table(binary: str) -> bool:
    """Ask a plain, unreplicated instance of the app, started with 256
    slots, to hold 300 keys."""
    port = interposed_app.free_ports(1)[0]
    app = subprocess.Popen([binary, str(port), "-s", "8"],
                           stderr=subprocess.DEVNULL)
    try:
        for _ in range(50):
            try:
                with socket.create_connection(("127.0.0.1", port),
                                              timeout=5) as s:
                    s.sendall(b"".join(b"SET k%d v\n" % i
                                       for i in range(300)))
                    f = s.makefile("rb")
                    return b"-ERR full" in {f.readline().strip()
                                            for _ in range(300)}
            except ConnectionRefusedError:
                if app.poll() is not None:
                    return False
                time.sleep(0.05)
        return False
    finally:
        app.kill()
        app.wait()


def ask_batched(conn, lines: list) -> list:
    """``conn.ask(lines)``, ``ASK_DEPTH`` questions a write."""
    conn.ask([])                        # opens it
    out = []
    for at in range(0, len(lines), ASK_DEPTH):
        part = lines[at:at + ASK_DEPTH]
        conn.sock.sendall(b"".join(ln + b"\n" for ln in part))
        # an app that answers a line a write: ask for the ACKs at once
        # (until this socket next sends), or its second answer waits
        # 40 ms for the first one's (Nagle's algorithm)
        conn.sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_QUICKACK, 1)
        for _ in part:
            reply = conn.file.readline()
            if not reply.endswith(b"\n"):
                raise OSError("connection closed mid-reply")
            out.append(reply.strip())
    return out


def contended_keys(w: dict, t_open: float, t_close: float) -> list:
    """Keys that two connections wrote within ``CONTENDED_S`` of each
    other inside the window, by the time of the first such pair."""
    import numpy as np
    key = np.asarray(w["key"], np.int64)
    conn = np.frombuffer(w["conn"], np.intc)
    t_req = np.frombuffer(w["t_req"], np.float64)
    t_rep = np.frombuffer(w["t_rep"], np.float64)
    state = np.frombuffer(w["state"], np.int8)
    rows = np.flatnonzero((state != FAILED) & (t_req >= t_open)
                          & (t_req < t_close))
    rows = rows[np.lexsort((t_req[rows], key[rows]))]
    a, b = rows[:-1], rows[1:]
    # an unresolved write's reply never came: it is concurrent with
    # whatever follows it
    a_rep = np.where(state[a] == OK, t_rep[a], np.inf)
    pair = ((key[a] == key[b]) & (conn[a] != conn[b])
            & (t_req[b] - a_rep < CONTENDED_S))
    first = {}
    for k, t in zip(key[b[pair]].tolist(), t_req[b[pair]].tolist()):
        first.setdefault(k, t)
    return sorted(first, key=first.get)


class Deployment(interposed_app.Deployment):

    def __init__(self, config: dict, ctx):
        super().__init__(config, ctx)
        binary = os.path.join(interposed_app.NATIVE, config["app"]["binary"])
        if not app_sizes_its_table(binary):
            # at once and before the chip is touched: a program whose
            # app cannot hold the keyspace cannot run this configuration
            raise SystemExit(
                f"perfbench: {binary} does not take its table's size "
                f"from the command line (-s): this program cannot run "
                f"configuration {config['name']!r}")

    def start(self) -> None:
        """``interposed_app.Deployment.start``, the app's arguments
        after its port."""
        from rdma_paxos_tpu.runtime.driver import ClusterDriver
        ctx = self.ctx
        app = self.config["app"]
        t0 = time.monotonic()
        self.ports = interposed_app.free_ports(self.R)
        self.driver = ClusterDriver(self.cfg, self.R, workdir=ctx.workdir,
                                    app_ports=self.ports,
                                    **self.driver_kwargs())
        ctx.part("driver", t0)
        t0 = time.monotonic()
        self.apps = []
        for r, port in enumerate(self.ports):
            env = dict(os.environ,
                       LD_PRELOAD=os.path.join(interposed_app.NATIVE,
                                               "interpose.so"),
                       RP_PROXY_SOCK=os.path.join(ctx.workdir,
                                                  f"proxy{r}.sock"))
            p = subprocess.Popen(
                [os.path.join(interposed_app.NATIVE, app["binary"]),
                 str(port)] + app["args"], env=env,
                stderr=subprocess.DEVNULL)
            self.apps.append(p)
            ctx.children.append(p)
        time.sleep(0.3)                     # let the apps bind
        if any(p.poll() is not None for p in self.apps):
            raise RuntimeError("an app exited at start-up")
        ctx.part("apps", t0)
        self.boot()

    # ---- correctness ------------------------------------------------

    def _check(self, conns, sample, seed: int) -> list:
        """First what to ask (the table of writes, drawn again from the
        seed), then everything the apps are asked, then the reference."""
        w = sample.writes()
        written = sorted({k for k, s in zip(w["key"], w["state"])
                          if s != FAILED})
        contended = contended_keys(w, *sample.window)
        numbers = contended[:CONTENDED_MAX]
        numbers += random.Random(f"sample:{seed}").sample(
            written, min(SAMPLE_KEYS, len(written)))
        numbers = list(dict.fromkeys(numbers))      # each key once
        questions = [b"GET " + key_of(k) for k in numbers]

        # the leader's app first: its answers are replicated requests,
        # and while they flow the group is busy and holds no election.
        # The followers' apply frontier trails the acks: a marker goes
        # through the leader's app, and a follower that shows it has
        # replayed everything the log holds before it
        lead = self.driver.leader()
        order = sorted(range(self.R), key=lambda r: r != lead)
        marker = b"frontier-%d" % seed
        counts = {lead: ask_count(conns[lead])}
        if conns[lead].ask([b"SET %s 1" % marker]) != [b"+OK"]:
            raise RuntimeError("the leader's app refused the marker")
        answers = {lead: ask_batched(conns[lead], questions)}
        deadline = time.monotonic() + FRONTIER_WAIT_S
        behind = set(order[1:])
        while behind and time.monotonic() < deadline:
            behind = {r for r in behind
                      if conns[r].ask([b"GET " + marker]) == [ABSENT]}
            if behind:
                time.sleep(0.02)
        for r in order[1:]:
            answers[r] = ask_batched(conns[r], questions)
            counts[r] = ask_count(conns[r]) - 1     # the marker
        self.raise_if_dead()

        asked = set(numbers)
        regs = ref.SetRegister(
            ((k, v, t_req, t_rep, ref.ACKED if s == OK else ref.UNRESOLVED)
             for k, v, t_req, t_rep, s in zip(w["key"], w["value"],
                                              w["t_req"], w["t_rep"],
                                              w["state"])
             if s != FAILED),           # an error reply: not done
            only=asked)
        least, most = regs.count_bounds()
        out = [dict(name=f"keys_r{r}",
                    what=f"keys held by replica {r}'s app apart from the "
                         f"keep-alive's and the marker", got=counts[r],
                    want=least,
                    limit=(f"the {least} keys of acknowledged SETs, and "
                           f"at most the {most - least} keys only "
                           f"unresolved ones wrote more"),
                    ok=least <= counts[r] <= most)
               for r in range(self.R)]
        out.append(dict(
            name="key_counts_differ",
            what=f"counts of the {self.R} apps that differ from the "
                 f"leader's app's",
            got=sum(1 for r in order[1:] if counts[r] != counts[lead]),
            want=0, limit="0 (exact)",
            ok=len(set(counts.values())) == 1))
        out.append(dict(
            name="apps_without_marker",
            what="apps that never showed the marker written after the run",
            got=sorted(behind), want=[], limit="0 (exact)", ok=not behind))
        got = {r: [None if a == ABSENT else a for a in answers[r]]
               for r in order}
        for r in order:
            faults = regs.faults(numbers, got[r])
            out.append(dict(
                name=f"inadmissible_values_r{r}",
                what=(f"of {len(numbers)} keys ({len(contended)} written "
                      f"by two connections within {CONTENDED_S} s, "
                      f"{min(len(contended), CONTENDED_MAX)} of them "
                      f"asked), those replica {r}'s app holds at a value "
                      f"the reference does not admit"),
                got=len(faults), want=0, limit="0 (exact)",
                ok=not faults and len(numbers) > 0, first=faults[:3]))
        differ = ref.apps_differ([got[r] for r in order])
        out.append(dict(
            name="values_apps_differ_on",
            what=f"of {len(numbers)} keys, those the {self.R} apps do "
                 f"not hold alike",
            got=differ, want=0, limit="0 (exact)", ok=differ == 0))
        out.append(dict(
            name="contended_keys",
            what=f"keys two connections wrote within {CONTENDED_S} s of "
                 f"each other in the window (told, not judged; at most "
                 f"{CONTENDED_MAX} asked)",
            got=len(contended), want="any", limit="none", ok=True))
        out.append(dict(
            name="ambiguous_keys",
            what="of the keys asked, those that may end on more than one "
                 "value (writes still concurrent at the end; told, not "
                 "judged)",
            got=regs.ambiguous(numbers), want="any", limit="none",
            ok=True))
        replay = [rt.replay for rt in self.driver.runtimes
                  if rt.replay is not None]
        out.append(dict(
            name="replay_order_timeouts",
            what="replayed writes whose answers did not all come in time "
                 "(told, not judged)",
            got=sum(e.order_timeouts for e in replay),
            want="any", limit="none", ok=True))
        return out

    # ---- faults, for the runs that show the check can fail ----------

    def inject(self, fault: str) -> None:
        """``follower_swaps_same_key``: see the module text; the others
        are ``interposed_app``'s."""
        from rdma_paxos_tpu.consensus.log import EntryType
        if fault != SWAP:
            return super().inject(fault)
        victim = next(r for r in range(self.R) if r != self.driver.leader())
        replay = self.driver.runtimes[victim].replay
        apply, send = replay.apply, int(EntryType.SEND)
        recent: collections.OrderedDict = collections.OrderedDict()

        def swapped(conn: int, line: bytes, now: float) -> bytes:
            parts = line.split(b" ")
            if len(parts) != 3 or parts[0] != b"SET":
                return line
            was = recent.pop(parts[1], None)
            if (was is not None and was[0] != conn
                    and now - was[2] < SWAP_WITHIN_S):
                parts[2] = was[1]       # as if the earlier came last
            recent[parts[1]] = (conn, parts[2], now)
            while recent and now - next(iter(recent.values()))[2] \
                    >= SWAP_WITHIN_S:
                recent.popitem(last=False)
            return b" ".join(parts)

        def faulty(etype, conn, payload):
            if etype == send:
                now = time.monotonic()
                payload = b"\n".join(swapped(conn, ln, now)
                                     for ln in payload.split(b"\n"))
            return apply(etype, conn, payload)
        replay.apply = faulty
        self.ctx.say("fault", f"{SWAP} on replica {victim}")


def build(config: dict, ctx) -> Deployment:
    return Deployment(config, ctx)
