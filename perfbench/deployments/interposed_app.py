"""An unmodified TCP server under ``LD_PRELOAD=native/interpose.so`` on
each replica, replicated by a ``ClusterDriver``: APUS's deployment.

``native/toyserver`` stands in for Redis (``SET``/``GET``/``COUNT``
over Redis inline commands). Clients talk to the leader's app; the shim
holds each request until the driver has committed it on a majority, and
the followers' apps get the committed stream replayed.

The read-back check is a copy of ``chip_smoke.py``'s: ``COUNT`` on all
apps against the number acknowledged, and a seeded sample of keys read
back from each against a plain dict of what was acknowledged.
"""

from __future__ import annotations

import os
import random
import socket
import subprocess
import time

from perfbench.deployments._driver_common import DriverDeployment
from perfbench.harness import spec

NATIVE = os.path.join(spec.ROOT, "native")
SAMPLE_KEYS = 200
FRONTIER_WAIT_S = 60
FRONTIER_STALL_S = 5
FAULTS = ("follower_drops_applies", "follower_alters_values")


def free_ports(n: int) -> list:
    socks = [socket.socket() for _ in range(n)]
    try:
        for s in socks:
            s.bind(("127.0.0.1", 0))
        return [s.getsockname()[1] for s in socks]
    finally:
        for s in socks:
            s.close()


class AppConn:
    """One connection to an app for the whole check (each connection to
    the leader's app is a replicated session: few of them, not one per
    question). A connection the shim severs is opened again."""

    def __init__(self, port: int, tries: int = 10):
        self.port, self.tries = port, tries
        self.sock = self.file = None

    def _open(self) -> None:
        self.close()
        self.sock = socket.create_connection(("127.0.0.1", self.port),
                                             timeout=60)
        self.file = self.sock.makefile("rb")

    def ask(self, lines: list) -> list:
        for attempt in range(self.tries):
            try:
                if self.sock is None:
                    self._open()
                out = []
                for ln in lines:
                    self.sock.sendall(ln + b"\n")
                    reply = self.file.readline()
                    if not reply.endswith(b"\n"):
                        raise OSError("connection closed mid-reply")
                    out.append(reply.strip())
                return out
            except OSError:
                self.close()
                if attempt == self.tries - 1:
                    raise
                time.sleep(0.5)

    def close(self) -> None:
        if self.sock is not None:
            self.file.close()
            self.sock.close()
            self.sock = self.file = None


class Deployment(DriverDeployment):

    def start(self) -> None:
        from rdma_paxos_tpu.runtime.driver import ClusterDriver
        ctx = self.ctx
        t0 = time.monotonic()
        self.ports = free_ports(self.R)
        self.driver = ClusterDriver(self.cfg, self.R, workdir=ctx.workdir,
                                    app_ports=self.ports,
                                    **self.driver_kwargs())
        ctx.part("driver", t0)
        t0 = time.monotonic()
        self.apps = []
        for r, port in enumerate(self.ports):
            env = dict(os.environ,
                       LD_PRELOAD=os.path.join(NATIVE, "interpose.so"),
                       RP_PROXY_SOCK=os.path.join(ctx.workdir,
                                                  f"proxy{r}.sock"))
            p = subprocess.Popen(
                [os.path.join(NATIVE, self.config["app"]["binary"]),
                 str(port)], env=env, stderr=subprocess.DEVNULL)
            self.apps.append(p)
            ctx.children.append(p)
        time.sleep(0.3)                     # let the apps bind
        if any(p.poll() is not None for p in self.apps):
            raise RuntimeError("an app exited at start-up")
        ctx.part("apps", t0)
        self.boot()

    def client_endpoint(self):
        return "127.0.0.1", self.ports[self.driver.leader()]

    # ---- correctness ------------------------------------------------

    def check(self, sample, seed: int) -> list:
        """Every acknowledged write must be in EVERY replica's app."""
        conns = [AppConn(p) for p in self.ports]
        try:
            return self._check(conns, sample, seed)
        except OSError as exc:
            raise RuntimeError(
                f"an app would not answer the check ({exc}); "
                f"{self.state_summary()}") from exc
        finally:
            for c in conns:
                c.close()

    def state_summary(self) -> str:
        d = self.driver
        last = d.cluster.last
        return (f"leader view {d.leader()}, terms "
                f"{None if last is None else last['term'].tolist()}, "
                f"app_dirty {[rt.app_dirty for rt in d.runtimes]}, "
                f"stepped_down {sorted(d.stepped_down)}, election "
                f"timeouts "
                f"{self.probe().get('counter.election_timeouts_total')}, "
                f"apps alive {[p.poll() is None for p in self.apps]}")

    def _check(self, conns, sample, seed: int) -> list:
        acked = sample.acked
        lo, hi = len(acked), len(acked) + sample.unresolved_total
        # the followers' apply frontier trails the acks: wait while the
        # counts still move, and FRONTIER_WAIT_S at most
        deadline = time.monotonic() + FRONTIER_WAIT_S
        counts, moved = None, time.monotonic()
        while True:
            now = [int(c.ask([b"COUNT"])[0]) for c in conns]
            if now != counts:
                counts, moved = now, time.monotonic()
            if (all(lo <= c <= hi for c in counts)
                    or time.monotonic() - moved > FRONTIER_STALL_S
                    or time.monotonic() > deadline):
                break
            time.sleep(0.2)
        out = [dict(what=f"keys held by replica {r}'s app", got=c, want=lo,
                    limit=(f"the {lo} acknowledged, and at most the "
                           f"{hi - lo} unresolved more"),
                    ok=lo <= c <= hi)
               for r, c in enumerate(counts)]
        keys = random.Random(f"sample:{seed}").sample(
            sorted(acked), min(SAMPLE_KEYS, len(acked)))
        # the leader's app first: its answers are replicated requests,
        # and one in flight when leadership moves quarantines the app;
        # while they flow the group is busy and holds no election
        lead = self.driver.leader()
        for r in sorted(range(self.R), key=lambda r: r != lead):
            got = conns[r].ask([b"GET " + k for k in keys])
            bad = sum(1 for k, g in zip(keys, got) if g != acked[k])
            out.append(dict(
                what=(f"of {len(keys)} sampled keys, those replica {r}'s "
                      f"app answers otherwise than acknowledged"),
                got=bad, want=0, limit="0 (exact)",
                ok=bad == 0 and len(keys) > 0))
        self.raise_if_dead()
        return out

    # ---- faults, for the runs that show the check can fail ----------

    def inject(self, fault: str) -> None:
        """``follower_drops_applies``: a follower's app misses every
        fourth replayed write (breaks "read back from every replica").
        ``follower_alters_values``: every value replayed to a follower's
        app has its first byte changed (an answer altered where it is
        produced; the key counts still agree)."""
        from rdma_paxos_tpu.consensus.log import EntryType
        if fault not in FAULTS:
            return super().inject(fault)
        victim = next(r for r in range(self.R) if r != self.driver.leader())
        replay = self.driver.runtimes[victim].replay
        apply, seen = replay.apply, [0]
        send = int(EntryType.SEND)

        def alter(line: bytes) -> bytes:
            parts = line.split(b" ")
            if len(parts) == 3 and parts[0] == b"SET" and parts[2]:
                parts[2] = bytes([parts[2][0] ^ 1]) + parts[2][1:]
            return b" ".join(parts)

        def faulty(etype, conn, payload):
            if etype == send and fault == "follower_drops_applies":
                kept = []
                for ln in payload.split(b"\n"):
                    seen[0] += bool(ln)
                    if not (ln and seen[0] % 4 == 0):
                        kept.append(ln)
                payload = b"\n".join(kept)
                if not payload.strip():
                    return None
            elif etype == send:
                payload = b"\n".join(map(alter, payload.split(b"\n")))
            return apply(etype, conn, payload)
        replay.apply = faulty
        self.ctx.say("fault", f"{fault} on replica {victim}")


def build(config: dict, ctx) -> Deployment:
    return Deployment(config, ctx)
