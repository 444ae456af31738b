"""``interposed_app_ycsb``'s apps under G consensus groups on the same R
replicas: ``ShardedClusterDriver(cfg, R, G, workdir, app_ports,
fanout="psum")``, group g led by replica g
(``cluster.place_leaders("round_robin")``), every replica's app leader
of one group and follower of the others. A client reaches a key through
the app of the replica that leads the key's group (the client contract
the configuration's ``guarantees`` state); the routing is the PROGRAM's
(``driver.router`` over ``driver._key_of``): this file asks it, and
hands the answers to the generator (``group_of_key``,
``group_endpoints``).

For the harness, leadership is ALL the groups': ``leader_term()`` is
``(0, ((leader, term) of every group))`` while group g is led by
replica g, and ``(-1, ...)`` on any other placement, so the harness's
equality test sees any group's leader or term move.

``check``, all of it exact (limit 0), against
``perfbench/reference/ycsb_register_cluster.py``:

* a marker written through EACH group's leader's app (the first
  request of its connection, which pins the connection to that group's
  log) has shown on every other app: an app that shows group g's has
  replayed everything g's log held before it;
* ``COUNT`` equals the mix's ``recordcount`` on every app, apart from
  the markers and from the keep-alive's key where the app holds it;
* the ``HOTTEST`` hottest and ``SAMPLE_KEYS`` seeded other records,
  read whole from every app: every field admissible, filed by the
  record's group (every app is asked for every group), and the apps'
  answers alike;
* every acknowledged read of the window current between request and
  reply;
* the generator's completions by group (counted by the connection it
  used) equal the routing table's count of the same operations.

Faults: ``interposed_app_ycsb``'s three, and ``group_replay_dropped``:
one replica's app misses every replayed write of ONE group it follows.
"""

from __future__ import annotations

import inspect
import os
import random
import socket
import subprocess
import time

from perfbench.deployments import interposed_app, interposed_app_ycsb
from perfbench.deployments._driver_common import ELECTION_FAULTS
from perfbench.deployments.interposed_app_ycsb import (
    FRONTIER_WAIT_S, HOTTEST, SAMPLE_KEYS)
from perfbench.generators.resp_ycsb import FAILED, OK, key_of
from perfbench.harness.keepalive import RESERVED_KEY, ask_count
from perfbench.reference import ycsb_register as ref
from perfbench.reference.ycsb_register_cluster import ClusterRegisters

GROUP_FAULT = "group_replay_dropped"


def check_shipped_group_timers(timers: dict) -> None:
    """A sharded driver's elections run on step-domain timers; the
    benchmark passes none, so the configuration must state the ones the
    program ships."""
    from rdma_paxos_tpu.runtime.sharded_driver import ShardedClusterDriver
    sig = inspect.signature(ShardedClusterDriver.__init__).parameters
    shipped = dict(group_timer_lo_steps=sig["group_timer_lo"].default,
                   group_timer_hi_steps=sig["group_timer_hi"].default)
    if timers != shipped:
        raise RuntimeError(
            f"configuration states timers {timers}, the program ships "
            f"{shipped}")


def app_holds_connections(binary: str, n: int) -> bool:
    """Ask a plain, unreplicated instance of the app ``n`` questions on
    ``n`` connections held open together: every front-end of this
    deployment holds its own group's clients and the other groups'
    replayed connections at once."""
    port = interposed_app.free_ports(1)[0]
    app = subprocess.Popen([binary, str(port)], stderr=subprocess.DEVNULL)
    socks = []
    try:
        for _ in range(50):
            try:
                socks.append(socket.create_connection(("127.0.0.1", port),
                                                      timeout=5))
                break
            except ConnectionRefusedError:
                time.sleep(0.05)
        try:
            while 0 < len(socks) < n:
                socks.append(socket.create_connection(("127.0.0.1", port),
                                                      timeout=5))
            for s in socks:
                s.sendall(b"COUNT\n")
            return bool(socks) and all(
                s.makefile("rb").readline().strip() == b"0" for s in socks)
        except OSError:
            return False
    finally:
        for s in socks:
            s.close()
        app.kill()
        app.wait()


def shipped_wall_clock_timers() -> dict:
    from rdma_paxos_tpu.config import TimeoutConfig
    t = TimeoutConfig()
    return dict(heartbeat_s=t.hb_period, election_low_s=t.elec_timeout_low,
                election_high_s=t.elec_timeout_high)


class Deployment(interposed_app_ycsb.Deployment):

    def __init__(self, config: dict, ctx):
        check_shipped_group_timers(config["timers"])
        # the base checks the wall-clock timers, which a sharded driver
        # builds and never polls: it is given the program's own
        super().__init__(dict(config, timers=shipped_wall_clock_timers()),
                         ctx)
        self.config = config
        self.G = int(config["groups"])
        binary = os.path.join(interposed_app.NATIVE, config["app"]["binary"])
        need = int(ctx.cell.traffic["connections"]) * self.G + 8
        if not app_holds_connections(binary, need):
            # at once and before the chip is touched, as the base does
            # for the hash commands
            raise SystemExit(
                f"perfbench: {binary} does not serve {need} connections "
                f"at once: this program cannot run configuration "
                f"{config['name']!r}")

    # ---- life cycle -------------------------------------------------

    def start(self) -> None:
        from rdma_paxos_tpu.runtime.sharded_driver import (
            ShardedClusterDriver)
        ctx = self.ctx
        t0 = time.monotonic()
        self.ports = interposed_app.free_ports(self.R)
        self.driver = ShardedClusterDriver(
            self.cfg, self.R, self.G, workdir=ctx.workdir,
            app_ports=self.ports, **self.driver_kwargs())
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
                [os.path.join(interposed_app.NATIVE,
                              self.config["app"]["binary"]), str(port)],
                env=env, stderr=subprocess.DEVNULL)
            self.apps.append(p)
            ctx.children.append(p)
        time.sleep(0.3)                     # let the apps bind
        if any(p.poll() is not None for p in self.apps):
            raise RuntimeError("an app exited at start-up")
        ctx.part("apps", t0)
        t0 = time.monotonic()
        # group g on replica g, before the loop starts (the step
        # programs compile or load here; the rest in boot's prewarm)
        self.driver.cluster.place_leaders("round_robin")
        ctx.part("place_leaders", t0)
        self.boot()
        timers = {(t.lo, t.hi) for t in self.driver._gtimers}
        want = (self.config["timers"]["group_timer_lo_steps"],
                self.config["timers"]["group_timer_hi_steps"])
        if timers != {want}:
            raise RuntimeError(f"group timers {timers}, stated {want}")

    def log_devices(self) -> list:
        """Where each (group, replica) ring row rests."""
        c = self.driver.cluster
        out = [[None] * c.R for _ in range(c.G)]
        for shard in c.state.log.buf.addressable_shards:
            for g in range(*shard.index[0].indices(c.G)):
                for r in range(*shard.index[1].indices(c.R)):
                    out[g][r] = str(shard.device)
        return [f"g{g}:" + ",".join(row) for g, row in enumerate(out)]

    # ---- the program's routing, asked ---------------------------------

    def group_of_key(self, key: bytes) -> int:
        d = self.driver
        return d.router.group_of(d._key_of(b"HGETALL " + key + b"\n"))

    def group_endpoints(self) -> list:
        """(host, port) of the app of the replica that leads group g."""
        return [("127.0.0.1", self.ports[lead])
                for lead in self.driver.leaders()]

    def client_endpoint(self):
        return self.group_endpoints()[self.group_of_key(RESERVED_KEY)]

    def key_in_group(self, stem: bytes, g: int) -> bytes:
        return next(k for k in (b"%s%d" % (stem, i) for i in range(10000))
                    if self.group_of_key(k) == g)

    # ---- views ------------------------------------------------------

    def leader_term(self):
        last = self.driver.cluster.last
        leaders = self.driver.leaders()
        if last is None:
            return (-1, ())
        view = tuple((lead, int(last["term"][g, lead]) if lead >= 0 else -1)
                     for g, lead in enumerate(leaders))
        placed = leaders == [g % self.R for g in range(self.G)]
        return (min(leaders) if placed else -1, view)

    def probe(self):
        out = super().probe()
        stem = "group_acks_total{group="
        for key, v in self.driver.obs.metrics.snapshot()[
                "counters"].items():
            if key.startswith(stem):
                out["counter.group_acks_total.g" + key[len(stem):-1]] = v
        return out

    def state_summary(self) -> str:
        return f"group leaders {self.driver.leaders()}, " + \
            super().state_summary()

    # ---- correctness ------------------------------------------------

    def _check(self, conns, sample, seed: int) -> list:
        """First everything the apps are asked, then the arithmetic (as
        ``interposed_app_ycsb``: the reference is seconds of Python in
        the process that also steps the groups)."""
        p = self.ctx.cell.traffic
        R, G = self.R, self.G
        leaders = self.driver.leaders()
        by_rank = sample.keys.by_rank
        rest = random.Random(f"sample:{seed}").sample(
            by_rank[HOTTEST:], min(SAMPLE_KEYS, len(by_rank) - HOTTEST))
        keys = [key_of(rec) for rec in by_rank[:HOTTEST] + rest]

        # group g's marker through g's leader's app, the FIRST request
        # of that connection: it rides g's log
        markers = [self.key_in_group(b"frontier%dg" % seed, g)
                   for g in range(G)]
        for g in range(G):
            said = conns[leaders[g]].ask([b"SET %s 1" % markers[g]])
            if said != [b"+OK"]:
                raise RuntimeError(f"group {g}'s marker was answered "
                                   f"{said!r}")
        deadline = time.monotonic() + FRONTIER_WAIT_S
        behind = {(r, g) for r in range(R) for g in range(G)
                  if r != leaders[g]}
        while behind and time.monotonic() < deadline:
            behind = {(r, g) for r, g in behind
                      if conns[r].ask([b"GET " + markers[g]]) != [b"1"]}
            if behind:
                time.sleep(0.02)
        answers, counts = {}, {}
        for r in range(R):
            answers[r] = conns[r].ask([b"HGETALL " + k for k in keys])
            shown = sum(conns[r].ask([b"GET " + m]) == [b"1"]
                        for m in markers)
            counts[r] = ask_count(conns[r]) - shown
        self.raise_if_dead()

        ops = sample.ops()
        writes = [ref.Write(op["key"], f, v, op["t_req"],
                            op["t_rep"] if op["state"] == OK else ref.INF,
                            ref.ACKED if op["state"] == OK
                            else ref.UNRESOLVED)
                  for op in ops if op["kind"] == "write"
                  and op["state"] != FAILED     # an error reply: not done
                  for f, v in op["fields"].items()]
        regs = ClusterRegisters(writes, sample.group_of, G)
        per_group = regs.records_per_group()
        out = [dict(name="records_in_groups",
                    what=f"records the routing table files under the {G} "
                         f"groups {per_group}, and writes to keys it does "
                         f"not hold ({len(regs.strays)})",
                    got=sum(per_group) - len(regs.strays),
                    want=p["recordcount"],
                    limit=f"{p['recordcount']} (exact)",
                    ok=(sum(per_group) == p["recordcount"]
                        and not regs.strays and all(per_group)))]
        out += [dict(name=f"records_r{r}",
                     what=f"records held by replica {r}'s app apart from "
                          f"the markers and the keep-alive's key",
                     got=counts[r], want=p["recordcount"],
                     limit=f"{p['recordcount']} (exact)",
                     ok=counts[r] == p["recordcount"])
                for r in range(R)]
        out.append(dict(
            name="apps_without_marker",
            what="(app, group) pairs where the app never showed the "
                 "marker written through that group's leader",
            got=sorted(behind), want=[], limit="0 (exact)", ok=not behind))
        for r in range(R):
            faults = regs.app_faults(
                {k: ref.parse_record(line)
                 for k, line in zip(keys, answers[r])})
            for g in range(G):
                n_g = sum(1 for k in keys if sample.group_of[k] == g)
                out.append(dict(
                    name=f"inadmissible_fields_r{r}_g{g}",
                    what=(f"of group {g}'s {n_g} of {len(keys)} records "
                          f"({HOTTEST} hottest), fields replica {r}'s app "
                          f"(leader of group "
                          f"{leaders.index(r) if r in leaders else '-'}) "
                          f"holds at a value the reference does not "
                          f"admit"),
                    got=len(faults[g]), want=0, limit="0 (exact)",
                    ok=not faults[g] and n_g > 0, first=faults[g][:3]))
        differ = sum(1 for i in range(len(keys))
                     if len({answers[r][i] for r in range(R)}) > 1)
        out.append(dict(
            name="records_apps_differ_on",
            what=f"of {len(keys)} records, those the {R} apps do not "
                 f"answer alike",
            got=differ, want=0, limit="0 (exact)", ok=differ == 0))

        reads = [ref.Read(ops[k]["key"], ref.parse_record(ops[k]["reply"]),
                          ops[k]["t_req"], ops[k]["t_rep"])
                 for k in sample.in_window if ops[k]["kind"] == "read"]
        what = "acknowledged reads of the window"
        if self.control == "stale_read_control":
            reads = [r if i % 10 else
                     ref.Read(r.key, regs.previous_version(r), r.t_req,
                              r.t_rep) for i, r in enumerate(reads)]
            what += (" (CONTROL: every tenth handed over with the "
                     "record's previous version)")
        stale = [f for r in reads for f in regs.read_faults(r)]
        out.append(dict(
            name="stale_read_fields",
            what=f"of {len(reads)} {what}, fields whose value was not "
                 f"current between request and reply",
            got=len(stale), want=0, limit="0 (exact)",
            ok=not stale and len(reads) > 0, first=stale[:3]))
        rep = sample.report
        off = sum(abs(a - b) for a, b in zip(rep["completions_by_group"],
                                             rep["completions_by_table"]))
        out.append(dict(
            name="group_completions_off",
            what=(f"completions the generator counted by group "
                  f"{rep['completions_by_group']} (by the connection "
                  f"used) against the routing table's count of the same "
                  f"{sum(rep['completions_by_table'])} operations "
                  f"{rep['completions_by_table']}: the sum of differences"),
            got=off, want=0, limit="0 (exact)",
            ok=off == 0 and all(rep["completions_by_group"])))
        out.append(dict(
            name="ambiguous_records",
            what="records that may end on more than one value (writes "
                 "still concurrent at the end; told, not judged)",
            got=regs.ambiguous_keys(), want="any", limit="none", ok=True))
        out.append(dict(
            name="replay_order_timeouts",
            what="replayed writes that went to another connection before "
                 "the app had answered the last (told, not judged)",
            got=sum(rt.replay.order_timeouts
                    for rt in self.driver.runtimes),
            want="any", limit="none", ok=True))
        return out

    # ---- faults, for the runs that show the check can fail ----------

    def inject(self, fault: str) -> None:
        """``interposed_app_ycsb``'s three (the follower is replica 1,
        for both groups it follows), and ``group_replay_dropped``:
        replica 1's app misses every replayed ``HMSET`` of group 2 and
        none of group 0. A sharded driver's elections are not timed out
        this way: the election faults are not this kind's."""
        from rdma_paxos_tpu.consensus.log import EntryType
        if fault in ELECTION_FAULTS:
            raise SystemExit(f"perfbench: fault {fault!r} is not for a "
                             f"deployment of {self.G} groups")
        if fault != GROUP_FAULT:
            return super().inject(fault)
        victim, group = 1, 2 % self.G
        replay = self.driver.runtimes[victim].replay
        apply, send = replay.apply, int(EntryType.SEND)
        of_group = self.group_of_key

        def faulty(etype, conn, payload):
            if etype == send:
                kept = [ln for ln in payload.split(b"\n")
                        if not (ln.startswith(b"HMSET ")
                                and of_group(ln.split(b" ", 2)[1]) == group)]
                payload = b"\n".join(kept)
                if not payload.strip():
                    return None
            return apply(etype, conn, payload)
        replay.apply = faulty
        self.ctx.say("fault", f"{fault}: replica {victim}'s app misses "
                     f"group {group}'s replayed writes")


def build(config: dict, ctx) -> Deployment:
    return Deployment(config, ctx)
