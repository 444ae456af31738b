"""``interposed_app``'s deployment, serving hash records that are read
and overwritten: the check is a register check, not a dict lookup.

The deployment is ``interposed_app.Deployment`` line for line (apps
under the shim, one ``ClusterDriver``, the followers' apps replayed
to); what differs is ``check``, which asks every app of the group, all
of it exact (limit 0):

* ``COUNT`` equals the mix's ``recordcount`` on every app (the
  followers' taken after the marker, which is one key more);
* after a marker written through the leader's app has shown on every
  follower's (the followers replay in log order, so they then hold
  everything acknowledged before it), the whole records of the
  ``HOTTEST`` hottest keys and ``SAMPLE_KEYS`` seeded others, read with
  ``HGETALL`` from every app (the leader's first): every field holds a
  value the plain reference admits at the end
  (``perfbench/reference/ycsb_register.py``, (a)), and all the apps
  give the same answer;
* every acknowledged read of the window returned, field by field, a
  value that was current at some instant between its request and its
  reply (the reference's (b)).

How many records may end on more than one value is printed.

Faults for the runs that show ``correct`` can come out false:
``interposed_app``'s two follower faults carried over to ``HMSET``
(faults of the system), and ``stale_read_control``, a control of the
CHECK: every tenth acknowledged read of the window is handed to it with
that record's previous version for a reply (the leader's app answers
its clients outside the driver's reach, so a stale read cannot be made
underneath a run; the reference must still catch one at the cell's
size).
"""

from __future__ import annotations

import os
import random
import socket
import subprocess
import time

from perfbench.deployments import interposed_app
from perfbench.generators.resp_ycsb import FAILED, OK, key_of
from perfbench.reference import ycsb_register as ref

HOTTEST = 20
SAMPLE_KEYS = 200
FRONTIER_WAIT_S = 60
FAULTS = interposed_app.FAULTS + ("stale_read_control",)


def app_serves_hashes(binary: str) -> bool:
    """Ask a plain, unreplicated instance of the app for a record."""
    port = interposed_app.free_ports(1)[0]
    app = subprocess.Popen([binary, str(port)], stderr=subprocess.DEVNULL)
    try:
        for _ in range(50):
            try:
                with socket.create_connection(("127.0.0.1", port),
                                              timeout=5) as s:
                    s.sendall(b"HGETALL nobody\n")
                    return s.makefile("rb").readline().strip() == b"-"
            except ConnectionRefusedError:
                time.sleep(0.05)
        return False
    finally:
        app.kill()
        app.wait()


class Deployment(interposed_app.Deployment):

    def __init__(self, config: dict, ctx):
        super().__init__(config, ctx)
        self.control = None
        binary = os.path.join(interposed_app.NATIVE, config["app"]["binary"])
        if not app_serves_hashes(binary):
            # at once and before the chip is touched: a program whose
            # app has no hash records cannot run this configuration
            raise SystemExit(
                f"perfbench: {binary} does not answer HGETALL: this "
                f"program cannot run configuration {config['name']!r}")

    # ---- correctness ------------------------------------------------

    def _check(self, conns, sample, seed: int) -> list:
        """First everything the apps are asked, then the arithmetic: the
        reference is seconds of Python in the process that also steps
        the group, and an idle group whose host is late elects."""
        p = self.ctx.cell.traffic
        lead = self.driver.leader()
        # the leader's app first: its answers are replicated requests,
        # and while they flow the group is busy and holds no election
        order = sorted(range(self.R), key=lambda r: r != lead)
        by_rank = sample.keys.by_rank
        rest = random.Random(f"sample:{seed}").sample(
            by_rank[HOTTEST:], min(SAMPLE_KEYS, len(by_rank) - HOTTEST))
        keys = [key_of(rec) for rec in by_rank[:HOTTEST] + rest]

        # the followers' apply frontier trails the acks: a marker goes
        # through the leader's app, and a follower that shows it has
        # replayed everything the log holds before it
        marker = b"frontier-%d" % seed
        counts = {lead: int(conns[lead].ask([b"COUNT"])[0])}
        assert conns[lead].ask([b"SET %s 1" % marker]) == [b"+OK"]
        answers = {lead: conns[lead].ask([b"HGETALL " + k for k in keys])}
        deadline = time.monotonic() + FRONTIER_WAIT_S
        behind = set(order[1:])
        while behind and time.monotonic() < deadline:
            behind = {r for r in behind
                      if conns[r].ask([b"GET " + marker]) != [b"1"]}
            if behind:
                time.sleep(0.02)
        for r in order[1:]:
            answers[r] = conns[r].ask([b"HGETALL " + k for k in keys])
            counts[r] = int(conns[r].ask([b"COUNT"])[0]) - 1   # the marker
        self.raise_if_dead()

        ops = sample.ops()
        writes = [ref.Write(op["key"], f, v, op["t_req"],
                            op["t_rep"] if op["state"] == OK else ref.INF,
                            ref.ACKED if op["state"] == OK
                            else ref.UNRESOLVED)
                  for op in ops if op["kind"] == "write"
                  and op["state"] != FAILED     # an error reply: not done
                  for f, v in op["fields"].items()]
        regs = ref.Registers(writes)
        out = [dict(what=f"records held by replica {r}'s app", got=counts[r],
                    want=p["recordcount"], limit="0 (exact)",
                    ok=counts[r] == p["recordcount"])
               for r in range(self.R)]
        out.append(dict(
            what="apps that never showed the marker written after the run",
            got=sorted(behind), want=[], limit="0 (exact)", ok=not behind))
        for r in order:
            faults = [f for k, line in zip(keys, answers[r])
                      for f in regs.record_faults(k, ref.parse_record(line))]
            out.append(dict(
                what=(f"of {len(keys)} records ({HOTTEST} hottest), fields "
                      f"replica {r}'s app holds at a value the reference "
                      f"does not admit"),
                got=len(faults), want=0, limit="0 (exact)",
                ok=not faults and len(keys) > 0, first=faults[:3]))
        differ = sum(1 for i in range(len(keys))
                     if len({answers[r][i] for r in order}) > 1)
        out.append(dict(
            what=f"of {len(keys)} records, those the {self.R} apps do "
                 f"not answer alike",
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
            what=f"of {len(reads)} {what}, fields whose value was not "
                 f"current between request and reply",
            got=len(stale), want=0, limit="0 (exact)",
            ok=not stale and len(reads) > 0, first=stale[:3]))
        out.append(dict(
            what="records that may end on more than one value (writes "
                 "still concurrent at the end; told, not judged)",
            got=regs.ambiguous_keys(), want="any", limit="none", ok=True))
        replay = [rt.replay for rt in self.driver.runtimes
                  if rt.replay is not None]
        out.append(dict(
            what="replayed writes that went to another connection before "
                 "the app had answered the last (told, not judged)",
            got=sum(getattr(e, "order_timeouts", 0) for e in replay),
            want="any", limit="none", ok=True))
        return out

    # ---- faults, for the runs that show the check can fail ----------

    def inject(self, fault: str) -> None:
        """``follower_drops_applies``: a follower's app misses every
        fourth replayed ``HMSET``. ``follower_alters_values``: every
        value of an ``HMSET`` replayed to a follower's app has its last
        byte changed. ``stale_read_control``: see the module text."""
        from rdma_paxos_tpu.consensus.log import EntryType
        if fault not in FAULTS:
            return super().inject(fault)
        if fault == "stale_read_control":
            self.control = fault
            self.ctx.say("fault", f"{fault}: a control of the check, the "
                         f"system runs sound")
            return
        victim = next(r for r in range(self.R) if r != self.driver.leader())
        replay = self.driver.runtimes[victim].replay
        apply, seen = replay.apply, [0]
        send = int(EntryType.SEND)

        def alter(line: bytes) -> bytes:
            parts = line.split(b" ")
            if parts[0] == b"HMSET" and len(parts) >= 4:
                for i in range(3, len(parts), 2):
                    parts[i] = parts[i][:-1] + bytes([parts[i][-1] ^ 1])
            return b" ".join(parts)

        def faulty(etype, conn, payload):
            if etype == send and fault == "follower_drops_applies":
                kept = []
                for ln in payload.split(b"\n"):
                    seen[0] += ln.startswith(b"HMSET")
                    if not (ln.startswith(b"HMSET") and seen[0] % 4 == 0):
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
