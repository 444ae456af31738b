"""``interposed_app``'s deployment, serving batched writes: the app is
``native/toyssdb`` and a request is one ``multi_set`` of several pairs,
which the log carries as several entries.

The deployment is ``interposed_app.Deployment`` line for line (apps
under the shim, one ``ClusterDriver``, the followers' apps replayed
to); what differs is ``check``, all of it exact (limit 0), against the
plain reference ``perfbench/reference/multiset_dict.py``, a dict fed
the acknowledged requests in the order acknowledged:

* ``COUNT`` on every app (apart from the keep-alive's one key, where
  that app says it holds it) is the reference's number of keys, and at
  most whole requests more where requests were sent and never answered
  (``pairs x lo <= c <= pairs x hi``);
* that count is a whole number of requests (``c % pairs == 0``): a
  request is in an app whole or not at all;
* for ``SAMPLE_REQUESTS`` seeded acknowledged requests every key of
  each is read back with ``GET`` from every app (the leader's first) and
  equals the reference's value.

Everything the apps are asked comes first, then the reference is built
(a second or two of Python for a million keys, in the process that also
steps the group).

Faults for the runs that show ``correct`` can come out false, each on
one follower's replay path: ``follower_drops_applies`` (its app misses
every fourth replayed ``multi_set``), ``follower_alters_values`` (one
byte of one value of each), ``follower_drops_fragment`` (every fourth
request loses its THIRD log entry's bytes; the app refuses what is left
as malformed, and holds that request not at all).
"""

from __future__ import annotations

import os
import random
import socket
import subprocess
import time

from perfbench.deployments import interposed_app
from perfbench.generators.line_multiset import acknowledged
from perfbench.harness.keepalive import ask_count
from perfbench.reference.multiset_dict import MultisetDict

SAMPLE_REQUESTS = 40
FRONTIER_WAIT_S = 60
FRONTIER_STALL_S = 5
FAULTS = ("follower_drops_applies", "follower_alters_values",
          "follower_drops_fragment")


def app_takes_multi_set(binary: str) -> bool:
    """Ask a plain, unreplicated instance of the app to store a pair."""
    if not os.path.exists(binary):
        return False
    port = interposed_app.free_ports(1)[0]
    app = subprocess.Popen([binary, str(port)], stderr=subprocess.DEVNULL)
    try:
        for _ in range(50):
            try:
                with socket.create_connection(("127.0.0.1", port),
                                              timeout=5) as s:
                    s.sendall(b"multi_set k v\n")
                    return s.makefile("rb").readline().strip() == b"+OK 1"
            except ConnectionRefusedError:
                time.sleep(0.05)
        return False
    finally:
        app.kill()
        app.wait()


class Deployment(interposed_app.Deployment):

    def __init__(self, config: dict, ctx):
        super().__init__(config, ctx)
        binary = os.path.join(interposed_app.NATIVE, config["app"]["binary"])
        if not app_takes_multi_set(binary):
            # at once and before the chip is touched: a program without
            # the app cannot run this configuration
            raise SystemExit(
                f"perfbench: {binary} does not answer multi_set: this "
                f"program cannot run configuration {config['name']!r}")

    # ---- correctness ------------------------------------------------

    def _check(self, conns, sample, seed: int) -> list:
        p = self.ctx.cell.traffic
        n = int(p["pairs"])
        acked = sample.acked            # [(conn, j)], as acknowledged
        lo, hi = len(acked), len(acked) + sample.unresolved_total
        # the followers' apply frontier trails the acks: wait while the
        # counts still move, and FRONTIER_WAIT_S at most. Each count is
        # taken apart from the keep-alive's one key, where the app
        # holds it (asked of that app, not assumed)
        deadline = time.monotonic() + FRONTIER_WAIT_S
        counts, moved = None, time.monotonic()
        while True:
            now = [ask_count(c) for c in conns]
            if now != counts:
                counts, moved = now, time.monotonic()
            if (all(n * lo <= c <= n * hi for c in counts)
                    or time.monotonic() - moved > FRONTIER_STALL_S
                    or time.monotonic() > deadline):
                break
            time.sleep(0.2)
        picked = random.Random(f"sample:{seed}").sample(
            range(len(acked)), min(SAMPLE_REQUESTS, len(acked)))
        keys = [k for pairs in acknowledged(p, seed,
                                            [acked[i] for i in picked])
                for k, _ in pairs]
        # the leader's app first: its answers are replicated requests,
        # and while they flow the group is busy and holds no election
        lead = self.driver.leader()
        order = sorted(range(self.R), key=lambda r: r != lead)
        answers = {r: conns[r].ask([b"GET " + k for k in keys])
                   for r in order}
        self.raise_if_dead()

        ref = MultisetDict(n).feed(acknowledged(p, seed, acked))
        least, most = ref.count_bounds(hi - lo)
        out = []
        for r, c in enumerate(counts):
            out.append(dict(
                name=f"keys_r{r}",
                what=f"keys held by replica {r}'s app apart from the "
                     f"keep-alive's", got=c, want=least,
                limit=(f"the {least} of the {lo} acknowledged "
                       f"requests, and at most those of the {hi - lo} "
                       f"unresolved more"),
                ok=least <= c <= most))
            out.append(dict(
                name=f"part_held_keys_r{r}",
                what=f"keys of replica {r}'s app beyond a whole number "
                     f"of {n}-pair requests", got=ref.part_held(c), want=0,
                limit="0 (exact)", ok=ref.part_held(c) == 0))
        for r in order:
            bad = ref.wrong_values(keys, answers[r])
            out.append(dict(
                name=f"wrong_values_r{r}",
                what=(f"of the {len(keys)} keys of {len(picked)} sampled "
                      f"requests, those replica {r}'s app answers "
                      f"otherwise than acknowledged"),
                got=bad, want=0, limit="0 (exact)",
                ok=bad == 0 and len(keys) > 0))
        return out

    # ---- faults, for the runs that show the check can fail ----------

    def inject(self, fault: str) -> None:
        """See the module text. A follower's engine is handed a
        request's bytes in one piece or several (its log entries,
        joined where they are neighbours), so the fault puts each
        connection's requests together again before it breaks one."""
        from rdma_paxos_tpu.consensus.log import EntryType
        if fault not in FAULTS:
            return super().inject(fault)
        victim = next(r for r in range(self.R) if r != self.driver.leader())
        replay = self.driver.runtimes[victim].replay
        apply, seen, held = replay.apply, [0], {}
        send, sb = int(EntryType.SEND), self.cfg.slot_bytes

        def broken(line: bytes) -> bytes:
            if not line.startswith(b"multi_set "):
                return line
            seen[0] += 1
            if fault == "follower_alters_values":
                return line[:-2] + bytes([line[-2] ^ 1]) + line[-1:]
            if seen[0] % 4:
                return line
            if fault == "follower_drops_applies":
                return b""
            return line[:2 * sb] + line[3 * sb:]    # its third entry

        def faulty(etype, conn, payload):
            if etype != send:
                held.pop(conn, None)
                return apply(etype, conn, payload)
            lines = (held.pop(conn, b"") + payload).split(b"\n")
            if lines[-1]:
                held[conn] = lines[-1]      # an unfinished request
            whole = b"".join(broken(ln + b"\n") for ln in lines[:-1])
            return apply(etype, conn, whole) if whole else None
        replay.apply = faulty
        self.ctx.say("fault", f"{fault} on replica {victim}")


def build(config: dict, ctx) -> Deployment:
    return Deployment(config, ctx)
