"""``interposed_app`` with one follower lost, evicted and replaced
inside the window: the reference's kill-follower / ``AddServer`` run
(``benchmarks/reconf_bench.sh``) under whatever load the cell's mix
offers the leader's app.

The harness tells a deployment of a window only through its first
``probe()`` (the window opens) and ``window_closed()``, so the schedule
starts at that first probe, on a thread of its own, at shares of the
window (``config["schedule"]``; a traced window is ``TRACE_CAP_S``):

* ``kill_at``: the victim (the highest-numbered follower) loses its
  machine: its app process gets ``SIGKILL`` and
  ``ClusterDriver.fail_replica`` cuts its row off
  (``cluster.partition``) and stops its election timer. Nobody tells
  the group: the leader's failure detector (``auto_evict``, the shipped
  ``fail_threshold``) evicts it by a two-phase configuration change.
* ``add_server_at``, or when that change is STABLE if that is later:
  a FRESH app process is started on the victim's port,
  ``recover_replica(victim, wait_app=False)`` installs the leader's
  snapshot in its row and its store and starts feeding the app the
  whole history, ``request_membership`` asks it back in. The thread
  then waits for STABLE on everybody, for the joiner to acknowledge
  the leader's window, and for the app to hold the history.

Every event is stamped on the monotonic clock (``self.events``): the
``event_timeline`` reader turns stamps and the generator's completions
into the cell's event metrics. ``check`` is ``interposed_app``'s on all
apps, the replaced one included, and the membership and the replaced
app's identity beside ``reference/replace_register.py``.
"""

from __future__ import annotations

import os
import socket
import subprocess
import threading
import time

from perfbench.deployments import interposed_app
from perfbench.harness.core import TRACE_CAP_S
from perfbench.reference import replace_register as ref

FAULTS = ("rejoin_without_restore", "rejoin_skips_suffix")
POLL_S = 0.005
EVENT_WAIT_S = 90.0     # for each thing the schedule waits for


class Deployment(interposed_app.Deployment):

    def __init__(self, config: dict, ctx):
        from rdma_paxos_tpu.runtime.driver import ClusterDriver
        if not hasattr(ClusterDriver, "fail_replica"):
            # at once and before the chip is touched
            raise SystemExit(
                "perfbench: this program cannot lose a replica's machine "
                "and put another in its place (no ClusterDriver."
                f"fail_replica): it cannot run {config['name']!r}")
        super().__init__(config, ctx)
        self.events: dict = {}          # name -> monotonic stamp
        self.notes: dict = {}           # what the schedule found
        self.schedule = None            # its thread
        self.schedule_error = None
        self.halt = threading.Event()
        self.probe_at_open = None
        self.old_pid = self.new_pid = self.victim = None

    # ---- life cycle -------------------------------------------------

    def boot(self) -> None:
        t0 = time.monotonic()
        self.driver.prewarm_recovery()
        self.ctx.part("prewarm_recovery", t0)
        super().boot()

    def probe(self):
        out = super().probe()
        if self.probe_at_open is None:
            self.probe_at_open = out
            self.events["open"] = time.monotonic()
            seconds = (min(self.ctx.seconds, TRACE_CAP_S)
                       if self.ctx.traced else self.ctx.seconds)
            self.schedule = threading.Thread(
                target=self.run_schedule, args=(seconds,), daemon=True)
            self.schedule.start()
        return out

    def stop(self) -> None:
        self.halt.set()
        if self.schedule is not None:
            self.schedule.join(10)
        super().stop()

    # ---- the schedule -----------------------------------------------

    def stamp(self, name: str) -> float:
        now = self.events[name] = time.monotonic()
        self.ctx.say("replace", f"{name} at "
                     f"{now - self.events['open']:.3f}s of the window")
        return now

    def sleep_until(self, t: float) -> None:
        while not self.halt.is_set():
            left = t - time.monotonic()
            if left <= 0:
                return
            self.halt.wait(min(left, 0.05))

    def wait_for(self, what: str, cond) -> None:
        deadline = time.monotonic() + EVENT_WAIT_S
        while not cond():
            self.raise_if_dead()
            if self.halt.is_set():
                raise RuntimeError(f"stopped while waiting for {what}")
            if time.monotonic() > deadline:
                raise RuntimeError(
                    f"{what} did not happen within {EVENT_WAIT_S:.0f}s; "
                    f"{self.state_summary()}")
            time.sleep(POLL_S)

    def stands_on(self, mask: int):
        def cond():
            m = self.driver.membership()
            return (m is not None and m["mask"] == mask and m["stable"]
                    and not m["changing"])
        return cond

    def run_schedule(self, seconds: float) -> None:
        try:
            self.replace_once(seconds)
        except BaseException as exc:  # noqa: BLE001 — told to the check
            self.schedule_error = exc
            self.ctx.say("replace", f"the schedule ended on {exc!r}")

    def replace_once(self, seconds: float) -> None:
        d, plan = self.driver, self.config["schedule"]
        t_open = self.events["open"]
        lead = d.leader()
        victim = self.victim = max(r for r in range(self.R) if r != lead)
        everybody = (1 << self.R) - 1

        self.sleep_until(t_open + seconds * plan["kill_at"])
        if self.halt.is_set():
            return
        self.old_pid = self.apps[victim].pid
        self.apps[victim].kill()            # SIGKILL
        d.fail_replica(victim)
        self.stamp("kill")
        self.apps[victim].wait()

        def evicting():
            # the detector counts the eviction as it submits the change
            m = d.membership()
            return m is not None and (m["changing"]
                                      or not (m["mask"] >> victim) & 1)
        self.wait_for("the eviction", evicting)
        self.stamp("evicted")
        self.wait_for("STABLE without the victim",
                      self.stands_on(everybody & ~(1 << victim)))
        t_stable = self.stamp("evicted_stable")

        t_add = t_open + seconds * plan["add_server_at"]
        self.notes["add_server_waited_for_the_eviction"] = int(
            t_stable > t_add)
        self.sleep_until(t_add)
        if self.halt.is_set():
            return
        self.stamp("add_server")
        self.apps[victim] = self.spawn_app(victim)
        self.new_pid = self.apps[victim].pid
        self.notes["keys_of_the_fresh_app"] = self.fresh_app_keys(victim)
        d.recover_replica(victim, wait_app=False)
        self.stamp("recovered")
        d.request_membership(everybody)
        self.wait_for("STABLE on everybody", self.stands_on(everybody))
        self.stamp("stable_on_everybody")
        self.wait_for("the joiner's acknowledgement", lambda: bool(
            d.cluster.last["peer_acked"][d.leader()][victim]))
        self.stamp("caught_up")
        d.wait_app_rebuilt(victim, EVENT_WAIT_S)
        self.stamp("app_rebuilt")
        self.notes["replacements_done"] = 1

    def spawn_app(self, r: int):
        env = dict(os.environ,
                   LD_PRELOAD=os.path.join(interposed_app.NATIVE,
                                           "interpose.so"),
                   RP_PROXY_SOCK=os.path.join(self.ctx.workdir,
                                              f"proxy{r}.sock"))
        p = subprocess.Popen(
            [os.path.join(interposed_app.NATIVE,
                          self.config["app"]["binary"]),
             str(self.ports[r])], env=env, stderr=subprocess.DEVNULL)
        self.ctx.children.append(p)
        return p

    def fresh_app_keys(self, r: int) -> int:
        """Wait until the app just started listens; -> its ``COUNT``
        (a follower's app serves a client locally), or -1 where the
        driver severs the question: it quarantines a replica's app from
        the moment a replayed write fails on it until it is rebuilt,
        and the old app may have died under one."""
        deadline = time.monotonic() + 10
        while True:
            try:
                s = socket.create_connection(
                    ("127.0.0.1", self.ports[r]), timeout=5)
                break
            except OSError:
                if time.monotonic() > deadline:
                    raise
                time.sleep(0.01)
        try:
            s.sendall(b"COUNT\n")
            return int(s.makefile("rb").readline())
        except (OSError, ValueError):
            return -1
        finally:
            s.close()

    # ---- correctness ------------------------------------------------

    def _check(self, conns, sample, seed: int) -> list:
        """STABLE on everybody is asked for here, not at the window's
        close: the schedule is waited for first."""
        if self.schedule is not None:
            self.schedule.join(EVENT_WAIT_S)
        if self.schedule_error is not None:
            raise RuntimeError(
                f"the replacement did not end: {self.schedule_error!r}")
        out = super()._check(conns, sample, seed)
        d, done = self.driver, self.notes.get("replacements_done", 0)
        happened = [n for n in ("evicted", "add_server")
                    if n in self.events and (n == "evicted" or done)]
        expected = ref.members_after(happened, range(self.R), self.victim)
        m = d.membership() or dict(mask=0, stable=False, changing=True)
        now = {r for r in range(self.R) if (m["mask"] >> r) & 1}
        settled = m["stable"] and not m["changing"]
        probe = self.probe()
        elections = max(
            probe[k] - self.probe_at_open[k]
            for k in ("counter.election_timeouts_total", "term"))

        def item(name, what, got, want, limit=None):
            return dict(name=name, what=what, got=got, want=want,
                        limit=limit or f"{want} (exact)", ok=got == want)
        out += [
            item("replacements_done", "followers lost, evicted and "
                 "replaced in the window", done, 1),
            item("members_at_close", "members of the leader's STABLE "
                 "configuration at the check (0: not STABLE, or not who "
                 "the timeline says)",
                 len(now) if settled and now == expected else 0, self.R),
            item("rejoined_with_an_old_app", "faults of the app in the "
                 "victim's place: the old process, or one that held keys "
                 f"when it started (it held "
                 f"{self.notes.get('keys_of_the_fresh_app')}; -1: the "
                 f"driver severed the question)",
                 ref.replaced_app_faults(
                     self.old_pid, self.new_pid,
                     self.notes.get("keys_of_the_fresh_app", -1)), 0),
            item("elections_in_window", "election time-outs or terms "
                 "since the window opened", elections, 0),
            item("failed", "operations of the window that failed",
                 sample.failed, 0),
            dict(name="add_server_waited_for_the_eviction",
                 what="AddServer came at its share of the window (0) or "
                      "when the eviction's change was STABLE, later (1)",
                 got=self.notes.get("add_server_waited_for_the_eviction",
                                    -1),
                 want=0, limit="0 or 1 (said, not judged)", ok=True)]
        return out

    # ---- faults, for the runs that show the check can fail ----------

    def inject(self, fault: str) -> None:
        """``rejoin_without_restore``: the joiner's app is fed nothing
        of the history its snapshot brought, only what committed after.
        ``rejoin_skips_suffix``: it is fed the snapshot's history, and
        nothing of what committed between the snapshot and the moment
        live replay took over."""
        if fault not in FAULTS:
            return super().inject(fault)
        d = self.driver
        if fault == "rejoin_without_restore":
            from rdma_paxos_tpu.proxy.proxy import dump_records

            def fed_nothing(rt, blob):
                base, records = dump_records(blob)
                return base + sum(1 for _ in records)
            d._feed_blob = fed_nothing
        else:
            d._feed_store = lambda rt, start, stop: None
        self.ctx.say("fault", fault)


def build(config: dict, ctx) -> Deployment:
    return Deployment(config, ctx)
