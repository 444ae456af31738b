"""A follower lost, evicted and replaced under load, through
``ClusterDriver``'s public methods and real apps.

Three ``native/toyserver`` processes under the interposition shim, a
small closed loop of ``SET``s (keys never repeat) to the leader's app,
and once, under that load: the highest-numbered follower's app gets
``SIGKILL`` and ``fail_replica`` cuts its row off; the failure detector
(``auto_evict``) evicts it; a FRESH app is started on its port,
``recover_replica(wait_app=False)`` installs the leader's snapshot and
starts feeding the app the history, ``request_membership`` asks it back
in. Then all three apps are held to the plain reference of what was
acknowledged (``tests/replace_register_ref.py``, the tier-1 copy of
``perfbench/reference/replace_register.py``), and the joiner's row of
the ring to the leader's. At the benchmark's rehearsal geometry
(1,024 x 128 B); ports from the OS.
"""

import os
import signal
import socket
import subprocess
import threading
import time

import numpy as np
import pytest

from rdma_paxos_tpu.config import LogConfig, TimeoutConfig
from rdma_paxos_tpu.consensus.state import Role
from rdma_paxos_tpu.runtime.driver import ClusterDriver
from rdma_paxos_tpu.runtime.sim import SimCluster

from tests import replace_register_ref as ref

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NATIVE = os.path.join(ROOT, "native")
CFG = LogConfig(n_slots=1024, slot_bytes=128, window_slots=64,
                batch_slots=64)
# wide: no election is meant, and a loaded host's long iteration must
# not depose the leader under the drill
TO = TimeoutConfig(elec_timeout_low=4.0, elec_timeout_high=8.0)
R, CLIENTS, FAIL_THRESHOLD = 3, 4, 20
CASES = {"serial": dict(pipeline=0, fanout="psum"),
         "pipelined": dict(pipeline=2, fanout="psum"),
         "pipelined_gather": dict(pipeline=2, fanout="gather")}


@pytest.fixture(scope="module", autouse=True)
def build_native():
    subprocess.run(["make", "-C", NATIVE], check=True, capture_output=True)


def free_ports(n):
    socks = [socket.socket() for _ in range(n)]
    try:
        for s in socks:
            s.bind(("127.0.0.1", 0))
        return [s.getsockname()[1] for s in socks]
    finally:
        for s in socks:
            s.close()


def spawn_app(workdir, r, port):
    env = dict(os.environ,
               LD_PRELOAD=os.path.join(NATIVE, "interpose.so"),
               RP_PROXY_SOCK=os.path.join(workdir, f"proxy{r}.sock"))
    return subprocess.Popen([os.path.join(NATIVE, "toyserver"), str(port)],
                            env=env, stderr=subprocess.DEVNULL)


def wait_listening(port, timeout=10.0):
    deadline = time.monotonic() + timeout
    while True:
        try:
            socket.create_connection(("127.0.0.1", port), timeout=5).close()
            return
        except OSError:
            if time.monotonic() > deadline:
                raise
            time.sleep(0.01)


class Loop:
    """``CLIENTS`` connections to one app, one ``SET`` outstanding
    each, until told to stop; the dict of what was acknowledged."""

    def __init__(self, port):
        self.port, self.stopping = port, threading.Event()
        self.acked, self.failed, self.lock = {}, [], threading.Lock()
        self.threads = [threading.Thread(target=self.client, args=(c,),
                                         daemon=True)
                        for c in range(CLIENTS)]

    def client(self, c):
        try:
            s = socket.create_connection(("127.0.0.1", self.port),
                                         timeout=60)
            f = s.makefile("rb")
            i = 0
            while not self.stopping.is_set():
                key, val = b"k%d-%d" % (c, i), b"v%d" % (i % 997)
                s.sendall(b"SET " + key + b" " + val + b"\n")
                reply = f.readline().strip()
                if reply != b"+OK":
                    raise OSError(f"{key!r}: {reply!r}")
                with self.lock:
                    self.acked[key] = val
                i += 1
            s.close()
        except OSError as exc:
            self.failed.append((c, exc))

    def start(self):
        for t in self.threads:
            t.start()

    def done(self):
        with self.lock:
            return len(self.acked)

    def wait_more(self, n, timeout=60.0):
        """Until ``n`` more operations have been acknowledged."""
        want, deadline = self.done() + n, time.monotonic() + timeout
        while self.done() < want:
            assert not self.failed, self.failed
            assert time.monotonic() < deadline, "the loop stands still"
            time.sleep(0.01)

    def stop(self):
        self.stopping.set()
        for t in self.threads:
            t.join(60)
            assert not t.is_alive()


def ask(port, lines):
    with socket.create_connection(("127.0.0.1", port), timeout=30) as s:
        f = s.makefile("rb")
        out = []
        for ln in lines:
            s.sendall(ln + b"\n")
            out.append(f.readline().strip())
        return out


def wait_until(cond, what, timeout=60.0):
    deadline = time.monotonic() + timeout
    while not cond():
        assert time.monotonic() < deadline, what
        time.sleep(0.005)


def election_timeouts(counters):
    return sum(v for k, v in counters.items()
               if k.startswith("election_timeouts_total"))


def stands_on(d, mask):
    def cond():
        m = d.membership()
        return (m is not None and m["mask"] == mask and m["stable"]
                and not m["changing"])
    return cond


@pytest.fixture(scope="module", params=sorted(CASES))
def replaced(request, tmp_path_factory):
    """The whole drill once a case; -> what the tests look at."""
    opts = CASES[request.param]
    workdir = str(tmp_path_factory.mktemp("replace"))
    ports = free_ports(R)
    d = ClusterDriver(CFG, R, workdir=workdir, app_ports=ports,
                      timeout_cfg=TO, auto_evict=True,
                      fail_threshold=FAIL_THRESHOLD, **opts)
    apps = [spawn_app(workdir, r, ports[r]) for r in range(R)]
    loop = None
    try:
        for p in ports:
            wait_listening(p)
        d.cluster.prewarm()
        d.prewarm_recovery()
        d.runtimes[0].timer._deadline = 0.0     # replica 0 leads
        d.run(period=0.002)
        wait_until(lambda: d.leader() == 0, "no leader")
        victim, everybody = R - 1, (1 << R) - 1
        events = {}
        loop = Loop(ports[0])
        loop.start()
        loop.wait_more(300)
        base = dict(d._phase_prof.acc)
        term0 = int(d.cluster.last["term"].max())
        timeouts0 = election_timeouts(d.obs.metrics.snapshot()["counters"])

        # the follower's machine is lost
        old_pid = apps[victim].pid
        apps[victim].kill()
        d.fail_replica(victim)
        apps[victim].wait()
        assert apps[victim].returncode == -signal.SIGKILL
        wait_until(stands_on(d, everybody & ~(1 << victim)),
                   "never evicted")
        events["evicted"] = d.membership()
        # the degraded stretch: every SET commits on both members left
        loop.wait_more(300)
        at_add = loop.done()

        # AddServer: a fresh app, the leader's snapshot, back in
        apps[victim] = spawn_app(workdir, victim, ports[victim])
        wait_listening(ports[victim])
        d.recover_replica(victim, wait_app=False)
        d.request_membership(everybody)
        wait_until(stands_on(d, everybody), "never STABLE on everybody")
        events["add_server"] = d.membership()
        wait_until(lambda: bool(d.cluster.last["peer_acked"][0][victim]),
                   "the joiner never acknowledged the leader's window")
        d.wait_app_rebuilt(victim, 120)
        loop.wait_more(300)
        loop.stop()
        assert not loop.failed, loop.failed

        lo, hi = ref.count_limits(loop.acked, 0)
        # the followers' apply frontier trails the acks
        wait_until(lambda: all(int(ask(p, [b"COUNT"])[0]) >= lo
                               for p in ports), "an app stays behind")
        time.sleep(0.2)
        keys = ref.sample_keys(loop.acked, seed=7)
        views = [dict(count=int(ask(p, [b"COUNT"])[0]),
                      answers=ask(p, [b"GET " + k for k in keys]))
                 for p in ports]
        # the check's own sessions on the leader's app are log entries
        # too, and a follower's store trails the leader's by a dispatch
        wait_until(lambda: len({len(rt.store) for rt in d.runtimes}) == 1,
                   "the stores never agree")
        stores = [len(rt.store) for rt in d.runtimes]
        d.stop()
        assert d.loop_error is None
        st = d.cluster.state
        counters = d.obs.metrics.snapshot()["counters"]
        return dict(
            case=request.param, acked=loop.acked, keys=keys, views=views,
            limits=(lo, hi), events=events, victim=victim,
            old_pid=old_pid, new_pid=apps[victim].pid, at_add=at_add,
            acc={p: (a[0] - base[p][0], a[1] - base[p][1])
                 for p, a in d._phase_prof.acc.items()},
            counters=counters, term0=term0, timeouts0=timeouts0,
            last=d.cluster.last, buf=np.asarray(st.log.buf),
            head=np.asarray(st.head), end=np.asarray(st.end),
            commit=np.asarray(st.commit),
            app_dirty=[rt.app_dirty for rt in d.runtimes],
            stores=stores)
    finally:
        if loop is not None:
            loop.stopping.set()
        d.stop()
        for a in apps:
            a.kill()
            a.wait()


def test_every_app_holds_what_was_acknowledged(replaced):
    """All three, the replaced one included: its count of keys and a
    seeded sample of values, against the plain reference."""
    lo, hi = replaced["limits"]
    assert lo > 900
    for r, view in enumerate(replaced["views"]):
        assert lo <= view["count"] <= hi, (r, view["count"], lo)
        assert ref.wrong_values(replaced["acked"], replaced["keys"],
                                view["answers"]) == 0, r
    assert replaced["app_dirty"] == [False] * R
    assert len(set(replaced["stores"])) == 1, replaced["stores"]


def test_membership_follows_the_three_line_timeline(replaced):
    victim, members = replaced["victim"], range(R)

    def mask(who):
        return sum(1 << r for r in who)
    ev = replaced["events"]
    assert ev["evicted"]["mask"] == mask(
        ref.members_after(["evicted"], members, victim)) == 0b011
    assert ev["add_server"]["mask"] == mask(
        ref.members_after(["evicted", "add_server"], members,
                          victim)) == 0b111
    assert ev["add_server"]["epoch"] == ev["evicted"]["epoch"] + 2
    last = replaced["last"]
    assert last["bitmask_new"].tolist() == [0b111] * R
    assert last["epoch"].tolist() == [4] * R
    assert replaced["counters"]["evictions_total"] == 1
    assert replaced["counters"]["config_changes_total"] == 2


def test_the_app_in_the_victims_place_is_a_new_process(replaced):
    assert ref.replaced_app_faults(replaced["old_pid"],
                                   replaced["new_pid"], 0) == 0
    assert ref.replaced_app_faults(replaced["old_pid"],
                                   replaced["old_pid"], 0) == 1


def test_joiner_row_equals_the_leaders_on_the_shared_suffix(replaced):
    """Installed from a snapshot (its ring wiped), the joiner holds
    from there on exactly the leader's entries: row for row of the
    fused ring, payload and metadata."""
    v = replaced["victim"]
    head, end, buf = replaced["head"], replaced["end"], replaced["buf"]
    assert end[v] == end[0] and replaced["commit"][v] == replaced["commit"][0]
    lo = int(max(head[0], head[v], end[0] - CFG.n_slots + 1))
    assert end[0] - lo >= 300       # what committed since AddServer
    slots = np.arange(lo, int(end[0])) % CFG.n_slots
    assert (buf[v][slots] == buf[0][slots]).all()
    assert (buf[1][slots] == buf[0][slots]).all()


def test_leadership_never_moved(replaced):
    last = replaced["last"]
    assert last["role"][0] == int(Role.LEADER)
    assert int(last["term"].max()) == replaced["term0"]
    assert (election_timeouts(replaced["counters"])
            == replaced["timeouts0"])


def test_phases_and_counters_of_the_replacement_are_recorded(replaced):
    acc, counters = replaced["acc"], replaced["counters"]
    assert acc["config_change"][0] == 2 and acc["config_change"][1] > 0
    assert acc["recover"][0] == 1 and acc["recover"][1] > 0
    assert acc["app_rebuild"][0] == 1 and acc["app_rebuild"][1] > 0
    assert acc["checkpoint"][0] == 0        # no app_snapshot hook here
    # the whole store since boot: a record an operation at the least
    assert counters["recover_entries_total"] >= replaced["at_add"]
    assert counters["recover_bytes_total"] > 16 * replaced["at_add"]
    # the fresh app's replay connections: one a client connection
    assert counters["replay_reconnects_total"] == CLIENTS
    # the rebuilt engine's connections were claimed, not taken for
    # clients: nothing of the joiner's app was ever refused or enqueued
    assert "proxy_events_total{replica=%d}" % replaced["victim"] \
        not in counters


# ---- the split a psum fan-out can take ------------------------------

def led_cluster(fanout, n=3):
    c = SimCluster(LogConfig(n_slots=64, slot_bytes=32, window_slots=16,
                             batch_slots=8), n, fanout=fanout)
    c.run_until_elected(0)
    return c


@pytest.mark.parametrize("groups, sound", [
    ([[0, 1], [2]], True),          # a follower's machine lost
    ([[0, 2], [1]], True),
    ([[0], [1, 2]], False),         # the others could elect a second
    ([[1, 2], [0]], False)], ids=str)
def test_psum_fanout_takes_only_a_split_that_leaves_one_leader(groups,
                                                               sound):
    c = led_cluster("psum")
    if not sound:
        with pytest.raises(ValueError, match="psum"):
            c.partition(groups)
        assert c.peer_mask.all()
        return
    c.partition(groups)
    cut = groups[1][0]
    c.submit(0, b"degraded")
    res = c.step()
    assert res["commit"][0] == res["end"][0]        # two of three
    assert not res["peer_acked"][0][cut]
    assert res["end"][cut] < res["end"][0]
    c.heal()
    for _ in range(3):
        res = c.step()
    assert res["end"].tolist() == [int(res["end"][0])] * 3


def test_psum_split_is_refused_before_a_leader_stands():
    c = SimCluster(LogConfig(n_slots=64, slot_bytes=32, window_slots=16,
                             batch_slots=8), 3, fanout="psum")
    with pytest.raises(ValueError, match="psum"):
        c.partition([[0, 1], [2]])


def test_a_stopped_timer_fires_again_only_after_a_beat():
    from rdma_paxos_tpu.runtime.timers import ElectionTimer
    now = [0.0]
    t = ElectionTimer(TimeoutConfig(elec_timeout_low=1.0,
                                    elec_timeout_high=1.0),
                      seed=1, clock=lambda: now[0])
    now[0] = 2.0
    assert t.expired()
    t.stop()
    now[0] = 1e9
    assert not t.expired() and t.remaining() == float("inf")
    t.beat()
    now[0] += 1.5
    assert t.expired()
