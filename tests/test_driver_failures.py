"""Driver-level failure handling: automatic eviction of dead members
(check_failure_count analog) and snapshot recovery through the driver."""

import numpy as np
import pytest

from rdma_paxos_tpu.config import LogConfig, TimeoutConfig
from rdma_paxos_tpu.consensus.state import ConfigState
from rdma_paxos_tpu.runtime.driver import ClusterDriver

CFG = LogConfig(n_slots=64, slot_bytes=32, window_slots=16, batch_slots=8)
TO = TimeoutConfig(elec_timeout_low=1e9, elec_timeout_high=2e9)  # manual


def make_driver(**kw):
    d = ClusterDriver(CFG, 5, timeout_cfg=TO, **kw)
    return d


def test_auto_eviction_of_dead_member():
    d = make_driver(auto_evict=True, fail_threshold=5)
    d.runtimes[0].timer.beat = lambda: None
    # elect replica 0 manually
    d.cluster.run_until_elected(0)
    d.step()
    assert d.leader() == 0
    # replica 4 dies
    d.cluster.partition([[0, 1, 2, 3], [4]])
    for _ in range(40):
        d.step()
    cur = d._mm.current(0)
    assert cur["bitmask_new"] == 0b01111, cur
    assert cur["cid_state"] == int(ConfigState.STABLE)
    # quorum shrank with it: 3-of-4 commits with one more member down
    d.cluster.partition([[0, 1, 2], [3], [4]])
    d.cluster.submit(0, b"post-evict")
    r = d.step()
    assert r["commit"][0] == r["end"][0]
    d.stop()


def test_eviction_steps_unchanged_with_view_taken_from_res():
    """The failure detector judges each step's ``peer_acked`` row with
    the config view of that step's own ``res`` (no device read): the
    dead follower is evicted at the step counts the device-state view
    gave (TRANSIT submitted on the fail_threshold-th silent step,
    adopted on the next, STABLE on the one after), and every step's
    view equals the device state's."""
    d = make_driver(auto_evict=True, fail_threshold=5)
    d.runtimes[0].timer.beat = lambda: None
    d.cluster.run_until_elected(0)
    d.step()
    d.cluster.partition([[0, 1, 2, 3], [4]])
    changes, prev = [], None
    for n in range(1, 13):
        r = d.step()
        view = {k: int(r[k][0]) for k in d._mm.current(0)}
        assert view == d._mm.current(0), n
        row = (d._config_phase and d._config_phase[0],
               view["bitmask_new"], view["cid_state"], view["epoch"])
        if row != prev:
            changes.append((n,) + row)
        prev = row
    assert changes == [
        (1, None, 0b11111, int(ConfigState.STABLE), 0),
        (5, "transit", 0b11111, int(ConfigState.STABLE), 0),
        (6, "stable", 0b01111, int(ConfigState.TRANSIT), 1),
        (7, None, 0b01111, int(ConfigState.STABLE), 2)], changes
    d.stop()


def test_driver_snapshot_recovery_path():
    d = make_driver()
    d.cluster.run_until_elected(0)
    d.step()
    # replica 3 pruned past: tiny ring + partition + load
    d.cluster.partition([[0, 1, 2], [3], [4]])
    small = 3 * CFG.n_slots
    for i in range(small):
        d.cluster.submit(0, b"w%03d" % i)
        d.step()
    d.step()
    assert int(d.cluster.last["head"][0]) > int(d.cluster.last["end"][3])
    d.cluster.heal()
    for _ in range(4):
        d.step()
    assert int(d.cluster.last["end"][3]) < int(d.cluster.last["end"][0])
    d.recover_replica(3)
    for _ in range(4):
        r = d.step()
    assert int(r["end"][3]) == int(r["end"][0])
    d.stop()


def test_flagged_leader_is_deposed_and_recovered():
    """A force-pruned replica that holds leadership acks windows and
    heartbeats normally, so nothing deposes it naturally — its app and
    store stay frozen (stale reads) and every other flagged member's
    recovery starves behind it. The driver must actively depose it by
    firing a healthy member's election timeout, then heal it once
    leadership has moved."""
    d = make_driver()
    d.cluster.run_until_elected(0)
    d.step()
    assert d.leader() == 0
    d.cluster.need_recovery.add(0)
    for _ in range(50):
        d.step()
        if d.leader() not in (-1, 0) and not d.cluster.need_recovery:
            break
    assert d.leader() >= 0 and d.leader() != 0, (
        "flagged leader was never deposed")
    assert not d.cluster.need_recovery, (
        "deposed ex-leader was never recovered")
    d.stop()


def test_poll_loop_crash_releases_and_rejects_events():
    """A step exception on the poll thread must fail every blocked
    commit waiter AND fail-fast any event arriving afterwards — app
    threads must never hang on a dead loop (advisor finding: the old
    loop died silently with waiters parked forever)."""
    import time

    d = make_driver()
    d.cluster.run_until_elected(0)
    d.step()
    handler = d._make_handler(0)
    conn = (0 << 24) | 1
    handler(2, conn, b"")               # CONNECT on the leader
    ev = handler(3, conn, b"blocked-op")
    assert ev is not None and not isinstance(ev, int)

    # poison the next cluster step, then run the loop (all four entry
    # points: the pipelined loop dispatches via begin_*, the serial
    # path via step/step_burst)
    def boom(*a, **k):
        raise RuntimeError("injected step failure")
    d.cluster.step = boom
    d.cluster.step_burst = boom
    d.cluster.begin_step = boom
    d.cluster.begin_burst = boom
    d.run()
    assert ev.done.wait(10), "blocked event never released"
    assert ev.status == -1
    assert isinstance(d.loop_error, RuntimeError)
    # post-crash events are rejected immediately, not queued
    t0 = time.time()
    assert handler(3, conn, b"late-op") == -1
    assert time.time() - t0 < 1.0
    d.stop()
