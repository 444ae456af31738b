"""Pipelined dispatch (begin_*/finish) — the perf PR's correctness bar.

Pipelining must be a PURE latency/throughput transform:

* engine level: the same submission schedule driven serial vs depth-2
  pipelined yields bit-identical step outputs, committed replay
  streams, and apply cursors — with the dispatch-concurrency counter
  proving the pipelined run really overlapped dispatches
* driver level: a recorded workload through ``ClusterDriver`` with
  ``pipeline=0`` vs ``pipeline=2`` run loops commits the identical
  client entry stream and releases the identical ack sequence — no
  duplicate, missing, or reordered acks
* under chaos: ``NemesisRunner(pipeline=2)`` schedules (crash,
  drops, partitions) keep I1–I5 + per-key linearizability green at
  100% audit
* auditing: injected log corruption is localized to the exact first
  ``(term, index)`` while dispatches overlap
* the sharded e2e driver routes connections by key prefix onto G
  groups and releases per-group acks through the same pipeline
* observability export runs on the READBACK thread, never the
  dispatch path
"""

import threading
import time

import numpy as np
import pytest

from rdma_paxos_tpu.config import LogConfig, TimeoutConfig
from rdma_paxos_tpu.runtime.driver import ClusterDriver
from rdma_paxos_tpu.runtime.sim import SimCluster

CFG = LogConfig(n_slots=128, slot_bytes=64, window_slots=32,
                batch_slots=8)
# manual elections only — wall-clock timers must never fire mid-test
TO = TimeoutConfig(elec_timeout_low=1e9, elec_timeout_high=2e9)

# commit-stream-relevant outputs. ``apply``/``head`` are deliberately
# EXCLUDED: the device apply echo / pruning frontier follow the
# apply_done INPUT, which lags by design while dispatches overlap (the
# readback hasn't run yet) — a capacity effect, not a protocol one.
# The replayed streams and final apply cursors are compared directly.
RES_CMP = ("term", "role", "leader_id", "commit", "end", "accepted",
           "acked", "hb_seen", "leadership_verified")


# ---------------------------------------------------------------------------
# engine-level bit-identity
# ---------------------------------------------------------------------------

def _drive_engine(pipelined: bool):
    c = SimCluster(CFG, 3)
    c.run_until_elected(0)
    outs = []
    inflight = []
    for i in range(24):
        for j in range(5):
            c.submit(0, b"p%d-%d" % (i, j))
        if pipelined:
            inflight.append(c.begin_step())
            if len(inflight) >= 2:
                outs.append(c.finish(inflight.pop(0)))
        else:
            outs.append(c.step())
    while inflight:
        outs.append(c.finish(inflight.pop(0)))
    # drain the committed tail so replay streams are complete
    for _ in range(4):
        outs.append(c.step())
    return c, outs


def test_engine_pipelined_step_stream_bit_identical():
    cs, serial = _drive_engine(False)
    cp, piped = _drive_engine(True)
    assert cp.max_inflight_dispatches >= 2, (
        "pipelined drive never overlapped dispatches")
    assert cs.max_inflight_dispatches <= 1
    assert len(serial) == len(piped)
    for k, (a, b) in enumerate(zip(serial, piped)):
        for key in RES_CMP:
            assert np.array_equal(a[key], b[key]), (k, key)
    for r in range(3):
        assert cs.replayed[r] == cp.replayed[r], r
    assert np.array_equal(cs.applied, cp.applied)


def test_engine_pipelined_burst_reservation_no_loss():
    """Two bursts in flight: the second's capacity clamp must reserve
    the first's not-yet-finished appends (they are invisible in
    ``last["end"]``) so the ring can never drop mid-burst — every
    submitted entry commits exactly once, in order."""
    c = SimCluster(CFG, 3)
    c.run_until_elected(0)
    sent = [b"e%03d" % i for i in range(160)]
    for p in sent[:100]:
        c.submit(0, p)
    t1 = c.begin_burst()                    # takes the first 100
    for p in sent[100:]:
        c.submit(0, p)
    # without the reservation this burst would size itself against the
    # PRE-t1 end/head and overrun the 128-slot ring mid-burst
    t2 = c.begin_burst()
    assert c.max_inflight_dispatches >= 2
    assert sum(len(t.taken[r]) for t in (t1, t2)
               for r in range(3)) <= CFG.n_slots - 1
    c.finish(t1)
    c.finish(t2)
    for _ in range(40):
        if not c.pending[0] and all(
                int(c.last["commit"][r]) == int(c.last["end"][0])
                for r in range(3)):
            break
        c.step_burst()
    got = [p for (_t, _c, _r, p) in c.replayed[0]]
    assert got == sent


def test_engine_pipelined_audit_localizes_corruption():
    """Digest auditing stays exact under overlapped dispatches: a
    single-bit flip of a follower's committed slot is localized to the
    exact first (term, index) while the pipeline is in flight."""
    import dataclasses

    c = SimCluster(CFG, 3, audit=True)
    c.run_until_elected(0)
    for i in range(12):
        c.submit(0, b"a%d" % i)
        c.step()
    target = int(c.last["commit"].min()) - 1
    slot = target & (CFG.n_slots - 1)
    buf = c.state.log.buf.at[2, slot, 0].add(1)
    c.state = dataclasses.replace(
        c.state, log=dataclasses.replace(c.state.log, buf=buf))
    t1 = c.begin_step()
    t2 = c.begin_step()
    c.finish(t1)
    c.finish(t2)
    assert c.max_inflight_dispatches >= 2
    f = c.auditor.first_divergence()
    assert f is not None, "corruption not detected under pipelining"
    assert f["index"] == target
    assert f["got_replicas"] == [2]


# ---------------------------------------------------------------------------
# driver-level identity (recorded workload, real run loop)
# ---------------------------------------------------------------------------

def _drive_driver(pipeline: int):
    d = ClusterDriver(CFG, 3, timeout_cfg=TO, pipeline=pipeline)
    d.cluster.run_until_elected(0)
    d.step()
    assert d.leader() == 0
    handler = d._make_handler(0)
    conns = [(0 << 24) | 11, (0 << 24) | 12]
    for conn in conns:
        st = handler(2, conn, b"")
        assert not isinstance(st, int) or st == 0
    # recorded workload: one intake thread, alternating connections,
    # no waiting between submissions — the submit order IS the record.
    # The whole record is queued BEFORE the loop starts and is SIZED
    # PAST one fused burst's capacity (K_TIERS[-1] * batch_slots,
    # further clamped by the 127-slot ring): on a fast
    # idle host, trickling events in against a live loop lets the
    # readback retire every ticket before the dispatch thread sees a
    # standing backlog — and a backlog one burst can swallow whole
    # vanishes at the first dispatch — so _pipeline_ready (which
    # needs a standing backlog) never engages and the overlap
    # assertion below races the machine instead of testing the
    # driver. A pre-queued record longer than one burst makes the
    # pipelined variant's overlap structural; the serial variant
    # drains the identical record.
    evs = []
    for i in range(200):
        ev = handler(3, conns[i % 2], b"w%03d" % i)
        assert not isinstance(ev, int), (i, ev)
        evs.append(ev)
    d.run(period=0.001)
    for i, ev in enumerate(evs):
        assert ev.done.wait(30), f"ack {i} never released"
    time.sleep(0.1)          # let follower replay frontiers settle
    d.stop()
    assert d.loop_error is None
    stream = [e for e in d.cluster.replayed[0]]
    statuses = [ev.status for ev in evs]
    return d, stream, statuses


def test_driver_pipelined_commit_and_ack_stream_identical():
    ds, stream_s, st_s = _drive_driver(0)
    dp, stream_p, st_p = _drive_driver(2)
    assert dp.cluster.max_inflight_dispatches >= 2, (
        "pipelined driver never overlapped dispatches")
    assert ds.cluster.max_inflight_dispatches <= 1
    # ack stream: every submission acked exactly once, successfully,
    # identically across the two drivers
    assert st_s == [0] * 200
    assert st_p == st_s
    # commit stream bit-identity: same entries, same order, same bytes
    assert stream_p == stream_s
    payloads = [p for (_t, _c, _r, p) in stream_s
                if p.startswith(b"w")]
    assert payloads == [b"w%03d" % i for i in range(200)]
    # per-connection req stamps strictly increase (no reorder, no dup)
    for conn_sel in (11, 12):
        reqs = [r for (_t, c, r, _p) in stream_p
                if c & 0xFFFFFF == conn_sel]
        assert reqs == sorted(reqs) and len(set(reqs)) == len(reqs)


def test_driver_observability_rides_readback_thread():
    """The small-fix satellite: _observe_step (and the whole post-step
    rule set) must run on the READBACK thread under pipelining, so
    observability can never serialize the dispatch path it measures."""
    d = ClusterDriver(CFG, 3, timeout_cfg=TO, pipeline=2)
    d.cluster.run_until_elected(0)
    d.step()
    seen = []
    orig = d._observe_step

    def spy(res):
        seen.append(threading.current_thread())
        return orig(res)
    d._observe_step = spy
    handler = d._make_handler(0)
    conn = (0 << 24) | 21
    handler(2, conn, b"")
    d.run(period=0.001)
    evs = [handler(3, conn, b"x%d" % i) for i in range(20)]
    for ev in evs:
        assert ev.done.wait(30)
    d.stop()
    assert d.loop_error is None
    assert d._rb_thread in seen, (
        "post-step observability never ran on the readback thread")


def test_driver_pipeline_crash_releases_waiters():
    """A dispatch-path exception under pipelining fails blocked waiters
    fast (no hang) and latches loop_error — same contract as serial."""
    d = ClusterDriver(CFG, 3, timeout_cfg=TO, pipeline=2)
    d.cluster.run_until_elected(0)
    d.step()
    handler = d._make_handler(0)
    conn = (0 << 24) | 31
    handler(2, conn, b"")
    ev = handler(3, conn, b"doomed")

    def boom(*a, **k):
        raise RuntimeError("injected dispatch failure")
    d.cluster.begin_step = boom
    d.cluster.begin_burst = boom
    d.cluster.step = boom
    d.cluster.step_burst = boom
    d.run()
    assert ev.done.wait(10), "waiter never released after crash"
    assert ev.status == -1
    assert isinstance(d.loop_error, RuntimeError)
    d.stop()


# ---------------------------------------------------------------------------
# chaos under pipelining
# ---------------------------------------------------------------------------

@pytest.mark.chaos
def test_nemesis_pipelined_green_and_overlapped():
    """NemesisRunner schedules (crash-restart, drops, partitions,
    skew) with pipeline depth 2: I1–I5 + per-key linearizability hold,
    audit (100%) finds nothing, no duplicate/reordered client acks —
    and the run provably overlapped dispatches."""
    from rdma_paxos_tpu.chaos.runner import NemesisRunner
    runner = NemesisRunner(n_replicas=3, seed=7, steps=50, pipeline=2)
    v = runner.run()
    assert v["ok"], v
    assert v["invariant_violations"] == []
    assert v["linearizability"]["ok"] is True
    assert v["audit"] is not None and v["audit"]["findings"] == 0
    assert runner.cluster.max_inflight_dispatches >= 2, (
        "chaos run never engaged the pipeline")
    # ack discipline: every client op completed at most once (the
    # recorder rejects double completion; re-assert through the data)
    ops = runner.history.ops(include_weak=True)
    ids = [o["op_id"] for o in ops if "op_id" in o]
    assert len(ids) == len(set(ids))


@pytest.mark.chaos
def test_nemesis_pipelined_leader_crash_midflight():
    """A schedule that provably crashes the elected leader mid-run:
    failover + retransmit under a depth-2 pipeline stays correct."""
    from rdma_paxos_tpu.chaos.faults import FaultSchedule
    from rdma_paxos_tpu.chaos.runner import NemesisRunner

    # probe the fault-free trajectory of THIS seed to learn who leads
    # at step 24, then crash exactly that replica mid-run — identical
    # seeds make the pre-crash trajectories bit-identical, so the
    # crash provably hits the serving leader
    probe = NemesisRunner(n_replicas=3, seed=21, steps=24,
                          schedule=FaultSchedule([]))
    violations: list = []
    lead = -1
    for t in range(24):
        lead = probe._one_step(t, lead, violations)
    lead = probe._drain(lead, violations)
    assert lead >= 0 and not violations
    sch = (FaultSchedule()
           .at(24, "crash", replica=lead)
           .at(27, "drop", p=0.25)
           .at(34, "drop", p=0.0)
           .at(42, "restart", replica=lead)
           .at(48, "heal"))
    runner = NemesisRunner(n_replicas=3, seed=21, steps=60,
                           schedule=sch, pipeline=2)
    v = runner.run()
    assert v["ok"], v
    assert runner.cluster.max_inflight_dispatches >= 2


# ---------------------------------------------------------------------------
# sharded e2e driver (key-prefix routing through the same pipeline)
# ---------------------------------------------------------------------------

def test_sharded_driver_key_prefix_routing_and_acks():
    from rdma_paxos_tpu.runtime.sharded_driver import (
        ShardedClusterDriver, key_prefix_of)

    assert key_prefix_of(b"SET k3-17 v1\n") == b"k3"
    assert key_prefix_of(
        b"*3\r\n$3\r\nSET\r\n$5\r\nk4-99\r\n$2\r\nv0\r\n") == b"k4"
    assert key_prefix_of(b"") == b""
    # the FIRST-occurring delimiter wins, not the first in scan order
    assert key_prefix_of(b"SET user.1-x v\n") == b"user"
    assert key_prefix_of(b"SET a:b.c-d v\n") == b"a"

    d = ShardedClusterDriver(
        CFG, 3, 4,
        timeout_cfg=TimeoutConfig(elec_timeout_low=0.05,
                                  elec_timeout_high=0.1))
    try:
        d.run(period=0.002)
        t0 = time.time()
        while d.leader() < 0:
            time.sleep(0.02)
            assert time.time() - t0 < 60, (d.leaders(), d.loop_error)
        # round-robin placement: G leaderships spread over R replicas
        assert sorted(set(d.leaders())) == [0, 1, 2]

        handlers = [d._make_handler(r) for r in range(3)]

        def client(r, tid, wave, n, acks):
            # flood the connection's SENDs, then collect the acks: the
            # pipeline engages only while append BACKLOG flows (strict
            # request-ack-request clients ride the serial latency path
            # by design), so depth >= 2 needs pipelined traffic
            h = handlers[r]
            conn = (r << 24) | (wave << 12) | (1000 + tid)
            st = h(2, conn, b"")
            assert st == 0 or st is None, st
            evs = []
            for i in range(n):
                ev = h(3, conn, b"SET k%d-%d v%d\n" % (tid, i, i))
                assert not isinstance(ev, int), (r, tid, i, ev)
                evs.append(ev)
            for i, ev in enumerate(evs):
                assert ev.done.wait(30), "ack timed out"
                assert ev.status == 0
                acks.append((tid, i))

        # overlap is opportunistic (the loop drains whenever backlog
        # momentarily empties), so under host load one wave may retire
        # every ticket before the next dispatch — repeat waves until a
        # depth >= 2 overlap is witnessed
        for wave in range(4):
            acks = []
            threads = [
                threading.Thread(target=client,
                                 args=(r, t, wave, 25, acks))
                for t, r in enumerate([0, 1, 2, 0, 1, 2])]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            assert len(acks) == 150
            assert d.loop_error is None
            if d.cluster.max_inflight_dispatches >= 2:
                break
        assert d.cluster.max_inflight_dispatches >= 2
        # the six prefixes really demuxed onto more than one group
        groups = {d.router.group_of(b"k%d" % t) for t in range(6)}
        assert len(groups) > 1
        # every group's committed stream replayed into every replica:
        # the per-(replica, group) apply cursors reached the commit
        c = d.cluster
        for g in groups:
            for r in range(3):
                assert c.applied[g, r] == int(
                    c.last["commit"][g, r]), (g, r)
        h = d.health()
        assert h["n_groups"] == 4 and len(h["leaders"]) == 4
        assert h["router"]["n_groups"] == 4
    finally:
        d.stop()


def test_sharded_driver_unsupported_admin_surfaces_raise():
    from rdma_paxos_tpu.runtime.sharded_driver import (
        ShardedClusterDriver)
    d = ShardedClusterDriver(CFG, 3, 2, timeout_cfg=TO)
    for call in (lambda: d.request_membership(0b11),
                 lambda: d.recover_replica(1),
                 lambda: d.reset_app(1),
                 lambda: d.checkpoint_app(1)):
        with pytest.raises(NotImplementedError):
            call()
    d.stop()
