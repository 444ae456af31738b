"""Device-resident K-window scan tier — correctness pins.

The scan tier must be a PURE readback transform: K fused protocol
steps with a consolidated minimal readback (scalar matrix + in-dispatch
replay rows) produce step outputs, replay streams, frames, and apply
cursors bit-identical to the burst path (which is itself pinned
bit-identical to K serial steps) on every engine; scan-off clusters'
STEP_CACHE key sets and programs are untouched; the driver's ack/commit
streams are unchanged; and a chaos schedule crashing a leader drains
the scan tier to the serial path with zero violations."""

import threading
import time

import numpy as np
import pytest

from rdma_paxos_tpu.config import LogConfig, TimeoutConfig
from rdma_paxos_tpu.runtime.driver import ClusterDriver
from rdma_paxos_tpu.runtime.sim import STEP_CACHE, SimCluster
from tests.readback_ref import assert_same, drive

CFG = LogConfig(n_slots=128, slot_bytes=64, window_slots=32,
                batch_slots=8)
TO = TimeoutConfig(elec_timeout_low=1e9, elec_timeout_high=2e9)

RES_CMP = ("term", "role", "leader_id", "commit", "end", "accepted",
           "acked", "hb_seen", "leadership_verified", "head", "apply",
           "peer_acked", "rebase_delta", "voted_term", "voted_for",
           "became_leader")


def _drive_engine(scan: bool, audit: bool = False):
    c = SimCluster(CFG, 3, scan=scan, audit=audit)
    c.collect_frames = True
    c.run_until_elected(0)
    outs = []
    for i in range(10):
        for j in range(20):
            c.submit(0, b"p%d-%d" % (i, j))
        outs.append(c.step_burst())
    for _ in range(4):
        outs.append(c.step())
    return c, outs


def test_engine_scan_bit_identical_to_burst():
    cb, ob = _drive_engine(False)
    cs, os_ = _drive_engine(True)
    assert cs.scan_dispatches > 0
    assert cb.scan_dispatches == 0
    assert len(ob) == len(os_)
    for k, (a, b) in enumerate(zip(ob, os_)):
        for key in RES_CMP:
            assert np.array_equal(a[key], b[key]), (k, key)
    for r in range(3):
        assert cb.replayed[r] == cs.replayed[r], r
        assert list(cb.frames[r]) == list(cs.frames[r]), r
    assert np.array_equal(cb.applied, cs.applied)
    # the scan tier replaced the standalone replay fetch dispatches:
    # every burst's replay rode the staged rows (commit deltas fit
    # the replay window on this workload)
    assert cs.applied.min() > 0


@pytest.mark.parametrize("mode", ["sim", "spmd"])
@pytest.mark.parametrize("n,K", [(12, 2), (100, 16)],
                         ids=["k2", "k16"])
def test_scan_packed_row_unpacks_to_fieldwise_readback(mode, n, K):
    """The scan tier's ONE matrix (``peer_acked`` and the config view
    now ride it) unpacks to the field-by-field readback of the same
    drive through bursts, dispatch for dispatch."""
    cfg = LogConfig(n_slots=512, slot_bytes=32, window_slots=16,
                    batch_slots=8)
    scans = drive(SimCluster(cfg, 3, mode=mode, scan=True), n)
    bursts = drive(SimCluster(cfg, 3, mode=mode), n)
    assert [(k, n) for k, n, _, _ in scans] == [
        ("scan" if k == "burst" else k, n) for k, n, _, _ in bursts]
    assert max(n for _, n, _, _ in scans) == K
    for (_, _, res, _), (_, _, _, ref) in zip(scans, bursts):
        assert_same(res, ref)


def test_scan_equals_k_serial_steps():
    """The satellite pin, direct form: ONE K-step scan dispatch
    produces the same committed stream and final frontiers as the K
    serial steps it fuses (the serial drive takes the identical
    per-step batch prefixes the scan packs)."""
    def drive(scan_mode):
        c = SimCluster(CFG, 3, scan=scan_mode)
        c.run_until_elected(0)
        for i in range(30):                  # ceil(30/8) -> tier K=4
            c.submit(0, b"s%02d" % i)
        if scan_mode:
            c.step_burst()
        else:
            for _ in range(4):
                c.step()
        for _ in range(4):                   # settle the replay tail
            c.step()
        return c

    cs = drive(True)
    cb = drive(False)
    assert cs.scan_dispatches == 1
    for r in range(3):
        assert cs.replayed[r] == cb.replayed[r], r
    for key in ("term", "role", "leader_id", "commit", "end", "head"):
        assert np.array_equal(cs.last[key], cb.last[key]), key
    assert np.array_equal(cs.applied, cb.applied)
    assert cs.step_index == cb.step_index


def test_engine_scan_audit_windows_identical():
    cb, _ = _drive_engine(False, audit=True)
    cs, _ = _drive_engine(True, audit=True)
    assert cb.auditor.summary() == cs.auditor.summary()
    assert cb.auditor.summary()["findings"] == 0
    assert cb.auditor.summary()["indices_checked"] > 0


def test_scan_off_cache_keys_unchanged():
    keys_before = set(STEP_CACHE)
    c = SimCluster(CFG, 3)
    c.run_until_elected(0)
    for j in range(9):
        c.submit(0, b"k%d" % j)
    c.step_burst()
    added = set(STEP_CACHE) - keys_before
    assert not any("scan" in k for k in added), added
    base = set(STEP_CACHE)
    # scan-on adds ONLY distinct "scan"-marked keys; every pre-scan
    # key (and thus program) is untouched
    cs = SimCluster(CFG, 3, scan=True)
    cs.run_until_elected(0)
    for j in range(9):
        cs.submit(0, b"k%d" % j)
    cs.step_burst()
    new = set(STEP_CACHE) - base
    assert new and all("scan" in k for k in new), new
    assert base <= set(STEP_CACHE)


@pytest.mark.parametrize("mesh", [None, (2, 2)])
def test_sharded_scan_bit_identical_to_burst(mesh):
    from rdma_paxos_tpu.shard.cluster import ShardedCluster

    def drive(scan):
        c = ShardedCluster(CFG, 2, 2, scan=scan, mesh=mesh)
        c.collect_frames = True
        c.place_leaders()
        outs = []
        for i in range(8):
            for g in range(2):
                lead = c.leader_hint(g)
                for j in range(12):
                    c.submit(g, lead, b"g%d-%d-%d" % (g, i, j))
            outs.append(c.step_burst())
        for _ in range(4):
            outs.append(c.step())
        return c, outs

    cb, ob = drive(False)
    cs, os_ = drive(True)
    assert cs.scan_dispatches > 0
    for k, (a, b) in enumerate(zip(ob, os_)):
        for key in RES_CMP:
            if key in a:
                assert np.array_equal(a[key], b[key]), (k, key)
    for g in range(2):
        for r in range(2):
            assert cb.replayed[g][r] == cs.replayed[g][r], (g, r)
            assert (list(cb.frames[g][r])
                    == list(cs.frames[g][r])), (g, r)
    assert np.array_equal(cb.applied, cs.applied)


# ---------------------------------------------------------------------------
# driver-level identity (recorded workload through the real run loop)
# ---------------------------------------------------------------------------

def _drive_driver(scan: bool):
    d = ClusterDriver(CFG, 3, timeout_cfg=TO, pipeline=0, scan=scan)
    d.cluster.run_until_elected(0)
    d.step()
    assert d.leader() == 0
    handler = d._make_handler(0)
    conns = [(0 << 24) | 11, (0 << 24) | 12]
    for conn in conns:
        st = handler(2, conn, b"")
        assert not isinstance(st, int) or st == 0
    evs = []
    for i in range(160):
        ev = handler(3, conns[i % 2], b"w%03d" % i)
        assert not isinstance(ev, int), (i, ev)
        evs.append(ev)
    d.run(period=0.001)
    for i, ev in enumerate(evs):
        assert ev.done.wait(30), f"ack {i} never released"
    time.sleep(0.1)
    d.stop()
    assert d.loop_error is None
    stream = [e for e in d.cluster.replayed[0]]
    statuses = [ev.status for ev in evs]
    return d, stream, statuses


def test_driver_scan_commit_and_ack_stream_identical():
    db, stream_b, st_b = _drive_driver(False)
    ds, stream_s, st_s = _drive_driver(True)
    assert ds.cluster.scan_dispatches > 0, (
        "the scan driver never engaged the scan tier")
    assert db.cluster.scan_dispatches == 0
    assert st_b == [0] * 160
    assert st_s == st_b
    assert stream_s == stream_b
    payloads = [p for (_t, _c, _r, p) in stream_s
                if p.startswith(b"w")]
    assert payloads == [b"w%03d" % i for i in range(160)]


# ---------------------------------------------------------------------------
# chaos: a NemesisRunner schedule drives the scan tier
# ---------------------------------------------------------------------------

def _chaos_verdict(seed=5):
    from rdma_paxos_tpu.chaos.runner import NemesisRunner
    r = NemesisRunner(steps=80, seed=seed, scan=True,
                      fault_kinds=("crash", "partition", "drop"))
    # the schedule must actually exercise the drain-to-serial path
    assert any(ev["op"] == "crash" for ev in r.schedule.events), (
        "seed produced no crash — pick another")
    v = r.run()
    return r, v


def test_chaos_scan_leader_crash_drains_to_serial():
    r, v = _chaos_verdict()
    assert v["ok"] is True, v
    assert v["invariant_violations"] == []
    assert v["linearizability"]["ok"] is True
    assert v["linearizability"]["violations"] == []
    assert r.cluster.scan_dispatches > 0, (
        "the chaos run never dispatched through the scan tier")
    # determinism: the same seed yields the identical verdict
    _r2, v2 = _chaos_verdict()
    for key in ("ok", "invariant_violations", "linearizability",
                "schedule_events", "steps"):
        assert v[key] == v2[key], key


def test_runner_rejects_scan_with_pipeline():
    from rdma_paxos_tpu.chaos.runner import NemesisRunner
    with pytest.raises(ValueError):
        NemesisRunner(steps=10, scan=True, pipeline=2)
