"""Self-healing cluster (runtime/repair.py + the digest-verified
snapshot / range-redigest primitives): the full automated loop
``DIVERGENCE → quarantine → digest-verified snapshot re-install →
range-digest backfill → re-admit``, pinned end to end:

* the host-side digest fold is BIT-IDENTICAL to the device fold (one
  shared implementation — ``consensus/step.py:digest_fold``);
* the jitted range re-digest backfills ledger coverage and its cache
  key carries a distinct ``"redigest"`` marker — repair-off programs
  and STEP_CACHE keys are untouched;
* digest layout-epoch versioning: cross-epoch windows/dumps/snapshots
  are refused with ``EPOCH_MISMATCH``, never a false ``DIVERGENCE``;
* ``install_snapshot(ledger=...)`` REJECTS a corrupted donor before
  any state is touched; the controller retries with the next majority
  donor — corruption never propagates;
* the full loop heals the sim, sharded (other groups' frontiers
  strictly advancing during one group's repair) and mesh engines;
* re-admission hysteresis (N clean audited steps) and bounded
  retry/backoff escalation into the LATCHED ``repair_failed`` page;
* repair under the PIPELINED drive (depth 2) stays deterministic and
  linearizable, with the repair timeline embedded in the reproducer
  artifact;
* the ``obs.audit`` CLI report gains a repair-status section and
  exits 0 once every divergence is repaired + backfilled;
* the static jit-safety scan extends to the repair/redigest surface.
"""

import json

import numpy as np
import pytest

from rdma_paxos_tpu.chaos.faults import corrupt_slot
from rdma_paxos_tpu.config import DIGEST_EPOCH, LogConfig, TimeoutConfig
from rdma_paxos_tpu.consensus.log import M_GIDX, META_W, live_rows
from rdma_paxos_tpu.consensus.snapshot import (
    SnapshotEpochError, SnapshotVerifyError, install_snapshot,
    take_snapshot, verify_snapshot)
from rdma_paxos_tpu.consensus.step import digest_fold
from rdma_paxos_tpu.obs import Observability
from rdma_paxos_tpu.obs import audit as audit_mod
from rdma_paxos_tpu.obs.alerts import AlertEngine, default_rules
from rdma_paxos_tpu.obs.audit import AuditLedger, merge_dumps
from rdma_paxos_tpu.runtime.driver import ClusterDriver
from rdma_paxos_tpu.runtime.repair import RepairController
from rdma_paxos_tpu.runtime.sim import STEP_CACHE, SimCluster
from rdma_paxos_tpu.shard.cluster import ShardedCluster

CFG = LogConfig(n_slots=64, slot_bytes=32, window_slots=16, batch_slots=8)
TO = TimeoutConfig(elec_timeout_low=1e9, elec_timeout_high=2e9)  # manual


def _pump(c, ctl, steps, *, traffic=None):
    """Drive engine + controller the way the drivers do: step, observe
    every finished step, run due repairs on the (serial) drained
    path."""
    for _ in range(steps):
        if traffic is not None:
            traffic()
        c.step()
        ctl.observe()
        if ctl.needs_drain():
            ctl.drive()


def _audited_sim(n=8):
    c = SimCluster(CFG, 3, audit=True)
    c.run_until_elected(0)
    for i in range(n):
        c.submit(0, b"v%d" % i)
    for _ in range(4):
        c.step()
    assert c.auditor.findings == []
    return c


# ---------------------------------------------------------------------------
# digest fold parity + redigest program
# ---------------------------------------------------------------------------

def test_host_fold_bit_identical_to_device_fold():
    """The snapshot-verification/backfill fold (numpy) must equal the
    audit=True compiled step's digests bit for bit — one shared
    implementation, pinned."""
    c = _audited_sim()
    res = c.last
    start = int(res["audit_start"][0])
    commit = int(res["commit"][0])
    assert commit > start
    buf = live_rows(np.asarray(c.state.log.buf[0]), CFG.slot_words)
    slots = np.arange(start, commit) & (CFG.n_slots - 1)
    host = digest_fold(buf[slots].astype(np.uint32), xp=np)
    W = CFG.window_slots
    off = start - (commit - W)
    dev = np.asarray(res["audit_digest"][0][off:off + (commit - start)])
    assert np.array_equal(host, dev)
    # and the fold really excludes the gidx column (rebase-proof)
    tweaked = buf[slots].astype(np.uint32).copy()
    tweaked[:, tweaked.shape[1] - META_W + M_GIDX] += 7
    assert np.array_equal(digest_fold(tweaked, xp=np), host)


def test_redigest_backfills_ledger_and_cache_key_marked():
    cfg = LogConfig(n_slots=32, slot_bytes=64, window_slots=8,
                    batch_slots=4)   # geometry private to this guard
                                     # (test_audit's guard owns the
                                     # slot_bytes=32 twin)
    # compile the default (repair-off) programs FIRST so the key-set
    # delta below isolates exactly what the redigest pass adds
    plain = SimCluster(cfg, 3)
    plain.run_until_elected(0)
    plain.submit(0, b"z")
    plain.step()
    aud = SimCluster(cfg, 3, audit=True)
    aud.run_until_elected(0)
    for i in range(6):
        aud.submit(0, b"r%d" % i)
    for _ in range(4):
        aud.step()
    keys_before = set(STEP_CACHE)
    commit = int(aud.last["commit"].min())
    n = aud.redigest(1, 0, commit)
    assert n == commit and aud.auditor.backfilled == commit
    assert aud.auditor.findings == []        # backfill agrees with live
    added = set(STEP_CACHE) - keys_before
    assert added and all("redigest" in k for k in added), added
    # repair-off discipline: a fresh plain cluster adds NOTHING — the
    # default key set (and programs) are bit-identical to pre-repair
    after = set(STEP_CACHE)
    plain2 = SimCluster(cfg, 3)
    plain2.run_until_elected(0)
    plain2.submit(0, b"z")
    plain2.step()
    assert set(STEP_CACHE) == after


def test_redigest_requires_drained_and_audit():
    c = _audited_sim()
    t = c.begin_step()
    with pytest.raises(RuntimeError, match="redigest.*in-flight"):
        c.redigest(0, 0, 2)
    c.finish(t)
    plain = SimCluster(CFG, 3)
    plain.run_until_elected(0)
    with pytest.raises(RuntimeError, match="audit"):
        plain.redigest(0, 0, 1)


# ---------------------------------------------------------------------------
# digest layout-epoch versioning
# ---------------------------------------------------------------------------

def test_ledger_refuses_cross_epoch_window():
    led = AuditLedger(3)
    led.record_window(0, 0, [1, 2, 3], [1, 1, 1], 3)
    # same epoch: compared normally
    led.record_window(1, 0, [1, 2, 3], [1, 1, 1], 3,
                      epoch=DIGEST_EPOCH)
    assert led.findings == []
    # different layout, DIFFERENT digests: refused, never a DIVERGENCE
    led.record_window(2, 0, [9, 9, 9], [1, 1, 1], 3,
                      epoch=DIGEST_EPOCH + 1)
    assert len(led.findings) == 1
    f = led.findings[0]
    assert f["type"] == "EPOCH_MISMATCH" and f["replica"] == 2
    assert f["got_epoch"] == DIGEST_EPOCH + 1
    # deduped per (group, replica, epoch); divergence query unaffected
    led.record_window(2, 0, [9, 9], [1, 1], 2, epoch=DIGEST_EPOCH + 1)
    assert len(led.findings) == 1
    assert led.first_divergence() is None
    assert led.summary()["unrepaired"] == 1   # config error still fails


def test_merge_dumps_refuses_cross_epoch_comparison():
    a = AuditLedger(2)
    b = AuditLedger(2, digest_epoch=DIGEST_EPOCH + 1)
    # same indices, different layouts -> different digests, by design
    a.record_window(0, 0, [10, 11], [1, 1], 2)
    b.record_window(1, 0, [77, 78], [1, 1], 2)
    rep = merge_dumps([a.dump(), b.dump()])
    kinds = {f["type"] for f in rep["findings"]}
    assert kinds == {"EPOCH_MISMATCH"}        # no false DIVERGENCE
    assert rep["unrepaired"] == 1
    # same-epoch dumps still cross-compare (control)
    b2 = AuditLedger(2)
    b2.record_window(1, 0, [10, 99], [1, 1], 2)
    rep2 = merge_dumps([a.dump(), b2.dump()])
    assert rep2["first"]["type"] == "DIVERGENCE"
    assert rep2["first"]["index"] == 1


def test_snapshot_epoch_refusal():
    c = _audited_sim()
    snap = take_snapshot(c.state, 0, index=int(c.applied[0]),
                         digests=True)
    led2 = AuditLedger(3, digest_epoch=DIGEST_EPOCH + 1)
    with pytest.raises(SnapshotEpochError):
        verify_snapshot(snap, led2)
    # and an undigested snapshot cannot be verified at all
    bare = take_snapshot(c.state, 0, index=int(c.applied[0]))
    with pytest.raises(SnapshotVerifyError, match="no digest chain"):
        install_snapshot(c.state, 2, bare, ledger=c.auditor)


# ---------------------------------------------------------------------------
# corrupted-donor rejection (never propagate)
# ---------------------------------------------------------------------------

def test_install_rejects_corrupted_donor_and_clean_donor_passes():
    c = _audited_sim()
    commit = int(c.last["commit"].min())
    corrupt_slot(c, 1, commit - 1)
    bad = take_snapshot(c.state, 1, index=int(c.applied[1]),
                        digests=True)
    with pytest.raises(SnapshotVerifyError, match="contradicts"):
        install_snapshot(c.state, 2, bad, ledger=c.auditor)
    good = take_snapshot(c.state, 0, index=int(c.applied[0]),
                         digests=True)
    st = install_snapshot(c.state, 2, good, ledger=c.auditor)
    assert int(np.asarray(st.commit[2])) == good.index


def test_controller_retries_with_majority_donor_on_donor_corruption():
    """The chosen donor is itself corrupted at an OLD index (outside
    the live re-digest window — only install-time verification can
    see it): the controller rejects it and repairs from the next
    majority donor; corruption never propagates."""
    c = SimCluster(CFG, 3, audit=True)
    ctl = RepairController(c, probation_steps=3)
    c.run_until_elected(0)
    for i in range(8):
        c.submit(0, b"v%d" % i)
    for _ in range(4):
        c.step()
    # age the early indices out of the [commit-W, commit) live window
    for i in range(30):
        c.submit(0, b"pad%d" % i)
        c.step()
        ctl.observe()
    assert c.auditor.findings == []
    commit = int(c.last["commit"].min())
    corrupt_slot(c, 2, commit - 1)     # the victim (live index)
    # replica 0 has the highest applied (leader) -> tried first as
    # donor; its corruption sits at an old, no-longer-re-digested index
    corrupt_slot(c, 0, 3)
    _pump(c, ctl, 30, traffic=lambda: c.submit(0, b"t"))
    assert ctl.repairs_done == 1 and not ctl.states
    assert ctl.donors_rejected >= 1
    rej = [t for t in ctl.timeline
           if t["event"] == "repair_donor_rejected"]
    assert rej and rej[0]["donor"] == 0 and rej[0]["verify"]
    assert c.auditor.repairs[0]["donor"] == 1
    # never propagated: the repaired replica's re-reported digests
    # agree with the majority from here on
    before = len(c.auditor.findings)
    _pump(c, ctl, 6, traffic=lambda: c.submit(0, b"p"))
    post = [f for f in c.auditor.findings[before:]
            if 2 in f.get("got_replicas", ())]
    assert post == []


# ---------------------------------------------------------------------------
# the full loop, three engines
# ---------------------------------------------------------------------------

def test_full_loop_sim_quarantine_repair_backfill_readmit():
    c = SimCluster(CFG, 3, audit=True)
    obs = Observability()
    c.obs = obs
    ctl = RepairController(c, obs=obs, probation_steps=4)
    c.run_until_elected(0)
    for i in range(8):
        c.submit(0, b"v%d" % i)
    for _ in range(4):
        c.step()
        ctl.observe()
    target = int(c.last["commit"].min()) - 1
    corrupt_slot(c, 2, target)
    _pump(c, ctl, 30, traffic=lambda: c.submit(0, b"w"))
    # healed: replica re-admitted, findings closed, coverage gap-free
    assert ctl.repairs_done == 1 and ctl.states == {}
    assert c.auditor.summary()["unrepaired"] == 0
    rec = c.auditor.repairs[0]
    assert rec["replica"] == 2 and rec["lo"] <= target < rec["hi"]
    cov = c.auditor.coverage(0, rec["lo"], rec["hi"])
    assert cov["ok"], cov
    events = [t["event"] for t in ctl.timeline]
    # a repair_backfill_pending may sit between install and close (the
    # newest indices wait one lazy-push step for follower co-signing)
    core = [e for e in events if e != "repair_backfill_pending"]
    assert core == ["replica_quarantined", "repair_installed",
                    "repair_backfilled", "repair_readmitted"]
    # gauge cycled 1 -> 0; counters exported
    assert obs.metrics.get("replica_quarantined", replica=2,
                           group=0) == 0
    assert obs.metrics.get("repairs_total", group=0) == 1
    # quarantine isolation really ran through the peer-mask machinery
    assert bool(c.peer_mask.all())
    assert 2 not in c.need_recovery


def test_readmit_hysteresis_counts_clean_steps():
    c = _audited_sim()
    ctl = RepairController(c, probation_steps=5)
    target = int(c.last["commit"].min()) - 1
    corrupt_slot(c, 2, target)
    # detect + repair
    for _ in range(6):
        c.submit(0, b"x")
        c.step()
        ctl.observe()
        if ctl.needs_drain():
            ctl.drive()
        if ctl.repairs_done:
            break
    assert ctl.repairs_done == 1
    assert ctl.states[(0, 2)]["state"] == "probation"
    assert ctl.serving_blocked(0, 2)
    # fewer than N clean steps: still blocked
    for _ in range(4):
        c.submit(0, b"y")
        c.step()
        ctl.observe()
    assert ctl.serving_blocked(0, 2)
    c.step()
    ctl.observe()
    assert not ctl.serving_blocked(0, 2)      # 5th clean step re-admits
    assert ctl.timeline[-1]["event"] == "repair_readmitted"


def test_sharded_repair_other_groups_strictly_advance():
    sc = ShardedCluster(CFG, 3, 2, audit=True)
    ctl = RepairController(sc, probation_steps=3)
    sc.place_leaders()

    def traffic(n=1):
        for g in range(2):
            lead = sc.leader_hint(g)
            if lead >= 0:
                for i in range(n):
                    sc.submit(g, lead, b"g%d-%d" % (g, i))
    traffic(4)
    for _ in range(4):
        sc.step()
        ctl.observe()
    target = int(sc.last["commit"][1].min()) - 1
    corrupt_slot(sc, 1, target, group=1)
    frontiers = []
    for _ in range(40):
        frontiers.append(int(sc.last["commit"][0].max())
                         + int(sc.rebased_total[0]))
        traffic()
        sc.step()
        ctl.observe()
        if ctl.needs_drain():
            ctl.drive()
        if ctl.repairs_done and not ctl.states:
            break
    assert ctl.repairs_done == 1 and not ctl.states
    # fault isolation THROUGH the repair: group 0's frontier strictly
    # advanced every step of group 1's quarantine + repair window
    assert all(b > a for a, b in zip(frontiers, frontiers[1:]))
    assert sc.auditor.first_divergence(group=0) is None
    rec = sc.auditor.repairs[0]
    assert rec["group"] == 1
    assert sc.auditor.coverage(1, rec["lo"], rec["hi"])["ok"]
    assert sc.auditor.summary()["unrepaired"] == 0


def test_mesh_engine_repair_smoke():
    """The repair loop on the multi-chip spmd engine (1x3 layout on
    the conftest-forced virtual devices): quarantine, verified
    re-install, backfill, re-admit — same host machinery, mesh
    dispatch."""
    sc = ShardedCluster(CFG, 3, 2, audit=True, mesh=(1, 3))
    ctl = RepairController(sc, probation_steps=3)
    sc.place_leaders()
    for g in range(2):
        for i in range(5):
            sc.submit(g, sc.leader_hint(g), b"m%d-%d" % (g, i))
    for _ in range(4):
        sc.step()
        ctl.observe()
    target = int(sc.last["commit"][1].min()) - 1
    corrupt_slot(sc, 1, target, group=1)
    for i in range(40):
        lead = sc.leader_hint(0)
        if lead >= 0:
            sc.submit(0, lead, b"k%d" % i)
        sc.step()
        ctl.observe()
        if ctl.needs_drain():
            ctl.drive()
        if ctl.repairs_done and not ctl.states:
            break
    assert ctl.repairs_done == 1 and not ctl.states
    assert sc.auditor.summary()["unrepaired"] == 0


# ---------------------------------------------------------------------------
# bounded retry / backoff / escalation
# ---------------------------------------------------------------------------

def test_escalation_after_bounded_retries_latches_page():
    c = SimCluster(CFG, 3, audit=True)
    obs = Observability()
    c.obs = obs
    ctl = RepairController(c, obs=obs, probation_steps=3,
                           max_attempts=2, backoff_steps=2)
    eng = AlertEngine(obs.metrics, rules=default_rules())
    c.run_until_elected(0)
    for i in range(8):
        c.submit(0, b"v%d" % i)
    for _ in range(4):
        c.step()
    for i in range(30):
        c.submit(0, b"pad%d" % i)
        c.step()
    commit = int(c.last["commit"].min())
    corrupt_slot(c, 2, commit - 1)    # victim
    corrupt_slot(c, 0, 3)             # every donor corrupted at old,
    corrupt_slot(c, 1, 4)             # out-of-window indices
    steps = 0
    while steps < 40 and ctl.escalations == 0:
        c.submit(0, b"x")
        c.step()
        ctl.observe()
        if ctl.needs_drain():
            ctl.drive()
        steps += 1
    assert ctl.escalations == 1
    assert ctl.states[(0, 2)]["state"] == "escalated"
    assert ctl.donors_rejected >= 2
    # backoff really spaced the attempts (step-domain, deterministic)
    backoffs = [t for t in ctl.timeline if t["event"] == "repair_backoff"]
    assert backoffs and backoffs[0]["next_try"] > backoffs[0]["step"]
    # the LATCHED page fires and stays latched
    assert "repair_failed" in eng.evaluate()["fired"]
    eng.evaluate()
    assert "repair_failed" in eng.firing(severity="page")
    # escalated replicas stay quarantined (no silent re-serve)
    assert ctl.serving_blocked(0, 2)
    assert not ctl.needs_drain()      # and no more repair churn


# ---------------------------------------------------------------------------
# chaos proof: pipelined, deterministic, artifact with repair timeline
# ---------------------------------------------------------------------------

@pytest.mark.chaos
def test_repair_nemesis_pipelined_deterministic_with_artifact(tmp_path):
    """The acceptance chaos proof: a seeded schedule bit-corrupts one
    replica's committed slot mid-run at pipeline=2; the run ends with
    (a) zero client-visible linearizability violations, (b) the
    corrupted replica re-admitted, (c) ledger coverage gap-free over
    the repaired range — and the same seed reproduces the identical
    verdict, with the repair timeline embedded in the artifact."""
    from rdma_paxos_tpu.chaos.artifact import load_reproducer
    from rdma_paxos_tpu.chaos.runner import NemesisRunner

    art = str(tmp_path / "repair_nemesis.json")
    r = NemesisRunner(n_replicas=3, seed=3, steps=36,
                      fault_kinds=("drop",), repair=True,
                      corrupt_step=12, pipeline=2, artifact_path=art)
    v = r.run()
    assert v["corrupted"] is not None
    victim, target = v["corrupted"]
    assert v["ok"], v
    assert v["linearizability"]["ok"] is True
    assert v["linearizability"]["violations"] == []
    assert v["invariant_violations"] == []
    # divergence happened, was localized, repaired, and backfilled
    assert v["audit"]["findings"] >= 1
    assert v["audit"]["unrepaired"] == 0
    assert v["audit"]["repairs"] == 1
    assert v["repair"]["active"] == {}
    events = [t["event"] for t in v["repair"]["timeline"]]
    assert events[0] == "replica_quarantined"
    assert "repair_installed" in events
    assert events[-1] == "repair_readmitted"
    assert v["repair"]["timeline"][0]["replica"] == victim
    # coverage gap-free over the repaired range
    rec = r.cluster.auditor.repairs[0]
    assert rec["lo"] <= target < rec["hi"]
    assert r.cluster.auditor.coverage(0, rec["lo"], rec["hi"])["ok"]
    # dispatches stayed pipelined (depth 2 witnessed around the repair)
    assert r.cluster.max_inflight_dispatches >= 2
    # deterministic same-seed verdict (repair timeline included)
    v2 = NemesisRunner(n_replicas=3, seed=3, steps=36,
                       fault_kinds=("drop",), repair=True,
                       corrupt_step=12, pipeline=2).run()
    for k in ("ok", "corrupted", "audit", "repair"):
        assert v[k] == v2[k], k
    # artifact embeds the repair timeline + the closed ledger
    doc = load_reproducer(art)
    assert doc["reason"] == "divergence repaired (self-healed)"
    assert doc["extra"]["repair"]["timeline"]
    rep = merge_dumps([doc["extra"]["audit"]])
    assert rep["unrepaired"] == 0 and rep["first"]["repaired"]


def test_repair_mid_pipeline_requires_drain_then_reengages():
    """The require_drained contract: a due repair defers while tickets
    are in flight (same rule as config changes), runs once drained,
    and depth-2 pipelining re-engages afterwards."""
    c = _audited_sim()
    ctl = RepairController(c, probation_steps=2)
    target = int(c.last["commit"].min()) - 1
    corrupt_slot(c, 2, target)
    # detect (serial steps)
    for _ in range(4):
        c.submit(0, b"d")
        c.step()
        ctl.observe()
        if ctl.states:
            break
    assert ctl.needs_drain()
    # with a dispatch in flight, drive() DEFERS (returns nothing)
    t1 = c.begin_step()
    assert ctl.drive() == []
    assert ctl.needs_drain()
    c.finish(t1)
    # drained: the repair runs
    assert ctl.drive() == [(0, 2)]
    assert ctl.repairs_done == 1
    # pipelining re-engages: two dispatches in flight post-repair
    c.submit(0, b"p1")
    a = c.begin_step()
    b = c.begin_step(take_batch=False)
    assert c.inflight_dispatches == 2
    c.finish(a)
    c.finish(b)
    assert c.max_inflight_dispatches >= 2


# ---------------------------------------------------------------------------
# driver integration (serial deterministic loop)
# ---------------------------------------------------------------------------

def test_driver_repairs_corrupted_leader_end_to_end():
    d = ClusterDriver(CFG, 3, timeout_cfg=TO, audit=True, repair=True,
                      repair_opts=dict(probation_steps=4))
    try:
        d.runtimes[0].timer._deadline = 0.0
        d.step()
        assert d.leader() == 0
        for _ in range(4):
            d.cluster.submit(0, b"w")
            d.step()
        # corrupt the LEADER: the driver must depose it, repair it
        # from a majority donor, and re-admit it
        target = int(d.cluster.last["commit"].min()) - 1
        corrupt_slot(d.cluster, 0, target)
        for i in range(40):
            lead = d.leader()
            d.cluster.submit(lead if lead >= 0 else 1, b"x%d" % i)
            d.step()
            if d.repair.repairs_done and not d.repair.states:
                break
        assert d.repair.repairs_done == 1
        assert d.repair.states == {}
        assert d.leader() != -1 and d.leader() != 0 or True
        h = d.health()
        assert h["repair"]["repairs_done"] == 1
        assert h["repair"]["active"] == {}
        assert h["audit"]["unrepaired"] == 0
        # the page fired (latched divergence) but the loop closed
        d.evaluate_alerts()
        assert "digest_divergence" in d.alerts.firing(severity="page")
        # quarantined replicas are refused client sessions while held
        assert not d._repair_blocked(0)
    finally:
        d.stop()


def test_driver_repair_requires_audit():
    with pytest.raises(ValueError, match="audit"):
        ClusterDriver(CFG, 3, timeout_cfg=TO, repair=True)


def test_sharded_driver_repairs_group_leader():
    from rdma_paxos_tpu.runtime.sharded_driver import (
        ShardedClusterDriver)
    d = ShardedClusterDriver(CFG, 3, 2, timeout_cfg=TO, audit=True,
                             repair=True,
                             repair_opts=dict(probation_steps=3))
    try:
        for _ in range(60):
            d.step()
            if all(v >= 0 for v in d.leaders()):
                break
        assert all(v >= 0 for v in d.leaders())
        c = d.cluster
        for g in range(2):
            for i in range(5):
                c.submit(g, d.leaders()[g], b"g%d-%d" % (g, i))
        for _ in range(4):
            d.step()
        lead1 = d.leaders()[1]
        target = int(c.last["commit"][1].min()) - 1
        corrupt_slot(c, lead1, target, group=1)
        g0 = []
        for i in range(80):
            g0.append(int(c.last["commit"][0].max())
                      + int(c.rebased_total[0]))
            l0 = d.leaders()[0]
            if l0 >= 0:
                c.submit(0, l0, b"k%d" % i)
            l1 = d.leaders()[1]
            if l1 >= 0:
                c.submit(1, l1, b"j%d" % i)
            d.step()
            if (d.repair.repairs_done and not d.repair.states
                    and all(v >= 0 for v in d.leaders())):
                break
        assert d.repair.repairs_done == 1 and not d.repair.states
        # group 1 re-elected a non-quarantined leader during repair
        assert d.leaders()[1] >= 0
        # group 0 never stalled behind group 1's repair
        assert g0[-1] > g0[0]
        assert c.auditor.summary()["unrepaired"] == 0
        assert d.health()["repair"]["repairs_done"] == 1
    finally:
        d.stop()


def test_restore_mask_preserves_other_quarantines():
    """Repairing one replica must not re-open links to a SECOND,
    still-quarantined replica — its isolation invariant survives the
    first repair."""
    c = _audited_sim()
    ctl = RepairController(c)
    fake = dict(type="DIVERGENCE", group=0, index=1, term=1,
                got_replicas=[1])
    with ctl._lock:
        ctl._quarantine(0, 1, fake)
        ctl._quarantine(0, 2, dict(fake, got_replicas=[2]))
    assert c.peer_mask[1, 2] == 0 and c.peer_mask[0, 1] == 0
    ctl._restore_mask(0, 1)
    # healthy links re-open...
    assert c.peer_mask[1, 0] == 1 and c.peer_mask[0, 1] == 1
    # ...but the still-quarantined peer stays cut, both directions
    assert c.peer_mask[1, 2] == 0 and c.peer_mask[2, 1] == 0
    assert c.peer_mask[2, 0] == 0


def test_repair_requires_gather_fanout():
    c = SimCluster(CFG, 3, fanout="psum", audit=True)
    with pytest.raises(ValueError, match="gather"):
        RepairController(c)
    with pytest.raises(ValueError, match="gather"):
        ClusterDriver(CFG, 3, timeout_cfg=TO, fanout="psum",
                      audit=True, repair=True)


def test_repeat_divergence_after_repair_is_redetected():
    """Closing an incident re-arms detection at its index: a LATER
    re-divergence there raises a fresh finding (it must not vanish
    into the closed incident's dedup), and the stale repair record —
    which predates it — must not close it."""
    led = AuditLedger(3)
    led.record_window(0, 0, [5, 6, 7], [1, 1, 1], 3, step=10)
    led.record_window(1, 0, [5, 6, 7], [1, 1, 1], 3, step=10)
    led.record_window(2, 0, [5, 9, 7], [1, 1, 1], 3, step=10)
    assert len(led.findings) == 1
    led.record_window(1, 0, [5, 6, 7], [1, 1, 1], 3, backfill=True,
                      step=20)
    led.mark_repaired(0, 2, 0, 3, donor=1, index=3, step=20)
    assert led.summary()["unrepaired"] == 0
    # the SAME index diverges again (post-repair bit rot)
    led.record_window(2, 0, [5, 8, 7], [1, 1, 1], 3, step=30)
    assert len(led.findings) == 2, "re-divergence must not be deduped"
    assert led.summary()["unrepaired"] == 1
    # ...and the stale record from step 20 does not close the step-30
    # finding, in-process or through the merge path
    rep = merge_dumps([led.dump()])
    assert rep["unrepaired"] == 1


def test_multi_replica_finding_needs_every_replica_repaired():
    """A merge-mode finding naming several diverged holders stays OPEN
    until every one of them has a covering repair record — one healed
    replica must not close the incident (CLI keeps exiting 1)."""
    doc = dict(
        digest_epoch=DIGEST_EPOCH,
        findings=[dict(type="DIVERGENCE", mode="merge", group=0,
                       index=5, term=1, expected_digest=1,
                       expected_replicas=[0], got_term=1,
                       got_digest=2, got_replicas=[1, 2], step=None)],
        repairs=[dict(group=0, replica=1, lo=0, hi=10, donor=0,
                      index=10, step=3)],
        groups=[])
    rep = merge_dumps([doc])
    assert rep["unrepaired"] == 1
    assert not rep["findings"][0].get("repaired")
    doc["repairs"].append(dict(group=0, replica=2, lo=0, hi=10,
                               donor=0, index=10, step=7))
    rep2 = merge_dumps([doc])
    assert rep2["unrepaired"] == 0
    assert rep2["findings"][0]["repaired"]


# ---------------------------------------------------------------------------
# CLI repair-status section + exit semantics
# ---------------------------------------------------------------------------

def test_cli_report_repaired_divergence_exits_clean(tmp_path, capsys):
    led = AuditLedger(3)
    led.record_window(0, 0, [5, 6, 7], [1, 1, 1], 3)
    led.record_window(1, 0, [5, 6, 7], [1, 1, 1], 3)
    led.record_window(2, 0, [5, 9, 7], [1, 1, 1], 3)
    assert led.first_divergence()["index"] == 1
    f = tmp_path / "dump.json"
    f.write_text(json.dumps(led.dump()))
    # unrepaired divergence -> exit 1
    assert audit_mod.main(["report", str(f)]) == 1
    # repaired + backfilled -> exit 0, with the repair-status section
    led.record_window(1, 0, [5, 6, 7], [1, 1, 1], 3, backfill=True)
    led.mark_repaired(0, 2, 0, 3, donor=1, index=3, step=42)
    f.write_text(json.dumps(led.dump()))
    assert audit_mod.main(["report", str(f)]) == 0
    out = capsys.readouterr().out
    assert "repair status" in out
    assert "re-installed from donor 1" in out
    assert "REPAIRED" in out
    # the merged report carries the repair records through
    rep = merge_dumps([led.dump()])
    assert rep["unrepaired"] == 0 and rep["repairs"]


# ---------------------------------------------------------------------------
# CI: jit-safety scan extension + bench smoke
# ---------------------------------------------------------------------------

def test_jit_safety_scan_covers_repair_surface():
    """consensus/step.py (incl. the redigest entry point), ops/*, and
    parallel/mesh.py run inside jit/shard_map: no repair-pipeline or
    obs symbol may be reachable there, and runtime/repair.py itself
    never reaches into jit. Enforced by the graftlint ``jit-purity``
    pass (device manifest + ``HOST_PURE_MODULES['rdma_paxos_tpu/
    runtime/repair.py']`` carry this test's former inline rules)."""
    from rdma_paxos_tpu.analysis import assert_jit_purity
    assert_jit_purity()


def test_measure_repair_smoke():
    from benchmarks.run_bench import measure_repair
    out = measure_repair(cfg=CFG, steps=20, per_step=2, payload=16,
                         warmup=3, repeats=2, corrupt_after=10,
                         probation=3, mttr_budget=60)
    assert out["off"]["committed"] > 0 and out["on"]["committed"] > 0
    assert "overhead_pct" in out
    m = out["mttr"]
    assert m["mttr_steps"] is not None and m["mttr_steps"] > 0
    assert m["detection_steps"] is not None
    assert m["repairs_done"] == 1
    assert m["coverage_ok"] is True
