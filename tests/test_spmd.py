"""shard_map path: the identical protocol program over a real 8-device mesh
(virtual CPU devices here; one replica per TPU chip in production). This is
the compilation/sharding contract the driver's dryrun validates."""

import jax
import numpy as np
import pytest

from rdma_paxos_tpu.config import LogConfig
from rdma_paxos_tpu.consensus.state import Role
from rdma_paxos_tpu.runtime.sim import SimCluster
from tests.readback_ref import assert_same, drive

CFG = LogConfig(n_slots=64, slot_bytes=32, window_slots=16, batch_slots=8)

pytestmark = pytest.mark.skipif(
    len(jax.devices()) < 8, reason="needs 8 (virtual) devices")


def test_spmd_replication_8_replicas():
    c = SimCluster(CFG, 8, mode="spmd")
    c.run_until_elected(0)
    c.submit(0, b"spmd!")
    res = c.step()
    assert res["commit"][0] == 2
    res = c.step()
    assert list(res["commit"]) == [2] * 8
    for r in range(8):
        assert [p for (_, _, _, p) in c.replayed[r]] == [b"spmd!"]


@pytest.mark.parametrize("mode", ["sim", "spmd"])
def test_psum_fanout_matches_gather(mode):
    """The O(W) psum window broadcast must be observably identical to the
    O(R·W) gather-select fan-out under full connectivity (the only regime
    it is specified for): same commits, same replayed bytes, same log.
    Parametrized over both execution modes because the collective
    LOWERING differs only under ``shard_map`` (a real masked all-reduce
    vs an all-gather + select); the vmap simulation lowers both to data
    movement on one device."""
    runs = {}
    for fo in ("gather", "psum"):
        c = SimCluster(CFG, 5, mode=mode, fanout=fo)
        c.run_until_elected(0)
        for i in range(6):
            c.submit(0, b"op-%d" % i)
            c.step()
        # leadership churn under full connectivity: new leader takes over
        c.step(timeouts=[2])
        c.submit(2, b"after-churn")
        for _ in range(3):
            res = c.step()
        runs[fo] = (res, c.replayed, np.asarray(c.state.log.buf))
    rg, replg, bufg = runs["gather"]
    rp, replp, bufp = runs["psum"]
    for k in ("term", "role", "commit", "end", "head"):
        assert list(rg[k]) == list(rp[k]), k
    assert replg == replp
    assert (bufg == bufp).all()


def test_spmd_group3_with_learners():
    """Mesh bigger than the voting group: replicas outside the membership
    bitmask are learners — they absorb the log but neither vote nor count
    toward quorum (the joiner state of the reference before its CONFIG
    entry commits, dare_ibv_ud.c:972-1068)."""
    c = SimCluster(CFG, 8, group_size=3, mode="spmd")
    c.run_until_elected(1)
    c.submit(1, b"learn")
    c.step()
    res = c.step()
    # everyone (members + learners) converges on the log...
    assert list(res["end"]) == [2] * 8
    # ...and commit required only the 3-member quorum
    assert res["commit"][1] == 2


def test_spmd_failover():
    c = SimCluster(CFG, 8, mode="spmd")
    c.run_until_elected(0)
    c.submit(0, b"pre")
    c.step()
    c.step()
    c.partition([[0], list(range(1, 8))])
    res = c.step(timeouts=[3])
    assert res["role"][3] == int(Role.LEADER)
    c.submit(3, b"post")
    res = c.step()
    assert res["commit"][3] == 4


@pytest.mark.parametrize("n,K,scan", [(5, 1, False), (12, 2, False),
                                      (100, 16, False), (12, 2, True),
                                      (100, 16, True)],
                         ids=["step", "burst_k2", "burst_k16", "scan_k2",
                              "scan_k16"])
def test_spmd_packed_row_unpacks_to_fieldwise_readback(n, K, scan):
    """One replica per device: the packed row is assembled per device
    and read back as one (sharded) array — same values as the
    per-field reads, the failure detector's ``peer_acked`` row and the
    config view included. And the same as the vmap run's on the same
    inputs, dispatch for dispatch, replayed streams and apply cursors
    too: who splits the arguments over the chips changes no value."""
    cfg = LogConfig(n_slots=512, slot_bytes=32, window_slots=16,
                    batch_slots=8)
    c = SimCluster(cfg, 3, mode="spmd", fanout="psum", scan=scan)
    v = SimCluster(cfg, 3, fanout="psum", scan=scan)
    seen, vseen = drive(c, n, fused=K > 1), drive(v, n, fused=K > 1)
    assert max(k for _, k, _, _ in seen) == K
    assert [(kind, k) for kind, k, _, _ in seen] == [
        (kind, k) for kind, k, _, _ in vseen]
    for (kind, _, res, ref), (_, _, vres, _) in zip(seen, vseen):
        assert (ref is None) == (kind == "scan")
        if ref is not None:
            assert_same(res, ref)
        assert_same(res, vres)
    assert c.last["commit"][0] == 1 + 3 * n
    assert c.scan_dispatches == (3 if scan else 0)
    for r in range(3):
        assert c.replayed[r] == v.replayed[r], r
        assert len(c.replayed[r]) == 3 * n
    assert np.array_equal(c.applied, v.applied)


# ---------------------------------------------------------------------------
# where a dispatch's arguments are put (ISSUE 46)
# ---------------------------------------------------------------------------

GCFG = LogConfig(n_slots=512, slot_bytes=128, window_slots=16,
                 batch_slots=8)


def _elected(**kw):
    c = SimCluster(GCFG, 3, mode="spmd", fanout="psum", **kw)
    c.run_until_elected(0)
    return c


def _guarded_step(c):
    c.submit(0, b"one step")
    res = c.finish(c.begin_step())
    assert res["commit"][0] == 2


def _guarded_burst(n, K):
    def run(c):
        for j in range(n):
            c.submit(0, b"b-%03d" % j)
        t = c.begin_burst()
        assert t.K == K and t.kind == ("scan" if c.scan else "burst")
        assert c.finish(t)["commit"][0] == 1 + n
    return run


def _guarded_txn_step(c):
    c.set_txn_watch(1, int(c.last["term"][0]))
    c.submit(0, b"watched")
    res = c.finish(c.begin_step())
    assert "txn_vote" in res and res["commit"][0] == 2


def _guarded_fetch(c):
    """A dispatch whose commit the host has not applied yet: finish
    runs the standalone replay fetch (``starts`` is its argument)."""
    fetches = []
    jitted = c._fetch_all

    def seen_fetch(log, starts):
        fetches.append(starts.sharding)
        return jitted(log, starts)
    c._fetch_all = seen_fetch
    for j in range(5):
        c.submit(0, b"f-%d" % j)
    c.finish(c.begin_step())
    c.finish(c.begin_step())        # the followers' commit
    assert fetches and all(
        s.spec == jax.sharding.PartitionSpec("replica") for s in fetches)
    assert [len(c.replayed[r]) for r in range(3)] == [5, 5, 5]


@pytest.mark.parametrize("kw,run", [
    ({}, _guarded_step),
    ({}, _guarded_burst(5, 2)),
    ({}, _guarded_burst(100, 16)),
    (dict(scan=True), _guarded_burst(12, 2)),
    (dict(txn=True), _guarded_txn_step),
    ({}, _guarded_fetch),
], ids=["step", "burst_k2", "burst_k16", "scan_k2", "txn_step",
        "replay_fetch"])
def test_spmd_arguments_are_put_where_the_program_wants_them(kw, run):
    """Every argument of a dispatch and of the replay fetch reaches the
    mesh already split over the replica axis: with device-to-device
    transfers disallowed, an argument put on ONE chip (``jnp.asarray``)
    fails inside the call, where the runtime would re-shard it ("
    Disallowed device-to-device transfer"); an argument put with the
    program's own input sharding passes. (The smallest fused tier is
    K = 2: ``K_TIERS``; K = 1 is the serial step.)"""
    c = _elected(**kw)
    with jax.transfer_guard_device_to_device("disallow"):
        run(c)


def test_default_mode_arguments_stay_one_device_arrays(monkeypatch):
    """Without a mesh the put IS ``jnp.asarray``: uncommitted arrays on
    the default device, and no sharding is built on the way, at
    construction or in a dispatch."""
    def refuse(*a, **k):
        raise AssertionError("the default mode builds no NamedSharding")
    monkeypatch.setattr(jax.sharding, "NamedSharding", refuse)
    monkeypatch.setattr(jax, "device_put", refuse)
    c = SimCluster(GCFG, 3, fanout="psum")
    assert c.mesh is None
    c.run_until_elected(0)
    c.submit(0, b"x")
    c.finish(c.begin_step())
    for j in range(12):
        c.submit(0, b"y%d" % j)
    c.finish(c.begin_burst())
    c.finish(c.begin_step())        # the followers' commit
    assert len(c.replayed[2]) == 13
    a = c._put(np.zeros(3, np.int32))
    assert isinstance(a.sharding, jax.sharding.SingleDeviceSharding)
    assert not a.committed


def test_spmd_prewarm_leaves_no_compile_for_the_served_path():
    """``prewarm`` builds its arguments through the dispatches' own put,
    so each program holds ONE executable, and a served step, burst, scan
    and replay fetch after it add none (a committed, sharded argument
    and an uncommitted one-chip argument are two executables of one
    ``jax.jit``: a prewarm on the other kind leaves the first served
    dispatch to compile inside the loop, past the election timers)."""
    # a geometry of its own: no earlier test compiled these programs
    cfg = LogConfig(n_slots=256, slot_bytes=128, window_slots=16,
                    batch_slots=8)
    c = SimCluster(cfg, 3, mode="spmd", fanout="psum", scan=True)
    c.prewarm(tiers=(2,))
    fns = dict(step=c._program("step", elections=True)[0],
               stable=c._program("step", elections=False)[0],
               burst=c._program("burst", 2)[0],
               scan=c._program("scan", 2)[0],
               **{"fetch_%d" % W: fn
                  for W, fn in c._fetch_all.programs.items()})
    assert len(fns) == 4 + 3        # the fetch at each of its widths
    assert {k: f._cache_size() for k, f in fns.items()} == dict.fromkeys(
        fns, 1)
    c.run_until_elected(0)
    for scan in (False, True):
        c.scan = scan
        for j in range(12):
            c.submit(0, b"%d-%d" % (scan, j))
        t = c.begin_burst()
        assert t.K == 2
        c.finish(t)
        c.finish(c.begin_step())
    c.scan = False
    c.submit(0, b"tail")            # a serial step that commits: a fetch
    c.finish(c.begin_step())
    c.finish(c.begin_step())
    assert [len(c.replayed[r]) for r in range(3)] == [25, 25, 25]
    assert {k: f._cache_size() for k, f in fns.items()} == dict.fromkeys(
        fns, 1)
