"""Three groups on three machines, a machine a chip:
``ShardedCluster(cfg, 3, 3, mesh=(1, 3))`` on three forced CPU devices,
replica r's ring row of every group on device r: the sharded engine's
``(group, replica)`` mesh path as the cell ``redis_ycsb_a_c50_g3r3_x4``
runs it (ISSUE 49).

* every argument of a dispatch, of the scan tier and of the replay
  fetch reaches the mesh already split ``P(group, replica)`` (the
  transfer guard, as ``tests/test_spmd.py`` has it for ``sim.py``);
* the same seeded inputs (elections, bursts, a partition, rollovers)
  through ``mesh=None`` and ``mesh=(1, 3)``: every dispatch's ``res``,
  the replayed streams and ``applied`` equal;
* without a mesh the arguments stay uncommitted one-device arrays;
* ``prewarm`` leaves no compile for the served path;
* the sharded engine rolls over (``_maybe_rebase``), with and without a
  mesh, on the serial step and after a burst;
* the phases are recorded at the same places on the mesh path as off
  it, and the put counts its calls and bytes;
* ``ShardedClusterDriver(..., mesh=(1, 3))`` with three ``toyserver``
  apps under the shim: YCSB-A through each group's leader, all three
  apps held to what was acknowledged, and with the benchmark's
  ``group_replay_dropped`` one (app, group) pair is not.
"""

import random
import types

import jax
import numpy as np
import pytest

from rdma_paxos_tpu.config import LogConfig
from rdma_paxos_tpu.consensus.log import META_W, EntryType
from rdma_paxos_tpu.consensus.state import Role
from rdma_paxos_tpu.consensus.step import arg_layout
from rdma_paxos_tpu.obs.metrics import MetricsRegistry
from rdma_paxos_tpu.obs.spans import StepPhaseProfiler
from rdma_paxos_tpu.shard.cluster import ShardedCluster

pytestmark = pytest.mark.skipif(
    len(jax.devices()) < 3, reason="needs 3 (virtual) devices")

R = G = 3
MESH = (1, 3)
GCFG = LogConfig(n_slots=512, slot_bytes=128, window_slots=16,
                 batch_slots=8)
# a ring that rolls over every few hundred entries (tests/test_rebase.py)
RCFG = LogConfig(n_slots=64, slot_bytes=32, window_slots=16, batch_slots=8,
                 rebase_threshold=300)


def split_over_the_mesh(c, arr) -> bool:
    """``arr`` lies ``P(group, replica)`` on ``c``'s mesh (an axis of
    one device may go unnamed: ``parallel/mesh.py`` ``axes_spec``)."""
    want = jax.sharding.NamedSharding(
        c.mesh, jax.sharding.PartitionSpec("group", "replica"))
    return arr.sharding.is_equivalent_to(want, arr.ndim)


SEND = int(EntryType.SEND)


def placed(cfg=GCFG, mesh=MESH, **kw):
    c = ShardedCluster(cfg, R, G, mesh=mesh,
                       **dict(dict(fanout="psum"), **kw))
    assert c.place_leaders("round_robin") == [0, 1, 2]
    return c


def submit_each(c, n, tag=b"e"):
    """``n`` entries a group, through the group's leader."""
    for g in range(G):
        c.submit_many(g, g, [(SEND, 1, 0, b"%s-%d-%03d" % (tag, g, j))
                             for j in range(n)])


# ---------------------------------------------------------------------------
# (a) where a dispatch's arguments are put
# ---------------------------------------------------------------------------

def _guarded_step(c):
    submit_each(c, 1)
    res = c.finish(c.begin_step())
    assert [int(res["commit"][g, g]) for g in range(G)] == [2, 2, 2]


def _guarded_burst(n, K):
    def run(c):
        submit_each(c, n)
        t = c.begin_burst()
        assert t.K == K and t.kind == ("scan" if c.scan else "burst")
        res = c.finish(t)
        assert [int(res["commit"][g, g]) for g in range(G)] == [1 + n] * G
    return run


def _guarded_txn_step(c):
    for g in range(G):
        c.set_txn_watch(g, 1, int(c.last["term"][g, g]))
    submit_each(c, 1)
    res = c.finish(c.begin_step())
    assert res["txn_vote"].shape == (G, R)


def _guarded_fetch(c):
    """Dispatches whose commit the host has not applied yet: finish
    runs the standalone replay fetch (``starts`` is its argument) and
    reads nine rows off three devices."""
    fetches = []
    jitted = c._fetch_all

    def seen_fetch(log, starts):
        fetches.append(split_over_the_mesh(c, starts))
        return jitted(log, starts)
    c._fetch_all = seen_fetch
    submit_each(c, 5)
    c.finish(c.begin_step())
    c.finish(c.begin_step())        # the followers' commit
    assert fetches and all(fetches)
    assert [[len(c.replayed[g][r]) for r in range(R)]
            for g in range(G)] == [[5] * R] * G


@pytest.mark.parametrize("kw,run", [
    ({}, _guarded_step),
    ({}, _guarded_burst(5, 2)),
    ({}, _guarded_burst(100, 16)),
    (dict(scan=True), _guarded_burst(12, 2)),
    (dict(txn=True), _guarded_txn_step),
    ({}, _guarded_fetch),
], ids=["step", "burst_k2", "burst_k16", "scan_k2", "txn_step",
        "replay_fetch"])
def test_mesh_arguments_are_put_where_the_program_wants_them(kw, run):
    """With device-to-device transfers disallowed, an argument put on
    ONE device (``jnp.asarray``) fails inside the call, where the
    runtime would split it over the mesh; an argument put with the
    program's own input sharding passes. ``finish`` (the packed row's
    read, the fetch's rows, the scan tier's staged rows) runs under the
    guard too."""
    c = placed(**kw)
    with jax.transfer_guard_device_to_device("disallow"):
        run(c)


def test_mesh_rollover_moves_nothing_between_devices():
    """A rebase under the guard: the deltas are put where the state's
    rows lie, and the state stays ``P(group, replica)``."""
    c = placed(RCFG)
    with jax.transfer_guard_device_to_device("disallow"):
        while c.rebases.min() < 1:
            submit_each(c, 8)
            c.step()
    assert all(split_over_the_mesh(c, leaf)
               for leaf in jax.tree.leaves(c.state))


# ---------------------------------------------------------------------------
# (c), (d): without a mesh nothing changes; prewarm covers the served path
# ---------------------------------------------------------------------------

def test_without_a_mesh_arguments_stay_one_device_arrays(monkeypatch):
    """``mesh is None``: the put IS ``jnp.asarray``, uncommitted arrays
    on the default device, and no sharding is built on the way (g3's
    programs and calls stay what they are)."""
    def refuse(*a, **k):
        raise AssertionError("no mesh: no NamedSharding, no device_put")
    monkeypatch.setattr(jax.sharding, "NamedSharding", refuse)
    monkeypatch.setattr(jax, "device_put", refuse)
    c = placed(RCFG, mesh=None)
    assert c.mesh is None
    submit_each(c, 1)
    c.finish(c.begin_step())
    submit_each(c, 12)
    c.finish(c.begin_burst())
    c.finish(c.begin_step())
    assert len(c.replayed[2][0]) == 13
    while c.rebases.min() < 1:      # a rebase's deltas too
        submit_each(c, 8)
        c.step()
    a = c._put(np.zeros((G, R), np.int32))
    assert isinstance(a.sharding, jax.sharding.SingleDeviceSharding)
    assert not a.committed


def test_mesh_prewarm_leaves_no_compile_for_the_served_path():
    """``prewarm(tiers=(2,))`` builds its arguments through the
    dispatches' own put, so each program holds ONE executable and a
    served step, burst, scan and replay fetch after it add none."""
    # a geometry of its own: no earlier test compiled these programs
    cfg = LogConfig(n_slots=256, slot_bytes=128, window_slots=16,
                    batch_slots=8)
    c = ShardedCluster(cfg, R, G, mesh=MESH, fanout="psum", scan=True)
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
    c.place_leaders("round_robin")
    for scan in (False, True):
        c.scan = scan
        submit_each(c, 12, b"%d" % scan)
        t = c.begin_burst()
        assert t.K == 2
        c.finish(t)
        c.finish(c.begin_step())
    c.scan = False
    submit_each(c, 1, b"tail")      # a serial step that commits: a fetch
    c.finish(c.begin_step())
    c.finish(c.begin_step())
    assert [[len(c.replayed[g][r]) for r in range(R)]
            for g in range(G)] == [[25] * R] * G
    assert {k: f._cache_size() for k, f in fns.items()} == dict.fromkeys(
        fns, 1)


# ---------------------------------------------------------------------------
# the rollover (PERF.md sec. 7 (5), found by PR 48's fuzz)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("how", ["step", "burst"])
@pytest.mark.parametrize("mesh", [None, MESH], ids=["one_chip", "mesh"])
def test_sharded_engine_rolls_over(mesh, how):
    """Every group crosses ``rebase_threshold`` more than once, on the
    serial step or at the end of a burst; the offsets come back under
    it in ``res`` and on the device, and the streams are exact."""
    c = placed(RCFG, mesh=mesh)
    sent = [[] for _ in range(G)]
    n = 0
    while c.rebases.min() < 2:
        for g in range(G):
            take = [b"%s%d-%05d" % (how.encode(), g, n + j)
                    for j in range(8 if how == "step" else 12)]
            sent[g] += take
            c.submit_many(g, g, [(SEND, 1, 0, p) for p in take])
        n += 12
        res = c.step() if how == "step" else c.step_burst()
        assert int(res["end"].max()) < RCFG.rebase_threshold + 2 * 12
    for _ in range(3):
        res = c.step()
    assert int(res["end"].max()) < RCFG.rebase_threshold
    assert (res["head"] <= res["apply"]).all()
    assert (res["apply"] <= res["commit"]).all()
    assert (res["commit"] <= res["end"]).all()
    assert np.array_equal(np.asarray(c.state.end), res["end"])
    assert (c.rebased_total > 0).all() and not c.need_recovery
    for g in range(G):
        for r in range(R):
            assert [p for (_, _, _, p) in c.replayed[g][r]] == sent[g], (
                g, r)


# ---------------------------------------------------------------------------
# (b) the two engines, the same inputs
# ---------------------------------------------------------------------------

def drive(mesh, seed=49):
    """A seeded schedule: elections, serial steps, bursts of both
    tiers' sizes, a leader partitioned away and a failover, the heal,
    and traffic until every group has rolled over; -> every dispatch's
    ``res``, the replayed streams, ``applied``."""
    rng = random.Random(seed)
    c = ShardedCluster(RCFG, R, G, mesh=mesh, fanout="gather")
    log = []
    finish = c.finish

    def noted(ticket):
        res = finish(ticket)
        log.append({k: np.array(v) for k, v in res.items()})
        return res
    c.finish = noted
    c.place_leaders("round_robin")
    leaders = [0, 1, 2]

    def traffic(rounds, most=14):
        for i in range(rounds):
            for g in range(G):
                c.submit_many(g, leaders[g], [
                    (SEND, 1 + g, 0, b"%d-%d-%d" % (g, i, j) * rng.randint(1, 2))
                    for j in range(rng.randint(0, most))])
            if rng.random() < 0.5:
                c.step()
            else:
                c.step_burst()
    traffic(6)
    c.partition(1, [[1], [0, 2]])   # group 1's leader cut off
    for _ in range(4):
        if c.leader_hint(1) == 2 and c.last["term"][1, 2] > 1:
            break
        c.step(timeouts={1: [2]})
    assert int(c.last["role"][1, 2]) == int(Role.LEADER)
    leaders[1] = 2
    # fewer entries than the window holds: a replica left further
    # behind than ``window_slots`` is caught up by snapshot alone
    traffic(3, most=4)
    c.heal()
    for _ in range(3):
        c.step()
    for _ in range(40):
        traffic(2)
    assert c.rebases.min() >= 1 and not c.need_recovery
    for _ in range(3):
        c.step()
    streams = [[list(c.replayed[g][r]) for r in range(R)]
               for g in range(G)]
    return log, streams, c.applied.copy(), c.rebases.copy()


def test_mesh_and_one_chip_engines_replay_equal_streams():
    one, one_streams, one_applied, one_rebases = drive(None)
    mesh, mesh_streams, mesh_applied, mesh_rebases = drive(MESH)
    assert len(one) == len(mesh) > 30
    for i, (a, b) in enumerate(zip(one, mesh)):
        assert set(a) == set(b)
        for k in a:
            assert np.array_equal(a[k], b[k]), (i, k)
    assert one_streams == mesh_streams
    assert sum(len(s) for s in one_streams[0]) > 0
    assert np.array_equal(one_applied, mesh_applied)
    assert np.array_equal(one_rebases, mesh_rebases) and one_rebases.min() >= 1


# ---------------------------------------------------------------------------
# the phases and the put's counters, on the mesh path as off it
# ---------------------------------------------------------------------------

PUT_PHASES = ("host_encode", "input_transfer", "device_dispatch",
              "program_call", "dispatch_lock_wait", "quorum_wait",
              "readback_rest", "post_readback", "apply", "replay_fetch",
              "fetch_lock_wait", "fetch_enqueue", "fetch_read",
              "replay_decode", "finish_tail")


def profiled(mesh):
    """The same few dispatches under a profiler; -> (phase counts,
    counters)."""
    c = placed(mesh=mesh)
    metrics = MetricsRegistry()
    c.profiler = StepPhaseProfiler(metrics)
    submit_each(c, 3)
    c.finish(c.begin_step())                # a step, and its fetch
    submit_each(c, 12)
    c.finish(c.begin_burst())               # a burst (K = 2), its fetch
    c.finish(c.begin_step())                # the followers' commit
    counts = {p: c.profiler.acc[p][0] for p in PUT_PHASES}
    return counts, metrics.snapshot()["counters"], c


def test_mesh_path_records_the_phases_where_the_one_chip_path_does():
    one, one_counters, _ = profiled(None)
    mesh, mesh_counters, c = profiled(MESH)
    assert one == mesh
    assert one["input_transfer"] == one["program_call"] == 3
    assert one["fetch_enqueue"] == one["fetch_read"] == one["replay_fetch"]
    fetches = one["replay_fetch"]
    assert fetches >= 2
    # ONE array a step, one a burst, one a fetch, on either path: a
    # device_put on the mesh, an asarray without one (six arrays a
    # burst and seven a step until PR 51)
    assert mesh_counters["input_put_calls_total"] == 3 + fetches
    assert one_counters["input_put_calls_total"] == 3 + fetches
    # a device buffer a call without a mesh, one a chip with
    assert one_counters["input_put_buffers_total"] == 3 + fetches
    assert mesh_counters["input_put_buffers_total"] == 3 * (3 + fetches)
    # the bytes handed over are the staging buffers', mesh or none: by
    # the layout's formula, a row a (group, replica) pair
    assert (mesh_counters["input_put_bytes_total"]
            == one_counters["input_put_bytes_total"])
    row = 4 * G * R
    step, burst = (128 * arg_layout(GCFG, R, k).rows for k in (1, 2))
    B, sw = GCFG.batch_slots, GCFG.slot_words
    for k, width in ((1, step), (2, burst)):
        words = k * B * (sw + META_W) + k + R + 3
        assert 0 <= width - words < 1024 * k and width % 1024 == 0
    assert one_counters["input_put_bytes_total"] == row * (
        2 * step + burst + fetches)
    assert c.health()["mesh"] == dict(layout="1x3", group_shards=1,
                                      devices=[0, 1, 2])


def test_both_engines_probes_carry_the_put_counters_from_construction():
    metrics = MetricsRegistry()
    StepPhaseProfiler(metrics)
    counters = metrics.snapshot()["counters"]
    assert counters["input_put_calls_total"] == 0
    assert counters["input_put_bytes_total"] == 0
    assert counters["input_put_buffers_total"] == 0


@pytest.mark.parametrize("mesh", [None, MESH], ids=["one_chip", "mesh"])
def test_put_buffers_a_dispatch_read_as_off_a_timeline(mesh):
    """``counter.input_put_buffers_total`` over
    ``phase.device_dispatch.count``, the way a run's ``timeline.json``
    gives it (two probes of the profiler's account): a burst's ONE
    array and its fetch's one are 2.0 a dispatch on one chip and 6.0
    on the three-chip mesh; six arrays a burst read 7.0 and 21.0."""
    c = placed(mesh=mesh)
    metrics = MetricsRegistry()
    c.profiler = StepPhaseProfiler(metrics)

    def probe():
        return dict(
            buffers=metrics.snapshot()["counters"][
                "input_put_buffers_total"],
            dispatches=c.profiler.acc["device_dispatch"][0],
            fetches=c.profiler.acc["replay_fetch"][0])
    c.finish(c.begin_step())
    opened = probe()
    for _ in range(4):              # every burst commits and is fetched
        submit_each(c, 12)
        c.finish(c.begin_burst())
    d = {k: v - opened[k] for k, v in probe().items()}
    assert d["dispatches"] == d["fetches"] == 4
    assert d["buffers"] / d["dispatches"] == (2.0 if mesh is None else 6.0)


# ---------------------------------------------------------------------------
# (e) the driver, three apps under the shim, the benchmark's reference
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("fault", [None, "group_replay_dropped"])
def test_mesh_driver_serves_three_apps_the_reference_admits(tmp_path, fault):
    """``ShardedClusterDriver(..., mesh=(1, 3))`` at the cell's
    rehearsal geometry: YCSB-A through each group's leader's app, then
    ``perfbench/reference/ycsb_register_cluster.py`` holds all three
    apps to what was acknowledged. With the benchmark's own
    ``group_replay_dropped`` (replica 1's app misses group 2's replayed
    writes) that pair alone is at fault."""
    from perfbench.deployments import interposed_app_cluster
    from tests import test_sharded_apps as apps

    with apps.served_cluster(tmp_path, mesh=MESH) as (driver, ports):
        assert driver.cluster.mesh.devices.shape == MESH
        assert driver.cluster.health()["engine"] == "spmd-group"
        if fault:
            dep = interposed_app_cluster.Deployment.__new__(
                interposed_app_cluster.Deployment)
            dep.driver, dep.G = driver, G
            dep.ctx = types.SimpleNamespace(say=lambda *a: None)
            dep.inject(fault)
        served = apps.serve_mix(driver, ports, "thread_per_group")
        rows = apps.apps_against_reference(driver, ports, served)
        per_group = [list(served["table"].values()).count(g)
                     for g in range(G)]
        for r, app in enumerate(rows):
            hit = fault and r == 1
            assert app["count"] == apps.RECORDS - (per_group[2] if hit
                                                   else 0)
            assert [bool(f) for f in app["faults"]] == [False, False,
                                                        bool(hit)]
        assert (rows[0]["records"] == rows[2]["records"])
        assert (rows[1]["records"] == rows[0]["records"]) == (not fault)
        assert driver.loop_error is None
        counters = driver.obs.metrics.snapshot()["counters"]
        assert counters["input_put_calls_total"] > 0
        assert counters["group_appends_total"] > 0
