"""The replay fetch brings back the rows that were committed.

``runtime.sim.ReplayFetch`` is the standalone fetch of both engines:
``fetch_window`` over every ring row, compiled at a few static widths of
the replay window and called at the smallest that holds the need (the
rows the furthest-behind replica lacks). Whatever the width, the same
entries reach the decode in the same order: checked here against a
cluster whose fetch knows the widest width alone, on the same inputs,
with the needed rows at the ring's start and across its wrap; and that
the width is the smallest that holds the need and is counted, that a
prewarmed cluster compiles nothing at any width, and that a slot
recycled under a laggard is still caught by the first fetched row.
"""

import logging

import jax
import numpy as np
import pytest

from rdma_paxos_tpu.config import LogConfig
from rdma_paxos_tpu.obs.metrics import MetricsRegistry
from rdma_paxos_tpu.obs.spans import StepPhaseProfiler
from rdma_paxos_tpu.runtime.sim import ReplayFetch, SimCluster
from rdma_paxos_tpu.shard.cluster import ShardedCluster

# the replay window is 256 rows (a quarter of the ring): 16, 64, 256
CFG = LogConfig(n_slots=1024, slot_bytes=32, window_slots=16,
                batch_slots=16)
WIDTHS = (16, 64, 256)
# 1, exactly a width, a width + 1, more than the widest
NEEDS = (1, 16, 17, 64, 65, 256, 257, 280)
# the apply cursor when the needed rows are committed: the ring's first
# turn, or three slots before its wrap (within any width of n_slots)
STARTS = (40, CFG.n_slots - 3)


class Single:
    """A led SimCluster behind the few calls the cases need."""

    def __init__(self):
        self.c = c = SimCluster(CFG, 3)
        c.profiler = StepPhaseProfiler(MetricsRegistry())
        c.run_until_elected(0)
        c.step()
        self.members = [0, 1, 2]
        self.seq = 0

    def submit(self, n):
        for _ in range(n):
            self.c.submit(0, b"e%06d" % self.seq)
            self.seq += 1

    def queued(self):
        return len(self.c.pending[0])

    def wedge(self, r):
        self.c.wedge_apply(r)

    def unwedge(self, r):
        self.c.unwedge_apply(r)

    def lag(self):
        return (self.c.last["commit"] - self.c.applied).astype(int)

    def streams(self):
        return [list(s) for s in self.c.replayed]

    def lost(self, r):
        return r in self.c.need_recovery


class Sharded(Single):
    """Two groups on three replicas, group g led by replica g; group 0
    carries the case's entries and group 1 a third as many."""

    def __init__(self):
        self.c = c = ShardedCluster(CFG, 3, 2)
        c.profiler = StepPhaseProfiler(MetricsRegistry())
        assert c.place_leaders("round_robin") == [0, 1]
        c.step()
        self.members = [0, 1, 2]
        self.seq = 0

    def submit(self, n):
        for i in range(n):
            self.c.submit(0, 0, b"e%06d" % self.seq)
            if i % 3 == 0:
                self.c.submit(1, 1, b"o%06d" % self.seq)
            self.seq += 1

    def queued(self):
        return len(self.c.pending[0][0]) + len(self.c.pending[1][1])

    def wedge(self, r):
        for g in range(2):
            self.c.wedge_apply(g, r)

    def unwedge(self, r):
        for g in range(2):
            self.c.unwedge_apply(g, r)

    def lag(self):
        # group 0's: the case's need (group 1 lacks fewer rows)
        return (self.c.last["commit"] - self.c.applied).astype(int)[0]

    def streams(self):
        return [list(s) for row in self.c.replayed for s in row]

    def lost(self, r):
        return (0, r) in self.c.need_recovery


ENGINES = dict(single=Single, sharded=Sharded)


def rows_counted(eng):
    return eng.c.profiler.metrics.get("fetch_rows_total")


def settle(eng):
    """Step until what was submitted is appended, then twice more: the
    followers learn a commit a step after the leader."""
    for _ in range(200):
        if not eng.queued():
            break
        eng.c.step()
    assert not eng.queued()
    eng.c.step()
    eng.c.step()


def watch_widths(eng):
    """-> the list the fetch's widths are appended to, read off what it
    hands back through a ``(log, starts)`` wrapper over the hook."""
    seen, fetch = [], eng.c._fetch_all

    def watched(log, starts):
        wd, wm = fetch(log, starts)
        seen.append(np.asarray(wm).shape[-2])
        return wd, wm
    eng.c._fetch_all = watched
    return seen


def lag_by(eng, start, need):
    """Every replica applied ``start`` entries, then ``need`` more
    were committed under a frozen apply: the next fetch needs ``need``
    rows from ``start``."""
    eng.submit(start - applied_of(eng))
    settle(eng)
    assert applied_of(eng) == start and not eng.lag().any()
    for r in eng.members:
        eng.wedge(r)
    eng.submit(need)
    settle(eng)
    assert list(eng.lag()) == [need] * 3
    for r in eng.members:
        eng.unwedge(r)


def applied_of(eng):
    applied = np.asarray(eng.c.applied).reshape(-1, 3)[0]
    assert len(set(applied.tolist())) == 1
    return int(applied[0])


def expected_rows(need):
    """Rows a replica the fetches bring back for ``need``: gulps of the
    widest, then the smallest width that holds the rest."""
    rows = []
    while need > 0:
        rows.append(next((W for W in WIDTHS if W >= need), WIDTHS[-1]))
        need -= rows[-1]
    return rows


def test_widths_are_fractions_of_the_replay_window():
    assert ReplayFetch(4096, 1).widths == (256, 1024, 4096)
    assert ReplayFetch(256, 2).widths == WIDTHS
    # a toy ring's window: no width of 0, none twice
    assert ReplayFetch(8, 1).widths == (1, 2, 8)
    assert ReplayFetch(2, 1).widths == (1, 2)
    rf = ReplayFetch(256, 1)
    assert [rf.width_for(n) for n in (0, 1, 16, 17, 64, 65, 256, 9999)] \
        == [16, 16, 16, 64, 64, 256, 256, 256]
    for engine in ENGINES.values():
        assert engine().c._replay_fetch.widths == WIDTHS


@pytest.mark.parametrize("engine", sorted(ENGINES))
def test_the_rows_come_to_the_host_as_one_array(engine):
    """The fetch hands back payload words and metadata as two column
    ranges of ONE device array (a read of the device costs the same
    half millisecond for 24 KB as for 400 KB: PERF.md, PR 48), each of
    which converts with ``np.asarray`` alone, as the benchmark's
    wrapper over the hook needs; they are ``extract_window``'s rows."""
    from rdma_paxos_tpu.consensus.log import (
        M_GIDX, META_W, extract_window)
    eng = ENGINES[engine]()
    eng.submit(40)
    settle(eng)
    c = eng.c
    lead = np.asarray(c.applied).shape
    starts = jax.numpy.asarray(np.full(lead, 7, np.int32))
    c._replay_fetch.need = 20
    wd, wm = c._fetch_all(c.state.log, starts)
    assert wd.rows is wm.rows and wd.rows.shape == lead + (
        64, CFG.slot_words + META_W)
    gather = lambda log, s: extract_window(log, s, 64)     # noqa: E731
    for _ in lead:
        gather = jax.vmap(gather)
    ref_d, ref_m = gather(c.state.log, starts)
    assert np.asarray(wd).shape == lead + (64, CFG.slot_words)
    np.testing.assert_array_equal(np.asarray(wd), ref_d)
    np.testing.assert_array_equal(np.asarray(wm), ref_m)
    assert int(np.asarray(wm).reshape(-1, 64, META_W)[0, 0, M_GIDX]) == 7


@pytest.mark.parametrize("start", STARTS, ids=["first_turn", "wrap"])
@pytest.mark.parametrize("need", NEEDS)
@pytest.mark.parametrize("engine", sorted(ENGINES))
def test_any_width_replays_what_the_widest_alone_replays(engine, need,
                                                         start):
    eng, ref = ENGINES[engine](), ENGINES[engine]()
    # the reference knows one width, as the fetch did before
    ref.c._replay_fetch.widths = WIDTHS[-1:]
    seen, ref_seen = watch_widths(eng), watch_widths(ref)
    for e in (eng, ref):
        lag_by(e, start, need)
    del seen[:], ref_seen[:]
    rows0 = rows_counted(eng)
    for e in (eng, ref):
        e.c.step()
        assert not e.lag().any()
    # the smallest width that holds the need, counted once a fetch
    assert seen == expected_rows(need)
    assert rows_counted(eng) - rows0 == sum(seen)
    assert set(ref_seen) == {WIDTHS[-1]}
    # the same entries in the same order, bit for bit
    streams = eng.streams()
    assert streams == ref.streams()
    sent = [b"e%06d" % i for i in range(eng.seq)]
    for stream in streams[:3]:
        assert [p for (_, _, _, p) in stream
                if p.startswith(b"e")] == sent


@pytest.mark.parametrize("engine", sorted(ENGINES))
def test_a_prewarmed_cluster_compiles_no_fetch_at_any_width(engine,
                                                            caplog):
    eng = ENGINES[engine]()
    eng.c.prewarm(tiers=())
    programs = eng.c._replay_fetch.programs
    assert sorted(programs) == list(WIDTHS)
    assert [fn._cache_size() for fn in programs.values()] == [1, 1, 1]
    seen = watch_widths(eng)
    with jax.log_compiles(True), caplog.at_level(logging.WARNING):
        for need in (3, 40, 200):
            lag_by(eng, applied_of(eng), need)
            eng.c.step()
    assert set(seen) == set(WIDTHS)
    assert [fn._cache_size() for fn in programs.values()] == [1, 1, 1]
    assert not [r.getMessage() for r in caplog.records
                if "ompiling" in r.getMessage()]


@pytest.mark.parametrize("width", WIDTHS)
@pytest.mark.parametrize("engine", sorted(ENGINES))
def test_a_recycled_slot_is_caught_at_every_width(engine, width):
    """A follower's apply stands still while the ring turns over under
    it (forced pruning lets the appends pass): the first row of its
    next fetch carries another entry's index, whatever the width, and
    the replica is handed to recovery with nothing replayed."""
    eng = ENGINES[engine]()
    eng.c._replay_fetch.widths = (width,)
    eng.submit(30)
    settle(eng)
    eng.wedge(2)
    held = len(eng.streams()[2])
    eng.submit(CFG.n_slots + 200)
    settle(eng)
    assert not eng.lost(2)
    seen = watch_widths(eng)
    eng.unwedge(2)
    eng.c.step()
    assert eng.lost(2) and seen and set(seen) == {width}
    assert len(eng.streams()[2]) == held
    sent = [b"e%06d" % i for i in range(eng.seq)]
    for r in (0, 1):
        assert [p for (_, _, _, p) in eng.streams()[r]
                if p.startswith(b"e")] == sent
