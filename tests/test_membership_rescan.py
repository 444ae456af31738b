"""The full-ring config rescan, behind its group-wide conditional.

The rescan branch of ``replica_step`` runs on a step in which ANY
replica's cached config source was invalidated, and inside it each
replica keeps ``where(its own flag, rescanned, kept)``. These cases
drive it with exactly ONE replica invalid while the others keep their
cache, under ``vmap`` and under ``shard_map`` on CPU devices, and hold
every step's state and the packed row's ``cfg_rescanned`` column to a
plain NumPy rendering of the rule: newest CONFIG in ``[head, end)``,
else the committed checkpoint.

The group engine batches groups into the same program and reduces the
branch's predicate over them too (``consensus.step.vmap_groups``), so
the same scenarios run in ONE group of three while the other two serve
steady traffic: every group must leave every step as the same group run
alone through ``SimCluster`` does, leaf for leaf, and the step is
counted once."""

import functools
import types

import jax
import numpy as np
import pytest

from rdma_paxos_tpu.config import LogConfig
from rdma_paxos_tpu.consensus.log import (
    EntryType, M_GIDX, M_TERM, M_TYPE)
from rdma_paxos_tpu.consensus.membership import MembershipManager
from rdma_paxos_tpu.consensus.state import ConfigState, Role
from rdma_paxos_tpu.consensus.step import SCAN_KEYS
from rdma_paxos_tpu.obs.metrics import MetricsRegistry
from rdma_paxos_tpu.obs.spans import StepPhaseProfiler
from rdma_paxos_tpu.runtime.sim import SimCluster, StepTicket, read_scalars
from rdma_paxos_tpu.shard.cluster import ShardedCluster

CFG = LogConfig(n_slots=64, slot_bytes=32, window_slots=16, batch_slots=8)
SW = CFG.slot_words
VIEW = ("cfg_src", "cfg_src_term", "bitmask_old", "bitmask_new",
        "cid_state", "epoch", "ccfg_old", "ccfg_new", "ccfg_cid",
        "ccfg_epoch", "head", "end")


def _host(st):
    """A device state's config cache, offsets and ring, on the host."""
    snap = {k: np.asarray(getattr(st, k)).astype(np.int64) for k in VIEW}
    snap["buf"] = np.asarray(st.log.buf)
    return snap


def _source_gone(pre, post, r):
    """Replica ``r``'s cached source entry is no longer in its log:
    truncated away, or another entry stands at its index."""
    src = pre["cfg_src"][r]
    if src < 0:
        return False
    if src >= post["end"][r]:
        return True
    meta = post["buf"][r, src % CFG.n_slots, SW:]
    return not (meta[M_GIDX] == src
                and meta[M_TYPE] == int(EntryType.CONFIG)
                and meta[M_TERM] == pre["cfg_src_term"][r])


def _derived(pre, post, r):
    """Newest CONFIG in ``[head, end)`` of replica ``r``'s log, else the
    committed checkpoint: ``(src, term, old, new, cid, epoch)``."""
    buf = post["buf"][r]
    gidx = buf[:, SW + M_GIDX]
    live = ((buf[:, SW + M_TYPE] == int(EntryType.CONFIG))
            & (gidx >= post["head"][r]) & (gidx < post["end"][r]))
    if not live.any():
        return (-1, 0, pre["ccfg_old"][r], pre["ccfg_new"][r],
                pre["ccfg_cid"][r], pre["ccfg_epoch"][r])
    row = buf[np.flatnonzero(live)[np.argmax(gidx[live])]]
    u32 = lambda w: int(np.uint32(w))
    return (row[SW + M_GIDX], row[SW + M_TERM], u32(row[0]), u32(row[1]),
            row[2], row[3])


def _held_to_rule(pre, post, R, at):
    """One group's step held to the NumPy rule; returns which replicas'
    cached source went in it."""
    gone = [_source_gone(pre, post, r) for r in range(R)]
    for r in range(R):
        # a replica that holds no CONFIG and never had one keeps its
        # genesis view: only the rule's first clause has a say there
        want = _derived(pre, post, r)
        if want[0] < 0 and not gone[r]:
            continue
        got = tuple(post[k][r] for k in VIEW[:6])
        assert got == tuple(int(x) for x in want), (at, r, got, want)
    return gone


class Checked:
    """``c.step`` with every step held to the NumPy rule."""

    def __init__(self, c):
        self.c, self.step, self.steps = c, c.step, []
        c.step = self

    def __call__(self, *a, **kw):
        c = self.c
        pre = _host(c.state)
        res = self.step(*a, **kw)
        gone = _held_to_rule(pre, _host(c.state), c.R, len(self.steps))
        # the row's column: the branch ran iff some replica's source
        # went, and says so on every replica alike
        flag = res["cfg_rescanned"]
        assert flag.tolist() == [int(any(gone))] * c.R, (
            len(self.steps), flag, gone)
        self.steps.append(gone)
        return res


class OneGroupOfThree:
    """Group :attr:`G0` of a three-group ``ShardedCluster`` behind
    ``SimCluster``'s surface, as far as the scenarios use it. Every
    ``step`` steps the whole engine once, with one entry submitted to
    the leader of each other group, and each group's twin, a
    ``SimCluster`` given the same events, once; then holds every group
    of the engine to its twin and to the NumPy rule."""

    G, G0 = 3, 1

    def __init__(self, R, **kw):
        self.R = R
        self.sh = ShardedCluster(CFG, R, self.G, **kw)
        self.alone = [SimCluster(CFG, R, **kw) for _ in range(self.G)]
        self.metrics = MetricsRegistry()
        self.sh.profiler = StepPhaseProfiler(metrics=self.metrics)
        self.ran = 0        # engine steps whose rescan branch ran
        self.n = 0
        for g in self.others:
            self.step(_group=g, timeouts=[g])
            assert self.sh.leader(g) == g

    @property
    def others(self):
        return [g for g in range(self.G) if g != self.G0]

    @property
    def state(self):
        return jax.tree.map(lambda x: x[self.G0], self.sh.state)

    def submit(self, r, payload, etype=EntryType.SEND):
        self.sh.submit(self.G0, r, payload, etype)
        self.alone[self.G0].submit(r, payload, etype)

    def partition(self, groups):
        self.sh.partition(self.G0, groups)
        self.alone[self.G0].partition(groups)

    def heal(self):
        self.sh.heal(self.G0)
        self.alone[self.G0].heal()

    def run_until_elected(self, r):
        res = self.step(timeouts=[r])
        assert res["role"][r] == int(Role.LEADER)

    def step(self, timeouts=(), _group=None):
        sh, at = self.sh, self.n
        tg = self.G0 if _group is None else _group
        for g in self.others:
            if sh.last is not None and sh.leader_hint(g) >= 0:
                sh.submit(g, sh.leader_hint(g), b"steady%d-%d" % (g, at))
                self.alone[g].submit(sh.leader_hint(g),
                                     b"steady%d-%d" % (g, at))
        pre = [_host(jax.tree.map(lambda x: x[g], sh.state))
               for g in range(self.G)]
        res = sh.step(timeouts={tg: list(timeouts)} if timeouts else ())
        twins = [c.step(timeouts=timeouts if g == tg else ())
                 for g, c in enumerate(self.alone)]
        ran = []
        for g, (c, twin) in enumerate(zip(self.alone, twins)):
            mine = jax.tree.map(lambda x: x[g], sh.state)
            for a, b in zip(jax.tree.leaves(mine),
                            jax.tree.leaves(c.state)):
                assert np.array_equal(np.asarray(a), np.asarray(b)), (
                    at, g, "differs from the group run alone")
            for k in twin.keys() - {"cfg_rescanned"}:
                assert np.array_equal(twin[k], res[k][g]), (at, g, k)
            gone = _held_to_rule(pre[g], _host(mine), self.R, at)
            assert twin["cfg_rescanned"].tolist() == (
                [int(any(gone))] * self.R)
            ran.append(any(gone))
        # only G0 ever rescans; the engine's column says that the
        # branch ran in EVERY group, and the counter counts one step
        assert not any(ran[g] for g in self.others), (at, ran)
        assert res["cfg_rescanned"].tolist() == (
            [[int(any(ran))] * self.R] * self.G), (at, ran)
        self.ran += any(ran)
        assert self.metrics.get("cfg_rescans_total") == self.ran
        self.n += 1
        return {k: v[self.G0] for k, v in res.items()}


def _backoff(make):
    """Replica 0's uncommitted CONFIG is truncated by divergence
    backoff; 1, 2 and 3 keep the committed CONFIG they cached."""
    c = make(5, group_size=3)
    mm = MembershipManager(c)
    c.run_until_elected(0)
    mm.change(0, 0b1111)            # a CONFIG every member caches
    ck = Checked(c)
    base = mm.current(0)
    assert (np.asarray(c.state.cfg_src)[:4] >= 0).all()
    c.partition([[0], [1, 2, 3], [4]])
    mm.submit_transit(0, 0b1111, 0b11111, epoch=base["epoch"] + 1)
    c.step()                        # adopted at append, never replicated
    assert mm.current(0)["cid_state"] == int(ConfigState.TRANSIT)
    res = c.step(timeouts=[1])
    assert res["role"][1] == int(Role.LEADER)
    c.submit(1, b"overwrite")
    c.step()
    c.heal()
    for _ in range(4):
        c.step()
    assert mm.current(0) == base, "the truncated CONFIG still governs"
    return ck


def _overwritten(make):
    """Replica 0's CONFIG is overwritten INSIDE an absorbed window by
    the new leader's CONFIG of a newer term at the same index (a
    laggard floors the window below it, so nothing backs off first)."""
    c = make(5)
    mm = MembershipManager(c)
    c.run_until_elected(0)
    c.step()
    ck = Checked(c)
    c.partition([[0], [1, 2, 3], [4]])
    c.submit(0, b"alone")
    mm.submit_stable(0, 0b11111, epoch=1)
    c.step()
    assert mm.current(0)["epoch"] == 1
    res = c.step(timeouts=[1])      # NOOP where 0 holds b"alone"
    assert res["role"][1] == int(Role.LEADER)
    mm.submit_stable(1, 0b11111, epoch=2)   # same index, newer term
    c.step()
    c.step()
    src = np.asarray(c.state.cfg_src)
    assert src[0] == src[1] == src[2] == src[3] >= 0
    c.heal()
    for _ in range(4):
        c.step()
    assert [mm.current(r)["epoch"] for r in range(5)] == [2] * 5
    return ck


@pytest.mark.parametrize("mode", ["sim", "spmd"])
@pytest.mark.parametrize("scenario", [_backoff, _overwritten])
def test_rescan_with_one_replica_invalid(scenario, mode):
    ck = scenario(functools.partial(SimCluster, CFG, mode=mode))
    ran = [gone for gone in ck.steps if any(gone)]
    assert ran, "the scenario never invalidated a config source"
    # exactly replica 0, every time: the others kept their cache through
    # a step in which the branch ran
    assert all(gone == [True] + [False] * 4 for gone in ran), ran


@pytest.mark.parametrize("scenario", [_backoff, _overwritten])
def test_rescan_in_one_group_of_three(scenario):
    """One replica of ONE group invalid: the branch runs for the whole
    program, is counted once, and the other two groups come out of
    that step as they do alone (``OneGroupOfThree.step`` holds every
    step to that)."""
    ck = scenario(OneGroupOfThree)
    ran = [gone for gone in ck.steps if any(gone)]
    assert ran and all(gone == [True] + [False] * 4 for gone in ran), ran
    assert ck.c.metrics.get("cfg_rescans_total") == len(ran)
    for g in ck.c.others:           # they served traffic throughout
        assert ck.c.sh.last["commit"][g].max() >= ck.c.n - 4


@pytest.mark.parametrize("fused", [False, True])
def test_flag_is_zero_over_steady_traffic_in_every_group(fused):
    G, R = 3, 3
    sh = ShardedCluster(CFG, R, G)
    sh.profiler = StepPhaseProfiler(metrics=MetricsRegistry())
    sh.place_leaders()
    for i in range(3):
        for g in range(G):
            for j in range(12):
                sh.submit(g, sh.leader(g), b"r%d-%03d" % (i, j))
        ticket = sh.begin_burst() if fused else sh.begin_step()
        assert (ticket.kind, ticket.K > 1) == (
            ("burst", True) if fused else ("step", False))
        for res in (sh.finish(ticket), sh.step()):
            assert res["cfg_rescanned"].tolist() == [[0] * R] * G
    assert sh.last["commit"].min() > 12
    assert sh.profiler.metrics.get("cfg_rescans_total") == 0


@pytest.mark.parametrize("fused", [False, True])
def test_flag_is_zero_over_steady_traffic(fused):
    from tests.readback_ref import drive
    c = SimCluster(CFG, 3)
    seen = drive(c, 12, fused=fused)
    assert {k for k, _K, _res, _ref in seen} == (
        {"step", "burst"} if fused else {"step"})
    for _kind, _K, res, _ref in seen:
        assert res["cfg_rescanned"].tolist() == [0, 0, 0]
    assert SCAN_KEYS[-1] == "cfg_rescanned"     # appended: nothing moved


def test_fused_dispatch_counts_every_step_that_rescanned():
    """A fused dispatch reads the final step's row; the flag alone is
    summed over its steps, so a rescan in an earlier one is counted."""
    K, R, col = 4, 3, SCAN_KEYS.index("cfg_rescanned")
    rows = np.zeros((K, R, len(SCAN_KEYS) + R), np.int32)
    rows[:, :, SCAN_KEYS.index("term")] = np.arange(K)[:, None]
    rows[0, :, col] = rows[2, :, col] = 1
    ticket = StepTicket("burst", types.SimpleNamespace(scal=rows),
                        None, None, K, None)
    res = read_scalars(ticket)
    assert res["cfg_rescanned"].tolist() == [2, 2, 2]
    assert res["term"].tolist() == [K - 1] * R
