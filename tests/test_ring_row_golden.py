"""The padded ring gives the parent's answers.

Since PR 50 a ring row is ``[payload | metadata | zero pad]``, its
width a multiple of 128 words. Nothing that can be observed may have
moved: a seeded schedule (elections, partitions and failovers, bursts,
joint-consensus changes, a stalled applier, pruning, rollovers,
snapshot installs) is driven through both engines and both mappings,
and every dispatch's ``res`` (the audit digests among it), the state's
LIVE columns and the replayed streams are hashed into a chain that
``tests/golden/ring_row_parent.json`` holds as the parent commit
(51bb263, 136-column rows) produced it. The pad columns must read zero
after every dispatch.

What leaves the device keeps the parent's format: an exported row's
``log_buf`` and a snapshot's digests have the parent's bytes (the same
file), and a ``log_buf`` of live columns installs into the padded ring
(``snapshot.genesis_row`` -> ``HostReplicaDriver.install_genesis``).

``python -m tests.test_ring_row_golden`` prints the chains of the tree
it runs on (how the golden file was made, on the parent); with ``keys``
the STEP_CACHE keys a short schedule leaves behind (``PARENT_KEYS``,
recorded on PR 52's parent).
"""

import hashlib
import json
import os
import random

import jax
import numpy as np
import pytest

from rdma_paxos_tpu.config import LogConfig
from rdma_paxos_tpu.consensus.log import META_W, EntryType
from rdma_paxos_tpu.consensus.membership import (
    MembershipManager, config_payload)
from rdma_paxos_tpu.consensus.snapshot import install_snapshot, take_snapshot
from rdma_paxos_tpu.consensus.state import ConfigState, Role
from rdma_paxos_tpu.runtime.sim import SimCluster
from rdma_paxos_tpu.shard.cluster import ShardedCluster

GOLDEN = os.path.join(os.path.dirname(__file__), "golden",
                      "ring_row_parent.json")
# a ring that prunes every 48 entries and rolls over every hundred or so
CFG = LogConfig(n_slots=64, slot_bytes=32, window_slots=16, batch_slots=8,
                rebase_threshold=120)
LIVE = CFG.slot_words + META_W
SEND = int(EntryType.SEND)
EVERY = 25          # dispatches between two recorded links of the chain


class Chain:
    """A running sha256 over everything a run can show."""

    def __init__(self, c):
        self.c, self.h, self.n, self.links = c, hashlib.sha256(), 0, []
        finish = c.finish

        def noted(ticket):
            res = finish(ticket)
            self.dispatch(res)
            return res
        c.finish = noted

    def feed(self, x):
        a = np.ascontiguousarray(np.asarray(x))
        self.h.update(str((a.dtype, a.shape)).encode())
        self.h.update(a.tobytes())

    def state(self):
        st = self.c.state
        buf = np.asarray(st.log.buf)
        assert not buf[..., LIVE:].any(), (
            f"dispatch {self.n}: the pad columns hold something")
        self.feed(buf[..., :LIVE])
        for leaf in jax.tree.leaves(
                {k: v for k, v in vars(st).items() if k != "log"}):
            self.feed(leaf)

    def dispatch(self, res):
        for k in sorted(res):
            self.h.update(k.encode())
            self.feed(res[k])
        self.state()
        self.n += 1
        if self.n % EVERY == 0:
            self.links.append(self.h.hexdigest())

    def close(self, streams):
        self.h.update(repr(streams).encode())
        self.links.append(self.h.hexdigest())
        return dict(dispatches=self.n, links=self.links)


def _payload(rng, tag):
    return (b"%s-%05d" % (tag, rng.randrange(10 ** 5))
            + b"x" * rng.randrange(0, CFG.slot_bytes - 12))


def _split(rng, R):
    ids = list(range(R))
    rng.shuffle(ids)
    cut = rng.randrange(1, R)
    return [ids[:cut], ids[cut:]]


def drive_single(mode, seed=50, steps=260):
    """One group of five slots, three members at first."""
    rng = random.Random(seed)
    R = 5
    c = SimCluster(CFG, R, group_size=3, mode=mode, audit=True)
    chain = Chain(c)
    mm = MembershipManager(c)
    c.run_until_elected(0)
    masks = [0b1111, 0b11111, 0b10111, 0b111]
    wedged = set()
    for i in range(steps):
        a = rng.random()
        if a < 0.08:
            c.partition(_split(rng, R))
        elif a < 0.22:
            c.heal()
        elif a < 0.26 and not wedged:
            wedged.add(rng.randrange(3))
            c.wedge_apply(next(iter(wedged)))
        elif a < 0.34 and wedged:
            c.unwedge_apply(wedged.pop())
        for r in range(R):
            if rng.random() < 0.5:
                for _ in range(rng.randrange(1, 7)):
                    c.submit(r, _payload(rng, b"s%d" % r))
        if rng.random() < 0.3:
            c.step_burst()
        else:
            c.step(timeouts=[r for r in range(R) if rng.random() < 0.03])
        if i % 45 == 44:
            # a joint-consensus change through whoever leads, healed
            c.heal()
            for w in list(wedged):
                c.unwedge_apply(w)
                wedged.discard(w)
            for _ in range(3):
                res = c.step()
            lead = [r for r in range(R)
                    if res["role"][r] == int(Role.LEADER)]
            if len(lead) != 1:
                res = c.step(timeouts=[0])
                res = c.step()
                lead = [r for r in range(R)
                        if res["role"][r] == int(Role.LEADER)]
            if len(lead) == 1:
                try:
                    mm.change(lead[0], masks[(i // 45) % len(masks)],
                              max_steps=12)
                except TimeoutError:
                    pass
        if i % 50 == 30:
            # a snapshot install into the row furthest behind
            c.heal()
            for _ in range(4):
                res = c.step()
            donor = int(np.argmax(res["commit"]))
            lag = int(np.argmin(res["end"]))
            snap = take_snapshot(c.state, donor=donor,
                                 index=int(c.applied[donor]), digests=True,
                                 rebased_total=c.rebased_total)
            chain.feed(snap.audit_digests)
            chain.h.update(repr((snap.index, snap.term, snap.audit_start,
                                 snap.epoch)).encode())
            c.state = install_snapshot(c.state, lag, snap)
            c.applied[lag] = snap.index
            c.replayed[lag] = list(c.replayed[donor])
            chain.state()
    c.heal()
    for w in wedged:
        c.unwedge_apply(w)
    for _ in range(8):
        c.step()
    assert c.rebases >= 1, "the schedule never rolled over"
    return chain.close([list(map(tuple, s)) for s in c.replayed])


def drive_groups(mesh, seed=51, steps=200):
    """Three groups of three (the sharded engine)."""
    rng = random.Random(seed)
    R = G = 3
    c = ShardedCluster(CFG, R, G, mesh=mesh, fanout="gather", audit=True)
    chain = Chain(c)
    c.place_leaders("round_robin")
    epoch = [0] * G
    for i in range(steps):
        a = rng.random()
        g = rng.randrange(G)
        if a < 0.08:
            c.partition(g, _split(rng, R))
        elif a < 0.24:
            c.heal(g)
        for g in range(G):
            for r in range(R):
                if rng.random() < 0.45:
                    c.submit_many(g, r, [
                        (SEND, 1 + r, 0, _payload(rng, b"g%d" % g))
                        for _ in range(rng.randrange(1, 6))])
        if i % 20 == 19:
            # config churn: a STABLE entry of a fresh epoch, the full
            # mask, through whoever the group takes for its leader
            g = (i // 20) % G
            lead = c.leader_hint(g)
            if lead >= 0:
                epoch[g] += 1
                c.submit(g, lead, config_payload(
                    0b111, 0b111, int(ConfigState.STABLE), epoch[g]),
                    EntryType.CONFIG)
        if rng.random() < 0.35:
            c.step_burst()
        else:
            tmo = {g: [r for r in range(R) if rng.random() < 0.02]
                   for g in range(G)}
            c.step(timeouts={g: t for g, t in tmo.items() if t})
        if i % 25 == 24:
            # whoever a partition left further behind than the window
            # holds is caught up by snapshot (nothing else can)
            c.heal()
            for _ in range(3):
                res = c.step()
            for g in range(G):
                donor = int(np.argmax(res["commit"][g]))
                lag = int(np.argmin(res["end"][g]))
                if res["end"][g, lag] >= res["head"][g, donor]:
                    continue
                snap = take_snapshot(
                    c.state, donor=donor, group=g,
                    index=int(c.applied[g, donor]), digests=True,
                    rebased_total=int(c.rebased_total[g]))
                chain.feed(snap.audit_digests)
                c.state = install_snapshot(c.state, lag, snap, group=g)
                c.applied[g, lag] = snap.index
                c.replayed[g][lag] = list(c.replayed[g][donor])
                c.need_recovery.discard((g, lag))
                chain.state()
    c.heal()
    for _ in range(8):
        c.step()
    assert c.rebases.min() >= 1, "a group never rolled over"
    return chain.close([[list(map(tuple, s)) for s in c.replayed[g]]
                        for g in range(G)])


def exported(seed=52):
    """A short run's exported rows and a digest-bearing snapshot, as
    sha256 of their bytes."""
    from rdma_paxos_tpu.consensus.snapshot import export_row
    rng = random.Random(seed)
    c = SimCluster(CFG, 3, audit=True)
    c.run_until_elected(0)
    for _ in range(30):         # the ring turns over once
        for _ in range(rng.randrange(1, 6)):
            c.submit(0, _payload(rng, b"e"))
        c.step()
    out = {}
    for r in range(3):
        row = export_row(c.state, r)
        assert row["log_buf"].shape == (CFG.n_slots, LIVE)
        h = hashlib.sha256()
        for k in sorted(row):
            a = np.ascontiguousarray(row[k])
            h.update(k.encode() + str((a.dtype, a.shape)).encode()
                     + a.tobytes())
        out["row%d" % r] = h.hexdigest()
    snap = take_snapshot(c.state, donor=0, index=int(c.applied[0]),
                         digests=True, rebased_total=c.rebased_total)
    out["snapshot"] = hashlib.sha256(
        repr((snap.index, snap.term, snap.epoch, snap.audit_start)).encode()
        + snap.audit_digests.tobytes()).hexdigest()
    return c, out


CASES = {
    "exported": lambda: exported()[1],
    "sim": lambda: drive_single("sim"),
    "spmd": lambda: drive_single("spmd"),
    "sim_g3": lambda: drive_groups(None),
    "spmd_g3": lambda: drive_groups((1, 3)),
}


def test_exported_rows_and_snapshot_digests_have_the_parents_bytes():
    with open(GOLDEN) as f:
        want = json.load(f)["exported"]
    assert exported()[1] == want


def test_a_log_buf_in_the_parents_format_installs_into_the_padded_ring():
    """The elastic rebuild's transfer unit end to end: a donor's row
    (live columns, the parent's 136-column format at the cells' slots,
    24 here) made a genesis row, installed on every replica of a host
    driver's world, exported again: the same bytes; on the device the
    pad is back and zero, and the world elects and commits."""
    if len(jax.devices()) < 3:
        pytest.skip("needs 3 (virtual) devices")
    from rdma_paxos_tpu.consensus.log import row_words
    from rdma_paxos_tpu.consensus.snapshot import export_row, genesis_row
    from rdma_paxos_tpu.runtime.host import HostReplicaDriver
    c, _ = exported()
    donor = export_row(c.state, 0)
    g = genesis_row(donor, group_mask=0b111, epoch=1, n_replicas=3)
    assert g["log_buf"].shape == (CFG.n_slots, LIVE)
    hd = HostReplicaDriver(CFG, process_id=0, num_processes=3,
                           coordinator="", initialize_distributed=False)
    hd.install_genesis(g)
    buf = np.asarray(hd.state.log.buf)
    assert buf.shape == (3, CFG.n_slots, row_words(CFG.slot_words))
    assert not buf[..., LIVE:].any()
    for r in range(3):
        np.testing.assert_array_equal(buf[r, :, :LIVE], g["log_buf"])
    back = hd.export_local_row()
    assert back["log_buf"].tobytes() == g["log_buf"].tobytes()
    assert sorted(back) == sorted(g)
    end = int(back["end"])
    res = hd.step(timeout_fired=True)
    assert int(res["role"]) == int(Role.LEADER)
    assert int(res["end"]) == end + 1          # the new leader's NOOP
    wd, wm = hd.fetch_local_window(end)
    assert wd.shape == (CFG.window_slots, CFG.slot_words)
    assert wm.shape == (CFG.window_slots, META_W)
    assert not np.asarray(hd.state.log.buf)[..., LIVE:].any()


@pytest.mark.parametrize("case", [k for k in CASES if k != "exported"])
def test_live_columns_digests_and_outputs_are_the_parents(case):
    if len(jax.devices()) < 5:
        pytest.skip("needs 5 (virtual) devices")
    with open(GOLDEN) as f:
        want = json.load(f)[case]
    got = CASES[case]()
    assert got["dispatches"] == want["dispatches"] > 150
    for at, (a, b) in enumerate(zip(got["links"], want["links"])):
        assert a == b, (
            f"{case}: first differs from the parent's run within "
            f"dispatches {at * EVERY}..{(at + 1) * EVERY}")
    assert len(got["links"]) == len(want["links"])


# ---------------------------------------------------------------------------
# the STEP_CACHE keys are the parent's
# ---------------------------------------------------------------------------

# the txn lane's records want 128-byte slots
KEY_CFG = LogConfig(n_slots=64, slot_bytes=128, window_slots=16,
                    batch_slots=8)
_SIM = (3, "sim", False, False, "gather")
_SPMD = (3, "spmd", False, False, "gather")
_G = (3, "sim", None, False, False, "gather")
_MESH = (3, "spmd-group", ((1, 3), (0, 1, 2)), False, False, "gather")


def _keys(head, *tails):
    return {(KEY_CFG,) + head + tail for tail in tails}


# what the parent commit (2b82374) left in STEP_CACHE after
# ``served_keys``, by ``python -m tests.test_ring_row_golden keys``
# there: (engine, mapping, option) -> keys
PARENT_KEYS = {
    ("single", "sim", None): _keys(
        _SIM, (True,), (False,), ("burst", 2), ("burst", 4)),
    ("single", "sim", "audit"): _keys(
        _SIM, (True, "audit"), (False, "audit"), ("burst", 2, "audit"),
        ("burst", 4, "audit")),
    ("single", "sim", "scan"): _keys(
        _SIM, (True,), (False,), ("burst", 2), ("scan", 2, 16),
        ("scan", 4, 32)),
    ("single", "sim", "txn"): _keys(
        _SIM, (True, "txn"), (False, "txn"), ("burst", 2), ("burst", 4)),
    ("single", "spmd", None): _keys(
        _SPMD, (True,), (False,), ("burst", 2), ("burst", 4)) | {
            (KEY_CFG, 3, "mesh")},
    ("groups", "sim", None): _keys(
        _G, ("group", True), ("group", False), ("group-burst", 2),
        ("group-burst", 4)),
    ("groups", "sim", "audit"): _keys(
        _G, ("group", True, "audit"), ("group", False, "audit"),
        ("group-burst", 2, "audit"), ("group-burst", 4, "audit")),
    ("groups", "sim", "scan"): _keys(
        _G, ("group", True), ("group", False), ("group-burst", 2),
        ("group-scan", 2, 16), ("group-scan", 4, 32)),
    ("groups", "sim", "txn"): _keys(
        _G, ("group", True, "txn"), ("group", False, "txn"),
        ("group-burst", 2), ("group-burst", 4)),
    ("groups", "spmd", None): _keys(
        _MESH, ("group", True), ("group", False), ("group-burst", 2),
        ("group-burst", 4)),
}


def served_keys(engine, mapping, option, seed=53):
    """STEP_CACHE's keys after a prewarm of one tier and a seeded
    schedule of steps (with and without an election) and bursts, one
    of them past the prewarmed tier."""
    from rdma_paxos_tpu.runtime.sim import STEP_CACHE
    rng = random.Random(seed)
    opts = {option: True} if option else {}
    STEP_CACHE.clear()
    if engine == "single":
        c = SimCluster(KEY_CFG, 3, mode=mapping, **opts)
        c.prewarm(tiers=(2,))
        c.run_until_elected(0)
        submit = lambda n: c.submit_many(0, [
            (SEND, 1, 0, _payload(rng, b"k")) for _ in range(n)])
    else:
        c = ShardedCluster(KEY_CFG, 3, 3, fanout="gather",
                           mesh=(1, 3) if mapping == "spmd" else None,
                           **opts)
        c.prewarm(tiers=(2,))
        c.place_leaders("round_robin")
        submit = lambda n: [c.submit_many(g, g, [
            (SEND, 1, 0, _payload(rng, b"k")) for _ in range(n)])
            for g in range(3)]
    for i in range(6):
        submit(rng.randrange(1, 2 * KEY_CFG.batch_slots))
        c.step_burst() if i % 2 else c.step()
    submit(3 * KEY_CFG.batch_slots)         # a burst of the next tier
    c.step_burst()
    c.step()
    return set(STEP_CACHE)


@pytest.mark.parametrize("engine,mapping,option", sorted(
    PARENT_KEYS, key=lambda case: tuple(map(str, case))))
def test_step_cache_keys_are_the_parents(engine, mapping, option):
    """The ONE place that forms a STEP_CACHE key forms the parent's,
    literally (``cfg`` by value), under every option that marks it."""
    if mapping == "spmd" and len(jax.devices()) < 3:
        pytest.skip("needs 3 (virtual) devices")
    got = served_keys(engine, mapping, option)
    assert got == PARENT_KEYS[engine, mapping, option], (
        got ^ PARENT_KEYS[engine, mapping, option])


if __name__ == "__main__":
    import sys
    if sys.argv[1:] == ["keys"]:
        for case in PARENT_KEYS:
            print(case, sorted(
                (k[1:] for k in served_keys(*case)), key=str))
    else:
        print(json.dumps({k: run() for k, run in CASES.items()},
                         indent=1))
