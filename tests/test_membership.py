"""Live membership change via joint consensus — the reference's
EXTENDED→TRANSIT→STABLE config machine (§3.5) driven through CONFIG log
entries, with dual-quorum enforcement while transitional."""

import numpy as np
import pytest

from rdma_paxos_tpu.config import LogConfig
from rdma_paxos_tpu.consensus.membership import MembershipManager
from rdma_paxos_tpu.consensus.state import ConfigState, Role
from rdma_paxos_tpu.runtime.sim import SimCluster

CFG = LogConfig(n_slots=64, slot_bytes=32, window_slots=16, batch_slots=8)


def test_upsize_3_to_5():
    c = SimCluster(CFG, 8, group_size=3)
    mm = MembershipManager(c)
    c.run_until_elected(0)
    c.submit(0, b"before")
    c.step()

    mm.change(0, 0b11111)       # add replicas 3 and 4
    cur = mm.current(0)
    assert cur["cid_state"] == int(ConfigState.STABLE)
    assert cur["bitmask_new"] == 0b11111

    # every member (incl. the new ones) converged on the config
    for r in range(5):
        assert mm.current(r)["bitmask_new"] == 0b11111

    # new quorum is 3-of-5: two failures tolerated...
    c.partition([[0, 1, 2], [3], [4]])
    c.submit(0, b"with-2-down")
    res = c.step()
    assert res["commit"][0] == res["end"][0]
    # ...three failures not
    c.partition([[0, 1], [2], [3], [4]])
    c.submit(0, b"with-3-down")
    res = c.step()
    assert res["commit"][0] < res["end"][0]
    c.heal()


def test_downsize_5_to_3():
    c = SimCluster(CFG, 8, group_size=5)
    mm = MembershipManager(c)
    c.run_until_elected(0)
    mm.change(0, 0b00111)
    assert mm.current(0)["bitmask_new"] == 0b111
    # removed replicas no longer count toward quorum: 2-of-3 commits even
    # with 3 and 4 gone
    c.partition([[0, 1, 2], [3], [4]])
    c.submit(0, b"small-group")
    res = c.step()
    assert res["commit"][0] == res["end"][0]


def test_transit_requires_both_majorities_for_commit():
    """While TRANSIT is in the log (before STABLE), commits need majorities
    of BOTH configs — losing the old majority blocks commit even though
    the new majority is intact (dare_ibv_rc.c:2799-2957 semantics)."""
    c = SimCluster(CFG, 8, group_size=3)
    mm = MembershipManager(c)
    c.run_until_elected(0)
    c.step()
    # enter joint consensus 0b111 -> 0b11111 but do NOT finalize
    mm.submit_transit(0, 0b111, 0b11111, epoch=1)
    res = c.step()
    assert mm.current(0)["cid_state"] == int(ConfigState.TRANSIT)
    committed_to = int(res["commit"][0])
    # old majority {0,1,2} broken (1,2 gone); new majority {0,3,4} intact
    c.partition([[0, 3, 4], [1], [2]])
    c.submit(0, b"blocked")
    res = c.step()
    res = c.step()
    assert int(res["commit"][0]) <= committed_to + 0, (
        "commit advanced without the old-config majority")
    # heal -> both quorums available -> commits flow again
    c.heal()
    res = c.step()
    res = c.step()
    assert int(res["commit"][0]) == int(res["end"][0])


def test_eviction_of_failed_member():
    """Failure-driven downsize (check_failure_count analog,
    dare_server.c:1189-1227): a permanently dead member is removed so the
    effective quorum shrinks."""
    c = SimCluster(CFG, 8, group_size=5)
    mm = MembershipManager(c)
    c.run_until_elected(0)
    c.step()
    # replicas 3 and 4 die; 3-of-5 quorum still holds, but the operator
    # (or failure detector) evicts them
    c.partition([[0, 1, 2], [3], [4]])
    mm.change(0, 0b00111)
    assert mm.current(0)["bitmask_new"] == 0b111
    # now a single further failure is tolerated (2-of-3)
    c.partition([[0, 1], [2], [3], [4]])
    c.submit(0, b"after-evict")
    res = c.step()
    assert res["commit"][0] == res["end"][0]


def test_election_under_new_config_after_upsize():
    c = SimCluster(CFG, 8, group_size=3)
    mm = MembershipManager(c)
    c.run_until_elected(0)
    mm.change(0, 0b11111)
    # old leader dies; a NEW member wins an election under the new config
    c.partition([[0], [1, 2, 3, 4]])
    res = c.step(timeouts=[3])
    assert res["role"][3] == int(Role.LEADER)
    c.submit(3, b"new-member-leads")
    res = c.step()
    assert res["commit"][3] == res["end"][3]


def test_extended_joiner_replicates_but_does_not_vote():
    """EXTENDED phase: the joiner receives the replication window (it is
    in bitmask_new) but quorum stays on the OLD config — the joiner's ack
    is neither needed nor counted for commit, and the joiner cannot stand
    for election (reference EXTENDED semantics,
    dare_ibv_ud.c:1024-1037)."""
    c = SimCluster(CFG, 8, group_size=3)
    mm = MembershipManager(c)
    c.run_until_elected(0)
    c.step()
    mm.submit_extended(0, 0b111, 3, epoch=1)
    res = c.step()
    cur = mm.current(0)
    assert cur["cid_state"] == int(ConfigState.EXTENDED)
    assert cur["bitmask_new"] == 0b1111

    # the joiner absorbs windows: its end catches up to the leader's
    for _ in range(3):
        res = c.step()
    assert int(res["end"][3]) == int(res["end"][0])

    # quorum unchanged: commit advances with the joiner partitioned away
    c.partition([[0, 1, 2], [3]])
    c.submit(0, b"no-joiner-needed")
    res = c.step()
    assert int(res["commit"][0]) == int(res["end"][0])

    # but still needs 2 of the OLD three: joiner's ack cannot substitute
    c.partition([[0, 3], [1], [2]])
    c.submit(0, b"joiner-cannot-vote")
    res = c.step()
    res = c.step()
    assert int(res["commit"][0]) < int(res["end"][0])

    # joiner firing its election timer while EXTENDED goes nowhere
    c.heal()
    c.step(timeouts=[3])
    assert int(c.last["role"][3]) != int(Role.LEADER)


def test_full_join_ladder_extended_transit_stable():
    """EXTENDED → TRANSIT → STABLE admits the joiner as a full voting
    member at the end (the reference's complete join path,
    dare_server.c:1861-1937)."""
    c = SimCluster(CFG, 8, group_size=3)
    mm = MembershipManager(c)
    c.run_until_elected(0)
    c.submit(0, b"history")
    c.step()
    mm.join(0, 3)
    cur = mm.current(0)
    assert cur["cid_state"] == int(ConfigState.STABLE)
    assert cur["bitmask_new"] == 0b1111
    # the joiner now counts: 3-of-4 majority holds with one old member out
    c.partition([[0, 1, 3], [2]])
    c.submit(0, b"joiner-votes-now")
    res = c.step()
    assert int(res["commit"][0]) == int(res["end"][0])
    # joiner replayed the full history
    c.heal()
    c.step()
    stream3 = [p for (_, _, _, p) in c.replayed[3]]
    assert b"history" in stream3


@pytest.mark.parametrize("kind", ["change", "join"])
def test_config_view_columns_equal_device_state_every_step(kind):
    """The packed readback row carries each replica's config view of
    the post-step state: at every step of a TRANSIT -> STABLE change
    and of a join (EXTENDED -> TRANSIT -> STABLE) its four columns are
    what ``MembershipManager.current(r)`` reads off the device."""
    c = SimCluster(CFG, 8, group_size=3)
    mm = MembershipManager(c)
    c.run_until_elected(0)
    c.submit(0, b"before")
    step, states = c.step, []

    def checked_step(*a, **kw):
        res = step(*a, **kw)
        for r in range(c.R):
            cur = mm.current(r)
            assert {k: int(res[k][r]) for k in cur} == cur, (
                len(states), r)
        states.append((int(res["cid_state"][0]), int(res["epoch"][0])))
        return res

    c.step = checked_step
    if kind == "change":
        mm.change(0, 0b11111)
        ladder = [ConfigState.TRANSIT, ConfigState.STABLE]
    else:
        mm.join(0, 3)
        ladder = [ConfigState.EXTENDED, ConfigState.TRANSIT,
                  ConfigState.STABLE]
    seen = [s for i, s in enumerate(states) if i == 0 or s != states[i - 1]]
    assert seen == [(int(s), e + 1) for e, s in enumerate(ladder)], seen
    assert c.last["bitmask_new"][0] == (0b11111 if kind == "change"
                                        else 0b1111)
