"""The plain reference of the pipelined SET cell
(``perfbench/reference/set_register.py``) on hand-made histories: which
values a key may end on when several connections write it, the bounds on
an app's key count, and that each of the cell's three controls (a
follower that drops applies, alters values, or applies two connections'
SETs of one key in the other order) reads as a fault."""

import pytest

from perfbench.reference.set_register import (ACKED, UNRESOLVED,
                                              SetRegister, apps_differ)


def w(key, value, t_req, t_rep, state=ACKED):
    return (key, value, t_req, t_rep, state)


def test_the_last_of_writes_that_follow_each_other_is_the_only_value():
    r = SetRegister([w(1, b"aaa", 0.0, 1.0), w(1, b"bbb", 2.0, 3.0),
                     w(1, b"ccc", 4.0, 5.0)])
    assert r.admissible(1) == {b"ccc"}
    assert r.faults([1], [b"ccc"]) == []
    assert len(r.faults([1], [b"bbb"])) == 1
    assert len(r.faults([1], [None])) == 1      # acknowledged: not absent


def test_two_connections_writing_at_once_are_both_admissible():
    """Neither strictly follows the other: the log's order decides, and
    the reference admits both; a third that follows both ends it."""
    r = SetRegister([w(7, b"one", 0.0, 2.0), w(7, b"two", 1.0, 3.0)])
    assert r.admissible(7) == {b"one", b"two"}
    assert r.ambiguous([7]) == 1
    r = SetRegister([w(7, b"one", 0.0, 2.0), w(7, b"two", 1.0, 3.0),
                     w(7, b"end", 3.5, 4.0)])
    assert r.admissible(7) == {b"end"}
    # a write that BEGAN before the last acknowledged one ended stays
    r = SetRegister([w(7, b"one", 0.0, 5.0), w(7, b"two", 1.0, 2.0)])
    assert r.admissible(7) == {b"one", b"two"}


def test_a_batch_shares_its_request_stamp():
    """Sixteen SETs of a batch are written at once and answered one by
    one: two of them on ONE key in one batch are concurrent by their
    stamps (the later line wins in the app, the reference admits both)."""
    r = SetRegister([w(3, b"x01", 10.0, 10.020), w(3, b"x02", 10.0, 10.021)])
    assert r.admissible(3) == {b"x01", b"x02"}


def test_unresolved_writes_may_or_may_not_have_happened():
    r = SetRegister([w(5, b"old", 0.0, 1.0),
                     w(5, b"new", 2.0, 0.0, UNRESOLVED),
                     w(6, b"may", 2.0, 0.0, UNRESOLVED)])
    assert r.admissible(5) == {b"old", b"new"}
    assert r.admissible(6) == {b"may", None}
    assert r.admissible(99) == {None}           # never written
    # key 5 is held for sure, key 6 perhaps
    assert r.count_bounds() == (1, 2)


def test_equal_values_from_two_writes_are_one_admissible_value():
    r = SetRegister([w(2, b"abc", 0.0, 1.0), w(2, b"abc", 5.0, 6.0)])
    assert r.admissible(2) == {b"abc"}
    assert r.faults([2], [b"abc"]) == []


def test_only_keeps_the_histories_asked_for_and_counts_every_key():
    writes = [w(k, b"v%02d" % k, float(k), k + 0.5) for k in range(50)]
    r = SetRegister(iter(writes), only={3, 4})
    assert r.count_bounds() == (50, 50)
    assert r.admissible(3) == {b"v03"}
    assert r.admissible(10) == {None}           # not kept: do not ask


HISTORY = [
    # connection 1 then connection 2 write key 1, well apart
    w(1, b"c1a", 0.0, 0.02), w(1, b"c2a", 1.0, 1.02),
    # both write key 2 at once; the leader's log put c2's last
    w(2, b"c1b", 2.0, 2.03), w(2, b"c2b", 2.01, 2.03),
    w(3, b"c1c", 3.0, 3.02),
]
LEADER = {1: b"c2a", 2: b"c2b", 3: b"c1c"}


@pytest.mark.parametrize("control, follower, inadmissible, differ", [
    ("sound", dict(LEADER), 0, 0),
    # every fourth apply dropped: key 3 never arrived, key 1 is stale
    ("follower_drops_applies", {1: b"c1a", 2: b"c2b", 3: None}, 2, 2),
    # a byte of every value changed
    ("follower_alters_values", {1: b"b2a", 2: b"b2b", 3: b"b1c"}, 3, 3),
    # two connections' SETs of one key in the other order: where they
    # were concurrent the value is admissible and the APPS differ, where
    # one followed the other it is inadmissible too
    ("follower_swaps_same_key", {1: b"c1a", 2: b"c1b", 3: b"c1c"}, 1, 2),
])
def test_each_control_reads_as_a_fault(control, follower, inadmissible,
                                       differ):
    r = SetRegister(HISTORY)
    keys = sorted(LEADER)
    lead = [LEADER[k] for k in keys]
    foll = [follower[k] for k in keys]
    assert r.faults(keys, lead) == []
    assert len(r.faults(keys, foll)) == inadmissible
    assert apps_differ([lead, foll, lead]) == differ
