"""The plain reference of the YCSB cell (``perfbench/reference/
ycsb_register.py``) on histories written by hand: what it must accept
(a linearizable history with concurrent writers to one key) and what it
must reject (a stale read, a lost acknowledged update, a value nobody
wrote)."""

import pytest

from perfbench.reference.ycsb_register import (
    ACKED, INF, UNRESOLVED, Read, Registers, Write, parse_record)

K, F, G = b"user1", b"field0", b"field1"


def w(value, t_req, t_rep, field=F, state=ACKED):
    return Write(K, field, value, t_req, t_rep, state)


# the load, then two writers whose updates overlap, then a later one
HISTORY = [w(b"load", 0, 1), w(b"load-g", 0, 1, field=G),
           w(b"a", 2, 5), w(b"b", 3, 6), w(b"c", 8, 9)]


def test_final_value_is_the_write_nothing_follows():
    regs = Registers(HISTORY)
    assert regs.admissible(K, F) == {b"c"}
    assert regs.admissible(K, G) == {b"load-g"}
    assert regs.record_faults(K, {F: b"c", G: b"load-g"}) == []
    assert regs.ambiguous_keys() == 0


def test_concurrent_last_writers_are_both_admissible():
    regs = Registers(HISTORY[:4])
    assert regs.admissible(K, F) == {b"a", b"b"}
    assert regs.ambiguous_keys() == 1
    for final in (b"a", b"b"):
        assert regs.record_faults(K, {F: final, G: b"load-g"}) == []


@pytest.mark.parametrize("value,t_req,t_rep,ok", [
    (b"load", 1.5, 2.5, True),      # before either update could finish
    (b"load", 4.0, 4.5, True),      # both updates still in flight
    (b"a", 4.0, 4.5, True),         # a may already have taken effect
    (b"b", 5.5, 7.0, True),         # a finished, b overlaps a: either order
    (b"a", 6.5, 7.0, True),         # a and b concurrent: a may be the later
    (b"load", 5.5, 7.0, False),     # STALE: a finished before the read began
    (b"a", 9.5, 10.0, False),       # STALE: c strictly follows a
    (b"b", 9.5, 10.0, False),
    (b"c", 7.0, 7.5, False),        # from the future: c began after the reply
    (b"c", 8.5, 10.0, True),
    (b"zzz", 4.0, 4.5, False),      # a value nobody wrote
    (None, 4.0, 4.5, False),        # absent after the load was acknowledged
    (None, 0.5, 0.8, True),         # absent while the load is in flight
])
def test_read_against_concurrent_writers(value, t_req, t_rep, ok):
    regs = Registers(HISTORY)
    assert regs.read_admissible(K, F, value, t_req, t_rep) is ok
    got = {G: b"load-g"}
    if value is not None:
        got[F] = value
    faults = regs.read_faults(Read(K, got, t_req, t_rep))
    assert (faults == []) is ok, faults


def test_lost_acknowledged_update_is_rejected():
    """The app ends on the value before an acknowledged update."""
    regs = Registers(HISTORY)
    faults = regs.record_faults(K, {F: b"b", G: b"load-g"})
    assert faults == ["user1.field0=b"]
    assert regs.record_faults(K, None) != []        # the record gone
    assert regs.record_faults(K, {F: b"c"}) == ["user1.field1=absent"]


def test_value_nobody_wrote_is_rejected():
    regs = Registers(HISTORY)
    assert regs.record_faults(K, {F: b"d", G: b"load-g"}) != []
    assert regs.record_faults(K, {F: b"c", G: b"load-g",
                                  b"field9": b"x"}) != []


def test_unresolved_write_may_or_may_not_have_happened():
    regs = Registers(HISTORY + [w(b"u", 4, INF, state=UNRESOLVED)])
    assert regs.admissible(K, F) == {b"c", b"u"}
    assert regs.read_admissible(K, F, b"u", 9.5, 10.0)
    assert not regs.read_admissible(K, F, b"u", 3.0, 3.5)   # not yet sent
    # but it hides no acknowledged write from a later reader
    assert not regs.read_admissible(K, F, b"a", 9.5, 10.0)


def test_a_value_written_twice_is_refused():
    with pytest.raises(ValueError):
        Registers([w(b"same", 0, 1), w(b"same", 2, 3)])


def test_previous_version_is_a_stale_read():
    """The control of the check: a read handed over with the record one
    acknowledged write earlier is always caught."""
    regs = Registers(HISTORY)
    fresh = Read(K, {F: b"c", G: b"load-g"}, 9.5, 10.0)
    assert regs.read_faults(fresh) == []
    stale = Read(K, regs.previous_version(fresh), 9.5, 10.0)
    assert stale.fields[F] in (b"a", b"b") and regs.read_faults(stale)
    # undoing a record's first write leaves it absent
    early = Read(K, {F: b"load", G: b"load-g"}, 1.5, 1.8)
    assert regs.previous_version(early) is None
    assert regs.read_faults(Read(K, None, 1.5, 1.8))
    # nothing finished before the read: nothing to undo
    first = Read(K, None, 0.2, 0.4)
    assert regs.previous_version(first) is None
    assert regs.read_faults(first) == []


def test_parse_record():
    assert parse_record(b"-") is None
    assert parse_record(b"field0 a field1 b") == {F: b"a", G: b"b"}
    with pytest.raises(ValueError):
        parse_record(b"field0 a field1")
