"""``correct`` is shown able to come out false.

Each run skips the harness's look for a chip (``--rehearse-cpu``) and
drives the rest of a run with the deployment broken underneath:

* the control breaks a guarantee the configuration states (every
  acknowledged write is read back from EVERY replica): one follower
  loses a quarter of the writes;
* the other alters an answer where it is produced: one follower stores
  every value with a byte changed, while all the counts still agree.

The same faults were run on the chip at the cells' own size, on three
seeds each (PERF.md, section 2).
"""

import pytest

from _run import run_cell

CASES = [
    ("redis_set_c50", "follower_drops_applies"),
    ("redis_set_c50", "follower_alters_values"),
    ("redis_set_c1", "follower_alters_values"),
    ("redis_set_c50_x4", "follower_drops_applies"),
]


@pytest.mark.parametrize("cell,fault", CASES)
def test_fault_is_caught(cell, fault):
    rc, last, out = run_cell(cell, fault=fault)
    assert rc == 0, out[-3000:]
    assert last["correct"] is False, out[-3000:]
    assert '"ok": false' in out


def test_sound_run_passes():
    rc, last, out = run_cell("redis_set_c50")
    assert rc == 0 and last["correct"] is True, out[-3000:]


@pytest.mark.parametrize("cell", ["redis_set_c50", "redis_set_c50_x4"])
def test_election_under_warm_up_is_rebuilt(cell):
    """The refusal of PR 25's first check: the group elected again
    between boot and the generator's start, the generator served itself
    from a deposed leader's app and the check (rightly) said false. The
    harness now aims load at a leader that has stood, and builds
    everything anew when leadership moves under the warm-up."""
    rc, last, out = run_cell(cell, fault="election_under_warm_up")
    assert rc == 0, out[-3000:]
    assert "bring-up 1 of" in out and "building the deployment again" in out
    assert last["correct"] is True and last["failed"] == 0, out[-3000:]
