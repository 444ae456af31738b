"""``redis_ycsb_a_c50_r7`` at ``--rehearse-cpu``: the sound run, and the
three runs that show its check can come out false.

* two faults of the SYSTEM, ``interposed_app``'s carried over to
  ``HMSET``: one follower loses every fourth replayed write; one
  follower stores every value with a byte changed;
* one control of the CHECK: every tenth acknowledged read is handed to
  the reference with the record's previous version for a reply (a stale
  read cannot be made underneath a run: the leader's app answers its
  clients outside the driver's reach).

The same three were run on the chip at the cell's own size (PERF.md,
section 6, PR 28).

    JAX_PLATFORMS=cpu python -m pytest perfbench/tests/test_ycsb.py -q
"""

import json

import pytest

from _run import RESULT_KEYS, run_cell

CELL = "redis_ycsb_a_c50_r7"


def checks(out: str) -> list:
    return [json.loads(ln[len("[check] "):]) for ln in out.splitlines()
            if ln.startswith("[check] ")]


def test_sound_run_asks_all_seven_apps():
    rc, last, out = run_cell(CELL, seed=2147483659)     # past 2**31
    assert rc == 0, out[-3000:]
    assert set(last) == RESULT_KEYS | {"rehearsal"}
    assert last["correct"] is True and last["failed"] == 0
    assert set(last["metrics"]) == {"ops_per_s", "latency_p50_ms",
                                    "latency_p95_ms", "setup_s"}
    cs = checks(out)
    assert all(c["ok"] for c in cs)
    assert sum("records held by replica" in c["what"] for c in cs) == 7
    assert sum("does not admit" in c["what"] for c in cs) == 7
    assert sum("do not answer alike" in c["what"] for c in cs) == 1
    reads = next(c for c in cs if "acknowledged reads" in c["what"])
    assert int(reads["what"].split()[1]) > 100      # "of N acknowledged"
    # the load went in before the window
    assert all(c["got"] == 1000 for c in cs if "records held" in c["what"])


def test_traced_run_reports_the_new_metrics():
    rc, last, out = run_cell(CELL, trace=1)
    assert rc == 0, out[-3000:]
    assert last["correct"] is True
    m = last["metrics"]
    assert abs(m["entries_per_op"]["value"] - 1.0) < 0.05
    assert 60 < m["payload_bytes_per_op"]["value"] < 110
    # six followers' apps each answer every replayed operation
    assert 2000 < m["replay_reply_bytes_per_op"]["value"] < 4500
    assert m["replay_us_per_follower"]["value"] > 0
    assert m["replay_applies_per_dispatch"]["value"] > 6


@pytest.mark.parametrize("fault,caught_by", [
    ("follower_drops_applies", "does not admit"),
    ("follower_alters_values", "does not admit"),
    ("stale_read_control", "acknowledged reads"),
])
def test_fault_is_caught(fault, caught_by):
    rc, last, out = run_cell(CELL, fault=fault)
    assert rc == 0, out[-3000:]
    assert last["correct"] is False, out[-3000:]
    bad = [c for c in checks(out) if not c["ok"]]
    assert bad and any(caught_by in c["what"] for c in bad), bad
    if fault == "stale_read_control":
        # a control of the check: the system itself ran sound
        assert all(caught_by in c["what"] for c in bad), bad
