"""The command end to end, at ``--rehearse-cpu``, for every cell.

Run by hand (not part of tier-1; each run boots a driver):

    JAX_PLATFORMS=cpu python -m pytest perfbench/tests -q
"""

import pytest

from _run import RESULT_KEYS, bench, run_cell

CELLS = [w["name"] for w in bench()["workloads"]]


def names(entries, cell, e2e=()):
    out = set()
    for m in entries:
        if "workloads" in m:
            if cell in m["workloads"]:
                out.add(m["name"])
        elif "moves" not in m or m["moves"] in e2e:
            out.add(m["name"])
    return out


@pytest.mark.parametrize("cell", CELLS)
def test_end_to_end_line(cell):
    rc, last, out = run_cell(cell, trace=0)
    assert rc == 0, out[-3000:]
    assert set(last) == RESULT_KEYS | {"rehearsal"}, last
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] > 0
    assert set(last["metrics"]) == names(bench()["end_to_end"], cell)
    assert last["device"]["platform"] == "cpu"      # never a result
    assert all(v["value"] > 0 for v in last["metrics"].values())
    # every run says on an earlier line whether an election fell inside
    assert "elections_in_window" in out and "[setup]" in out


@pytest.mark.parametrize("cell", CELLS)
def test_traced_line(cell):
    rc, last, out = run_cell(cell, trace=1)
    assert rc == 0, out[-3000:]
    assert set(last) == RESULT_KEYS | {"rehearsal", "breakdown"}, last
    assert last["correct"] is True
    b = bench()
    due = names(b["per_layer"], cell, names(b["end_to_end"], cell))
    # the CPU backend reports no memory statistics, XLA:CPU names no
    # collectives and peaks.json has no CPU: those readers find nothing
    # to read and their metrics are left out
    assert due - set(last["metrics"]) <= {
        "peak_device_bytes", "collective_us_per_step", "step_roofline"}
    assert "elections_in_window" in last["metrics"]
    assert set(last["metrics"]) <= due
    assert last["device"]["busy_s"] > 0
    assert 0 < last["device"]["window_s"]
    assert set(last["breakdown"]) == {"device_ops", "idle_gaps"}
    assert 0 < len(last["breakdown"]["device_ops"]) <= 10


def test_no_chip_no_result():
    """Off the TPU and not rehearsing: non-zero, names the platform,
    prints no result line."""
    rc, last, out = run_cell(CELLS[0], rehearse=False)
    assert rc != 0 and last is None
    assert "needs a TPU" in out and "'cpu'" in out


def test_unknown_workload():
    rc, last, out = run_cell("no_such_cell")
    assert rc != 0 and last is None
