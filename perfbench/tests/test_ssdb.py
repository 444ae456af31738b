"""``ssdb_multiset16_c50_r5`` at ``--rehearse-cpu``: five replicas,
``native/toyssdb``, 16-pair ``multi_set``s of four log entries each, on
a ring that has wrapped before the window opens.

* the sound run, traced and untraced: ``correct`` true, four entries an
  operation, the ring pruned as fast as it fills, and in the traced run a
  number under every metric the cell stands in the list of;
* the three runs that show the check can come out false:
  ``follower_drops_applies``, ``follower_alters_values`` and
  ``follower_drops_fragment``;
* the cell was added from new files only: every file the benchmark had
  at the parent commit is held to its bytes there, every entry of
  ``BENCHMARK.json`` to what it was but for the cell's name at the end
  of ``workloads`` lists.

The mix's ``warmup_ops`` (34,000 requests) are served in the rehearsal
too: about 26 s here at the toy geometry, 66 turns of its 2,048-slot
ring. A run is given 900 s.

    JAX_PLATFORMS=cpu python -m pytest perfbench/tests/test_ssdb.py -q
"""

import json
import os
import subprocess

import pytest

from _run import RESULT_KEYS, ROOT, bench, run_cell

CELL = "ssdb_multiset16_c50_r5"
CONFIG = "apus_ssdb_r5"
PARENT = "70f0f73e1d34d479580193c09e652ade7e488c83"
NEW_METRICS = {"pruned_slots_per_dispatch", "append_clamped_per_dispatch",
               "ring_wraps_in_window", "replay_order_timeouts",
               "replay_applies_per_request"}
Y7_ONLY = {"entries_per_op", "payload_bytes_per_op",
           "replay_us_per_follower", "replay_reply_bytes_per_op"}
NEW_FILES = {
    "perfbench/README_ssdb.md",
    f"perfbench/configs/{CONFIG}.json",
    "perfbench/traffic/multiset16_c50.json",
    "perfbench/generators/line_multiset.py",
    "perfbench/deployments/interposed_app_ssdb.py",
    "perfbench/reference/multiset_dict.py",
    "perfbench/tests/test_ssdb.py",
} | {f"perfbench/metrics/{m}.json" for m in NEW_METRICS}


def checks(out: str) -> dict:
    return {c["name"]: c for c in (
        json.loads(ln[len("[check] "):]) for ln in out.splitlines()
        if ln.startswith("[check] "))}


@pytest.fixture(scope="module")
def sound():
    """One untraced run (a seed past 2**31) and one traced."""
    rc, last, out = run_cell(CELL, seed=2147483659, seconds=4, timeout=900)
    assert rc == 0, out[-3000:]
    rc, traced, tout = run_cell(CELL, seed=5, seconds=30, trace=1,
                                timeout=900)
    assert rc == 0, tout[-3000:]
    return dict(last=last, out=out, traced=traced, tout=tout)


def test_sound_run_is_correct_on_all_five_apps(sound):
    last, out = sound["last"], sound["out"]
    assert set(last) == RESULT_KEYS | {"rehearsal", "compared"}
    assert last["correct"] is True and last["failed"] == 0
    assert set(last["metrics"]) == {"ops_per_s", "latency_p50_ms",
                                    "latency_p95_ms", "setup_s"}
    cs = checks(out)
    assert all(c["ok"] for c in cs.values())
    keys = {cs[f"keys_r{r}"]["got"] for r in range(5)}
    assert len(keys) == 1 and keys.pop() >= 16 * 34000
    assert all(cs[f"part_held_keys_r{r}"]["got"] == 0 for r in range(5))
    assert {f"wrong_values_r{r}" for r in range(5)} <= set(cs)
    assert "DISCARDED" not in out
    assert '"compilations_in_window": 0' in out


def test_traced_run_reads_every_metric_the_cell_is_listed_under(sound):
    traced = sound["traced"]
    assert traced["correct"] is True
    listed = {m["name"] for m in bench()["per_layer"]
              if CELL in m["workloads"]}
    assert NEW_METRICS | Y7_ONLY <= listed
    # the rehearsal has no TPU plane and the CPU backend reports no
    # memory: the device-trace metrics and the peak are read on the chip
    on_chip = {m["name"] for m in bench()["per_layer"]
               if m["source"] == "device_trace"} | {"peak_device_bytes"}
    missing = listed - on_chip - set(traced["metrics"])
    assert not missing, missing
    m = {k: v["value"] for k, v in traced["metrics"].items()}
    assert 4.0 <= m["entries_per_op"] <= 4.05
    # both count what intake admitted over what the window completed,
    # so requests in flight at its ends move them alike
    assert abs(m["payload_bytes_per_op"] / m["entries_per_op"]
               - 1834 / 4) < 0.01
    # the pruner is a sawtooth (one step each time the toy ring of
    # 2,048 slots passes 3/4): what a window appended and what it gave
    # back differ by less than one step, however few its dispatches;
    # nothing is clamped, nothing waited for in vain
    dispatches = 6e3 / m["dispatch_period_ms"]      # core.TRACE_CAP_S
    assert m["pruned_slots_per_dispatch"] > 0
    assert abs(m["pruned_slots_per_dispatch"] - 4 * m["ops_per_dispatch"]
               ) * dispatches <= 0.75 * 2048 + 200
    assert m["append_clamped_per_dispatch"] == 0
    assert m["replay_order_timeouts"] == 0
    assert m["ring_wraps_in_window"] >= 1       # the toy ring: 2,048 slots
    # a request's four entries are neighbours: one write a follower
    assert 1.0 <= m["replay_applies_per_request"] <= 1.5
    assert m["elections_in_window"] == 0
    assert m["cfg_rescans_per_dispatch"] == 0


@pytest.mark.parametrize("fault, shows_in", [
    ("follower_drops_applies", "keys_r"),
    ("follower_alters_values", "wrong_values_r"),
    ("follower_drops_fragment", "keys_r")])
def test_fault_is_caught(fault, shows_in):
    rc, last, out = run_cell(CELL, seed=11, seconds=3, fault=fault,
                             timeout=900)
    assert rc == 0, out[-3000:]
    assert last["correct"] is False, out[-3000:]
    bad = [c for name, c in checks(out).items()
           if name.startswith(shows_in) and not c["ok"]]
    assert len(bad) == 1, bad           # one follower's app, no other
    if shows_in == "keys_r":
        # whole requests are missing, none is held in part
        assert bad[0]["got"] < bad[0]["want"]
        assert (bad[0]["want"] - bad[0]["got"]) % 16 == 0
    else:
        assert bad[0]["got"] == 40      # one byte of one value of each


def parent_file(path: str):
    p = subprocess.run(["git", "show", f"{PARENT}:{path}"], cwd=ROOT,
                       capture_output=True)
    return p.stdout if p.returncode == 0 else None


def test_old_files_are_byte_for_byte_and_entries_only_added():
    listed = subprocess.run(
        ["git", "ls-tree", "-r", "--name-only", PARENT, "perfbench"],
        cwd=ROOT, capture_output=True, text=True, check=True).stdout.split()
    assert len(listed) > 100
    for path in listed:
        with open(os.path.join(ROOT, path), "rb") as f:
            assert f.read() == parent_file(path), path
    here = {os.path.relpath(os.path.join(base, f), ROOT)
            for base, _d, files in os.walk(os.path.join(ROOT, "perfbench"))
            if "__pycache__" not in base for f in files}
    assert here - set(listed) == NEW_FILES
    old, new = json.loads(parent_file("BENCHMARK.json")), bench()
    for key in ("command", "paths", "run_seconds"):
        assert new[key] == old[key]
    for key in ("configs", "workloads", "end_to_end", "per_layer"):
        for was, now in zip(old[key], new[key]):
            if was != now:      # the cell's name, at the end of its list
                assert now == dict(was, workloads=was["workloads"] + [CELL])
    assert [c["name"] for c in new["configs"][len(old["configs"]):]] == [
        CONFIG]
    assert [w["name"] for w in new["workloads"][len(old["workloads"]):]] \
        == [CELL]
    assert [m["name"] for m in new["per_layer"][len(old["per_layer"]):]] \
        == ["pruned_slots_per_dispatch", "append_clamped_per_dispatch",
            "ring_wraps_in_window", "replay_order_timeouts",
            "replay_applies_per_request"]
    thr = {m["name"] for m in old["end_to_end"] + old["per_layer"]
           if {"redis_set_c50", "redis_ycsb_a_c50_r7"}
           <= set(m.get("workloads", ()))}
    mine = {m["name"] for m in new["end_to_end"] + new["per_layer"]
            if CELL in m.get("workloads", ())}
    assert mine == thr | Y7_ONLY | NEW_METRICS
