"""``redis_set_c50_replace_follower`` at ``--rehearse-cpu``: one
follower lost, evicted and replaced inside the window, under
``set_c50``'s load.

* the sound run, traced and untraced: ``correct`` true, one
  replacement, STABLE on three at the check, no election, and in the
  traced run a number under every metric the cell stands in the list
  of;
* the schedule fires once and at the stated shares of the window, for
  a 30 s window as for a traced run's 6 s;
* the three runs that show the check can come out false:
  ``rejoin_without_restore`` (the joiner's app is fed nothing of the
  history its snapshot brought), ``rejoin_skips_suffix`` (nothing of
  what committed between the snapshot and the hand-over to live
  replay), and ``interposed_app``'s ``follower_drops_applies``;
* the cell was added from new files only: every file the benchmark had
  at the parent commit is held to its bytes there, every entry of
  ``BENCHMARK.json`` to what it was but for the cell's name at the end
  of ``workloads`` lists.

    JAX_PLATFORMS=cpu python -m pytest perfbench/tests/test_replace.py -q
"""

import json
import os
import re
import subprocess

import pytest

from _run import RESULT_KEYS, ROOT, bench, run_cell

CELL = "redis_set_c50_replace_follower"
CONFIG = "apus_redis_r3_replace_follower"
PARENT = "efc3b65f1f75588e2c17868ffc7f5a5e3a48cfad"
NEW_METRICS = {"kill_service_gap_ms", "rejoin_service_gap_ms",
               "evict_detect_ms", "config_change_ms", "recover_ms",
               "app_rebuild_ms", "recover_bytes", "catch_up_ms",
               "degraded_ops_per_s"}
NEW_FILES = {
    "perfbench/README_replace.md",
    f"perfbench/configs/{CONFIG}.json",
    "perfbench/deployments/interposed_app_replace.py",
    "perfbench/readers/event_timeline.py",
    "perfbench/reference/replace_register.py",
    "perfbench/tests/test_replace.py",
} | {f"perfbench/metrics/{m}.json" for m in NEW_METRICS}
EVENTS = ("kill", "evicted", "evicted_stable", "add_server", "recovered",
          "stable_on_everybody", "caught_up", "app_rebuilt")


def checks(out: str) -> dict:
    return {c["name"]: c for c in (
        json.loads(ln[len("[check] "):]) for ln in out.splitlines()
        if ln.startswith("[check] "))}


def stamps(out: str) -> dict:
    """event -> seconds into the window, off the ``[replace]`` lines."""
    return {m.group(1): float(m.group(2)) for m in re.finditer(
        r"^\[replace\] (\w+) at ([0-9.]+)s of the window$", out, re.M)}


@pytest.fixture(scope="module")
def sound():
    """One untraced run of 12 s (a seed past 2**31) and one traced."""
    rc, last, out = run_cell(CELL, seed=2147483659, seconds=12)
    assert rc == 0, out[-3000:]
    rc, traced, tout = run_cell(CELL, seed=5, seconds=30, trace=1)
    assert rc == 0, tout[-3000:]
    return dict(last=last, out=out, traced=traced, tout=tout)


def test_sound_run_replaces_one_follower(sound):
    last, out = sound["last"], sound["out"]
    assert set(last) == RESULT_KEYS | {"rehearsal", "compared"}
    assert last["correct"] is True and last["failed"] == 0
    assert set(last["metrics"]) == {"ops_per_s", "latency_p50_ms",
                                    "latency_p95_ms", "setup_s"}
    cs = checks(out)
    assert all(c["ok"] for c in cs.values())
    got = {name: c["got"] for name, c in cs.items()}
    assert got["replacements_done"] == 1 and got["members_at_close"] == 3
    assert got["rejoined_with_an_old_app"] == 0
    assert got["elections_in_window"] == 0 and got["failed"] == 0
    # all three apps, the replaced one included, hold every key
    assert got["keys_r0"] == got["keys_r1"] == got["keys_r2"] > 1000
    assert {f"wrong_values_r{r}" for r in range(3)} <= set(cs)
    assert "DISCARDED" not in out
    assert '"compilations_in_window": 0' in out


@pytest.mark.parametrize("which, window", [("out", 12.0), ("tout", 6.0)],
                         ids=["untraced_12s", "traced_6s_of_30"])
def test_schedule_fires_once_at_its_shares(sound, which, window):
    at = stamps(sound[which])
    assert list(at) == list(EVENTS), at      # each once, in this order
    assert window / 6 <= at["kill"] < window / 6 + 0.1
    assert window * 2 / 3 <= at["add_server"] < window * 2 / 3 + 0.1
    assert at["evicted_stable"] < at["add_server"]
    with open(os.path.join(ROOT, "perfbench", "configs",
                           CONFIG + ".json")) as f:
        plan = json.load(f)["schedule"]
    # 30 s: 5 s and 20 s; a traced run's 6 s: 1 s and 4 s
    assert (30 * plan["kill_at"], 30 * plan["add_server_at"]) == (5, 20)
    assert (6 * plan["kill_at"], 6 * plan["add_server_at"]) == (1, 4)


def test_traced_run_reads_every_metric_the_cell_is_listed_under(sound):
    traced = sound["traced"]
    assert traced["correct"] is True
    listed = {m["name"] for m in bench()["per_layer"]
              if CELL in m["workloads"]}
    assert NEW_METRICS <= listed
    # the rehearsal has no TPU plane and the CPU backend reports no
    # memory: the device-trace metrics and the peak are read on the chip
    on_chip = {m["name"] for m in bench()["per_layer"]
               if m["source"] == "device_trace"} | {"peak_device_bytes"}
    missing = listed - on_chip - set(traced["metrics"])
    assert not missing, missing
    m = {k: v["value"] for k, v in traced["metrics"].items()}
    assert m["recover_bytes"] > 0 and m["degraded_ops_per_s"] > 0
    assert 0 < m["config_change_ms"] < 5000
    assert m["elections_in_window"] == 0
    assert m["readback_arrays_per_dispatch"] == 1.0


@pytest.mark.parametrize("fault, shows_in", [
    ("rejoin_without_restore", "keys_r2"),
    ("rejoin_skips_suffix", "keys_r2"),
    ("follower_drops_applies", "keys_r1")])
def test_fault_is_caught(fault, shows_in):
    rc, last, out = run_cell(CELL, seed=11, seconds=9, fault=fault)
    assert rc == 0, out[-3000:]
    assert last["correct"] is False, out[-3000:]
    cs = checks(out)
    assert not cs[shows_in]["ok"]
    assert cs[shows_in]["got"] < cs[shows_in]["want"]
    # the replacement itself went through: the fault is in what the
    # app was fed, and only the check can tell
    assert cs["replacements_done"]["ok"] and cs["members_at_close"]["ok"]


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
    assert {m["name"] for m in new["per_layer"][len(old["per_layer"]):]} \
        == NEW_METRICS
    c50 = {m["name"] for m in old["end_to_end"] + old["per_layer"]
           if "redis_set_c50" in m.get("workloads", ())}
    mine = {m["name"] for m in new["end_to_end"] + new["per_layer"]
            if CELL in m.get("workloads", ())}
    assert mine == c50 | NEW_METRICS
