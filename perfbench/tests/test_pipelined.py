"""``redis_set_p16_c50`` at ``--rehearse-cpu``: three replicas,
``native/toyserver -s 21``, fifty connections that each pipeline
sixteen SETs over a million keys, at the toy geometry (1,024 slots of
512 B: a batch of sixteen stays ONE entry).

* the sound run, traced and untraced: ``correct`` true, sixteen
  operations an entry and a shim event, sixteen answers a replayed
  write, no handoff unproven, and in the traced run a number under
  every metric the cell stands in the list of;
* the three runs that show the check can come out false:
  ``follower_drops_applies``, ``follower_alters_values`` and
  ``follower_swaps_same_key``;
* the cell was added from new files only: every file the benchmark had
  at the parent commit is held to its bytes there, every entry of
  ``BENCHMARK.json`` to what it was but for the cell's name at the end
  of ``workloads`` lists.

The rehearsal serves 50,000-60,000 operations a second on this sandbox's
CPU (not the chip's number), so a window of a few seconds holds a
thousand keys that two connections wrote within 100 ms of each other.

    JAX_PLATFORMS=cpu python -m pytest perfbench/tests/test_pipelined.py -q
"""

import json
import os
import subprocess

import pytest

from _run import RESULT_KEYS, ROOT, bench, run_cell

CELL = "redis_set_p16_c50"
CONFIG = "apus_redis_r3_p16"
PARENT = "659fee627cf51c0fe13982b02b3bc595222316bb"
NEW_METRICS = ["replay_answers_per_write", "replay_unproven_handoffs"]
# the lists c50 is not in and this cell is
BESIDE_C50 = {"entries_per_op", "payload_bytes_per_op",
              "replay_reply_bytes_per_op", "replay_order_timeouts",
              "replay_applies_per_request"}
NEW_FILES = {
    "perfbench/README_pipelined.md",
    f"perfbench/configs/{CONFIG}.json",
    "perfbench/traffic/set_p16_c50.json",
    "perfbench/generators/resp_pipelined.py",
    "perfbench/deployments/interposed_app_pipelined.py",
    "perfbench/reference/set_register.py",
    "perfbench/tests/test_pipelined.py",
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


def test_sound_run_is_correct_on_all_three_apps(sound):
    last, out = sound["last"], sound["out"]
    assert set(last) == RESULT_KEYS | {"rehearsal", "compared"}
    assert last["correct"] is True and last["failed"] == 0
    assert set(last["metrics"]) == {"ops_per_s", "latency_p50_ms",
                                    "setup_s"}
    cs = checks(out)
    assert all(c["ok"] for c in cs.values())
    keys = {cs[f"keys_r{r}"]["got"] for r in range(3)}
    assert len(keys) == 1 and keys.pop() > 10000
    assert cs["key_counts_differ"]["got"] == 0
    assert cs["values_apps_differ_on"]["got"] == 0
    assert all(cs[f"inadmissible_values_r{r}"]["got"] == 0
               for r in range(3))
    # connections DID write the same keys at once, and those were asked
    assert cs["contended_keys"]["got"] > 50
    assert f"({cs['contended_keys']['got']} written by two connections" \
        in cs["inadmissible_values_r0"]["what"]
    assert cs["replay_order_timeouts"]["got"] == 0
    assert "DISCARDED" not in out
    assert '"compilations_in_window": 0' in out
    # whole batches: what was attempted in the window is a multiple of 16
    assert last["attempted"] % 16 == 0


def test_traced_run_reads_every_metric_the_cell_is_listed_under(sound):
    traced = sound["traced"]
    assert traced["correct"] is True
    listed = {m["name"] for m in bench()["per_layer"]
              if CELL in m["workloads"]}
    assert set(NEW_METRICS) | BESIDE_C50 <= listed
    # the rehearsal has no TPU plane and the CPU backend reports no
    # memory: the device-trace metrics and the peak are read on the chip
    on_chip = {m["name"] for m in bench()["per_layer"]
               if m["source"] == "device_trace"} | {"peak_device_bytes"}
    missing = listed - on_chip - set(traced["metrics"])
    assert not missing, missing
    m = {k: v["value"] for k, v in traced["metrics"].items()}
    # an entry, a shim event and a replayed write are sixteen operations
    # (requests in flight at the window's ends move them a little)
    assert 0.97 / 16 <= m["entries_per_op"] <= 1.03 / 16
    assert 0.97 / 16 <= m["shim_events_per_op"] <= 1.03 / 16
    assert abs(m["payload_bytes_per_op"] / m["entries_per_op"] - 400) < 0.01
    assert 700 <= m["ops_per_dispatch"] <= 808
    # two followers, fifty writes each, a dispatch; every write a whole
    # batch; every answer waited for and matched, no handoff unproven
    assert 90 <= m["replay_applies_per_dispatch"] <= 101
    assert m["replay_applies_per_request"] == 1.0
    assert 15.9 <= m["replay_answers_per_write"] <= 16.1
    assert m["replay_unproven_handoffs"] == 0
    assert m["replay_order_timeouts"] == 0
    # "+OK\n" a SET from each of two followers
    assert 7.9 <= m["replay_reply_bytes_per_op"] <= 8.1
    assert m["elections_in_window"] == 0
    assert m["generator_busy_share"] < 50


@pytest.mark.parametrize("fault, shows_in", [
    ("follower_drops_applies", "keys_r"),
    ("follower_alters_values", "inadmissible_values_r"),
    ("follower_swaps_same_key", "inadmissible_values_r")])
def test_fault_is_caught(fault, shows_in):
    rc, last, out = run_cell(CELL, seed=11, seconds=3, fault=fault,
                             timeout=900)
    assert rc == 0, out[-3000:]
    assert last["correct"] is False, out[-3000:]
    cs = checks(out)
    bad = [c for name, c in cs.items()
           if name.startswith(shows_in) and not c["ok"]]
    assert len(bad) == 1, bad           # one follower's app, no other
    assert cs["values_apps_differ_on"]["got"] > 0
    if fault == "follower_drops_applies":
        assert bad[0]["got"] < bad[0]["want"]
        assert cs["key_counts_differ"]["got"] == 1
    elif fault == "follower_alters_values":
        # every key asked, and the counts agree
        assert str(bad[0]["got"]) == bad[0]["what"].split()[1]
        assert cs["key_counts_differ"]["got"] == 0
    else:
        # only keys two connections wrote at once; the counts agree,
        # nothing else is off
        assert 0 < bad[0]["got"] <= cs["contended_keys"]["got"]
        assert cs["key_counts_differ"]["got"] == 0
        assert cs["apps_without_marker"]["got"] == []


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
    assert new["configs"][-1]["reduced"] == []
    assert [w["name"] for w in new["workloads"][len(old["workloads"]):]] \
        == [CELL]
    assert [m["name"] for m in new["per_layer"][len(old["per_layer"]):]] \
        == NEW_METRICS
    c50 = {m["name"] for m in old["end_to_end"] + old["per_layer"]
           if "redis_set_c50" in m.get("workloads", ())}
    mine = {m["name"] for m in new["end_to_end"] + new["per_layer"]
            if CELL in m.get("workloads", ())}
    # latency_p95_ms is c50's and not (yet) this cell's: PERF.md sec. 2
    assert mine | {"latency_p95_ms"} == c50 | BESIDE_C50 | set(NEW_METRICS)
    cfg = json.load(open(os.path.join(ROOT, new["configs"][-1]["file"])))
    r3 = json.load(open(os.path.join(
        ROOT, "perfbench/configs/apus_redis_r3.json")))
    for key in ("replicas", "chips", "mapping", "geometry",
                "driver_options", "timers", "prewarm_burst_tiers"):
        assert cfg[key] == r3[key], key
    assert cfg["source"] == new["configs"][-1]["source"]
